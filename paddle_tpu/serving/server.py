"""The serving front door: a long-lived asyncio HTTP process over
``ContinuousBatchingEngine`` with observability as its first-class
citizen (ISSUE 6 tentpole; ROADMAP "Serving front door").

Architecture — one engine thread, one event loop, a thread-safe seam:

- The **engine thread** owns the ``ContinuousBatchingEngine`` exclusively
  (the engine is deliberately not thread-safe — its state is device
  arrays chained between dispatches).  It pulls submissions from a
  thread-safe inbox, admits them through the engine's existing admission
  path, runs the fused engine step in a loop, and after each step diffs
  every live request's ``output`` and posts fresh tokens into the owning
  HTTP connection's asyncio queue via ``loop.call_soon_threadsafe``.
  Every ``engine.step()`` gathers the steps that have landed, so a
  stream gets a chunk a step, one step after the device sampled the
  token: streaming granularity is the device step, and the engine has no
  drain cadence (``generation.MAX_STEPS_IN_FLIGHT``).
- The **event loop** parses HTTP, makes the SLO admission decision
  (``slo.SLOController`` — histogram burn, not queue length), enqueues,
  and streams Server-Sent Events as token batches arrive.

Endpoints:

- ``POST /v1/completions`` — OpenAI-compatible completion over token ids
  (``prompt``: list of ints; no tokenizer in-tree, so ``text`` fields
  carry space-joined ids and ``token_ids`` the raw list).  ``stream``
  true sends an SSE chunk a step; the response/chunk ``id`` is the
  request's trace-context id, the SAME id on its engine lifecycle spans.
- ``GET /metrics`` — live Prometheus exposition of the whole registry.
- ``GET /healthz`` — liveness (engine thread up; the pre-ISSUE-7 shape).
- ``GET /readyz`` — readiness: 503 until the engine's bucket warmup
  compile has completed (``warmup=True``), so a router never places
  live traffic on a replica that would compile under it.
- ``GET /statusz`` — engine/pool/prefix-cache gauges, jit cache stats,
  SLO burn state, the prefix-residency digest (router placement),
  flight-recorder state, build/flag info.

Observability wiring: every request carries a trace id from accept
through retire (one Chrome-trace track), the flight recorder's span ring
is attached for the server's lifetime with periodic registry snapshots
folded in from the engine loop, the watchdog watches every engine step
(a hung device dispatch fires the timeout hook → flight-recorder dump),
and SIGTERM dumps before shutdown.
"""

from __future__ import annotations

import asyncio
import json
import os
import queue
import signal
import threading
import time
from typing import List, Optional

from .. import flags
from .. import observability as _obs
from ..observability.flight_recorder import FlightRecorder
from . import http as _http
from .slo import SHED, SLOController, jittered_retry_after

__all__ = ["ServingServer", "serve_forever"]

_TRACE_ID_OK = _http.SAFE_ID_OK

# process-wide server ordinal: the per-replica track tag in merged fleet
# timelines (ISSUE 20)
_SERVER_SEQ = 0


class _HttpMetrics:
    """Registry handles for the HTTP layer, resolved once (the PR 5
    serving-engine idiom)."""

    __slots__ = ("requests", "streams", "responses", "inflight",
                 "request_ms", "queue_expired")

    def __init__(self):
        m = _obs.metrics
        self.requests = m.counter("serving.http.requests")
        self.streams = m.counter("serving.http.streams")
        # one labeled series per status code: bounded, guard-safe
        self.responses = lambda code: m.counter("serving.http.responses",
                                                code=str(code))
        self.inflight = m.gauge("serving.http.inflight")
        self.request_ms = m.histogram("serving.http.request_ms")
        # queue-expiry shedding (ISSUE 15): requests retired from the
        # inbox with 504 before dispatch — prefill never spent on a
        # client that already gave up
        self.queue_expired = m.counter("serving.http.queue_expired")


class _Stream:
    """Bridge between one HTTP connection (event loop side) and its
    engine request (engine thread side)."""

    __slots__ = ("trace_id", "prompt", "max_new_tokens", "q", "loop",
                 "req", "sent", "cancelled", "t_accept")

    def __init__(self, trace_id, prompt, max_new_tokens, loop):
        self.trace_id = trace_id
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.q: asyncio.Queue = asyncio.Queue()
        self.loop = loop
        self.req = None               # engine Request, set on engine thread
        self.sent = 0                 # tokens already pushed to the client
        self.cancelled = False
        self.t_accept = time.perf_counter()

    def post(self, item) -> None:
        """Engine thread -> event loop handoff."""
        if self.cancelled:
            return
        try:
            self.loop.call_soon_threadsafe(self.q.put_nowait, item)
        except RuntimeError:
            # the handler's event loop is closed (embedder tore it down
            # mid-request): stop posting — this must never look like an
            # engine crash to the engine loop
            self.cancelled = True


class ServingServer:
    """Long-lived serving process over one ``ContinuousBatchingEngine``.

    The engine must be constructed by the caller (model/pool sizing is
    workload policy); the server owns its lifecycle from ``start()`` to
    ``close()``.  ``slo=None`` builds a flag-configured
    ``SLOController``; ``slo=False`` disables shedding.
    ``flight_recorder=None`` builds one and attaches its ring (watchdog /
    SIGTERM / excepthook triggers are wired by ``install_crash_hooks`` or
    ``serve_forever``, not implicitly — signal handlers belong to the
    process owner); ``flight_recorder=False`` runs without.
    """

    def __init__(self, engine, *, model_name: str = "paddle-tpu",
                 slo=None, flight_recorder=None, watchdog=None,
                 sentinel=None, poll_s: float = 0.02,
                 warmup: bool = False, role: Optional[str] = None):
        self.engine = engine
        self.model_name = model_name
        # disaggregated serving (ISSUE 16): the role this replica
        # advertises via /statusz — a routing preference the router's
        # phase placement reads, never an engine capability (a decode
        # replica still prefills what it is asked to)
        self.role = str(flags.flag("serving_role") if role is None
                        else role)
        if self.role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"serving role must be mixed/prefill/decode, "
                f"got {self.role!r}")
        # readiness (ISSUE 7): with warmup=True the engine thread compiles
        # the step-program pair on junk traffic before /readyz reports
        # ready, so a router never places live traffic on a cold replica
        self._warmup = warmup
        self._ready = threading.Event()
        self.slo: Optional[SLOController] = \
            SLOController() if slo is None else (slo or None)
        self.flight_recorder: Optional[FlightRecorder] = \
            FlightRecorder() if flight_recorder is None \
            else (flight_recorder or None)
        # regression sentinel (ISSUE 10): EWMA+MAD drift detection over
        # the live registry, swept from the engine loop.  ``None`` builds
        # one per FLAGS_serving_sentinel (metrics on only — with the
        # registry dark there is nothing to watch); ``False`` disables.
        if sentinel is None and flags.flag("serving_sentinel") \
                and _obs.metrics_enabled():
            sentinel = _obs.Sentinel(flight_recorder=self.flight_recorder)
        self.sentinel: Optional[_obs.Sentinel] = sentinel or None
        self._watchdog = watchdog     # CommTaskManager or None
        self._poll_s = poll_s
        # queue-expiry shedding (ISSUE 15): a request still waiting in
        # the engine inbox past this is retired 504 pre-dispatch
        self._queue_timeout_s = float(flags.flag("serving_queue_timeout_s"))
        self._inbox: "queue.SimpleQueue[_Stream]" = queue.SimpleQueue()
        # engine control ops (ISSUE 14): arbitrary fn(engine) calls
        # marshalled onto the engine thread between steps — the seam the
        # session-migration endpoints and the fleet supervisor use to
        # touch single-owner engine state without racing the step loop
        self._control: "queue.SimpleQueue" = queue.SimpleQueue()
        self._live: List[_Stream] = []
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._dead = False            # set BEFORE the final inbox sweep
        # graceful drain (ISSUE 12): once set, new completions 503 while
        # in-flight requests run to completion — shutdown is a bounded
        # protocol (FLAGS_fleet_drain_timeout_s), not a SIGKILL
        self._draining = False
        self._conns_open = 0          # event-loop-side open connections
        self._t0 = time.perf_counter()
        self._engine_error: Optional[BaseException] = None
        self._next_rid = 0
        self._rid_lock = threading.Lock()
        self._m = _HttpMetrics()
        self._asyncio_server = None
        # component identity for the fleet trace collector (ISSUE 20):
        # stamped onto engine lifecycle spans and this server's HTTP
        # spans so the merged timeline gets one track per replica even
        # when several servers share a process (tests, the in-proc
        # disagg bench)
        global _SERVER_SEQ
        _SERVER_SEQ += 1
        self.trace_proc = f"{self.role}-{_SERVER_SEQ}"

    # ------------------------------------------------------- lifecycle --
    def start(self) -> "ServingServer":
        """Attach the flight-recorder ring and start the engine thread."""
        if self._thread is not None:
            return self
        if self.flight_recorder is not None:
            self.flight_recorder.attach()
        # tag the engine's retroactive lifecycle spans with this
        # replica's identity for the fleet collector's per-track merge
        self.engine.trace_proc = self.trace_proc
        self._stop.clear()
        self._dead = False
        self._draining = False
        self._ready.clear()
        self._thread = threading.Thread(target=self._engine_loop,
                                        name="serving-engine", daemon=True)
        self._thread.start()
        return self

    def ready(self) -> bool:
        """Readiness: the engine thread is up AND (when ``warmup=True``)
        its bucket warmup compile has completed AND the server is not
        draining — a draining replica must fall out of router placement
        the moment its ``/readyz``//``/statusz`` is next polled."""
        return self.engine_alive() and self._ready.is_set() \
            and not self._draining

    # ------------------------------------------------------------- drain --
    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self) -> None:
        """Stop admission: new completions 503 from here on; in-flight
        requests (accepted streams AND inbox submissions) run to
        completion.  Idempotent, safe from any thread or signal
        handler — it only sets flags."""
        self._draining = True
        self._wake.set()

    def drained(self) -> bool:
        """True once a begun drain has retired every in-flight request:
        no live streams, an empty inbox, an idle engine.  (Reads are
        GIL-atomic snapshots of engine-thread state — the monotone
        drain direction makes a momentarily-stale read harmless.)"""
        if not self._draining:
            return False
        if self._dead or self._thread is None:
            return True                  # engine gone: nothing to wait out
        return not self._live and self._inbox.empty() \
            and not self.engine.has_work()

    def drain(self, timeout_s: Optional[float] = None,
              poll_s: float = 0.02) -> bool:
        """Blocking graceful shutdown: stop admission, wait out in-flight
        requests bounded by ``FLAGS_fleet_drain_timeout_s`` (or
        ``timeout_s``), then close.  Returns True when the drain
        completed inside the bound.  Call from a non-event-loop thread
        (the supervisor / main-thread shutdown path); the asyncio side
        uses the same flags via ``begin_drain()``/``drained()``."""
        self.begin_drain()
        deadline = time.perf_counter() + float(
            flags.flag("fleet_drain_timeout_s")
            if timeout_s is None else timeout_s)
        while time.perf_counter() < deadline and not self.drained():
            time.sleep(poll_s)
        ok = self.drained()
        self.close()
        return ok

    def install_drain_signal(self):
        """SIGTERM → ``begin_drain()`` (chaining any previous handler):
        shutdown becomes stop-admission-and-wait instead of mid-stream
        death.  Install BEFORE ``install_crash_hooks`` so the flight
        recorder's SIGTERM dump fires first and then chains here —
        ``serve_forever`` wires exactly that order.  Returns the
        previous handler (test seam)."""
        prev = signal.getsignal(signal.SIGTERM)

        def _on_sigterm(signum, frame):
            self.begin_drain()
            if callable(prev):
                prev(signum, frame)

        try:
            signal.signal(signal.SIGTERM, _on_sigterm)
        except ValueError:               # not the main thread
            return None
        return prev

    def close(self) -> None:
        self._stop.set()
        self._wake.set()
        t = self._thread
        if t is not None:
            t.join(timeout=30)
            if t.is_alive():
                # a hung device step outlived the join: do NOT forget the
                # thread — start() would spawn a second owner over the
                # (not thread-safe) engine.  engine_alive() stays True and
                # start() keeps returning early until it actually exits.
                import sys
                print("[paddle_tpu serving] engine thread did not exit "
                      "within 30s; refusing to forget it", file=sys.stderr)
            else:
                self._thread = None
        if self.flight_recorder is not None:
            self.flight_recorder.detach()

    def install_crash_hooks(self, **kw) -> None:
        """Wire the flight recorder's watchdog/SIGTERM/excepthook dump
        triggers (main-thread serving processes; see FlightRecorder)."""
        if self.flight_recorder is not None:
            self.flight_recorder.install(manager=self._watchdog, **kw)

    # ------------------------------------------------- engine control ops --
    def run_on_engine(self, fn, timeout_s: float = 30.0):
        """Run ``fn(engine)`` ON the engine thread (between steps) and
        return its result — the only sanctioned way for another thread
        to touch engine state.  Blocking; call from the supervisor /
        executor threads, never from the event loop directly (async
        handlers go through ``run_in_executor``)."""
        if not self.engine_alive():
            raise RuntimeError("engine thread down")
        box: dict = {}
        done = threading.Event()
        self._control.put((fn, box, done))
        self._wake.set()
        if not done.wait(timeout_s):
            raise TimeoutError(
                f"engine thread did not service the control op within "
                f"{timeout_s}s")
        if "error" in box:
            raise box["error"]
        return box["result"]

    def _run_control(self, eng) -> None:
        while True:
            try:
                fn, box, done = self._control.get_nowait()
            except queue.Empty:
                return
            try:
                box["result"] = fn(eng)
            except BaseException as e:
                box["error"] = e
            done.set()

    def export_sessions(self) -> List[dict]:
        """Snapshot every in-flight session's KV (ISSUE 14 drain
        migration, victim side).  Thread-safe; runs on the engine
        thread.  Works while draining — exporting the sessions a drain
        is about to strand is exactly the point."""
        from ..inference import migration as _mig
        return self.run_on_engine(_mig.export_all)

    def import_sessions(self, snaps: List[dict],
                        resume: bool = False) -> dict:
        """Install exported session snapshots into this replica's
        prefix cache (successor side).  Raises MigrationError when the
        engine has no prefix cache to index into."""
        from ..inference import migration as _mig
        if self.engine.prefix_cache is None:
            raise _mig.MigrationError(
                "import needs the prefix cache (FLAGS_prefix_cache) on "
                "the successor replica")

        def op(eng):
            return _mig.import_sessions(
                eng, [_mig.from_wire(s) for s in snaps], resume=resume)

        return self.run_on_engine(op)

    async def start_http(self, host: str = "127.0.0.1", port: int = 0):
        """Bind a real socket listener (bench/production path; the tests
        drive ``handle`` over in-process transports instead).  Returns
        the bound (host, port)."""
        self.start()
        self._asyncio_server = await asyncio.start_server(
            self.handle, host, port)
        return self._asyncio_server.sockets[0].getsockname()[:2]

    async def stop_http(self) -> None:
        if self._asyncio_server is not None:
            self._asyncio_server.close()
            await self._asyncio_server.wait_closed()
            self._asyncio_server = None
        self.close()

    # ------------------------------------------------------ engine loop --
    def engine_alive(self) -> bool:
        # _dead is set (before the final stream sweep) the moment the
        # loop stops serving; counting it here makes liveness flip
        # DETERMINISTICALLY with the sweep's client-visible retirements
        # instead of racing the thread's last instructions on exit
        return self._thread is not None and self._thread.is_alive() \
            and not self._dead

    def _engine_loop(self) -> None:
        eng = self.engine
        wd = self._watchdog
        fr = self.flight_recorder
        finish = "server_shutdown"
        flush = False                 # a step ran since the last idle flush
        try:
            if self._warmup:
                self._warm()
                if self.slo is not None:
                    self.slo.forget()     # warmup latency is compile time
            # ready: the start-up log closes (`/statusz`'s `startup` block)
            _obs.startup.seal()
            self._ready.set()
            tracer = _obs.TRACER
            while not self._stop.is_set():
                with tracer.span("serve.intake"):
                    self._intake(eng)
                if eng.has_work():
                    if wd is not None:
                        tid = wd.begin("serving.engine_step")
                        try:
                            eng.step()
                        finally:
                            wd.end(tid)
                    else:
                        eng.step()
                    self._publish()
                    flush = True
                else:
                    if flush:
                        # one idle step() after the last active one is the
                        # public tail flush: with no active slots it waits
                        # for whatever is still in flight and returns
                        eng.step()
                        self._publish()
                        flush = False
                    with tracer.span("serve.idle"):
                        self._wake.wait(self._poll_s)
                        self._wake.clear()
                with tracer.span("serve.housekeeping"):
                    if fr is not None:
                        fr.maybe_snapshot()
                    if self.sentinel is not None:
                        # host-side registry reads only (never a device
                        # sync); time-gated by FLAGS_sentinel_interval_s
                        self.sentinel.maybe_check()
        except Exception as e:
            # the engine died mid-serve: THE flight-recorder moment.
            # Dump, then fall through to retire every waiter — clients
            # get an 'error' finish instead of hanging forever
            finish = "error"
            self._engine_error = e
            import sys
            import traceback
            print(f"[paddle_tpu serving] engine thread died: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            traceback.print_exc()
            if fr is not None:
                fr.dump(reason=f"engine-crash-{type(e).__name__}")
        finally:
            # retire in-flight streams AND submissions still in the inbox
            # (enqueued after the last sweep) so no handler hangs.
            # _dead is set FIRST: a handler that enqueues after this sweep
            # observes it and retires its own stream (submit-vs-death race)
            self._dead = True
            while True:
                try:
                    self._live.append(self._inbox.get_nowait())
                except queue.Empty:
                    break
            # fail queued control ops so their callers don't wait out
            # the full timeout against a dead thread
            while True:
                try:
                    _fn, box, done = self._control.get_nowait()
                except queue.Empty:
                    break
                box["error"] = RuntimeError("engine thread down")
                done.set()
            for h in list(self._live):
                h.post(("done", {"finish_reason": finish,
                                 "n": len(h.req.output) if h.req else 0}))
            self._live.clear()

    def _intake(self, eng) -> None:
        """Between steps, on the engine thread: hand the inbox to the
        engine, run control operations, shed what waited too long."""
        while True:
            try:
                h = self._inbox.get_nowait()
            except queue.Empty:
                break
            h.req = eng.submit(h.prompt, h.max_new_tokens,
                               trace_id=h.trace_id)
            self._live.append(h)
        self._run_control(eng)
        if self._queue_timeout_s > 0 and self._live:
            # queue-expiry shedding (ISSUE 15): a request that admission
            # hasn't picked up inside the bound is retired 504 BEFORE its
            # prefill is spent — the client behind it gave up long ago;
            # an admitted request is past the point of free cancellation
            # and runs out (continuous batching has no cheap mid-flight
            # cancel)
            now = time.perf_counter()
            for h in list(self._live):
                if h.req is not None and not h.req.done and \
                        now - h.t_accept > self._queue_timeout_s \
                        and eng.cancel_waiting(h.req):
                    self._m.queue_expired.inc()
                    self._live.remove(h)
                    h.post(("done",
                            {"finish_reason": "queue_expired",
                             "n": 0}))

    @_obs.startup.around("startup.warm")
    def _warm(self) -> None:
        """Compile the engine's step-program pair (T=prefill_bucket mixed
        + T=1 decode) by driving one junk request to completion on the
        engine thread, BEFORE ``/readyz`` flips to ready.  The warmup
        prompt is deterministic; with the prefix cache on its few pages
        land idle in the LRU pool (evicted at the first real pressure)
        and greedy outputs are unaffected (the PR 4 bit-match contract).
        """
        eng = self.engine
        vocab = eng.g.config.vocab_size
        n = eng.g.prefill_bucket + 3      # chunked prefill + partial tail
        # clamp to what the pool physically holds: warmup exists to
        # compile the step programs (any length crosses the T=bucket and
        # T=1 programs), not to exercise pool exhaustion — an oversized
        # warmup prompt on an undersized pool would MemoryError the
        # engine thread and leave a permanently-unready process behind a
        # launcher that exited 0
        alloc = eng.g.cache.allocator
        n = max(1, min(n, alloc.num_pages * alloc.page_size - 2))
        prompt = [(i % (vocab - 1)) + 1 for i in range(n)]
        req = eng.submit(prompt, max_new_tokens=2, trace_id="warmup")
        while not req.done and not self._stop.is_set():
            eng.step()
        eng.step()                        # idle tail flush (blocking)
        if eng.prefix_cache is not None:
            # compile the session-migration upload program too (ISSUE
            # 14) so a live import/migration never compiles under
            # routed traffic (with spill on this is a cache hit — the
            # spill tier warmed the same program at engine init)
            from ..inference import migration as _mig
            _mig.warm(eng)

    def _publish(self) -> None:
        """Diff every live request's gathered output; push fresh tokens
        (after every step: a chunk a step for every stream that moved)."""
        eos = self.engine.gen_cfg.eos_token_id
        with _obs.TRACER.span("serve.publish") as span:
            tokens = streams = 0
            for h in list(self._live):
                req = h.req
                out = req.output
                if len(out) > h.sent:
                    h.post(("tokens", list(out[h.sent:])))
                    tokens += len(out) - h.sent
                    streams += 1
                    h.sent = len(out)
                if req.done:
                    reason = "stop" if (eos is not None and out
                                        and out[-1] == eos) else "length"
                    h.post(("done", {"finish_reason": reason,
                                     "n": len(out)}))
                    self._live.remove(h)
            span.set_metadata(tokens=tokens, streams=streams)

    # ---------------------------------------------------------- handler --
    async def handle(self, reader, writer) -> None:
        """One HTTP connection (asyncio.start_server signature; equally
        happy with in-process stream stand-ins)."""
        t0 = time.perf_counter()
        status = 500
        # counted from connection accept so responses{code} never
        # outruns requests (parse failures are requests too)
        self._m.requests.inc()
        self._m.inflight.inc(1)
        self._conns_open += 1         # per-server (the gauge is process-wide)
        try:
            try:
                method, path, headers, body = \
                    await _http.read_request(reader)
            except _http.HttpError as e:
                status = e.status
                writer.write(_http.error_response(e.status, e.message))
                await writer.drain()
                return
            status = await self._route(method, path, headers, body, writer)
        except (ConnectionError, asyncio.IncompleteReadError):
            status = 499              # client went away mid-stream
        except Exception as e:
            try:
                writer.write(_http.error_response(
                    500, f"{type(e).__name__}: {e}",
                    err_type="internal_error"))
                await writer.drain()
            except Exception:
                pass
        finally:
            self._conns_open -= 1
            self._m.inflight.inc(-1)
            self._m.responses(status).inc()
            self._m.request_ms.observe((time.perf_counter() - t0) * 1e3)
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:
                pass

    async def _route(self, method, path, headers, body, writer) -> int:
        path, _, query = path.partition("?")
        if path == "/drainz" and method == "POST":
            # the fleet supervisor's drain trigger (SIGTERM's HTTP twin):
            # stop admission NOW, report what is still in flight; the
            # caller polls /statusz (or waits for process exit on the
            # SIGTERM path) for completion
            self.begin_drain()
            writer.write(_http.json_response(200, {
                "draining": True,
                "streams_live": len(self._live),
                "waiting": len(self.engine.waiting),
                "drained": self.drained()}))
            await writer.drain()
            return 200
        if path == "/metrics" and method == "GET":
            text = _obs.prometheus_text().encode()
            writer.write(_http.response(
                200, text, content_type="text/plain; version=0.0.4"))
            await writer.drain()
            return 200
        if path == "/healthz" and method == "GET":
            # liveness, the pre-ISSUE-7 shape: engine thread up.  A cold
            # (warming) replica is ALIVE here but not ready below.
            alive = self.engine_alive()
            writer.write(_http.json_response(
                200 if alive else 503,
                {"status": "ok" if alive else "engine thread down"}))
            await writer.drain()
            return 200 if alive else 503
        if path == "/readyz" and method == "GET":
            ready = self.ready()
            why = ("ok" if ready else
                   "engine warmup compile in progress"
                   if self.engine_alive() else "engine thread down")
            writer.write(_http.json_response(
                200 if ready else 503, {"ready": ready, "status": why}))
            await writer.drain()
            return 200 if ready else 503
        if path == "/statusz" and method == "GET":
            # digest DELTA sync (ISSUE 14): ?digest_since=<gen>:<epoch>
            # asks for only the index changes since the caller's last
            # confirmed epoch instead of the full re-shipped set
            since = None
            if query:
                from urllib.parse import parse_qs
                since = (parse_qs(query).get("digest_since")
                         or [None])[0]
            writer.write(_http.json_response(
                200, self.statusz(digest_since=since)))
            await writer.drain()
            return 200
        if path == "/migratez/export" and method == "POST":
            return await self._migrate_export(body, writer)
        if path == "/migratez/import" and method == "POST":
            return await self._migrate_import(body, writer)
        if path == "/v1/completions" and method == "POST":
            return await self._completions(headers, body, writer)
        if path in ("/metrics", "/healthz", "/readyz", "/statusz",
                    "/v1/completions", "/drainz", "/migratez/export",
                    "/migratez/import"):
            writer.write(_http.error_response(405, f"{method} not allowed"))
            await writer.drain()
            return 405
        writer.write(_http.error_response(404, f"no route {path}"))
        await writer.drain()
        return 404

    # ------------------------------------------- session migration (14) --
    async def _migrate_export(self, body, writer) -> int:
        """``POST /migratez/export`` — stream session snapshot(s):
        ``{"req_id": N}`` one in-flight session, ``{"tokens": [...]}``
        a parked session's prefix chain, ``{"all": true}`` every
        in-flight session (the drain-migration bulk shape).  Runs on
        the engine thread; allowed while draining (exporting what a
        drain would otherwise strand is the point).  Bounded and
        cancellable — aborting the connection at any byte costs
        nothing (the snapshot is assembled before the first response
        byte; no allocator state changes on export)."""
        from ..inference import migration as _mig
        try:
            payload = json.loads(body.decode() or "{}")
            if not isinstance(payload, dict):
                raise ValueError("body must be a JSON object")
        except (ValueError, UnicodeDecodeError) as e:
            writer.write(_http.error_response(400, f"bad JSON body: {e}"))
            await writer.drain()
            return 400
        if not self.engine_alive():
            writer.write(_http.error_response(
                503, "engine thread down", err_type="internal_error"))
            await writer.drain()
            return 503

        def op(eng):
            if payload.get("all"):
                snaps = _mig.export_all(eng)
            elif "req_id" in payload:
                snaps = [_mig.export_session(
                    eng, req_id=int(payload["req_id"]))]
            elif "tokens" in payload:
                snaps = [_mig.export_session(
                    eng, tokens=list(payload["tokens"]))]
            else:
                raise _mig.MigrationError(
                    "body needs one of req_id / tokens / all")
            return [_mig.to_wire(s) for s in snaps]

        t0 = time.perf_counter()
        loop = asyncio.get_running_loop()
        try:
            snaps = await loop.run_in_executor(
                None, self.run_on_engine, op)
        except (_mig.MigrationError, ValueError, TypeError) as e:
            writer.write(_http.error_response(400, str(e)))
            await writer.drain()
            return 400
        except Exception as e:
            writer.write(_http.error_response(
                503, f"export failed: {type(e).__name__}: {e}",
                err_type="internal_error"))
            await writer.drain()
            return 503
        # trace propagation (ISSUE 20 satellite): a handoff/takeover leg
        # joins the ORIGINATING request's trace lane — the caller's
        # trace id rides the body, is stamped onto snapshots that lack
        # one (token-chain exports), and the export itself becomes a
        # span on that lane instead of starting a fresh one
        trace_id = payload.get("trace_id")
        if isinstance(trace_id, str) and trace_id and _TRACE_ID_OK(trace_id):
            for s in snaps:
                if not s.get("trace_id"):
                    s["trace_id"] = trace_id
        else:
            trace_id = next((s.get("trace_id") for s in snaps
                             if s.get("trace_id")), None)
        if _obs.TRACER.enabled and trace_id:
            _obs.TRACER.event("migrate.export", t0,
                              time.perf_counter() - t0, cat="migration",
                              tid=trace_id,
                              args={"trace_id": trace_id,
                                    "proc": self.trace_proc,
                                    "sessions": len(snaps)})
        writer.write(_http.json_response(200, {"sessions": snaps}))
        await writer.drain()
        return 200

    async def _migrate_import(self, body, writer) -> int:
        """``POST /migratez/import`` — install exported session
        snapshot(s) (``{"sessions": [...]}`` or one bare snapshot) into
        this replica's prefix cache; ``"resume": true`` also registers
        each session's continuation request on the engine thread.  Safe
        to abort at any byte: a truncated body fails JSON parsing (400,
        nothing installed) and a partial page list imports as a shorter
        contiguous chain with zero dangling allocator refs."""
        from ..inference import migration as _mig
        try:
            payload = json.loads(body.decode() or "{}")
            if not isinstance(payload, dict):
                raise ValueError("body must be a JSON object")
        except (ValueError, UnicodeDecodeError) as e:
            writer.write(_http.error_response(400, f"bad JSON body: {e}"))
            await writer.drain()
            return 400
        sessions = payload.get("sessions")
        if sessions is None and "version" in payload:
            sessions = [payload]
        if not isinstance(sessions, list):
            writer.write(_http.error_response(
                400, "body needs a 'sessions' list (or one snapshot)"))
            await writer.drain()
            return 400
        if self._draining:
            writer.write(_http.error_response(
                503, "draining: this replica is leaving the fleet and "
                     "cannot adopt sessions", err_type="overloaded_error"))
            await writer.drain()
            return 503
        if not self.engine_alive():
            writer.write(_http.error_response(
                503, "engine thread down", err_type="internal_error"))
            await writer.drain()
            return 503
        resume = bool(payload.get("resume", False))
        # trace propagation (ISSUE 20 satellite): stamp the caller's
        # trace id onto snapshots that lack one BEFORE import, so a
        # resumed continuation request inherits the originating lane and
        # its decode-leg lifecycle spans join the same merged timeline
        trace_id = payload.get("trace_id")
        if isinstance(trace_id, str) and trace_id and _TRACE_ID_OK(trace_id):
            for s in sessions:
                if isinstance(s, dict) and not s.get("trace_id"):
                    s["trace_id"] = trace_id
        else:
            trace_id = next((s.get("trace_id") for s in sessions
                             if isinstance(s, dict) and s.get("trace_id")),
                            None)
        t0 = time.perf_counter()
        loop = asyncio.get_running_loop()
        try:
            result = await loop.run_in_executor(
                None, self.import_sessions, sessions, resume)
        except _mig.MigrationError as e:
            writer.write(_http.error_response(409, str(e)))
            await writer.drain()
            return 409
        except Exception as e:
            writer.write(_http.error_response(
                503, f"import failed: {type(e).__name__}: {e}",
                err_type="internal_error"))
            await writer.drain()
            return 503
        if payload.get("handoff"):
            # prefill->decode handoff accounting (ISSUE 16): how much
            # of the shipped prefix this successor must re-prefill —
            # the acceptance lever is 0 full pages
            _mig.record_handoff(sessions, result)
        if _obs.TRACER.enabled and trace_id:
            _obs.TRACER.event("migrate.import", t0,
                              time.perf_counter() - t0, cat="migration",
                              tid=trace_id,
                              args={"trace_id": trace_id,
                                    "proc": self.trace_proc,
                                    "resume": resume,
                                    "handoff": bool(payload.get("handoff")),
                                    "sessions": len(sessions)})
        writer.write(_http.json_response(200, result))
        await writer.drain()
        return 200

    # ------------------------------------------------------ completions --
    def _parse_prompt(self, p) -> List[int]:
        if isinstance(p, str):
            try:
                p = [int(t) for t in p.split()]
            except ValueError:
                raise _http.HttpError(
                    400, "string prompts must be space-separated token ids "
                         "(no tokenizer in-tree)")
        if not isinstance(p, list) or not p or \
                not all(isinstance(t, int) and not isinstance(t, bool)
                        for t in p):
            raise _http.HttpError(
                400, "prompt must be a non-empty list of token ids")
        vocab = self.engine.g.config.vocab_size
        if not all(0 <= t < vocab for t in p):
            # out-of-range ids would be silently clamped by the embedding
            # gather and return plausible-looking garbage
            raise _http.HttpError(
                400, f"token ids must be in [0, {vocab})")
        return p

    def _trace_id(self, headers=None) -> str:
        """Request id == trace-context id.  A syntactically-safe
        ``X-Trace-Id`` request header is honored (the multi-replica
        router propagates its id here so one request is ONE correlated
        trace track, router span + replica engine spans on one lane);
        anything else gets a fresh id."""
        if headers:
            t = headers.get("x-trace-id", "")
            if t and _TRACE_ID_OK(t):
                return t
        with self._rid_lock:
            n = self._next_rid
            self._next_rid += 1
        return f"cmpl-{os.getpid():x}-{n:06x}-{os.urandom(4).hex()}"

    async def _completions(self, headers, body, writer) -> int:
        try:
            payload = json.loads(body.decode() or "{}")
            if not isinstance(payload, dict):
                raise ValueError("body must be a JSON object")
        except (ValueError, UnicodeDecodeError) as e:
            writer.write(_http.error_response(400, f"bad JSON body: {e}"))
            await writer.drain()
            return 400
        try:
            prompt = self._parse_prompt(payload.get("prompt"))
        except _http.HttpError as e:
            writer.write(_http.error_response(e.status, e.message))
            await writer.drain()
            return e.status
        max_tokens = payload.get("max_tokens",
                                 self.engine.gen_cfg.max_new_tokens)
        if not isinstance(max_tokens, int) or isinstance(max_tokens, bool) \
                or max_tokens < 1:
            writer.write(_http.error_response(
                400, "max_tokens must be a positive integer"))
            await writer.drain()
            return 400
        # a prompt whose page demand exceeds the whole KV pool would raise
        # MemoryError inside engine admission and kill the engine thread —
        # reject it here instead (admission truncates to max_seq_len-1, so
        # the truncated length is the demand that matters)
        g = self.engine.g
        need = -(-min(len(prompt), g.max_seq_len - 1) // g.page_size)
        if need > g.num_pages:
            writer.write(_http.error_response(
                413, f"prompt needs {need} KV pages but the pool only has "
                     f"{g.num_pages}"))
            await writer.drain()
            return 413
        stream = bool(payload.get("stream", False))

        if self._draining:
            # graceful drain: admission is closed but in-flight requests
            # are still finishing — the router should already be steering
            # around this replica; a direct client retries elsewhere
            # (jittered so a drained-out fleet's clients don't re-herd)
            ra = jittered_retry_after(2)
            writer.write(_http.error_response(
                503, "draining: admission closed, in-flight requests "
                     "finishing (see /statusz)",
                err_type="overloaded_error",
                extra_headers=(("Retry-After", str(ra)),),
                fields={"retry_after_s": ra}))
            await writer.drain()
            return 503

        if not self.engine_alive():
            # the engine thread is down (crashed or closed): refuse
            # rather than enqueue into a dead inbox
            why = (f": {type(self._engine_error).__name__}"
                   if self._engine_error is not None else "")
            writer.write(_http.error_response(
                503, f"engine thread down{why}",
                err_type="internal_error"))
            await writer.drain()
            return 503

        # SLO-driven admission: histogram burn, not queue length.
        # Retry-After is derived from the LIVE burn window (how long the
        # current violation rate takes to dilute back under the shed
        # threshold at the live observation rate), not a constant, and is
        # mirrored into the JSON error body for header-blind clients.
        if self.slo is not None and self.slo.decide() == SHED:
            ra = self.slo.retry_after_s()
            writer.write(_http.error_response(
                503, "shedding load: serving latency SLO burn "
                     f"(see /statusz)", err_type="overloaded_error",
                extra_headers=(("Retry-After", str(ra)),),
                fields={"retry_after_s": ra}))
            await writer.drain()
            return 503

        trace_id = self._trace_id(headers)
        h = _Stream(trace_id, prompt, max_tokens,
                    asyncio.get_running_loop())
        self._inbox.put(h)
        self._wake.set()
        if self._dead:
            # the engine exited between the liveness check and the put:
            # its final sweep may have missed this submission, so retire
            # it here (a double 'done' is harmless — first one wins)
            h.post(("done", {"finish_reason": "error"
                             if self._engine_error else "server_shutdown",
                             "n": 0}))
        try:
            if stream:
                self._m.streams.inc()
                code = await self._stream_response(h, writer)
            else:
                code = await self._unary_response(h, writer)
        except BaseException:
            # CancelledError (caller timeout / loop teardown) included:
            # nobody is reading this queue any more — stop posting to it
            h.cancelled = True
            raise
        if _obs.TRACER.enabled:
            _obs.TRACER.event("http.request", h.t_accept,
                              time.perf_counter() - h.t_accept,
                              cat="serving", tid=trace_id,
                              args={"trace_id": trace_id,
                                    "stream": stream,
                                    "proc": self.trace_proc,
                                    "prompt_tokens": len(prompt)})
        return code

    def _chunk(self, h: _Stream, token_ids, finish_reason=None) -> dict:
        return {"id": h.trace_id, "object": "text_completion.chunk",
                "model": self.model_name,
                "choices": [{"index": 0,
                             "text": " ".join(str(t) for t in token_ids),
                             "token_ids": list(token_ids),
                             "finish_reason": finish_reason}]}

    async def _stream_response(self, h: _Stream, writer) -> int:
        writer.write(_http.sse_headers(
            extra_headers=(("X-Request-Id", h.trace_id),)))
        await writer.drain()
        # the response head is out: from here NO error document may be
        # written into the event stream — failures terminate it and are
        # reported by status code only
        try:
            while True:
                kind, payload = await h.q.get()
                if kind == "tokens":
                    writer.write(_http.sse_event(self._chunk(h, payload)))
                    await writer.drain()
                else:
                    writer.write(_http.sse_event(self._chunk(
                        h, (), finish_reason=payload["finish_reason"])))
                    writer.write(_http.sse_done())
                    await writer.drain()
                    return 200
        except (ConnectionError, RuntimeError,
                asyncio.IncompleteReadError):
            # client disconnected: stop posting; the engine finishes the
            # request (continuous batching has no cheap mid-flight cancel)
            h.cancelled = True
            return 499
        except Exception as e:
            h.cancelled = True
            import sys
            print(f"[paddle_tpu serving] stream {h.trace_id} failed "
                  f"mid-flight: {type(e).__name__}: {e}", file=sys.stderr)
            return 500

    async def _unary_response(self, h: _Stream, writer) -> int:
        toks: List[int] = []
        while True:
            kind, payload = await h.q.get()
            if kind == "tokens":
                toks.extend(payload)
            else:
                finish = payload["finish_reason"]
                break
        if finish == "queue_expired":
            # queue-expiry shedding (ISSUE 15): the request waited in
            # the inbox past FLAGS_serving_queue_timeout_s and was
            # retired before dispatch — 504, zero prefill spent
            writer.write(_http.error_response(
                504, "request expired in queue before dispatch "
                     f"(FLAGS_serving_queue_timeout_s="
                     f"{self._queue_timeout_s})",
                err_type="timeout_error",
                extra_headers=(("X-Request-Id", h.trace_id),)))
            await writer.drain()
            return 504
        if finish in ("error", "server_shutdown"):
            # the engine died (or shut down) before this request finished:
            # headers are not out yet on the unary path, so report it as
            # the failure it is instead of a 200 with finish='error'
            writer.write(_http.error_response(
                503, f"engine {finish} before the request completed",
                err_type="internal_error",
                extra_headers=(("X-Request-Id", h.trace_id),)))
            await writer.drain()
            return 503
        out = {"id": h.trace_id, "object": "text_completion",
               "model": self.model_name,
               "choices": [{"index": 0,
                            "text": " ".join(str(t) for t in toks),
                            "token_ids": toks,
                            "finish_reason": finish}],
               "usage": {"prompt_tokens": len(h.prompt),
                         "completion_tokens": len(toks),
                         "total_tokens": len(h.prompt) + len(toks)}}
        writer.write(_http.json_response(
            200, out, extra_headers=(("X-Request-Id", h.trace_id),)))
        await writer.drain()
        return 200

    # ----------------------------------------------------------- status --
    def statusz(self, digest_since: Optional[str] = None) -> dict:
        """Everything a human (or scraper) needs to know the process is
        sane: engine/pool/prefix gauges, jit cache stats, SLO burn,
        flight recorder, build/flag info.  ``digest_since`` (ISSUE 14)
        requests a prefix-digest DELTA against a previously confirmed
        ``<gen>:<epoch>`` instead of the full set."""
        import sys

        import jax

        from .. import jit as _jit
        eng = self.engine
        out = {
            "uptime_s": round(time.perf_counter() - self._t0, 3),
            "model": self.model_name,
            # disaggregated serving (ISSUE 16): the router's phase
            # routing keys off this
            "role": self.role,
            "ready": self.ready(),
            # drain protocol (ISSUE 12): the router marks this replica
            # `draining` off its next poll; the supervisor polls
            # `drained` for completion on the /drainz path
            "draining": self._draining,
            "drained": self.drained(),
            "engine": {
                **eng.last_stats,
                "waiting": len(eng.waiting),
                "slots_busy": sum(r is not None for r in eng.slot_req),
                "slots": eng.B,
                "streams_live": len(self._live),
                # capacity advertisement (ISSUE 18): tensor-parallel
                # degree + host-global KV pool bytes, the inputs of the
                # router's capacity-weighted heterogeneous placement
                # (explicit here so the advertisement never depends on
                # a gather having refreshed last_stats)
                "tp": getattr(eng.g, "tp", 1),
                "pool_bytes": getattr(eng.g, "pool_bytes", 0),
                # the router's failover-resume eligibility check (ISSUE
                # 14/15): greedy replays are bit-exact anywhere; sampled
                # replays are bit-exact on a survivor with the IDENTICAL
                # seeded positional config — advertise the whole thing
                "sampling": {"do_sample": bool(eng.gen_cfg.do_sample),
                             "seed": int(eng.gen_cfg.seed),
                             "temperature": float(
                                 eng.gen_cfg.temperature),
                             "top_k": int(eng.gen_cfg.top_k),
                             "top_p": float(eng.gen_cfg.top_p),
                             "positional": True},
            },
            # router placement inputs (ISSUE 7): which prefixes this
            # replica holds, as chain hashes a router scores against —
            # full set, or adds/evictions since `digest_since` (ISSUE 14)
            "prefix_digest": eng.prefix_digest(since=digest_since)
            if hasattr(eng, "prefix_digest") else None,
            "slo": self.slo.state() if self.slo is not None else None,
            # latency quantiles (ISSUE 10 satellite): the p50/p95/p99
            # the registry already computes, surfaced per series incl.
            # every per-phase step_ms — a scraper-free latency read
            "latency": self._latency_summaries(),
            # hung-request table: top-K oldest in-flight with trace ids
            "inflight_requests": eng.inflight_requests()
            if hasattr(eng, "inflight_requests") else None,
            # per-(phase, bucket) EWMA step-cost table (ISSUE 10)
            "attribution": eng.attribution.baselines()
            if getattr(eng, "attribution", None) is not None else None,
            # sentinel verdicts (ISSUE 10): recent anomalies + detector
            # baselines; the router aggregates these fleet-wide
            "anomalies": self.sentinel.state()
            if self.sentinel is not None else None,
            "flight_recorder": None,
            "jit_cache": _jit.cache_stats(),
            # time to ready by phase (observability/startup.py): the
            # records on the process's own age, sealed when /readyz flipped
            "startup": _obs.startup.status(),
            "build": {
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "python": sys.version.split()[0],
                "pid": os.getpid(),
            },
            "flags": flags.get_flags(),
        }
        fr = self.flight_recorder
        if fr is not None:
            out["flight_recorder"] = {
                "ring_events": len(fr._ring),
                "ring_capacity": fr.max_events,
                "last_dump": fr.last_dump,
                "dumps": int(_obs.metrics.counter(
                    "flight_recorder.dumps").value),
                "suppressed": int(_obs.metrics.counter(
                    "flight_recorder.suppressed_dumps").value),
                "min_interval_s": fr.min_interval_s,
            }
        return out

    @staticmethod
    def _latency_summaries() -> dict:
        """p50/p95/p99 per latency series (every label set — the
        per-phase ``serving.step_ms{phase=...}`` family included)."""
        from ..observability.metrics import _series_name
        out = {}
        for fam in ("serving.ttft_ms", "serving.itl_ms",
                    "serving.queue_wait_ms", "serving.step_ms"):
            for h in _obs.REGISTRY.find(fam, "histogram"):
                s = h.summary()
                out[_series_name(h.name, h.labels)] = {
                    k: s[k] for k in ("count", "p50", "p95", "p99")}
        return out


async def _serve_async(server: ServingServer, host: str, port: int):
    bound = await server.start_http(host, port)
    print(f"[paddle_tpu serving] listening on http://{bound[0]}:{bound[1]}"
          f"  (/v1/completions, /metrics, /healthz, /statusz)")
    try:
        while not server.draining:
            await asyncio.sleep(0.1)
        # SIGTERM (or /drainz) began a drain: wait out in-flight requests
        # bounded by FLAGS_fleet_drain_timeout_s, then give the handlers
        # a short grace to flush their final frames before the listener
        # closes — exit is clean (rc 0), never a mid-stream cut
        deadline = time.perf_counter() + float(
            flags.flag("fleet_drain_timeout_s"))
        while time.perf_counter() < deadline and not server.drained():
            await asyncio.sleep(0.05)
        t_flush = time.perf_counter()
        while time.perf_counter() - t_flush < 2.0 and server._conns_open:
            await asyncio.sleep(0.02)
        print("[paddle_tpu serving] drain "
              f"{'complete' if server.drained() else 'TIMED OUT'}; "
              "shutting down")
    finally:
        await server.stop_http()


def serve_forever(engine, *, host: str = "127.0.0.1", port: int = 8000,
                  **kw) -> None:
    """Blocking convenience entry: build the server, wire the SIGTERM
    graceful-drain handler plus crash hooks (watchdog + SIGTERM +
    excepthook flight-recorder dumps — the dump fires first, then
    chains into the drain), serve until killed.  SIGTERM shutdown is a
    bounded drain protocol: admission stops, in-flight requests finish
    (up to ``FLAGS_fleet_drain_timeout_s``), exit code 0."""
    from ..distributed.watchdog import get_comm_task_manager
    kw.setdefault("watchdog", get_comm_task_manager())
    server = ServingServer(engine, **kw)
    server.start()
    server.install_drain_signal()     # BEFORE crash hooks: dump chains here
    server.install_crash_hooks()
    # fleet span export (ISSUE 20): with a collector address configured
    # (the fleet launcher passes its router's host:port down via
    # --set trace_collector=...), ship this replica's spans over direct
    # HTTP POST /collectz — host-side daemon thread, off the dispatch
    # path, so the warm-step 0-compile/0-sync contract is untouched
    exporter = None
    addr = str(flags.flag("trace_collector"))
    if addr and float(flags.flag("trace_sample_rate")) > 0:
        from ..observability.collector import HttpTransport, SpanExporter
        exporter = SpanExporter(
            HttpTransport(addr),
            proc=f"{server.trace_proc}@{host}:{port}",
            role=server.role).start()
    try:
        asyncio.run(_serve_async(server, host, port))
    except KeyboardInterrupt:
        pass
    finally:
        if exporter is not None:
            exporter.close()
        server.close()
