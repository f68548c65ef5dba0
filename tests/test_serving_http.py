"""Serving front door (ISSUE 6): the asyncio HTTP layer over the
continuous-batching engine, driven through IN-PROCESS transports — no
sockets, so tier-1 stays offline — plus the SLO shed path, the HTTP-on
overhead contract, and the crash flight recorder's watchdog/SIGTERM
dump paths.  The one socket-binding test is marked ``slow``.
"""

import asyncio
import json
import os
import signal
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ServingServer, SLOController

from test_observability import parse_prometheus


# ---------------------------------------------------------------------------
# in-process transport plumbing: the handler only needs readline/readexactly
# on one side and write/drain/close on the other
# ---------------------------------------------------------------------------

class MemWriter:
    def __init__(self):
        self.buf = bytearray()
        self.closed = False

    def write(self, b):
        self.buf.extend(b)

    async def drain(self):
        pass

    def close(self):
        self.closed = True

    async def wait_closed(self):
        pass

    def get_extra_info(self, *a, **k):
        return None

    def is_closing(self):
        return self.closed


def mem_conn(raw: bytes):
    r = asyncio.StreamReader()
    r.feed_data(raw)
    r.feed_eof()
    return r, MemWriter()


def http_bytes(method, path, body=None):
    body = body or b""
    head = (f"{method} {path} HTTP/1.1\r\nHost: test\r\n"
            f"Content-Length: {len(body)}\r\n\r\n")
    return head.encode() + body


def split_response(raw: bytes):
    head, _, body = bytes(raw).partition(b"\r\n\r\n")
    lines = head.decode("latin-1").split("\r\n")
    status = int(lines[0].split()[1])
    headers = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers, body


def sse_chunks(body: bytes):
    """Parsed `data:` JSON frames (excluding the [DONE] terminator)."""
    out = []
    for ln in body.decode().splitlines():
        if ln.startswith("data: ") and ln != "data: [DONE]":
            out.append(json.loads(ln[len("data: "):]))
    return out


async def do(server, method, path, body=None):
    r, w = mem_conn(http_bytes(method, path, body))
    await server.handle(r, w)
    return split_response(w.buf)


def completion_body(prompt, max_tokens, stream=False):
    return json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "stream": stream}).encode()


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("gen", GenerationConfig(max_new_tokens=6))
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_bucket", 8)
    return ContinuousBatchingEngine(model, **kw)


PROMPTS = ([1, 2, 3, 4, 5], [9, 8, 7], [4, 5, 6, 7])


@pytest.fixture(scope="module")
def oracle(model):
    """Direct ContinuousBatchingEngine outputs for PROMPTS — the
    bit-identity reference for everything streamed over HTTP."""
    eng = _engine(model)
    rids = [eng.add_request(p) for p in PROMPTS]
    out = eng.run()
    return {tuple(p): out[r] for p, r in zip(PROMPTS, rids)}


# ---------------------------------------------------------------------------
# streaming + scrape-during-load (the acceptance criterion)
# ---------------------------------------------------------------------------

def test_stream_bit_identical_with_concurrent_metrics_scrape(model, oracle):
    """End-to-end: streamed tokens are bit-identical to the direct engine
    run, while a /metrics scrape taken MID-STREAM (after the first chunk,
    before [DONE]) returns strictly parseable Prometheus text containing
    the serving.ttft_ms histogram for that traffic."""
    obs.reset("serving.")
    server = ServingServer(_engine(model), slo=False,
                           flight_recorder=False).start()
    try:
        async def main():
            r, w = mem_conn(http_bytes(
                "POST", "/v1/completions",
                completion_body(list(PROMPTS[0]), 6, stream=True)))
            task = asyncio.create_task(server.handle(r, w))
            deadline = time.perf_counter() + 60
            while b"data: " not in w.buf:
                assert time.perf_counter() < deadline, "no first chunk"
                await asyncio.sleep(0.005)
            # mid-stream scrape, same loop, same process
            status, headers, text = await do(server, "GET", "/metrics")
            await task
            return status, headers, text, w.buf

        status, headers, text, raw = asyncio.run(main())
        assert status == 200
        assert headers["content-type"].startswith("text/plain")
        fams = parse_prometheus(text.decode())
        assert fams["paddle_tpu_serving_ttft_ms"]["type"] == "histogram"
        ttft_count = [v for n, lb, v in
                      fams["paddle_tpu_serving_ttft_ms"]["samples"]
                      if n.endswith("_count")]
        assert float(ttft_count[0]) >= 1          # THIS traffic is in it

        sstatus, sheaders, sbody = split_response(raw)
        assert sstatus == 200
        assert sheaders["content-type"].startswith("text/event-stream")
        chunks = sse_chunks(sbody)
        toks = [t for c in chunks for t in c["choices"][0]["token_ids"]]
        assert toks == oracle[tuple(PROMPTS[0])]   # bit-identical
        assert sbody.rstrip().endswith(b"data: [DONE]")
        # the response id is one trace context across every chunk AND the
        # X-Request-Id header
        ids = {c["id"] for c in chunks}
        assert ids == {sheaders["x-request-id"]}
        assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    finally:
        server.close()


def test_unary_completion_and_concurrent_streams(model, oracle):
    """N concurrent requests (mixed stream/unary) all bit-match the
    direct-engine oracle — continuous batching order cannot change any
    request's greedy output."""
    server = ServingServer(_engine(model), slo=False,
                           flight_recorder=False).start()
    try:
        async def one(prompt, stream):
            status, headers, body = await do(
                server, "POST", "/v1/completions",
                completion_body(list(prompt), 6, stream=stream))
            assert status == 200
            if stream:
                return [t for c in sse_chunks(body)
                        for t in c["choices"][0]["token_ids"]]
            doc = json.loads(body)
            assert doc["usage"]["completion_tokens"] == \
                len(doc["choices"][0]["token_ids"])
            assert doc["usage"]["prompt_tokens"] == len(prompt)
            assert doc["id"].startswith("cmpl-")
            return doc["choices"][0]["token_ids"]

        async def main():
            return await asyncio.gather(
                one(PROMPTS[0], True), one(PROMPTS[1], False),
                one(PROMPTS[2], True))

        results = asyncio.run(main())
        for p, got in zip(PROMPTS, results):
            assert got == oracle[tuple(p)]
    finally:
        server.close()


def test_http_error_paths(model):
    server = ServingServer(_engine(model), slo=False,
                           flight_recorder=False).start()
    try:
        async def main():
            out = {}
            out["notfound"] = await do(server, "GET", "/nope")
            out["method"] = await do(server, "GET", "/v1/completions")
            out["badjson"] = await do(server, "POST", "/v1/completions",
                                      b"{not json")
            out["badprompt"] = await do(
                server, "POST", "/v1/completions",
                json.dumps({"prompt": ["a", "b"]}).encode())
            out["badmax"] = await do(
                server, "POST", "/v1/completions",
                json.dumps({"prompt": [1, 2], "max_tokens": 0}).encode())
            out["boolmax"] = await do(
                server, "POST", "/v1/completions",
                json.dumps({"prompt": [1, 2], "max_tokens": True}).encode())
            out["strprompt"] = await do(
                server, "POST", "/v1/completions",
                json.dumps({"prompt": "5 6 7", "max_tokens": 2}).encode())
            return out

        out = asyncio.run(main())
        assert out["notfound"][0] == 404
        assert out["method"][0] == 405
        assert out["badjson"][0] == 400
        assert out["badprompt"][0] == 400
        assert out["badmax"][0] == 400
        assert out["boolmax"][0] == 400
        # space-separated token-id strings are accepted (no tokenizer)
        assert out["strprompt"][0] == 200
        assert json.loads(out["strprompt"][2])["usage"]["prompt_tokens"] == 3
        for key in ("notfound", "method", "badjson"):
            err = json.loads(out[key][2])["error"]
            assert err["code"] == out[key][0]
    finally:
        server.close()


def test_prompt_exceeding_pool_rejected_413(model, oracle):
    """A prompt whose page demand exceeds the whole KV pool must be a
    per-request 413, NOT a MemoryError that kills the engine thread (one
    bad request must never take down the serving process)."""
    eng = _engine(model, num_pages=2)     # pool: 2 pages of 8 tokens
    server = ServingServer(eng, slo=False, flight_recorder=False).start()
    try:
        async def main():
            big = await do(server, "POST", "/v1/completions",
                           completion_body(list(range(1, 41)), 2))
            ok = await do(server, "POST", "/v1/completions",
                          completion_body(list(PROMPTS[0]), 6))
            return big, ok

        big, ok = asyncio.run(main())
        assert big[0] == 413
        assert "pages" in json.loads(big[2])["error"]["message"]
        # the engine survived and still serves fitting requests correctly
        assert ok[0] == 200
        assert json.loads(ok[2])["choices"][0]["token_ids"] == \
            list(oracle[tuple(PROMPTS[0])])
        assert server.engine_alive()
    finally:
        server.close()


def test_healthz_statusz(model):
    server = ServingServer(_engine(model), flight_recorder=False).start()
    try:
        async def main():
            h = await do(server, "GET", "/healthz")
            s = await do(server, "GET", "/statusz")
            return h, s

        (hstatus, _, hbody), (sstatus, _, sbody) = asyncio.run(main())
        assert hstatus == 200 and json.loads(hbody)["status"] == "ok"
        assert sstatus == 200
        doc = json.loads(sbody)
        # engine/pool gauges, jit cache stats, SLO state, build/flag info
        assert doc["engine"]["slots"] == 2
        assert "pages_in_use" in doc["engine"]
        assert "backend_compiles" in doc["jit_cache"]["jit"]
        assert doc["slo"]["quantile"] == flags.flag("serving_slo_quantile")
        assert doc["build"]["jax"] and doc["build"]["pid"] == os.getpid()
        assert doc["flags"]["metrics"] == flags.flag("metrics")
        # ISSUE 10: latency quantiles, hung-request table, per-phase
        # attribution and the sentinel's anomaly section ride statusz
        assert {"count", "p50", "p95", "p99"} <= set(
            doc["latency"]["serving.ttft_ms"])
        assert isinstance(doc["inflight_requests"], list)
        assert doc["attribution"] is not None
        if flags.flag("serving_sentinel"):
            assert "anomalies_total" in doc["anomalies"]
        server.close()
        hstatus2 = asyncio.run(main())[0][0]
        assert hstatus2 == 503                   # engine thread down
    finally:
        server.close()


# ---------------------------------------------------------------------------
# SLO-driven load shedding (synthetic histogram fill -> 503 + counters)
# ---------------------------------------------------------------------------

def test_slo_shed_path_503(model):
    obs.reset("serving.")
    slo = SLOController(ttft_ms=100.0, itl_ms=0.0, quantile=0.95,
                        burn=2.0, min_samples=8, window=64)
    server = ServingServer(_engine(model), slo=slo,
                           flight_recorder=False).start()
    try:
        shed = obs.metrics.counter("serving.http.shed")
        ttft = obs.metrics.histogram("serving.ttft_ms")
        for _ in range(16):
            ttft.observe(5.0)                    # healthy traffic
        status, _, _ = asyncio.run(do(
            server, "POST", "/v1/completions",
            completion_body([1, 2, 3], 2)))
        assert status == 200 and shed.value == 0
        for _ in range(32):
            ttft.observe(5000.0)                 # SLO burning
        s0 = shed.value
        status, headers, body = asyncio.run(do(
            server, "POST", "/v1/completions",
            completion_body([1, 2, 3], 2)))
        assert status == 503
        err = json.loads(body)["error"]
        assert err["type"] == "overloaded_error"
        # Retry-After is derived from the live burn window (ISSUE 7), not
        # a constant: a positive integer, mirrored into the JSON body for
        # header-blind clients, and consistent with the controller's view
        ra = int(headers["retry-after"])
        assert 1 <= ra <= 60
        assert err["retry_after_s"] == ra
        assert shed.value == s0 + 1
        assert obs.metrics.counter("serving.http.slo_decision",
                                   decision="shed").value >= 1
        # /metrics and /healthz never shed
        assert asyncio.run(do(server, "GET", "/metrics"))[0] == 200
        assert asyncio.run(do(server, "GET", "/healthz"))[0] == 200
    finally:
        server.close()


def test_slo_decisions_read_histograms_not_queue_length():
    """Pure controller semantics: burn is computed from histogram deltas
    in the current window; queue/shed thresholds at 1x / burn-x budget."""
    obs.reset("serving.")
    slo = SLOController(ttft_ms=100.0, itl_ms=100.0, quantile=0.9,
                        burn=3.0, min_samples=10, window=100)
    h = obs.metrics.histogram("serving.ttft_ms")
    assert slo.decide(record=False) == "admit"   # cold start admits
    for _ in range(40):
        h.observe(1.0)
    for _ in range(8):
        h.observe(9999.0)                        # 17% > 10% budget: queue
    assert slo.decide(record=False) == "queue"
    for _ in range(40):
        h.observe(9999.0)                        # 55% > 30%: shed
    assert slo.decide(record=False) == "shed"
    # the ITL term burns independently of TTFT health
    obs.reset("serving.")
    slo2 = SLOController(ttft_ms=100.0, itl_ms=100.0, quantile=0.9,
                         burn=3.0, min_samples=10, window=100)
    for _ in range(50):
        obs.metrics.histogram("serving.ttft_ms").observe(1.0)
        obs.metrics.histogram("serving.itl_ms").observe(9999.0)
    assert slo2.decide(record=False) == "shed"


def test_slo_judges_only_traffic_since_it_started(model):
    """The histograms are process-wide.  What they held before the
    controller was built is not its evidence, and neither is the warmup
    request (one compile per observation): a replica that has just
    compiled must admit its first requests."""
    obs.reset("serving.")
    itl = obs.metrics.histogram("serving.itl_ms")
    for _ in range(64):
        itl.observe(9999.0)                  # an earlier engine's stalls
    slo = SLOController(ttft_ms=100.0, itl_ms=100.0, quantile=0.95,
                        burn=2.0, min_samples=8, window=64)
    assert slo.decide(record=False) == "admit"
    assert slo.burn_rates()["itl"]["window_n"] == 0
    for _ in range(16):
        itl.observe(9999.0)
    assert slo.decide(record=False) == "shed"
    slo.forget()
    assert slo.decide(record=False) == "admit"
    # the server forgets what its own warmup observed
    slo = SLOController(ttft_ms=1e-6, itl_ms=1e-6, quantile=0.95,
                        burn=2.0, min_samples=1, window=64)
    server = ServingServer(_engine(model, metrics=True), slo=slo,
                           flight_recorder=False, warmup=True).start()
    try:
        deadline = time.time() + 120
        while not server.ready() and time.time() < deadline:
            time.sleep(0.02)
        assert server.ready()
        assert obs.metrics.histogram("serving.ttft_ms").count >= 1
        terms = slo.burn_rates()
        assert terms["ttft"]["window_n"] == terms["itl"]["window_n"] == 0
        assert slo.decide(record=False) == "admit"
    finally:
        server.close()


def test_slo_sustained_burn_survives_window_rebase():
    """A window rebase carries the completed window forward: sustained
    100%-violation traffic keeps shedding across every rebase boundary
    instead of flapping back to admit for min_samples observations."""
    obs.reset("serving.")
    slo = SLOController(ttft_ms=100.0, itl_ms=0.0, quantile=0.95,
                        burn=2.0, min_samples=16, window=32)
    h = obs.metrics.histogram("serving.ttft_ms")
    for i in range(200):
        h.observe(9999.0)
        if i >= slo.min_samples:
            assert slo.decide(record=False) == "shed", f"flapped at obs {i}"
    # recovery is symmetric: two windows of healthy traffic clear it
    for _ in range(2 * slo.window + 1):
        h.observe(1.0)
        slo.decide(record=False)
    assert slo.decide(record=False) == "admit"


def test_engine_crash_retires_streams_and_rejects_new(model, tmp_path):
    """An exception escaping the engine step must not strand clients:
    in-flight streams get an 'error' finish, the crash dumps the flight
    ring, and new completions 503 instead of entering a dead inbox."""
    fr = obs.FlightRecorder(path=str(tmp_path / "ec.json"),
                            max_events=64, snapshot_every_s=1e9)
    eng = _engine(model)
    server = ServingServer(eng, slo=False, flight_recorder=fr).start()
    try:
        boom = RuntimeError("t6 injected step failure")

        def exploding_step(*a, **k):
            raise boom

        eng.step = exploding_step
        status, _, body = asyncio.run(do(
            server, "POST", "/v1/completions",
            completion_body([1, 2, 3], 4, stream=True)))
        assert status == 200                     # stream opened, then...
        chunks = sse_chunks(body)
        assert chunks[-1]["choices"][0]["finish_reason"] == "error"
        assert fr.last_dump is not None
        assert json.loads(open(fr.last_dump).read())["metadata"][
            "reason"] == "engine-crash-RuntimeError"
        # thread is dead: healthz degrades and new work is refused
        assert not server.engine_alive()
        assert asyncio.run(do(server, "GET", "/healthz"))[0] == 503
        status, _, body = asyncio.run(do(
            server, "POST", "/v1/completions",
            completion_body([1, 2, 3], 4)))
        assert status == 503
        assert "RuntimeError" in json.loads(body)["error"]["message"]
    finally:
        server.close()


# ---------------------------------------------------------------------------
# the PR 5 overhead contract with the HTTP layer on
# ---------------------------------------------------------------------------

def test_http_layer_warm_steps_zero_recompiles(model):
    """Warm traffic through the FULL front door (HTTP parse -> SLO ->
    engine thread -> SSE stream) compiles nothing: the step programs are
    the same two the engine warmed up."""
    obs.reset("serving.")     # earlier tests fill the SLO histograms
    server = ServingServer(_engine(model), slo=None,
                           flight_recorder=False).start()
    try:
        async def one(prompt):
            status, _, body = await do(
                server, "POST", "/v1/completions",
                completion_body(prompt, 6, stream=True))
            assert status == 200
            return [t for c in sse_chunks(body)
                    for t in c["choices"][0]["token_ids"]]

        asyncio.run(one([1, 2, 3, 4, 5]))        # warm both T programs
        with obs.assert_overhead(record=True) as rec:
            async def main():
                return await asyncio.gather(one([6, 7, 8]), one([2, 4]))
            outs = asyncio.run(main())
        assert all(len(o) == 6 for o in outs)
        assert rec.compiles == 0                 # zero recompiles, HTTP on
    finally:
        server.close()


# ---------------------------------------------------------------------------
# crash flight recorder: watchdog-timeout and SIGTERM dump paths
# ---------------------------------------------------------------------------

def _load_chrome_trace(path):
    doc = json.loads(open(path).read())
    assert isinstance(doc["traceEvents"], list)
    assert all("ph" in e for e in doc["traceEvents"])
    return doc


def test_flight_recorder_watchdog_dump_carries_request_ids(model, tmp_path):
    """A watchdog timeout dumps the span ring as a loadable Chrome trace
    whose request track carries the SAME id the HTTP response returned
    (the trace-context acceptance criterion)."""
    from paddle_tpu.distributed.watchdog import CommTaskManager

    obs.reset("serving.")
    fr = obs.FlightRecorder(path=str(tmp_path / "fr.json"),
                            max_events=256, snapshot_every_s=0.5)
    server = ServingServer(_engine(model), slo=False,
                           flight_recorder=fr).start()
    manager = CommTaskManager()
    manager.poll_interval = 0.05
    old = flags.get_flags(["comm_timeout_s"])
    try:
        # ring attached by server.start(): request spans land in it
        status, headers, body = asyncio.run(do(
            server, "POST", "/v1/completions",
            completion_body([1, 2, 3, 4, 5], 4, stream=True)))
        assert status == 200
        rid = headers["x-request-id"]
        # a hung "device step" fires the watchdog -> flight-record dump
        manager.add_timeout_hook(fr._on_watchdog_timeout)
        flags.set_flags({"comm_timeout_s": 0})
        manager.start()
        manager.begin("t6-hung-engine-step")
        deadline = time.time() + 10.0
        while fr.last_dump is None and time.time() < deadline:
            time.sleep(0.05)
        assert fr.last_dump is not None, "watchdog dump never fired"
        doc = _load_chrome_trace(fr.last_dump)
        assert doc["metadata"]["reason"].startswith("watchdog-")
        assert "registry" in doc["metadata"]
        events = doc["traceEvents"]
        # the request's engine lifecycle spans ride a lane NAMED the
        # HTTP response id, args threaded with the same trace id
        lanes = [e["args"]["name"] for e in events if e["ph"] == "M"]
        assert rid in lanes
        spans = [e for e in events
                 if e.get("args", {}).get("trace_id") == rid]
        names = {e["name"] for e in spans}
        assert "http.request" in names           # accept-side span
        assert any(n.endswith(".decode") for n in names)   # engine-side
        # periodic registry snapshots folded into the ring
        assert any(e["name"] == "registry.snapshot" for e in events)
    finally:
        manager.shutdown()
        flags.set_flags(old)
        server.close()


def test_flight_recorder_sigterm_dump(model, tmp_path):
    """SIGTERM dumps the ring then chains to the previous handler."""
    fr = obs.FlightRecorder(path=str(tmp_path / "sig.json"),
                            max_events=64, snapshot_every_s=1e9)
    chained = []
    prev = signal.getsignal(signal.SIGTERM)
    signal.signal(signal.SIGTERM, lambda s, f: chained.append(s))
    try:
        fr.install(watchdog=False, sigterm=True, excepthook=False)
        obs.TRACER.instant("pre-sigterm-marker", tid="t6-lane")
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5.0
        while not chained and time.time() < deadline:
            time.sleep(0.01)
        assert chained == [signal.SIGTERM]       # previous handler ran
        assert fr.last_dump is not None
        doc = _load_chrome_trace(fr.last_dump)
        assert doc["metadata"]["reason"] == "sigterm"
        assert any(e.get("name") == "pre-sigterm-marker"
                   for e in doc["traceEvents"])
    finally:
        fr.uninstall()
        signal.signal(signal.SIGTERM, prev)
    assert not obs.TRACER.enabled                # ring detached


def test_flight_recorder_crash_hook(model, tmp_path):
    """An unhandled exception reaching sys.excepthook dumps the ring."""
    import sys

    fr = obs.FlightRecorder(path=str(tmp_path / "crash.json"),
                            max_events=64, snapshot_every_s=1e9)
    seen = []
    old_hook = sys.excepthook
    sys.excepthook = lambda *a: seen.append(a[0])
    try:
        fr.install(watchdog=False, sigterm=False, excepthook=True)
        try:
            raise RuntimeError("t6 simulated crash")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
        assert seen == [RuntimeError]            # chained
        doc = _load_chrome_trace(fr.last_dump)
        assert doc["metadata"]["reason"] == "crash-RuntimeError"
    finally:
        fr.uninstall()
        sys.excepthook = old_hook


# ---------------------------------------------------------------------------
# graceful drain protocol + Retry-After jitter (ISSUE 12)
# ---------------------------------------------------------------------------

def test_retry_after_jitter_stays_inside_clamp():
    """±20% jitter on every shed-path Retry-After, never outside the
    [1, 60]s clamp (the thundering-herd satellite)."""
    import random

    from paddle_tpu.serving.slo import jittered_retry_after

    seen = set()
    for seed in range(200):
        rng = random.Random(seed)
        for base in (0.2, 1, 7, 30, 59, 60, 400):
            v = jittered_retry_after(base, rng=rng)
            assert 1 <= v <= 60, (base, v)
            if base == 30:
                seen.add(v)
                assert 24 <= v <= 36, v    # ±20% around 30
    assert len(seen) > 3                   # it actually jitters


def test_drain_stops_admission_and_finishes_inflight(model, oracle):
    """begin_drain(): new completions 503 (jittered Retry-After),
    /readyz flips unready, /statusz reports draining — while the
    in-flight stream finishes BIT-IDENTICAL to the oracle."""
    server = ServingServer(_engine(model), slo=False,
                           flight_recorder=False).start()
    try:
        async def main():
            t = asyncio.ensure_future(do(
                server, "POST", "/v1/completions",
                completion_body(list(PROMPTS[0]), 6, stream=True)))
            deadline = time.perf_counter() + 60
            while not server._live:        # stream admitted = in flight
                assert time.perf_counter() < deadline
                await asyncio.sleep(0.005)
            server.begin_drain()
            refused = await do(server, "POST", "/v1/completions",
                               completion_body([1, 2], 2))
            ready = await do(server, "GET", "/readyz")
            statusz = await do(server, "GET", "/statusz")
            return await t, refused, ready, statusz

        (status, headers, body), refused, ready, statusz = \
            asyncio.run(main())
        # the in-flight stream drained out complete, not cut
        assert status == 200
        chunks = sse_chunks(body)
        toks = [t for c in chunks for t in c["choices"][0]["token_ids"]]
        assert toks == oracle[tuple(PROMPTS[0])]
        assert chunks[-1]["choices"][0]["finish_reason"] in ("stop",
                                                            "length")
        # admission closed: 503 + jittered-but-clamped Retry-After
        assert refused[0] == 503
        err = json.loads(refused[2])["error"]
        assert "draining" in err["message"]
        ra = int(refused[1]["retry-after"])
        assert 1 <= ra <= 60 and err["retry_after_s"] == ra
        assert ready[0] == 503             # a router would stop placing
        doc = json.loads(statusz[2])
        assert doc["draining"] is True
        # everything retired: the drain is complete
        deadline = time.perf_counter() + 30
        while not server.drained():
            assert time.perf_counter() < deadline
            time.sleep(0.01)
    finally:
        server.close()


def test_drainz_endpoint(model):
    server = ServingServer(_engine(model), slo=False,
                           flight_recorder=False).start()
    try:
        status, _, body = asyncio.run(do(server, "POST", "/drainz"))
        assert status == 200
        assert json.loads(body)["draining"] is True
        assert asyncio.run(do(server, "GET", "/drainz"))[0] == 405
        assert server.draining
        status, _, _ = asyncio.run(do(
            server, "POST", "/v1/completions",
            completion_body([1, 2, 3], 2)))
        assert status == 503
    finally:
        server.close()


def test_sigterm_drains_active_streams_and_dumps(model, oracle, tmp_path):
    """The ISSUE 12 satellite: SIGTERM during active streams — the
    flight-recorder dump fires (first, then chains into the drain
    handler), every in-flight request finishes bit-identical, and the
    server reaches drained() cleanly."""
    fr = obs.FlightRecorder(path=str(tmp_path / "term.json"),
                            max_events=64, snapshot_every_s=1e9)
    server = ServingServer(_engine(model), slo=False,
                           flight_recorder=fr).start()
    prev = signal.getsignal(signal.SIGTERM)
    try:
        # serve_forever's wiring order: drain handler first, then the
        # flight recorder's dump hook chains to it
        server.install_drain_signal()
        fr.install(watchdog=False, sigterm=True, excepthook=False)

        async def main():
            tasks = [asyncio.ensure_future(do(
                server, "POST", "/v1/completions",
                completion_body(list(p), 6, stream=True)))
                for p in PROMPTS[:2]]
            deadline = time.perf_counter() + 60
            while len(server._live) < 2:   # both genuinely in flight
                assert time.perf_counter() < deadline
                await asyncio.sleep(0.005)
            os.kill(os.getpid(), signal.SIGTERM)
            return await asyncio.gather(*tasks)

        results = asyncio.run(main())
        assert server.draining             # the drain handler ran
        # dump fired BEFORE the chain, reason sigterm
        assert fr.last_dump is not None
        assert _load_chrome_trace(fr.last_dump)["metadata"][
            "reason"] == "sigterm"
        # in-flight requests finished: complete, bit-identical streams
        for (status, headers, body), p in zip(results, PROMPTS[:2]):
            assert status == 200
            chunks = sse_chunks(body)
            toks = [t for c in chunks
                    for t in c["choices"][0]["token_ids"]]
            assert toks == oracle[tuple(p)]
            assert chunks[-1]["choices"][0]["finish_reason"] in (
                "stop", "length")
        deadline = time.perf_counter() + 30
        while not server.drained():
            assert time.perf_counter() < deadline
            time.sleep(0.01)
    finally:
        fr.uninstall()
        signal.signal(signal.SIGTERM, prev)
        server.close()


@pytest.mark.slow
def test_sigterm_drain_real_process(tmp_path):
    """Real-socket variant: a launcher-spawned replica process holding
    an active stream gets SIGTERM — the stream completes ([DONE], no
    error finish) and the process exits 0 (the serve_forever drain
    path), never a mid-stream cut."""
    import http.client
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.serving", "--port", str(port),
         "--max-batch", "2", "--max-seq-len", "256",
         "--prefill-bucket", "16", "--max-new-tokens", "64",
         "--set", "fleet_drain_timeout_s=60"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        deadline = time.time() + 300
        while True:                        # wait out the warmup compile
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=2)
                conn.request("GET", "/readyz")
                if conn.getresponse().status == 200:
                    conn.close()
                    break
                conn.close()
            except OSError:
                pass
            assert time.time() < deadline, "replica never became ready"
            assert proc.poll() is None, "replica died during warmup"
            time.sleep(0.5)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        conn.request("POST", "/v1/completions",
                     completion_body([5, 6, 7, 8], 64, stream=True))
        resp = conn.getresponse()
        assert resp.status == 200
        first = resp.fp.readline()         # head of the event stream out
        assert first is not None
        proc.send_signal(signal.SIGTERM)   # mid-stream
        body = first + resp.read()         # stream runs to completion
        conn.close()
        text = body.decode()
        assert "data: [DONE]" in text
        chunks = sse_chunks(body)
        toks = [t for c in chunks for t in c["choices"][0]["token_ids"]]
        assert len(toks) == 64             # full budget: drained, not cut
        finishes = [c["choices"][0]["finish_reason"] for c in chunks
                    if c["choices"][0]["finish_reason"]]
        assert finishes == ["length"]
        assert proc.wait(timeout=90) == 0  # exit clean
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


# ---------------------------------------------------------------------------
# real socket round trip (slow: binds a port; tier-1 runs -m 'not slow')
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_socket_round_trip(model, oracle):
    import http.client

    server = ServingServer(_engine(model), slo=False,
                           flight_recorder=False)

    async def main():
        host, port = await server.start_http("127.0.0.1", 0)

        def client():
            conn = http.client.HTTPConnection(host, port, timeout=60)
            conn.request("POST", "/v1/completions",
                         completion_body(list(PROMPTS[0]), 6, stream=True))
            resp = conn.getresponse()
            assert resp.status == 200
            body = resp.read()
            conn.close()
            return [t for c in sse_chunks(body)
                    for t in c["choices"][0]["token_ids"]]

        toks = await asyncio.get_running_loop().run_in_executor(
            None, client)
        await server.stop_http()
        return toks

    toks = asyncio.run(main())
    assert toks == oracle[tuple(PROMPTS[0])]


# ---------------------------------------------------------------------------
# queue-expiry shedding (ISSUE 15 satellite)
# ---------------------------------------------------------------------------

def test_queue_expired_request_retired_504_before_dispatch(model):
    """A request still WAITING in the engine inbox past
    FLAGS_serving_queue_timeout_s is retired with 504 before any
    prefill is spent (serving.http.queue_expired counts it); the
    request occupying the slot finishes normally, and an admitted
    request is never expired."""
    obs.reset("serving.http.")
    old = flags.get_flags(["serving_queue_timeout_s"])
    flags.set_flags({"serving_queue_timeout_s": 0.05})
    try:
        # one slot: the first request parks the second in eng.waiting
        server = ServingServer(
            _engine(model, max_batch=1,
                    gen=GenerationConfig(max_new_tokens=24)),
            slo=False, flight_recorder=False).start()
    finally:
        flags.set_flags(old)
    try:
        async def main():
            first = asyncio.ensure_future(do(
                server, "POST", "/v1/completions",
                completion_body(list(PROMPTS[0]), 24)))
            # let the first admit (occupy the only slot)
            deadline = time.perf_counter() + 30
            while not any(r is not None
                          for r in server.engine.slot_req):
                assert time.perf_counter() < deadline
                await asyncio.sleep(0.005)
            second = asyncio.ensure_future(do(
                server, "POST", "/v1/completions",
                completion_body(list(PROMPTS[1]), 4)))
            st2, _, body2 = await second
            st1, _, body1 = await first
            return st1, body1, st2, body2

        st1, body1, st2, body2 = asyncio.run(main())
        # the queued request expired 504 with zero prefill spent
        assert st2 == 504
        doc = json.loads(body2)
        assert doc["error"]["type"] == "timeout_error"
        assert "expired in queue" in doc["error"]["message"]
        # the slot-holder finished normally
        assert st1 == 200
        assert json.loads(body1)["choices"][0]["finish_reason"] in (
            "stop", "length")
        assert int(obs.metrics.counter(
            "serving.http.queue_expired").value) == 1
        # the expired request never touched the engine's books
        assert len(server.engine.waiting) == 0
    finally:
        server.close()


def test_queue_expiry_off_by_default(model):
    """serving_queue_timeout_s defaults to 0 (disabled): queued
    requests wait out admission however long it takes."""
    assert float(flags.flag("serving_queue_timeout_s")) == 0.0
    server = ServingServer(_engine(model, max_batch=1), slo=False,
                           flight_recorder=False).start()
    try:
        async def main():
            a = asyncio.ensure_future(do(
                server, "POST", "/v1/completions",
                completion_body(list(PROMPTS[0]), 6)))
            b = asyncio.ensure_future(do(
                server, "POST", "/v1/completions",
                completion_body(list(PROMPTS[1]), 6)))
            return await a, await b

        (sta, _, _), (stb, _, _) = asyncio.run(main())
        assert sta == 200 and stb == 200
    finally:
        server.close()
