"""Unified metrics + tracing runtime (ISSUE 5).

One process-wide registry (``metrics``) and one span tracer (``tracer``)
behind every subsystem's telemetry:

- **serving** — the continuous-batching engine records per-request
  lifecycle spans (enqueue → admission → prefill → first token → per-token
  decode → drain) as TTFT/ITL/queue-wait/batch-occupancy histograms and
  page-pool/prefix-cache gauges (``serving.*``), all stamped at the
  existing drain so the hot loop stays sync-free.
- **training** — ``StepTimer`` (wired into ``PretrainStep.train_step``)
  records step wall time, tokens/s, per-step recompiles and analytic
  grad-comm bytes (``train.*``) from host timestamps only: timing reads
  ride the caller's existing host drain, never a device sync.
- **compile** — the jax.monitoring backend-compile listener lives HERE and
  feeds ``jit.backend_compiles`` / ``jit.backend_compile_ms``;
  ``paddle_tpu.jit.cache_stats()`` and ``assert_no_recompiles`` read the
  same series, so compile telemetry is one system.  The same listener
  hands every trace, lowering, compile and cache-read event to the
  start-up log (``startup.py``), which keeps it for the set-up phase open
  on the event's thread.
- **profiler** — ``paddle_tpu.profiler.RecordEvent`` is a thin frontend
  over this tracer + registry (same public API; ``summary()`` reads the
  registry).

``assert_overhead`` generalizes ``jit.assert_no_recompiles``: it bounds
both XLA backend compiles AND marked host<->device syncs
(``count_sync``) across a block — the warm-step overhead contract of the
serving engine and the train step, telemetry-asserted in tests.
"""

from __future__ import annotations

import time
from typing import Optional

from .. import flags
from . import catalog, collectives, metrics, startup, tracing
from .attribution import StepAttribution
from .collector import (ClockSync, HttpTransport, InprocTransport,
                        SpanExporter, StoreTransport, TraceCollector)
from .flight_recorder import FlightRecorder
from .metrics import (REGISTRY, counter, find, gauge, histogram,
                      prometheus_text, reset, set_help, snapshot)
from .sentinel import Sentinel
from .tracing import TRACER, Tracer

tracer = TRACER

__all__ = ["metrics", "tracing", "catalog", "collectives", "startup",
           "REGISTRY",
           "counter", "gauge", "histogram", "snapshot", "prometheus_text",
           "reset", "find",
           "set_help", "tracer", "Tracer", "TRACER", "FlightRecorder",
           "StepAttribution", "Sentinel",
           "ClockSync", "SpanExporter", "TraceCollector",
           "InprocTransport", "StoreTransport", "HttpTransport",
           "metrics_enabled", "count_sync", "assert_overhead", "StepTimer",
           "export_chrome_trace"]


def metrics_enabled() -> bool:
    """Master switch for hot-path instrumentation (``FLAGS_metrics``)."""
    return bool(flags.flag("metrics"))


def export_chrome_trace(path: str) -> str:
    return TRACER.export_chrome_trace(path)


# ---------------------------------------------------------------------------
# XLA backend-compile telemetry — THE process-wide compile counter.
# Registered once here (paddle_tpu.jit re-exports the series); every
# backend compile in the process increments it, StaticFunction or raw
# jax.jit alike.
# ---------------------------------------------------------------------------

_BACKEND_COMPILES = metrics.counter("jit.backend_compiles")
_COMPILE_MS = metrics.histogram("jit.backend_compile_ms")


def _on_event_duration(name, *args, **kw):
    dur = args[0] if args else kw.get("duration_secs")
    if not isinstance(dur, (int, float)):
        dur = None
    if name == "/jax/core/compile/backend_compile_duration":
        _BACKEND_COMPILES.inc()
        if dur is not None:
            _COMPILE_MS.observe(dur * 1e3)
    if dur is not None:
        # the start-up log keeps what the phase open on this thread traced,
        # lowered, compiled or read from the persistent cache
        startup.LOG.on_jit_event(name, dur)


import jax as _jax  # noqa: E402  (after the registry exists)

_jax.monitoring.register_event_duration_secs_listener(_on_event_duration)


def backend_compiles() -> int:
    """Process-wide XLA backend-compile count so far."""
    return int(_BACKEND_COMPILES.value)


# ---------------------------------------------------------------------------
# marked host<->device syncs
# ---------------------------------------------------------------------------

_SYNCS = metrics.counter("host.device_syncs")


def count_sync(n: int = 1) -> None:
    """Mark an intentional blocking host<->device read (the serving drain,
    the generator's all-done probe).  ``assert_overhead`` bounds the count
    across a block, which is how "zero added device syncs" is asserted
    rather than asserted-by-comment."""
    _SYNCS.inc(n)


class assert_overhead:
    """Context manager bounding the observability overhead contract:
    at most ``max_compiles`` XLA backend compiles and ``max_syncs`` marked
    host<->device syncs inside the block.

    The general form of ``paddle_tpu.jit.assert_no_recompiles`` (which it
    subsumes — both read the same registry series)::

        with observability.assert_overhead(max_compiles=0, max_syncs=0):
            for _ in range(32):
                engine.step()          # warm steps: no compile, no sync

    ``record=True`` never raises; ``.compiles`` / ``.syncs`` hold the
    observed deltas either way.
    """

    def __init__(self, max_compiles: int = 0, max_syncs: int = 0,
                 record: bool = False):
        self.max_compiles = max_compiles
        self.max_syncs = max_syncs
        self.record = record
        self.compiles = 0
        self.syncs = 0

    def __enter__(self):
        self._c0 = _BACKEND_COMPILES.value
        self._s0 = _SYNCS.value
        return self

    def __exit__(self, exc_type, exc, tb):
        self.compiles = _BACKEND_COMPILES.value - self._c0
        self.syncs = _SYNCS.value - self._s0
        if exc_type is None and not self.record:
            if self.compiles > self.max_compiles:
                raise AssertionError(
                    f"{self.compiles} XLA backend compile(s) inside an "
                    f"assert_overhead(max_compiles={self.max_compiles}) "
                    "block — the warm path recompiled")
            if self.syncs > self.max_syncs:
                raise AssertionError(
                    f"{self.syncs} marked device sync(s) inside an "
                    f"assert_overhead(max_syncs={self.max_syncs}) block — "
                    "instrumentation added a host<->device round trip")
        return False


# ---------------------------------------------------------------------------
# train-step telemetry
# ---------------------------------------------------------------------------

class StepTimer:
    """Per-step train telemetry from host timestamps only (zero device
    syncs: the step's arrays stay in flight; wall time is dispatch-to-
    dispatch, which converges to true step time in any steady loop whose
    caller eventually drains).

    Records into the registry under ``<name>.``:

    - ``steps`` (counter), ``step_ms`` (histogram, warm steps only),
      ``tokens_per_sec`` (gauge, from the last warm step),
    - ``recompiles`` (counter: backend compiles attributed per step —
      compile-bearing steps are excluded from ``step_ms`` so the warm
      latency histogram is not polluted by one 30s XLA compile),
    - ``grad_comm_bytes`` (counter: the analytic per-step gradient-sync
      traffic from ``quantized_collectives.bytes_moved``).
    """

    def __init__(self, name: str = "train"):
        self.name = name
        self._steps = metrics.counter(f"{name}.steps")
        self._step_ms = metrics.histogram(f"{name}.step_ms")
        self._tps = metrics.gauge(f"{name}.tokens_per_sec")
        self._recompiles = metrics.counter(f"{name}.recompiles")
        self._comm = metrics.counter(f"{name}.grad_comm_bytes")
        self._last: Optional[float] = None
        self._compiles_seen = _BACKEND_COMPILES.value

    def begin_step(self) -> None:
        """Snapshot the compile counter at step entry, so ``tick`` only
        attributes compiles that happened INSIDE the step (eager work
        between steps — eval probes, checkpointing — stays out of the
        per-step recompile series)."""
        self._compiles_seen = _BACKEND_COMPILES.value

    def tick(self, tokens: int = 0, comm_bytes: int = 0) -> None:
        """Call once per dispatched step, AFTER the dispatch."""
        now = time.perf_counter()
        self._steps.inc()
        c = _BACKEND_COMPILES.value
        fresh = c - self._compiles_seen
        self._compiles_seen = c
        if fresh:
            self._recompiles.inc(fresh)
        if comm_bytes:
            self._comm.inc(comm_bytes)
        if self._last is not None and not fresh:
            dt = now - self._last
            self._step_ms.observe(dt * 1e3)
            if tokens and dt > 0:
                self._tps.set(tokens / dt)
        self._last = now
