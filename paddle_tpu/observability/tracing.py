"""Span tracer: one span API, two sinks.

``Tracer.span(name, **args)`` is the one way a live span is recorded.  It
always enters a ``jax.profiler.TraceAnnotation``, which records only while
a profiler session runs: whoever runs the JAX profiler (a benchmark's
traced run, an operator, ``profiler.Profiler``) finds the program's spans
in the same ``.xplane.pb`` as the device operations, on one clock, nested
by time on the thread that ran them.  When the tracer's own sinks are on
(``start``, a flight-recorder ring, a fleet export sink) the span is also
appended, as a Chrome "X" event, to the in-memory buffer exported as the
``traceEvents`` JSON that chrome://tracing and https://ui.perfetto.dev
load.  Retroactive events (per-request serving lifecycles, stamped at the
drain from saved timestamps) reach the Chrome sinks only: the profiler's
trace takes no event after the fact.

Live span names are a closed vocabulary: ``catalog.SPANS`` (generated into
``docs/metrics.md``) gives each its lane, its arguments, and whether the
fleet exporter ships it.

With no profiler session and the sinks off (the default) a span costs one
``TraceAnnotation`` (under a microsecond) and nothing allocates in the
tracer.  With the sinks on each span is one buffer append more; the buffer
is capped (``FLAGS_trace_max_events``) and the overflow count is reported
in the exported file's metadata rather than silently dropped.
"""

from __future__ import annotations

import functools
import json
import os
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from .. import flags
from . import metrics as _metrics
from .catalog import SPANS

__all__ = ["Tracer", "TRACER", "device_tracing_available", "named"]

# process-wide visibility for FLAGS_trace_max_events overflow (ISSUE 6
# satellite): dropping a span is telemetry too — a flat buffer cap no
# longer hides a tracer that stopped recording mid-run
_DROPPED_EVENTS = _metrics.counter("tracing.dropped_events")


def device_tracing_available() -> bool:
    """True when a jax device trace may start: the backend is not CPU.
    The env probe short-circuits before any backend initialization, so
    the CPU tier-1 suite (JAX_PLATFORMS=cpu) never pays for — or
    pollutes — a device-trace attempt (``profiler.Profiler``'s guard)."""
    if os.environ.get("JAX_PLATFORMS", "").strip() == "cpu":
        return False
    try:
        import jax
        return jax.default_backend() != "cpu"
    except Exception:
        return False


# a name outside the vocabulary (tests, ad-hoc spans) rides the calling
# thread's lane and goes as far as any event does
_UNLISTED = ("host", None, "fleet")


def named(fn, name: str):
    """``fn`` under ``name`` for ``jax.jit``: a profiler trace's ``XLA
    Modules`` line (and the compile log) then reads ``jit_<name>``.  A
    ``functools.partial`` or a ``shard_map`` wrapper has no name of its
    own and would read ``jit__unknown``."""
    fn = functools.partial(fn)
    fn.__name__ = name
    return fn


class _SinkSpan(TraceAnnotation):
    """A live span while the tracer's own sinks are on: the profiler's
    annotation, and on exit one Chrome "X" event.  ``set_metadata`` adds
    the counts that are known only once the span is under way, to both."""

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        super().__init__(name, **args)
        self._tracer = tracer
        self._name = name
        self._args = args
        self._t0 = 0.0

    def __enter__(self):
        super().__enter__()
        self._t0 = time.perf_counter()
        return self

    def set_metadata(self, **args) -> None:
        super().set_metadata(**args)
        self._args.update(args)

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self._t0
        super().__exit__(exc_type, exc, tb)
        self._tracer._live(self._name, self._t0, dur, self._args)
        return False


class Tracer:
    """Chrome-trace event buffer.  All timestamps ride
    ``time.perf_counter()`` (µs in the export), so retroactive events can
    be stamped from any saved ``perf_counter`` reading."""

    def __init__(self, max_events: Optional[int] = None):
        self._events: List[dict] = []
        self._enabled = False
        self._active = False
        self._max = max_events
        self._ring = None           # flight-recorder sink (bounded deque)
        self._export = None         # span-export sink (collector shipping)
        self.dropped = 0
        self._tids: Dict[str, int] = {}
        self._lock = threading.Lock()

    @property
    def enabled(self) -> bool:
        """True when spans are being recorded anywhere — the flat export
        buffer (``start``) OR an attached flight-recorder ring.  Every
        instrumentation site gates on this one attribute."""
        return self._active

    def listening(self) -> bool:
        """True when a span's arguments reach anybody: the sinks are on, or
        a profiler session runs.  A site whose arguments cost a loop to
        compute asks first."""
        return self._active or TraceAnnotation.is_enabled()

    # -------------------------------------------------------- lifecycle --
    def start(self, clear: bool = True) -> "Tracer":
        if clear:
            self._events = []
            self.dropped = 0
            self._tids = {}
        self._enabled = True
        self._active = True
        return self

    def stop(self) -> "Tracer":
        self._enabled = False
        self._active = self._ring is not None or self._export is not None
        return self

    def attach_ring(self, ring) -> None:
        """Attach a bounded ``deque(maxlen=...)`` that receives EVERY
        event from now on (even with the flat buffer stopped) — the crash
        flight recorder's always-on last-N-spans window.  The deque's
        maxlen is the bound; eviction is free."""
        self._ring = ring
        self._active = True

    def detach_ring(self) -> None:
        self._ring = None
        self._active = self._enabled or self._export is not None

    def attach_export(self, sink) -> None:
        """Attach a span-export sink (``SpanExporter.offer``-shaped: any
        object with a non-blocking ``offer(ev)``) that receives every
        event from now on — the fleet-tracing shipping lane (ISSUE 20).
        Like the flight-recorder ring, attachment alone activates span
        recording; the sink must be a bounded buffer, never a network
        call (``offer`` runs on the engine/event-loop threads)."""
        self._export = sink
        self._active = True

    def detach_export(self) -> None:
        self._export = None
        self._active = self._enabled or self._ring is not None

    # a serving process mints one lane per request trace-id: the name->tid
    # map must be bounded or it (and thread_metadata()) grows forever.
    # Past the cap, lanes get a stable hashed tid with no stored metadata
    # (numeric lanes in the viewer — degraded naming, bounded memory).
    MAX_NAMED_LANES = 8192

    # ------------------------------------------------------------ events --
    def _tid(self, tid) -> int:
        """Map a logical lane name ("slot3", "train") to a stable integer
        tid, emitting the thread_name metadata event on first use."""
        if tid is None:
            return threading.get_ident() & 0x7FFFFFFF
        if isinstance(tid, int):
            return tid
        n = self._tids.get(tid)
        if n is None:
            with self._lock:
                n = self._tids.get(tid)
                if n is None:
                    if len(self._tids) >= self.MAX_NAMED_LANES:
                        # stable but unnamed; offset clear of stored tids
                        return (hash(tid) & 0x3FFFFFFF) \
                            + self.MAX_NAMED_LANES + 1
                    n = len(self._tids) + 1
                    self._tids[tid] = n
                    self._append({"ph": "M", "pid": 0, "tid": n,
                                  "name": "thread_name",
                                  "args": {"name": tid}})
        return n

    def lane_names(self) -> Dict[int, str]:
        """Snapshot of the integer-tid -> lane-name map (request trace ids,
        "train", ...).  Span-export batches carry this so the collector can
        recover trace ids from the compact integer tids."""
        with self._lock:
            return {n: name for name, n in self._tids.items()}

    def thread_metadata(self) -> List[dict]:
        """Fresh thread_name metadata events for every known lane — the
        flight recorder prepends these to a ring dump, where the original
        metadata events may have been evicted."""
        return [{"ph": "M", "pid": 0, "tid": n, "name": "thread_name",
                 "args": {"name": name}}
                for name, n in sorted(self._tids.items(), key=lambda x: x[1])]

    def _append(self, ev: dict, export: bool = True) -> None:
        ring = self._ring
        if ring is not None:
            ring.append(ev)         # deque(maxlen): bounded, oldest out
        exp = self._export
        if exp is not None and export:
            exp.offer(ev)           # bounded ring append, never blocks
        if not self._enabled:
            return
        cap = self._max
        if cap is None:
            cap = int(flags.flag("trace_max_events"))
        if cap and len(self._events) >= cap:
            self.dropped += 1
            _DROPPED_EVENTS.inc()
            return
        self._events.append(ev)

    def event(self, name: str, t0: float, dur: float, *, cat: str = "host",
              tid=None, args: Optional[dict] = None,
              export: bool = True) -> None:
        """Retroactive complete ("X") event: ``t0``/``dur`` in seconds on
        the perf_counter clock (the serving drain stamps request phases
        from timestamps it recorded at dispatch time).  Chrome sinks only;
        ``export=False`` keeps it from the fleet export sink."""
        if not self._active:
            return
        ev = {"ph": "X", "name": name, "cat": cat, "pid": 0,
              "tid": self._tid(tid), "ts": t0 * 1e6,
              "dur": max(dur, 0.0) * 1e6}
        if args:
            ev["args"] = args
        self._append(ev, export)

    def span(self, name: str, **args) -> TraceAnnotation:
        """Context-managed live span around host work; ``args`` are the
        counts measured at its boundary (``set_metadata(**more)`` on the
        returned span adds those known only inside it).  Always in the
        profiler's trace while a session runs; in the Chrome sinks when
        they are on, as far as ``catalog.SPANS`` lets its name go."""
        if not self._active or SPANS.get(name, _UNLISTED)[2] == "profiler":
            return TraceAnnotation(name, **args)
        return _SinkSpan(self, name, args)

    def _live(self, name: str, t0: float, dur: float, args: dict) -> None:
        """A closed live span into the Chrome sinks, on the lane and under
        the category ``catalog.SPANS`` gives its name."""
        cat, lane, sinks = SPANS.get(name, _UNLISTED)[:3]
        self.event(name, t0, dur, cat=cat, tid=lane, args=args,
                   export=sinks == "fleet")

    def instant(self, name: str, *, cat: str = "host", tid=None,
                args: Optional[dict] = None) -> None:
        if not self._active:
            return
        ev = {"ph": "i", "s": "t", "name": name, "cat": cat, "pid": 0,
              "tid": self._tid(tid), "ts": time.perf_counter() * 1e6}
        if args:
            ev["args"] = args
        self._append(ev)

    # ------------------------------------------------------------ export --
    def export_chrome_trace(self, path: str) -> str:
        """Write the buffered events as Chrome-trace JSON; returns path."""
        doc = {"traceEvents": list(self._events),
               "displayTimeUnit": "ms",
               "metadata": {"producer": "paddle_tpu.observability",
                            "dropped_events": self.dropped}}
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


# the process-wide tracer every subsystem emits into
TRACER = Tracer()
