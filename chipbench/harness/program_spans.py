"""The program's own spans in the profiler's trace, and what the per-layer
readers make of them.

What the trace holds (looked at by hand on a v5e, jax 0.9, PR 24): every
``Tracer.span`` of the program is a ``jax.profiler.TraceAnnotation``, so it
is an event of the ``/host:CPU`` plane on the line of the thread that ran
it (the engine's thread and the main thread both read ``python``), named as
the program's vocabulary names it (``paddle_tpu.observability.catalog.
SPANS``), with its arguments as the event's stats, on the clock of the
device planes.  A jitted program runs on the ``XLA Modules`` line under
``jit_<name>(<fingerprint>)``.

The host does not only wait for the chip in ``engine.drain.wait``: the
runtime holds about four launches in flight, and the call of a further one
(``engine.dispatch``) returns when the oldest has finished, a whole device
step later.  So both count as time the chip holds the host, not the host
the chip.  The eager upload programs of ``engine.h2d`` are launches too and
can meet the same wait (PR 25: with shorter steps the places fill sooner).
The runtime's own events tell that wait from the upload's work: every
launch is a ``CommonPjRtLoadedExecutable::ExecutePrepare`` event of the
host plane (on a line of the runtime's, not the thread's), and what lies
between its start and the start of the ``Acquire semaphore`` event inside
it is the wait for a place (about a microsecond when one is free, a device
step when none is).  ``launch waits`` below are those intervals.  A span
that is open when the profiler starts or stops is not in the trace, so what
happens before the first recorded span and after the last can be charged to
nothing.

A program from before the vocabulary has no such events: every function
here then finds nothing, and every reader returns None.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
from dataclasses import dataclass, field
from types import SimpleNamespace

from chipbench.harness import trace_reduce as tr
from chipbench.harness.checks import emit

HOST_PLANE = "/host:CPU"
STEP = "engine.step"
WAIT = "engine.drain.wait"
DISPATCH = "engine.dispatch"
UPLOAD = "engine.h2d"
PREPARE = "CommonPjRtLoadedExecutable::ExecutePrepare"
ACQUIRE = "Acquire semaphore"
# where the chip holds the host: the drain's transfer, and a launch that
# blocks until the runtime has a place in flight for it
HELD_BY_DEVICE = (WAIT, DISPATCH)
# where the engine thread is not holding the chip back: waiting for work,
# or waiting for the chip itself
NOT_HOST_WORK = ("serve.idle", WAIT)
UNATTRIBUTED = "unattributed"
_STEP_PROGRAM = re.compile(r"^jit_serve_step_T(\d+)\(")


def vocabulary() -> frozenset:
    """The program's span names; empty for a program without the table."""
    try:
        from paddle_tpu.observability.catalog import SPANS
    except ImportError:
        return frozenset()
    return frozenset(SPANS)


@dataclass
class Span:
    name: str
    start: float
    end: float
    stats: dict
    thread: str
    children: list = field(default_factory=list)

    @property
    def dur(self) -> float:
        return self.end - self.start


def read_host(path: str, names=None) -> tuple:
    """(spans, launch waits) of the host plane.  Spans: every event whose
    name is in ``names`` (default: the program's vocabulary), on every
    line, nested by time within its thread, sorted by start.  Launch
    waits: [(start, end)] in ns, sorted, one for each launch the runtime
    prepared: from the start of its ``ExecutePrepare`` event to the start
    of the first ``Acquire semaphore`` event inside it."""
    names = vocabulary() if names is None else frozenset(names)
    if not names:
        return [], []
    from jax.profiler import ProfileData
    spans, waits = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            # a line's name is not its thread's alone (the engine's thread
            # and the main thread both read "python")
            thread = f"{line.name}#{i}"
            mine, launches, acquired = [], [], []
            for e in line.events:
                if e.name in names:
                    mine.append(Span(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns,
                                     dict(e.stats), thread))
                elif e.name == PREPARE:
                    launches.append((e.start_ns, e.start_ns + e.duration_ns))
                elif e.name == ACQUIRE:
                    acquired.append(e.start_ns)
            nest(mine)
            spans += mine
            acquired.sort()
            for a, b in launches:
                i = bisect.bisect_left(acquired, a)
                if i < len(acquired) and acquired[i] < b:
                    waits.append((a, acquired[i]))
    return sorted(spans, key=lambda s: s.start), sorted(waits)


def load(path: str, names=None) -> list:
    """The spans of ``read_host`` alone."""
    return read_host(path, names)[0]


def nest(spans: list) -> None:
    """Give each span of ONE thread its direct children: those that lie
    inside it and inside no shorter span."""
    stack = []
    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and s.start >= stack[-1].end:
            stack.pop()
        if stack:
            stack[-1].children.append(s)
        stack.append(s)


def _clip(s: Span, lo: float, hi: float) -> tuple:
    return max(s.start, lo), min(s.end, hi)


def self_ns(s: Span, lo: float, hi: float) -> float:
    """The part of [lo, hi] the span covers and its children do not."""
    a, b = _clip(s, lo, hi)
    if b <= a:
        return 0.0
    covered = tr.union([_clip(c, a, b) for c in s.children
                        if c.end > a and c.start < b])
    return (b - a) - sum(y - x for x, y in covered)


def within(s: Span, name: str):
    """Every span called ``name`` nested in ``s``, at any depth."""
    for c in s.children:
        if c.name == name:
            yield c
        yield from within(c, name)


def innermost(spans: list, t: float):
    """The shortest span that covers ``t``, or None."""
    cover = [s for s in spans if s.start <= t < s.end]
    return min(cover, key=lambda s: s.dur) if cover else None


def idle_by_span(spans: list, busy: list, lo: float, hi: float) -> dict:
    """{span name: ns}: the gaps between the merged busy intervals of a
    chip inside [lo, hi], cut at every span edge that falls inside one,
    each piece charged to the innermost program span over it
    (``unattributed`` where none is)."""
    cuts = sorted({x for s in spans for x in (s.start, s.end)})
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    total = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        inner = cuts[bisect.bisect_right(cuts, a):bisect.bisect_left(cuts, b)]
        points = [a] + inner + [b]
        for x, y in zip(points, points[1:]):
            s = innermost(spans, (x + y) / 2)
            name = s.name if s is not None else UNATTRIBUTED
            total[name] = total.get(name, 0.0) + (y - x)
    return total


# ---------------------------------------------------------------------------
# what the readers share
# ---------------------------------------------------------------------------

def of(run) -> list:
    """The run's program spans, read once (with its launch waits)."""
    if getattr(run, "program_spans", None) is None:
        run.program_spans, run.launch_waits = read_host(
            tr.find_xplane(run.tracer.dir))
    return run.program_spans


def upload_blocked_ns(run, upload: Span, lo: float, hi: float) -> float:
    """The part of [lo, hi] in which an ``engine.h2d`` span waits for a
    place to launch one of its upload programs: held by the chip, not the
    upload's own work."""
    of(run)
    a, b = _clip(upload, lo, hi)
    return sum(min(y, b) - max(x, a) for x, y in run.launch_waits
               if y > a and x < b)


def steps(run) -> list:
    """The ``engine.step`` spans that dispatched a program and lie wholly
    inside the traced window."""
    lo, hi = run.trace_window
    return [s for s in of(run) if s.name == STEP and s.start >= lo
            and s.end <= hi and int(s.stats.get("T", 0)) > 0]


def _by_kind(found: list) -> dict:
    kinds = {}
    for s in found:
        k = str(s.stats.get("kind"))
        kinds[k] = kinds.get(k, 0) + 1
    return kinds


def host_step_ms(run):
    """Mean over the window's steps of the step's duration minus the time
    the chip held the host inside it: ``HELD_BY_DEVICE`` and the launch
    waits of its uploads."""
    found = steps(run)
    if not found:
        return None
    held = {name: [sum(w.dur for w in within(s, name)) for s in found]
            for name in HELD_BY_DEVICE}
    held[UPLOAD] = [sum(upload_blocked_ns(run, h, s.start, s.end)
                        for h in within(s, UPLOAD)) for s in found]
    host = [s.dur - sum(held[name][i] for name in held)
            for i, s in enumerate(found)]
    emit(phase="metric_detail", name="host_step_ms", steps=len(found),
         steps_by_kind=_by_kind(found), min_ms=min(host) / 1e6,
         max_ms=max(host) / 1e6, wait_ms=sum(held[WAIT]) / 1e6,
         dispatch_ms=sum(held[DISPATCH]) / 1e6,
         dispatch_min_ms=min(held[DISPATCH]) / 1e6,
         h2d_blocked_ms=sum(held[UPLOAD]) / 1e6,
         h2d_blocked_max_ms=max(held[UPLOAD]) / 1e6)
    return sum(host) / len(host) / 1e6


def token_occupancy_pct(run):
    """Query tokens the window's steps held over the rows x T their
    programs computed."""
    found = steps(run)
    real = sum(int(s.stats["q_tokens"]) for s in found)
    room = sum(int(s.stats["slots"]) * int(s.stats["T"]) for s in found)
    if not room:
        return None
    emit(phase="metric_detail", name="token_occupancy_pct", steps=len(found),
         steps_by_kind=_by_kind(found), q_tokens=real, computed_tokens=room)
    return 100.0 * real / room


def gemm_occupancy_pct(run):
    """Query tokens the window's steps held over the rows their step
    programs multiplied (``gemm_rows``: the packed member's row bucket, or
    the grid), where ``token_occupancy_pct`` reads the grid.  A program
    from before the argument has nothing to read."""
    found = [s for s in steps(run) if "gemm_rows" in s.stats]
    real = sum(int(s.stats["q_tokens"]) for s in found)
    rows = sum(int(s.stats["gemm_rows"]) for s in found)
    if not rows:
        return None
    by_rows = {}
    for s in found:
        k = str(s.stats["gemm_rows"])
        by_rows[k] = by_rows.get(k, 0) + 1
    emit(phase="metric_detail", name="gemm_occupancy_pct", steps=len(found),
         steps_by_gemm_rows=by_rows, q_tokens=real, gemm_rows=rows)
    return 100.0 * real / rows


def host_busy_pct(run):
    """Share of the window the engine's thread spends in program spans
    other than waiting for work or held by the chip (self times, the
    uploads' without their launch waits)."""
    lo, hi = run.trace_window
    spans = of(run)
    threads = {s.thread for s in spans if s.name == STEP}
    if not threads:
        return None
    by_name, blocked = {}, 0.0
    for s in spans:
        if s.thread in threads:
            by_name[s.name] = by_name.get(s.name, 0.0) + self_ns(s, lo, hi)
            if s.name == UPLOAD:
                blocked += upload_blocked_ns(run, s, lo, hi)
    busy = sum(v for k, v in by_name.items()
               if k != "serve.idle" and k not in HELD_BY_DEVICE) - blocked
    emit(phase="metric_detail", name="host_busy_pct",
         self_ms_by_span={k: v / 1e6 for k, v in sorted(
             by_name.items(), key=lambda kv: -kv[1])},
         h2d_blocked_ms=blocked / 1e6, threads=sorted(threads))
    return 100.0 * busy / (hi - lo)


def host_bound_idle_pct(run):
    """Share of the window in which chip 0 is idle while the host is at
    work in a program span (not waiting for work, not in the drain's
    transfer).  Charged between the first recorded span's start and the
    last one's end; the window's idle outside that is reported apart."""
    lo, hi = run.trace_window
    spans = of(run)
    if not spans or not run.trace.ops:      # a CPU trace has no device plane
        return None
    ops = run.trace.ops[0]
    busy_ns = sum(b - a for a, b in tr.union(tr.clipped(ops, lo, hi)))
    whole = (hi - lo) - busy_ns
    first = max(lo, spans[0].start)
    last = min(hi, max(s.end for s in spans))
    idle = idle_by_span(spans, tr.union(tr.clipped(ops, first, last)),
                        first, last)
    total = sum(idle.values())
    emit(phase="metric_detail", name="host_bound_idle_pct",
         idle_ms_by_span={k: v / 1e6 for k, v in sorted(
             idle.items(), key=lambda kv: -kv[1])},
         idle_ms=whole / 1e6,
         idle_ms_outside_recorded_spans=(whole - total) / 1e6,
         attributed_share=1.0 - idle.get(UNATTRIBUTED, 0.0) / total
         if total else None)
    bound = sum(v for k, v in idle.items()
                if k != UNATTRIBUTED and k not in NOT_HOST_WORK)
    return 100.0 * bound / (hi - lo)


def step_program_ms(run, mixed: bool):
    """Mean device time of the runs of ``serve_step_T<bucket>`` on chip 0
    that lie wholly inside the window (the run in flight when the profiler
    stops is cut short): buckets over 1 (``mixed``) or bucket 1."""
    lo, hi = run.trace_window
    runs = {}
    for mod in run.trace.modules[0] if run.trace.modules else ():
        m = _STEP_PROGRAM.match(mod.name)
        if m and lo <= mod.start and mod.end <= hi:
            runs.setdefault(int(m.group(1)), []).append(mod.dur)
    mine = [d for T, durs in runs.items() if (T > 1) == mixed for d in durs]
    if not mine:
        return None
    emit(phase="metric_detail",
         name="step_device_ms_" + ("mixed" if mixed else "decode"),
         runs_by_program={f"serve_step_T{T}": len(d)
                          for T, d in sorted(runs.items())},
         min_ms=min(mine) / 1e6, max_ms=max(mine) / 1e6)
    return sum(mine) / len(mine) / 1e6


def unknown_modules(run) -> int:
    """Program runs on chip 0 in the window that have no name."""
    lo, hi = run.trace_window
    return sum(1 for m in (run.trace.modules[0] if run.trace.modules else ())
               if lo <= m.start < hi and "_unknown" in m.name)


# ---------------------------------------------------------------------------
# the readers over a trace that is already on disk
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    """``python -m chipbench.harness.program_spans <trace dir>``: every
    reader in ``layer_metrics/`` that needs nothing but the trace
    (``TRACE_ONLY = True`` in its file), over the trace a ``--trace 1`` run
    left under ``.chipbench_out/<cell>/trace``.  One JSON line a metric."""
    from chipbench.harness import spec
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(main.__doc__, file=sys.stderr)
        return 2
    run = SimpleNamespace(tracer=SimpleNamespace(dir=argv[0]),
                          program_spans=None)
    run.trace = tr.load(tr.find_xplane(argv[0]))
    run.trace_window = tr.window(run.trace)
    emit(phase="trace", window_s=(run.trace_window[1]
                                  - run.trace_window[0]) / 1e9,
         program_spans=len(of(run)), unknown_modules=unknown_modules(run))
    for path in sorted(glob.glob(os.path.join(
            spec.BENCH_DIR, "layer_metrics", "*.py"))):
        name = os.path.basename(path)[:-3]
        reader = spec.load_module(spec.ROOT, "layer_metrics", name)
        if getattr(reader, "TRACE_ONLY", False):
            print(json.dumps({"metric": name, "value": reader.read(run),
                              "unit": reader.UNIT}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
