"""From the profiler's ``.xplane.pb`` to numbers.  Reads with nothing but
``jax.profiler.ProfileData``.

What the trace holds (looked at by hand on a v5e, jax 0.9): a plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event per
run of a jitted program) and ``XLA Ops`` (one event per device operation,
named by its whole HLO line; a ``while`` holds its body's operations inside
its own interval, so busy time is a UNION of intervals, never a sum); and a
plane ``/host:CPU`` whose line ``python`` holds the benchmark's own
``jax.profiler.TraceAnnotation`` spans (``bench.*``).  Times are nanoseconds
on one clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

CONTAINERS = ("while", "conditional", "call")
WINDOW_SPAN = "bench.trace_window"
_HEAD = re.compile(r"^%?(\S+) = (.*?) ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")


@dataclass
class Op:
    text: str
    start: float
    dur: float
    name: str = ""
    opcode: str = ""
    out_shapes: list = field(default_factory=list)      # [(dtype, dims)]
    operand_shapes: list = field(default_factory=list)

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def is_kernel(self) -> bool:
        return "tpu_custom_call" in self.text

    def short(self) -> str:
        dt, dims = self.out_shapes[0] if self.out_shapes else ("", ())
        return "%s %s %s[%s]" % (self.opcode, self.name, dt,
                                 ",".join(map(str, dims)))


def parse_op(text: str, start: float, dur: float) -> Op:
    op = Op(text, start, dur)
    m = _HEAD.match(text)
    if not m:
        op.name = text.split(" ", 1)[0].lstrip("%")
        return op
    op.name, typ, op.opcode = m.group(1), m.group(2), m.group(3)
    shapes = lambda s: [(d, tuple(int(x) for x in dims.split(",") if x))  # noqa: E731
                        for d, dims in _SHAPE.findall(s)]
    op.out_shapes = shapes(typ)
    rest = text[m.end():]
    cut = rest.find("custom_call_target")
    op.operand_shapes = shapes(rest if cut < 0 else rest[:cut])
    return op


@dataclass
class Span:
    name: str
    start: float
    dur: float
    stats: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    ops: list          # per chip: [Op] of the XLA Ops line, by start
    modules: list      # per chip: [Span] of the XLA Modules line
    spans: list        # the benchmark's own host spans (bench.*)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip_ops, chip_mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chip_ops = sorted(
                        (parse_op(e.name, e.start_ns, e.duration_ns)
                         for e in line.events), key=lambda o: o.start)
                elif line.name == "XLA Modules":
                    chip_mods = [Span(e.name, e.start_ns, e.duration_ns, {})
                                 for e in line.events]
            ops.append(chip_ops)
            modules.append(chip_mods)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Span(e.name, e.start_ns, e.duration_ns,
                                          dict(e.stats)))
    used = [i for i, o in enumerate(ops) if o]
    return Trace([ops[i] for i in used], [modules[i] for i in used],
                 sorted(spans, key=lambda s: s.start))


def window(trace: Trace, span: str = WINDOW_SPAN) -> tuple:
    """(start, end) of the traced window: the benchmark's own span."""
    for s in trace.spans:
        if s.name == span:
            return s.start, s.end
    raise ValueError(f"the trace holds no {span} span")


def union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clipped(ops: list, lo: float, hi: float) -> list:
    return [(max(o.start, lo), min(o.end, hi)) for o in ops
            if o.end > lo and o.start < hi and o.dur > 0]


def busy_s(trace: Trace, lo: float, hi: float) -> list:
    """Per chip: seconds of [lo, hi] in which an operation ran."""
    return [sum(b - a for a, b in union(clipped(chip, lo, hi))) / 1e9
            for chip in trace.ops]


def leaf_ops(ops: list, lo: float, hi: float) -> list:
    """Operations that start in the window and are not containers."""
    return [o for o in ops if lo <= o.start < hi
            and o.opcode not in CONTAINERS]


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[short name, seconds]] of chip 0's costliest operations."""
    total = {}
    for o in leaf_ops(trace.ops[0], lo, hi):
        key = o.short()
        total[key] = total.get(key, 0.0) + o.dur / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[what the host was doing, seconds]]: chip 0's idle gaps inside the
    window, each charged to the innermost ``bench.*`` span that covers its
    middle (``host:no_bench_span`` where none does)."""
    busy = union(clipped(trace.ops[0], lo, hi))
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    spans = [s for s in trace.spans if s.name != WINDOW_SPAN]
    total = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        cover = [s for s in spans if s.start <= mid < s.end]
        name = min(cover, key=lambda s: s.dur).name if cover \
            else "host:no_bench_span"
        total[name] = total.get(name, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def kernel_calls(trace: Trace, lo: float, hi: float, match) -> list:
    """[(Op, shapes)] of chip 0's operations in the window that a kernel
    file's ``match`` recognises."""
    out = []
    for o in leaf_ops(trace.ops[0], lo, hi):
        if o.is_kernel:
            shapes = match(o)
            if shapes is not None:
                out.append((o, shapes))
    return out


def step_modules(trace: Trace, lo: float, hi: float) -> list:
    """Chip 0's program runs in the window that hold a Pallas kernel: the
    step programs (a program's name is not stable; what it runs is)."""
    kernels = [o.start for o in trace.ops[0] if o.is_kernel]
    out = []
    for mod in trace.modules[0]:
        if not lo <= mod.start < hi:
            continue
        i = bisect.bisect_left(kernels, mod.start)
        if i < len(kernels) and kernels[i] < mod.end:
            out.append(mod)
    return out


def exposed_collective_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds of chip 0's window in which a collective ran and no other
    operation did."""
    coll = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
            "collective-permute")
    ops = leaf_ops(trace.ops[0], lo, hi)
    is_coll = lambda o: o.opcode.startswith(coll)        # noqa: E731
    c = union([(o.start, min(o.end, hi)) for o in ops if is_coll(o)])
    rest = union([(o.start, min(o.end, hi)) for o in ops
                  if not is_coll(o) and o.dur > 0])
    exposed = 0.0
    for a, b in c:
        covered = 0.0
        for x, y in rest:
            if y <= a:
                continue
            if x >= b:
                break
            covered += min(b, y) - max(a, x)
        exposed += (b - a) - covered
    return exposed / 1e9
