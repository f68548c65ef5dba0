"""A traced run's result: reduce the trace, then ask each per-layer
metric's own reader (``layer_metrics/<name>.py``) for its number.  A reader
that finds nothing to read returns None and its metric is left out."""

from __future__ import annotations

from chipbench.harness import spec, trace_reduce as tr
from chipbench.harness.checks import emit


def per_layer(run) -> tuple:
    """(metrics, breakdown, the device's busy_s and window_s)."""
    run.trace = tr.load(tr.find_xplane(run.tracer.dir))
    if not run.trace.ops:
        if run.rehearse:        # a CPU trace has no device plane
            emit(phase="trace", why="rehearsal: no device plane to reduce",
                 bench_spans=len(run.trace.spans))
            return {}, None, {}
        raise RuntimeError("the trace holds no /device:TPU plane")
    lo, hi = tr.window(run.trace)
    run.trace_window = (lo, hi)
    busy = tr.busy_s(run.trace, lo, hi)
    window_s = (hi - lo) / 1e9
    extra = {"busy_s": sum(busy) / len(busy), "window_s": window_s}
    emit(phase="trace", window_s=window_s, busy_s_per_chip=busy,
         chips_traced=len(busy),
         device_ops=sum(len(c) for c in run.trace.ops),
         bench_spans=len(run.trace.spans))
    if extra["busy_s"] <= 0:
        raise RuntimeError("no operation ran on the device in the traced "
                           "window")
    breakdown = {"device_ops": tr.top_ops(run.trace, lo, hi),
                 "idle_gaps": tr.idle_gaps(run.trace, lo, hi)}
    metrics = {}
    for entry in run.cell.per_layer:
        reader = spec.load_module(run.cell.root, "layer_metrics",
                                  entry["name"])
        value = reader.read(run)
        if value is None:
            emit(phase="metric", name=entry["name"], value=None,
                 why="nothing to read in this run")
            continue
        metrics[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return metrics, breakdown, extra
