"""The arithmetic the per-layer readers share.  Each metric's own file in
``layer_metrics/`` says which of these it is, with its layer, unit and the
end-to-end metric it should move."""

from __future__ import annotations

from chipbench.harness import registry, roofline, trace_reduce as tr
from chipbench.harness.checks import emit
from chipbench.harness.spec import load_module


def client_ttft(run, which: str):
    ttft = run.results.get("client", {}).get("ttft_ms")
    return None if not ttft else ttft[which]


def registry_percentile(run, series: str, q: float):
    d = run.results.get("registry", {}).get(series)
    return None if d is None else registry.percentile(d, q)


def registry_mean(run, series: str, scale: float = 1.0):
    d = run.results.get("registry", {}).get(series)
    mean = None if d is None else registry.mean(d)
    return None if mean is None else scale * mean


def step_device_ms(run):
    """Mean device time of the step programs' runs in the traced window
    (a program run that holds a Pallas kernel is a step)."""
    mods = tr.step_modules(run.trace, *run.trace_window)
    if not mods:
        return None
    emit(phase="metric_detail", name="step_device_ms", steps=len(mods),
         min_ms=min(m.dur for m in mods) / 1e6,
         max_ms=max(m.dur for m in mods) / 1e6)
    return sum(m.dur for m in mods) / len(mods) / 1e6


def device_idle_pct(run):
    lo, hi = run.trace_window
    busy = tr.busy_s(run.trace, lo, hi)
    return 100.0 * (1.0 - (sum(busy) / len(busy)) / ((hi - lo) / 1e9))


def collective_exposed_pct(run):
    if run.cell.chips < 2:
        return None
    lo, hi = run.trace_window
    return 100.0 * tr.exposed_collective_s(run.trace, lo, hi) \
        / ((hi - lo) / 1e9)


def mfu_pct(run):
    """Model FLOP/s utilization from the trace: tokens a step over the mean
    period between the step programs' starts on chip 0, times the
    operations a token needs (6 N + attention, recomputation not counted),
    over chips times the peak."""
    from chipbench.harness.model_math import train_flops_per_token
    import importlib
    mods = tr.step_modules(run.trace, *run.trace_window)
    if len(mods) < 2:
        return None
    period_s = (mods[-1].start - mods[0].start) / (len(mods) - 1) / 1e9
    ref = importlib.import_module(
        "chipbench.references." + run.cell.config["family"])
    m = run.model
    n = ref.count_params(m, m["num_hidden_layers"])["active"]
    tokens = run.results["window"]["tokens_per_step"]
    seq = int(run.traffic["seq_len"])
    per_token = train_flops_per_token(m, n, seq)
    rate = tokens / period_s
    pk = run.peaks()
    least = tokens * per_token["total"] / (run.cell.chips
                                           * pk["bf16_flops_per_s"])
    emit(phase="metric_detail", name="mfu_pct", step_period_s=period_s,
         tokens_per_s=rate, flops_per_token=per_token, active_params=n)
    return roofline.share_pct("mfu_pct", least, period_s, tokens=tokens,
                              flops_per_token=per_token["total"])


def kernel_roofline_pct(run, kernel: str, cost_of):
    """A kernel's share of its roofline: over its calls on chip 0 in the
    traced window, the least time each could take (max of operations over
    peak and bytes over bandwidth, from the kernel's own file) over the
    device time the calls took.  ``cost_of(module, shapes)`` -> (flops,
    bytes) or None for a call it cannot price."""
    mod = load_module(run.cell.root, "kernels", kernel)
    calls = tr.kernel_calls(run.trace, *run.trace_window, mod.match)
    pk = run.peaks()
    least = took = 0.0
    bounds = {"compute": 0, "memory": 0}
    for op, shapes in calls:
        cost = cost_of(mod, shapes)
        if cost is None:
            continue
        t, bound = roofline.min_time_s(cost[0], cost[1], pk)
        least += t
        took += op.dur / 1e9
        bounds[bound] += 1
    if took <= 0:
        return None
    emit(phase="metric_detail", name=kernel + "_roofline", calls=len(calls),
         least_s=least, took_s=took, bound_by=bounds)
    return roofline.share_pct(kernel + "_roofline", least, took,
                              calls=len(calls), bound_by=bounds)


def layer_windows(m: dict) -> list:
    """[(window or None, share of the layers)]: where the model states
    ``layer_types`` and a ``sliding_window``, its sliding-attention layers
    see a window and the others everything, in their published ratio (a
    call of the trace does not say which layer it is)."""
    types, window = m.get("layer_types"), m.get("sliding_window")
    if not types or not window:
        return [(None, 1.0)]
    sliding = sum(1 for t in types if t == "sliding_attention") / len(types)
    return [(w, share) for w, share in ((int(window), sliding),
                                        (None, 1.0 - sliding)) if share > 0]


def paged_cost_of(run):
    """Per-call cost of the paged kernel by step program: the mean over the
    steps the host logged while the trace ran, since a call's work depends
    on each slot's query and context lengths, which shapes do not give;
    over the layer kinds too where some see a window (``layer_windows``)."""
    log = run.results.get("step_log") or []
    t0 = run.tracer.t_started
    inside = [s for s in log if t0 <= s["t"] <= t0 + run.tracer.seconds]
    m = run.model
    group = m["num_attention_heads"] // m["num_key_value_heads"]
    heads = (m["num_attention_heads"], m["num_key_value_heads"],
             m["head_dim"])
    page = int(run.traffic["engine"]["page_size"])
    kinds = [({} if w is None else {"window": w, "page_size": page}, share)
             for w, share in layer_windows(m)]
    by_T = {}
    for s in inside:
        by_T.setdefault(s["T"], []).append(s["rows"])

    def cost_of(mod, shapes):
        rows_q = shapes["q_rows"]
        T = next((T for T in by_T if max(8, T * group) == rows_q), None)
        if T is None:
            return None
        flops = nbytes = 0.0
        for kw, share in kinds:
            costs = [mod.cost(rows, *heads, **kw) for rows in by_T[T]]
            flops += share * sum(c[0] for c in costs) / len(costs)
            nbytes += share * sum(c[1] for c in costs) / len(costs)
        return flops, nbytes

    return cost_of
