"""The gated-delta-rule calls' share of their roofline over the traced window: the calls named ragged_kda_update, priced from each logged step's query lengths at the recurrence's own 7 x key_dim x value_dim operations a token a head and one read and one write of a working slot's float32 state (kernels/kda_update.py)."""
from chipbench.harness import readers

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "device_trace"


def read(run):
    """Per-call cost as ``readers.paged_cost_of`` takes it (the mean over
    the steps the host logged while the trace ran, by step program: a call
    that holds chunks is a mixed step's, one without a decode step's); a
    model without linear-attention layers, or a program without the kernel,
    has no such call and reads nothing."""
    if "linear_attn_config" not in run.model:
        return None
    log = run.results.get("step_log") or []
    t0 = run.tracer.t_started
    by_T = {}
    for s in log:
        if t0 <= s["t"] <= t0 + run.tracer.seconds:
            by_T.setdefault(s["T"], []).append(s["rows"])

    def cost_of(mod, shapes):
        steps = by_T.get(shapes["chunk"])
        if not steps:
            return None
        costs = [mod.cost(rows, shapes["heads"], shapes["key_dim"],
                          shapes["value_dim"]) for rows in steps]
        return (sum(c[0] for c in costs) / len(costs),
                sum(c[1] for c in costs) / len(costs))

    return readers.kernel_roofline_pct(run, "kda_update", cost_of)
