"""The state-space scan calls' share of their roofline over the traced window: the calls named ragged_ssd_update, priced from each logged step's query lengths at the recurrence's own 5 x head_dim x state operations a token a head and one read and one write of a working slot's float32 state (kernels/ssd_update.py)."""
from chipbench.harness import readers

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "device_trace"


def read(run):
    """Per-call cost as ``readers.paged_cost_of`` takes it (the mean over
    the steps the host logged while the trace ran, by step program); a
    model without a state-space mixer has no such call and reads nothing."""
    m = run.model
    if "mamba_d_state" not in m:
        return None
    log = run.results.get("step_log") or []
    t0 = run.tracer.t_started
    by_T = {}
    for s in log:
        if t0 <= s["t"] <= t0 + run.tracer.seconds:
            by_T.setdefault(s["T"], []).append(s["rows"])

    def cost_of(mod, shapes):
        T = next((T for T in by_T
                  if -(-max(8, T) // 8) * 8 == shapes["q_rows"]), None)
        if T is None:
            return None
        costs = [mod.cost(rows, shapes["heads"], shapes["head_dim"],
                          shapes["state"], m["mamba_n_groups"])
                 for rows in by_T[T]]
        return (sum(c[0] for c in costs) / len(costs),
                sum(c[1] for c in costs) / len(costs))

    return readers.kernel_roofline_pct(run, "ssd_update", cost_of)
