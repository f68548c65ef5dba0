"""The sliding-attention calls' share of their roofline over the traced window: the paged kernel's calls named ragged_paged_attention_w<window> alone, priced with the window (kernels/paged_attention_sliding.py)."""
from chipbench.harness import readers

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "device_trace"


def read(run):
    """Per-call cost as ``readers.paged_cost_of`` takes it (the mean over
    the steps the host logged while the trace ran, by step program), at
    the call's own window instead of the mean over the layer kinds."""
    log = run.results.get("step_log") or []
    t0 = run.tracer.t_started
    by_T = {}
    for s in log:
        if t0 <= s["t"] <= t0 + run.tracer.seconds:
            by_T.setdefault(s["T"], []).append(s["rows"])
    m = run.model
    group = m["num_attention_heads"] // m["num_key_value_heads"]
    heads = (m["num_attention_heads"], m["num_key_value_heads"],
             m["head_dim"])
    page = int(run.traffic["engine"]["page_size"])

    def cost_of(mod, shapes):
        T = next((T for T in by_T
                  if max(8, T * group) == shapes["q_rows"]), None)
        if T is None:
            return None
        costs = [mod.cost(rows, *heads, window=shapes["window"],
                          page_size=page) for rows in by_T[T]]
        return (sum(c[0] for c in costs) / len(costs),
                sum(c[1] for c in costs) / len(costs))

    return readers.kernel_roofline_pct(run, "paged_attention_sliding",
                                       cost_of)
