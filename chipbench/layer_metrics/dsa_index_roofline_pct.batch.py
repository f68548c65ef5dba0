"""The learned index's share of its roofline over the traced window: the device time of scoring AND choosing together (the calls named latent_index_scores and latent_index_select) against the least one layer's scoring and choosing need, priced from each logged step's (query length, context) rows at heads x dim x 2 operations a pair and one read of a slot's index keys (kernels/latent_index.py)."""
from chipbench.harness import readers

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "device_trace"


def read(run):
    """A scores call is priced with its step's whole cost (the mean over
    the steps the host logged while the trace ran, by step program); a
    select call adds its time and no cost of its own.  A model without an
    index has no such call and reads nothing."""
    m = run.model
    if "index_topk" not in m:
        return None
    log = run.results.get("step_log") or []
    t0 = run.tracer.t_started
    by_T = {}
    for s in log:
        if t0 <= s["t"] <= t0 + run.tracer.seconds:
            by_T.setdefault(s["T"], []).append(s["rows"])
    heads, dim, top_k = (m["index_n_heads"], m["index_head_dim"],
                         m["index_topk"])

    def cost_of(mod, shapes):
        if shapes["kind"] == "select":
            return 0.0, 0.0
        # the kernel pads a step's query tokens to whole tiles of 8
        T = next((T for T in by_T if -(-T // 8) * 8 == shapes["tokens"]),
                 None)
        if T is None:
            return None
        costs = [mod.cost(rows, heads, dim, top_k) for rows in by_T[T]]
        return (sum(c[0] for c in costs) / len(costs),
                sum(c[1] for c in costs) / len(costs))

    return readers.kernel_roofline_pct(run, "latent_index", cost_of)
