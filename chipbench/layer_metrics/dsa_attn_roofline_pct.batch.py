"""The sparse latent attention calls' share of their roofline over the traced window: the calls named ragged_paged_attention_latent_sparse, priced from each logged step's (query length, context) rows at the SELECTED keys alone, (rank + rope) + rank operations a query-key pair and one read of a selected row a query token (kernels/paged_attention_latent_sparse.py)."""
from chipbench.harness import readers

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "device_trace"


def read(run):
    """Per-call cost as ``readers.kernel_roofline_pct`` takes it (the mean
    over the steps the host logged while the trace ran, by step program);
    a model without an index has no such call and reads nothing."""
    m = run.model
    if "index_topk" not in m:
        return None
    log = run.results.get("step_log") or []
    t0 = run.tracer.t_started
    by_T = {}
    for s in log:
        if t0 <= s["t"] <= t0 + run.tracer.seconds:
            by_T.setdefault(s["T"], []).append(s["rows"])
    heads, top_k = m["num_attention_heads"], m["index_topk"]

    def cost_of(mod, shapes):
        T = next((T for T in by_T
                  if -(-max(8, T * heads) // 8) * 8 == shapes["q_rows"]),
                 None)
        if T is None:
            return None
        costs = [mod.cost(rows, heads, shapes["rank"], shapes["rope"], top_k)
                 for rows in by_T[T]]
        return (sum(c[0] for c in costs) / len(costs),
                sum(c[1] for c in costs) / len(costs))

    return readers.kernel_roofline_pct(run, "paged_attention_latent_sparse",
                                       cost_of)
