"""Device time inside the learned sparse attention's calls (latent_index_scores, latent_index_select and ragged_paged_attention_latent_sparse: kernels/latent_index.py, kernels/paged_attention_latent_sparse.py) over chip 0's busy time in the traced window: how much of the step the mechanism is."""
from chipbench.harness import trace_reduce as tr
from chipbench.harness.checks import emit
from chipbench.harness.spec import load_module

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "device_trace"


def read(run):
    lo, hi = run.trace_window
    busy = tr.busy_s(run.trace, lo, hi)[0]
    inside, calls = 0.0, {}
    for name in ("latent_index", "paged_attention_latent_sparse"):
        mod = load_module(run.cell.root, "kernels", name)
        found = tr.kernel_calls(run.trace, lo, hi, mod.match)
        calls[name] = len(found)
        inside += sum(min(op.end, hi) - op.start for op, _ in found) / 1e9
    if not inside or busy <= 0:
        return None
    emit(phase="metric_detail", name="dsa_share", calls=calls,
         inside_s=inside, busy_s=busy)
    return 100.0 * inside / busy
