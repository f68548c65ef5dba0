"""Device time inside the gated-delta-rule calls (ragged_kda_update, kernels/kda_update.py) over chip 0's busy time in the traced window: how much of the step the linear layers' recurrent state costs."""
from chipbench.harness import trace_reduce as tr
from chipbench.harness.checks import emit
from chipbench.harness.spec import load_module

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "device_trace"


def read(run):
    mod = load_module(run.cell.root, "kernels", "kda_update")
    lo, hi = run.trace_window
    calls = tr.kernel_calls(run.trace, lo, hi, mod.match)
    busy = tr.busy_s(run.trace, lo, hi)[0]
    if not calls or busy <= 0:
        return None
    inside = sum(min(op.end, hi) - op.start for op, _ in calls) / 1e9
    emit(phase="metric_detail", name="kda_share", calls=len(calls),
         inside_s=inside, busy_s=busy)
    return 100.0 * inside / busy
