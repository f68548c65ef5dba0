"""The paged-attention kernel's share of its roofline over the traced window (kernels/paged_attention.py)."""
from chipbench.harness import readers

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "device_trace"


def read(run):
    return readers.kernel_roofline_pct(run, "paged_attention", readers.paged_cost_of(run))
