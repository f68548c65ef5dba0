"""The grouped-matmul kernel's share of its roofline over the traced window (kernels/grouped_matmul.py)."""
from chipbench.harness import readers

LAYER = "kernels"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "device_trace"


def read(run):
    return readers.kernel_roofline_pct(run, "grouped_matmul", lambda mod, shapes: mod.cost(shapes))
