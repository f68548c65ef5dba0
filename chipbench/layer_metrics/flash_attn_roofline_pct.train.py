"""The flash-attention kernels' (forward, dQ, dK/dV) share of their roofline over the traced window (kernels/flash_attention.py)."""
from chipbench.harness import readers

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tok_s_chip"
SOURCE = "device_trace"


def read(run):
    return readers.kernel_roofline_pct(run, "flash_attention", lambda mod, shapes: mod.cost(shapes))
