"""Model FLOP/s utilization: tokens/s of the traced steps x (6 N + attention) over chips x peak; recomputation not counted."""
from chipbench.harness import readers

LAYER = "step program"
UNIT = "%"
MOVES = "train_tok_s_chip"
SOURCE = "device_trace"


def read(run):
    return readers.mfu_pct(run)
