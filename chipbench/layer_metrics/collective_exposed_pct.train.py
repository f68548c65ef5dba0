"""Share of the traced window in which a collective runs on chip 0 and no other operation does."""
from chipbench.harness import readers

LAYER = "collectives"
UNIT = "%"
MOVES = "train_tok_s_chip"
SOURCE = "device_trace"


def read(run):
    return readers.collective_exposed_pct(run)
