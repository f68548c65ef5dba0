"""Mean share of the engine's slots in use per step (registry series serving.batch_occupancy) over the window."""
from chipbench.harness import readers

LAYER = "scheduler"
UNIT = "%"
MOVES = "serve_total_tok_s"
SOURCE = "program_counter"


def read(run):
    return readers.registry_mean(run, "serving.batch_occupancy", 100.0)
