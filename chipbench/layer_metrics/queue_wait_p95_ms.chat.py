"""95th percentile of the engine's queue wait (registry histogram serving.queue_wait_ms) over the window."""
from chipbench.harness import readers

LAYER = "scheduler"
UNIT = "ms"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"


def read(run):
    return readers.registry_percentile(run, "serving.queue_wait_ms", 0.95)
