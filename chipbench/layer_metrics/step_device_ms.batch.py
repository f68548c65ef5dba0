"""Mean device time of one run of the step program in the traced window."""
from chipbench.harness import readers

LAYER = "step program"
UNIT = "ms"
MOVES = "serve_total_tok_s"
SOURCE = "device_trace"


def read(run):
    return readers.step_device_ms(run)
