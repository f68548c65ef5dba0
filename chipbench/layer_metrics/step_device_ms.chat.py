"""Mean device time of one run of the step program in the traced window."""
from chipbench.harness import readers

LAYER = "step program"
UNIT = "ms"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"


def read(run):
    return readers.step_device_ms(run)
