"""1 - union of device-operation intervals over the traced window, mean over the chips."""
from chipbench.harness import readers

LAYER = "device"
UNIT = "%"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"


def read(run):
    return readers.device_idle_pct(run)
