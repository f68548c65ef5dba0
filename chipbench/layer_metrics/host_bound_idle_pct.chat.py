"""Share of the window in which chip 0 is idle and the gap's middle lies in a program span other than serve.idle and engine.drain.wait."""
from chipbench.harness import program_spans

LAYER = "device"
UNIT = "%"
MOVES = "itl_p99_ms"
SOURCE = "program_span"
TRACE_ONLY = True


def read(run):
    return program_spans.host_bound_idle_pct(run)
