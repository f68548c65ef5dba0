"""Sum of q_tokens over sum of slots x T, over the window's engine.step spans: how full the step programs' rows are."""
from chipbench.harness import program_spans

LAYER = "scheduler"
UNIT = "%"
MOVES = "itl_p99_ms"
SOURCE = "program_span"
TRACE_ONLY = True


def read(run):
    return program_spans.token_occupancy_pct(run)
