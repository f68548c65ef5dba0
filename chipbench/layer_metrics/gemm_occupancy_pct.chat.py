"""Sum of q_tokens over sum of gemm_rows, over the window's engine.step spans: how full the rows are that the step programs multiply."""
from chipbench.harness import program_spans

LAYER = "scheduler"
UNIT = "%"
MOVES = "itl_p99_ms"
SOURCE = "program_span"
TRACE_ONLY = True


def read(run):
    return program_spans.gemm_occupancy_pct(run)
