"""Mean device time of the runs of serve_step_T<bucket> with a bucket over 1 (steps that hold a prefill chunk) in the window."""
from chipbench.harness import program_spans

LAYER = "step program"
UNIT = "ms"
MOVES = "itl_p99_ms"
SOURCE = "device_trace"
TRACE_ONLY = True


def read(run):
    return program_spans.step_program_ms(run, mixed=True)
