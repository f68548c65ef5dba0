"""Mean host time of an engine step in the traced window: the engine.step span minus the engine.drain.wait inside it."""
from chipbench.harness import program_spans

LAYER = "scheduler"
UNIT = "ms"
MOVES = "itl_p99_ms"
SOURCE = "program_span"
TRACE_ONLY = True


def read(run):
    return program_spans.host_step_ms(run)
