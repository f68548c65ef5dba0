"""Mean host time of an engine step in the traced window: the engine.step span minus what the chip holds of it (engine.drain.wait, engine.dispatch, the launch waits of engine.h2d)."""
from chipbench.harness import program_spans

LAYER = "scheduler"
UNIT = "ms"
MOVES = "serve_total_tok_s"
SOURCE = "program_span"
TRACE_ONLY = True


def read(run):
    return program_spans.host_step_ms(run)
