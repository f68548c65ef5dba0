"""Share of the window the engine thread spends in program spans other than serve.idle, engine.drain.wait, engine.dispatch and the launch waits of engine.h2d (self times)."""
from chipbench.harness import program_spans

LAYER = "scheduler"
UNIT = "%"
MOVES = "itl_p99_ms"
SOURCE = "program_span"
TRACE_ONLY = True


def read(run):
    return program_spans.host_busy_pct(run)
