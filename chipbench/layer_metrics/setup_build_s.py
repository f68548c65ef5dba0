"""Seconds of set-up building the engine, the train step and the server's warm-up, without the programs inside them: startup.engine_build, startup.train_build and startup.warm before ready, minus their startup.program spans."""
from chipbench.harness import startup

LAYER = "start-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return startup.share(run, "build")
