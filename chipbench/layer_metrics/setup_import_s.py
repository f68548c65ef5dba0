"""Seconds of set-up inside the program's import: the startup.import record of the program's start-up log, before ready."""
from chipbench.harness import startup

LAYER = "start-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return startup.share(run, "import")
