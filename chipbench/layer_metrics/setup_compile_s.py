"""Seconds of set-up in the backend's compile or the persistent cache's read, and the load: the startup.compile records before ready; of a program compiled by its first call, the backend's compile seconds."""
from chipbench.harness import startup

LAYER = "start-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return startup.share(run, "compile")
