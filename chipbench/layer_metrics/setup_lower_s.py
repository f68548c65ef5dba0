"""Seconds of set-up tracing and lowering step programs (Python, paid on every start whatever the cache holds): the startup.lower records before ready; of a program compiled by its first call, its span minus the backend's compile seconds."""
from chipbench.harness import startup

LAYER = "start-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return startup.share(run, "lower")
