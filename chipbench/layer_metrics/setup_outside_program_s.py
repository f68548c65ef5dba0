"""Seconds of set-up under no phase of the program: setup_s minus the union of its start-up records before ready (the interpreter, jax, the backend's start, the benchmark's seeded weights and schedule)."""
from chipbench.harness import startup

LAYER = "start-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return startup.share(run, "outside_program")
