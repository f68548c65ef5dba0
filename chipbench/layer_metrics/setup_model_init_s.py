"""Seconds of set-up inside a model's constructor (the random initialisation that seeded or loaded weights replace): the startup.model_init records before ready."""
from chipbench.harness import startup

LAYER = "start-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return startup.share(run, "model_init")
