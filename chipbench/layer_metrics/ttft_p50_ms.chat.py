"""Median time to first token, at the client, from when the request was due."""
from chipbench.harness import readers

LAYER = "entry"
UNIT = "ms"
MOVES = "itl_p99_ms"
SOURCE = "host_clock"


def read(run):
    return readers.client_ttft(run, "p50")
