"""Programs the backend compiled during set-up because the persistent cache did not hold them: startup.program records before ready whose cache_hit is false; 0 on a warm start."""
from chipbench.harness import startup

LAYER = "start-up"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    programs = startup.compiled_programs(run)
    return None if programs is None else len(programs)
