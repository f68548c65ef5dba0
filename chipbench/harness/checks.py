"""What decides ``correct``: every number compared, beside its limit."""

from __future__ import annotations

import json
import sys


def emit(**kw) -> None:
    """One early line of the run (phases, counts, checks): JSON on stdout."""
    print(json.dumps(kw), flush=True)


class Checks:
    """The run's comparisons.  Each is printed when it is made; ``correct``
    is true only if every one held and at least one was made."""

    def __init__(self):
        self.made = []

    def add(self, name: str, value, limit, rule: str = "<=") -> bool:
        value = float(value)
        ok = {"<=": value <= limit, ">=": value >= limit,
              "==": value == limit}[rule]
        if value != value:                       # NaN never passes
            ok = False
        self.made.append((name, ok))
        emit(phase="check", check=name, value=value, limit=limit, rule=rule,
             ok=bool(ok))
        return ok

    def fail(self, name: str, why: str) -> None:
        self.made.append((name, False))
        emit(phase="check", check=name, ok=False, why=why)

    @property
    def correct(self) -> bool:
        return bool(self.made) and all(ok for _, ok in self.made)


def last_line(correct: bool, attempted: int, failed: int, metrics: dict,
              device: dict, breakdown: dict | None = None,
              rehearsal: bool = False) -> None:
    """The result: the LAST line of stdout, with the contract's keys."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    if rehearsal:
        out["rehearsal"] = True
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
