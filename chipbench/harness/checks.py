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
        self.made = {}               # name -> its number beside its limit

    def add(self, name: str, value, limit, rule: str = "<=") -> bool:
        value = float(value)
        ok = {"<=": value <= limit, ">=": value >= limit,
              "==": value == limit}[rule]
        if value != value:                       # NaN never passes
            ok = False
        self._note(name, value=value, limit=limit, rule=rule, ok=bool(ok))
        return ok

    def fail(self, name: str, why: str) -> None:
        self._note(name, ok=False, why=why)

    def _note(self, name: str, **made) -> None:
        if name in self.made:
            raise ValueError(f"the check {name!r} was made twice")
        self.made[name] = made
        emit(phase="check", check=name, **made)

    @property
    def correct(self) -> bool:
        return bool(self.made) and all(c["ok"] for c in self.made.values())


def _beside_its_limit(name: str, c: dict) -> str:
    what = f"{c['value']!r} {c['rule']} {c['limit']!r}" if "value" in c \
        else c["why"]
    return f"check {name}: {what}" + ("" if c["ok"] else "  NOT OK")


def last_line(checks: Checks, attempted: int, failed: int, metrics: dict,
              device: dict, breakdown: dict | None = None,
              rehearsal: bool = False) -> None:
    """The result: the LAST line of stdout, with the contract's keys and,
    last in it, every number compared beside its limit (``checks``); the
    same numbers are the last lines of stderr, one a line."""
    out = {"correct": checks.correct, "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        out["breakdown"] = breakdown
    if rehearsal:
        out["rehearsal"] = True
    out["checks"] = checks.made
    sys.stdout.flush()
    for name, c in checks.made.items():
        print(_beside_its_limit(name, c), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
