"""``python -m chipbench.control_verdict <output of a run> ...``: what a
serving cell's limits say of the lower-precision control.

A run with ``--control 1`` prints the int8 reference's readings beside the
program's (``"control_int8"`` in its ``reference`` line); only the program's
decide that run's ``correct``.  This reads such outputs again and puts BOTH
sets of readings through the harness's own ``Checks`` against the limits of
the cell's file as it stands: the program's have to pass every one, the
control's have to fail at least one.  One line a run, then a summary; exit
1 unless every run says so.  Needs no chip: it reads what a chip run left.
"""

from __future__ import annotations

import contextlib
import io
import json
import sys

from chipbench.harness import spec
from chipbench.harness.checks import Checks

# the limits ``harness/serving.py::check_served`` knows, and the reading
# each is held against
READING_OF = {"served_logit_gap_max": "gap_max",
              "served_logit_gap_p99": "gap_p99",
              "served_logit_gap_mean": "gap_mean",
              "served_disagree_share": "disagree_share",
              "served_flip_share": "flip_share"}


def lines_of(path: str) -> tuple:
    """(the ``start`` line, the ``reference`` line) of one run's stdout."""
    start = ref = None
    with open(path, errors="replace") as f:
        for line in f:
            if not line.startswith("{"):
                continue
            try:
                d = json.loads(line)
            except json.JSONDecodeError:
                continue
            if d.get("phase") == "start":
                start = d
            elif d.get("phase") == "reference":
                ref = d
    return start, ref


def verdict(cell, readings: dict, tokens: int) -> dict:
    """``readings`` (``gap_*``, ``greedy_agree_share``, ``flip_share`` where
    the cell limits it) against the cell's
    limits, through ``Checks``: {"correct", "not_ok": [names]}."""
    got = dict(readings,
               disagree_share=1.0 - readings["greedy_agree_share"])
    ck = Checks()
    with contextlib.redirect_stdout(io.StringIO()):   # one line a run here
        ck.add("served_tokens_compared", tokens,
               cell.limit("served_tokens_compared"), ">=")
        for name, key in READING_OF.items():
            if name in cell.extras["limits"]:
                ck.add(name, got[key], cell.limit(name))
    return {"correct": ck.correct,
            "not_ok": [n for n, c in ck.made.items() if not c["ok"]],
            "checks": {n: c["value"] for n, c in ck.made.items()}}


def main(argv=None) -> int:
    paths = list(sys.argv[1:] if argv is None else argv)
    cells, as_wanted, runs = {}, 0, 0
    for path in paths:
        start, ref = lines_of(path)
        if not start or not ref or "control_int8" not in ref:
            print(json.dumps({"file": path, "skipped": "no run with "
                              "--control 1 that reached its reference"}))
            continue
        name = start["workload"]
        cell = cells.setdefault(name, spec.load_cell(name, spec.ROOT))
        program = verdict(cell, ref, ref["tokens"])
        control = verdict(cell, ref["control_int8"], ref["tokens"])
        runs += 1
        as_wanted += program["correct"] and not control["correct"]
        print(json.dumps({"workload": name, "seed": start["seed"],
                          "program": program, "control_int8": control}))
    print(json.dumps({"runs": runs, "program_correct_and_control_refused":
                      int(as_wanted)}))
    return 0 if runs and as_wanted == runs else 1


if __name__ == "__main__":
    sys.exit(main())
