"""``python -m chipbench.measure``: the builder's runs of a cell, one process
each (this parent never touches JAX, so each child gets the chip), with
every run's output kept under ``chiprun_out/<tag>/`` and a summary of the
last lines: medians and the spread (distance between the quartiles of
``statistics.quantiles(values, n=4)`` as a share of the median).

    python -m chipbench.measure --workload <cell> --seconds 51 \\
        --seeds 11 22 33 --sets 2 --trace-seed 44 --tag sets

``--extra`` passes further arguments to every run (``--control 1``,
``--set arrivals.rate_rps=3``).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time


def spread(values: list) -> float | None:
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None


def run_one(workload, seed, seconds, trace, extra, out_dir, label) -> dict:
    cmd = [sys.executable, "-m", "chipbench.run", "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)] + list(extra)
    t0 = time.time()
    proc = subprocess.run(cmd, capture_output=True, text=True)
    took = time.time() - t0
    base = os.path.join(out_dir, f"{workload}.{label}.{seed}")
    with open(base + ".out", "w") as f:
        f.write(proc.stdout)
    with open(base + ".err", "w") as f:
        f.write(proc.stderr)
    last = None
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode == 0 and lines:
        try:
            last = json.loads(lines[-1])
        except json.JSONDecodeError:
            last = None
    print(json.dumps({"label": label, "seed": seed, "rc": proc.returncode,
                      "wall_s": round(took, 1),
                      "correct": None if last is None else last["correct"],
                      "metrics": None if last is None else {
                          k: v["value"] for k, v in last["metrics"].items()}}),
          flush=True)
    if proc.returncode != 0 or last is None:
        sys.stdout.write(proc.stdout[-3000:])
        sys.stdout.write(proc.stderr[-3000:])
    return {"rc": proc.returncode, "last": last, "lines": lines}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.measure")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace-seed", type=int, default=None)
    ap.add_argument("--tag", default="measure")
    ap.add_argument("--show", nargs="*", default=[],
                    help="phases of the early lines to print per run")
    ap.add_argument("--extra", nargs=argparse.REMAINDER, default=[])
    args = ap.parse_args(argv)
    out_dir = os.path.join("chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    by_set = {}
    bad = 0
    for s in range(args.sets):
        label = "ABCDEFGH"[s]
        for seed in args.seeds:
            res = run_one(args.workload, seed, args.seconds, 0, args.extra,
                          out_dir, label)
            for ln in res["lines"]:
                if any(f'"phase": "{p}"' in ln for p in args.show):
                    print("   ", ln[:1500], flush=True)
            if res["last"] is None or not res["last"]["correct"]:
                bad += 1
                continue
            for k, v in res["last"]["metrics"].items():
                by_set.setdefault(k, {}).setdefault(label, []).append(
                    v["value"])
    if args.trace_seed is not None:
        res = run_one(args.workload, args.trace_seed, args.seconds, 1,
                      args.extra, out_dir, "T")
        if res["last"] is None or not res["last"]["correct"]:
            bad += 1
        else:
            print(json.dumps({"traced": res["last"]}), flush=True)
    summary = {}
    for name, sets in by_set.items():
        summary[name] = {
            label: {"n": len(v), "median": statistics.median(v),
                    "spread": spread(v), "min": min(v), "max": max(v)}
            for label, v in sets.items()}
    print(json.dumps({"summary": summary, "runs_not_correct": bad}),
          flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
