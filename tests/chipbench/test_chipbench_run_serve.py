"""Whole runs of the serving cells on the CPU at rehearsal sizes: the last
line's keys, no metric under a device metric's name, failure without a chip,
and ``correct`` false when the timed path is broken."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)


def run(args, script=None):
    cmd = [sys.executable] + (script or ["-m", "chipbench.run"]) + args
    p = subprocess.run(cmd, cwd=ROOT, env=ENV, capture_output=True,
                       text=True, timeout=600)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, lines


def parsed(lines):
    return [json.loads(ln) for ln in lines]


@pytest.fixture(scope="module")
def chat():
    p, lines = run(["--workload", "mistral7b-chat-steady", "--seed",
                    str(2**31 + 17), "--seconds", "5", "--trace", "0",
                    "--rehearse", "1", "--control", "1"])
    assert p.returncode == 0, p.stderr[-2000:]
    out = parsed(lines)
    out[-1]["stderr_end"] = p.stderr.splitlines()[-len(out[-1]["checks"]):]
    return out


def test_last_line_has_the_contracts_keys(chat):
    last = dict(chat[-1])
    stderr_end = last.pop("stderr_end")
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device", "rehearsal", "checks"}
    # each number compared beside its limit: the line's last key, and the
    # last lines of stderr
    assert list(last)[-1] == "checks"
    made = {c["check"]: c for c in chat if c.get("phase") == "check"}
    assert set(last["checks"]) == set(made) and len(made) >= 6
    for name, c in last["checks"].items():
        assert (c["value"], c["limit"], c["ok"]) == (
            made[name]["value"], made[name]["limit"], made[name]["ok"])
    assert [ln.split(":")[0] for ln in stderr_end] == [
        "check " + name for name in last["checks"]]
    assert "served_logit_gap_max: " in stderr_end[4] and \
        stderr_end[4].endswith("<= 0.11")
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["failed"] == 0 and last["attempted"] > 5
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # a CPU run prints nothing under a device metric's name
    assert last["metrics"] == {} and last["device"]["platform"] == "cpu"


def test_every_number_compared_is_printed_beside_its_limit(chat):
    checks = {c["check"]: c for c in chat if c.get("phase") == "check"}
    assert {"compiles_in_window", "requests_failed", "streams_complete",
            "served_tokens_compared", "served_logit_gap_max",
            "served_logit_gap_mean"} <= set(checks)
    assert all("limit" in c and "value" in c for c in checks.values())
    assert checks["compiles_in_window"]["value"] == 0
    ref = next(c for c in chat if c.get("phase") == "reference")
    assert ref["gap_max"] < 1e-3                      # float32 on the CPU
    assert ref["control_int8"]["gap_max"] > 3 * max(ref["gap_max"], 1e-4)
    assert 0 in ref["sample"] or len(ref["sample"]) >= 1


def test_request_and_gap_counts_do_not_depend_on_the_seed(chat):
    p, lines = run(["--workload", "mistral7b-chat-steady", "--seed", "5",
                    "--seconds", "5", "--trace", "0", "--rehearse", "1"])
    assert p.returncode == 0, p.stderr[-2000:]
    other = parsed(lines)
    pick = lambda run_, ph: next(c for c in run_ if c.get("phase") == ph)  # noqa: E731
    assert pick(chat, "schedule") == pick(other, "schedule")
    a, b = pick(chat, "client"), pick(other, "client")
    assert (a["requests"], a["token_gaps"], a["tokens"]) == (
        b["requests"], b["token_gaps"], b["tokens"])
    assert a["token_gaps"] == pick(chat, "schedule")["token_gaps"]


def test_a_served_token_altered_where_it_is_produced_is_not_correct():
    p, lines = run(["token", "--workload", "mistral7b-chat-steady", "--seed",
                    "9", "--seconds", "4", "--trace", "0", "--rehearse",
                    "1"], script=[os.path.join(HERE, "broken_run.py")])
    assert p.returncode == 0, p.stderr[-2000:]
    out = parsed(lines)
    assert out[-1]["correct"] is False
    bad = [c["check"] for c in out
           if c.get("phase") == "check" and not c["ok"]]
    assert "served_logit_gap_max" in bad
    assert bad == [n for n, c in out[-1]["checks"].items() if not c["ok"]]
    assert "served_logit_gap_max" in p.stderr.splitlines()[-4] and \
        p.stderr.splitlines()[-4].endswith("NOT OK")


def test_without_a_chip_it_fails_and_prints_no_result():
    p, lines = run(["--workload", "mistral7b-chat-steady", "--seed", "1",
                    "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any('"correct"' in ln for ln in lines)


def test_an_unknown_cell_fails_and_prints_no_result():
    p, lines = run(["--workload", "no-such-cell", "--seed", "1",
                    "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and not lines


def test_the_closed_loop_cell_runs_and_traces_on_the_cpu():
    p, lines = run(["--workload", "mixtral8x7b-batch-docs", "--seed", "21",
                    "--seconds", "3", "--trace", "1", "--rehearse", "1"])
    assert p.returncode == 0, p.stderr[-2000:]
    out = parsed(lines)
    assert out[-1]["correct"] is True and out[-1]["metrics"] == {}
    win = next(c for c in out if c.get("phase") == "window")
    assert win["window_s"] >= 3.0 and win["tokens"] > 0
    assert win["documents_handed_in"] == \
        win["documents_finished"] + win["in_flight"]
    trace = next(c for c in out if c.get("phase") == "trace")
    assert trace["bench_spans"] > 1               # the benchmark's own spans
