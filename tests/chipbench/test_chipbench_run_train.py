"""Whole runs of the training cells on the CPU at rehearsal sizes (the
four-chip cell on four virtual devices), the control, and ``correct`` false
when the step returns its parameters unchanged."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
ENV.pop("XLA_FLAGS", None)


def run(args, script=None):
    cmd = [sys.executable] + (script or ["-m", "chipbench.run"]) + args
    p = subprocess.run(cmd, cwd=ROOT, env=ENV, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    return [json.loads(ln) for ln in p.stdout.splitlines() if ln.strip()]


def pick(out, phase):
    return next(c for c in out if c.get("phase") == phase)


@pytest.fixture(scope="module")
def train():
    return run(["--workload", "mistral7b-train-4k", "--seed",
                str(2**31 + 3), "--seconds", "2", "--trace", "0",
                "--rehearse", "1", "--control", "1"])


def test_the_train_cell_follows_the_reference(train):
    assert train[-1]["correct"] is True and train[-1]["metrics"] == {}
    cmp_ = pick(train, "compare")
    assert len(cmp_["losses"]) == 3
    for name in ("loss_rel_gap", "first_grad_norm_gap",
                 "param_change_norm_gap"):
        assert cmp_[name] < 1e-4, name               # float32 on the CPU
    win = pick(train, "window")
    assert win["steps"] >= 2 and win["tokens_per_step"] == 2 * 128
    checks = {c["check"]: c for c in train if c.get("phase") == "check"}
    assert checks["compiles_in_window"]["value"] == 0
    assert {"loss_rel_gap", "first_grad_norm_gap",
            "param_change_norm_gap"} <= set(checks)


def test_the_int8_control_reads_far_from_the_reference(train):
    sound, control = pick(train, "compare"), pick(train,
                                                  "control_int8_compare")
    assert control["first_grad_norm_gap"] > 100 * sound["first_grad_norm_gap"]
    assert control["loss_rel_gap"] > 100 * sound["loss_rel_gap"]


def test_a_step_that_returns_its_parameters_unchanged_is_not_correct():
    out = run(["state", "--workload", "mistral7b-train-4k", "--seed", "8",
               "--seconds", "1", "--trace", "0", "--rehearse", "1"],
              script=[os.path.join(HERE, "broken_run.py")])
    assert out[-1]["correct"] is False
    bad = [c["check"] for c in out
           if c.get("phase") == "check" and not c["ok"]]
    assert "param_change_norm_gap" in bad


def test_the_four_chip_cell_runs_on_four_virtual_devices():
    out = run(["--workload", "mistral7b-train-dp2mp2", "--seed", "77",
               "--seconds", "2", "--trace", "0", "--rehearse", "1"])
    assert out[-1]["correct"] is True
    assert out[-1]["device"]["count"] == 4
    assert pick(out, "built")["layout"]["dp"] == 2
    assert pick(out, "window")["tokens_per_step"] == 4 * 128
    assert pick(out, "reference")["steps_followed"] == 2
