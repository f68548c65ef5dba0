"""chip_smoke.py off the chip: it must refuse to pass without a TPU, and
its CPU rehearsal (tiny size, interpreted kernels) must run the same code
end to end.  The chip run itself is the driver's."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, **env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=ROOT,
                          env=e, capture_output=True, text=True, timeout=900)


def test_no_accelerator_is_a_failure_with_no_ok_line():
    r = _smoke()
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no accelerator" in r.stderr


def test_cpu_rehearsal_runs_every_phase_and_prints_no_ok_line():
    r = _smoke("--rehearse")
    assert r.returncode == 0, r.stderr[-3000:]
    assert '"ok"' not in r.stdout
    for phase in ("kernels", "train", "serve", "done"):
        assert f'"phase": "{phase}"' in r.stdout, r.stdout[-2000:]


def test_four_chip_rehearsal_runs_only_the_sharded_paths():
    r = _smoke("--rehearse", "--chips", "4",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stderr[-3000:]
    assert '"ok"' not in r.stdout
    for phase in ("train_dp2_mp2", "serve_tp4", "done"):
        assert f'"phase": "{phase}"' in r.stdout, r.stdout[-2000:]
    for phase in ("kernels", "train", "serve"):
        assert f'"phase": "{phase}"' not in r.stdout
