"""Package init under the one installed jax (PR 21): no compat shims, and
the compilation cache is placed from outside."""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, **env):
    e = {k: v for k, v in os.environ.items()
         if k != "JAX_COMPILATION_CACHE_DIR"}
    e.update(JAX_PLATFORMS="cpu", **env)
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=e,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-1]


def test_import_installs_nothing_on_jax():
    out = _run(
        "import jax, jax.lax\n"
        "before = (set(vars(jax)), set(vars(jax.lax)))\n"
        "import paddle_tpu\n"
        "after = (set(vars(jax)), set(vars(jax.lax)))\n"
        "print(sorted((after[0] - before[0]) | (after[1] - before[1])))")
    # importing submodules may bind them on the jax package; nothing else
    added = [a for a in eval(out) if a not in (
        "export", "experimental", "scipy", "monitoring")]
    assert added == [], added


def test_cache_dir_is_fixed_inside_the_checkout_when_env_is_unset():
    out = _run("import jax, paddle_tpu\n"
               "print(jax.config.jax_compilation_cache_dir)")
    assert out == os.path.join(ROOT, ".paddle_tpu_cache", "xla")
    from paddle_tpu import flags
    assert "jit_cache_dir" not in flags.get_flags()


def test_cache_dir_from_the_environment_is_left_alone(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, jax reads it itself and the
    package sets no directory in code."""
    code = (
        "import jax\n"
        "calls = []\n"
        "real = jax.config.update\n"
        "def spy(name, val):\n"
        "    calls.append(name)\n"
        "    return real(name, val)\n"
        "jax.config.update = spy\n"
        "import paddle_tpu\n"
        "from paddle_tpu.kernels import autotune\n"
        "assert 'jax_compilation_cache_dir' not in calls, calls\n"
        "print(jax.config.jax_compilation_cache_dir)")
    out = _run(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out == str(tmp_path)
