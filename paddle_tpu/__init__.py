"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle's
capability surface, built on JAX/XLA/Pallas/pjit.

Architecture notes live in SURVEY.md §7 of the repo root; each module
docstring cites the reference component (file:line) it re-implements.
"""

import os as _os
import time as _time

# the first of the two clock readings `startup.import` is recorded from
# (observability/startup.py, after the fact: no tracer exists yet)
_IMPORT_BEGAN = _time.perf_counter()

import jax as _jax  # noqa: E402

# Paddle's dtype surface includes real int64/float64 tensors
# (phi DataType::INT64/FLOAT64); without x64 JAX silently narrows to 32-bit.
# Weak-typed Python scalars still combine at the other operand's dtype, and
# all defaults here remain float32, so TPU compute paths are unaffected.
# An explicit JAX_ENABLE_X64 in the environment wins over this default.
if "JAX_ENABLE_X64" not in _os.environ:
    _jax.config.update("jax_enable_x64", True)

from . import dtypes, errors, flags

# Persistent XLA compilation cache — the CompilationCache slot of the
# reference's CINN stack (paddle/cinn/hlir/framework/pir/compilation_cache.h):
# compiled executables are reused across processes.  Placed from outside:
# where JAX_COMPILATION_CACHE_DIR is set jax reads it itself and nothing is
# set here; otherwise the cache goes to ONE fixed git-ignored directory
# inside the checkout (the path is part of the cache key, so it must not
# depend on home, a temp name, a pid or the time).  The autotune JSON
# lives beside it (kernels/autotune.py).
CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".paddle_tpu_cache")
if "JAX_COMPILATION_CACHE_DIR" not in _os.environ:
    _jax.config.update("jax_compilation_cache_dir",
                       _os.path.join(CACHE_DIR, "xla"))

from .dtypes import (  # noqa: F401
    bfloat16, bool_, complex64, complex128, dtype, float8_e4m3fn,
    float8_e5m2, float16, float32, float64, get_default_dtype, int8, int16,
    int32, int64, pstring, raw, set_default_dtype, uint8,
)
from .flags import get_flags, set_flags  # noqa: F401
from .core import (  # noqa: F401
    Parameter, Tensor, enable_grad, grad, is_grad_enabled, is_tensor, no_grad,
    set_grad_enabled, to_tensor,
)
from .core.random import get_rng_state, seed, set_rng_state  # noqa: F401
from .ops import *  # noqa: F401,F403
from .ops import creation as _creation  # noqa: F401
from . import ops  # noqa: F401

version = "0.1.0"
__version__ = version


def disable_static(place=None):
    from . import static as _static
    _static.disable_static()


def enable_static():
    """Switch to static capture/replay mode (static.Program + Executor over
    the op-record seam; see paddle_tpu/static/__init__.py)."""
    from . import static as _static
    _static.enable_static()


def in_dynamic_mode():
    from . import static as _static
    return not _static.in_static_mode()


_device = [None]


def set_device(device: str):
    _device[0] = device
    return device


def get_device() -> str:
    if _device[0] is not None:
        return _device[0]
    import jax
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def device_count() -> int:
    import jax
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    import builtins
    import jax
    # note: bare any/all/sum/... here are paddle ops after the star-import above
    return builtins.any(d.platform == "tpu" for d in jax.devices())


# Subsystem imports (each mirrors a reference python/paddle/* package).
_SUBMODULES = [
    "nn", "optimizer", "amp", "io", "jit", "autograd", "framework", "vision",
    "linalg", "fft", "signal", "incubate", "metric", "sparse", "profiler",
    "hapi", "hub", "device", "distributed", "distribution", "static", "audio",
    "text", "quantization", "utils", "inference", "regularizer",
    "geometric", "sysconfig", "onnx", "ir", "observability",
]


def __getattr__(name):
    """Lazy submodule import (keeps `import paddle_tpu` cheap and cycle-free)."""
    if name in _SUBMODULES:
        import importlib
        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    if name in ("save", "load"):
        from .framework import io as _fio
        globals()["save"], globals()["load"] = _fio.save, _fio.load
        return globals()[name]
    if name in ("Model", "summary", "flops"):
        from . import hapi as _hapi
        from .hapi.summary import flops as _flops
        globals()["Model"], globals()["summary"] = _hapi.Model, _hapi.summary
        globals()["flops"] = _flops
        return globals()[name]
    if name == "callbacks":
        from .hapi import callbacks as _cb
        globals()["callbacks"] = _cb
        return _cb
    if name == "batch":
        from .batch import batch as _batch
        globals()["batch"] = _batch
        return _batch
    if name == "DataParallel":
        from .distributed.parallel import DataParallel as _DP
        globals()["DataParallel"] = _DP
        return _DP
    if name in ("CPUPlace", "CUDAPlace", "CUDAPinnedPlace", "TPUPlace",
                "XPUPlace", "CustomPlace"):
        from . import device as _dev
        globals()[name] = getattr(_dev, name)
        return globals()[name]
    if name == "ParamAttr":
        from .nn.layer import ParamAttr as _PA
        globals()["ParamAttr"] = _PA
        return _PA
    if name == "bool":
        # paddle.bool is a dtype; exposed lazily so the builtin is never
        # shadowed inside this module (annotations, future bool() calls)
        return dtypes.bool_
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


_IMPORT_ENDED = _time.perf_counter()
