"""Kernel block-size autotuner with a persistent cache.

Reference: paddle/phi/kernels/autotune/cache.h + switch_autotune.h — runtime
algorithm selection cached across runs.  TPU-native equivalent: the tunable
"algorithm" is the Pallas (block_q, block_kv) tiling, the measurement is a
real compiled execution on the attached chip, and the cache is a JSON file
keyed by (kernel, shape-bucket, dtype, device_kind) so one process's search
feeds every later run on the same hardware.

Mechanics: kernels consult :func:`lookup_or_tune` at trace time (shapes are
static under jit, so the key is concrete even on tracers).  On a cache miss
with tuning enabled, candidate configs are measured OUTSIDE the ongoing
trace — each probe is its own jitted call on concrete dummy inputs, which is
legal re-entrant dispatch — and the winner is persisted.  With tuning
disabled (CPU, interpret mode, or ``enable=False``) the caller's default is
returned, so the tuner never changes numerics, only tiling.

``paddle.incubate.autotune.set_config`` drives the enable switch and cache
path (the reference's user surface).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, List, Optional, Sequence, Tuple

from .. import flags

_LOCK = threading.Lock()
_MEM: dict = {}          # key -> chosen config (list)
_LOADED = [False]
_MEASURED = {}           # key -> {config_str: ms} measurement log (debug)


def _cache_path() -> str:
    """FLAGS_autotune_cache_path, else beside the compile cache: one fixed
    git-ignored directory inside the checkout (``paddle_tpu.CACHE_DIR``)."""
    p = flags.flag("autotune_cache_path")
    if p:
        return os.path.expanduser(p)
    from .. import CACHE_DIR
    return os.path.join(CACHE_DIR, "autotune.json")


def _load():
    if _LOADED[0]:
        return
    _LOADED[0] = True
    try:
        with open(_cache_path()) as f:
            _MEM.update(json.load(f))
    except (OSError, ValueError):
        pass


def _save():
    path = _cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(_MEM, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # cache is an optimization; never fail the computation


def clear(persist: bool = False):
    """Drop the in-memory cache (and the on-disk file with persist=True).
    The next lookup lazily re-reads whatever remains on disk — so a plain
    clear() behaves like a fresh process."""
    with _LOCK:
        _MEM.clear()
        _MEASURED.clear()
        _LOADED[0] = False
        if persist:
            try:
                os.unlink(_cache_path())
            except OSError:
                pass


def enabled() -> bool:
    return bool(flags.flag("autotune_enable"))


def device_kind() -> str:
    import jax

    try:
        d = jax.devices()[0]
        return getattr(d, "device_kind", d.platform).replace(" ", "_")
    except Exception:
        return "unknown"


def make_key(kernel: str, **attrs) -> str:
    """Stable string key: kernel|device|attr=value|..."""
    parts = [kernel, device_kind()]
    for k in sorted(attrs):
        parts.append(f"{k}={attrs[k]}")
    return "|".join(parts)


def measure(fn: Callable[[], None], warmup: int = 2, reps: int = 5) -> float:
    """Median wall-clock ms of ``fn()`` (fn must block on completion)."""
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return times[len(times) // 2]


def lookup(key: str):
    with _LOCK:
        _load()
        v = _MEM.get(key)
        return tuple(v) if isinstance(v, list) else v


def entries(kernel: str) -> dict:
    """Every cached ``key -> config`` of one kernel (what a run's tiles
    were: chip_smoke.py prints it)."""
    with _LOCK:
        _load()
        return {k: v for k, v in _MEM.items()
                if k.split("|", 1)[0] == kernel}


def measured(kernel: str) -> dict:
    """``key -> {config: median ms}`` of the tuning passes THIS process
    ran for one kernel (empty when every key was a cache hit): how far
    apart the candidates were, i.e. whether the winner is noise."""
    with _LOCK:
        return {k: dict(v) for k, v in _MEASURED.items()
                if k.split("|", 1)[0] == kernel}


def record(key: str, config, measurements: Optional[dict] = None):
    """Explicitly store a winner chosen outside the tuner (the benchmark
    pins flash tiles this way)."""
    with _LOCK:
        _load()
        _MEM[key] = list(config) if isinstance(config, (tuple, list)) \
            else config
        if measurements:
            _MEASURED[key] = {str(k): round(float(v), 4)
                              for k, v in measurements.items()}
        _save()


def lookup_or_tune(key: str, candidates: Sequence,
                   bench: Callable[[object], Optional[Callable[[], None]]],
                   default):
    """Cached config for ``key``, measuring candidates on a miss.

    ``bench(config)`` returns a nullary timed closure (must block until the
    device finishes), or None if the config is infeasible; a measurement
    error disqualifies that candidate, and a pass in which EVERY measured
    candidate failed raises with each one's error.  Returns ``default``
    untouched when tuning is disabled and the cache is cold.
    """
    got = lookup(key)
    if got is not None:
        return got
    if not enabled() or not candidates:
        return default
    best, best_ms, log, errors = None, float("inf"), {}, {}
    for cand in candidates:
        try:
            fn = bench(cand)
            if fn is None:
                continue
            ms = measure(fn)
        except Exception as e:   # compile/runtime failure: disqualify
            errors[str(cand)] = f"{type(e).__name__}: {e}"
            continue
        log[str(cand)] = round(ms, 4)
        if ms < best_ms:
            best, best_ms = cand, ms
    if best is None:
        if errors:
            # one bad tile is a disqualification; ALL of them failing is a
            # broken kernel or device, and the default would fail the same
            # way a moment later with less to go on
            raise RuntimeError(
                f"autotune {key}: every candidate failed to compile or "
                f"run: {errors}")
        return default
    with _LOCK:
        _MEM[key] = list(best) if isinstance(best, (tuple, list)) else best
        _MEASURED[key] = log
        _save()
    return tuple(best) if isinstance(best, (tuple, list)) else best


def grouped_matmul_candidates(M: int, K: int, N: int, itemsize: int = 2,
                              bm: int = 512, kind: str = "gmm",
                              vmem_budget: int = 10 << 20
                              ) -> List[Tuple[int, int]]:
    """Feasible (bn, bk) tilings for the grouped-matmul kernels
    (kernels/grouped_matmul.py).

    Feasibility: the tile must divide its operand dim (K for bk, N for
    bn), be an MXU-friendly multiple of 128, and keep the resident VMEM
    under ``vmem_budget``.  The block shapes differ per kernel: gmm holds
    lhs [bm, bk] + rhs [bk, bn] + a [bm, bn] fp32 accumulator and output,
    while tgmm holds lhs [bm, bk] + rhs [bm, bn] + a [bk, bn] fp32
    accumulator and output."""
    def opts(d):
        return [b for b in (128, 256, 512, 1024) if b <= d and d % b == 0]

    cands = []
    for bn in opts(N):
        for bk in opts(K):
            if kind == "tgmm":
                vmem = (bm * bk + bm * bn) * itemsize + \
                    bk * bn * (4 + itemsize)
            else:
                vmem = (bm * bk + bk * bn) * itemsize + \
                    bm * bn * (4 + itemsize)
            if vmem <= vmem_budget:
                cands.append((bn, bk))
    return cands


def flash_attention_candidates(sq: int, sk: int, d: int,
                               vmem_budget: int = 10 << 20
                               ) -> List[Tuple[int, int]]:
    """Feasible (block_q, block_kv) tilings for the flash kernels.

    Feasibility: divisibility into the sequence lengths, MXU-friendly
    multiples of 128 (or the full length when shorter), and a conservative
    VMEM estimate (Q/KV/acc blocks in fp32) under ``vmem_budget``."""
    def opts(n):
        o = [b for b in (128, 256, 512, 1024) if b <= n and n % b == 0]
        return o or ([n] if n <= 1024 else [])

    cands = []
    for bq in opts(sq):
        for bkv in opts(sk):
            vmem = 4 * (bq * d + 2 * bkv * d + bq * bkv + 2 * bq * d)
            if vmem <= vmem_budget:
                cands.append((bq, bkv))
    return cands
