"""Kernel autotuner tests (reference: paddle/phi/kernels/autotune/cache.h —
measured algorithm selection with a persistent cache; user surface
python/paddle/incubate/autotune.py set_config).

The measurement itself needs a TPU; everything around it — candidate
generation, selection, persistence, key stability, the incubate wiring, and
the flash-attention cache consultation — is exercised here on CPU.
"""

import json
import os

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu import flags
from paddle_tpu.kernels import autotune


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path):
    flags.set_flags({"autotune_cache_path": str(tmp_path / "at.json"),
                     "autotune_enable": True})
    autotune.clear()
    yield
    autotune.clear()
    flags.set_flags({"autotune_cache_path": "", "autotune_enable": True})


def test_candidates_divisibility_and_vmem():
    cands = autotune.flash_attention_candidates(2048, 2048, 128)
    assert (128, 128) in cands and (512, 512) in cands
    for bq, bkv in cands:
        assert 2048 % bq == 0 and 2048 % bkv == 0
    # short sequences fall back to the full length
    assert autotune.flash_attention_candidates(64, 64, 64) == [(64, 64)]
    # vmem budget prunes the huge tiles
    big = autotune.flash_attention_candidates(4096, 4096, 256,
                                              vmem_budget=2 << 20)
    assert (1024, 1024) not in big


def test_lookup_or_tune_picks_fastest_and_persists(tmp_path):
    import time

    durations = {(1, 1): 0.005, (2, 2): 0.001, (3, 3): 0.003}
    calls = []

    def bench(cand):
        def timed():
            calls.append(cand)
            time.sleep(durations[cand])
        return timed

    key = autotune.make_key("fake", n=1)
    got = autotune.lookup_or_tune(key, list(durations), bench, (9, 9))
    assert got == (2, 2)
    # cached: no more measuring
    n = len(calls)
    assert autotune.lookup_or_tune(key, list(durations), bench, (9, 9)) == (2, 2)
    assert len(calls) == n
    # persisted: a fresh in-memory cache re-reads from disk
    autotune.clear()
    assert autotune.lookup_or_tune(key, list(durations), bench, (9, 9)) == (2, 2)
    assert len(calls) == n
    with open(flags.flag("autotune_cache_path")) as f:
        assert key in json.load(f)


def test_disabled_returns_default():
    flags.set_flags({"autotune_enable": False})
    called = []

    def bench(c):
        called.append(c)
        return lambda: None

    got = autotune.lookup_or_tune("k", [(1, 1)], bench, (7, 7))
    assert got == (7, 7) and not called


def test_failing_candidates_are_disqualified():
    def bench(cand):
        if cand == (1, 1):
            raise RuntimeError("compile failed")
        if cand == (2, 2):
            return None  # infeasible
        return lambda: None

    got = autotune.lookup_or_tune("k2", [(1, 1), (2, 2), (3, 3)], bench,
                                  (9, 9))
    assert got == (3, 3)


def test_all_candidates_fail_is_reported():
    """One failing tile is disqualified; a pass in which EVERY candidate
    failed is a broken kernel or device and raises with each error."""
    def bench(cand):
        raise RuntimeError("nope")

    with pytest.raises(RuntimeError, match=r"every candidate.*nope"):
        autotune.lookup_or_tune("k3", [(1, 1)], bench, (5, 5))


def test_all_candidates_infeasible_returns_default():
    assert autotune.lookup_or_tune(
        "k4", [(1, 1)], lambda cand: None, (5, 5)) == (5, 5)


def test_key_includes_device_shape_dtype():
    k1 = autotune.make_key("flash_fwd", sq=2048, d=128, dt="bfloat16")
    k2 = autotune.make_key("flash_fwd", sq=1024, d=128, dt="bfloat16")
    k3 = autotune.make_key("flash_fwd", sq=2048, d=128, dt="float32")
    assert len({k1, k2, k3}) == 3
    assert autotune.device_kind() in k1


def test_incubate_set_config_drives_flag(tmp_path):
    import paddle_tpu.incubate.autotune as iat

    iat.set_config({"kernel": {"enable": False}})
    assert flags.flag("autotune_enable") is False
    iat.set_config({"kernel": {"enable": True,
                               "cache_path": str(tmp_path / "alt.json")}})
    assert flags.flag("autotune_enable") is True
    assert flags.flag("autotune_cache_path") == str(tmp_path / "alt.json")
    assert iat.get_config()["kernel"]["enable"] is True


def test_flash_attention_consults_cache(monkeypatch):
    """A pre-seeded cache entry must drive the kernel's block choice on the
    TPU path (exercised via the interpret-mode kernel on CPU)."""
    from paddle_tpu.kernels import flash_attention as fa

    b, s, h, d = 1, 256, 2, 64
    rng = np.random.default_rng(0)
    q = rng.standard_normal((b, s, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, h, d)).astype(np.float32)
    v = rng.standard_normal((b, s, h, d)).astype(np.float32)

    # force the tuned path by pretending we're on the compiled backend,
    # while routing the pallas_call through interpret mode
    monkeypatch.setattr(fa, "_pallas_mode", lambda: "tpu")
    seen = {}
    real_fwd = fa._fa_pallas_forward

    def spy_fwd(q_, k_, v_, causal, mask, sq_, sk_, blocks, mode,
                drop_p=0.0, seed=None):
        seen["blocks"] = blocks
        return real_fwd(q_, k_, v_, causal, mask, sq_, sk_, blocks,
                        "interpret", drop_p, seed)

    monkeypatch.setattr(fa, "_fa_pallas_forward", spy_fwd)

    key = autotune.make_key(
        "flash_fwd", sq=s, sk=s, d=d, hq=h, hkv=h, dt="float32",
        causal=1, m=0, s=0)
    autotune._MEM[key] = [128, 128]

    out = fa._flash_attention_arrays(q, k, v, True)
    assert seen["blocks"] == (128, 128)
    ref = fa._reference_attention(q, k, v, True, None, None, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_cold_cache_untuned_uses_default(monkeypatch):
    """With tuning disabled and a cold cache, the flagged default block
    sizes are used unchanged."""
    from paddle_tpu.kernels import flash_attention as fa

    flags.set_flags({"autotune_enable": False})
    monkeypatch.setattr(fa, "_pallas_mode", lambda: "tpu")
    seen = {}
    monkeypatch.setattr(
        fa, "_fa_pallas_forward",
        lambda q, k, v, causal, mask, sq, sk, blocks, mode, *drop:
        seen.update(blocks=blocks) or
        (np.zeros((q.shape[0], q.shape[2], q.shape[1], q.shape[3]),
                  np.float32),
         np.zeros((q.shape[0], q.shape[2], q.shape[1], 1), np.float32)))

    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 1024, 2, 64)).astype(np.float32)
    fa._flash_attention_arrays(x, x, x, False)
    assert seen["blocks"] == (min(512, 1024), min(512, 1024))
