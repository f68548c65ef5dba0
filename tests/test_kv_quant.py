"""Quantized KV memory plane + host-RAM spill tier tests (ISSUE 13).

Covers the tentpole end to end: the blockwise quantizer at page
granularity (the error bounds the kernel relies on), the int8 ragged
kernel vs the dequantized reference oracle, the page-RMW quantized
commit, engine-level parity / bit-stability / zero-overhead contracts,
and the spill tier's full lifecycle (evict->spill->swap-in hit matching
the never-evicted oracle, ring pressure, no-leak/no-double-free books,
spec-rollback coexistence).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu import flags
from paddle_tpu.distributed.quantized_collectives import (
    dequantize_blockwise, quantize_blockwise)
from paddle_tpu.inference import (ContinuousBatchingEngine,
                                  GenerationConfig, PageAllocator,
                                  PagedKVCache, PrefixCache)
from paddle_tpu.inference.kv_spill import HostSpillPool
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _tiny_model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


# ---------------------------------------------------------------------------
# satellite: quantize_blockwise at page granularity
# ---------------------------------------------------------------------------

def test_quantize_blockwise_page_granularity_roundtrip(rng):
    """The in-tree quantizer, run at the KV pool's granularity: one block
    per (kv-head, page) over [kvh, n_pages, page, d] values with a ragged
    tail (context_len NOT a multiple of page_size — the tail page is
    zero-padded, and zeros quantize to exactly 0).  Asserts the scale
    layout the kernel indexes (one fp32 per (kv-head, page)) and the
    absmax error bound the dequant path relies on: |x - deq(q(x))| <=
    scale/2 = absmax/254 per block."""
    kvh, n_pages, page, d = 2, 4, 8, 16
    ctx = 27                                    # ragged: 27 = 3*8 + 3
    x = np.zeros((kvh, n_pages, page, d), np.float32)
    rows = rng.standard_normal((kvh, ctx, d)).astype(np.float32)
    for h in range(kvh):
        for t in range(ctx):
            x[h, t // page, t % page] = rows[h, t]

    block = page * d
    flat = x.reshape(kvh * n_pages * block)
    q, scales = quantize_blockwise(jnp.asarray(flat), block=block)
    # per-(kv-head, page) scale layout: exactly one scale per pool page
    scales = np.asarray(scales).reshape(kvh, n_pages)
    assert scales.shape == (kvh, n_pages)
    deq = np.asarray(dequantize_blockwise(q, jnp.asarray(
        scales.reshape(-1)), length=flat.shape[0])).reshape(x.shape)

    amax = np.abs(x).max(axis=(2, 3))           # [kvh, n_pages]
    bound = amax / 254.0 + 1e-7
    err = np.abs(deq - x).max(axis=(2, 3))
    assert (err <= bound + 1e-6).all(), (err, bound)
    # ragged tail: the pad region must round-trip to exactly zero
    last = ctx // page
    assert (deq[:, last, ctx % page:] == 0).all()
    assert (deq[:, last + 1:] == 0).all()
    # a zero page quantizes with the sentinel scale 1.0 (never 0/0)
    assert (scales[:, last + 1:] == 1.0).all()


# ---------------------------------------------------------------------------
# kernel: int8 dequant path vs the dequantized reference oracle
# ---------------------------------------------------------------------------

def _int8_pool(rng, kvh=2, n_pages=16, page=32, d=128):
    kc = jnp.asarray(rng.integers(-127, 128, (kvh, n_pages, page, d)),
                     jnp.int8)
    vc = jnp.asarray(rng.integers(-127, 128, (kvh, n_pages, page, d)),
                     jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (kvh, n_pages)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (kvh, n_pages)), jnp.float32)
    return kc, vc, ks, vs


@pytest.mark.parametrize("t,qls", [(1, (1, 1)), (4, (4, 1)), (16, (16, 3))])
def test_int8_kernel_parity_vs_reference(rng, t, qls):
    """The Pallas int8 kernel (interpret mode) must match the XLA
    dequantize-then-attend oracle at every serving program shape."""
    kc, vc, ks, vs = _int8_pool(rng)
    b, qh, d = 2, 4, 128
    bt = jnp.asarray(rng.integers(0, 16, (b, 4)), jnp.int32)
    cl = jnp.asarray([70, 33], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, t, qh, d)), jnp.float32)
    ql = jnp.asarray(qls, jnp.int32)
    kn = jnp.asarray(rng.standard_normal((b, t, 2, d)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((b, t, 2, d)), jnp.float32)

    ref, _ = pa._reference_ragged_paged_attention(
        q, kc, vc, bt, cl, ql, kn, vn, ks, vs)
    old = flags.get_flags(["paged_attention_interpret"])
    flags.set_flags({"paged_attention_interpret": True})
    try:
        got = pa.ragged_paged_attention(
            q, pa.pool_of_heads(kc, vc), bt, cl, q_lens=ql, k_new=kn,
            v_new=vn, k_scale=ks, v_scale=vs)
    finally:
        flags.set_flags(old)
    for i in range(b):
        n = int(ql[i])
        np.testing.assert_allclose(np.asarray(got[i, :n]),
                                   np.asarray(ref[i, :n]),
                                   rtol=2e-5, atol=2e-5)


def test_int8_dequant_scale_semantics(rng):
    """Scale semantics oracle: an int8 pool with scales s must attend
    exactly like a float pool holding q * s."""
    kc, vc, ks, vs = _int8_pool(rng, page=8, d=64)
    kf = kc.astype(jnp.float32) * ks[:, :, None, None]
    vf = vc.astype(jnp.float32) * vs[:, :, None, None]
    b = 2
    bt = jnp.asarray(rng.integers(0, 16, (b, 3)), jnp.int32)
    cl = jnp.asarray([20, 9], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, 1, 4, 64)), jnp.float32)
    got, _ = pa._reference_ragged_paged_attention(
        q, kc, vc, bt, cl, None, None, None, ks, vs)
    want, _ = pa._reference_ragged_paged_attention(
        q, kf, vf, bt, cl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# the page-RMW quantized commit
# ---------------------------------------------------------------------------

def test_quantized_commit_matches_float_oracle(rng):
    """write_kv_pages_all_layers_quantized vs a float mirror: commit the
    same fresh rows into (a) the int8 pool and (b) an fp32 shadow, then
    dequantize (a) — every written row matches within the absmax bound,
    untouched pages are bit-identical, and rows straddling a page
    boundary land in both pages."""
    L, kvh, n_pages, page, d = 2, 2, 8, 8, 16
    B, T, W, max_len = 2, 6, 4, 32
    kc = jnp.zeros((L, kvh, n_pages, page, d), jnp.int8)
    vc = jnp.zeros((L, kvh, n_pages, page, d), jnp.int8)
    ks = jnp.ones((L, kvh, n_pages), jnp.float32)
    vs = jnp.ones((L, kvh, n_pages), jnp.float32)
    k_all = jnp.asarray(rng.standard_normal((L, B * T, kvh, d)), jnp.float32)
    v_all = jnp.asarray(rng.standard_normal((L, B * T, kvh, d)), jnp.float32)
    # row 0 starts mid-page (straddles 5->6 boundary at pos 8); row 1
    # ragged (2 valid tokens)
    positions = jnp.asarray([5, 16], jnp.int32)
    ql = jnp.asarray([T, 2], jnp.int32)
    bt = jnp.asarray([[0, 1, 0, 0], [4, 5, 6, 0]], jnp.int32)

    # the pool is page-major; the assertions read it back head-major
    kvq, ks2, vs2 = pa.write_kv_pages_all_layers_quantized(
        pa.pool_of_heads(kc, vc), ks, vs, k_all, v_all, positions, ql, bt,
        max_len)
    kq, vq = pa.heads_of_pool(kvq)
    deq = np.asarray(kq, np.float32) * np.asarray(ks2)[..., None, None]

    kn = np.asarray(k_all)
    scales = np.asarray(ks2)
    for bi, (p0, n) in enumerate([(5, T), (16, 2)]):
        for tt in range(n):
            pos = p0 + tt
            pg = int(bt[bi, pos // page])    # row 0: pages 0,1; row 1: 6
            want = kn[:, bi * T + tt]                    # [L, kvh, d]
            got = deq[:, :, pg, pos % page]
            # per-(layer, head) absmax bound: |x - deq| <= scale/2
            assert (np.abs(got - want).max(axis=-1)
                    <= scales[:, :, pg] / 2 + 1e-6).all()
    # untouched pages stay bit-identical with the sentinel scale 1.0
    # (row 0 wrote pages 0 and 1; row 1's two ragged tokens at pos
    # 16-17 land in page-list index 2 = page 6 — pages 4 and 5 of its
    # table were never touched, proving the ragged clamp)
    for pg in (2, 3, 4, 5, 7):
        assert (np.asarray(kq)[:, :, pg] == 0).all()
        assert (scales[:, :, pg] == 1.0).all()


def test_quantized_commit_masks_recycled_page_garbage(rng):
    """A freed page is never scrubbed: when a new sequence's first token
    lands in a recycled page still holding a large-magnitude previous
    occupant, the commit must NOT let the stale bytes inflate the absmax
    scale — the live row's error stays bounded by its own magnitude and
    the stale region requantizes to zero."""
    L, kvh, n_pages, page, d = 1, 1, 2, 8, 16
    # page 0: previous occupant at full int8 range with a huge scale
    kc = jnp.full((L, kvh, n_pages, page, d), 127, jnp.int8)
    ks = jnp.full((L, kvh, n_pages), 0.5, jnp.float32)   # absmax ~63.5
    fresh = jnp.asarray(rng.uniform(-0.01, 0.01, (L, 1, kvh, d)),
                        jnp.float32)                      # tiny new row
    kvq, ks2, _ = pa.write_kv_pages_all_layers_quantized(
        pa.pool_of_heads(kc, kc), ks, ks, fresh, fresh,
        jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32),
        jnp.zeros((1, 1), jnp.int32), 16)
    kq, vq = pa.heads_of_pool(kvq)
    assert (np.asarray(kq) == np.asarray(vq)).all()   # V took the same rows
    got = np.asarray(kq, np.float32)[0, 0, 0, 0] \
        * float(np.asarray(ks2)[0, 0, 0])
    want = np.asarray(fresh)[0, 0, 0]
    # scale derives from the LIVE content (~0.01/127), not the stale 63.5
    assert float(np.asarray(ks2)[0, 0, 0]) < 1e-3
    assert np.abs(got - want).max() <= 0.01 / 254 + 1e-6
    # the stale region is scrubbed to exact zero
    assert (np.asarray(kq)[0, 0, 0, 1:] == 0).all()


def test_quantized_commit_is_deterministic(rng):
    L, kvh, n_pages, page, d = 1, 1, 4, 8, 16
    kc = jnp.asarray(rng.integers(-50, 50, (L, kvh, n_pages, page, d)),
                     jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.02, (L, kvh, n_pages)), jnp.float32)
    k_all = jnp.asarray(rng.standard_normal((L, 2, kvh, d)), jnp.float32)
    args = (pa.pool_of_heads(kc, kc), ks, ks, k_all, k_all,
            jnp.asarray([3, 9], jnp.int32), jnp.asarray([1, 1], jnp.int32),
            jnp.asarray([[0, 1], [1, 2]], jnp.int32), 16)
    a = pa.write_kv_pages_all_layers_quantized(*args)
    b = pa.write_kv_pages_all_layers_quantized(*args)
    for x, y in zip(a, b):
        assert (np.asarray(x) == np.asarray(y)).all()


# ---------------------------------------------------------------------------
# engine: parity, bit-stability, zero-overhead
# ---------------------------------------------------------------------------

def _run_engine(model, prompts, *, cache_dtype=None, prefix_cache=False,
                max_batch=3, num_pages=None, max_new_tokens=6,
                kv_spill_pages=None, metrics=None, spec_decode=None):
    gc = GenerationConfig(max_new_tokens=max_new_tokens, do_sample=False)
    eng = ContinuousBatchingEngine(
        model, max_batch=max_batch, gen=gc, max_seq_len=64, page_size=8,
        prefill_bucket=8, num_pages=num_pages, prefix_cache=prefix_cache,
        cache_dtype=cache_dtype, kv_spill_pages=kv_spill_pages,
        metrics=metrics, spec_decode=spec_decode)
    rids = [eng.add_request(p) for p in prompts]
    out = eng.run()
    return [out[r] for r in rids], eng


# How far an int8-served token's logit may lie below the float model's best
# (the model's own whole-sequence forward over prompt + served tokens, the
# measure of chipbench's ``served_logit_gap_*``).  Readings, these prompts,
# model seeds 0-7 (CPU, float32): largest gap 8.702e-05 (seed 0) and
# 2.234e-04 (seed 7), where the float model's first and second choice lie
# 1e-4 and 2e-4 apart and int8 takes the other; 0.0 on the six other seeds,
# whose smallest first-to-second margin is 0.0049 (median 0.19-0.37).  The
# limit is 9 x the largest reading and under every margin int8 left alone.
INT8_LOGIT_GAP_LIMIT = 2e-3


@pytest.mark.parametrize("seed", [0, 7])
def test_engine_int8_parity_and_bit_stability(seed):
    """The tolerance contract (MIGRATION.md "KV dtype & spill tier"):
    greedy int8 outputs are bit-stable run-to-run, and every token they
    hold is, by the float model's logits, its best choice or within
    ``INT8_LOGIT_GAP_LIMIT`` of it.  Not token equality with the float
    arm: on both seeds here int8 takes the second of two near-tied
    choices and the texts part from there."""
    paddle.seed(seed)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    prompts = [list(range(1, 20)), [5, 6, 7, 8, 9, 10, 11],
               [9, 9, 9, 1, 2]]
    fp, eng_fp = _run_engine(model, prompts, cache_dtype=None)
    q1, eng_q = _run_engine(model, prompts, cache_dtype="int8")
    q2, _ = _run_engine(model, prompts, cache_dtype="int8")
    assert q1 == q2                       # bit-stable run-to-run
    # the float arm holds the measure to account: the engine's own tokens
    # are the forward's best choices (0.0 on every seed read)
    for served, limit in ((fp, 1e-5), (q1, INT8_LOGIT_GAP_LIMIT)):
        for prompt, toks in zip(prompts, served):
            ids = np.asarray([prompt + toks], np.int32)
            logits = np.asarray(model(paddle.to_tensor(ids))._data)[0]
            rows = logits[len(prompt) - 1:len(prompt) - 1 + len(toks)]
            gap = rows.max(-1) - rows[np.arange(len(toks)), toks]
            assert gap.max() <= limit, (gap, limit)
    assert eng_q.stats()["kv_cache_dtype"] == "int8"
    assert eng_fp.stats()["kv_cache_dtype"] != "int8"


def test_engine_int8_prefix_cache_cow_moves_scales():
    """COW over the int8 plane copies scale entries with the page bytes:
    a fully-cached re-hit (the COW path) must reproduce the cache-off
    int8 oracle."""
    model = _tiny_model()
    S = list(range(1, 25))                # 3 pages of 8: COW on full match
    prompts = [S + [30, 31], S + [40], S[:16], S + [30, 31]]
    base, _ = _run_engine(model, prompts, cache_dtype="int8")
    got, eng = _run_engine(model, prompts, cache_dtype="int8",
                           prefix_cache=True)
    assert got == base
    assert eng.stats()["prefix_hits"] >= 2


def test_engine_int8_warm_steps_zero_compiles_zero_syncs(monkeypatch):
    """Acceptance: the int8 arm's warm engine steps, attribution on,
    compile nothing and sync nothing while the steps in flight stay under
    their bound (the steps that land are gathered on the way)."""
    from paddle_tpu.inference import generation
    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", 64)
    model = _tiny_model()
    gc = GenerationConfig(max_new_tokens=12, do_sample=False)
    eng = ContinuousBatchingEngine(
        model, max_batch=2, gen=gc, max_seq_len=64, page_size=8,
        prefill_bucket=8, cache_dtype="int8", metrics=True)
    assert eng.attribution is not None
    for p in ([1, 2, 3], [4, 5]):
        eng.add_request(p)
    eng.run()                             # warm the T-pair programs
    for p in ([9, 8, 7], [2, 3]):
        eng.add_request(p)
    with obs.assert_overhead(max_compiles=0, max_syncs=0):
        for _ in range(6):
            eng.step()
    out = eng.run()
    assert all(len(v) == 12 for v in out.values())


def test_engine_int8_speculative_parity():
    """Spec decode rides the int8 plane: fused-K greedy outputs match
    the spec-off int8 engine (positional rollback + page-RMW commit
    interact only through positions, which rollback owns)."""
    model = _tiny_model()
    prompts = [list(range(1, 12)), [7, 7, 7, 2, 1]]
    base, _ = _run_engine(model, prompts, cache_dtype="int8",
                          max_new_tokens=10)
    got, eng = _run_engine(model, prompts, cache_dtype="int8",
                           max_new_tokens=10, spec_decode="fused")
    assert got == base
    assert eng.stats()["spec_steps"] > 0


def test_quant_bytes_saved_counter():
    before = obs.metrics.counter("serving.kv.quant_bytes_saved").value
    PagedKVCache(num_layers=2, num_pages=4, page_size=8, num_kv_heads=2,
                 head_dim=16, dtype="int8")
    after = obs.metrics.counter("serving.kv.quant_bytes_saved").value
    # 2 planes * (elements * 3 bytes saved - scale plane cost)
    per = 2 * 2 * 4
    assert after - before == 2 * (per * 8 * 16 * 3 - per * 4)


def test_bytes_per_page_accounting():
    fp = PagedKVCache.bytes_per_page(2, 2, 8, 16, "float32")
    q = PagedKVCache.bytes_per_page(2, 2, 8, 16, "int8")
    assert fp == 2 * 2 * 2 * 8 * 16 * 4
    assert q == 2 * 2 * 2 * (8 * 16 + 4)
    assert fp / q > 3.5                   # ~4x capacity at equal bytes


def test_engine_int8_holds_more_sessions_in_the_same_pool_bytes():
    """At equal pool bytes the int8 plane has >= 1.8 x the float pool's
    pages, and the engine keeps that many more sessions resident at once
    (a count of sequences the allocator holds, from the same traffic)."""
    model = _tiny_model()
    c = model.config
    bpp = {d: PagedKVCache.bytes_per_page(
        c.num_hidden_layers, c.num_key_value_heads, 8, c.head_dim, d)
        for d in ("float32", "int8")}
    pool_bytes = 6 * bpp["float32"]
    pages = {d: pool_bytes // bpp[d] for d in bpp}
    assert pages["int8"] >= 1.8 * pages["float32"]
    prompts = [list(range(1 + i, 17 + i)) for i in range(8)]   # 2 pages + 1

    def resident_high_water(dtype):
        eng = ContinuousBatchingEngine(
            model, max_batch=8, max_seq_len=64, page_size=8,
            prefill_bucket=8, num_pages=int(pages[dtype]), cache_dtype=dtype,
            gen=GenerationConfig(max_new_tokens=4, do_sample=False))
        assert eng.g.pool_bytes <= pool_bytes
        for p in prompts:
            eng.add_request(p)
        high = 0
        while eng.has_work():
            eng.step()
            high = max(high, eng.g.cache.allocator.stats()["active_seqs"])
        # every request is answered; one the pool cannot grow ends early
        assert len(eng.run()) == len(prompts)
        return high

    fp, q = resident_high_water("float32"), resident_high_water("int8")
    assert q >= 1.8 * fp, (fp, q)


# ---------------------------------------------------------------------------
# spill tier
# ---------------------------------------------------------------------------

def _pressure_scenario(model, *, spill, cache_dtype=None, num_pages=8):
    """Seed a shared prefix, crush the pool with filler traffic (forcing
    LRU eviction of the idle prefix pages), then re-request the shared
    prompt.  Returns (first run output, post-pressure output, engine)."""
    S = list(range(1, 17))                # 2 pages of 8
    gc = GenerationConfig(max_new_tokens=8, do_sample=False)
    eng = ContinuousBatchingEngine(
        model, max_batch=2, gen=gc, max_seq_len=64, page_size=8,
        prefill_bucket=8, num_pages=num_pages, prefix_cache=True,
        kv_spill_pages=spill, cache_dtype=cache_dtype)
    r0 = eng.add_request(S + [30])
    first = eng.run()[r0]
    for i in range(3):
        eng.add_request(list(range(60 + 8 * i, 76 + 8 * i)),
                        max_new_tokens=12)
    eng.run()
    r1 = eng.add_request(S + [30])
    out = eng.run()[r1]
    return first, out, eng


@pytest.mark.parametrize("cache_dtype", [None, "int8"])
def test_spill_swapin_hit_matches_never_evicted_oracle(cache_dtype):
    """Acceptance: a spilled-then-swapped-in page serves a prefix hit
    whose outputs match the never-evicted oracle, on both KV dtypes."""
    model = _tiny_model()
    # oracle: same traffic, pool big enough that nothing ever evicts
    f0, o0, eng0 = _pressure_scenario(model, spill=0, num_pages=64,
                                      cache_dtype=cache_dtype)
    assert eng0.stats()["evicted_pages"] == 0
    f1, o1, eng = _pressure_scenario(model, spill=16,
                                     cache_dtype=cache_dtype)
    st = eng.stats()
    assert st["kv_spilled_pages"] > 0     # pressure really spilled
    assert st["kv_swapins"] > 0           # and the re-hit swapped back in
    assert (f1, o1) == (f0, o0)
    # no leak / no double free: every device page accounted for
    alloc = eng.g.cache.allocator
    assert alloc.free_pages + eng.prefix_cache.evictable_pages() \
        == alloc.num_pages
    # ring books: resident slots = spills - swap-ins - drops
    assert st["kv_spill_resident"] == eng.spill.capacity \
        - eng.spill.free_slots


def test_spill_ring_pressure_drops_coldest():
    """A full ring drops its coldest spilled node to admit a warmer
    eviction; dropped slots are retired exactly once (no leak)."""
    model = _tiny_model()
    f, o, eng = _pressure_scenario(model, spill=1)
    st = eng.stats()
    assert st["kv_spilled_pages"] >= 2    # more spills than slots
    assert st["kv_spill_resident"] <= 1
    assert eng.spill.free_slots + st["kv_spill_resident"] == 1
    assert f == o


def test_spill_off_is_bit_identical_to_pre_spill_engine():
    """FLAGS_kv_spill_pages=0 (default): evictions drop, outputs and
    telemetry match the pre-ISSUE-13 engine exactly."""
    model = _tiny_model()
    f, o, eng = _pressure_scenario(model, spill=0)
    st = eng.stats()
    assert not st["kv_spill_enabled"]
    assert "kv_spilled_pages" not in st
    assert st["evicted_pages"] > 0
    assert f == o                         # dropped pages re-prefill


def test_spill_with_spec_rollback_books_balance():
    """Speculative tail rollback (PageAllocator.truncate) coexists with
    the spill tier: rollback only touches the sequence's own tail pages
    (spilled pages are never in a block table), and after everything
    retires the device + ring books balance — no leak, no double free."""
    model = _tiny_model()
    S = list(range(1, 17))
    gc = GenerationConfig(max_new_tokens=10, do_sample=False)
    eng = ContinuousBatchingEngine(
        model, max_batch=2, gen=gc, max_seq_len=64, page_size=8,
        prefill_bucket=8, num_pages=10, prefix_cache=True,
        kv_spill_pages=8, spec_decode="fused", cache_dtype="int8")
    r0 = eng.add_request(S + [30])
    eng.run()
    for i in range(3):
        eng.add_request(list(range(60 + 8 * i, 76 + 8 * i)))
    eng.run()
    r1 = eng.add_request(S + [30])
    out = eng.run()
    assert len(out[r1]) == 10
    alloc = eng.g.cache.allocator
    assert alloc.free_pages + eng.prefix_cache.evictable_pages() \
        == alloc.num_pages
    assert eng.spill.free_slots + eng.spill.resident == eng.spill.capacity
    assert eng.prefix_cache.spilled_pages() == eng.spill.resident


def test_spill_pool_unit_roundtrip(rng):
    """HostSpillPool unit: spill -> swap_in round-trips the page bytes
    (all planes) and retires the slot; free_slot retires without upload;
    a full ring returns None."""
    cache = PagedKVCache(num_layers=2, num_pages=4, page_size=8,
                         num_kv_heads=2, head_dim=16, dtype="int8")
    assert cache.kv.shape == (2, 4, 2, 2, 8, 16)   # [L, pages, K|V, kvh, ..]
    kvq = jnp.asarray(rng.integers(-127, 128, cache.kv.shape), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.01, 0.02, cache.k_scale.shape),
                     jnp.float32)
    vs = jnp.asarray(rng.uniform(0.02, 0.03, cache.v_scale.shape),
                     jnp.float32)
    cache.update(kvq, ks, vs)
    kvq, ks = np.asarray(kvq), np.asarray(ks)   # the pool is donated below
    pool = HostSpillPool(cache, capacity=2)
    pool.warm()
    before = cache.page_planes(1)
    # every head's K and V of the page, all layers, and its scale rows
    assert np.array_equal(before[0], kvq[:, 1])
    assert np.array_equal(before[1], ks[:, :, 1])
    s0 = pool.spill(1)
    s1 = pool.spill(2)
    assert s0 is not None and s1 is not None
    assert pool.spill(3) is None          # ring full
    # clobber page 1 on device, then swap the spilled copy into page 3
    cache.update(*(jnp.zeros_like(a) for a in cache.arrays))
    pool.swap_in(s0, 3)
    after = cache.page_planes(3)
    for b, a in zip(before, after):
        assert (b == a).all()
    assert pool.free_slots == 1 and pool.resident == 1
    pool.free_slot(s1)
    assert pool.free_slots == 2 and pool.resident == 0
    with pytest.raises(KeyError):
        pool.free_slot(s1)                # double retire raises
    # the full-ring spill attempt was refused: only successes count
    assert pool.swapins == 1 and pool.spilled_pages == 2


def test_allocator_acquire_page_contract():
    alloc = PageAllocator(num_pages=2, page_size=8)
    p = alloc.acquire_page()
    assert alloc.ref_count(p) == 1
    alloc.acquire_page()
    with pytest.raises(MemoryError):
        alloc.acquire_page()
    alloc.release_page(p)
    assert alloc.acquire_page() == p      # recycled
    alloc.release_page(p)
    with pytest.raises(ValueError):
        alloc.release_page(p)             # double free raises


@pytest.mark.parametrize("cache_dtype", [None, "int8"])
def test_migration_of_spilled_prefix_ships_ring_bytes(cache_dtype):
    """Spill <-> migration interaction (ISSUE 14 satellite): exporting a
    parked session whose prefix pages were demoted to the host ring
    ships the RING bytes directly — zero swap-ins, no device
    round-trip — and the importer installs them verbatim (on the int8
    plane the migrated pool bytes are bit-identical: a migration is a
    memcpy of quantized bytes, not a dequant round-trip)."""
    from paddle_tpu.inference import migration as mig
    model = _tiny_model()
    S = list(range(1, 17))                # 2 full pages of 8

    def _eng():
        return ContinuousBatchingEngine(
            model, max_batch=2,
            gen=GenerationConfig(max_new_tokens=8, do_sample=False),
            max_seq_len=64, page_size=8, prefill_bucket=8, num_pages=8,
            prefix_cache=True, kv_spill_pages=8, cache_dtype=cache_dtype)

    eng = _eng()
    r0 = eng.add_request(S + [30])
    first = eng.run()[r0]
    for i in range(3):                    # crush the pool: S spills
        eng.add_request(list(range(60 + 8 * i, 76 + 8 * i)),
                        max_new_tokens=12)
    eng.run()
    assert eng.prefix_cache.spilled_pages() >= 2
    swapins0 = eng.spill.swapins
    snap = mig.export_session(eng, tokens=S)
    assert eng.spill.swapins == swapins0  # shipped WITHOUT swap-in
    assert eng.prefix_cache.spilled_pages() >= 2   # ...and still spilled
    assert [p["source"] for p in snap["pages"]] == ["spill", "spill"]

    dst = _eng()
    res = mig.import_session(dst, snap)
    assert res["imported"] == len(snap["pages"]) == 2
    if cache_dtype == "int8":
        # the quantized bytes (and their scale rows) moved verbatim
        nodes = dst.prefix_cache.chain(S)
        assert len(nodes) == 2
        for node, pg in zip(nodes, snap["pages"]):
            # the wire stays head-major (k, v, k_scale, v_scale)
            k, v = pa.heads_of_pool(dst.g.cache.kv)
            for plane, arr in zip(pg["planes"],
                                  (k, v, dst.g.cache.k_scale,
                                   dst.g.cache.v_scale)):
                assert np.array_equal(plane,
                                      np.asarray(arr[:, :, node.page]))
    r1 = dst.add_request(S + [30])
    out = dst.run()[r1]
    assert out == first                   # import, not recompute...
    assert dst.g.cache.allocator.prefix_hits >= 1
    assert dst.g.cache.allocator.prefix_tokens_saved >= 16


def test_spill_telemetry_counters_and_stats():
    model = _tiny_model()
    c0 = obs.metrics.counter("serving.kv.spilled_pages").value
    w0 = obs.metrics.counter("serving.kv.swapins").value
    h0 = obs.metrics.histogram("serving.kv.swapin_wait_ms").count
    _f, _o, eng = _pressure_scenario(model, spill=16)
    st = eng.stats()
    assert obs.metrics.counter("serving.kv.spilled_pages").value - c0 \
        == st["kv_spilled_pages"]
    assert obs.metrics.counter("serving.kv.swapins").value - w0 \
        == st["kv_swapins"]
    assert obs.metrics.histogram("serving.kv.swapin_wait_ms").count - h0 \
        == st["kv_swapins"]
    for key in ("kv_spill_capacity", "kv_spill_resident",
                "kv_spilled_pages", "kv_swapins"):
        assert key in st


def test_geometry_rule_asks_the_attached_tpu_for_its_smem(monkeypatch):
    """With no ``smem_bytes`` given, an int8 geometry on a TPU backend is
    held to the SMEM the attached device reports (``pltpu.get_tpu_info``);
    off a TPU the rule is left to the compiler."""
    import types

    import jax
    from jax.experimental.pallas import tpu as pltpu
    from paddle_tpu.kernels import paged_attention as pa

    kw = dict(quantized=True, kv_heads=32, num_pages=4096,
              table_shape=(8, 32))
    assert pa.kernel_geometry_error(32, 128, **kw) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        pltpu, "get_tpu_info",
        lambda: types.SimpleNamespace(smem_capacity_bytes=1 << 20))
    assert "SMEM" in pa.kernel_geometry_error(32, 128, **kw)
    assert pa.kernel_geometry_error(32, 128, **{**kw, "num_pages": 3968}) \
        is None
