"""Inference/serving stack tests: paged-attention kernel parity (interpret
mode), page allocator, paged decode vs full-recompute oracle, sampling, and
the Predictor API over a jit.save'd program.

Mirrors the reference's serving test surface around
block_multi_head_attention (paged KV) and AnalysisPredictor
(paddle/fluid/inference/api/analysis_predictor.h:105).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu.inference import (Config, GenerationConfig, LlamaGenerator,
                                  PagedKVCache, PageAllocator,
                                  create_predictor)
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------

def _mk_cache(rng, n_pages, page_size, kvh, d, dtype=jnp.float32):
    k = jnp.asarray(rng.standard_normal((kvh, n_pages, page_size, d)), dtype)
    v = jnp.asarray(rng.standard_normal((kvh, n_pages, page_size, d)), dtype)
    return k, v


@pytest.mark.parametrize("qh,kvh", [(4, 4), (8, 2)])
def test_paged_attention_reference_vs_dense(rng, qh, kvh):
    """The XLA fallback must equal dense masked attention on gathered pages."""
    d, page, B = 64, 8, 3
    n_pages = 12
    kc, vc = _mk_cache(rng, n_pages, page, kvh, d)
    q = jnp.asarray(rng.standard_normal((B, qh, d)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, n_pages, (B, 4)), jnp.int32)
    ctx = jnp.asarray([5, 17, 32], jnp.int32)

    out = pa._reference_paged_attention(q, kc, vc, bt, ctx)

    # dense oracle per sequence
    import math
    for b in range(B):
        keys = np.asarray(kc[:, bt[b]]).reshape(kvh, -1, d)[:, : int(ctx[b])]
        vals = np.asarray(vc[:, bt[b]]).reshape(kvh, -1, d)[:, : int(ctx[b])]
        group = qh // kvh
        for h in range(qh):
            hk = h // group
            s = np.asarray(q[b, h]) @ keys[hk].T / math.sqrt(d)
            p = np.exp(s - s.max())
            p = p / p.sum()
            expect = p @ vals[hk]
            np.testing.assert_allclose(np.asarray(out[b, h]), expect,
                                       rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("qh,kvh,dtype", [(4, 4, jnp.float32),
                                          (8, 2, jnp.float32),
                                          (8, 8, jnp.bfloat16)])
def test_paged_attention_kernel_parity(rng, qh, kvh, dtype):
    """Interpreter-mode Pallas kernel vs the XLA reference."""
    d, page, B = 128, 16, 4
    n_pages = 16
    kc, vc = _mk_cache(rng, n_pages, page, kvh, d, dtype)
    q = jnp.asarray(rng.standard_normal((B, qh, d)), dtype)
    bt = jnp.asarray(rng.integers(0, n_pages, (B, 6)), jnp.int32)
    ctx = jnp.asarray([1, 16, 40, 96], jnp.int32)

    expect = pa._reference_paged_attention(q, kc, vc, bt, ctx)
    old = flags.get_flags(["paged_attention_interpret"])
    flags.set_flags({"paged_attention_interpret": True})
    try:
        got = pa.paged_attention(q, kc, vc, bt, ctx)
    finally:
        flags.set_flags(old)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_write_kv_pages_scatter(rng):
    kvh, d, page = 2, 64, 8
    kc, vc = _mk_cache(rng, 4, page, kvh, d)
    k_new = jnp.asarray(rng.standard_normal((3, kvh, d)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((3, kvh, d)), jnp.float32)
    slots = jnp.asarray([0, 9, -1], jnp.int32)   # last token dropped
    # the pool is page-major; the assertions read it back head-major
    k2, v2 = pa.heads_of_pool(pa.write_kv_pages(
        pa.pool_of_heads(kc, vc), k_new, v_new, slots))
    # slot 0 = page 0 offset 0; slot 9 = page 1 offset 1
    np.testing.assert_allclose(np.asarray(k2[:, 0, 0]), np.asarray(k_new[0]))
    np.testing.assert_allclose(np.asarray(k2[:, 1, 1]), np.asarray(k_new[1]))
    np.testing.assert_allclose(np.asarray(v2[:, 1, 1]), np.asarray(v_new[1]))
    # slot -1: cache unchanged anywhere else
    mask = np.ones((4 * page,), bool)
    mask[[0, 9]] = False
    np.testing.assert_allclose(
        np.asarray(k2.reshape(kvh, -1, d)[:, mask]),
        np.asarray(kc.reshape(kvh, -1, d)[:, mask]))


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------

def test_page_allocator_lifecycle():
    a = PageAllocator(num_pages=8, page_size=4)
    s0 = a.allocate(0, 6)            # 2 pages
    assert s0.shape == (6,)
    assert a.free_pages == 6
    assert a.context_len(0) == 6
    s1 = a.extend(0, 3)              # crosses into a 3rd page
    assert a.context_len(0) == 9
    assert len(set(s0.tolist()) & set(s1.tolist())) == 0
    bt = a.block_table([0])
    assert bt.shape[1] == 3
    # slots must agree with the block table addressing
    pages = bt[0]
    expect0 = pages[0] * 4 + np.arange(4)
    np.testing.assert_array_equal(s0[:4], expect0)
    a.free(0)
    assert a.free_pages == 8


def test_page_allocator_exhaustion():
    a = PageAllocator(num_pages=2, page_size=4)
    a.allocate(0, 8)
    with pytest.raises(MemoryError):
        a.allocate(1, 1)


def test_page_allocator_double_free_keyerror_both_paths():
    """ISSUE 4 satellite: free()/release() raise a CLEAR KeyError on
    unknown AND double-freed seq ids on every path (free is explicitly
    not idempotent), and the refcounts make a page-level double free
    structurally impossible."""
    a = PageAllocator(num_pages=4, page_size=4)
    with pytest.raises(KeyError, match="seq id 3 not allocated"):
        a.free(3)
    with pytest.raises(KeyError, match="seq id 3 not allocated"):
        a.release(3)
    a.allocate(0, 4)
    page = a.page_list(0)[0]
    a.free(0)
    with pytest.raises(KeyError, match="seq id 0 not allocated"):
        a.free(0)
    with pytest.raises(KeyError, match="seq id 0 not allocated"):
        a.release(0)
    # the page went back exactly once; another release is refused
    assert a.free_pages == 4
    with pytest.raises(ValueError, match="double free"):
        a.release_page(page)


# ---------------------------------------------------------------------------
# end-to-end generation
# ---------------------------------------------------------------------------

def _tiny_model():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(num_hidden_layers=2, max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


def _oracle_greedy(model, prompt, n_new):
    """Full-recompute greedy decode through the eager model."""
    ids = list(prompt)
    out = []
    for _ in range(n_new):
        logits = model(paddle.to_tensor(np.asarray([ids], np.int32)))
        nxt = int(np.argmax(np.asarray(logits._data)[0, -1]))
        out.append(nxt)
        ids.append(nxt)
    return out


def test_generate_greedy_matches_full_recompute():
    model = _tiny_model()
    prompts = [[3, 14, 15, 9, 2, 6], [5, 3]]
    gen = LlamaGenerator(model, max_batch=2, max_seq_len=64, page_size=8,
                         prefill_bucket=8)
    got = gen.generate(prompts, GenerationConfig(max_new_tokens=8))
    for p, g in zip(prompts, got):
        expect = _oracle_greedy(model, p, 8)
        assert g == expect, f"paged decode diverged: {g} vs {expect}"


def test_generate_ragged_batch_and_reuse():
    """Different prompt lengths in one batch; generator reused across calls
    (allocator must fully recycle pages)."""
    model = _tiny_model()
    gen = LlamaGenerator(model, max_batch=3, max_seq_len=64, page_size=8,
                         prefill_bucket=8)
    for _ in range(2):
        outs = gen.generate([[1, 2, 3, 4, 5, 6, 7, 8, 9], [4], [7, 7, 7]],
                            GenerationConfig(max_new_tokens=4))
        assert all(len(o) == 4 for o in outs)
    assert gen.cache.allocator.free_pages == gen.cache.allocator.num_pages


def test_generate_eos_stops_early():
    model = _tiny_model()
    prompts = [[3, 1, 4]]
    gen = LlamaGenerator(model, max_batch=1, max_seq_len=64, page_size=8,
                         prefill_bucket=8)
    full = gen.generate(prompts, GenerationConfig(max_new_tokens=8))[0]
    eos = full[2]
    gen2 = LlamaGenerator(model, max_batch=1, max_seq_len=64, page_size=8,
                          prefill_bucket=8)
    stopped = gen2.generate(prompts, GenerationConfig(max_new_tokens=8,
                                                      eos_token_id=eos))[0]
    # generation stops at the FIRST occurrence of eos in the stream (the
    # tiny model may emit the chosen token before index 2)
    assert stopped == full[:full.index(eos) + 1]


def test_generate_sampling_deterministic_by_seed():
    model = _tiny_model()
    cfg = GenerationConfig(max_new_tokens=6, do_sample=True, temperature=0.8,
                           top_k=16, top_p=0.9, seed=42)
    a = paddle.inference.generate(model, [[2, 7, 1]], cfg)
    b = paddle.inference.generate(model, [[2, 7, 1]], cfg)
    assert a == b
    c = paddle.inference.generate(
        model, [[2, 7, 1]],
        GenerationConfig(max_new_tokens=6, do_sample=True, temperature=0.8,
                         top_k=16, top_p=0.9, seed=43))
    assert isinstance(c[0], list) and len(c[0]) == 6


# ---------------------------------------------------------------------------
# Predictor API
# ---------------------------------------------------------------------------

def test_predictor_over_saved_program(tmp_path):
    import paddle_tpu.nn as nn
    from paddle_tpu.static import InputSpec

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    path = str(tmp_path / "deploy")
    paddle.jit.save(net, path, input_spec=[InputSpec([2, 8], "float32")])

    config = Config(path)
    pred = create_predictor(config)
    names = pred.get_input_names()
    assert len(names) == 1

    x = np.random.default_rng(0).standard_normal((2, 8)).astype(np.float32)
    pred.get_input_handle(names[0]).copy_from_cpu(x)
    pred.run()
    out_names = pred.get_output_names()
    got = pred.get_output_handle(out_names[0]).copy_to_cpu()

    expect = net(paddle.to_tensor(x))
    np.testing.assert_allclose(got, np.asarray(expect._data), rtol=1e-5,
                               atol=1e-5)
    # convenience form
    got2 = pred.run([x])[0]
    np.testing.assert_allclose(got2, got)


# ---------------- continuous batching ----------------

def test_continuous_batching_parity_and_staggering(rng):
    from paddle_tpu.inference.generation import (
        ContinuousBatchingEngine, GenerationConfig, LlamaGenerator)
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    gc = GenerationConfig(max_new_tokens=5, do_sample=False)
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]

    base = LlamaGenerator(model, max_batch=4, max_seq_len=64,
                          page_size=8).generate(prompts, gc)

    # batch-at-once engine matches the static generator exactly (greedy)
    eng = ContinuousBatchingEngine(model, max_batch=4, gen=gc,
                                   max_seq_len=64, page_size=8)
    ids = [eng.add_request(p) for p in prompts]
    out = eng.run()
    assert [out[i] for i in ids] == base

    # more requests than slots: all complete, earlier ones still exact
    eng2 = ContinuousBatchingEngine(model, max_batch=2, gen=gc,
                                    max_seq_len=64, page_size=8)
    ids2 = [eng2.add_request(p) for p in prompts + [[2, 2], [9]]]
    out2 = eng2.run()
    assert all(len(out2[i]) == 5 for i in ids2)
    for i in range(3):
        assert out2[ids2[i]] == base[i]


def test_continuous_batching_mid_stream_admission(rng):
    """A request added while another is mid-decode gets picked up."""
    from paddle_tpu.inference.generation import (
        ContinuousBatchingEngine, GenerationConfig)
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    gc = GenerationConfig(max_new_tokens=4, do_sample=False)
    eng = ContinuousBatchingEngine(model, max_batch=2, gen=gc,
                                   max_seq_len=64, page_size=8)
    r1 = eng.add_request([1, 2, 3])
    eng.step()                       # r1 admitted + first decode
    r2 = eng.add_request([7, 8])     # joins while r1 is running
    results = {}
    while eng.has_work():
        for req in eng.step():
            results[req.req_id] = req.output
    assert len(results[r1]) == 4 and len(results[r2]) == 4


def test_continuous_batching_budget_and_eos_at_prefill(rng):
    from paddle_tpu.inference.generation import (
        ContinuousBatchingEngine, GenerationConfig)
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    gc = GenerationConfig(max_new_tokens=3, do_sample=False)
    eng = ContinuousBatchingEngine(model, max_batch=2, gen=gc,
                                   max_seq_len=64, page_size=8)
    # max_new_tokens=1 must yield exactly ONE token (the prefill sample)
    r1 = eng.add_request([1, 2, 3], max_new_tokens=1)
    out = eng.run()
    assert len(out[r1]) == 1

    # eos on the prefill token ends the request with a single eos
    first_tok = out[r1][0]
    gc2 = GenerationConfig(max_new_tokens=5, do_sample=False,
                           eos_token_id=first_tok)
    eng2 = ContinuousBatchingEngine(model, max_batch=2, gen=gc2,
                                    max_seq_len=64, page_size=8)
    r2 = eng2.add_request([1, 2, 3])
    out2 = eng2.run()
    assert out2[r2] == [first_tok]


def test_continuous_batching_exact_page_multiple_prompts(rng):
    """Regression: a prompt whose length is an exact page multiple must get
    a fresh page BEFORE its first decode write — with the stale table it
    corrupted another sequence's page 0."""
    from paddle_tpu.inference.generation import (
        ContinuousBatchingEngine, GenerationConfig, LlamaGenerator)
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    gc = GenerationConfig(max_new_tokens=6, do_sample=False)
    p8 = list(range(1, 9))            # len == page_size
    p16 = list(range(1, 17))          # len == 2 * page_size
    p3 = [5, 6, 7]
    prompts = [p3, p8, p16]
    base = LlamaGenerator(model, max_batch=4, max_seq_len=64,
                          page_size=8).generate(prompts, gc)
    eng = ContinuousBatchingEngine(model, max_batch=4, gen=gc,
                                   max_seq_len=64, page_size=8)
    ids = [eng.add_request(p) for p in prompts]
    out = eng.run()
    assert [out[i] for i in ids] == base


# ---------------------------------------------------------------------------
# ragged kernel edge cases (vs the reference oracles)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qh,kvh,ctx,max_pages", [
    # exact page multiples (ctx % page == 0), incl. a 1-page and a max-page
    # sequence in one ragged batch
    (4, 4, (8, 64, 16, 32), 8),
    # single-token contexts next to max-page ones
    (4, 2, (1, 64, 1, 40), 8),
    # GQA ratio 4, ragged mix: a KV block of 128 pages (1,024 keys) under
    # a table of 264, so the walk takes one, two and three blocks: contexts
    # inside a block, at its edge, one page past it, at the table's end
    (8, 2, (5, 1024, 1032, 2112), 264),
    # MQA-ish ratio 8, the same with odd ends inside the second and third
    # block and a single token
    (8, 1, (2050, 1, 1025, 777), 264),
])
def test_paged_attention_edge_cases_vs_oracle(rng, qh, kvh, ctx, max_pages):
    """Decode kernel vs the reference across the ragged edge shapes: page
    and block boundaries, single tokens, 1-page/max-page mixes, GQA
    ratios != 1."""
    d, page = 128, 8
    n_pages = 64
    B = len(ctx)
    assert pa._pages_per_block(page, max_pages) == min(max_pages, 128)
    kc, vc = _mk_cache(rng, n_pages, page, kvh, d)
    q = jnp.asarray(rng.standard_normal((B, qh, d)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, n_pages, (B, max_pages)), jnp.int32)
    cl = jnp.asarray(ctx, jnp.int32)

    expect = pa._reference_paged_attention(q, kc, vc, bt, cl)
    old = flags.get_flags(["paged_attention_interpret"])
    flags.set_flags({"paged_attention_interpret": True})
    try:
        got = pa.paged_attention(q, kc, vc, bt, cl)
    finally:
        flags.set_flags(old)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_block_and_tile_sizes_follow_from_the_shapes():
    """A KV block aims at 1,024 keys in whole pages and never passes the
    table; a row tile is the whole block up to 256 rows, else a multiple
    of 16 that divides it."""
    assert [pa._pages_per_block(p, w) for p, w in
            [(16, 160), (16, 776), (8, 264), (32, 34), (16, 10), (8, 8)]] \
        == [64, 64, 128, 32, 10, 8]
    assert [pa.row_tile(t, g) for t, g in
            [(1, 1), (1, 4), (1, 16), (8, 4), (64, 1), (64, 4), (64, 16),
             (64, 5), (24, 11)]] == [8, 8, 16, 32, 64, 256, 256, 160, 264]
    # the rows a call covers: a slot's q_len x group rows in whole tiles
    assert pa.attn_rows([64, 1, 0, 17], 64, 16) == 1024 + 256 + 512
    assert pa.attn_rows([64, 1, 0, 33], 64, 4) == 3 * 256
    assert pa.attn_rows([0, 0], 64, 4) == 0


def _schedule_case(rng, cache, *, kvh, group, T, ctx, ql, window=None):
    """One mixed-mode call on a long table (2,112 positions: a KV block is
    1,024 keys) with a float32, bf16 or int8 pool; (kernel output and lse,
    float32 oracle's) over the inputs as the pool holds them."""
    d = 128
    page = {"float32": 16, "bfloat16": 16, "int8": 32}[cache]
    W, n_pages, B = 2112 // page, 96, len(ctx)
    qdt = jnp.bfloat16 if cache == "bfloat16" else jnp.float32
    qh = kvh * group
    q = jnp.asarray(rng.standard_normal((B, T, qh, d)), qdt)
    kn = jnp.asarray(rng.standard_normal((B, T, kvh, d)), qdt)
    vn = jnp.asarray(rng.standard_normal((B, T, kvh, d)), qdt)
    bt = jnp.asarray(rng.integers(0, n_pages, (B, W)), jnp.int32)
    cl, qlens = jnp.asarray(ctx, jnp.int32), jnp.asarray(ql, jnp.int32)
    ks = vs = None
    if cache == "int8":
        kc = jnp.asarray(rng.integers(-127, 128, (kvh, n_pages, page, d)),
                         jnp.int8)
        vc = jnp.asarray(rng.integers(-127, 128, (kvh, n_pages, page, d)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.005, 0.02, (kvh, n_pages)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.005, 0.02, (kvh, n_pages)),
                         jnp.float32)
    else:
        kc, vc = _mk_cache(rng, n_pages, page, kvh, d, qdt)

    def f32(a):
        return a.astype(jnp.float32)

    ref = pa._reference_ragged_paged_attention(
        f32(q), kc if ks is not None else f32(kc),
        vc if vs is not None else f32(vc), bt, cl, qlens, f32(kn), f32(vn),
        k_scale=ks, v_scale=vs, window=window)
    old = flags.get_flags(["paged_attention_interpret"])
    flags.set_flags({"paged_attention_interpret": True})
    try:
        got = pa.ragged_paged_attention(
            q, pa.pool_of_heads(kc, vc), bt, cl, q_lens=qlens, k_new=kn,
            v_new=vn, k_scale=ks, v_scale=vs, window=window, with_lse=True)
    finally:
        flags.set_flags(old)
    return got, ref


def _assert_live_rows_match(got, ref, ql, cache):
    """Rows past q_lens[b] (all of an idle slot's) are don't-care.  bf16
    operands multiply exactly, so only the output's own rounding shows."""
    (out, lse), (ref_out, ref_lse) = got, ref
    assert np.isfinite(np.asarray(out.astype(jnp.float32))).all()
    tol = 1e-2 if cache == "bfloat16" else 5e-5
    for b, n in enumerate(ql):
        np.testing.assert_allclose(
            np.asarray(out[b, :n].astype(jnp.float32)),
            np.asarray(ref_out[b, :n]), rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(lse[b, :n]),
                                   np.asarray(ref_lse[b, :n]),
                                   rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("cache", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("T,ctx,ql", [
    # decode beside a chunk: contexts inside the first block, at its edge
    # (1,024), one page past it, at the second block's edge, the table's end
    (8, (300, 1024, 1056, 2048, 2104), (1, 8, 1, 5, 8)),
    # an idle slot (q_lens == 0) between live slots, contexts of none, two
    # blocks and a bit, exactly two blocks
    (8, (0, 2100, 1500, 2048), (8, 0, 3, 0)),
], ids=["block_edges", "idle_between_live"])
def test_block_walk_vs_oracle(rng, cache, T, ctx, ql):
    """The KV walk in blocks of many pages: the last block's tail is
    masked by position, a slot without work leaves its neighbours' rows
    right, and the bf16 and int8 pools multiply as stored."""
    got, ref = _schedule_case(rng, cache, kvh=2, group=4, T=T, ctx=ctx,
                              ql=ql)
    _assert_live_rows_match(got, ref, ql, cache)


@pytest.mark.parametrize("cache", ["float32", "bfloat16"])
@pytest.mark.parametrize("group,ql", [
    # 64 rows: one tile, the whole block
    (1, (1, 64, 33, 0)),
    # 256 rows: one tile again, whatever the slot holds of it
    (4, (1, 64, 33, 0)),
    # 1,024 rows in tiles of 256: one tile, four, 17 x 16 = 272 rows (a
    # second tile for sixteen of them), none
    (16, (1, 64, 17, 0)),
])
def test_row_tiles_follow_q_lens_inside_a_step_of_64(rng, cache, group, ql):
    """A decoding slot inside a T = 64 step computes one row tile, a
    ``q_len x group`` that is no multiple of the tile one more than it
    fills, and only the tiles past them are written as zeros."""
    assert pa.row_tile(64, group) == min(64 * group, 256)
    got, ref = _schedule_case(rng, cache, kvh=1, group=group, T=64,
                              ctx=(1030, 17, 2000, 64), ql=ql)
    _assert_live_rows_match(got, ref, ql, cache)
    out = np.asarray(got[0].astype(jnp.float32))
    tile_tokens = max(pa.row_tile(64, group) // group, 1)
    for b, n in enumerate(ql):
        covered = -(-n // tile_tokens) * tile_tokens
        assert not out[b, covered:].any()


def test_ragged_paged_attention_mixed_mode_parity(rng):
    """The mixed-mode kernel (prefill chunks + decode tokens in ONE
    pallas_call) vs the ragged reference AND a dense numpy oracle: ragged
    q_lens incl. empty rows, zero prior context, page-exact contexts."""
    import math
    d, page, kvh, qh, T = 128, 16, 2, 8, 8
    n_pages = 16
    B = 4
    kc, vc = _mk_cache(rng, n_pages, page, kvh, d)
    q = jnp.asarray(rng.standard_normal((B, T, qh, d)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, T, kvh, d)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, T, kvh, d)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, n_pages, (B, 6)), jnp.int32)
    ctx = jnp.asarray([0, 16, 33, 96], jnp.int32)     # incl. fresh prefill
    qlens = jnp.asarray([8, 1, 5, 0], jnp.int32)      # incl. an idle row

    ref, ref_lse = pa._reference_ragged_paged_attention(
        q, kc, vc, bt, ctx, qlens, kn, vn)
    old = flags.get_flags(["paged_attention_interpret"])
    flags.set_flags({"paged_attention_interpret": True})
    try:
        out, lse = pa.ragged_paged_attention(
            q, pa.pool_of_heads(kc, vc), bt, ctx, q_lens=qlens, k_new=kn,
            v_new=vn, with_lse=True)
    finally:
        flags.set_flags(old)
    group = qh // kvh
    for b in range(B):
        n = int(qlens[b])
        if n == 0:
            continue                      # rows past q_lens are don't-care
        np.testing.assert_allclose(np.asarray(out[b, :n]),
                                   np.asarray(ref[b, :n]),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse[b, :n]),
                                   np.asarray(ref_lse[b, :n]),
                                   rtol=2e-5, atol=2e-5)
        # dense oracle: cached context + causal prefix of the fresh rows
        c0 = int(ctx[b])
        keys = np.asarray(kc[:, bt[b]]).reshape(kvh, -1, d)[:, :c0]
        vals = np.asarray(vc[:, bt[b]]).reshape(kvh, -1, d)[:, :c0]
        for j in range(n):
            for h in range(qh):
                hk = h // group
                ks = np.concatenate(
                    [keys[hk], np.asarray(kn[b, :j + 1, hk])], 0)
                vs = np.concatenate(
                    [vals[hk], np.asarray(vn[b, :j + 1, hk])], 0)
                s = np.asarray(q[b, j, h]) @ ks.T / math.sqrt(d)
                p = np.exp(s - s.max())
                p = p / p.sum()
                np.testing.assert_allclose(np.asarray(out[b, j, h]), p @ vs,
                                           rtol=3e-5, atol=3e-5)


def test_paged_attention_kernel_under_shard_map(rng):
    """The ragged kernel inside shard_map on the 8-device CPU mesh: batch
    sharded over 'dp', KV pool replicated — per-shard results must match
    the unsharded reference to fp32 tolerance."""
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    assert len(devs) >= 8, "conftest forces an 8-device CPU platform"
    mesh = Mesh(np.asarray(devs[:8]).reshape(8), ("dp",))
    d, page, kvh, qh = 128, 8, 2, 4
    n_pages = 32
    B = 8                                  # one sequence per device
    kc, vc = _mk_cache(rng, n_pages, page, kvh, d)
    q = jnp.asarray(rng.standard_normal((B, qh, d)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, n_pages, (B, 8)), jnp.int32)
    ctx = jnp.asarray([1, 8, 64, 17, 32, 5, 40, 64], jnp.int32)

    expect = pa._reference_paged_attention(q, kc, vc, bt, ctx)

    def local(qb, kcb, vcb, btb, ctxb):
        return pa.paged_attention(qb, kcb, vcb, btb, ctxb)

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P("dp"), P(), P(), P("dp"), P("dp")),
                      out_specs=P("dp"), check_vma=False)
    old = flags.get_flags(["paged_attention_interpret"])
    flags.set_flags({"paged_attention_interpret": True})
    try:
        got = jax.jit(f)(q, kc, vc, bt, ctx)
    finally:
        flags.set_flags(old)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# recompile telemetry: warm serving steps must not compile anything
# ---------------------------------------------------------------------------

def test_assert_no_recompiles_counts_and_raises():
    from paddle_tpu.jit import assert_no_recompiles

    with assert_no_recompiles(record=True) as rec:
        jax.jit(lambda x: x * 3.0 + 1)(jnp.ones((3,)))
    assert rec.compiles >= 1               # a fresh jit definitely compiled
    with pytest.raises(AssertionError):
        with assert_no_recompiles():
            jax.jit(lambda x: x * 5.0 - 2)(jnp.ones((4,)))
    x = jnp.ones((8,))                     # eager fill compiles — outside
    with assert_no_recompiles():           # pure transfers are fine
        np.asarray(x)


def test_engine_warm_steps_zero_recompiles():
    """Acceptance: warm ContinuousBatchingEngine steps — admission chunks,
    decode steps and drains alike — trigger ZERO XLA compiles."""
    from paddle_tpu.inference.generation import ContinuousBatchingEngine
    from paddle_tpu.jit import assert_no_recompiles

    model = _tiny_model()
    gc = GenerationConfig(max_new_tokens=6, do_sample=False)
    eng = ContinuousBatchingEngine(model, max_batch=2, gen=gc,
                                   max_seq_len=64, page_size=8,
                                   prefill_bucket=8)
    # warmup: one full lifecycle compiles the T=bucket and T=1 steps
    for p in ([1, 2, 3], [4, 5]):
        eng.add_request(p)
    eng.run()

    with assert_no_recompiles():
        rids = [eng.add_request(p) for p in
                ([5, 6, 7], [8, 9], [1, 4, 1, 4, 1, 4, 1, 4, 1])]
        out = eng.run()
    assert all(len(out[r]) == 6 for r in rids)


def test_engine_prefix_hits_zero_recompiles():
    """ISSUE 4 satellite: warm engine steps with PREFIX-CACHE HITS —
    partial-page hits, full-match COW admissions, concurrent same-batch
    sharing (gated rows) and LRU-parked re-hits — trigger ZERO XLA
    compiles; the cache can never reintroduce per-shape programs."""
    from paddle_tpu.inference.generation import ContinuousBatchingEngine
    from paddle_tpu.jit import assert_no_recompiles

    model = _tiny_model()
    gc = GenerationConfig(max_new_tokens=6, do_sample=False)
    eng = ContinuousBatchingEngine(model, max_batch=2, gen=gc,
                                   max_seq_len=64, page_size=8,
                                   prefill_bucket=8, prefix_cache=True)
    S = list(range(1, 17))                 # 2 full pages
    # warmup: one miss + hit + full-match (COW) lifecycle compiles the
    # T=bucket/T=1 steps and the page-copy program
    for p in ([1, 2, 3], S, S + [4, 5], S):
        eng.add_request(p)
    eng.run()
    with assert_no_recompiles():
        rids = [eng.add_request(p) for p in
                (S + [9], S, S + [4, 5], S + [9], [7, 8, 9])]
        out = eng.run()
    assert all(len(out[r]) == 6 for r in rids)
    st = eng.stats()
    assert st["prefix_hits"] >= 4 and st["cow_copies"] >= 1


def test_engine_capacity_frozen_output_trimmed():
    """A request frozen at cache capacity must return exactly the tokens
    that physically fit (max_seq - prompt), not frozen-repeat padding."""
    from paddle_tpu.inference.generation import ContinuousBatchingEngine

    model = _tiny_model()
    eng = ContinuousBatchingEngine(
        model, max_batch=2, gen=GenerationConfig(max_new_tokens=50),
        max_seq_len=16, page_size=8, prefill_bucket=8)
    r = eng.add_request(list(range(1, 11)))      # 10-token prompt
    out = eng.run()
    assert len(out[r]) == 16 - 10


def test_engine_undersized_pool_finalizes_early():
    """With num_pages below the dense worst case, a sequence whose decode
    growth finds the pool dry finalizes early (capped output) instead of
    crashing, and every page returns to the free list."""
    from paddle_tpu.inference.generation import ContinuousBatchingEngine

    model = _tiny_model()
    eng = ContinuousBatchingEngine(
        model, max_batch=2, gen=GenerationConfig(max_new_tokens=40),
        max_seq_len=64, page_size=8, prefill_bucket=8, num_pages=3)
    a = eng.add_request([1, 2, 3, 4, 5])
    b = eng.add_request([7, 8, 9])
    out = eng.run()
    assert len(out[a]) >= 1 and len(out[b]) >= 1
    alloc = eng.g.cache.allocator
    assert alloc.free_pages == alloc.num_pages
    assert alloc.stats()["peak_in_use"] == 3


def test_generator_warm_generate_zero_recompiles():
    from paddle_tpu.jit import assert_no_recompiles

    model = _tiny_model()
    gen = LlamaGenerator(model, max_batch=2, max_seq_len=64, page_size=8,
                         prefill_bucket=8)
    gc = GenerationConfig(max_new_tokens=4)
    prompts = [[1, 2, 3, 4, 5], [7, 8]]
    first = gen.generate(prompts, gc)
    with assert_no_recompiles():
        again = gen.generate(prompts, gc)
    assert again == first


def test_generate_moe_model_matches_full_recompute():
    """MoE serving (r5): the routed expert FFN runs in prefill AND decode;
    greedy paged decode must match the model's own full-recompute forward
    token for token."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    import dataclasses

    paddle.seed(7)
    # grouped dispatch drops nothing, exactly like the serving FFN — the
    # capacity formulations drop overflow tokens, which would make the
    # full-recompute oracle itself diverge from routed-exact serving
    cfg = dataclasses.replace(LlamaConfig.mixtral_tiny(),
                              moe_dispatch="grouped", moe_block_m=8)
    model = LlamaForCausalLM(cfg)
    prompts = [[3, 14, 15, 9, 2, 6], [5, 3]]
    gen = LlamaGenerator(model, max_batch=2, max_seq_len=64, page_size=8,
                         prefill_bucket=8)
    got = gen.generate(prompts, GenerationConfig(max_new_tokens=8))
    for p, g in zip(prompts, got):
        expect = _oracle_greedy(model, p, 8)
        assert g == expect, f"MoE paged decode diverged: {g} vs {expect}"
