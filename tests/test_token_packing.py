"""The mixed serving step multiplies only the tokens it holds (ISSUE 25):
inside the T=bucket step program everything that is per token runs over a
packed ``[rows, H]`` array, ``rows`` the smaller of two row buckets that
holds the step's query tokens.  Packed against dense at every bucket, the
bucket rule, which programs hold a pack operation, and an engine that
serves the same tokens either way.  The test engines are far smaller than
``MIN_GEMM_ROWS``, so the tests lower that floor for themselves."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (ContinuousBatchingEngine,
                                  GenerationConfig, LlamaGenerator)
from paddle_tpu.inference import generation
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

B, T, PAGE, MAX_LEN, FLOOR = 4, 16, 8, 64, 8
BUCKETS = [8, 16, 32, 64]


FAMILY = [B * T // 4, B * T]      # what an engine of 4 x 16 places compiles


@pytest.fixture
def low_floor(monkeypatch):
    monkeypatch.setattr(generation, "MIN_GEMM_ROWS", FLOOR)


def _model(kind):
    paddle.seed(11)
    if kind == "moe":
        # grouped dispatch: the expert-sorted ragged GEMM the Mixtral cell
        # serves with; padding rows ride the router and an expert's tile
        cfg = dataclasses.replace(LlamaConfig.mixtral_tiny(),
                                  moe_dispatch="grouped", moe_block_m=8)
    else:
        cfg = LlamaConfig.tiny()
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module", params=["dense", "moe"])
def primed(request):
    """A generator whose pool already holds context: slots 0 and 1 have
    prefilled 16 and 5 tokens (one dense step), slot 2's cursor stands two
    tokens short of ``max_seq_len``."""
    g = LlamaGenerator(_model(request.param), max_batch=B,
                       max_seq_len=MAX_LEN, page_size=PAGE,
                       prefill_bucket=T)
    bt = jnp.asarray(np.arange(B * g.pages_per_seq, dtype=np.int32).reshape(
        B, g.pages_per_seq))
    rng = np.random.default_rng(3)
    vocab = g.config.vocab_size
    toks = jnp.asarray(rng.integers(1, vocab, (B, T)).astype(np.int32))
    ql = jnp.asarray(np.array([16, 5, 0, 0], np.int32))
    pos = jnp.zeros((B,), jnp.int32)
    _, cache, _ = g._forward_tokens(g.params, tuple(g.cache.arrays), toks,
                                    ql, pos, bt)
    return g, bt, cache, rng


# q_lens and write cursors of the step under test.  Slot 0 decodes (one
# token after its 16), slot 1 goes on prefilling, slot 2 runs into
# ``max_seq_len`` (two of its tokens have no place), slot 3 starts a prompt
# or, with 0 tokens, is idle, finished or gated.
RAGGED = {
    "decode+chunks+row_at_max_len": ([1, 11, 4, 16], [16, 5, MAX_LEN - 2, 0]),
    "zero_length_rows": ([1, 0, 0, 7], [16, 5, 0, 0]),
    "decode_only_rows": ([1, 1, 0, 0], [16, 5, 0, 0]),
    "every_slot_a_full_chunk": ([16, 16, 16, 16], [16, 5, 0, 0]),
    "nothing_live": ([0, 0, 0, 0], [16, 5, 0, 0]),
}


@pytest.mark.parametrize("case", sorted(RAGGED))
def test_packed_forward_equals_dense_at_every_bucket(primed, case):
    """Hidden states of the live tokens and the committed pool, packed
    against dense, at every bucket that holds the step's tokens."""
    g, bt, cache, rng = primed
    ql_np, pos_np = (np.asarray(a, np.int32) for a in RAGGED[case])
    toks = jnp.asarray(rng.integers(1, g.config.vocab_size,
                                    (B, T)).astype(np.int32))
    ql, pos = jnp.asarray(ql_np), jnp.asarray(pos_np)
    want_h, want_cache, _ = g._forward_tokens(g.params, cache, toks, ql, pos,
                                              bt)
    live = np.arange(T)[None, :] < ql_np[:, None]
    tried = 0
    for rows in BUCKETS:
        if rows < ql_np.sum():
            continue
        tried += 1
        got_h, got_cache, _ = jax.jit(functools.partial(
            g._forward_tokens, rows=rows))(g.params, cache, toks, ql, pos, bt)
        assert got_h.shape == want_h.shape
        np.testing.assert_allclose(np.asarray(got_h)[live],
                                   np.asarray(want_h)[live],
                                   rtol=2e-5, atol=2e-5, err_msg=str(rows))
        if rows < B * T:       # a place past its slot's tokens reads zero
            assert not np.asarray(got_h)[~live].any()
        for a, b in zip(got_cache, want_cache):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=str(rows))
    assert tried >= 1


def test_pack_plan_by_hand():
    """ql = [2, 0, 3, 1] into 8 rows: slot 0 takes rows 0-1, slot 2 rows
    2-4, slot 3 row 5; rows 6 and 7 are padding."""
    live, src, dst = generation._pack_plan(
        jnp.asarray([2, 0, 3, 1], jnp.int32), 4, 8)
    assert live.tolist() == [True] * 6 + [False] * 2
    assert src.tolist()[:6] == [0, 1, 8, 9, 10, 12]
    assert all(0 <= s < 16 for s in src.tolist())
    assert dst.tolist() == [[0, 1, 8, 8], [8, 8, 8, 8], [2, 3, 4, 8],
                            [5, 8, 8, 8]]


# ---------------------------------------------------------------------------
# the bucket rule
# ---------------------------------------------------------------------------

class _Shape:
    """What the rule reads of a generator."""
    row_buckets = LlamaGenerator.row_buckets
    gemm_rows = LlamaGenerator.gemm_rows

    def __init__(self, max_batch):
        self.max_batch = max_batch


@pytest.mark.parametrize("max_batch,t,want", [
    (32, 64, [512, 2048]),                 # both serving cells
    (32, 1, [32]),                         # decode-only: under the floor
    (24, 64, [384, 1536]),
    (64, 128, [2048, 8192]),
    (8, 64, [256, 512]),                   # the floor, not a quarter
    (4, 64, [256]),                        # the grid IS the floor
    (2, 8, [16]),
])
def test_row_buckets(max_batch, t, want):
    assert _Shape(max_batch).row_buckets(t) == want


@pytest.mark.parametrize("q_tokens,want", [
    (0, 512), (1, 512), (133, 512), (512, 512), (513, 2048), (580, 2048),
    (2048, 2048)])
def test_gemm_rows_is_the_smallest_bucket_that_holds_the_tokens(q_tokens,
                                                                want):
    assert _Shape(32).gemm_rows(64, q_tokens) == want
    assert _Shape(32).gemm_rows(1, min(q_tokens, 32)) == 32


def _engine(kind="dense", **kw):
    kw.setdefault("gen", GenerationConfig(max_new_tokens=6))
    return ContinuousBatchingEngine(
        _model(kind), max_batch=B, max_seq_len=MAX_LEN, page_size=PAGE,
        prefill_bucket=T, **kw)


def _packs(lowered):
    """How often the pack and unpack scopes stand in a lowered program's
    operation names."""
    text = lowered.as_text(debug_info=True)
    assert "/attention/" in text         # scope names are in the text
    return len(re.findall(r"/token_pack/", text)), \
        len(re.findall(r"/token_unpack/", text))


def test_only_the_mixed_steps_small_buckets_hold_a_pack_operation(low_floor):
    eng = _engine()
    g = eng.g
    assert g.row_buckets(T) == FAMILY and g.row_buckets(1) == [B]
    for rows in BUCKETS[:-1]:          # any row count lowers, not only 16
        lowered = eng.lowered_step(T, rows)
        assert min(_packs(lowered)) > 0, rows
        assert lowered.as_text().startswith(
            f"module @jit_serve_step_T{T} ")               # one name
    # B x T rows is the dense program: the same jitted object, the same text
    assert g._step_jit(eng.gen_cfg, T, False, B * T) is \
        g._step_jit(eng.gen_cfg, T, False)
    for lowered in (eng.lowered_step(T, B * T), eng.lowered_step(T),
                    eng.lowered_step(1)):
        assert _packs(lowered) == (0, 0)


@pytest.mark.parametrize("mode", ["ngram", "fused"])
def test_speculative_programs_hold_no_pack_operation(low_floor, mode):
    eng = _engine(spec_decode=mode, spec_k=4)
    g = eng.g
    state = (eng.positions, eng.finished, eng.counts, eng.budgets,
             eng._caps_dev, eng._bt_dev, eng.key)
    if mode == "ngram":
        hist, hist_len = eng._hist.device_arrays()
        lowered = g._spec_jit(eng.gen_cfg, 4, eng.spec.ngram_max).lower(
            g.params, g.cache.arrays, eng.tokens, eng._recent, hist,
            hist_len, *state)
    else:
        lowered = g._fused_jit(eng.gen_cfg, 4).lower(
            g.params, g.cache.arrays, eng.tokens, *state)
    assert _packs(lowered) == (0, 0)
    # the same engine's mixed step packs (the ngram ring rides it as well)
    assert min(_packs(eng.lowered_step(T, FLOOR))) > 0


# ---------------------------------------------------------------------------
# the engine serves the same tokens either way
# ---------------------------------------------------------------------------

def _serve(eng, seed):
    """A mixed arrival pattern: prompts of 3-40 tokens handed in between
    steps, so chunks, decode rows and idle slots share steps."""
    rng = np.random.default_rng(seed)
    vocab = eng.g.config.vocab_size
    seen = []
    inner = eng.g.gemm_rows
    eng.g.gemm_rows = lambda t, n: seen.append((t, inner(t, n))) or seen[-1][1]
    for n in (5, 40, 17, 3, 33, 9, 26):
        eng.submit(rng.integers(1, vocab, n).tolist())
        eng.step()
        eng.step()
    return eng.run(), seen


@pytest.mark.parametrize("kind,sampled", [("dense", False), ("dense", True),
                                          ("moe", False)])
def test_engine_serves_the_same_tokens_packed_and_dense(monkeypatch, kind,
                                                        sampled):
    gen = GenerationConfig(max_new_tokens=6, do_sample=sampled,
                           temperature=0.8, top_k=20, seed=5)
    dense, rows_dense = _serve(_engine(kind, gen=gen), 1)
    assert {r for t, r in rows_dense if t == T} == {B * T}
    monkeypatch.setattr(generation, "MIN_GEMM_ROWS", FLOOR)
    packed, rows_packed = _serve(_engine(kind, gen=gen), 1)
    assert packed == dense and len(packed) == 7
    assert {r for t, r in rows_packed if t == T} == set(FAMILY)
    assert {r for t, r in rows_packed if t == 1} == {B}


def test_offline_generate_takes_the_same_rule(low_floor):
    """All rows prefill together, so the rule lands on the dense grid or
    near it; a second call compiles nothing."""
    from paddle_tpu.jit import assert_no_recompiles
    g = LlamaGenerator(_model("dense"), max_batch=B, max_seq_len=MAX_LEN,
                       page_size=PAGE, prefill_bucket=T)
    gc = GenerationConfig(max_new_tokens=5)
    prompts = [list(range(1, 41)), [7, 8, 9], list(range(3, 20)), [5]]
    first = g.generate(prompts, gc)
    rows = sorted(k[3] for k in g._jit_cache if k[1] == T)
    assert rows == [16, 64]              # chunks of 16+3+16+1, 16+1, 8
    with assert_no_recompiles():
        assert g.generate(prompts, gc) == first
    dense = LlamaGenerator(_model("dense"), max_batch=B, max_seq_len=MAX_LEN,
                           page_size=PAGE, prefill_bucket=T)
    dense.row_buckets = lambda t: [B * t]
    assert dense.generate(prompts, gc) == first


# ---------------------------------------------------------------------------
# the layer scan reads each layer's expert banks where they lie (PR 30)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def primed_two_periods():
    """Mixtral-tiny at widths the kernel's tiles divide (128), two layers =
    two periods of the scan, the pool holding 16 and 5 tokens of context."""
    paddle.seed(11)
    cfg = LlamaConfig.mixtral_tiny(hidden_size=128, intermediate_size=128,
                                   moe_dispatch="grouped", moe_block_m=8)
    model = LlamaForCausalLM(cfg)
    g = LlamaGenerator(model, max_batch=B, max_seq_len=MAX_LEN,
                       page_size=PAGE, prefill_bucket=T)
    assert g.spec.periods == 2 and g.spec.moe.dispatch == "grouped"
    # the banks come unstacked, and are the model's own arrays: no copy
    place, = g.params["blocks"]
    for name in generation.EXPERT_BANKS:
        assert isinstance(place[name], tuple) and len(place[name]) == 2
        assert place[name][1] is dict(
            model.llama.layers[1].named_parameters())[name]._data
    bt = jnp.asarray(np.arange(B * g.pages_per_seq, dtype=np.int32).reshape(
        B, g.pages_per_seq))
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, T)).astype(
        np.int32))
    _, cache, _ = g._forward_tokens(
        g.params, tuple(g.cache.arrays), toks,
        jnp.asarray([16, 5, 0, 0], jnp.int32), jnp.zeros((B,), jnp.int32), bt)
    return g, bt, cache


@pytest.mark.parametrize("kernel", ["interpreted", "xla_reference"])
@pytest.mark.parametrize("step", ["mixed", "decode"])
def test_scanned_layers_read_their_own_banks_bit_for_bit(
        primed_two_periods, monkeypatch, step, kernel):
    """Two periods of a Mixtral-tiny stack: the logits, hidden states and
    pool of a mixed and of a decode step on unstacked banks (the scan's body
    picks the layer's by the period's number) are those of the same weights
    stacked and sliced by the scan, as PR 29 ran them, to the bit."""
    from paddle_tpu import flags
    g, bt, cache = primed_two_periods
    monkeypatch.setitem(flags._VALUES, "grouped_matmul_interpret",
                        kernel == "interpreted")
    t = T if step == "mixed" else 1
    ql, pos = ([1, 11, 4, 16], [16, 5, MAX_LEN - 2, 0]) \
        if step == "mixed" else ([1, 1, 0, 1], [16, 5, 0, 0])
    toks = jnp.asarray(np.random.default_rng(5).integers(
        1, g.config.vocab_size, (B, t)).astype(np.int32))
    stacked = {**g.params, "blocks": tuple(
        {n: jnp.stack(a) if isinstance(a, tuple) else a
         for n, a in place.items()} for place in g.params["blocks"])}

    def served(params):
        h, pool, _ = jax.jit(g._forward_tokens)(
            params, cache, toks, jnp.asarray(ql, jnp.int32),
            jnp.asarray(pos, jnp.int32), bt)
        return [np.asarray(g._head_logits(params, h[:, 0])),
                np.asarray(h)] + [np.asarray(a) for a in pool]

    got, want = served(g.params), served(stacked)
    assert all(np.array_equal(a, b) for a, b in zip(got, want))
    assert np.abs(got[0]).max() > 0
