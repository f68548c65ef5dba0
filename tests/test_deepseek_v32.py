"""``models/deepseek_v32.py`` through the serving engine at test size (a
64-wide rope part, 128-wide index keys, ``index_topk`` 32): prefill in
chunks and decoding through the three-plane pool against the plain
reference's full forward (``chipbench/references/deepseek_v32.py``: the
expanded form, its own index and selection, float32) over contexts under
and over the test ``index_topk``; the program's chosen sets against the
reference's; the group-limited choice; the sixteen shares of a layer; what
stands on the page pool (COW, the prefix cache, the speculative lanes)
with the index plane; the spec, the presets and every refusal."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu.inference import ContinuousBatchingEngine, migration
from paddle_tpu.inference import generation as gen
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.kernels import latent_index as li
from paddle_tpu.models.decoder_spec import (DecoderSpec, LatentAttn,
                                            LatentIndex, LayerKind, MoeSpec,
                                            RopeYarn)
from paddle_tpu.models.deepseek_v32 import (DeepseekV32Config,
                                            DeepseekV32ForCausalLM)
from paddle_tpu.models.llama import _route_topk
import paddle_tpu.observability as obs
from paddle_tpu.observability import metrics

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.references import deepseek_v32 as ref  # noqa: E402

GEOMETRY = dict(max_batch=4, max_seq_len=256, page_size=16, prefill_bucket=64)
# contexts under the test index_topk (5, 20), over it (70: a chunk that
# straddles 32; 130: three chunks) and decoding on from each
PROMPTS = (130, 5, 70, 20)
SOURCE_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "index_n_heads", "index_head_dim",
    "index_topk", "rms_norm_eps", "rope_theta", "rope_scaling",
    "num_experts_per_tok", "n_shared_experts", "n_group", "topk_group",
    "routed_scaling_factor", "first_k_dense_replace", "vocab_size",
    "num_hidden_layers")


def _prompts(vocab):
    rng = np.random.default_rng(1)
    return [list(rng.integers(1, vocab, n)) for n in PROMPTS]


def _seeded(model, key):
    """Biases and norms that matter: a selection bias wide enough to move a
    choice, a LayerNorm bias that is not zero."""
    keys = iter(jax.random.split(jax.random.key(key), 8))
    for layers in (*model.leading, model.experts):
        for name, p in layers._parameters.items():
            if name.endswith("gate.bias"):
                p._data = 0.2 * jax.random.normal(next(keys), p._data.shape,
                                                  jnp.float32)
            if name.endswith("k_norm.bias"):
                p._data = 0.1 * jax.random.normal(next(keys), p._data.shape,
                                                  p._data.dtype)
    return model


def _model(held=8, offset=0, **kw):
    """A tiny model that holds ``held`` of its 8 experts (the banks are the
    uncut model's, sliced)."""
    paddle.seed(0)
    full = _seeded(DeepseekV32ForCausalLM(DeepseekV32Config.tiny(**kw)), 5)
    if held == 8:
        return full
    paddle.seed(0)
    model = DeepseekV32ForCausalLM(DeepseekV32Config.tiny(
        experts_held=held, expert_offset=offset, **kw))
    for mine, whole in ((model.experts, full.experts),
                        (model.leading[0], full.leading[0])):
        for name, p in mine._parameters.items():
            a = whole._parameters[name]._data
            p._data = a[offset:offset + held] \
                if name.startswith("mlp.experts_") else a
    for name in ("embed_tokens", "norm", "lm_head"):
        getattr(model, name)._data = getattr(full, name)._data
    return model


def _reference_model(model):
    """(m, get_layer, flat) as the harness hands them to the reference:
    ``Run.model``'s keys from the model's config, the model's own arrays."""
    c = model.config
    m = {k: getattr(c, k) for k in SOURCE_KEYS}
    m["n_routed_experts"] = c.experts_held
    m["published"] = {"n_routed_experts": c.n_routed_experts}
    m["share"] = {"chips": c.n_routed_experts // c.experts_held,
                  "index": c.expert_offset // c.experts_held}
    params = model.serving_params()
    k = c.first_k_dense_replace

    def get_layer(l):
        if l < k:
            return dict(params["leading"][l])
        out = {}
        for n, a in params["blocks"][0].items():
            out[n] = jnp.stack(a)[l - k] if isinstance(a, tuple) else a[l - k]
        return out

    flat = {n: params[n] for n in ("embed", "norm", "head")}
    return m, get_layer, flat


def _reference_logits(model, seqs):
    m, get_layer, flat = _reference_model(model)
    with jax.default_matmul_precision("highest"):
        return ref.sequence_logits(
            get_layer, flat, m["num_hidden_layers"], m, seqs,
            [list(range(len(s))) for s in seqs])


@pytest.fixture
def interpreted(request):
    flags.set_flags({"paged_attention_interpret": bool(request.param)})
    yield request.param
    flags.set_flags({"paged_attention_interpret": False})


# ------------------------------------------------- engine == reference ----

@pytest.mark.parametrize("interpreted", [False, True], indirect=True,
                         ids=["oracles", "kernels_interpreted"])
@pytest.mark.parametrize("held,offset", [(8, 0), (2, 4)],
                         ids=["uncut", "share_2_of_4"])
def test_engine_serves_what_the_references_full_forward_gives(
        interpreted, held, offset):
    """Four prompts admitted together (130, 5, 70, 20 tokens: contexts under
    and over ``index_topk`` 32, a chunk that straddles it), chunked prefill
    then ten decode steps through the three-plane pool: every served token
    is the plain reference's first choice on the same sequence (its own
    index scores, its own selection, the expanded attention)."""
    model = _model(held, offset)
    prompts = _prompts(model.config.vocab_size)
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    out = eng.run()
    seqs = [p + out[r.req_id][:-1] for p, r in zip(prompts, reqs)]
    for p, r, lg in zip(prompts, reqs, _reference_logits(model, seqs)):
        assert out[r.req_id] == list(lg[len(p) - 1:].argmax(-1))


def test_the_models_own_forward_is_the_references():
    """Logits, not first choices: the model's own expanded forward over a
    sequence of 150 tokens against the reference's full forward, both
    float32: they differ by the order of their sums, bound at 2e-4 of the
    largest logit.  (The engine's core against the same reference, logits
    too: ``tests/chipbench/test_chipbench_deepseek_v32.py``.)"""
    model = _model()
    rng = np.random.default_rng(5)
    ids = rng.integers(1, 256, 150).tolist()
    want = _reference_logits(model, [ids])[0]
    own = np.asarray(model(paddle.to_tensor(np.asarray([ids])))._data)[0]
    assert np.max(np.abs(own - want)) < 2e-4 * max(1.0, np.abs(want).max())
    assert (own.argmax(-1) == want.argmax(-1)).mean() > 0.98


def test_the_programs_chosen_sets_are_the_references():
    """One layer's index on the same activations: the program's scores
    (paged keys and the step's own, a chunk of 64 at context 70) and its
    selection against the reference's ``index_scores`` / ``chosen_set``,
    written apart: the same 32 positions of every query token, float32."""
    rng = np.random.default_rng(9)
    S, T, heads, dim, top_k = 134, 64, 4, 128, 32
    ctx = S - T
    q_i = jnp.asarray(rng.normal(size=(S, heads, dim)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(S, heads)), jnp.float32)
    k_i = jnp.asarray(rng.normal(size=(S, dim)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.chosen_set(
            ref.index_scores(q_i[ctx:], w[ctx:], k_i, ctx), ctx, top_k))
    # the program: the first 70 keys paged (pages in another order), the
    # chunk's own 64 handed in beside them
    pages = 9
    order = rng.permutation(pages)
    plane = np.zeros((pages, 16, dim), np.float32)
    padded = np.zeros((pages * 16, dim), np.float32)
    padded[:ctx] = np.asarray(k_i[:ctx])
    plane[order] = padded.reshape(pages, 16, dim)
    scores = li.latent_index_scores(
        q_i[None, ctx:], w[None, ctx:], jnp.asarray(plane),
        jnp.asarray(order[None], jnp.int32), jnp.asarray([ctx], jnp.int32),
        k_new=k_i[None, ctx:])
    pos = ctx + jnp.arange(T)[None]
    got = np.asarray(li.latent_index_select(
        scores, jnp.minimum(pos + 1, top_k)))[0]
    both = np.concatenate([got[:, :ctx], got[:, pages * 16:]], axis=1)
    np.testing.assert_array_equal(both, want)
    assert (both.sum(-1) == top_k).all()


# ------------------------------------------------------------ routing ----

def test_a_strong_expert_in_a_weak_group_is_not_chosen():
    """Hand-made scores, 8 experts in 4 groups of 2 of which 2 are kept, 2
    chosen: expert 0 has the largest score of all and its group the
    smallest sum of two, so the choice falls inside the two strongest
    groups; ``groups=None`` is today's choice to the bit."""
    logit = lambda p: float(np.log(p / (1 - p)))        # noqa: E731
    want_s = np.asarray([[.9, .01, .6, .5, .55, .5, .2, .1],
                         [.3, .3, .9, .05, .1, .1, .5, .45]], np.float32)
    x = jnp.eye(2, dtype=jnp.float32)
    w = jnp.asarray(np.vectorize(logit)(want_s), jnp.float32)
    v, i, _, _ = _route_topk(x, w, 2, "sigmoid", scale=2.5, groups=4,
                             groups_kept=2)
    i, v = np.asarray(i), np.asarray(v)
    # token 0: groups sum .91, 1.1, 1.05, .3 -> groups 1 and 2 -> 2 and 4
    assert sorted(i[0]) == [2, 4]
    # token 1: groups .6, .95, .2, .95 -> groups 1 and 3 -> 2 and 6
    assert sorted(i[1]) == [2, 6]
    chosen = np.take_along_axis(want_s, i, -1)
    np.testing.assert_allclose(v, 2.5 * chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)
    plain_v, plain_i, _, _ = _route_topk(x, w, 2, "sigmoid", scale=2.5)
    assert sorted(np.asarray(plain_i)[0]) == [0, 2]      # ungrouped: 0 wins
    # a bias selects groups and experts, and is not in the gate
    bias = jnp.asarray([0, 0, 0, 0, 0, 0, 1.0, 1.0], jnp.float32)
    vb, ib, _, _ = _route_topk(x, w, 2, "sigmoid", bias=bias, scale=2.5,
                               groups=4, groups_kept=2)
    assert sorted(np.asarray(ib)[0]) == [6, 7]
    np.testing.assert_allclose(np.asarray(vb)[0].sum(), 2.5, rtol=1e-6)
    # the reference's router, written apart, agrees entry for entry
    m = {"num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
         "routed_scaling_factor": 2.5}
    gates = np.asarray(ref.router_gates(
        x, {"mlp.gate.weight": w, "mlp.gate.bias": bias}, m))
    np.testing.assert_allclose(np.take_along_axis(gates, np.asarray(ib), -1),
                               np.asarray(vb), rtol=1e-5)
    assert np.count_nonzero(gates) == 2 * 2


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_without_groups_the_choice_is_todays_to_the_bit(bias):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 8)) / 6, jnp.float32)
    b = jnp.asarray(rng.normal(size=8) / 5, jnp.float32) if bias else None
    a = _route_topk(x, w, 3, "sigmoid", bias=b, scale=2.5)
    c = _route_topk(x, w, 3, "sigmoid", bias=b, scale=2.5, groups=None,
                    groups_kept=None)
    for u, v in zip(a, c):
        assert np.array_equal(np.asarray(u), np.asarray(v))
    # one group of everything kept whole is the ungrouped choice too
    d = _route_topk(x, w, 3, "sigmoid", bias=b, scale=2.5, groups=1,
                    groups_kept=1)
    assert np.array_equal(np.asarray(a[1]), np.asarray(d[1]))
    with pytest.raises(ValueError, match="do not hold"):
        MoeSpec(num_experts=8, top_k=4, groups=4, groups_kept=1)
    with pytest.raises(ValueError, match="stated together"):
        MoeSpec(num_experts=8, top_k=2, groups=4)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """One expert layer at test size, 16 experts in 4 groups over 16 chips
    (one expert a chip): the routed parts of the sixteen shares (each
    chip's own expert, the program's ``_moe_ffn``), with the shared expert
    counted once, add up to the uncut reference's routed sum plus its
    shared expert; attention, index and the dense layer are what every
    chip computes alike."""
    from paddle_tpu.inference.generation import _moe_ffn
    kw = dict(n_routed_experts=16, num_experts_per_tok=4)
    paddle.seed(0)
    full = _seeded(DeepseekV32ForCausalLM(DeepseekV32Config.tiny(**kw)), 5)
    m, get_layer, _ = _reference_model(full)
    w = get_layer(1)
    rng = np.random.default_rng(4)
    y = jnp.asarray(rng.normal(size=(48, full.config.hidden_size)),
                    jnp.float32)
    lp_full = {n: (a[0] if not isinstance(a, tuple) else a[0])
               for n, a in full.serving_params()["blocks"][0].items()}
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref.shared_expert(y, w, "highest"))
        whole = np.asarray(ref.routed_experts(y, w, m, "highest")) + shared
        total = np.zeros_like(whole)
        for index in range(16):
            moe = DeepseekV32Config.tiny(
                experts_held=1, expert_offset=index, **kw).moe_spec()
            lp = dict(lp_full)
            for name in gen.EXPERT_BANKS:
                lp[name] = lp_full[name][index:index + 1]
            out, rows = jax.jit(lambda y, lp, moe=moe: _moe_ffn(y, lp, moe))(
                y, lp)
            total += np.asarray(out) - shared          # the routed part
            assert rows is not None
            mi = dict(m, n_routed_experts=1,
                      published={"n_routed_experts": 16},
                      share={"chips": 16, "index": index})
            wi = dict(w, **{n: w[n][index:index + 1]
                            for n in gen.EXPERT_BANKS})
            np.testing.assert_allclose(
                np.asarray(out) - shared,
                np.asarray(ref.routed_experts(y, wi, mi, "highest")),
                rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(total + shared, whole, rtol=2e-4, atol=2e-5)
    # the vocabulary is divided over 8 of the 16: part index % 8
    for index in range(16):
        assert ref.vocab_part({"share": {"chips": 16, "index": index,
                                         "over": {"vocab_size": 8}}}) \
            == index % 8
    assert ref.vocab_part({"share": {"chips": 4, "index": 3}}) == 3
    assert ref.held(dict(n_routed_experts=16,
                         published={"n_routed_experts": 256},
                         share={"chips": 16, "index": 5})) == (256, 16, 80)


# ----------------------------------------------------------- the pool ----

@pytest.fixture(scope="module")
def model():
    return _model()


def test_the_pool_has_a_third_plane(model):
    eng = ContinuousBatchingEngine(model, metrics=True, **GEOMETRY)
    cache = eng.g.cache
    pages = 4 * 16
    assert cache.latent == (128, 64) and cache.page_axes == (1, 1, 1)
    c, r, ik = cache.arrays
    assert c.shape == (3, pages, 16, 128)
    assert r.shape == (3, pages, 8, 128)
    assert ik.shape == (3, pages, 16, 128) and ik.dtype == c.dtype
    assert c.nbytes + r.nbytes + ik.nbytes == eng.g.pool_bytes == \
        pages * PagedKVCache.bytes_per_page(3, 1, 16, 192, "float32",
                                            latent=(128, 64), index=128)
    # 512 + 64 + 128 in bf16 over five layers: 7,040 B a token, 1,280 of
    # them the index keys; the cell's pool 1.86 GB
    assert PagedKVCache.bytes_per_page(5, 1, 16, 192, "bfloat16",
                                       latent=(512, 64), index=128) \
        == 16 * 7040
    assert 16512 * 16 * 7040 == 1_859_911_680
    assert PagedKVCache.pages_for(8, 33024, 16) == 16512
    row = (128 + 64 + 128) * 4
    assert metrics.gauge("serving.kv_bytes_per_token").value == 3 * row
    assert metrics.gauge("serving.index_bytes_per_token").value \
        == 3 * 128 * 4
    planes = cache.page_planes(3)
    assert [p.shape for p in planes] == [(3, 16, 128), (3, 8, 128),
                                         (3, 16, 128)]
    with pytest.raises(ValueError, match="plane of a latent pool"):
        PagedKVCache(2, 8, 16, 1, 64, "float32", index=128)


def test_cow_copies_all_three_planes(model):
    g = ContinuousBatchingEngine(model, **GEOMETRY).g
    rng = np.random.default_rng(0)
    arrays = tuple(jnp.asarray(rng.normal(size=a.shape), a.dtype)
                   for a in g.cache.arrays)
    src = jnp.asarray([5, -1, 9, -1], jnp.int32)
    dst = jnp.asarray([20, 21, 22, 23], jnp.int32)
    out = gen._cow_copy_pages(arrays, src, dst, g.cache.page_axes)
    for before, after in zip(arrays, out):
        before, after = np.asarray(before), np.asarray(after)
        np.testing.assert_array_equal(after[:, 20], before[:, 5])
        np.testing.assert_array_equal(after[:, 22], before[:, 9])
        np.testing.assert_array_equal(after[:, 21], before[:, 21])
        np.testing.assert_array_equal(after[:, :20], before[:, :20])


def test_prefix_cache_shares_and_copies_index_pages(model):
    """A prompt asked twice, over ``index_topk``: the second admission
    attaches the cached pages (their index keys with them: the scores read
    them) and privatises its last page copy-on-write over all THREE
    planes; tokens equal the cache-off engine's."""
    rng = np.random.default_rng(2)
    prompt = list(rng.integers(1, 256, 96))        # six whole pages
    plain = ContinuousBatchingEngine(model, **GEOMETRY)
    want = plain.submit(prompt, max_new_tokens=5)
    want = plain.run()[want.req_id]
    eng = ContinuousBatchingEngine(model, prefix_cache=True, **GEOMETRY)
    first = eng.submit(prompt, max_new_tokens=5)
    assert eng.run()[first.req_id] == want
    again = eng.submit(prompt, max_new_tokens=5)
    other = eng.submit(prompt[:48] + [9] * 20, max_new_tokens=5)
    done = eng.run()
    assert done[again.req_id] == want
    stats = eng.stats()
    assert stats["prefix_hits"] >= 2 and stats["prefix_tokens_saved"] >= 96
    assert stats["cow_copies"] >= 1
    check = ContinuousBatchingEngine(model, **GEOMETRY)
    ref_ = check.submit(prompt[:48] + [9] * 20, max_new_tokens=5)
    assert done[other.req_id] == check.run()[ref_.req_id]


@pytest.mark.parametrize("mode", ["ngram", "fused"])
def test_the_speculative_lanes_serve_through_the_three_plane_pool(
        model, mode):
    """``serve_spec_verify_K*`` and ``serve_fused_K*`` run the same core,
    so they commit index keys and read them back, and a rejected draft's
    rows are rolled back page by page in all three planes: their tokens
    are the plain engine's."""
    prompts = _prompts(model.config.vocab_size)[1:]
    plain = ContinuousBatchingEngine(model, **GEOMETRY)
    spec = ContinuousBatchingEngine(model, spec_decode=mode, spec_k=4,
                                    **GEOMETRY)
    want = [plain.submit(p, max_new_tokens=10) for p in prompts]
    got = [spec.submit(p, max_new_tokens=10) for p in prompts]
    a, b = plain.run(), spec.run()
    assert spec.stats()["spec_steps"] > 0
    for w, g in zip(want, got):
        assert a[w.req_id] == b[g.req_id]


@pytest.mark.parametrize("what", ["spill", "migration", "int8", "tp"])
def test_what_refuses_a_latent_pool_refuses_this_one(model, what):
    if what == "spill":
        with pytest.raises(ValueError, match=r"inference/kv_spill\.py"):
            ContinuousBatchingEngine(model, prefix_cache=True,
                                     kv_spill_pages=8, **GEOMETRY)
    elif what == "migration":
        eng = ContinuousBatchingEngine(model, prefix_cache=True, **GEOMETRY)
        with pytest.raises(migration.MigrationError,
                           match=r"inference/migration\.py"):
            migration.export_session(eng, tokens=[1, 2, 3])
    elif what == "int8":
        with pytest.raises(ValueError, match=r"kv_cache\.py.*int8"):
            ContinuousBatchingEngine(model, cache_dtype="int8", **GEOMETRY)
    else:
        with pytest.raises(ValueError, match="latent"):
            ContinuousBatchingEngine(model, tensor_parallel=2, **GEOMETRY)


def test_the_step_counts_pairs_and_chosen_keys(model, tmp_path):
    """``index_pairs`` = q x ctx + q (q + 1) / 2 a working slot and
    ``selected_keys`` = min(position + 1, top_k) a query token, on the
    step's span and in the registry, while somebody listens."""
    import json
    eng = ContinuousBatchingEngine(model, metrics=True, **GEOMETRY)
    g = eng.g
    assert g.index_counts([(64, 0)]) == (64 * 65 // 2,
                                         32 * 33 // 2 + 32 * 32)
    assert g.index_counts([(64, 10), (1, 100), (3, 31)]) == (
        64 * 10 + 64 * 65 // 2 + 101 + 3 * 31 + 6,
        (22 * 10 + 22 * 23 // 2 + 42 * 32) + 32 + (32 + 32 + 32))
    before = metrics.histogram("serving.index_pairs").count
    obs.tracer.start()
    try:
        eng.submit(_prompts(256)[2], max_new_tokens=3)       # 70 tokens
        eng.run()
    finally:
        obs.tracer.stop()
    doc = json.load(open(obs.export_chrome_trace(str(tmp_path / "t.json"))))
    steps = [e["args"] for e in doc["traceEvents"]
             if e["name"] == "engine.step" and e["args"]["T"]]
    assert [s["index_pairs"] for s in steps[:3]] == [
        64 * 65 // 2, 6 * 64 + 21, 71]
    assert [s["selected_keys"] for s in steps[:3]] == [
        32 * 33 // 2 + 32 * 32, 6 * 32, 32]
    assert metrics.histogram("serving.index_pairs").count - before \
        == len(steps)


# ------------------------------------------------- the spec, the presets ----

def test_the_spec_states_the_published_numbers():
    c = DeepseekV32Config()
    spec = DeepseekV32ForCausalLM.decoder_spec(
        type("M", (), {"config": c})())
    assert spec.latent == LatentAttn(rank=512, nope=128, rope=64, value=128,
                                     q_rank=1536)
    assert spec.index == LatentIndex(heads=64, dim=128, rope=64, top_k=2048)
    assert spec.moe == MoeSpec(
        num_experts=256, top_k=8, score="sigmoid", held=256, offset=0,
        shared=1, dispatch="grouped", block_m=128, select_bias=True,
        gate_scale=2.5, groups=8, groups_kept=4)
    assert spec.rope_yarn == RopeYarn(40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert abs(spec.softmax_scale - 0.1352) < 5e-5
    assert (spec.num_layers, spec.periods, len(spec.leading)) == (61, 58, 3)
    assert all(k.dense_ffn and k.index == spec.index for k in spec.leading)
    assert (spec.num_heads, spec.num_kv_heads, spec.head_dim) == (128, 1, 192)


def test_the_share_preset_is_the_configurations_file():
    import json
    from paddle_tpu.serving.__main__ import _DEEPSEEK_V32_PRESETS
    c = _DEEPSEEK_V32_PRESETS["deepseek_v32_ep16"](DeepseekV32Config)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "deepseek-v3.2-ep16.json")) as f:
        config = json.load(f)
    for key in SOURCE_KEYS + ("n_routed_experts",):
        want = config["model"][key] if key not in config["reduced"] \
            else config[key]
        got = c.experts_held if key == "n_routed_experts" \
            else getattr(c, key)
        assert got == want, key
    assert c.n_routed_experts == config["model"]["n_routed_experts"] == 256
    assert c.expert_offset == 16 * config["share"]["index"] == 0
    moe = c.moe_spec()
    assert moe.partial and (moe.held, moe.offset) == (16, 0)
    assert DeepseekV32Config.deepseek_v32_ep16(index=5).expert_offset == 80


def test_the_launcher_preset_serves_through_the_same_engine():
    from paddle_tpu.serving.__main__ import build_engine, build_parser
    args = build_parser().parse_args(
        ["--preset", "deepseek_v32_tiny", "--max-batch", "2",
         "--max-seq-len", "64", "--page-size", "16", "--prefill-bucket",
         "16"])
    eng = build_engine(args)
    assert type(eng) is ContinuousBatchingEngine
    assert isinstance(eng.g.config, DeepseekV32Config)
    req = eng.submit(list(range(1, 50)), max_new_tokens=3)   # over top_k 32
    assert len(eng.run()[req.req_id]) == 3
    params = eng.g.params
    assert len(params["leading"]) == 1 and len(eng.g.cache.arrays) == 3
    banks = params["blocks"][0]["mlp.experts_gate"]
    assert isinstance(banks, tuple) and len(banks) == 2
    assert params["head"].shape == (64, 256)               # untied


@pytest.mark.parametrize("key, value, sentence", [
    ("topk_method", "greedy", "group-limited one with a selection bias"),
    ("scoring_func", "softmax", "scores are sigmoids"),
    ("n_shared_experts", 2, "one shared expert is added ungated"),
    ("attention_bias", True, "projections have no bias"),
    ("rope_scaling", {"type": "linear", "factor": 2}, "only 'yarn'"),
    ("index_head_dim", 64, "multiple of 128"),
    ("norm_topk_prob", False, "divided by their sum"),
    ("hidden_act", "gelu", "SiLU-gated"),
    ("tie_word_embeddings", True, "untied")])
def test_what_the_model_does_not_compute_is_refused(key, value, sentence):
    with pytest.raises(ValueError, match="deepseek_v32") as e:
        DeepseekV32Config.from_source({key: value})
    assert sentence in str(e.value) and key.split("_")[0] in str(e.value)


def test_from_source_reads_the_sources_own_keys():
    c = DeepseekV32Config.from_source(
        {"n_routed_experts": 16, "torch_dtype": "float32",
         "model_type": "deepseek_v32", "ep_size": 1, "index_topk": 7},
        num_experts=256, experts_held=16, expert_offset=32)
    assert (c.n_routed_experts, c.experts_held, c.expert_offset) == \
        (256, 16, 32)
    assert (c.dtype, c.index_topk) == ("float32", 7)


def test_a_stack_holds_one_index_or_none():
    la = LatentAttn(rank=128, nope=128, rope=64, value=128, q_rank=32)
    ix = LatentIndex(heads=4, dim=128, rope=64, top_k=32)
    kw = dict(periods=1, num_heads=4, num_kv_heads=1, head_dim=192)
    with pytest.raises(ValueError, match="same index or none"):
        DecoderSpec(pattern=(LayerKind(latent=la, index=ix),
                             LayerKind(latent=la)), **kw)
    with pytest.raises(ValueError, match="same index or none"):
        DecoderSpec(pattern=(LayerKind(latent=la, index=ix),),
                    leading=(LayerKind(latent=la, dense_ffn=True),), **kw)
    with pytest.raises(ValueError, match="query latent"):
        DecoderSpec(pattern=(LayerKind(
            latent=LatentAttn(128, 128, 64, 128), index=ix),), **kw)
    with pytest.raises(ValueError, match="one table serves both"):
        DecoderSpec(pattern=(LayerKind(
            latent=la, index=LatentIndex(4, 128, 32, 32)),), **kw)


# --------------------------- sarvam_mla's step program is what it was ----

@pytest.mark.parametrize("T", [64, 1], ids=["mixed", "decode"])
def test_a_stack_without_an_index_lowers_to_the_same_program(monkeypatch, T):
    """``sarvam_mla`` states no query latent and no index: its step
    program is lowered once as the tree stands and once with every entry
    point this family added made unreachable (each raises), and the two
    texts are the same bytes; the text names the dense latent call and
    nothing of the index.  (Against the parent commit the three members'
    texts were byte-equal when this was written: PERF.md section 6, PR
    39.)"""
    from paddle_tpu.models.sarvam_mla import (SarvamMlaConfig,
                                              SarvamMlaForCausalLM)
    paddle.seed(0)
    sarvam = SarvamMlaForCausalLM(SarvamMlaConfig.tiny())

    def texts():
        eng = ContinuousBatchingEngine(sarvam, max_batch=8, max_seq_len=256,
                                       page_size=16, prefill_bucket=64)
        assert len(eng.g.cache.arrays) == 2
        return [eng.lowered_step(T, rows).as_text()
                for rows in eng.g.row_buckets(T)]

    as_is = texts()

    def unreachable(*a, **kw):
        raise AssertionError("a stack without an index reached the index")

    for name in ("latent_index_scores", "latent_index_select",
                 "ragged_paged_attention_latent_sparse"):
        monkeypatch.setattr(gen, name, unreachable)
    inner = gen.write_latent_pages_all_layers

    def two_planes(*args):
        assert len(args) == 5
        return inner(*args)

    monkeypatch.setattr(gen, "write_latent_pages_all_layers", two_planes)
    assert texts() == as_is
    for text in as_is:
        assert "latent_index" not in text and "q_a_proj" not in text
