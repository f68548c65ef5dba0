"""``models/solar_open2.py`` through the serving engine at test size (two
periods of a gated softmax layer without positions and three gated
delta-rule linear layers; 8 sigmoid-routed experts with a selection bias
and a shared one): prefill in chunks of the bucket and decoding through the
pool (one layer in four) and the recurrent state (three in four) against
the plain reference's full forward (``chipbench/references/solar_open2.py``:
the bare recurrence token by token, float32), LOGITS compared; a reused
slot; the packed member; the share; every refusal."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu.inference import (ContinuousBatchingEngine, GenerationConfig,
                                  migration)
from paddle_tpu.inference.kv_cache import (LayerPlanes, PagedKVCache,
                                           RecurrentState)
from paddle_tpu.inference.kv_spill import HostSpillPool
from paddle_tpu.models.decoder_spec import (DecoderSpec, DeltaMixer,
                                            LatentAttn, LayerKind, MoeSpec,
                                            SsmMixer)
from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                           SolarOpen2ForCausalLM)
import paddle_tpu.observability as obs
from paddle_tpu.observability import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from chipbench.references import solar_open2 as ref  # noqa: E402

GEOMETRY = dict(max_batch=4, max_seq_len=256, page_size=16, prefill_bucket=16)
PROMPTS = (130, 5, 16, 33)      # nine chunks, one, a whole one, three
PUBLISHED = DeltaMixer(heads=64, key_dim=128, value_dim=128, conv=4,
                       gate_rank=128, neg_eigval=True)
# float32 on both sides: the engine's cached keys, its packed rows, its
# grouped experts and beta folded into k and v are the reference's numbers
# summed in another order, so the logits (std 0.99, up to 4.4 here) agree to
# float32 roundings carried through sixteen sublayers: the engine reads
# 2.4e-5 to 3.8e-5 off the reference (the model's own forward 1.7e-5).  With
# the recurrent state rounded to bf16 between steps it reads 0.41 and with
# the delta term dropped (S_t = S' + beta k v^T) over 1, so the tolerance
# stands 5 times over the one and 2,000 times under the other.
LOGIT_ATOL = 2e-4


def _model(dtype="float32", **kw):
    """The tiny model with the small leaves drawn, not at their initial
    zeros: a channel's log decay a token from under 0.01 (remembers
    hundreds of tokens) to over 1 (forgets within one); a selection bias
    that moves choices."""
    paddle.seed(0)
    model = SolarOpen2ForCausalLM(SolarOpen2Config.tiny(dtype=dtype, **kw))
    rng = np.random.default_rng(3)
    for block in model.blocks:
        for name, std in (("linear_attn.A_log", 1.0),
                          ("linear_attn.dt_bias", 2.0),
                          ("mlp.gate.bias", 0.05)):
            if name in block._parameters:
                a = block._parameters[name]._data
                block._parameters[name]._data = jnp.asarray(
                    rng.normal(0, std, a.shape), a.dtype)
    return model


def _prompts(vocab, lens=PROMPTS):
    rng = np.random.default_rng(1)
    return [list(rng.integers(1, vocab, n)) for n in lens]


SOURCE_KEYS = (
    "hidden_size", "intermediate_size", "moe_intermediate_size", "vocab_size",
    "head_dim", "num_attention_heads", "num_key_value_heads",
    "num_hidden_layers", "rms_norm_eps", "linear_attn_config", "gqa_layers",
    "use_gqa_gate", "kda_allow_neg_eigval", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor")


def _reference_model(model):
    """(m, get_layer, flat) as the harness hands them to the reference:
    ``Run.model``'s keys from the model's config, the model's own arrays
    (layer ``l`` is period ``l // 4``'s place ``l % 4``)."""
    c = model.config
    m = {k: getattr(c, k) for k in SOURCE_KEYS}
    params = model.serving_params()
    P = len(params["blocks"])

    def get_layer(l):
        return {n: a[l // P] for n, a in params["blocks"][l % P].items()}

    return m, get_layer, {n: params[n] for n in ("embed", "norm", "head")}


def _reference_logits(model, seqs, **over):
    m, get_layer, flat = _reference_model(model)
    m.update(over)
    with jax.default_matmul_precision("highest"):
        return ref.sequence_logits(
            get_layer, flat, m["num_hidden_layers"], m, seqs,
            [list(range(len(s))) for s in seqs])


def _engine_logits(model, seqs, state_dtype=None):
    """Logits at every position of ``seqs`` (one a slot) from the engine's
    own ``_forward_tokens``, a chunk of the bucket a step and then (the
    second sequence) a token a step, through pool and recurrent state;
    ``state_dtype`` rounds the state between steps."""
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    g = eng.g
    B, T = GEOMETRY["max_batch"], GEOMETRY["prefill_bucket"]
    table = jnp.asarray(np.arange(B * g.pages_per_seq, dtype=np.int32)
                        .reshape(B, g.pages_per_seq))
    cache, pos = tuple(g.cache.arrays), np.zeros((B,), np.int32)
    got = [[] for _ in seqs]
    step = jax.jit(lambda c, t, q, p: g._forward_tokens(g.params, c, t, q, p,
                                                        table)[:2])
    while any(pos[b] < len(s) for b, s in enumerate(seqs)):
        toks, ql = np.zeros((B, T), np.int32), np.zeros((B,), np.int32)
        for b, s in enumerate(seqs):
            # the second sequence decodes from its 20th token on: a row of
            # one token beside the others' chunks
            n = 1 if b == 1 and pos[b] >= 20 else T
            chunk = s[pos[b]:pos[b] + n]
            toks[b, :len(chunk)], ql[b] = chunk, len(chunk)
        h, cache = step(cache, jnp.asarray(toks), jnp.asarray(ql),
                        jnp.asarray(pos))
        if state_dtype is not None:
            cache = (cache[0], cache[1].astype(state_dtype)
                     .astype(jnp.float32), cache[2])
        lg = np.asarray(g._head_logits(g.params, h), np.float32)
        for b in range(len(seqs)):
            got[b].append(lg[b, :ql[b]])
        pos = pos + ql
    return [np.concatenate(rows) for rows in got]


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def seqs(model):
    return _prompts(model.config.vocab_size, (100, 37))


@pytest.fixture(scope="module")
def engine_logits(model, seqs):
    return _engine_logits(model, seqs)


@pytest.fixture(scope="module")
def reference_logits(model, seqs):
    return _reference_logits(model, seqs)


def _worst(got, want):
    return max(float(np.abs(g - w).max()) for g, w in zip(got, want))


@pytest.mark.timeout(300)
def test_chunked_prefill_then_decode_logits_are_the_references(
        engine_logits, reference_logits):
    """Seven chunks of 16 (and a slot that turns to one token a step)
    through pages on two layers and recurrent state on six give, under the
    head, the reference's logits (the recurrence from ``S_0 = 0`` over the
    whole sequence, no cache) at every position."""
    assert _worst(engine_logits, reference_logits) <= LOGIT_ATOL


@pytest.mark.timeout(300)
def test_a_bf16_state_would_fail_the_tolerance(model, seqs, reference_logits):
    """The same steps with the recurrent state rounded to bf16 between
    them read far over ``LOGIT_ATOL``: the tolerance is tight enough to
    catch a state kept in lower precision than the model's file says."""
    got = _engine_logits(model, seqs, state_dtype=jnp.bfloat16)
    assert _worst(got, reference_logits) > 500 * LOGIT_ATOL


@pytest.mark.timeout(300)
def test_a_dropped_delta_term_would_fail_the_tolerance(
        model, seqs, engine_logits, monkeypatch):
    """Against a reference whose update is plain gated linear attention
    (``S_t = S' + beta k v^T``: no ``- S'^T k``) the engine reads far over
    ``LOGIT_ATOL``: the delta term is computed."""
    def plain(q, k, v, g, beta, s0=None):
        def step(s, t):
            qt, kt, vt, gt, bt = t
            s = s * jnp.exp(gt)[..., None] \
                + (bt[:, None] * kt)[..., None] * vt[:, None, :]
            return s, jnp.einsum("hkv,hk->hv", s, qt, precision="highest")
        s0 = jnp.zeros(q.shape[1:] + v.shape[-1:], jnp.float32)
        state, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
        return o, state

    monkeypatch.setattr(ref, "recurrence", plain)
    assert _worst(engine_logits, _reference_logits(model, seqs)) \
        > 1000 * LOGIT_ATOL


@pytest.mark.timeout(300)
@pytest.mark.parametrize("interpret", [False, True],
                         ids=["xla_oracle", "interpreted_kernel"])
def test_engine_serves_what_the_references_full_forward_gives(model,
                                                              interpret):
    """``submit`` / ``step`` with mixed steps (prefill chunks beside decode
    rows, five requests through four slots, so one slot is admitted again)
    serve tokens whose logit under the plain reference is its best at every
    served position."""
    flags.set_flags({"paged_attention_interpret": interpret})
    try:
        eng = ContinuousBatchingEngine(model, **GEOMETRY)
        prompts = _prompts(model.config.vocab_size, PROMPTS + (21,))
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        done = eng.run()
    finally:
        flags.set_flags({"paged_attention_interpret": False})
    logits = _reference_logits(
        model, [p + done[r.req_id][:-1] for p, r in zip(prompts, reqs)])
    for p, r, lg in zip(prompts, reqs, logits):
        served = np.asarray(done[r.req_id])
        at = lg[len(p) - 1:]
        gap = at.max(-1) - np.take_along_axis(at, served[:, None], -1)[:, 0]
        assert gap.max() <= LOGIT_ATOL, (len(p), gap)


def test_the_models_own_forward_is_the_references(model, seqs,
                                                  reference_logits):
    got = np.asarray(model(paddle.to_tensor(
        np.asarray([seqs[1]], np.int32)))._data)[0]
    assert np.abs(got - reference_logits[1]).max() <= LOGIT_ATOL


@pytest.mark.timeout(300)
def test_bf16_prefill_and_decode_stay_near_the_float32_reference(seqs):
    """The model in bfloat16 (weights, activations, the kernel's operands;
    the state stays float32) against the float32 reference over the SAME
    bf16 weights.  A sublayer alone reads 0.5 % off in bf16 (the linear
    mixer 0.50 %, the experts 0.49-0.52 %: 2^-9 a product), and sixteen of
    them on a seeded stack, whose router flips a close second choice now
    and then, carry that to a MEAN absolute logit error of 0.17 to 0.19
    where the logits' own spread is 0.99 and two unrelated rows of logits
    lie 1.12 apart: held under 0.35, a third of that distance.  (No single
    position is held: one flipped expert moves a token's logits by 1.)"""
    model = _model("bfloat16")
    got = _engine_logits(model, seqs)
    want = _reference_logits(model, seqs)
    for g, w in zip(got, want):
        assert 1e-3 < float(np.abs(g - w).mean()) < 0.35
    assert float(np.abs(want[0][:37] - want[1]).mean()) > 0.9
    assert model.serving_params()["blocks"][1]["linear_attn.A_log"].dtype \
        == jnp.float32


def test_a_reused_slot_serves_what_a_fresh_engine_serves(model):
    """One slot, two requests one after the other: the second finds the
    first's state in its slot, which the step that runs its first chunk
    zeroes on the device; its tokens and the state it leaves are a fresh
    engine's, bit for bit."""
    first, second = _prompts(model.config.vocab_size, (40, 23))
    geometry = dict(GEOMETRY, max_batch=1)
    used = ContinuousBatchingEngine(model, **geometry)
    used.submit(first, max_new_tokens=5)
    used.run()
    assert jnp.any(used.g.cache.recurrent.ssm)             # something lies there
    again = used.submit(second, max_new_tokens=5)
    out = used.run()[again.req_id]
    fresh = ContinuousBatchingEngine(model, **geometry)
    req = fresh.submit(second, max_new_tokens=5)
    assert fresh.run()[req.req_id] == out
    for a, b in zip(used.g.cache.recurrent.arrays,
                    fresh.g.cache.recurrent.arrays):
        assert jnp.array_equal(a, b)


def test_the_packed_step_serves_what_the_dense_step_serves(model,
                                                           monkeypatch):
    """With the packed member in reach (its floor lowered to 16 rows) a
    mixed step's per-token work, the convolution and the recurrence among
    it, runs over the packed rows: the same tokens, the same state."""
    from paddle_tpu.inference import generation as gen
    prompts = _prompts(model.config.vocab_size, (40, 3, 18))

    def serve():
        eng = ContinuousBatchingEngine(
            model, gen=GenerationConfig(max_new_tokens=5), **GEOMETRY)
        reqs = [eng.submit(p) for p in prompts]
        done = eng.run()
        return eng, [done[r.req_id] for r in reqs]

    dense, want = serve()
    assert dense.g.row_buckets(16) == [64]
    monkeypatch.setattr(gen, "MIN_GEMM_ROWS", 16)
    packed, got = serve()
    assert packed.g.row_buckets(16) == [16, 64]
    assert got == want
    for a, b in zip(dense.g.cache.recurrent.arrays,
                    packed.g.cache.recurrent.arrays):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# --------------------------------------------------------- the share ----

def test_the_eight_shares_add_up_to_the_uncut_layer(model):
    """Each of eight chips computes its experts' part of the routed sum
    from the router at its full width; the parts, plus the shared expert
    counted once, are the uncut reference's expert mixture."""
    m, get_layer, _ = _reference_model(model)
    w = get_layer(1)
    x = jax.random.normal(jax.random.key(7), (24, m["hidden_size"]),
                          jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_experts(x, w, m, "highest") \
            + ref.shared_expert(x, w, "highest")
        parts = ref.shared_expert(x, w, "highest")
        for index in range(8):
            held = dict(m, n_routed_experts=1, published={
                "n_routed_experts": 8}, share={"chips": 8, "index": index})
            mine = {n: a[index:index + 1] if n.startswith("mlp.experts_")
                    else a for n, a in w.items()}
            part = ref.routed_experts(x, mine, held, "highest")
            assert ref.held(held) == (8, 1, index)
            parts = parts + part
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               atol=1e-5)
    assert float(jnp.abs(whole).max()) > 0.1


def test_a_share_of_the_experts_serves_its_part_of_the_references():
    """The engine on a model that holds experts [2, 4) of 8 (the router at
    its width of 8) gives the reference's logits for the same share: what
    the absent experts would add is left out on both sides."""
    model = _model(experts_held=2, expert_offset=2)
    seqs = _prompts(model.config.vocab_size, (40, 21))
    m, get_layer, flat = _reference_model(model)
    m.update(n_routed_experts=2, published={"n_routed_experts": 8},
             share={"chips": 4, "index": 1})
    with jax.default_matmul_precision("highest"):
        want = ref.sequence_logits(get_layer, flat, 8, m, seqs,
                                   [list(range(len(s))) for s in seqs])
    assert _worst(_engine_logits(model, seqs), want) <= LOGIT_ATOL
    uncut = _reference_logits(_model(), seqs)
    assert _worst(want, uncut) > 1000 * LOGIT_ATOL


# ------------------------------------------------- what it is made of ----

def test_the_published_sizes_reach_the_engines_spec():
    c = SolarOpen2Config.solar_open2_250b()
    spec = SolarOpen2ForCausalLM.decoder_spec(type("M", (), {"config": c})())
    assert (c.num_hidden_layers, c.experts_held, c.expert_offset,
            c.vocab_size) == (4, 40, 0, 24576)
    soft, *linear = spec.pattern
    assert soft == LayerKind(rope=False, out_gate=True)
    assert linear == [LayerKind(linear=PUBLISHED)] * 3
    assert (spec.page_places, spec.state_places) == ((0,), (1, 2, 3))
    assert (spec.page_layers, spec.state_layers, spec.num_layers) == (1, 3, 4)
    assert spec.windows == (None,) and spec.state_mixer == PUBLISHED
    assert spec.ssm is None and spec.linear == PUBLISHED
    assert (spec.num_heads, spec.num_kv_heads, spec.head_dim) == (64, 8, 128)
    assert spec.moe == MoeSpec(
        num_experts=320, top_k=8, score="sigmoid", held=40, offset=0,
        shared=1, dispatch="grouped", block_m=128, select_bias=True,
        gate_scale=1.0)
    # a slot: 4,194,304 B of float32 state and three rows of 24,576 in
    # bf16 a linear layer; what 3,180 cached tokens of the ONE page layer
    # hold
    assert PUBLISHED.state_bytes("bfloat16") == 4_194_304 + 147_456
    assert RecurrentState.bytes_per_slot(PUBLISHED, 3, "bfloat16") == \
        13_025_280
    per_token = PagedKVCache.bytes_per_page(1, 8, 16, 128, "bfloat16") // 16
    assert per_token == 4096 and 13_025_280 // per_token == 3180
    other = SolarOpen2Config.solar_open2_250b(depth=8, share=16, index=5)
    assert (other.experts_held, other.expert_offset, other.vocab_size) == (
        20, 100, 24576)


def test_the_references_count_is_the_published_250b_a15b():
    """The assumed layer (the gate rank above all) lands on the published
    size: 250 B parameters, 15 B a token; the chip's share on 3.31 G."""
    source = json.load(open(os.path.join(
        ROOT, "chipbench/configs/solar-open2-250b-ep8.json")))
    whole = ref.count_params(source["model"], 48)
    assert whole["total"] == 250_287_810_304
    assert whole["active"] == 14_735_697_664
    m = dict(source["model"], **source["share"]["serve"],
             published={k: source["model"][k] for k in source["reduced"]},
             share={"chips": 8, "index": 0})
    held = ref.count_params(m, 4)
    assert held["total"] == 3_308_353_344
    assert (held["linear_layer"], held["softmax_layer"]) == (
        783_925_760, 755_245_376)
    assert held["experts"] == 40 * 15_728_640
    assert held["embed_and_head"] == 2 * 24576 * 4096 + 4096


def test_what_the_model_file_does_not_compute_is_refused():
    for bad in (dict(use_rope=True), dict(kda_use_full_proj=True),
                dict(gqa_layers=(1, 5)), dict(gqa_layers=(0, 2, 4, 6)),
                dict(num_hidden_layers=6), dict(tie_word_embeddings=True),
                dict(first_k_dense_replace=1), dict(hidden_act="gelu"),
                dict(linear_attn_config={
                    "short_conv_kernel_size": 4, "head_dim": 16,
                    "num_heads": 4, "num_kv_heads": 2})):
        with pytest.raises(ValueError, match="solar_open2"):
            SolarOpen2Config.tiny(**bad)
    source = json.load(open(os.path.join(
        ROOT, "chipbench/configs/solar-open2-250b-ep8.json")))["model"]
    c = SolarOpen2Config.from_source(source)
    assert c.__dict__ == SolarOpen2Config().__dict__
    assert c.gqa_layers == tuple(range(0, 48, 4)) and c.dtype == "bfloat16"
    cut = SolarOpen2Config.from_source(source, num_hidden_layers=4)
    assert cut.gqa_layers == (0,)


def test_a_stack_with_linear_places_states_what_the_engine_serves():
    mx = DeltaMixer(4, 16, 16, 4, 16)
    soft, lin = LayerKind(rope=False), LayerKind(linear=mx)
    kw = dict(periods=2, num_heads=4, num_kv_heads=2, head_dim=32)
    with pytest.raises(ValueError, match="latent"):
        DecoderSpec(pattern=(LayerKind(latent=LatentAttn(128, 64, 64, 64)),
                             LayerKind(latent=LatentAttn(128, 64, 64, 64),
                                       linear=mx)), **kw)
    with pytest.raises(ValueError, match="two state shapes"):
        DecoderSpec(pattern=(soft, lin, LayerKind(
            linear=DeltaMixer(4, 32, 16, 4, 16))), **kw)
    with pytest.raises(ValueError, match="one recurrent state serves"):
        DecoderSpec(pattern=(LayerKind(ssm=SsmMixer(4, 16, 32, 2, 4)), lin),
                    **kw)
    with pytest.raises(ValueError, match="no pool to page"):
        DecoderSpec(pattern=(lin,), **kw)
    with pytest.raises(ValueError, match="sequential residuals only"):
        DecoderSpec(pattern=(soft, lin), parallel_block=True, **kw)
    with pytest.raises(ValueError, match="not among the leading"):
        DecoderSpec(pattern=(soft, lin), leading=(lin,), **kw)
    with pytest.raises(ValueError, match="reads the attention's input"):
        DecoderSpec(pattern=(soft, lin), moe=MoeSpec(
            8, 2, router_input="attention"), **kw)
    with pytest.raises(ValueError, match="output gate"):
        DecoderSpec(pattern=(soft, LayerKind(linear=mx, out_gate=True)),
                    **kw)
    spec = DecoderSpec(pattern=(soft, lin, lin), **kw)
    assert LayerPlanes.of(spec) == LayerPlanes(
        pages=(0, None, None, 1, None, None),
        state=(None, 0, 1, None, 2, 3))


def test_two_kinds_of_cache_each_over_its_own_layers(model):
    """The pool counts the two layers that have pages, the recurrent state
    the six that have a state; the gauges read the same map."""
    eng = ContinuousBatchingEngine(model, metrics=True, **GEOMETRY)
    c, cache = model.config, eng.g.cache
    kv, state, conv = cache.arrays
    assert eng.g.planes == LayerPlanes(
        pages=(0, None, None, None, 1, None, None, None),
        state=(None, 0, 1, 2, None, 3, 4, 5))
    assert kv.shape == (2, cache.allocator.num_pages, 2, 2, 16, 32)
    assert state.shape == (6, 4, 4, 16, 16) and state.dtype == jnp.float32
    assert conv.shape == (6, 4, 3, 3 * 4 * 16)
    assert cache.num_layers == 2 and kv.nbytes == eng.g.pool_bytes
    assert (state.nbytes + conv.nbytes) // 4 == eng.g.state_bytes_per_slot \
        == 6 * c.mixer().state_bytes("float32")
    assert metrics.gauge("serving.kv_bytes_per_token").value == \
        2 * 2 * 2 * 32 * 4
    assert metrics.gauge("serving.state_bytes_per_slot").value == \
        eng.g.state_bytes_per_slot
    assert eng.g.kv_read_tokens([(1, 10)]) == 2 * 11        # two layers read


# ------------------------------------------------------- the refusals ----

def test_the_prefix_cache_refuses_a_recurrent_state(model):
    with pytest.raises(ValueError, match=r"inference/prefix_cache\.py"):
        ContinuousBatchingEngine(model, prefix_cache=True, **GEOMETRY)


def test_the_speculative_lanes_refuse_a_recurrent_state(model):
    with pytest.raises(ValueError, match=r"inference/speculative\.py"):
        ContinuousBatchingEngine(model, spec_decode="ngram", spec_k=4,
                                 **GEOMETRY)


def test_the_spill_ring_and_migration_refuse_a_recurrent_state(model):
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    with pytest.raises(ValueError, match=r"inference/kv_spill\.py"):
        HostSpillPool(eng.g.cache, 8)
    with pytest.raises(migration.MigrationError,
                       match=r"inference/migration\.py.*recurrent"):
        migration.export_session(eng, tokens=[1, 2, 3])


def test_the_int8_plane_and_tensor_parallel_refuse_a_recurrent_state(model):
    with pytest.raises(ValueError, match=r"inference/kv_cache\.py.*int8"):
        ContinuousBatchingEngine(model, cache_dtype="int8", **GEOMETRY)
    with pytest.raises(ValueError, match="no sharded layout"):
        ContinuousBatchingEngine(model, tensor_parallel=2, **GEOMETRY)


# ------------------------------------------------ spans, scopes, presets ----

def test_scopes_of_the_two_mixers_and_the_steps_span(model, tmp_path):
    """A device trace tells the linear places (``linear_attn`` with
    ``conv``, ``gates`` and ``kda`` inside) from the softmax place
    (``attention`` with ``out_gate`` inside); ``engine.step`` says of the
    recurrent state what it says for a state-space stack, under the same
    keys."""
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    text = eng.lowered_step(16).as_text(debug_info=True)
    for scope in ("linear_attn/conv", "linear_attn/gates", "linear_attn/kda",
                  "attention/out_gate", "attention/kv_write"):
        assert scope in text, scope
    resets = metrics.counter("serving.state_resets")
    before = resets.value
    eng = ContinuousBatchingEngine(model, metrics=True, **GEOMETRY)
    obs.tracer.start()
    try:
        for p in _prompts(model.config.vocab_size, (33, 5, 7)):
            eng.submit(p, max_new_tokens=3)
        eng.run()
    finally:
        obs.tracer.stop()
    assert resets.value - before == 3
    doc = json.load(open(obs.export_chrome_trace(str(tmp_path / "t.json"))))
    steps = [e["args"] for e in doc["traceEvents"]
             if e["name"] == "engine.step" and e["args"]["kind"] != "idle"]
    assert steps[0]["ssm_slots"] == 3
    assert steps[0]["ssm_tokens"] == steps[0]["q_tokens"] == 16 + 5 + 7


@pytest.mark.parametrize("preset", ["solar_open2_tiny"])
def test_the_launcher_preset_serves_through_the_same_engine(preset):
    from paddle_tpu.serving.__main__ import build_engine, build_parser
    args = build_parser().parse_args(
        ["--preset", preset, "--max-batch", "2", "--max-seq-len", "64",
         "--page-size", "16", "--prefill-bucket", "16"])
    eng = build_engine(args)
    assert type(eng) is ContinuousBatchingEngine
    assert isinstance(eng.g.config, SolarOpen2Config)
    req = eng.submit(list(range(1, 30)), max_new_tokens=3)
    assert len(eng.run()[req.req_id]) == 3
    assert "solar_open2_250b" in build_parser().format_help()


# ------------------------------------- the other families' programs ----

# sha256 (first 16 hex) of the lowered text of the T = 16 step family at the
# families' test sizes on the CPU (max_batch 4, 256 positions, pages of 16,
# MIN_GEMM_ROWS 16), read on the PARENT of the PR that brought linear places
# (PR 46): the spec's and the cache's new fields move no operation of a
# stack that has none.  A later PR that changes these programs on purpose
# reads them again (the test prints what it finds).
PARENT_PROGRAMS = {
    "falcon_h1": ("24a4dfa4916f94cd", "2e01a1c01153f271"),
    "cohere2_moe": ("a6396b1ed67aa87c", "92a74a5d0833a8b1"),
    "smallthinker": ("a6a73f5b6db10129", "5392cccf740cddf3"),
}
PARENT_CACHES = {
    "falcon_h1": [(2, 64, 2, 1, 16, 32), (2, 4, 4, 16, 32), (2, 4, 3, 192)],
    "cohere2_moe": [(4, 64, 2, 2, 16, 32)],
    "smallthinker": [(8, 64, 2, 2, 16, 32)],
}


def _family(name):
    from paddle_tpu.models import cohere2_moe, falcon_h1, smallthinker
    return {"falcon_h1": lambda: falcon_h1.FalconH1ForCausalLM(
                falcon_h1.FalconH1Config.tiny()),
            "cohere2_moe": lambda: cohere2_moe.CohereMoeForCausalLM(
                cohere2_moe.Cohere2MoeConfig.tiny()),
            "smallthinker": lambda: smallthinker.SmallThinkerForCausalLM(
                smallthinker.SmallThinkerConfig.tiny())}[name]()


@pytest.mark.timeout(300)
@pytest.mark.parametrize("family", sorted(PARENT_PROGRAMS))
def test_the_other_families_keep_their_programs_and_caches(family,
                                                           monkeypatch):
    from paddle_tpu.inference import generation as gen
    monkeypatch.setattr(gen, "MIN_GEMM_ROWS", 16)
    paddle.seed(0)
    eng = ContinuousBatchingEngine(_family(family), **GEOMETRY)
    assert [tuple(a.shape) for a in eng.g.cache.arrays] == \
        PARENT_CACHES[family]
    planes = eng.g.planes
    assert planes.pages == tuple(range(eng.g.spec.num_layers))
    assert eng.g.spec.page_layers == eng.g.cache.num_layers
    found = tuple(hashlib.sha256(eng.lowered_step(16, rows).as_text()
                                 .encode()).hexdigest()[:16]
                  for rows in eng.g.row_buckets(16))
    assert found == PARENT_PROGRAMS[family], found
