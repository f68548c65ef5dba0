"""``models/sarvam_mla.py`` through the serving engine at test size (a
64-wide rope part, a 192-wide query head): prefill in chunks and decoding
through the latent pool against the plain reference's full forward
(``chipbench/references/sarvam_mla.py``: the expanded form, float32), the
absorbed call against the model's own expanded forward, the yarn
frequencies and the softmax scale at the published numbers, the selection
bias, and the four shares of a layer against the uncut reference."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models.decoder_spec import (DecoderSpec, LatentAttn,
                                            LayerKind, RopeYarn)
from paddle_tpu.models.llama import _route_topk
from paddle_tpu.models.sarvam_mla import (SarvamMlaConfig,
                                          SarvamMlaForCausalLM)
import paddle_tpu.observability as obs
from paddle_tpu.observability import metrics

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.references import sarvam_mla as ref  # noqa: E402

GEOMETRY = dict(max_batch=4, max_seq_len=256, page_size=16, prefill_bucket=64)
PROMPTS = (130, 5, 70, 33)      # three chunks, one token row, two chunks


def _prompts(vocab):
    rng = np.random.default_rng(1)
    return [list(rng.integers(1, vocab, n)) for n in PROMPTS]


def _model(held=8, offset=0, **kw):
    """A tiny model that holds ``held`` of its 8 experts (the banks are the
    uncut model's, sliced) with a selection bias wide enough to matter."""
    paddle.seed(0)
    full = SarvamMlaForCausalLM(SarvamMlaConfig.tiny(**kw))
    bias = full.experts._parameters["mlp.gate.bias"]
    bias._data = 0.2 * jax.random.normal(jax.random.key(5), bias._data.shape,
                                         jnp.float32)
    if held == 8:
        return full
    paddle.seed(0)
    model = SarvamMlaForCausalLM(SarvamMlaConfig.tiny(
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
    m = {k: getattr(c, k) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rms_norm_eps", "rope_theta",
        "rope_scaling", "num_experts_per_tok", "num_shared_experts",
        "moe_router_enable_expert_bias", "routed_scaling_factor",
        "first_k_dense_replace", "vocab_size", "num_hidden_layers")}
    m["num_experts"] = c.experts_held
    m["published"] = {"num_experts": c.num_experts}
    m["share"] = {"chips": c.num_experts // c.experts_held,
                  "index": c.expert_offset // c.experts_held}
    params = model.serving_params()
    k = c.first_k_dense_replace

    def get_layer(l):
        if l < k:
            return dict(params["leading"][l])
        return {n: a[l - k] for n, a in params["blocks"][0].items()}

    flat = {n: params[n] for n in ("embed", "norm", "head")}
    return m, get_layer, flat


def _reference_logits(model, seqs):
    m, get_layer, flat = _reference_model(model)
    with jax.default_matmul_precision("highest"):
        return ref.sequence_logits(
            get_layer, flat, m["num_hidden_layers"], m, seqs,
            [list(range(len(s))) for s in seqs])


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["xla_oracle", "interpreted_kernel"])
@pytest.mark.parametrize("held,offset", [(8, 0), (2, 4)],
                         ids=["all_held", "a_quarter_held"])
def test_engine_serves_what_the_references_full_forward_gives(
        held, offset, interpret):
    """Prefill in chunks of 64 and decoding through the latent pool (pages
    of 16, the absorbed call) serve tokens whose logit under the plain
    reference (expanded form, no cache, float32) is its best at every
    served position: logits compared, never sampled tokens alone."""
    model = _model(held, offset)
    flags.set_flags({"paged_attention_interpret": interpret})
    try:
        eng = ContinuousBatchingEngine(model, **GEOMETRY)
        prompts = _prompts(model.config.vocab_size)
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        done = eng.run()
    finally:
        flags.set_flags({"paged_attention_interpret": False})
    seqs = [p + done[r.req_id][:-1] for p, r in zip(prompts, reqs)]
    logits = _reference_logits(model, seqs)
    for p, r, lg in zip(prompts, reqs, logits):
        served = np.asarray(done[r.req_id])
        at = lg[len(p) - 1:]
        gap = at.max(-1) - np.take_along_axis(at, served[:, None], -1)[:, 0]
        assert gap.max() <= 1e-4, (len(p), gap)


def test_chunked_prefill_logits_are_the_references():
    """The hidden states of two steps (a chunk of 64, then the rest and a
    decode row) through ``_forward_tokens`` give, under the head, the
    reference's logits at every position."""
    model = _model()
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    g = eng.g
    B, T = 4, 64
    prompts = _prompts(model.config.vocab_size)
    seqs = [prompts[0][:100], prompts[2][:64] + [7]]
    table = jnp.asarray(np.arange(B * g.pages_per_seq, dtype=np.int32)
                        .reshape(B, g.pages_per_seq))
    toks = np.zeros((2, B, T), np.int32)
    toks[0, 0], toks[0, 1] = seqs[0][:64], seqs[1][:64]
    toks[1, 0, :36], toks[1, 1, 0] = seqs[0][64:], seqs[1][64]
    qls = np.array([[64, 64, 0, 0], [36, 1, 0, 0]], np.int32)
    cache, pos = tuple(g.cache.arrays), jnp.zeros((B,), jnp.int32)
    got = [[], []]
    for step in range(2):
        ql = jnp.asarray(qls[step])
        h, cache, _ = g._forward_tokens(g.params, cache,
                                        jnp.asarray(toks[step]), ql, pos,
                                        table)
        lg = np.asarray(g._head_logits(g.params, h))
        for b in range(2):
            got[b].append(lg[b, :qls[step, b]])
        pos = pos + ql
    want = _reference_logits(model, seqs)
    for b in range(2):
        np.testing.assert_allclose(np.concatenate(got[b]), want[b],
                                   rtol=2e-4, atol=2e-4)


def test_absorbed_equals_expanded():
    """The engine (``W_uk`` carried into the query, one row of keys for all
    heads, ``W_uv`` after the call) serves the greedy tokens of the model's
    own whole-sequence forward, which expands every head's key and value."""
    model = _model()
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    prompts = _prompts(model.config.vocab_size)[:2]
    reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    done = eng.run()
    for p, r in zip(prompts, reqs):
        seq, out = list(p), []
        for _ in range(5):
            logits = model(paddle.to_tensor(np.asarray([seq], np.int32)))
            out.append(int(np.asarray(logits._data)[0, -1].argmax()))
            seq.append(out[-1])
        assert done[r.req_id] == out
    # and the model's logits are the reference's
    want = _reference_logits(model, [prompts[0]])[0]
    got = np.asarray(model(paddle.to_tensor(
        np.asarray([prompts[0]], np.int32)))._data)[0]
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_yarn_frequencies_and_softmax_scale_at_the_published_numbers():
    """deepseek_yarn at theta 10000, factor 40, original 4096, beta 32 / 1
    over the 64-wide rope part: dimensions under 10 rotate as published,
    those over 23 forty times slower, a linear ramp between; cos / sin
    unscaled; the softmax scale 192^-0.5 x (0.1 ln 40 + 1)^2 = 0.1352."""
    spec = SarvamMlaForCausalLM.decoder_spec(
        type("M", (), {"config": SarvamMlaConfig.sarvam_105b()})())
    yarn = spec.rope_yarn
    assert yarn == RopeYarn(40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    inv = yarn.inv_freq(64, 10000.0)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    mid = inv[11:23] / plain[11:23]
    assert np.all(np.diff(mid) < 0) and mid[0] < 1 and mid[-1] > 1 / 40
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(
        64, 10000.0, SarvamMlaConfig.sarvam_105b().rope_scaling), rtol=1e-6)
    assert yarn.table_scale == 1.0
    assert abs(spec.softmax_scale - 0.1352) < 5e-5
    assert abs(spec.softmax_scale - 192 ** -0.5 * 1.8739) < 1e-5
    assert spec.latent == LatentAttn(rank=512, nope=128, rope=64, value=128)
    assert (spec.num_layers, spec.periods, len(spec.leading)) == (32, 31, 1)
    assert spec.leading[0].dense_ffn and not spec.pattern[0].dense_ffn


@pytest.mark.parametrize("head_dim, theta, seq", [
    (128, 1e6, 8192), (128, 50000.0, 12544), (16, 10000.0, 256)],
    ids=["mistral", "command-a-plus", "tiny"])
def test_a_plain_stacks_rotary_tables_are_what_the_engine_always_built(
        head_dim, theta, seq):
    """No yarn and no latent head: ``DecoderSpec.rope_tables`` gives the
    float32 tables of ``models.llama._rope_cos_sin`` bit for bit, so the
    engine builds every family's from the spec (one path, PR 31)."""
    from paddle_tpu.models.decoder_spec import LayerKind
    from paddle_tpu.models.llama import _rope_cos_sin
    spec = DecoderSpec(pattern=(LayerKind(),), periods=2, num_heads=4,
                       num_kv_heads=2, head_dim=head_dim, rope_theta=theta)
    want = _rope_cos_sin(seq, head_dim, theta, jnp.float32)
    for got, w in zip(spec.rope_tables(seq), want):
        assert got.dtype == np.float32 and got.shape == (seq, head_dim // 2)
        assert np.array_equal(got, np.asarray(w))


def test_the_bias_selects_and_is_not_in_the_gate():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 8)) / 6, jnp.float32)
    bias = jnp.asarray([2.0, -2.0, 0, 0, 0, 0, 0, 0], jnp.float32)
    s = np.asarray(jax.nn.sigmoid(x @ w))
    v0, i0, _, _ = _route_topk(x, w, 3, "sigmoid", scale=2.5)
    v, i, _, _ = _route_topk(x, w, 3, "sigmoid", bias=bias, scale=2.5)
    i, v = np.asarray(i), np.asarray(v)
    # a bias of +2 always selects expert 0, one of -2 never expert 1
    assert np.all((i == 0).any(-1)) and not (i == 1).any()
    assert (np.asarray(i0) == 1).any()
    # the chosen are the largest of s + b; the gates are 2.5 s / sum(s)
    want_i = np.argsort(-(s + np.asarray(bias)), -1)[:, :3]
    assert np.array_equal(np.sort(i, -1), np.sort(want_i, -1))
    chosen = np.take_along_axis(s, i, -1)
    np.testing.assert_allclose(v, 2.5 * chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(v.sum(-1), 2.5, rtol=1e-6)
    # the reference's router, written apart, agrees entry for entry
    m = {"num_experts_per_tok": 3, "moe_router_enable_expert_bias": True,
         "routed_scaling_factor": 2.5}
    gates = np.asarray(ref.router_gates(
        x, {"mlp.gate.weight": w, "mlp.gate.bias": bias}, m))
    np.testing.assert_allclose(np.take_along_axis(gates, i, -1), v, rtol=1e-5)
    assert np.count_nonzero(gates) == 16 * 3


def test_the_four_shares_add_up_to_the_uncut_layer():
    """One expert layer: the routed parts of the four shares (each chip's
    own experts, the program's ``_moe_ffn``), with the shared expert counted
    once, add up to the uncut reference's routed sum plus its shared
    expert; the dense layer is what every chip computes alike."""
    from paddle_tpu.inference.generation import _moe_ffn
    full = _model()
    m, get_layer, _ = _reference_model(full)
    w = get_layer(1)
    rng = np.random.default_rng(4)
    y = jnp.asarray(rng.normal(size=(48, full.config.hidden_size)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(ref.shared_experts(y, w, m, "highest"))
        whole = np.asarray(ref.routed_experts(y, w, m, "highest")) + shared
        parts = []
        for index in range(4):
            model = _model(2, 2 * index)
            lp = {n: a[0] for n, a in
                  model.serving_params()["blocks"][0].items()}
            out, rows = _moe_ffn(y, lp, model.decoder_spec().moe)
            parts.append(np.asarray(out) - shared)     # the routed part
            assert rows is not None
            # the reference handed the same share gives the same part
            mi, gl, _ = _reference_model(model)
            np.testing.assert_allclose(
                parts[-1], np.asarray(ref.routed_experts(y, gl(1), mi,
                                                         "highest")),
                rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=2e-4,
                               atol=2e-5)
    # every share's leading dense layer is the uncut model's, whole
    dense = full.serving_params()["leading"][0]
    for name, a in _model(2, 4).serving_params()["leading"][0].items():
        assert np.array_equal(np.asarray(a), np.asarray(dense[name]))


def test_the_step_counts_rows_and_reads_for_a_latent_place(tmp_path):
    """``attn_rows`` = q_len x heads in whole row tiles against one row of
    keys; ``kv_read_tokens`` one row a cached token a layer, on the step's
    span; the gauge gives the pool bytes a token over all layers."""
    model = _model()
    eng = ContinuousBatchingEngine(model, metrics=True, **GEOMETRY)
    g = eng.g
    heads, layers = model.config.num_attention_heads, 3
    assert g.attn_rows(64, [(64, 0), (1, 100)]) == 64 * heads + 64 * 0 + \
        -(-1 * heads // 256) * 256
    assert g.attn_rows(1, [(1, 100)]) == max(8, heads)
    assert g.kv_read_tokens([(64, 10), (1, 100)]) == layers * (74 + 101)
    row = (model.config.kv_lora_rank + model.config.qk_rope_head_dim) * 4
    assert g.pool_bytes == g.num_pages * 16 * layers * row
    assert metrics.gauge("serving.kv_bytes_per_token").value == layers * row
    obs.tracer.start()
    try:
        eng.submit(_prompts(256)[3], max_new_tokens=3)
        eng.run()
    finally:
        obs.tracer.stop()
    doc = json.load(open(obs.export_chrome_trace(str(tmp_path / "t.json"))))
    reads = [e["args"]["kv_read_tokens"] for e in doc["traceEvents"]
             if e["name"] == "engine.step" and e["args"]["T"]]
    # a prompt of 33 in one chunk, then a decode row a step until the host
    # has gathered the last token, a step or two behind the device (the
    # host counts what it dispatched: a row frozen on the device too); the
    # idle step that flushes what is still in flight reads nothing
    assert len(reads) >= 3
    assert reads == [layers * (33 + i) for i in range(len(reads))]


@pytest.mark.parametrize("preset", ["sarvam_mla_tiny"])
def test_the_launcher_preset_serves_through_the_same_engine(preset):
    from paddle_tpu.serving.__main__ import build_engine, build_parser
    args = build_parser().parse_args(
        ["--preset", preset, "--max-batch", "2", "--max-seq-len", "64",
         "--page-size", "16", "--prefill-bucket", "16"])
    eng = build_engine(args)
    assert type(eng) is ContinuousBatchingEngine
    assert isinstance(eng.g.config, SarvamMlaConfig)
    req = eng.submit(list(range(1, 30)), max_new_tokens=3)
    assert len(eng.run()[req.req_id]) == 3
    # the parameters exist once: the engine holds the model's own arrays
    model_params = eng.g.params
    assert len(model_params["leading"]) == 1
    banks = model_params["blocks"][0]["mlp.experts_gate"]
    assert isinstance(banks, tuple) and len(banks) == 2
    assert model_params["head"].shape == (64, 256)      # untied


def test_the_share_preset_states_the_published_widths():
    from paddle_tpu.serving.__main__ import _SARVAM_MLA_PRESETS
    c = _SARVAM_MLA_PRESETS["sarvam_105b_ep4"](SarvamMlaConfig)
    assert (c.hidden_size, c.intermediate_size, c.moe_intermediate_size) == \
        (4096, 16384, 2048)
    assert (c.num_attention_heads, c.q_head_dim, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank) == \
        (64, 192, 128, 64, 128, 512)
    assert (c.num_experts, c.experts_held, c.num_experts_per_tok) == \
        (128, 32, 8)
    assert (c.num_hidden_layers, c.first_k_dense_replace, c.vocab_size) == \
        (5, 1, 65536)
    moe = c.moe_spec()
    assert moe.partial and moe.score == "sigmoid" and moe.shared == 1
    assert moe.select_bias and moe.gate_scale == 2.5


@pytest.mark.parametrize("key, value", [
    ("hidden_act", "gelu"), ("tie_word_embeddings", True),
    ("q_head_dim", 128), ("head_dim", 512),
    ("rope_scaling", {"type": "linear", "factor": 2})])
def test_what_the_model_does_not_compute_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        SarvamMlaConfig.from_source({key: value})


def test_a_stack_holds_one_kind_of_pool():
    la = LatentAttn(rank=128, nope=128, rope=64, value=128)
    with pytest.raises(ValueError, match="one pool"):
        DecoderSpec(pattern=(LayerKind(latent=la), LayerKind()), periods=1,
                    num_heads=4, num_kv_heads=1, head_dim=192)
    with pytest.raises(ValueError, match="sequential"):
        DecoderSpec(pattern=(LayerKind(latent=la),), periods=1, num_heads=4,
                    num_kv_heads=1, head_dim=192, parallel_block=True)


@pytest.mark.parametrize("mode", ["ngram", "fused"])
def test_the_speculative_lanes_serve_through_the_latent_pool(mode):
    """``serve_spec_verify_K*`` and ``serve_fused_K*`` run the same core
    (``_forward_tokens``), so they commit latent rows and read them back:
    their tokens are the plain engine's."""
    model = _model()
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
