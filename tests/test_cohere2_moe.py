"""``models/cohere2_moe.py`` through the serving engine at test size: a
stack whose layers are of more than one kind, a share of the experts, the
launcher's presets, the speculative lanes under a window, and the guard
that holds the Llama family's period-of-one path to the numbers of its
layers written out by hand."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.kernels.paged_attention import ragged_paged_attention
from paddle_tpu.kernels.rms_norm import rms_norm_fp32
from paddle_tpu.models.cohere2_moe import (Cohere2MoeConfig,
                                           CohereMoeForCausalLM)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import metrics

GEOMETRY = dict(max_batch=4, max_seq_len=128, page_size=8, prefill_bucket=16)
PROMPTS = (70, 5, 33)          # past the window of 24, inside it, across it


def _prompts(vocab):
    rng = np.random.default_rng(1)
    return [list(rng.integers(1, vocab, n)) for n in PROMPTS]


def _model(dispatch, held, offset):
    """A tiny model that holds ``held`` of its 8 experts: the banks are the
    uncut model's, sliced, so every share routes with the same router."""
    paddle.seed(0)
    full = CohereMoeForCausalLM(Cohere2MoeConfig.tiny(moe_dispatch=dispatch))
    if held == 8:
        return full
    paddle.seed(0)
    model = CohereMoeForCausalLM(Cohere2MoeConfig.tiny(
        moe_dispatch=dispatch, experts_held=held, expert_offset=offset))
    for mine, whole in zip(model.blocks, full.blocks):
        for name, p in mine._parameters.items():
            a = whole._parameters[name]._data
            p._data = a[:, offset:offset + held] \
                if name.startswith("mlp.experts_") else a
    model.embed_tokens._data = full.embed_tokens._data
    return model


def _greedy_by_forward(model, prompt, n):
    seq, out = list(prompt), []
    for _ in range(n):
        logits = model(paddle.to_tensor(np.asarray([seq], np.int32)))
        out.append(int(np.asarray(logits._data)[0, -1].argmax()))
        seq.append(out[-1])
    return out


@pytest.mark.parametrize("held,offset", [(8, 0), (4, 4)],
                         ids=["all_held", "half_held"])
@pytest.mark.parametrize("dispatch", ["dense", "grouped"])
def test_engine_serves_the_mixed_stack_as_the_whole_sequence_forward(
        dispatch, held, offset):
    """Prefill in chunks of 16 and decode through pages of 8 (two periods of
    one sliding layer of window 24 and one full layer without positions)
    give the tokens of the model's own whole-sequence forward."""
    model = _model(dispatch, held, offset)
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    assert eng.g.spec.windows == (24, None, 24, None)
    before = metrics.histogram("serving.moe_held_rows").count
    prompts = _prompts(model.config.vocab_size)
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    done = eng.run()
    for req, prompt in zip(reqs, prompts):
        assert done[req.req_id] == _greedy_by_forward(model, prompt, 6)
    counted = metrics.histogram("serving.moe_held_rows").count - before
    # a share counts its rows, and the grouped path with every expert held
    assert (counted > 0) == (held < 8 or dispatch == "grouped")


def test_a_share_counts_its_rows_on_the_drain_that_exists():
    """Each plain step observes [entries on held experts, rows laid out],
    the sums over the layers; they ride to the host with the step's own
    tokens and nothing but the gather reads them."""
    model = _model("grouped", 4, 0)
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    held_h = metrics.histogram("serving.moe_held_rows")
    laid_h = metrics.histogram("serving.moe_rows_laid_out")
    h0, l0 = held_h.sum, laid_h.sum
    n0, m0 = held_h.count, laid_h.count
    steps0 = metrics.counter("serving.steps").value
    eng.submit(_prompts(256)[0], max_new_tokens=4)
    eng.run()
    steps = metrics.counter("serving.steps").value - steps0
    assert held_h.count - n0 == laid_h.count - m0 == steps > 0
    held, laid = held_h.sum - h0, laid_h.sum - l0
    # 70 prompt tokens + 3 fed-back tokens, 4 layers, 4 of 8 experts chosen:
    # at most every choice falls here, and every held expert owns a tile
    assert 0 < held <= 73 * 4 * 4
    assert laid >= held and laid >= steps * 4 * 4 * 8
    assert eng._pending == []


@pytest.mark.parametrize("mode", ["ngram", "fused"])
def test_the_speculative_lanes_take_the_window(mode):
    """``serve_spec_verify_K*`` and ``serve_fused_K*`` run the same core, so
    they see the window: their tokens are the plain engine's."""
    model = _model("dense", 8, 0)
    prompts = _prompts(model.config.vocab_size)
    plain = ContinuousBatchingEngine(model, **GEOMETRY)
    spec = ContinuousBatchingEngine(model, spec_decode=mode, spec_k=4,
                                    **GEOMETRY)
    want = [plain.submit(p, max_new_tokens=12) for p in prompts]
    got = [spec.submit(p, max_new_tokens=12) for p in prompts]
    a, b = plain.run(), spec.run()
    assert spec.stats()["spec_steps"] > 0
    for w, g in zip(want, got):
        assert a[w.req_id] == b[g.req_id]


def test_kv_read_tokens_applies_each_layers_window():
    eng = ContinuousBatchingEngine(_model("dense", 8, 0), **GEOMETRY)
    g = eng.g
    # a decode token after 100: a window of 24 reads 24 keys, a full layer 101
    assert g.kv_read_tokens([(1, 100)]) == 2 * 24 + 2 * 101
    # a chunk of 16 after 10: nothing lies behind the window yet
    assert g.kv_read_tokens([(16, 10)]) == 4 * 26
    # a chunk of 16 after 40: the first query sees 17.. of the context
    assert g.kv_read_tokens([(16, 40), (1, 0)]) == 2 * 39 + 2 * 56 + 4 * 1
    llama = ContinuousBatchingEngine(
        LlamaForCausalLM(LlamaConfig.tiny()), **GEOMETRY)
    assert llama.g.kv_read_tokens([(16, 40)]) == 2 * 56


@pytest.mark.parametrize("preset", ["cohere2_moe_tiny"])
def test_the_launcher_preset_serves_through_the_same_engine(preset):
    from paddle_tpu.serving.__main__ import build_engine, build_parser
    args = build_parser().parse_args(
        ["--preset", preset, "--max-batch", "2", "--max-seq-len", "64",
         "--page-size", "8", "--prefill-bucket", "16"])
    eng = build_engine(args)
    assert type(eng) is ContinuousBatchingEngine
    assert isinstance(eng.g.config, Cohere2MoeConfig)
    req = eng.submit(list(range(1, 30)), max_new_tokens=3)
    assert len(eng.run()[req.req_id]) == 3
    # the parameters exist once: the engine scans the model's own arrays
    place = eng.g.params["blocks"][0]
    for name, p in args and eng.g.params["blocks"][0].items():
        assert p is place[name]
    assert "head" not in eng.g.params          # tied: the embedding itself


def test_the_command_a_plus_share_preset_states_the_published_widths():
    from paddle_tpu.serving.__main__ import _COHERE2_MOE_PRESETS
    c = _COHERE2_MOE_PRESETS["command_a_plus_ep8"](Cohere2MoeConfig)
    assert (c.hidden_size, c.intermediate_size, c.head_dim) == (4096,) * 2 + (128,)
    assert (c.num_attention_heads, c.num_key_value_heads) == (128, 8)
    assert (c.num_experts, c.experts_held, c.num_experts_per_tok) == (128, 16, 8)
    assert (c.num_hidden_layers, c.period(), c.vocab_size) == (4, 4, 32768)
    moe = c.moe_spec()
    assert moe.partial and moe.score == "sigmoid" and moe.shared == 4


@pytest.mark.parametrize("key, value", [
    ("use_qk_norm", True), ("position_embedding_type", "rope"),
    ("shared_expert_combination_strategy", "sum"),
    ("norm_topk_prob", False), ("logit_scale", 0.25)])
def test_what_the_model_does_not_compute_is_refused(key, value):
    """One rotary pairing, the mean of the shared experts, renormalised
    gates and a logit scale of 1 are what Command A+ and the Llama family
    state: another value is refused at the configuration, not ignored."""
    with pytest.raises(ValueError, match=key):
        Cohere2MoeConfig.from_source({key: value})


def test_the_models_parameters_are_the_engines_stacks():
    model = CohereMoeForCausalLM(Cohere2MoeConfig.tiny())
    params = model.serving_params()
    assert len(params["blocks"]) == 2              # a period of two places
    for place, layer in zip(params["blocks"], model.blocks):
        for name, arr in place.items():
            assert arr is layer._parameters[name]._data
            assert arr.shape[0] == 2               # [periods, ...]
    assert params["embed"] is model.embed_tokens._data


def _rope(x, cos, sin):
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                     axis=-1).reshape(x.shape).astype(x.dtype)


@pytest.mark.parametrize("experts", [0, 4], ids=["llama", "mixtral"])
def test_the_period_of_one_is_its_layers_written_out_by_hand(experts):
    """The Llama family through the pattern-of-kinds code (a scan over
    periods of ONE layer, the pool read by layer number) gives the hidden
    states of the same layers unrolled by hand over one layer's cache
    slice each (the form the step had before PR 27).  Float32 on both
    sides; a scan's body and eager operations fuse differently, so the
    last bits differ: held to 2e-5 (the lowered step programs themselves
    were compared with the parent's text, CHANGES.md)."""
    from paddle_tpu.inference.generation import _moe_ffn
    paddle.seed(3)
    cfg = LlamaConfig.mixtral_tiny() if experts else LlamaConfig.tiny()
    eng = ContinuousBatchingEngine(LlamaForCausalLM(cfg), **GEOMETRY)
    g, c = eng.g, cfg
    B, T = 4, 16
    rng = np.random.default_rng(2)
    table = jnp.asarray(np.arange(B * g.pages_per_seq, dtype=np.int32)
                        .reshape(B, g.pages_per_seq))
    cache = tuple(g.cache.arrays)
    toks = jnp.asarray(rng.integers(1, c.vocab_size, (B, T)).astype(np.int32))
    ql = jnp.asarray(np.array([16, 9, 1, 0], np.int32))
    pos = jnp.zeros((B,), jnp.int32)
    _, cache, _ = g._forward_tokens(g.params, cache, toks, ql, pos, table)
    toks2 = jnp.asarray(rng.integers(1, c.vocab_size, (B, T)).astype(np.int32))
    got, _, _ = g._forward_tokens(g.params, cache, toks2, ql, ql, table)

    blocks, = g.params["blocks"]
    kv, = cache
    offs = jnp.arange(T, dtype=jnp.int32)
    at = jnp.minimum(ql[:, None] + offs[None, :], g.max_seq_len - 1)
    cos, sin = jnp.take(g._cos, at, axis=0), jnp.take(g._sin, at, axis=0)
    x = jnp.take(g.params["embed"], toks2, axis=0)
    for l in range(c.num_hidden_layers):
        lp = {k: v[l] for k, v in blocks.items()}
        y = rms_norm_fp32(x, lp["input_layernorm.weight"], c.rms_norm_eps)
        q = (y @ lp["self_attn.q_proj.weight"]).reshape(
            B, T, c.num_attention_heads, c.head_dim)
        k = (y @ lp["self_attn.k_proj.weight"]).reshape(
            B, T, c.num_key_value_heads, c.head_dim)
        v = (y @ lp["self_attn.v_proj.weight"]).reshape(
            B, T, c.num_key_value_heads, c.head_dim)
        q, k = _rope(q, cos, sin), _rope(k, cos, sin)
        attn = ragged_paged_attention(q, kv[l], table, ql, q_lens=ql,
                                      k_new=k, v_new=v)
        x = x + attn.reshape(B, T, -1) @ lp["self_attn.o_proj.weight"]
        y = rms_norm_fp32(x, lp["post_attention_layernorm.weight"],
                          c.rms_norm_eps)
        if experts:
            x = x + _moe_ffn(y, lp, g.spec.moe)[0]
        else:
            x = x + (jax.nn.silu(y @ lp["mlp.gate_proj.weight"])
                     * (y @ lp["mlp.up_proj.weight"])) \
                @ lp["mlp.down_proj.weight"]
    want = rms_norm_fp32(x, g.params["norm"], c.rms_norm_eps)
    live = np.arange(T)[None, :] < np.asarray(ql)[:, None]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
