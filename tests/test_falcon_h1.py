"""``models/falcon_h1.py`` through the serving engine at test size (2 groups,
a group of 5 query heads a KV head, a state wider than a head, time scales
from a token to hundreds): prefill in chunks of the bucket and decoding
through pool and recurrent state against the plain reference's full forward
(``chipbench/references/falcon_h1.py``: the bare recurrence, float32),
LOGITS compared; a reused slot; a frozen slot; every multiplier."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import flags
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.models.falcon_h1 import (FalconH1Config,
                                         FalconH1ForCausalLM)
import paddle_tpu.observability as obs
from paddle_tpu.observability import metrics

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.references import falcon_h1 as ref  # noqa: E402

GEOMETRY = dict(max_batch=4, max_seq_len=256, page_size=16, prefill_bucket=16)
PROMPTS = (130, 5, 16, 33)      # nine chunks, one, a whole one, three
# float32 on both sides: the engine's chunk form, its cached keys and its
# packed rows are the reference's numbers summed in another order, so the
# logits (all within +-0.035 here, std 0.0079: lm_head_multiplier 0.0078)
# agree to a float32 rounding or two: the engine reads 6.5e-9 off the
# reference.  With the recurrent state rounded to bf16 between steps it
# reads 3.5e-6 to 4.2e-6 (``test_a_bf16_state_would_fail_the_tolerance``),
# so the tolerance stands 15 times over the one and 35 times under the other.
LOGIT_ATOL = 1e-7


def _model(**kw):
    """The tiny model with the mixer's small leaves drawn, not at their
    initial zeros: a head's ``dt x |A|`` from 0.003 (remembers hundreds of
    tokens) to over 1 (forgets within one)."""
    paddle.seed(0)
    model = FalconH1ForCausalLM(FalconH1Config.tiny(**kw))
    rng = np.random.default_rng(3)
    p = model.layers._parameters
    shape = p["mamba.dt_bias"]._data.shape                   # [layers, heads]
    p["mamba.dt_bias"]._data = jnp.asarray(
        np.tile([-4.5, -2.0, 0.0, 1.0], (shape[0], 1)), jnp.float32)
    p["mamba.A_log"]._data = jnp.asarray(
        np.tile([-1.2, 0.0, 0.5, -0.5], (shape[0], 1)), jnp.float32)
    for name, std in (("mamba.D", 1.0), ("mamba.conv1d.bias", 0.5)):
        a = p[name]._data
        p[name]._data = jnp.asarray(rng.normal(0, std, a.shape), a.dtype)
    return model


def _prompts(vocab, lens=PROMPTS):
    rng = np.random.default_rng(1)
    return [list(rng.integers(1, vocab, n)) for n in lens]


def _reference_model(model):
    """(m, get_layer, flat) as the harness hands them to the reference:
    ``Run.model``'s keys from the model's config, the model's own arrays."""
    c = model.config
    m = {k: getattr(c, k) for k in (
        "hidden_size", "intermediate_size", "vocab_size", "head_dim",
        "num_attention_heads", "num_key_value_heads", "num_hidden_layers",
        "rms_norm_eps", "rope_theta", "mamba_d_ssm", "mamba_n_heads",
        "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
        "embedding_multiplier", "attention_in_multiplier", "key_multiplier",
        "attention_out_multiplier", "ssm_in_multiplier", "ssm_multipliers",
        "ssm_out_multiplier", "mlp_multipliers", "lm_head_multiplier")}
    params = model.serving_params()
    stack, = params["blocks"]
    return m, (lambda l: {n: a[l] for n, a in stack.items()}), \
        {n: params[n] for n in ("embed", "norm", "head")}


def _reference_logits(model, seqs, **over):
    m, get_layer, flat = _reference_model(model)
    m.update(over)
    with jax.default_matmul_precision("highest"):
        return ref.sequence_logits(
            get_layer, flat, m["num_hidden_layers"], m, seqs,
            [list(range(len(s))) for s in seqs])


def _engine_logits(model, seqs, state_dtype=None):
    """Logits at every position of ``seqs`` (one a slot) from the engine's
    own ``_forward_tokens``, a chunk of the bucket a step, through pool and
    recurrent state; ``state_dtype`` rounds the state between steps."""
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
            chunk = s[pos[b]:pos[b] + T]
            toks[b, :len(chunk)], ql[b] = chunk, len(chunk)
        h, cache = step(cache, jnp.asarray(toks), jnp.asarray(ql),
                        jnp.asarray(pos))
        if state_dtype is not None:
            cache = cache[:2] + (cache[2].astype(state_dtype)
                                 .astype(jnp.float32),) + cache[3:]
        lg = np.asarray(g._head_logits(g.params, h))
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


@pytest.mark.timeout(300)
def test_chunked_prefill_logits_are_the_references(model, seqs,
                                                   engine_logits):
    """Seven chunks of 16 through pages and recurrent state give, under the
    head, the reference's logits (the recurrence from ``S_0 = 0`` over the
    whole sequence, no cache) at every position."""
    want = _reference_logits(model, seqs)
    for got, w in zip(engine_logits, want):
        assert np.abs(got - w).max() <= LOGIT_ATOL, np.abs(got - w).max()


@pytest.mark.timeout(300)
def test_a_bf16_state_would_fail_the_tolerance(model, seqs):
    """The same steps with the recurrent state rounded to bf16 between
    them read far over ``LOGIT_ATOL``: the tolerance is tight enough to
    catch a state kept in lower precision than the model's file says."""
    want = _reference_logits(model, seqs)
    got = _engine_logits(model, seqs, state_dtype=jnp.bfloat16)
    worst = max(np.abs(g - w).max() for g, w in zip(got, want))
    assert worst > 10 * LOGIT_ATOL, worst


@pytest.mark.timeout(300)
@pytest.mark.parametrize("interpret", [False, True],
                         ids=["xla_oracle", "interpreted_kernel"])
def test_engine_serves_what_the_references_full_forward_gives(model,
                                                              interpret):
    """``submit`` / ``step`` with mixed steps (prefill chunks beside decode
    rows, five requests through four slots) serve tokens whose logit under
    the plain reference is its best at every served position."""
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


def test_the_models_own_forward_is_the_references(model, seqs):
    got = np.asarray(model(paddle.to_tensor(
        np.asarray([seqs[1]], np.int32)))._data)[0]
    want = _reference_logits(model, [seqs[1]])[0]
    assert np.abs(got - want).max() <= LOGIT_ATOL


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


def test_a_finished_slots_frozen_steps_leave_its_state_bit_equal(
        model, monkeypatch):
    """Until it has gathered a slot's last token the host keeps dispatching
    a slot the device has frozen (its budget is spent); here no step looks
    landed and the bound is far, so it does for nine steps: ``ql`` is 0
    there, the scan call names a neighbour's block and the convolution
    keeps the carried rows, so the slot's state does not move while the
    other slot's does."""
    from paddle_tpu.inference import generation
    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", 64)
    monkeypatch.setattr(generation._InFlight, "landed", lambda self: False)
    short, long_ = _prompts(model.config.vocab_size, (9, 12))
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    eng.submit(short, max_new_tokens=2)
    eng.submit(long_, max_new_tokens=12)
    for _ in range(4):             # the prompts' chunk, then slot 0 is done
        eng.step()
    before = [np.asarray(a) for a in eng.g.cache.recurrent.arrays]
    for _ in range(5):
        eng.step()
    after = [np.asarray(a) for a in eng.g.cache.recurrent.arrays]
    for a, b in zip(before, after):
        assert np.array_equal(a[:, 0], b[:, 0])              # frozen
        assert not np.array_equal(a[:, 1], b[:, 1])          # still running
    assert len(eng._pending) == 9 and eng.slot_req[0] is not None
    done = eng.run()
    assert [len(done[i]) for i in (0, 1)] == [2, 12]


MULTIPLIERS = [("embedding_multiplier", None), ("attention_in_multiplier", None),
               ("key_multiplier", None), ("attention_out_multiplier", None),
               ("ssm_in_multiplier", None), ("ssm_out_multiplier", None),
               ("lm_head_multiplier", None)] \
    + [("ssm_multipliers", i) for i in range(5)] \
    + [("mlp_multipliers", i) for i in range(2)]


@pytest.mark.timeout(300)
@pytest.mark.parametrize("key,index", MULTIPLIERS,
                         ids=[k if i is None else f"{k}[{i}]"
                              for k, i in MULTIPLIERS])
def test_every_multiplier_is_applied_where_the_equations_say(
        model, seqs, engine_logits, key, index):
    """The engine's logits are the reference's at the published value of
    every multiplier (the test above) and NOT the reference's with this one
    doubled: each is applied, and where the equations put it."""
    value = getattr(model.config, key)
    doubled = 2 * value if index is None else tuple(
        2 * v if i == index else v for i, v in enumerate(value))
    want = _reference_logits(model, seqs[1:], **{key: doubled})[0]
    assert np.abs(engine_logits[1] - want).max() > 5 * LOGIT_ATOL


def test_the_published_multipliers_reach_the_engines_spec():
    c = FalconH1Config.falcon_h1_34b(num_hidden_layers=4)
    spec = FalconH1ForCausalLM.decoder_spec(type("M", (), {"config": c})())
    mx = spec.ssm
    assert (spec.embed_scale, spec.logit_scale) == (5.656854249492381,
                                                    0.0078125)
    assert (spec.attn_in_scale, spec.key_scale, spec.attn_out_scale) == (
        1.0, 0.011048543456039804, 0.0375)
    assert (spec.mlp_gate_scale, spec.mlp_out_scale) == (
        0.1767766952966369, 0.011160714285714284)
    assert (mx.in_scale, mx.out_scale) == (0.25, 0.08838834764831845)
    assert mx.zone_scales == (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    assert (mx.heads, mx.head_dim, mx.state, mx.groups, mx.conv) == (
        32, 128, 256, 2, 4)
    assert (mx.inner, mx.conv_width, mx.in_width) == (4096, 5120, 9248)
    assert spec.rope_theta == 1e11 and spec.num_heads // spec.num_kv_heads == 5
    assert spec.num_layers * mx.state_bytes("bfloat16") == 16_900_096
    cos, sin = spec.rope_tables(2048)
    assert np.isfinite(cos).all() and cos.shape == (2048, 64)


def test_what_the_model_file_does_not_compute_is_refused():
    for bad in (dict(mamba_norm_before_gate=True), dict(attention_bias=True),
                dict(mamba_d_ssm=48), dict(rope_scaling={"factor": 2}),
                dict(ssm_multipliers=(1.0, 1.0))):
        with pytest.raises(ValueError, match="falcon_h1"):
            FalconH1Config.tiny(**bad)
    source = json.load(open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "chipbench/configs/falcon-h1-34b-instruct.json")))["model"]
    c = FalconH1Config.from_source(source, num_hidden_layers=4)
    assert dict(c.__dict__, num_hidden_layers=72) == \
        FalconH1Config.falcon_h1_34b().__dict__


def test_spans_counters_and_the_gauge_of_the_recurrent_state(model, tmp_path):
    """``engine.step`` carries the slots whose state the step's scan calls
    move and the tokens they scan; ``serving.state_bytes_per_slot`` beside
    ``serving.kv_bytes_per_token``; ``serving.state_resets`` counts the
    slots zeroed at admission."""
    resets = metrics.counter("serving.state_resets")
    before = resets.value
    eng = ContinuousBatchingEngine(model, metrics=True, **GEOMETRY)
    c = model.config
    per_layer = 4 * c.mamba_n_heads * c.mamba_d_head * c.mamba_d_state \
        + 3 * (c.mamba_d_ssm + 2 * c.mamba_n_groups * c.mamba_d_state) * 4
    assert eng.g.state_bytes_per_slot == c.num_hidden_layers * per_layer
    assert metrics.gauge("serving.state_bytes_per_slot").value == \
        eng.g.state_bytes_per_slot
    assert metrics.gauge("serving.kv_bytes_per_token").value == \
        c.num_hidden_layers * 2 * c.num_key_value_heads * c.head_dim * 4
    obs.tracer.start()
    try:
        for p in _prompts(c.vocab_size, (33, 5, 7)):
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
    assert all(a["ssm_tokens"] == a["q_tokens"]
               and 0 < a["ssm_slots"] <= a["rows"] for a in steps)


@pytest.mark.parametrize("preset", ["falcon_h1_tiny"])
def test_the_launcher_preset_serves_through_the_same_engine(preset):
    from paddle_tpu.serving.__main__ import build_engine, build_parser
    args = build_parser().parse_args(
        ["--preset", preset, "--max-batch", "2", "--max-seq-len", "64",
         "--page-size", "16", "--prefill-bucket", "16"])
    eng = build_engine(args)
    assert type(eng) is ContinuousBatchingEngine
    assert isinstance(eng.g.config, FalconH1Config)
    req = eng.submit(list(range(1, 30)), max_new_tokens=3)
    assert len(eng.run()[req.req_id]) == 3


def test_the_packed_step_serves_what_the_dense_step_serves(model,
                                                           monkeypatch):
    """With the packed member in reach (its floor lowered to 16 rows) a
    mixed step's per-token work runs over the packed rows and the mixer's
    convolution and scan over the slots' places: the same tokens."""
    from paddle_tpu.inference import generation as gen
    prompts = _prompts(model.config.vocab_size, (40, 3, 18))

    def serve():
        eng = ContinuousBatchingEngine(
            model, gen=GenerationConfig(max_new_tokens=5), **GEOMETRY)
        reqs = [eng.submit(p) for p in prompts]
        done = eng.run()
        return eng, [done[r.req_id] for r in reqs]

    dense_eng, dense = serve()
    assert dense_eng.g.row_buckets(16) == [64]
    monkeypatch.setattr(gen, "MIN_GEMM_ROWS", 16)
    packed_eng, packed = serve()
    assert packed_eng.g.row_buckets(16) == [16, 64]
    assert packed == dense
