"""``models/smallthinker.py`` through the serving engine at test size (6
query heads over 2, 8 experts of which 3 are chosen, a window of 48, two
periods of full / window / window / window): the model's own forward,
prefill in chunks and then decoding through the paged cache, and the
engine's ``submit`` / ``step``, against the plain reference's full forward
(``chipbench/references/smallthinker.py``, float32) on sequences that cross
the window three times over; each thing the reference states, done
otherwise, fails that comparison; a place's unstacked banks at two periods;
the two new fields of ``MoeSpec``; the presets, the refusals, the counters.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.inference import generation as gen
from paddle_tpu.models import llama as llama_mod
from paddle_tpu.models.decoder_spec import (EXPERT_BANKS, DecoderSpec,
                                            LatentAttn, LayerKind, MoeSpec)
from paddle_tpu.models.smallthinker import (SmallThinkerConfig,
                                            SmallThinkerForCausalLM,
                                            _forward)
import paddle_tpu.observability as obs
from paddle_tpu.observability import metrics

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench.references import smallthinker as ref  # noqa: E402

GEOMETRY = dict(max_batch=4, max_seq_len=256, page_size=16, prefill_bucket=16)
# a sequence that crosses the window of 48 three times over beside short
# ones, in one batch; the last DECODE tokens of each go one a step
LENGTHS = (150, 20, 70, 5)
DECODE = 4
# float32 on both sides: the engine and the reference differ by the order
# of their sums (measured 5e-6 on logits of up to 4.4); each departure below
# moves a logit by 0.05 or more
LOGIT_ATOL = 5e-5
SOURCE_KEYS = (
    "hidden_size", "vocab_size", "head_dim", "num_attention_heads",
    "num_key_value_heads", "num_hidden_layers", "rms_norm_eps", "rope_theta",
    "rope_layout", "sliding_window_layout", "sliding_window_size",
    "moe_ffn_hidden_size", "moe_num_primary_experts",
    "moe_num_active_primary_experts", "moe_primary_router_apply_softmax",
    "norm_topk_prob")


def _prompts(vocab, lens=LENGTHS):
    rng = np.random.default_rng(1)
    return [list(rng.integers(1, vocab, n)) for n in lens]


def _reference_model(model):
    """(m, get_layer, flat) as the harness hands them to the reference:
    ``Run.model``'s keys from the model's config, the model's own arrays
    (layer ``l`` is place ``l % 4`` in period ``l // 4``)."""
    c = model.config
    m = {k: getattr(c, k) for k in SOURCE_KEYS}
    params = model.serving_params()
    P = len(params["blocks"])

    def get_layer(l):
        return {n: a[l // P] for n, a in params["blocks"][l % P].items()}

    return m, get_layer, {n: params[n] for n in ("embed", "norm", "head")}


def _reference_logits(model, seqs, assumed=None, **over):
    m, get_layer, flat = _reference_model(model)
    m.update(over)
    return ref.sequence_logits(
        get_layer, flat, m["num_hidden_layers"], m, seqs,
        [list(range(len(s))) for s in seqs], assumed=assumed)


def _engine_logits(model, seqs, decode=DECODE):
    """Logits at every position of ``seqs`` (one a slot) from the engine's
    own ``_forward_tokens``, as the engine drives it: all but the last
    ``decode`` tokens of each sequence in chunks of the bucket (the last one
    ragged), the rest one token a step, short and long in one batch."""
    eng = ContinuousBatchingEngine(model, **GEOMETRY)
    g = eng.g
    B, bucket = GEOMETRY["max_batch"], GEOMETRY["prefill_bucket"]
    table = jnp.asarray(np.arange(B * g.pages_per_seq, dtype=np.int32)
                        .reshape(B, g.pages_per_seq))
    cache, pos = tuple(g.cache.arrays), np.zeros((B,), np.int32)
    got = [[] for _ in seqs]
    step = jax.jit(lambda c, t, q, p: g._forward_tokens(g.params, c, t, q, p,
                                                        table)[:2])
    while any(pos[b] < len(s) for b, s in enumerate(seqs)):
        prefilling = any(pos[b] < len(s) - decode
                         for b, s in enumerate(seqs))
        T = bucket if prefilling else 1
        toks, ql = np.zeros((B, T), np.int32), np.zeros((B,), np.int32)
        for b, s in enumerate(seqs):
            # a slot that has reached its decode part takes one token a step
            n = min(T, len(s) - decode - pos[b]) \
                if pos[b] < len(s) - decode else min(1, len(s) - pos[b])
            toks[b, :n], ql[b] = s[pos[b]:pos[b] + n], n
        h, cache = step(cache, jnp.asarray(toks), jnp.asarray(ql),
                        jnp.asarray(pos))
        lg = np.asarray(g._head_logits(g.params, h))
        for b in range(len(seqs)):
            got[b].append(lg[b, :ql[b]])
        pos = pos + ql
    return [np.concatenate(rows) for rows in got]


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return SmallThinkerForCausalLM(SmallThinkerConfig.tiny())


@pytest.fixture(scope="module")
def seqs(model):
    return _prompts(model.config.vocab_size)


@pytest.fixture(scope="module")
def engine_logits(model, seqs):
    return _engine_logits(model, seqs)


@pytest.fixture(scope="module")
def engine(model):
    """ONE tiny engine for the cases that drive ``submit`` / ``step``."""
    return ContinuousBatchingEngine(model, metrics=True, **GEOMETRY)


# ------------------------------------------- against the plain reference ----

def test_the_models_own_forward_is_the_references(model, seqs):
    spec, params = model.decoder_spec(), model.serving_params()
    ids = jnp.asarray([seqs[0]], jnp.int32)
    got = np.asarray(jax.jit(lambda p: _forward(spec, p, ids))(params))[0]
    want = _reference_logits(model, [seqs[0]])[0]
    assert np.abs(got - want).max() <= LOGIT_ATOL
    assert np.abs(want).max() > 1.0


def test_prefill_in_chunks_then_decoding_is_the_references_full_forward(
        model, seqs, engine_logits):
    """Chunks of 16 (mixed with decoding slots once the short sequences
    reach their last tokens), then one token a step, through the paged
    cache with and without a window, without positions on the full layers,
    the choice made before the attention call: the reference's logits (no
    cache, the whole sequence at once) at every position of four sequences
    of 5 to 150 tokens, the longest over three windows of 48."""
    want = _reference_logits(model, seqs)
    for got, w in zip(engine_logits, want):
        assert got.shape == w.shape
        assert np.abs(got - w).max() <= LOGIT_ATOL, np.abs(got - w).max()


DEPARTURES = {
    "the_router_reads_the_ffns_input": dict(
        assumed={"router_input": "ffn", "activation": "relu"}),
    "silu_for_relu": dict(
        assumed={"router_input": "attention", "activation": "silu"}),
    "rope_on_the_full_layers": dict(rope_layout=(1,) * 8),
    "no_rope_on_the_windowed_layers": dict(rope_layout=(0,) * 8),
    "softmax_over_all_unnormalised": dict(norm_topk_prob=False),
    "the_window_off": dict(sliding_window_layout=(0,) * 8),
}


@pytest.mark.parametrize("what", sorted(DEPARTURES))
def test_what_the_reference_states_moves_its_logits(model, seqs,
                                                    engine_logits, what):
    """Each thing the reference states, done otherwise, puts it far outside
    the tolerance the engine is held to above: were the program to route
    from ``z``, run SiLU, rotate the full layers, not rotate the windowed
    ones, leave the softmax over all experts unnormalised or drop the
    window, the comparison would fail.  (The window moves nothing before
    position 48: the first 48 logits of that case agree.)"""
    other = _reference_logits(model, [seqs[0]], **DEPARTURES[what])[0]
    worst = np.abs(other - engine_logits[0]).max()
    assert worst > 100 * LOGIT_ATOL, worst
    if what == "the_window_off":
        assert np.abs(other[:48] - engine_logits[0][:48]).max() <= LOGIT_ATOL
        assert np.abs(other[48:] - engine_logits[0][48:]).max() \
            > 100 * LOGIT_ATOL


def test_a_places_banks_are_its_own_layers_at_two_periods(model, seqs,
                                                          engine_logits):
    """Place 1 holds the banks of layers 1 and 5, unstacked, and the scan
    picks the period's by ``lax.switch``: the engine's logits are the
    reference's with layer ``l``'s banks at layer ``l`` (above), and leave
    it by far where the reference is handed the two layers' banks
    swapped."""
    params = model.serving_params()
    assert all(isinstance(place[n], tuple) and len(place[n]) == 2
               for place in params["blocks"] for n in EXPERT_BANKS)
    m, get_layer, flat = _reference_model(model)

    def swapped(l):
        w = dict(get_layer(l))
        if l in (1, 5):
            w.update({n: get_layer(6 - l)[n] for n in EXPERT_BANKS})
        return w

    other = ref.sequence_logits(swapped, flat, 8, m, [seqs[2]],
                                [list(range(len(seqs[2])))])[0]
    assert np.abs(other - engine_logits[2]).max() > 100 * LOGIT_ATOL


def test_engine_serves_what_the_references_full_forward_gives(model, engine):
    """``submit`` / ``step`` with packed mixed steps (prefill chunks beside
    decode rows, five requests through four slots) serve tokens whose logit
    under the plain reference is its best at every served position."""
    prompts = _prompts(model.config.vocab_size, LENGTHS + (21,))
    reqs = [engine.submit(p, max_new_tokens=6) for p in prompts]
    done = engine.run()
    logits = _reference_logits(
        model, [p + done[r.req_id][:-1] for p, r in zip(prompts, reqs)])
    for p, r, lg in zip(prompts, reqs, logits):
        served = np.asarray(done[r.req_id])
        at = lg[len(p) - 1:]
        gap = at.max(-1) - np.take_along_axis(at, served[:, None], -1)[:, 0]
        assert gap.max() <= LOGIT_ATOL, (len(p), gap)


# ------------------------------------------------ the spec's new fields ----

@pytest.mark.parametrize("kw,match", [
    (dict(router_input="mlp"), "router input"),
    (dict(activation="gelu"), "expert activation"),
])
def test_the_moe_spec_refuses_what_it_does_not_know(kw, match):
    with pytest.raises(ValueError, match=match):
        MoeSpec(8, 2, **kw)


def test_the_new_fields_default_to_what_every_family_ran():
    moe = MoeSpec(8, 2)
    assert (moe.router_input, moe.activation) == ("ffn", "silu")
    stated = MoeSpec(8, 2, router_input="attention", activation="relu")
    assert (stated.router_input, stated.activation) == ("attention", "relu")


def test_a_latent_stack_refuses_a_router_on_the_attentions_input():
    la = LatentAttn(rank=32, nope=16, rope=8, value=16)
    with pytest.raises(ValueError, match="per-head attention"):
        DecoderSpec(pattern=(LayerKind(latent=la),), periods=1, num_heads=2,
                    num_kv_heads=1, head_dim=24,
                    moe=MoeSpec(8, 2, router_input="attention"))


@pytest.mark.parametrize("dispatch", ["grouped", "dense"])
def test_the_defaults_add_no_operation(dispatch):
    """A family that states neither field traces what it traced: the
    jaxpr of ``_moe_ffn`` with the fields left out, with them stated at
    their defaults, and of the choice and the experts called apart on one
    tensor are the same text; ReLU experts trace another."""
    rng = np.random.default_rng(0)
    H, I, E = 32, 16, 4
    lp = {"mlp.gate.weight": jnp.asarray(rng.normal(size=(H, E)), jnp.float32),
          "mlp.experts_gate": jnp.asarray(rng.normal(size=(E, H, I)),
                                          jnp.float32),
          "mlp.experts_up": jnp.asarray(rng.normal(size=(E, H, I)),
                                        jnp.float32),
          "mlp.experts_down": jnp.asarray(rng.normal(size=(E, I, H)),
                                          jnp.float32)}
    y = jnp.asarray(rng.normal(size=(12, H)), jnp.float32)

    def text(moe, apart=False):
        def run(y, lp):
            if apart:
                return gen._moe_experts(y, lp, moe,
                                        gen._moe_choice(y, lp, moe))
            return gen._moe_ffn(y, lp, moe)
        return str(jax.make_jaxpr(run)(y, lp))

    left_out = MoeSpec(E, 2, dispatch=dispatch, block_m=8)
    stated = MoeSpec(E, 2, dispatch=dispatch, block_m=8, router_input="ffn",
                     activation="silu")
    assert text(left_out) == text(stated) == text(left_out, apart=True)
    relu = MoeSpec(E, 2, dispatch=dispatch, block_m=8, activation="relu")
    assert text(relu) != text(left_out)
    assert "max" in text(relu) and "logistic" not in text(relu)


@pytest.mark.parametrize("kw,counts", [
    (dict(dispatch="grouped"), 3),              # every expert held: + fullest
    (dict(dispatch="grouped", held=2, offset=2), 2),
    (dict(dispatch="dense", held=2, offset=2), 2),
    (dict(dispatch="dense"), None),
])
def test_what_an_arm_of_the_experts_counts(kw, counts):
    """The step's counts by arm, with no field of the spec asking for
    them: the whole-bank grouped arm hands back the fullest expert's
    entries beside the two every counting arm gives (Mixtral's too)."""
    H, I, E = 32, 16, 4
    moe = MoeSpec(E, 2, block_m=8, **kw)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    lp = {"mlp.gate.weight": f32(H, E),
          "mlp.experts_gate": f32(moe.held, H, I),
          "mlp.experts_up": f32(moe.held, H, I),
          "mlp.experts_down": f32(moe.held, I, H)}
    _, rows = jax.eval_shape(lambda y, lp: gen._moe_ffn(y, lp, moe),
                             f32(12, H), lp)
    assert (rows is None) if counts is None else rows.shape == (counts,)


def test_relu_experts_through_the_grouped_call_are_the_dense_mixtures():
    """``_grouped_ffn_fwd(activation="relu")`` against the sum written
    out; training's ``_grouped_ffn`` takes no activation (its backward is
    written for SiLU alone) and still gives SiLU's numbers to the bit."""
    from paddle_tpu.kernels.grouped_matmul import sorted_dispatch_plan
    rng = np.random.default_rng(3)
    N, H, I, E, k, bm = 10, 32, 16, 4, 2, 8
    x = jnp.asarray(rng.normal(size=(N, H)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(E, H, I)), jnp.float32) * 0.2
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(E, I, H)), jnp.float32) * 0.2
    topi = jnp.asarray(np.stack([rng.permutation(E)[:k] for _ in range(N)]))
    gates = jnp.asarray(rng.uniform(size=(N, k)), jnp.float32)
    inv, pos, tg = sorted_dispatch_plan(topi.reshape(N * k), E, bm)
    args = (x, wg, wu, wd, gates, inv, pos, tg, E, k, bm)
    for name, act in (("relu", jax.nn.relu), ("silu", jax.nn.silu)):
        got = llama_mod._grouped_ffn_fwd(*args, activation=name)[0]
        want = sum(gates[:, j, None] * jnp.einsum(
            "ni,nih->nh", act(jnp.einsum("nh,nhi->ni", x, wg[topi[:, j]]))
            * jnp.einsum("nh,nhi->ni", x, wu[topi[:, j]]), wd[topi[:, j]])
            for j in range(k))
        assert np.abs(np.asarray(got - want)).max() < 1e-5
    assert np.array_equal(
        np.asarray(llama_mod._grouped_ffn(*args)),
        np.asarray(llama_mod._grouped_ffn_fwd(*args, activation="silu")[0]))


def test_relu_experts_stay_replicated_under_tensor_parallelism():
    """The tensor-parallel grouped arm runs training's ``_grouped_ffn``
    (SiLU): a stack of ReLU experts is never handed to it, and the arm
    itself refuses one with a sentence."""
    moe = MoeSpec(4, 2, dispatch="grouped", activation="relu")
    with pytest.raises(ValueError, match="tensor parallelism"):
        gen._moe_experts(jnp.zeros((4, 8)), {n: jnp.zeros((4, 8, 8))
                                             for n in EXPERT_BANKS},
                         moe, (jnp.zeros((4, 2)), jnp.zeros((4, 2), int)),
                         mp_shards=2)


# ----------------------------------------- the spec, presets, refusals ----

def test_the_spec_states_the_published_numbers():
    c = SmallThinkerConfig.smallthinker_21b()
    spec = SmallThinkerForCausalLM.decoder_spec(
        type("M", (), {"config": c})())
    full, windowed = LayerKind(window=None, rope=False), \
        LayerKind(window=4096, rope=True)
    assert spec.pattern == (full, windowed, windowed, windowed)
    assert (spec.periods, spec.num_layers) == (2, 8)
    assert spec.moe == MoeSpec(
        num_experts=64, top_k=6, score="softmax", dispatch="grouped",
        block_m=128, router_input="attention", activation="relu")
    assert (spec.num_heads, spec.num_kv_heads, spec.head_dim) == (28, 4, 128)
    assert (spec.norm, spec.norm_eps, spec.rope_theta) == ("rms", 1e-6, 1.5e6)
    assert not spec.parallel_block and spec.windows == \
        (None, 4096, 4096, 4096) * 2
    assert SmallThinkerConfig().num_hidden_layers == 52
    assert SmallThinkerConfig().period() == 4


@pytest.mark.parametrize("over,match", [
    (dict(moe_primary_router_apply_softmax=False), "softmax"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(rope_layout=[0, 1, 1, 0, 0, 1, 1, 1]), "layer 3"),
    (dict(num_hidden_layers=6), "whole periods"),
])
def test_what_the_model_file_does_not_compute_is_refused(over, match):
    source = dict(SmallThinkerConfig.tiny().__dict__)
    source.update(over)
    with pytest.raises(ValueError, match=match):
        SmallThinkerConfig.from_source(source)


def test_from_source_reads_the_sources_own_keys():
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chipbench", "configs",
            "smallthinker-21ba3b-instruct.json")) as f:
        source = json.load(f)["model"]
    c = SmallThinkerConfig.from_source(source, num_hidden_layers=8)
    assert c == SmallThinkerConfig.smallthinker_21b(depth=8)
    for key in SOURCE_KEYS:
        if key not in ("num_hidden_layers", "rope_layout",
                       "sliding_window_layout"):
            assert getattr(c, key) == source[key], key
    assert c.rope_layout == tuple(source["rope_layout"][:8])
    assert c.dtype == "bfloat16"


@pytest.mark.parametrize("preset", ["smallthinker_tiny"])
def test_the_launcher_preset_serves_through_the_same_engine(preset):
    from paddle_tpu.serving.__main__ import (_SMALLTHINKER_PRESETS,
                                             build_engine, build_parser)
    args = build_parser().parse_args(
        ["--preset", preset, "--max-batch", "2", "--max-seq-len", "64",
         "--page-size", "16", "--prefill-bucket", "16"])
    eng = build_engine(args)
    assert type(eng) is ContinuousBatchingEngine
    assert isinstance(eng.g.config, SmallThinkerConfig)
    req = eng.submit(list(range(1, 30)), max_new_tokens=3)
    assert len(eng.run()[req.req_id]) == 3
    assert _SMALLTHINKER_PRESETS["smallthinker_21b"](SmallThinkerConfig) \
        == SmallThinkerConfig.smallthinker_21b(depth=8)


@pytest.mark.parametrize("sets,want", [
    ([], False), (["--set", "autotune_enable=true"], True)])
def test_the_launcher_tunes_no_kernel_unless_asked(monkeypatch, sets, want):
    """``python -m paddle_tpu.serving`` starts with the kernels' autotuner
    off (its probe fails inside the step's trace on the chip: the engine
    thread died in warm-up and ``/readyz`` stayed 503), as every benchmark
    cell does; ``--set`` still has the last word."""
    from paddle_tpu import flags
    from paddle_tpu.serving import __main__ as launcher
    from paddle_tpu.serving import server
    seen = {}
    monkeypatch.setattr(launcher, "build_engine", lambda args: seen.update(
        tuned=flags.flag("autotune_enable")) or "engine")
    monkeypatch.setattr(server, "serve_forever", lambda engine, **kw:
                        seen.update(engine=engine, **kw))
    before = flags.flag("autotune_enable")
    try:
        assert launcher.main(["--preset", "smallthinker_21b", *sets]) == 0
    finally:
        flags.set_flags({"autotune_enable": before})
    assert seen["tuned"] is want and seen["engine"] == "engine"
    assert seen["model_name"] == "smallthinker_21b" and seen["warmup"]


# ------------------------------------------------ spans and counters ----

def test_the_router_before_attention_has_a_scope_of_its_own(engine):
    """A device trace tells the early router from the experts: its
    operations carry ``moe_router``, between two runs of ``attention``."""
    text = engine.lowered_step(16).as_text(debug_info=True)
    assert "moe_router/router" in text and "moe/experts" in text
    assert "moe/router" not in text


def test_the_fullest_experts_entries_are_observed_while_somebody_listens(
        model, engine):
    """``serving.moe_expert_rows_max``: the fullest expert's entries,
    summed over the eight layers, one observation a step whose counts land
    while a tracer listens and none otherwise; between ``entries /
    experts`` and the step's tokens.  ``serving.kv_bytes_per_token`` reads
    8 layers x 2 x 2 KV heads x 32 x 4 B here (16,384 at the published
    sizes: 8 x 2 x 4 x 128 x 2 B)."""
    assert metrics.gauge("serving.kv_bytes_per_token").value == \
        8 * 2 * 2 * 32 * 4
    assert 8 * 2 * 4 * 128 * 2 == 16384
    assert engine.g.counts_moe_rows
    h = metrics.histogram("serving.moe_expert_rows_max")
    held = metrics.histogram("serving.moe_held_rows")
    before, held_before = (h.count, h.sum), (held.count, held.sum)
    engine.submit(_prompts(256)[2], max_new_tokens=3)
    engine.run()
    assert h.count == before[0] and held.count > held_before[0]
    before, held_before = (h.count, h.sum), (held.count, held.sum)
    obs.tracer.start()
    try:
        engine.submit(_prompts(256)[2], max_new_tokens=3)      # 70 tokens
        engine.run()
    finally:
        obs.tracer.stop()
    steps = held.count - held_before[0]
    assert h.count - before[0] == steps > 0
    entries = held.sum - held_before[1]         # both over eight layers
    fullest = h.sum - before[1]
    # 3 of 8 experts a token: the fullest holds at least an eighth of the
    # entries and at most a third (every token's entries go to 3 experts)
    assert entries / 8 <= fullest <= entries / 3 + 1e-9
