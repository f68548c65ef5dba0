"""The plain references against the program at tiny widths (CPU, float32),
the seeded weights, and the lower-precision control."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import weights  # noqa: E402
from chipbench.references import llama as ref  # noqa: E402

DENSE = {"hidden_size": 256, "intermediate_size": 384,
         "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 128,
         "vocab_size": 320, "rms_norm_eps": 1e-5, "rope_theta": 1e6,
         "num_hidden_layers": 2, "torch_dtype": "float32"}
MOE = dict(DENSE, num_local_experts=4, num_experts_per_tok=2)


def _program_logits(m, seed, ids):
    """The program's own eager forward, carrying the benchmark's weights."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    from chipbench.programs.llama import llama_config
    model = LlamaForCausalLM(llama_config(m, 256))
    leaves = ref.leaf_specs(m)
    for l, lyr in enumerate(model.llama.layers):
        w = weights.make_layer(seed, leaves, l, "float32")
        for name, p in lyr.named_parameters():
            p._data = w[name]
    flat = weights.make_flat(seed, leaves, "float32")
    model.llama.embed_tokens.weight._data = flat["embed"]
    model.lm_head.weight._data = flat["head"]
    model.llama.norm.weight._data = flat["norm"]
    out = model(paddle.to_tensor(np.asarray(ids, np.int64)[None]))
    return np.asarray(out._data)[0]


def _reference_logits(m, seed, ids, precision="highest"):
    leaves = ref.leaf_specs(m)
    flat = weights.make_flat(seed, leaves, "float32")
    return ref.sequence_logits(
        lambda l: weights.make_layer(seed, leaves, l, "float32"), flat,
        m["num_hidden_layers"], m, [ids], [list(range(len(ids)))],
        precision=precision)[0]


@pytest.mark.parametrize("m", [DENSE, MOE], ids=["dense", "experts"])
def test_reference_forward_equals_the_programs(m):
    rng = np.random.default_rng(3)
    ids = rng.integers(1, m["vocab_size"], 70).tolist()
    got = _program_logits(m, 2**31 + 99, ids)
    want = _reference_logits(m, 2**31 + 99, ids)
    assert got.shape == want.shape == (70, m["vocab_size"])
    assert np.max(np.abs(got - want)) < 2e-4 * max(1.0, np.abs(want).max())
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.98


@pytest.mark.parametrize("m", [DENSE, MOE], ids=["dense", "experts"])
def test_the_int8_control_is_told_apart_from_float32(m):
    """The control must be refused: at the same positions its first choice
    lies below the reference's best by far more than a sound float32 run's
    served token does (which is 0 here)."""
    rng = np.random.default_rng(4)
    ids = rng.integers(1, m["vocab_size"], 200).tolist()
    want = _reference_logits(m, 7, ids)
    low = _reference_logits(m, 7, ids, precision="int8")
    sound = want.max(-1) - np.take_along_axis(
        want, want.argmax(-1)[:, None], -1)[:, 0]
    control = want.max(-1) - np.take_along_axis(
        want, low.argmax(-1)[:, None], -1)[:, 0]
    assert sound.max() == 0.0
    assert np.abs(low - want).max() > 1e-3
    assert control.max() > 1e-3 and (control > 0).mean() > 0.01


def test_stacked_and_layerwise_weights_are_the_same_numbers():
    leaves = ref.leaf_specs(MOE)
    big = 2**31 + 12345                      # more than 32 signed bits hold
    stacked = weights.make(big, leaves, 3, "float32")
    for l in range(3):
        one = weights.make_layer(big, leaves, l, "float32")
        for name, v in one.items():
            assert np.array_equal(np.asarray(stacked[name][l]),
                                  np.asarray(v)), name
    flat = weights.make_flat(big, leaves, "float32")
    assert np.array_equal(np.asarray(flat["embed"]),
                          np.asarray(stacked["embed"]))
    other = weights.make_layer(big + 1, leaves, 0, "float32")
    assert not np.array_equal(np.asarray(other["mlp.experts_up"]),
                              np.asarray(stacked["mlp.experts_up"][0]))
    # a seed and the seed 2**31 higher are different seeds
    wrapped = weights.make_flat(big - 2**31, leaves, "float32")
    assert not np.array_equal(np.asarray(wrapped["head"]),
                              np.asarray(flat["head"]))


def test_weights_have_the_stated_spread_and_type():
    leaves = ref.leaf_specs(DENSE)
    w = weights.make(5, leaves, 2, "bfloat16")
    assert str(w["head"].dtype) == "bfloat16"
    q = np.asarray(w["self_attn.q_proj.weight"], np.float32)
    assert q.shape == (2, 256, 256)
    assert abs(q.std() - 1 / 16) < 0.005 and abs(q.mean()) < 0.002
    n = np.asarray(w["input_layernorm.weight"], np.float32)
    assert abs(n.mean() - 1.0) < 0.03 and 0.05 < n.std() < 0.15


def test_adamw_step_by_hand():
    hp = {"beta1": 0.9, "beta2": 0.95, "eps": 1e-8, "learning_rate": 0.1,
          "weight_decay": 0.5}
    p, m, v = ref.adamw(np.float32(2.0), np.float32(4.0), np.float32(0.0),
                        np.float32(0.0), 1.0, hp)
    # first step: m_hat = g, v_hat = g^2, update = sign(g)
    assert float(m) == pytest.approx(0.4) and float(v) == pytest.approx(0.8)
    assert float(p) == pytest.approx(2.0 - 0.1 * (1.0 + 0.5 * 2.0), rel=1e-6)


def test_worst_leaf_gap_measures_against_the_median_leaf():
    from chipbench.drivers.train import worst_leaf_gap
    want = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.1, "b": 2.0, "c": 3e-9}
    gap, leaf = worst_leaf_gap(got, want)
    # c is all but zero: its gap counts against the median leaf, not itself
    assert leaf == "a" and gap == pytest.approx(0.1)
