"""Peaks, the kernels' operations and bytes against hand-worked cases, the
105 % rule, and the operations a training token needs."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import model_math, roofline, spec  # noqa: E402
from chipbench.references import llama as ref  # noqa: E402

V5E = roofline.peaks("TPU v5 lite")


def test_peaks_of_the_v5e_and_an_unknown_kind():
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["ici_bits_per_s"] == 1600e9
    assert "source" in V5E
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks("_about")


def test_least_time_says_which_bound():
    assert roofline.min_time_s(197e12, 1.0, V5E) == (1.0, "compute")
    t, bound = roofline.min_time_s(1.0, 819e9 * 2, V5E)
    assert (t, bound) == (2.0, "memory")


@pytest.mark.parametrize("least,took,ok", [(1.0, 2.0, True), (1.0, 1.0, True),
                                           (1.04, 1.0, True),
                                           (1.06, 1.0, False),
                                           (3.0, 1.0, False)])
def test_a_share_above_105_percent_fails_with_its_numbers(least, took, ok):
    if ok:
        assert roofline.share_pct("k", least, took) == pytest.approx(
            100 * least / took)
    else:
        with pytest.raises(roofline.ShareTooHigh) as e:
            roofline.share_pct("k", least, took, calls=3)
        assert "calls" in str(e.value) and str(least) in str(e.value)


def test_a_share_is_never_clipped_to_100():
    assert roofline.share_pct("k", 1.03, 1.0) == pytest.approx(103.0)


def test_paged_attention_cost_by_hand():
    k = spec.load_module(ROOT, "kernels", "paged_attention")
    # one decode row, 100 tokens of context, 32 query heads, 8 KV heads, 128
    flops, nbytes = k.cost([(1, 100)], 32, 8, 128)
    assert flops == 4 * 32 * 128 * (100 + 1)
    assert nbytes == 2 * 8 * 101 * 128 * 2 + 2 * 32 * 1 * 128 * 2
    # a prefill chunk of 64 on an empty cache: causal inside the chunk
    flops, _ = k.cost([(64, 0)], 32, 8, 128)
    assert flops == 4 * 32 * 128 * (64 * 65 / 2)
    # idle slots cost nothing; rows add
    a = k.cost([(1, 100), (0, 0), (64, 0)], 32, 8, 128)
    assert a[0] == k.cost([(1, 100)], 32, 8, 128)[0] + flops
    # a decode row is bound by reading its context
    t, bound = roofline.min_time_s(*k.cost([(1, 2000)] * 32, 32, 8, 128), V5E)
    assert bound == "memory"


def test_grouped_matmul_cost_by_hand():
    k = spec.load_module(ROOT, "kernels", "grouped_matmul")
    shapes = {"rows": 4096, "k": 4096, "n": 14336, "experts": 8}
    flops, nbytes = k.cost(shapes)
    assert flops == 2 * 4096 * 4096 * 14336
    assert nbytes == 2 * (8 * 4096 * 14336 + 4096 * 4096 + 4096 * 14336)
    assert roofline.min_time_s(flops, nbytes, V5E)[1] == "compute"
    # a decode step's 64 routed rows are bound by the experts' weights
    few = dict(shapes, rows=64)
    assert roofline.min_time_s(*k.cost(few), V5E)[1] == "memory"


def test_grouped_matmul_match_leaves_the_padding_out():
    from chipbench.harness.trace_reduce import parse_op
    k = spec.load_module(ROOT, "kernels", "grouped_matmul")
    op = parse_op(
        "%closed_call.38 = bf16[5120,14336]{1,0} custom-call(s32[10]{0} %t, "
        "bf16[5120,4096]{1,0} %x, bf16[8,4096,14336]{2,1,0} %w), "
        "custom_call_target=\"tpu_custom_call\"", 0.0, 1.0)
    got = k.match(op)
    assert (got["rows_laid_out"], got["block_m"], got["rows"]) == (
        5120, 512, 1024)
    assert (got["k"], got["n"], got["experts"]) == (4096, 14336, 8)
    paged = spec.load_module(ROOT, "kernels", "paged_attention")
    flash = spec.load_module(ROOT, "kernels", "flash_attention")
    assert paged.match(op) is None and flash.match(op) is None


def test_flash_attention_cost_by_hand():
    k = spec.load_module(ROOT, "kernels", "flash_attention")
    s = {"kind": "fwd", "b": 1, "hq": 32, "hkv": 8, "sq": 4096, "sk": 4096,
         "d": 128}
    flops, nbytes = k.cost(s)
    assert flops == 2 * 2 * 32 * (4096 * 4096 / 2) * 128       # 2 matmuls
    q, kv = 32 * 4096 * 128, 8 * 4096 * 128
    assert nbytes == 2 * (2 * q + 2 * kv)
    assert k.cost(dict(s, kind="dq"))[0] == flops * 3 / 2
    assert k.cost(dict(s, kind="dkv"))[0] == flops * 2
    assert roofline.min_time_s(flops, nbytes, V5E)[1] == "compute"


def test_operations_a_training_token_needs():
    m = spec.load_json(os.path.join(
        ROOT, "chipbench/configs/mistral-7b-v0.3.json"))
    model = dict(m["model"], num_hidden_layers=m["depth"]["train"])
    n = ref.count_params(model, 3)
    assert n["per_layer"] == m["mfu"]["params_per_layer"] == 218112000
    assert n["embed_and_head"] == m["mfu"]["embed_and_head"]
    assert n["total"] == n["active"] == 3 * 218112000 + 268439552
    f = model_math.train_flops_per_token(model, n["active"], 4096)
    assert f["six_n"] == 6.0 * 922775552
    assert f["attention"] == 3 * 6.0 * 4096 * 4096
    assert f["total"] == pytest.approx(5.8386e9, rel=1e-3)


def test_mixtral_counts_two_of_eight_experts_as_active():
    m = spec.load_json(os.path.join(
        ROOT, "chipbench/configs/mixtral-8x7b-v0.1.json"))["model"]
    n = ref.count_params(m, 32)
    assert 46.5e9 < n["total"] < 46.9e9          # "8x7B" holds 46.7 G
    assert 12.7e9 < n["active"] < 13.1e9         # and touches 12.9 G a token
