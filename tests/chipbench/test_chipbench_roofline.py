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


def _cost_before_the_window(rows, q_heads, kv_heads, head_dim,
                            dtype_bytes=2):
    """``cost`` as it stood before it knew a window (PR 23), kept here so
    that ``window=None`` is held to it to the last digit."""
    flops = nbytes = 0.0
    for q, ctx in rows:
        if q <= 0:
            continue
        attended = q * ctx + q * (q + 1) / 2.0
        flops += 4.0 * q_heads * head_dim * attended
        kv_tokens = ctx + q
        nbytes += 2.0 * kv_heads * kv_tokens * head_dim * dtype_bytes \
            + 2.0 * q_heads * q * head_dim * dtype_bytes
    return flops, nbytes


ROWS = [[(1, 100)], [(64, 0)], [(64, 4031), (1, 4223), (0, 0), (37, 12)],
        [(1, 7)] * 32, [(64, 64 * i) for i in range(40)],
        [(q, 997 * q % 4000) for q in range(1, 65)]]


@pytest.mark.parametrize("rows", ROWS, ids=range(len(ROWS)))
def test_paged_attention_cost_without_a_window_is_as_before(rows):
    k = spec.load_module(ROOT, "kernels", "paged_attention")
    for heads in ((32, 8, 128), (128, 8, 128)):
        assert k.cost(rows, *heads) == _cost_before_the_window(rows, *heads)
        assert k.cost(rows, *heads, window=None, page_size=16) == \
            _cost_before_the_window(rows, *heads)
        # a window wider than every context changes nothing either
        assert k.cost(rows, *heads, window=10 ** 6, page_size=1) == \
            pytest.approx(_cost_before_the_window(rows, *heads), rel=1e-15)


def test_paged_attention_cost_with_a_window_by_hand():
    k = spec.load_module(ROOT, "kernels", "paged_attention")
    hq, hkv, d = 32, 8, 128
    # a decode row behind 100 tokens, window 16: it attends to the last 16
    # (15 of the context and itself); they begin at token 85, in the page
    # of 16 that begins at 80, so 21 tokens of K and of V are read
    flops, nbytes = k.cost([(1, 100)], hq, hkv, d, window=16, page_size=16)
    assert flops == 4 * hq * d * 16
    assert nbytes == 2 * hkv * 21 * d * 2 + 2 * hq * 1 * d * 2
    # by tokens, not pages: 16 are read
    assert k.cost([(1, 100)], hq, hkv, d, window=16)[1] == \
        2 * hkv * 16 * d * 2 + 2 * hq * 1 * d * 2
    # a chunk of 8 behind 4 tokens, window 6: the query tokens see
    # 5, 6, 6, 6, 6, 6, 6, 6 = 47; the first looks back to token 0
    flops, nbytes = k.cost([(8, 4)], hq, hkv, d, window=6, page_size=4)
    assert flops == 4 * hq * d * 47
    assert nbytes == 2 * hkv * 12 * d * 2 + 2 * hq * 8 * d * 2
    # the same chunk behind 10 tokens: every query token sees 6; the first
    # looks back to token 5, whose page of 4 begins at 4: 18 - 4 = 14 read
    flops, nbytes = k.cost([(8, 10)], hq, hkv, d, window=6, page_size=4)
    assert flops == 4 * hq * d * 48
    assert nbytes == 2 * hkv * 14 * d * 2 + 2 * hq * 8 * d * 2
    # a window never adds work, and idle slots still cost nothing
    rows = [(64, 4031), (1, 4223), (0, 0), (37, 12)]
    whole = k.cost(rows, hq, hkv, d)
    seen = k.cost(rows, hq, hkv, d, window=4096, page_size=16)
    assert seen[0] < whole[0] and seen[1] < whole[1]
    assert k.cost([(0, 50)], hq, hkv, d, window=8) == (0.0, 0.0)


def test_a_call_is_priced_over_the_layer_kinds_in_their_published_ratio():
    """Three sliding layers to one full one: a call of the trace does not
    say which it is, so it costs the mean."""
    from types import SimpleNamespace
    from chipbench.harness import readers
    k = spec.load_module(ROOT, "kernels", "paged_attention")
    dense = {"num_attention_heads": 32, "num_key_value_heads": 8,
             "head_dim": 128, "sliding_window": None}
    mixed = dict(dense, sliding_window=64, layer_types=[
        "sliding_attention"] * 3 + ["full_attention"])
    assert readers.layer_windows(dense) == [(None, 1.0)]
    assert readers.layer_windows(dict(dense, sliding_window=64)) == [
        (None, 1.0)]                        # no layer_types: no window priced
    assert readers.layer_windows(mixed) == [(64, 0.75), (None, 0.25)]
    assert readers.layer_windows(dict(mixed, layer_types=[
        "sliding_attention"] * 4)) == [(64, 1.0)]
    steps = [[(1, 300), (1, 20)], [(1, 301), (1, 21)]]
    shapes = {"q_rows": 8}                  # T = 1: max(8, 1 x group 4)

    def price(m):
        run = SimpleNamespace(
            model=m, traffic={"engine": {"page_size": 16}},
            tracer=SimpleNamespace(t_started=10.0, seconds=1.0),
            results={"step_log": [
                {"T": 1, "rows": rows, "t": 10.2 + i}   # the 2nd: outside
                for i, rows in enumerate(steps)]})
        return readers.paged_cost_of(run)(k, shapes)

    whole = k.cost(steps[0], 32, 8, 128)
    seen = k.cost(steps[0], 32, 8, 128, window=64, page_size=16)
    assert price(dense) == whole            # to the last digit
    assert price(mixed) == (0.75 * seen[0] + 0.25 * whole[0],
                            0.75 * seen[1] + 0.25 * whole[1])
    assert price(mixed)[1] < whole[1]
    assert readers.paged_cost_of(SimpleNamespace(
        model=dense, traffic={"engine": {"page_size": 16}},
        tracer=SimpleNamespace(t_started=0.0, seconds=1.0),
        results={}))(k, shapes) is None     # no step logged: nothing to price


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
