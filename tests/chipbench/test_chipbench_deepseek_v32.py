"""The ``deepseek_v32`` family (DeepSeek-V3.2) in the benchmark: the
program's engine with the BENCHMARK's seeded weights against the plain
reference at a small size (its own index, its own selection), the int8
control and a reference that attends densely, the new configuration's
files, the two new kernel files' costs on hand-counted shapes and their
matchers, the three new readers on a trace recorded on a v5e, and the
recorded readings under the cell's limits."""

import gzip
import json
import os
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import program_spans as ps  # noqa: E402
from chipbench.harness import spec, trace_reduce as tr, weights  # noqa: E402
from chipbench.references import deepseek_v32 as ref  # noqa: E402

CELL = "deepseekv32-batch-docs32k"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
YARN = {"type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 64}
# an uncut model at test size: 8 experts in 4 groups of which 2 are kept,
# all held; a choice of 32 keys, strict over 32 tokens
FULL = {"hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "q_lora_rank": 32, "kv_lora_rank": 128, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "index_n_heads": 4,
        "index_head_dim": 128, "index_topk": 32, "vocab_size": 320,
        "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": YARN,
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "n_routed_experts": 8, "num_experts_per_tok": 2, "n_group": 4,
        "topk_group": 2, "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "attention_bias": False,
        "tie_word_embeddings": False, "hidden_act": "silu",
        "torch_dtype": "float32"}
CHIPS = 4


def share(index: int) -> dict:
    """``Run.model`` of chip ``index`` of four that share each layer."""
    return dict(FULL, n_routed_experts=FULL["n_routed_experts"] // CHIPS,
                published={"n_routed_experts": FULL["n_routed_experts"]},
                share={"chips": CHIPS, "index": index})


def _reference_logits(m, seed, ids, precision="highest"):
    leaves = ref.leaf_specs(m)
    flat = weights.make_flat(seed, leaves, "float32")
    return ref.sequence_logits(
        lambda l: weights.make_layer(seed, leaves, l, "float32"), flat,
        m["num_hidden_layers"], m, [ids], [list(range(len(ids)))],
        precision=precision)[0]


def _engine_logits(m, seed, ids, prefill, chunk=64):
    """The program's engine core over one sequence, as the engine drives
    it: the first ``prefill`` tokens in chunks (the last one ragged), the
    rest one token a step, every step through the three-plane pool (pages
    of 16, the kernels interpreted), built by the cell's own program
    file."""
    from chipbench.programs import deepseek_v32 as prog
    eng, _ = prog.build_engine(
        m, {"max_batch": 2, "max_seq_len": 256, "page_size": 16,
            "num_pages": 32, "prefill_bucket": chunk, "max_new_tokens": 8},
        seed)
    g = eng.g
    table = jnp.asarray(np.arange(2 * g.pages_per_seq, dtype=np.int32)
                        .reshape(2, g.pages_per_seq))
    cache = tuple(g.cache.arrays)
    out = np.zeros((len(ids), m["vocab_size"]), np.float32)
    core = jax.jit(g._forward_tokens)       # one program a T, as the engine's
    pos = 0
    while pos < len(ids):
        T = chunk if pos < prefill else 1
        q = min(T, prefill - pos) if pos < prefill else 1
        toks = np.zeros((2, T), np.int32)
        toks[0, :q] = ids[pos:pos + q]
        h, cache, _ = core(
            g.params, cache, jnp.asarray(toks),
            jnp.asarray([q, 0], jnp.int32), jnp.asarray([pos, 0], jnp.int32),
            table)
        out[pos:pos + q] = np.asarray(g._head_logits(g.params, h[0, :q]))
        pos += q
    return out


@pytest.fixture
def interpreted():
    from paddle_tpu import flags
    from paddle_tpu.kernels import paged_attention  # noqa: F401 (its flag)
    flags.set_flags({"paged_attention_interpret": True})
    yield
    flags.set_flags({"paged_attention_interpret": False})


@pytest.mark.parametrize("m", [FULL, share(3)], ids=["uncut", "share_3_of_4"])
def test_engine_prefill_then_decode_equals_the_reference(m, interpreted):
    """150 tokens: two whole chunks of 64 and a ragged one, then 12 decode
    steps, through the three-plane pool, the scores kernel, the exact
    selection and the masked walk, against the reference's expanded full
    forward with its own scores and its own sets (a strict choice of 32
    from position 32 on).  Both sides are float32: they differ by the order
    of their sums, bound at 2e-4 of the largest logit."""
    rng = np.random.default_rng(5)
    ids = rng.integers(1, m["vocab_size"], 150).tolist()
    seed = 2**31 + 39
    got = _engine_logits(m, seed, ids, prefill=138)
    want = _reference_logits(m, seed, ids)
    assert got.shape == want.shape == (150, m["vocab_size"])
    assert np.max(np.abs(got - want)) < 2e-4 * max(1.0, np.abs(want).max())
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.98


def test_what_the_reference_states_moves_its_logits():
    """Without the selection (an ``index_topk`` over every context: dense
    attention), with another ``index_topk``, with every group
    kept, or with the leading layer an expert layer, the reference reads
    something else (guards the guard); the first 32 tokens choose
    everything either way."""
    rng = np.random.default_rng(6)
    ids = rng.integers(1, FULL["vocab_size"], 96).tolist()
    want = _reference_logits(FULL, 9, ids)
    narrower = _reference_logits(dict(FULL, index_topk=16), 9, ids)
    all_groups = _reference_logits(dict(FULL, topk_group=4), 9, ids)
    no_dense = _reference_logits(dict(FULL, first_k_dense_replace=0), 9, ids)
    dense = _reference_logits(dict(FULL, index_topk=10**6), 9, ids)
    for other in (dense, narrower, all_groups, no_dense):
        assert np.abs(other[40:] - want[40:]).max() > 1e-3
    assert np.abs(dense[:32] - want[:32]).max() < 1e-5
    assert np.abs(narrower[:16] - want[:16]).max() < 1e-5


def test_the_int8_control_is_told_apart():
    rng = np.random.default_rng(4)
    ids = rng.integers(1, FULL["vocab_size"], 128).tolist()
    m = share(0)
    want = _reference_logits(m, 7, ids)
    low = _reference_logits(m, 7, ids, precision="int8")
    control = want.max(-1) - np.take_along_axis(
        want, low.argmax(-1)[:, None], -1)[:, 0]
    assert np.abs(low - want).max() > 1e-3
    assert control.max() > 1e-3 and (control > 0).mean() > 0.01


def test_a_strict_choice_is_what_bf16_moves():
    """Why a sound bf16 run of this cell disagrees with float32 on several
    times the tokens the 16k cell's does (the limits' ``from``): the
    model's own forward on bf16-rounded weights, computed in bf16 and in
    float32, at test size.  With a strict choice of 32 keys the two
    disagree on several times the tokens they disagree on with everything
    chosen: bf16 moves a set at its edge, and under seeded weights a set
    that differs in a few keys moves the attention's output.  (Counts from
    the CPU, not a device reading.)"""
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v32 import (DeepseekV32Config,
                                                DeepseekV32ForCausalLM,
                                                _forward)
    rng = np.random.default_rng(1)
    ids = jnp.asarray([list(rng.integers(1, 256, 160))])
    share_of = {}
    for top_k in (32, 4096):
        gaps = []
        for seed in (0, 1):
            paddle.seed(seed)
            model = DeepseekV32ForCausalLM(DeepseekV32Config.tiny(
                index_topk=top_k, hidden_size=256, num_attention_heads=8,
                q_lora_rank=64, moe_intermediate_size=64,
                intermediate_size=256))
            spec_, p32 = model.decoder_spec(), model.serving_params()
            p16 = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a, p32)
            back = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), p16)
            run = jax.jit(lambda p, spec_=spec_: _forward(spec_, p, ids))
            want = np.asarray(run(back))[0]
            got = np.asarray(run(p16))[0].argmax(-1)
            gap = want.max(-1) - np.take_along_axis(
                want, got[:, None], -1)[:, 0]
            gaps.append((gap[64:] > 0).mean())
        share_of[top_k] = float(np.mean(gaps))
    assert share_of[32] > 2.5 * share_of[4096] and share_of[32] > 0.2


# ---- the configuration's files ----

def _config():
    return spec.load_json(os.path.join(
        ROOT, "chipbench", "configs", "deepseek-v3.2-ep16.json"))


def _a_run(cell, rehearse=0):
    import argparse
    from chipbench.harness import core
    return core.Run(cell, argparse.Namespace(
        seed=2**31 + 5, seconds=1.0, trace=0, rehearse=rehearse, control=0),
        {"kind": "none"})


def test_spec_validate_is_empty_with_the_new_files(root=ROOT):
    bench = spec.benchmark(root)
    assert spec.validate(bench, root) == []
    assert len(bench["configs"]) >= 7 and len(bench["workloads"]) >= 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = spec.load_cell(CELL, root)
    assert cell.kind == "closed_loop_serve" and cell.chips == 1
    names = {m["name"] for m in cell.per_layer}
    assert names == set(cell.extras["reports"]["per_layer"]) == {
        "step_device_ms.batch", "device_idle_pct.batch", "host_step_ms.batch",
        "host_bound_idle_pct.batch", "token_occupancy_pct.batch",
        "gemm_occupancy_pct.batch", "gmm_held_roofline_pct.batch",
        "expert_rows_occupancy_pct.batch", "dsa_index_roofline_pct.batch",
        "dsa_attn_roofline_pct.batch", "dsa_share_pct.batch"}
    assert {m["name"] for m in cell.end_to_end} == {"serve_total_tok_s",
                                                    "setup_s"}
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"].startswith("dsa_")}
    assert len(new) == 3
    assert {(m["layer"], m["moves"], m["source"], m["unit"])
            for m in new.values()} == {
        ("kernels", "serve_total_tok_s", "device_trace", "%")}
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert new["dsa_index_roofline_pct.batch"]["better"] == "higher"
    assert new["dsa_attn_roofline_pct.batch"]["better"] == "higher"
    assert new["dsa_share_pct.batch"]["better"] == "lower"
    # new entries stand last in their lists
    assert bench["configs"][-1]["name"] == "deepseek-v3.2-ep16"
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(new)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []) and not m["name"].startswith("dsa"):
            assert m["workloads"][-1] == CELL
    for name in new:
        mod = spec.load_module(root, "layer_metrics", name)
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            "kernels", "%", "serve_total_tok_s", "device_trace")
    # what the cell leaves out, each with its reason
    said = cell.extras["reports"]["not_reported"]
    for name in ("mla_attn_roofline_pct.batch", "mla_attn_share_pct.batch",
                 "paged_attn_roofline_pct.batch", "gmm_roofline_pct.batch",
                 "slot_occupancy_pct.batch"):
        assert name in said and name not in names


def test_the_new_entries_keep_the_forms_validate_does_not_hold():
    bench = spec.benchmark(ROOT)
    config = next(c for c in bench["configs"]
                  if c["name"] == "deepseek-v3.2-ep16")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    for text in (config["why"], config["source"], cell["why"]):
        assert 1 <= len(text) <= 200
        assert text.isascii() and text.isprintable()
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "first_k_dense_replace"]
    assert cell["why"] == spec.load_cell(CELL, ROOT).extras["why"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_the_traffic_is_the_issues_letter_for_letter():
    t = spec.load_cell(CELL, ROOT).traffic
    assert (t["kind"], t["schedule_seed"], t["clients"], t["documents"]) == \
        ("closed_loop_serve", 39, 8, 256)
    assert t["prompt_len"] == {"dist": "uniform", "min": 4096, "max": 32768}
    assert t["output_len"] == {"dist": "uniform", "min": 128, "max": 256}
    assert t["engine"] == {"max_batch": 8, "max_seq_len": 33024,
                           "page_size": 16, "num_pages": 16512,
                           "prefill_bucket": 64, "max_new_tokens": 256}
    assert t["trace"] == {"offset_s": 20.0, "seconds": 3.0}
    assert (t["reference_sample"], t["sampling"], t["early_stop"]) == \
        (4, "greedy", False)
    # the 16k cell's page and chunk
    other = spec.load_cell("sarvam105b-batch-docs16k", ROOT).traffic
    assert (t["engine"]["page_size"], t["engine"]["prefill_bucket"]) == \
        (other["engine"]["page_size"], other["engine"]["prefill_bucket"])
    # every document in flight fits at its longest; a cached token holds
    # 512 + 64 + 128 numbers a layer: 7,040 B over five layers
    assert t["engine"]["num_pages"] == 8 * (32768 + 256) // 16 == 8 * 2064
    assert 16512 * 16 * 5 * (512 + 64 + 128) * 2 == 1_859_911_680
    # what the mix's text says of its documents
    from chipbench.harness import schedule
    docs = schedule.requests(t)
    assert len(docs) == 256
    tokens = sum(d.prompt_len for d in docs)
    beyond = sum(max(d.prompt_len - 2048, 0) for d in docs) / tokens
    assert 0.87 < beyond < 0.91
    mean_ctx = sum(d.prompt_len ** 2 for d in docs) / 2 / tokens
    assert 10_500 < mean_ctx < 11_700
    # the first eight are long: two finish inside a window (the limits)
    assert sorted(d.prompt_len for d in docs[:8])[:3] == [7184, 8136, 22691]


def test_the_model_is_the_catalogs_config_verbatim():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V3.2")
    config = _config()
    assert config["source"] == row["source_url"]
    assert {k: v for k, v in config["model"].items()
            if k != "torch_dtype"} == row["config"]
    assert "torch_dtype" in config["assumed"]
    # what the driver's check reads: the file's own top level, key for key
    assert {k for k, v in row["config"].items()
            if k not in config or config[k] != v} == set(config["reduced"])


def test_the_files_top_level_is_the_source_as_this_chip_runs_it():
    config = _config()
    source = {k: v for k, v in config["model"].items() if k != "torch_dtype"}
    assert set(source) <= set(config)
    differs = {k for k in source if config[k] != source[k]
               or type(config[k]) is not type(source[k])}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "first_k_dense_replace"}
    assert not [k for k in differs if spec.is_width(k)]
    entry = next(c for c in spec.benchmark(ROOT)["configs"]
                 if c["name"] == config["name"])
    assert differs == set(entry["reduced"])
    m = _a_run(spec.load_cell(CELL, ROOT)).model
    assert {k: m[k] for k in source} == {k: config[k] for k in source}
    assert m["published"] == {k: source[k] for k in differs}
    assert m["share"] == {"chips": 16, "index": 0, "over": {"vocab_size": 8}}
    assert config["depth"] == {"published": 61, "serve": 5}
    assert config["layer_pattern"] == {
        "period": 1, "leading_dense": 1,
        "leading_key": "first_k_dense_replace"}
    assert config["share"]["serve"] == {"n_routed_experts": 16,
                                        "vocab_size": 16160}
    assert set(config["not_run"]) == {"num_nextn_predict_layers"}
    assert set(config["assumed"]) >= {
        "index_dtype", "index_hadamard", "index_weight_constants",
        "index_rotary", "index_k_norm", "latent_norms", "expert_bias_values",
        "torch_dtype", "moe_dispatch", "moe_block_m"}
    for text in ("16 chips", "56 layers", "240 absent experts",
                 "4,635,518,208"):
        assert text in config["deployment"], text
    r = config["rehearsal_model"]
    assert (r["hidden_size"], r["num_attention_heads"], r["q_lora_rank"],
            r["kv_lora_rank"], r["index_n_heads"], r["index_head_dim"],
            r["index_topk"], r["n_routed_experts"], r["n_group"],
            r["topk_group"], r["num_experts_per_tok"]) == \
        (64, 4, 32, 128, 4, 128, 32, 8, 4, 2, 2)


def test_the_share_and_the_program_read_the_same_sizes():
    """``Run.model`` of the cell -> the program's own configuration: the
    router at its published width, 16 experts held from number 0 on, the
    vocabulary's part, one dense layer and four expert layers, every
    published width, 4,635,518,208 parameters held once."""
    from chipbench.programs import deepseek_v32 as prog
    cell = spec.load_cell(CELL, ROOT)
    m = _a_run(cell).model
    cfg = prog.model_config(m, 33024)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_offset) == \
        (256, 16, 0)
    assert (cfg.vocab_size, cfg.num_hidden_layers,
            cfg.first_k_dense_replace) == (16160, 5, 1)
    for key, want in (
            ("hidden_size", 7168), ("num_attention_heads", 128),
            ("q_lora_rank", 1536), ("kv_lora_rank", 512),
            ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
            ("v_head_dim", 128), ("index_n_heads", 64),
            ("index_head_dim", 128), ("index_topk", 2048),
            ("intermediate_size", 18432), ("moe_intermediate_size", 2048),
            ("num_experts_per_tok", 8), ("n_shared_experts", 1),
            ("n_group", 8), ("topk_group", 4),
            ("routed_scaling_factor", 2.5)):
        assert getattr(cfg, key) == cell.config["model"][key] == want, key
    assert cfg.rope_scaling["factor"] == 40
    n = ref.count_params(m, 5)
    attn = 7168 * 1536 + 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 \
        + 2 * 512 * 128 * 128 + 128 * 128 * 7168
    index = 1536 * 64 * 128 + 7168 * 128 + 2 * 128 + 7168 * 64
    assert (attn, index) == (187_107_328, 13_959_424)
    dense = attn + index + 2 * 7168 + 3 * 7168 * 18432
    expert = attn + index + 2 * 7168 + 7168 * 256 + 256 \
        + 17 * 3 * 7168 * 2048
    assert (n["dense_layer"], n["per_layer"]) == (dense, expert) == \
        (597_442_816, 951_599_616)
    assert n["total"] == dense + 4 * expert + 2 * 16160 * 7168 + 7168 \
        == 4_635_518_208                                  # 9.27 GB in bf16
    # a token touches 8 x 16 / 256 = half a held expert a layer on average
    assert n["active"] == n["total"] - 4 * 3 * 7168 * 2048 * 16 \
        + 4 * (3 * 7168 * 2048 * 16 * 8 // 256)
    assert ref.held(m) == (256, 16, 0) and ref.vocab_part(m) == 0
    spec_ = prog.DeepseekV32ForCausalLM.decoder_spec(
        SimpleNamespace(config=cfg))
    assert abs(spec_.softmax_scale - 0.1352) < 5e-5
    assert spec_.moe.partial and spec_.index.top_k == 2048


def test_the_cells_registry_series_reach_the_drivers_snapshot():
    from chipbench.harness import registry
    always = ("serving.batch_occupancy",)
    assert registry.series_of(spec.load_cell(CELL, ROOT), always) == \
        always + ("serving.moe_held_rows", "serving.moe_rows_laid_out",
                  "serving.index_pairs", "serving.selected_keys")


def test_the_parent_fails_at_the_top_of_the_program_file():
    """The cell's program file imports the model before anything else: a
    program that lacks it fails at once (PERF.md section 6, PR 39: rc 1 in
    14.6 s on the chip)."""
    path = os.path.join(ROOT, "chipbench", "programs", "deepseek_v32.py")
    with open(path) as f:
        lines = [ln for ln in f.read().split('"""', 2)[2].splitlines()
                 if ln.startswith(("import ", "from "))]
    first = next(i for i, ln in enumerate(lines) if "paddle_tpu" in ln)
    assert not any("chipbench" in ln for ln in lines[:first + 2])
    assert any("paddle_tpu.models.deepseek_v32" in ln for ln in lines)


# ---- the two new kernel files ----

def _kernel(name):
    return spec.load_module(ROOT, "kernels", name)


def test_cost_on_hand_counted_shapes():
    """The index: one decode row after 1,000 cached tokens and a chunk of
    64 after 100, 64 index heads of 128: ``pairs x 64 x 128 x 2``
    operations; a slot's index keys read ONCE at 256 B a token, the
    queries (64 x 128 in bf16) and weights (64 float32) of a query token
    in, 2,048 int32 out.  The sparse call: the SELECTED keys alone."""
    k = _kernel("latent_index")
    flops, nbytes = k.cost([(1, 1000)], 64, 128, 2048)
    assert flops == 1001 * 64 * 128 * 2
    assert nbytes == 1001 * 256 + 64 * (256 + 4) + 2048 * 4
    pairs = 64 * 100 + 64 * 65 // 2
    flops, nbytes = k.cost([(64, 100), (0, 7), (1, 1000)], 64, 128, 2048)
    assert flops == (pairs + 1001) * 64 * 128 * 2
    assert nbytes == (164 + 1001) * 256 + 65 * (64 * 260 + 2048 * 4)
    s = _kernel("paged_attention_latent_sparse")
    # under top_k everything is chosen: the dense call's operations
    dense = _kernel("paged_attention_latent")
    rows = [(64, 100), (1, 1000)]
    assert s.selected(rows, 2048) == pairs + 1001
    assert s.cost(rows, 128, 512, 64, 2048)[0] == \
        dense.cost(rows, 128, 512, 64)[0]
    # over it: 2,048 a query token, whatever the context
    assert s.selected([(1, 30000)], 2048) == 2048
    assert s.selected([(64, 2000)], 2048) == \
        sum(min(2000 + j + 1, 2048) for j in range(64))
    flops, nbytes = s.cost([(64, 30000), (0, 5), (1, 9000)], 128, 512, 64,
                           2048)
    chosen = 65 * 2048
    assert flops == chosen * 128 * (576 + 512) * 2
    assert nbytes == chosen * 1152 + 65 * 128 * 1088 * 2
    # a fifth of the dense call's operations at this cell's mean context
    d = dense.cost([(64, 11100)], 128, 512, 64)[0]
    assert 0.17 < s.cost([(64, 11100)], 128, 512, 64, 2048)[0] / d < 0.20


def _call(name, out, operands):
    tail = '), custom_call_target="tpu_custom_call", operand_layout=...'
    return tr.parse_op(f"%{name} = {out}{{2,1,0:T(8,128)}} custom-call("
                       + operands + tail, 0.0, 1.0)


def test_match_takes_the_new_calls_and_passes_the_16k_cells_by():
    index, sparse = _kernel("latent_index"), \
        _kernel("paged_attention_latent_sparse")
    dense = _kernel("paged_attention_latent")
    scalars = "s32[8,2064]{1,0} %a, s32[8]{0} %b, s32[8]{0} %c, s32[1]{0} %d"
    scores = _call(
        "latent_index_scores.3", "f32[8,64,33792]",
        scalars + ", bf16[8,4096,128]{2,1,0} %q, f32[8,4096,1]{2,1,0} %w, "
        "bf16[5,16512,16,128]{3,2,1,0} %plane")
    assert index.match(scores) == {"kind": "scores", "slots": 8,
                                   "tokens": 64, "heads": 64, "dim": 128}
    select = _call(
        "latent_index_select.7", "bf16[8,64,33792]",
        "s32[8]{0} %ql, s32[8]{0} %cl, f32[8,64,33792]{2,1,0} %s, "
        "s32[8,64,1]{2,1,0} %k")
    assert index.match(select) == {"kind": "select", "slots": 8,
                                   "tokens": 64, "positions": 33792}
    latent = (scalars + ", bf16[8,8192,512]{2,1,0} %qc, "
              "bf16[8,8192,128]{2,1,0} %qlo, bf16[8,8192,128]{2,1,0} %qhi, "
              "bf16[8,64,512]{2,1,0} %cn, bf16[8,64,128]{2,1,0} %rn, "
              "bf16[8,64,64]{2,1,0} %sn, bf16[5,16512,16,512]{3,2,1,0} %c, "
              "bf16[5,16512,8,128]{3,2,1,0} %r, "
              "bf16[8,33,64,1024]{3,2,1,0} %sel")
    walk = _call("ragged_paged_attention_latent_sparse.9",
                 "bf16[8,8192,512]", latent)
    assert sparse.match(walk) == {"slots": 8, "q_rows": 8192, "rank": 512,
                                  "rope": 64, "dtype": "bf16"}
    # the names decide: no yardstick takes another's call
    assert dense.match(walk) is None and index.match(walk) is None
    the_16k = _call("ragged_paged_attention_latent.9", "bf16[8,8192,512]",
                    latent)
    assert dense.match(the_16k) is not None
    assert sparse.match(the_16k) is None and index.match(the_16k) is None
    assert sparse.match(scores) is None and dense.match(select) is None
    # and so do the shapes
    assert index.match(_call("latent_index_scores.3", "bf16[8,64,33792]",
                             scalars)) is None
    assert index.match(_call("latent_index_select.7", "bf16[8,64,33792]",
                             "s32[8]{0} %ql")) is None
    paged = _kernel("paged_attention")
    assert paged.match(walk) is None and paged.match(scores) is None


# ---- the three new readers on a trace recorded on the chip ----

def _reader(name):
    return spec.load_module(ROOT, "layer_metrics", name)


def _run_of(xplane, want, model, config=None):
    run = SimpleNamespace()
    run.program_spans, run.launch_waits = ps.read_host(xplane)
    run.trace = tr.load(xplane)
    run.trace_window = tr.window(run.trace, want["window_span"])
    run.results = {"step_log": want["step_log"],
                   "registry": want.get("registry", {})}
    run.tracer = SimpleNamespace(t_started=0.0,
                                 seconds=want["trace_seconds"])
    run.model = model
    run.traffic = {"engine": {"page_size": want["page_size"]}}
    run.cell = SimpleNamespace(root=ROOT, config=config or {})
    run.peaks = lambda: PEAKS
    return run


def _unpacked(tmp_path_factory, name):
    with open(os.path.join(DATA, name + ".json")) as f:
        want = json.load(f)
    xplane = str(tmp_path_factory.mktemp(name) / (name + ".xplane.pb"))
    with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz"), "rb") as src, \
            open(xplane, "wb") as dst:
        dst.write(src.read())
    return xplane, want


@pytest.fixture(scope="module")
def dsa(tmp_path_factory):
    xplane, want = _unpacked(tmp_path_factory, "recorded_dsa_trace")
    return _run_of(xplane, want, want["model"], _config()), want


def test_the_three_calls_of_the_recorded_window(dsa):
    """Five of each a step (the leading layer's and the four of the scan),
    all of the T = 64 program in this window: 64 query tokens of 64 index
    heads scored, chosen in tiles of 16, 8,192 query rows walked; the 16k
    cell's matcher and the per-head kernel's see none of them."""
    run, want = dsa
    lo, hi = run.trace_window
    assert hi - lo == want["window_ns"]
    index = tr.kernel_calls(run.trace, lo, hi, _kernel("latent_index").match)
    walk = tr.kernel_calls(run.trace, lo, hi,
                           _kernel("paged_attention_latent_sparse").match)
    by_kind = {}
    for op, s in index:
        by_kind.setdefault(s["kind"], []).append(op.dur)
        assert (s["slots"], s["tokens"]) == (8, 64)
    assert len(by_kind["scores"]) == want["scores_calls"] > 0
    assert len(by_kind["select"]) == want["select_calls"] == len(walk) \
        == want["sparse_calls"]
    # a window opens and closes inside a step: whole steps of five, and a
    # few calls of the two cut ones
    assert len(walk) >= 5 * (len(run.results["step_log"]) - 2)
    assert sum(by_kind["scores"]) == want["scores_calls_ns"]
    assert sum(by_kind["select"]) == want["select_calls_ns"]
    assert sum(op.dur for op, _ in walk) == want["sparse_calls_ns"]
    assert {s["q_rows"] for _, s in walk} == {8192}
    for other in ("paged_attention_latent", "paged_attention"):
        assert tr.kernel_calls(run.trace, lo, hi,
                               _kernel(other).match) == []


def test_the_readers_read_what_was_worked_out_apart(dsa):
    run, want = dsa
    said = want["readers_said_on_the_chip"]
    for name in ("dsa_index_roofline_pct", "dsa_attn_roofline_pct",
                 "dsa_share_pct"):
        got = _reader(name + ".batch").read(run)
        assert got == pytest.approx(want[name], rel=1e-9), name
        assert got == pytest.approx(said[name + ".batch"]["value"],
                                    rel=1e-6), name
        assert 0 < got < 105
    # the share is the three calls' time over the busy time (the last
    # call of the window is cut at its end)
    inside = (want["scores_calls_ns"] + want["select_calls_ns"]
              + want["sparse_calls_ns"]) / want["busy_ns"]
    assert 100 * inside == pytest.approx(want["dsa_share_pct"], rel=1e-2)
    # the held GEMMs are priced over the four layers that have experts
    held = _reader("gmm_held_roofline_pct.batch").read(run)
    assert held == pytest.approx(
        said["gmm_held_roofline_pct.batch"]["value"], rel=1e-6)
    rows = _reader("expert_rows_occupancy_pct.batch").read(run)
    assert rows == pytest.approx(
        said["expert_rows_occupancy_pct.batch"]["value"], rel=1e-6)


def test_a_program_without_an_index_reads_nothing(tmp_path_factory):
    """On the trace PR 31 recorded from the 16k cell (a latent pool, the
    dense call, no index) the three readers return None, not an error: the
    metric is left out of the line.  That is what the parent's traced runs
    give, with this PR's benchmark files laid over it."""
    xplane, want = _unpacked(tmp_path_factory, "recorded_latent_trace")
    run = _run_of(xplane, want, want["model"])
    for name in ("dsa_index_roofline_pct.batch",
                 "dsa_attn_roofline_pct.batch", "dsa_share_pct.batch"):
        assert _reader(name).read(run) is None
    # even where a model states an index, no call means no reading
    run.model = dict(want["model"], index_topk=2048, index_n_heads=64,
                     index_head_dim=128)
    for name in ("dsa_index_roofline_pct.batch",
                 "dsa_attn_roofline_pct.batch"):
        assert _reader(name).read(run) is None


# ---- the recorded readings under the cell's limits ----

def _recorded_readings():
    path = os.path.join(DATA, "recorded_docs32k_readings.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f]


def test_the_limits_stand_between_the_recorded_readings():
    """Every run of the cell by PR 39 on the chip (each a line of the
    recorded file), through the harness's ``Checks`` and the limits of the
    cell's file as it stands: every program reading passes, the int8
    control is refused in every run by ``served_flip_share``, the number
    the verdict rests on (the share of served tokens that lie more than
    ``gap_over`` below the reference's best: its two readings stand three
    times apart, which neither the mean gap nor the disagreeing share
    does), and so is the program against a reference that attends densely
    (the comparison sees the selection).  A limit moved past a reading
    fails here."""
    from chipbench import control_verdict
    from chipbench.harness.serving import readings
    cell = spec.load_cell(CELL, ROOT)
    limits = cell.extras["limits"]
    assert set(limits) == {
        "served_tokens_compared", "served_flip_share",
        "served_logit_gap_mean", "served_disagree_share"}
    flip = limits["served_flip_share"]
    # every position counts (no margin of any run reaches 2): the reading
    # is a share of the served tokens
    assert (flip["gap_over"], flip["margin_under"], flip["plus"]) == (
        1.0, 100, 0)
    got = readings(np.asarray([0.0, 1.01, 0.99, 2.0]),
                   np.asarray([0.1, 1.9, 0.0, 0.3]), flip)
    assert (got["flips"], got["near"], got["flip_share"]) == (2, 4, 0.5)
    runs = _recorded_readings()
    sound = [r for r in runs if not r.get("dense_reference")]
    control = [r for r in sound if "control_int8" in r]
    dense = [r for r in runs if r.get("dense_reference")]
    assert len({r["seed"] for r in sound}) >= 20 and len(control) >= 10
    assert len(dense) >= 1
    for r in sound:
        assert control_verdict.verdict(cell, r, r["tokens"])["correct"], r
        assert r["failed"] == 0 and r["near"] == r["tokens"]
        if "control_int8" in r:
            v = control_verdict.verdict(cell, r["control_int8"], r["tokens"])
            assert not v["correct"]
            assert "served_flip_share" in v["not_ok"], r["seed"]
    for r in dense:
        v = control_verdict.verdict(cell, r, r["tokens"])
        assert not v["correct"] and r["gap_mean"] > 9 * cell.limit(
            "served_logit_gap_mean") and r["flip_share"] > 0.9
    for name, limit in limits.items():
        assert limit.get("from"), name
    # the verdict's number: three times between the sound runs' largest and
    # the control's smallest, the limit with room on both sides, and what
    # its text quotes, digit for digit
    high = max(r["flip_share"] for r in sound)
    low = min(r["control_int8"]["flip_share"] for r in control)
    assert low >= 3 * high
    assert 1.5 * high < cell.limit("served_flip_share") < low / 1.5
    text = flip["from"]
    for number in (f"{min(r['flip_share'] for r in sound):.4f}",
                   f"{high:.4f}", f"{low:.4f}",
                   f"{max(r['control_int8']['flip_share'] for r in control):.4f}",
                   f"{low / high:.2f} times"):
        assert number in text, number
    # the two guards beside it: between their readings, under three times
    gaps = [r["gap_mean"] for r in sound]
    low = [r["control_int8"]["gap_mean"] for r in control]
    text = limits["served_logit_gap_mean"]["from"]
    for number in (f"{min(gaps):.4f}", f"{max(gaps):.4f}",
                   f"{min(low):.4f}", f"{max(low):.4f}",
                   f"{min(low) / max(gaps):.2f} times"):
        assert number in text, number
    assert max(gaps) < cell.limit("served_logit_gap_mean") < min(low)
    away = [1 - r["greedy_agree_share"] for r in sound]
    low = [1 - r["control_int8"]["greedy_agree_share"] for r in control]
    assert max(away) < cell.limit("served_disagree_share") < min(low)
    # the memory a run holds: over a quarter of the chip
    assert all(r["memory_peak_bytes"] > 0.25 * 16e9 for r in runs)
