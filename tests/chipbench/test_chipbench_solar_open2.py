"""The ``solar_open2`` family (Solar-Open2-250B) in the benchmark: the
program's engine with the BENCHMARK's seeded weights against the plain
reference at a small size, the int8 control, the new configuration's files
(read from the cell's OWN files, pinned to no place in a list), the
delta-rule kernel's cost on hand-counted rows, the two new readers on a
trace recorded on a v5e, the recorded readings under the cell's limits, and
a rehearsal of the cell."""

import gzip
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import program_spans as ps  # noqa: E402
from chipbench.harness import spec, trace_reduce as tr, weights  # noqa: E402
from chipbench.references import solar_open2 as ref  # noqa: E402

CELL = "solaropen2-batch-generate-long"
CONFIG = "solar-open2-250b-ep8"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the model at test size as ``Run.model`` hands a share: two periods, 2 of
# the router's 8 experts held (those of chip 1 of 4), half the vocabulary
SMALL = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                        "num_heads": 4, "num_kv_heads": None},
    vocab_size=160, rms_norm_eps=1e-5, rope_theta=10000, use_rope=False,
    gqa_interval=3, gqa_layers=[0, 4], use_gqa_gate=True,
    kda_use_full_proj=False, kda_allow_neg_eigval=True,
    first_k_dense_replace=0, n_routed_experts=2, n_shared_experts=1,
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=1,
    tie_word_embeddings=False, num_hidden_layers=8, torch_dtype="float32",
    published={"n_routed_experts": 8, "vocab_size": 320,
               "num_hidden_layers": 48},
    share={"chips": 4, "index": 1})


def _reference_logits(m, seed, ids, precision="highest"):
    leaves = ref.leaf_specs(m)
    flat = weights.make_flat(seed, leaves, "float32")
    return ref.sequence_logits(
        lambda l: weights.make_layer(seed, leaves, l, "float32"), flat,
        m["num_hidden_layers"], m, [ids], [list(range(len(ids)))],
        precision=precision)[0]


def _engine_logits(m, seed, ids, prefill, chunk=16):
    """The program's engine core over one sequence, as the engine drives
    it: the first ``prefill`` tokens in chunks (the last one ragged), the
    rest one token a step, every step through pages and recurrent state
    (the kernels interpreted), built by the cell's own program file."""
    from chipbench.programs import solar_open2 as prog
    eng, _ = prog.build_engine(
        m, {"max_batch": 2, "max_seq_len": 256, "page_size": 16,
            "num_pages": 32, "prefill_bucket": chunk, "max_new_tokens": 8},
        seed)
    g = eng.g
    table = jnp.asarray(np.arange(2 * g.pages_per_seq, dtype=np.int32)
                        .reshape(2, g.pages_per_seq))
    cache = tuple(g.cache.arrays)
    out = np.zeros((len(ids), m["vocab_size"]), np.float32)
    pos = 0
    while pos < len(ids):
        T = chunk if pos < prefill else 1
        q = min(T, prefill - pos) if pos < prefill else 1
        toks = np.zeros((2, T), np.int32)
        toks[0, :q] = ids[pos:pos + q]
        h, cache, _ = g._forward_tokens(
            g.params, cache, jnp.asarray(toks),
            jnp.asarray([q, 0], jnp.int32), jnp.asarray([pos, 0], jnp.int32),
            table)
        out[pos:pos + q] = np.asarray(g._head_logits(g.params, h[0, :q]))
        pos += q
    return out, eng


@pytest.fixture
def interpreted():
    from paddle_tpu import flags
    from paddle_tpu.kernels import paged_attention  # noqa: F401 (its flag)
    flags.set_flags({"paged_attention_interpret": True})
    yield
    flags.set_flags({"paged_attention_interpret": False})


@pytest.mark.timeout(300)
def test_engine_prefill_then_decode_equals_the_reference(interpreted):
    """90 tokens: four whole chunks of 16 and a ragged one, then 12 decode
    steps, through pages (two layers) and the recurrent state (six; the
    interpreted kernel token by token), against the reference's bare
    recurrence over the whole sequence, for chip 1 of 4's share of the
    experts.  Tolerance: both sides are float32 and differ by the order of
    their sums through sixteen sublayers; the logits' spread is 1, so 2e-4
    is a few roundings of them carried along (tests/test_solar_open2.py
    reads 3.8e-5 at this size, a state held in bf16 0.4)."""
    rng = np.random.default_rng(5)
    ids = rng.integers(1, SMALL["vocab_size"], 90).tolist()
    seed = 2**31 + 46
    got, eng = _engine_logits(SMALL, seed, ids, prefill=78)
    want = _reference_logits(SMALL, seed, ids)
    assert got.shape == want.shape == (90, SMALL["vocab_size"])
    assert 0.5 < want.std() < 2.0
    assert np.max(np.abs(got - want)) < 2e-4
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.98
    # the share as the program holds it: experts [2, 4) of the router's 8
    moe = eng.g.spec.moe
    assert (moe.num_experts, moe.held, moe.offset) == (8, 2, 2)
    assert eng.g.cache.kv.shape[0] == 2                  # two page layers
    assert eng.g.cache.recurrent.ssm.shape[:2] == (6, 2)


def test_what_the_reference_states_moves_its_logits():
    """Without the output gate of the softmax layer, without the doubled
    beta, or with another share of the experts, the reference reads
    something else (guards the guard)."""
    rng = np.random.default_rng(6)
    ids = rng.integers(1, SMALL["vocab_size"], 64).tolist()
    want = _reference_logits(SMALL, 9, ids)
    for other in (dict(SMALL, use_gqa_gate=False),
                  dict(SMALL, kda_allow_neg_eigval=False),
                  dict(SMALL, share={"chips": 4, "index": 2}),
                  dict(SMALL, gqa_layers=[0, 1, 4])):
        assert np.abs(_reference_logits(other, 9, ids) - want).max() > 1e-3


def test_the_int8_control_is_told_apart():
    rng = np.random.default_rng(4)
    ids = rng.integers(1, SMALL["vocab_size"], 128).tolist()
    want = _reference_logits(SMALL, 7, ids)
    low = _reference_logits(SMALL, 7, ids, precision="int8")
    control = want.max(-1) - np.take_along_axis(
        want, low.argmax(-1)[:, None], -1)[:, 0]
    assert np.abs(low - want).max() > 1e-2
    assert control.max() > 1e-3 and (control > 0).sum() >= 1


def test_the_seeded_decays_spread_as_the_file_says():
    """``dt_bias`` (std 3, a channel) and ``A_log`` (std 1, a head): at the
    published 64 x 128 channels a layer some remember a thousand tokens (a
    log decay under 2^-8 a token at a gate of zero, where a bf16 state
    would stall) and some forget within one."""
    m = _a_run(spec.load_cell(CELL, ROOT)).model
    leaves = [lf for lf in ref.leaf_specs(m)
              if lf.name in ("linear_attn.dt_bias", "linear_attn.A_log")]
    w = weights.make_layer(4600000011, leaves, 1, "bfloat16")
    rate = np.log1p(np.exp(np.asarray(
        w["linear_attn.dt_bias"], np.float32))).reshape(64, 128) \
        * np.exp(np.asarray(w["linear_attn.A_log"], np.float32))[:, None]
    assert rate.shape == (64, 128)
    assert (rate < 2.0 ** -8).mean() > 0.01 and (rate > 1.0).mean() > 0.2
    assert 0.05 < np.median(rate) < 5.0


# ---- the configuration's files ----

def _config():
    return spec.load_json(os.path.join(
        ROOT, "chipbench", "configs", CONFIG + ".json"))


def _a_run(cell, rehearse=0):
    import argparse
    from chipbench.harness import core
    return core.Run(cell, argparse.Namespace(
        seed=2**31 + 5, seconds=1.0, trace=0, rehearse=rehearse, control=0),
        {"kind": "none"})


LISTS = ("step_device_ms.batch", "device_idle_pct.batch",
         "host_step_ms.batch", "host_bound_idle_pct.batch",
         "token_occupancy_pct.batch", "gemm_occupancy_pct.batch",
         "paged_attn_roofline_pct.batch", "gmm_held_roofline_pct.batch",
         "expert_rows_occupancy_pct.batch", "kda_roofline_pct.batch",
         "kda_share_pct.batch")


def test_spec_validate_is_empty_with_the_new_files(root=ROOT):
    """The cell from its own files; its entries are there, wherever later
    ones stand (no assertion on a place in a list: PERF.md section 7)."""
    bench = spec.benchmark(root)
    assert spec.validate(bench, root) == []
    assert len(bench["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = spec.load_cell(CELL, root)
    assert cell.kind == "closed_loop_serve" and cell.chips == 1
    assert (cell.config_name, cell.traffic_name) == (
        CONFIG, "batch-generate-long")
    names = {m["name"] for m in cell.per_layer}
    assert names == set(cell.extras["reports"]["per_layer"]) == set(LISTS)
    assert {m["name"] for m in cell.end_to_end} == \
        set(cell.extras["reports"]["end_to_end"]) == {"serve_total_tok_s",
                                                      "setup_s"}
    assert cell.extras["reports"]["registry_series"] == [
        "serving.moe_held_rows", "serving.moe_rows_laid_out"]
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"].startswith("kda_")}
    assert sorted(new) == ["kda_roofline_pct.batch", "kda_share_pct.batch"]
    assert {(m["layer"], m["moves"], m["source"], m["unit"])
            for m in new.values()} == {
        ("kernels", "serve_total_tok_s", "device_trace", "%")}
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert new["kda_roofline_pct.batch"]["better"] == "higher"
    assert new["kda_share_pct.batch"]["better"] == "lower"
    assert CONFIG in [c["name"] for c in bench["configs"]]
    # no other cell's list lost a name to this one
    for m in bench["per_layer"]:
        if m["name"] == "ssd_roofline_pct.batch":
            assert m["workloads"] == ["falconh1-batch-generate"]


def test_the_new_entries_keep_the_forms_validate_does_not_hold():
    bench = spec.benchmark(ROOT)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    for text in (config["why"], config["source"], cell["why"]):
        assert 1 <= len(text) <= 200
        assert text.isascii() and text.isprintable()
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert sorted(config["reduced"]) == ["n_routed_experts",
                                         "num_hidden_layers", "vocab_size"]
    assert not [k for k in config["reduced"] if spec.is_width(k)]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for name in ("kda_roofline_pct.batch", "kda_share_pct.batch", CELL,
                 CONFIG, "batch-generate-long"):
        assert spec.NAME_RE.match(name)
    assert cell["why"] == spec.load_cell(CELL, ROOT).extras["why"]


def test_the_traffic_is_the_issues_letter_for_letter():
    t = spec.load_cell(CELL, ROOT).traffic
    assert (t["kind"], t["schedule_seed"], t["clients"], t["documents"]) == \
        ("closed_loop_serve", 46, 192, 2048)
    assert t["prompt_len"] == {"dist": "uniform", "min": 128, "max": 1024}
    assert t["output_len"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert t["engine"] == {"max_batch": 192, "max_seq_len": 2560,
                           "page_size": 16, "num_pages": 30720,
                           "prefill_bucket": 64, "max_new_tokens": 1536}
    assert t["trace"] == {"offset_s": 20.0, "seconds": 3.0}
    assert (t["reference_sample"], t["sampling"], t["early_stop"]) == \
        (4, "greedy", False)
    # every request in flight fits at its longest; 4,096 B a cached token
    # on the ONE layer that keeps pages: 2.01 GB; the state 2.50 GB
    assert t["engine"]["num_pages"] == 192 * 2560 // 16 == 192 * 160
    assert 30720 * 16 * 1 * 2 * 8 * 128 * 2 == 2013265920
    assert 192 * 13025280 == 2500853760
    assert {"clients", "engine", "prompt_len", "output_len", "trace"} <= \
        set(t["rehearsal"])


def test_the_model_is_the_catalogs_config_verbatim():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
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
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    entry = next(c for c in spec.benchmark(ROOT)["configs"]
                 if c["name"] == config["name"])
    assert differs == set(entry["reduced"])
    m = _a_run(spec.load_cell(CELL, ROOT)).model
    assert {k: m[k] for k in source} == {k: config[k] for k in source}
    assert m["published"] == {"num_hidden_layers": 48,
                              "n_routed_experts": 320, "vocab_size": 196608}
    assert m["share"] == {"chips": 8, "index": 0}
    assert config["depth"] == {"published": 48, "serve": 4}
    assert config["layer_pattern"] == {"period": 4, "leading_dense": 0}
    assert config["share"]["serve"] == {"n_routed_experts": 40,
                                        "vocab_size": 24576}
    assert set(config["assumed"]) >= {
        "kda_gate_rank", "kda_conv", "kda_qk_norm", "kda_decay", "kda_beta",
        "kda_output_gate", "state_dtype", "gqa_gate", "no_positions",
        "router", "hidden_act", "shared_expert_width", "torch_dtype"}
    for text in ("3,308,353,344", "6.617 GB", "13,025,280", "11 further",
                 "250.29 B", "12 pipeline stages"):
        assert text in config["deployment"], text
    for key, want in (
            ("hidden_size", 4096), ("num_attention_heads", 64),
            ("num_key_value_heads", 8), ("head_dim", 128),
            ("moe_intermediate_size", 1280), ("intermediate_size", 10240),
            ("num_experts_per_tok", 8), ("n_shared_experts", 1),
            ("gqa_interval", 3), ("use_rope", False),
            ("kda_use_full_proj", False), ("kda_allow_neg_eigval", True),
            ("max_position_embeddings", 1048576)):
        assert config[key] == want, key
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert config["gqa_layers"] == list(range(0, 48, 4))
    assert config["program_flags"] == {"autotune_enable": False}


def test_the_program_reads_the_same_sizes_and_holds_them_once():
    """``Run.model`` of the cell -> the program's own configuration: every
    published width, one period, 40 experts from 0, 3,308,353,344
    parameters held once."""
    from chipbench.programs import solar_open2 as prog
    cell = spec.load_cell(CELL, ROOT)
    m = _a_run(cell).model
    cfg = prog.model_config(m, 2560)
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.dtype) == \
        (4, 24576, "bfloat16")
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_offset) == \
        (320, 40, 0)
    assert cfg.gqa_layers == (0,) and cfg.max_position_embeddings == 2560
    assert ref.held(m) == (320, 40, 0) and ref.vocab_part(m) == 0
    assert ref.held(dict(m, share={"chips": 8, "index": 5})) == (320, 40, 200)
    n = ref.count_params(m, 4)
    mixer = 4096 * 24576 + 4 * 24576 + 2 * (4096 * 128 + 128 * 8192) \
        + 64 + 8192 + 4096 * 64 + 128 + 8192 * 4096
    attention = 3 * 4096 * 8192 + 2 * 4096 * 1024
    common = 2 * 4096 + 4096 * 320 + 320 + 3 * 4096 * 1280
    assert (mixer, attention, common) == (137732288, 109051904, 17047872)
    assert n["linear_layer"] == mixer + common + 40 * 15728640
    assert n["softmax_layer"] == attention + common + 40 * 15728640
    assert n["total"] == 3308353344                       # 6.617 GB in bf16
    assert n["active"] == n["total"] - 4 * (629145600 - 629145600 * 8 // 320)
    # the rehearsal keeps the shape of the thing at tiny widths
    tiny = _a_run(cell, rehearse=1).model
    assert tiny["num_hidden_layers"] == 4 and tiny["n_routed_experts"] == 8
    assert tiny["published"]["n_routed_experts"] == 320
    assert tiny["linear_attn_config"]["num_heads"] == 4


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "chipbench", "references",
                           "solar_open2.py")) as f:
        text = f.read()
    code = text.split('"""', 2)[2]
    assert "paddle_tpu" not in code
    assert "lax.scan" in code and "default" not in code


# ---- the delta-rule kernel's file ----

def _kernel():
    return spec.load_module(ROOT, "kernels", "kda_update")


def test_cost_on_hand_counted_rows():
    """One decode slot and one chunk of 64, 64 heads of 128 x 128: a
    working slot reads and writes 2 x 4,194,304 B of state whatever its
    tokens, a token brings q, k, v and takes o (4 x 16,384 B) and its
    float32 log decay (32,768 B); the recurrence's 7 x 128 x 128
    operations a token a head.  A slot without work costs nothing."""
    k = _kernel()
    flops, nbytes = k.cost([(1, 1000)], 64, 128, 128)
    assert flops == 64 * 7 * 128 * 128 == 7340032
    assert nbytes == 2 * 4194304 + 4 * 16384 + 32768 == 8486912
    flops, nbytes = k.cost([(64, 100), (0, 7), (1, 1000)], 64, 128, 128)
    assert flops == 65 * 7340032
    assert nbytes == 2 * 8388608 + 65 * 98304
    # bytes-bound by far: 0.86 operations a byte for a decode slot, 32 for
    # a chunk of 64, against the chip's ridge of 240
    f, b = k.cost([(1, 0)], 64, 128, 128)
    assert 0.8 < f / b < 0.9
    f, b = k.cost([(64, 0)], 64, 128, 128)
    assert 30 < f / b < 34
    # the cell's steady step: 192 working slots a layer, 1.64 GB, 2.0 ms
    f, b = k.cost([(1, 1100)] * 190 + [(64, 300)] * 2, 64, 128, 128)
    assert 1.9e-3 < b / 819e9 < 2.1e-3 and f / 197e12 < 2e-5


_CALL_HEAD = ("%ragged_kda_update.3 = (bf16[192,64,128]{2,1,0:T(8,128)"
              "(2,1)}, bf16[192,64,64,128]{3,2,1,0:T(8,128)(2,1)}, "
              "f32[3,192,64,128,128]{4,3,2,1,0:T(8,128)}) custom-call(")
_CALL_OPERANDS = (
    "s32[192]{0} %ql, s32[192]{0} %fresh, s32[1]{0} %any, s32[192]{0} %off, "
    "s32[192]{0} %ssrc, s32[192]{0} %row, s32[192]{0} %cstart, "
    "s32[192]{0} %osrc, s32[192]{0} %csrc, s32[1]{0} %layer, "
    "bf16[3072,64,128]{2,1,0} %q, bf16[3072,64,128]{2,1,0} %k, "
    "bf16[3072,64,128]{2,1,0} %v, f32[3072,64,128]{2,1,0} %g, "
    "bf16[3072,64,128]{2,1,0} %q, bf16[3072,64,128]{2,1,0} %k, "
    "bf16[3072,64,128]{2,1,0} %v, f32[3072,64,128]{2,1,0} %g, "
    "f32[3,192,64,128,128]{4,3,2,1,0} %state")
_CALL_TAIL = '), custom_call_target="tpu_custom_call", operand_layout=...'


def test_match_takes_the_call_by_name_and_shapes():
    k = _kernel()
    call = _CALL_HEAD + _CALL_OPERANDS + _CALL_TAIL
    op = tr.parse_op(call, 0.0, 1.0)
    assert k.match(op) == {"slots": 192, "heads": 64, "key_dim": 128,
                           "value_dim": 128, "chunk": 64, "dtype": "bf16"}
    decode = tr.parse_op(call.replace(
        "bf16[192,64,64,128]{3,2,1,0:T(8,128)(2,1)}, ", ""), 0.0, 1.0)
    assert k.match(decode)["chunk"] == 1
    other = tr.parse_op(call.replace("ragged_kda_update",
                                     "ragged_ssd_update"), 0.0, 1.0)
    assert k.match(other) is None                        # the name decides
    not_aliased = tr.parse_op(_CALL_HEAD + _CALL_OPERANDS.replace(
        "f32[3,192,64,128,128]{4,3,2,1,0} %state",
        "f32[192,64,128,128]{3,2,1,0} %state") + _CALL_TAIL, 0.0, 1.0)
    assert k.match(not_aliased) is None         # and so do the shapes
    # a state STORED in bf16 (half the bytes) is no call to the matcher:
    # the traced run then lacks the two metrics this cell lists
    stored_in_bf16 = call.replace("f32[3,192,64,128,128]",
                                  "bf16[3,192,64,128,128]")
    assert k.match(tr.parse_op(stored_in_bf16, 0.0, 1.0)) is None
    # the scan's and the paged kernel's matchers pass this call by
    for name in ("ssd_update", "paged_attention"):
        assert spec.load_module(ROOT, "kernels", name).match(op) is None


# ---- the two new readers on a trace recorded on the chip ----

def _reader(name):
    return spec.load_module(ROOT, "layer_metrics", name)


def _run_of(xplane, want, model):
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
    run.cell = SimpleNamespace(root=ROOT, config=_config())
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
def recorded(tmp_path_factory):
    xplane, want = _unpacked(tmp_path_factory, "recorded_kda_trace")
    return _run_of(xplane, want, want["model"]), want


def test_the_delta_rule_calls_of_the_recorded_window(recorded):
    """Three calls a step (one a linear layer) beside ONE paged call (the
    softmax layer's); a mixed step's calls carry the chunks' rows, a decode
    step's do not; every call's state is the cell's ``[3, 192, 64, 128,
    128]`` float32, aliased."""
    run, want = recorded
    lo, hi = run.trace_window
    assert hi - lo == want["window_ns"]
    calls = tr.kernel_calls(run.trace, lo, hi, _kernel().match)
    assert len(calls) == want["kda_calls"] > 0
    assert sum(op.dur for op, _ in calls) == want["kda_calls_ns"]
    by_chunk = {}
    for _, s in calls:
        assert (s["slots"], s["heads"], s["key_dim"], s["value_dim"]) == \
            (192, 64, 128, 128)
        by_chunk[str(s["chunk"])] = by_chunk.get(str(s["chunk"]), 0) + 1
    assert by_chunk == want["kda_calls_by_chunk"]
    assert set(by_chunk) <= {"1", "64"} and "64" in by_chunk
    paged = spec.load_module(ROOT, "kernels", "paged_attention")
    pcalls = tr.kernel_calls(run.trace, lo, hi, paged.match)
    assert len(pcalls) == want["paged_calls"]
    assert abs(len(calls) - 3 * len(pcalls)) <= 3      # a step cut by the edge
    assert {s["q_rows"] for _, s in pcalls} <= {8, 512}    # a group of 8


def test_both_readers_read_what_was_worked_out_apart(recorded):
    run, want = recorded
    roofline = _reader("kda_roofline_pct.batch").read(run)
    share = _reader("kda_share_pct.batch").read(run)
    assert roofline == pytest.approx(want["kda_roofline_pct"], rel=1e-9)
    assert share == pytest.approx(want["kda_share_pct"], rel=1e-9)
    assert 0 < roofline < 105 and 0 < share < 100
    said = want["readers_said_on_the_chip"]
    assert roofline == pytest.approx(
        said["kda_roofline_pct.batch"]["value"], rel=1e-6)
    assert share == pytest.approx(
        said["kda_share_pct.batch"]["value"], rel=1e-6)
    # the accepted readers price this cell's calls too: ONE paged call a
    # step at a group of 8, twelve grouped calls over 40 held experts
    for name in ("paged_attn_roofline_pct.batch",
                 "gmm_held_roofline_pct.batch",
                 "expert_rows_occupancy_pct.batch"):
        got = _reader(name).read(run)
        assert got == pytest.approx(said[name]["value"], rel=1e-6), name
        assert 0 < got < 105


def test_a_program_without_linear_layers_reads_nothing(tmp_path_factory):
    """On the trace PR 34 recorded from the generation cell (a scan call,
    no delta-rule call) both readers return None, not an error: the metric
    is left out of the line; and where a model states linear layers, no
    call means no reading (the parent of PR 46 on any cell)."""
    xplane, want = _unpacked(tmp_path_factory, "recorded_ssd_trace")
    run = _run_of(xplane, want, want["model"])
    assert _reader("kda_roofline_pct.batch").read(run) is None
    assert _reader("kda_share_pct.batch").read(run) is None
    run.model = dict(want["model"], linear_attn_config={
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64})
    assert _reader("kda_roofline_pct.batch").read(run) is None


# ---- the recorded readings under the cell's limits ----

def _recorded_readings():
    path = os.path.join(DATA, "recorded_generate_long_readings.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f]


def test_the_limits_stand_between_the_recorded_readings():
    """Every run of the cell by PR 46 on the chip (each a line of the
    recorded file), through the harness's ``Checks`` and the limits of the
    cell's file as it stands: every sound reading passes and the int8
    control is refused.  A limit moved past a reading fails here."""
    from chipbench import control_verdict
    cell = spec.load_cell(CELL, ROOT)
    runs = _recorded_readings()
    control = [r for r in runs if "control_int8" in r]
    assert len({r["seed"] for r in runs}) >= 6 and len(control) >= 6
    for r in runs:
        assert r["correct"] and r["failed"] == 0
        assert control_verdict.verdict(cell, r, r["tokens"])["correct"], r
    for r in control:
        v = control_verdict.verdict(cell, r["control_int8"], r["tokens"])
        assert not v["correct"], r
    for name, limit in cell.extras["limits"].items():
        assert limit.get("from") and "PLACEHOLDER" not in limit["from"], name
    assert "PLACEHOLDER" not in cell.extras["limits_origin"]
    assert "TO BE WRITTEN" not in cell.extras["about"]
    # over a quarter of the chip's 16 GB by far
    assert all(r["memory_peak_bytes"] > 0.6 * 16e9 for r in runs)


def test_the_verdict_rests_on_a_three_fold_distance():
    """The number the verdict rests on keeps at least three times between
    the sound runs' largest and the int8 control's smallest, the limit at
    their geometric middle; the file says which number that is."""
    runs = _recorded_readings()
    control = [r["control_int8"] for r in runs if "control_int8" in r]
    x = spec.load_cell(CELL, ROOT).extras
    sound = max(r["gap_mean"] for r in runs)
    low = min(c["gap_mean"] for c in control)
    assert low > 3 * sound
    limit = x["limits"]["served_logit_gap_mean"]["limit"]
    assert limit == pytest.approx((sound * low) ** 0.5, rel=0.15)
    assert "rests on served_logit_gap_mean" in x["limits_origin"]
    assert "recorded_generate_long_readings.jsonl" in x["limits_origin"]
    assert "chiprun_out" not in json.dumps(x)


# ---- a rehearsal of the cell ----

@pytest.mark.timeout(600)
def test_a_rehearsal_of_the_cell_runs_on_the_cpu():
    """``chipbench.run --rehearse 1`` of the cell: the driver, the program
    at tiny sizes with the kernels interpreted, the reference's comparison
    (float32 on both sides: every served token is the reference's best)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--rehearse", "1",
         "--workload", CELL, "--seed", str(2**31 + 4646), "--seconds", "3",
         "--trace", "1"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=560)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.splitlines()
             if l.startswith("{")]
    last = lines[-1]
    assert last["correct"] and last["rehearsal"] and last["failed"] == 0
    assert last["attempted"] > 4 and last["metrics"] == {}
    refs = [l for l in lines if l.get("phase") == "reference"]
    assert refs and refs[0]["greedy_agree_share"] > 0.97
    window = next(l for l in lines if l.get("phase") == "window")
    assert window["documents_finished"] >= 4
