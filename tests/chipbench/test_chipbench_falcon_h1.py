"""The ``falcon_h1`` family (Falcon-H1-34B-Instruct) in the benchmark: the
program's engine with the BENCHMARK's seeded weights against the plain
reference at a small size, the int8 control, the new configuration's files,
the scan kernel's cost on hand-counted rows, the two new readers on a trace
recorded on a v5e, and the recorded readings under the cell's limits."""

import gzip
import json
import os
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
from chipbench.references import falcon_h1 as ref  # noqa: E402

CELL = "falconh1-batch-generate"
CONFIG = "falcon-h1-34b-instruct"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SOUND_SEEDS, CONTROL_SEEDS = 28, 7      # recorded runs of PR 34
CONTROL_REFUSED = 7                     # each of them by every limit
MULTIPLIERS = {
    "embedding_multiplier": 5.656854249492381, "attention_in_multiplier": 1,
    "key_multiplier": 0.011048543456039804,
    "attention_out_multiplier": 0.0375, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "lm_head_multiplier": 0.0078125}
# the model at test size: 2 groups, a group of 5 query heads a KV head, a
# state wider than a head, every multiplier at its published value
SMALL = dict(MULTIPLIERS, hidden_size=64, intermediate_size=96,
             num_attention_heads=5, num_key_value_heads=1, head_dim=32,
             mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
             mamba_d_state=32, mamba_n_groups=2, mamba_d_conv=4,
             vocab_size=320, rms_norm_eps=1e-5, rope_theta=1e11,
             num_hidden_layers=3, torch_dtype="float32")


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
    from chipbench.programs import falcon_h1 as prog
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
    return out


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
    steps, through pages and the recurrent state (the chunk form in the
    interpreted kernel), against the reference's bare recurrence over the
    whole sequence.  Tolerance: both sides are float32 and differ by the
    order of their sums; the logits lie within +-0.04 (``lm_head_
    multiplier``), so 1e-7 is a few roundings of them; a state held in
    bf16 reads forty times that (tests/test_falcon_h1.py)."""
    rng = np.random.default_rng(5)
    ids = rng.integers(1, SMALL["vocab_size"], 90).tolist()
    seed = 2**31 + 34
    got = _engine_logits(SMALL, seed, ids, prefill=78)
    want = _reference_logits(SMALL, seed, ids)
    assert got.shape == want.shape == (90, SMALL["vocab_size"])
    assert np.abs(want).max() < 0.06
    assert np.max(np.abs(got - want)) < 1e-7
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.98


def test_what_the_reference_states_moves_its_logits():
    """Without the mixer's branch, with the state's decay switched off, or
    with another multiplier, the reference reads something else (guards
    the guard); the first token has no history: a decay moves nothing
    there."""
    rng = np.random.default_rng(6)
    ids = rng.integers(1, SMALL["vocab_size"], 64).tolist()
    want = _reference_logits(SMALL, 9, ids)
    no_mixer = _reference_logits(dict(SMALL, ssm_out_multiplier=0.0), 9, ids)
    no_keys = _reference_logits(dict(SMALL, key_multiplier=1.0), 9, ids)
    for other in (no_mixer, no_keys):
        assert np.abs(other[8:] - want[8:]).max() > 1e-5
    assert np.abs(no_keys[0] - want[0]).max() < 1e-9


def test_the_int8_control_is_told_apart():
    rng = np.random.default_rng(4)
    ids = rng.integers(1, SMALL["vocab_size"], 128).tolist()
    want = _reference_logits(SMALL, 7, ids)
    low = _reference_logits(SMALL, 7, ids, precision="int8")
    control = want.max(-1) - np.take_along_axis(
        want, low.argmax(-1)[:, None], -1)[:, 0]
    assert np.abs(low - want).max() > 1e-4
    assert control.max() > 1e-5 and (control > 0).sum() >= 1


def test_the_seeded_time_scales_spread_as_the_file_says():
    """``dt_bias`` (std 3) and ``A_log`` (std 1): at the published 32 heads
    a layer and four layers, some heads remember hundreds of tokens
    (``dt x |A|`` under 2^-8, where a bf16 state would stall) and some
    forget within one."""
    m = _a_run(spec.load_cell(CELL, ROOT)).model
    leaves = [lf for lf in ref.leaf_specs(m)
              if lf.name in ("mamba.dt_bias", "mamba.A_log")]
    rate = []
    for layer in range(4):
        w = weights.make_layer(3400000011, leaves, layer, "bfloat16")
        dt = np.log1p(np.exp(np.asarray(w["mamba.dt_bias"], np.float32)))
        rate += list(dt * np.exp(np.asarray(w["mamba.A_log"], np.float32)))
    rate = np.asarray(rate)
    assert rate.shape == (128,)
    assert (rate < 2.0 ** -8).sum() >= 2 and (rate > 1.0).sum() >= 20
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


def test_spec_validate_is_empty_with_the_new_files(root=ROOT):
    bench = spec.benchmark(root)
    assert spec.validate(bench, root) == []
    assert len(bench["configs"]) >= 6 and len(bench["workloads"]) >= 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = spec.load_cell(CELL, root)
    assert cell.kind == "closed_loop_serve" and cell.chips == 1
    names = {m["name"] for m in cell.per_layer}
    assert names == set(cell.extras["reports"]["per_layer"]) == {
        "step_device_ms.batch", "device_idle_pct.batch", "host_step_ms.batch",
        "host_bound_idle_pct.batch", "token_occupancy_pct.batch",
        "gemm_occupancy_pct.batch", "paged_attn_roofline_pct.batch",
        "ssd_roofline_pct.batch", "ssd_share_pct.batch"}
    assert {m["name"] for m in cell.end_to_end} == {"serve_total_tok_s",
                                                    "setup_s"}
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"].startswith("ssd_")}
    assert {(m["layer"], m["moves"], m["source"], m["unit"])
            for m in new.values()} == {
        ("kernels", "serve_total_tok_s", "device_trace", "%")}
    assert all(CELL in m["workloads"] for m in new.values())
    assert new["ssd_roofline_pct.batch"]["better"] == "higher"
    assert new["ssd_share_pct.batch"]["better"] == "lower"
    # its entries are there, wherever later ones stand, and its two
    # metrics follow each other
    assert CONFIG in [c["name"] for c in bench["configs"]]
    assert CELL in [w["name"] for w in bench["workloads"]]
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("ssd_roofline_pct.batch")
    assert names[at:at + 2] == sorted(new)


def test_the_new_entries_keep_the_forms_validate_does_not_hold():
    """``spec.validate`` holds a cell's ``why`` to 200 characters and not a
    configuration's; the driver holds both."""
    bench = spec.benchmark(ROOT)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    for text in (config["why"], config["source"], cell["why"]):
        assert 1 <= len(text) <= 200
        assert text.isascii() and text.isprintable()
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["reduced"] == ["num_hidden_layers"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for name in ("ssd_roofline_pct.batch", "ssd_share_pct.batch"):
        assert spec.NAME_RE.match(name)


def test_the_traffic_is_the_issues_letter_for_letter():
    t = spec.load_cell(CELL, ROOT).traffic
    assert (t["kind"], t["schedule_seed"], t["clients"], t["documents"]) == \
        ("closed_loop_serve", 34, 128, 2048)
    assert t["prompt_len"] == {"dist": "uniform", "min": 128, "max": 1024}
    assert t["output_len"] == {"dist": "uniform", "min": 512, "max": 1024}
    # ISSUE 34's six parameters, letter for letter, and no other (the
    # engine's own drain cadence, as in every other cell)
    assert "sync_every_why" not in t
    assert t["engine"] == {"max_batch": 128, "max_seq_len": 2048,
                      "page_size": 16, "num_pages": 16384,
                      "prefill_bucket": 16, "max_new_tokens": 1024}
    assert t["trace"] == {"offset_s": 20.0, "seconds": 3.0}
    assert (t["reference_sample"], t["sampling"], t["early_stop"]) == \
        (3, "greedy", False)
    # every request in flight fits at its longest; 8,192 B a cached token
    assert t["engine"]["num_pages"] == 128 * 2048 // 16
    assert 16384 * 16 * 4 * 2 * 4 * 128 * 2 == 2147483648
    assert {"clients", "engine", "prompt_len", "output_len", "trace"} <= \
        set(t["rehearsal"])


def test_the_model_is_the_catalogs_config_verbatim():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Falcon-H1-34B-Instruct")
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
    assert differs == set(config["reduced"]) == {"num_hidden_layers"}
    assert not [k for k in differs if spec.is_width(k)]
    entry = next(c for c in spec.benchmark(ROOT)["configs"]
                 if c["name"] == config["name"])
    assert differs == set(entry["reduced"])
    m = _a_run(spec.load_cell(CELL, ROOT)).model
    assert {k: m[k] for k in source} == {k: config[k] for k in source}
    assert "share" not in config and "published" not in m
    assert config["depth"] == {"published": 72, "serve": 4}
    assert set(config["assumed"]) >= {
        "ssm_multipliers_order", "rotary_pairs", "state_dtype",
        "time_step_limit", "torch_dtype"}
    for text in ("4,394,354,048", "68 layers", "18 x", "16,900,096"):
        assert text in config["deployment"], text
    for key, want in (
            ("hidden_size", 5120), ("num_attention_heads", 20),
            ("num_key_value_heads", 4), ("head_dim", 128),
            ("mamba_n_heads", 32), ("mamba_d_head", 128),
            ("mamba_d_state", 256), ("mamba_n_groups", 2),
            ("mamba_d_conv", 4), ("mamba_d_ssm", 4096),
            ("intermediate_size", 21504), ("vocab_size", 261120),
            ("rope_theta", 100000000000)):
        assert config[key] == want, key
    assert {k: config[k] for k in MULTIPLIERS} == MULTIPLIERS


def test_the_program_reads_the_same_sizes_and_holds_them_once():
    """``Run.model`` of the cell -> the program's own configuration: every
    published width, four layers, 4,394,354,048 parameters held once."""
    from chipbench.programs import falcon_h1 as prog
    cell = spec.load_cell(CELL, ROOT)
    m = _a_run(cell).model
    cfg = prog.model_config(m, 2048)
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.dtype) == \
        (4, 261120, "bfloat16")
    assert cfg.max_position_embeddings == 2048
    mx = cfg.mixer()
    assert (mx.heads, mx.head_dim, mx.state, mx.groups, mx.conv,
            mx.in_width) == (32, 128, 256, 2, 4, 9248)
    n = ref.count_params(m, 4)
    attention = 5120 * 2560 * 2 + 5120 * 512 * 2
    mixer = 5120 * 9248 + 4096 * 5120 + 4 * 5120 + 5120 + 4096 + 3 * 32
    mlp = 3 * 5120 * 21504
    assert (attention, mixer, mlp) == (31457280, 68351072, 330301440)
    assert n["per_layer"] == attention + mixer + mlp + 2 * 5120 == 430120032
    assert n["embed_and_head"] == 2 * 261120 * 5120 + 5120
    assert n["total"] == n["active"] == 4394354048       # 8.79 GB in bf16
    # the rehearsal keeps the shape of the thing at tiny widths
    tiny = _a_run(cell, rehearse=1).model
    assert tiny["num_attention_heads"] // tiny["num_key_value_heads"] == 5
    assert tiny["mamba_d_state"] > tiny["mamba_d_head"]
    assert tiny["mamba_n_groups"] == 2
    assert tiny["mamba_d_ssm"] == tiny["mamba_n_heads"] * tiny["mamba_d_head"]


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "chipbench", "references",
                           "falcon_h1.py")) as f:
        text = f.read()
    assert "paddle_tpu" not in text.split('"""', 2)[2]
    assert "lax.scan" in text and "default" not in text.split('"""', 2)[2]


# ---- the scan kernel's file ----

def _kernel():
    return spec.load_module(ROOT, "kernels", "ssd_update")


def test_cost_on_hand_counted_rows():
    """One decode slot and one chunk of 16, 32 heads of 128 over a state of
    256 in 2 groups: a working slot reads and writes 2 x 4,194,304 B of
    state whatever its tokens, a token brings x and takes y (2 x 8,192 B),
    B and C (2 x 1,024 B) and dt (128 B); the recurrence's 5 x 128 x 256
    operations a token a head.  A slot without work costs nothing."""
    k = _kernel()
    flops, nbytes = k.cost([(1, 1000)], 32, 128, 256, 2)
    assert flops == 32 * 5 * 128 * 256 == 5242880
    assert nbytes == 2 * 4194304 + (2 * 8192 + 2 * 1024 + 128) == 8407168
    flops, nbytes = k.cost([(16, 100), (0, 7), (1, 1000)], 32, 128, 256, 2)
    assert flops == 17 * 5242880
    assert nbytes == 2 * 8388608 + 17 * 18560
    # bytes-bound by far: 0.62 operations a byte for a decode slot, 9.6 for
    # a chunk of 16, against the chip's ridge of 240
    f, b = k.cost([(1, 0)], 32, 128, 256, 2)
    assert 0.6 < f / b < 0.65
    f, b = k.cost([(16, 0)], 32, 128, 256, 2)
    assert 9 < f / b < 10
    # the cell's steady step: 128 working slots a layer, 1.08 GB, 1.31 ms
    f, b = k.cost([(1, 1100)] * 122 + [(16, 300)] * 6, 32, 128, 256, 2)
    assert 1.30e-3 < b / 819e9 < 1.33e-3 and f / 197e12 < 1e-5


_SCAN_HEAD = ("%ragged_ssd_update.3 = (bf16[128,32,16,128]{3,2,1,0:T(8,128)"
              "(2,1)}, f32[4,128,32,128,256]{4,3,2,1,0:T(8,128)}) "
              "custom-call(")
_SCAN_OPERANDS = (
    "s32[128]{0} %ql, s32[128]{0} %src, s32[128]{0} %fresh, "
    "s32[1]{0} %any, f32[32]{0} %d, f32[4096]{0} %lq, "
    "f32[4096]{0} %aq, s32[1]{0} %layer, f32[128,32,16]{2,1,0} "
    "%l, f32[128,32,16]{2,1,0} %dt, bf16[128,32,16,128]{3,2,1,0} "
    "%x, bf16[128,2,16,256]{3,2,1,0} %b, "
    "bf16[128,2,16,256]{3,2,1,0} %c, "
    "f32[4,128,32,128,256]{4,3,2,1,0} %state")
_SCAN_TAIL = '), custom_call_target="tpu_custom_call", operand_layout=...'


def test_match_takes_the_scan_call_by_name_and_shapes():
    k = _kernel()
    head, operands, tail = _SCAN_HEAD, _SCAN_OPERANDS, _SCAN_TAIL
    op = tr.parse_op(head + operands + tail, 0.0, 1.0)
    assert k.match(op) == {"slots": 128, "heads": 32, "head_dim": 128,
                           "state": 256, "q_rows": 16, "dtype": "bf16"}
    other = tr.parse_op((head + operands + tail).replace(
        "ragged_ssd_update", "ragged_paged_attention"), 0.0, 1.0)
    assert k.match(other) is None                        # the name decides
    not_aliased = tr.parse_op(head + operands.replace(
        "f32[4,128,32,128,256]{4,3,2,1,0} %state",
        "f32[128,32,128,256]{3,2,1,0} %state") + tail, 0.0, 1.0)
    assert k.match(not_aliased) is None         # and so do the shapes
    # the paged kernel's matcher passes this call by (its second result is
    # no log-sum-exp), and the scan's matcher the paged call
    paged = spec.load_module(ROOT, "kernels", "paged_attention")
    assert paged.match(op) is None
    a_paged = tr.parse_op(
        "%ragged_paged_attention.2 = (bf16[128,4,80,128]{3,2,1,0}, "
        "f32[128,4,80,1]{3,2,1,0}) custom-call(s32[128,128]{1,0} %bt, "
        "s32[128]{0} %cl), custom_call_target='tpu_custom_call'", 0.0, 1.0)
    assert paged.match(a_paged)["q_rows"] == 80 and k.match(a_paged) is None


def test_a_state_stored_in_bf16_is_no_scan_call_to_the_matcher():
    """What holds the program to the float32 state the configuration's
    file states, on the chip: ``correct`` does not see the state's
    precision at the seeded weights (the cell's ``not_compared``), but a
    program that STORES the state in bf16 (half the scan's bytes) hands
    the matcher no call, so its traced run lacks ``ssd_roofline_pct.batch``
    and ``ssd_share_pct.batch``, which the cell lists: refused."""
    k = _kernel()
    call = _SCAN_HEAD + _SCAN_OPERANDS + _SCAN_TAIL
    assert k.match(tr.parse_op(call, 0.0, 1.0)) is not None
    stored_in_bf16 = call.replace("f32[4,128,32,128,256]",
                                  "bf16[4,128,32,128,256]")
    assert stored_in_bf16.count("bf16[4,128,32,128,256]") == 2
    assert k.match(tr.parse_op(stored_in_bf16, 0.0, 1.0)) is None
    x = spec.load_cell(CELL, ROOT).extras
    assert "ssd_roofline_pct.batch" in x["reports"]["per_layer"]
    assert "the recurrent state's precision" in x["not_compared"]


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
    run.cell = SimpleNamespace(root=ROOT)
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
def scan(tmp_path_factory):
    xplane, want = _unpacked(tmp_path_factory, "recorded_ssd_trace")
    return _run_of(xplane, want, want["model"]), want


def test_the_scan_calls_of_the_recorded_window(scan):
    """Four calls a step (the layer scan's body, once a layer), each beside
    one paged call; every call's state is the cell's ``[4, 128, 32, 128,
    256]`` float32, aliased."""
    run, want = scan
    lo, hi = run.trace_window
    assert hi - lo == want["window_ns"]
    calls = tr.kernel_calls(run.trace, lo, hi, _kernel().match)
    assert len(calls) == want["ssd_calls"] > 0
    assert len(calls) % want["model"]["num_hidden_layers"] == 0
    assert sum(op.dur for op, _ in calls) == want["ssd_calls_ns"]
    by_rows = {}
    for _, s in calls:
        assert (s["slots"], s["heads"], s["head_dim"], s["state"]) == \
            (128, 32, 128, 256)
        by_rows[str(s["q_rows"])] = by_rows.get(str(s["q_rows"]), 0) + 1
    assert by_rows == want["ssd_calls_by_q_rows"]
    paged = spec.load_module(ROOT, "kernels", "paged_attention")
    pcalls = tr.kernel_calls(run.trace, lo, hi, paged.match)
    assert len(pcalls) == want["paged_calls"] == len(calls)
    assert {s["q_rows"] for _, s in pcalls} <= {8, 80}     # a group of 5


def test_both_readers_read_what_was_worked_out_apart(scan):
    run, want = scan
    roofline = _reader("ssd_roofline_pct.batch").read(run)
    share = _reader("ssd_share_pct.batch").read(run)
    assert roofline == pytest.approx(want["ssd_roofline_pct"], rel=1e-9)
    assert share == pytest.approx(want["ssd_share_pct"], rel=1e-9)
    assert 0 < roofline < 105 and 0 < share < 100
    said = want["readers_said_on_the_chip"]
    assert roofline == pytest.approx(
        said["ssd_roofline_pct.batch"]["value"], rel=1e-6)
    assert share == pytest.approx(
        said["ssd_share_pct.batch"]["value"], rel=1e-6)
    # the accepted reader prices the paged calls of a group of 5
    paged = _reader("paged_attn_roofline_pct.batch").read(run)
    assert paged == pytest.approx(
        said["paged_attn_roofline_pct.batch"]["value"], rel=1e-6)
    assert 0 < paged < 105


def test_a_program_without_a_mixer_reads_nothing(tmp_path_factory):
    """On the trace PR 31 recorded from the 16k-documents cell (no scan
    call) both readers return None, not an error: the metric is left out of
    the line; and where a model states a mixer, no call means no reading."""
    xplane, want = _unpacked(tmp_path_factory, "recorded_latent_trace")
    run = _run_of(xplane, want, want["model"])
    assert _reader("ssd_roofline_pct.batch").read(run) is None
    assert _reader("ssd_share_pct.batch").read(run) is None
    run.model = dict(want["model"], mamba_d_state=256, mamba_n_groups=2)
    assert _reader("ssd_roofline_pct.batch").read(run) is None


# ---- the recorded readings under the cell's limits ----

def _recorded_readings():
    path = os.path.join(DATA, "recorded_generate_readings.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f]


def test_the_limits_stand_between_the_recorded_readings():
    """Every run of the cell by PR 34 on the chip (each a line of the
    recorded file), through the harness's ``Checks`` and the limits of the
    cell's file as it stands: every sound reading passes and the int8
    control is refused.  A limit moved past a reading fails here."""
    from chipbench import control_verdict
    cell = spec.load_cell(CELL, ROOT)
    runs = [r for r in _recorded_readings() if "state" not in r]
    control = [r for r in runs if "control_int8" in r]
    assert len({r["seed"] for r in runs}) >= SOUND_SEEDS
    assert len(control) >= CONTROL_SEEDS
    refused = 0
    for r in runs:
        assert r["correct"] and r["failed"] == 0
        assert control_verdict.verdict(cell, r, r["tokens"])["correct"], r
        if "control_int8" in r:
            v = control_verdict.verdict(cell, r["control_int8"], r["tokens"])
            refused += not v["correct"]
    assert refused == CONTROL_REFUSED
    for name, limit in cell.extras["limits"].items():
        assert limit.get("from") and "PROVISIONAL" not in limit["from"], name
    assert "PROVISIONAL" not in cell.extras["limits_origin"]
    # the fullest device: over a quarter of the chip's 16 GB by far
    assert all(r["memory_peak_bytes"] > 0.75 * 16e9 for r in runs)


def test_the_verdict_rests_on_the_mean_gap_and_a_bf16_state_is_not_caught():
    """The three compared statistics keep a three-fold distance between
    the sound runs' largest and the int8 control's smallest (the widest
    gap does not, and is not compared); the file says which the verdict
    rests on; and the two recorded runs with the state held in bf16 read
    INSIDE the sound runs' range: the file says that too."""
    runs = [r for r in _recorded_readings() if "state" not in r]
    control = [r["control_int8"] for r in runs if "control_int8" in r]
    x = spec.load_cell(CELL, ROOT).extras

    def apart(key, of=lambda r, k: r[k]):
        return min(of(c, key) for c in control) \
            / max(of(r, key) for r in runs)

    disagree = apart(None, lambda r, _: 1 - r["greedy_agree_share"])
    assert (f"{apart('gap_mean'):.1f}", f"{apart('gap_p99'):.1f}",
            f"{disagree:.2f}") == ("12.6", "6.9", "3.24")
    assert apart("gap_max") < 3 and "served_logit_gap_max" not in x["limits"]
    for text in ("12.6", "6.9", "3.24", "rests on served_logit_gap_mean",
                 "recorded_generate_readings.jsonl"):
        assert text in x["limits_origin"], text
    assert "chiprun_out" not in json.dumps(x)
    for r in runs:                      # each limit has room on both sides
        for name, key in (("served_logit_gap_mean", "gap_mean"),
                          ("served_logit_gap_p99", "gap_p99")):
            assert r[key] * 2.5 < x["limits"][name]["limit"]
    for c in control:
        assert c["gap_mean"] > 3 * x["limits"]["served_logit_gap_mean"][
            "limit"]
    held_in_bf16 = [r for r in _recorded_readings() if "state" in r]
    assert len(held_in_bf16) == 2
    for r in held_in_bf16:
        for key in ("gap_mean", "gap_p99"):
            assert min(s[key] for s in runs) <= r[key] \
                <= max(s[key] for s in runs)
    assert "does NOT catch a state kept in bf16" in x["limits_origin"]
