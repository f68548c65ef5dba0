"""The ``smallthinker`` family (SmallThinker-21BA3B-Instruct) in the
benchmark: the new configuration's files against the catalog and ISSUE 41's
table, the program and the reference reading the same sizes, the cell's
registry series, a rehearsal of the cell on the CPU, the int8 control, the
two new readers on a piece of the traced window recorded on a v5e against a
hand count, a Mixtral window read as before, and the recorded readings under
the cell's limits."""

import gzip
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import program_spans as ps  # noqa: E402
from chipbench.harness import spec, trace_reduce as tr, weights  # noqa: E402
from chipbench.references import smallthinker as ref  # noqa: E402

CELL = "smallthinker21b-batch-generate4k"
CONFIG = "smallthinker-21ba3b-instruct"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("gmm_counted_roofline_pct.batch", "gmm_share_pct.batch")
REPORTED = {
    "step_device_ms.batch", "device_idle_pct.batch", "host_step_ms.batch",
    "host_bound_idle_pct.batch", "token_occupancy_pct.batch",
    "gemm_occupancy_pct.batch", "paged_attn_roofline_pct.batch",
    "paged_attn_sliding_roofline_pct.batch",
    "expert_rows_occupancy_pct.batch", *NEW}
# the model at test size, under the source's own keys
SMALL = {"head_dim": 32, "hidden_size": 64, "moe_ffn_hidden_size": 32,
         "moe_num_active_primary_experts": 3, "moe_num_primary_experts": 8,
         "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
         "num_attention_heads": 6, "num_hidden_layers": 8,
         "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
         "rope_layout": [0, 1, 1, 1] * 2, "rope_scaling": None,
         "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 2,
         "sliding_window_size": 48, "tie_word_embeddings": False,
         "vocab_size": 320, "torch_dtype": "float32"}


def _config():
    return spec.load_json(os.path.join(
        ROOT, "chipbench", "configs", CONFIG + ".json"))


def _a_run(cell, rehearse=0):
    import argparse
    from chipbench.harness import core
    return core.Run(cell, argparse.Namespace(
        seed=2**31 + 5, seconds=1.0, trace=0, rehearse=rehearse, control=0),
        {"kind": "none"})


def _reference_logits(m, seed, ids, precision="highest"):
    leaves = ref.leaf_specs(m)
    flat = weights.make_flat(seed, leaves, "float32")
    return ref.sequence_logits(
        lambda l: weights.make_layer(seed, leaves, l, "float32"), flat,
        m["num_hidden_layers"], m, [ids], [list(range(len(ids)))],
        precision=precision)[0]


# ---- program, reference and control at a small size ----

def test_the_seeded_program_holds_the_references_numbers_and_int8_is_told_apart():
    """The cell's own program file builds the parameters from ``--seed``,
    every place's banks one array a layer: the numbers
    ``weights.make_layer`` hands the reference for that layer (the rehearsal
    below compares the engine built on them with the reference); and the
    int8 control reads something else than the reference in float32."""
    from chipbench.programs import smallthinker as prog
    seed = 2**31 + 41
    cfg = prog.model_config(SMALL, 256)
    assert (cfg.num_hidden_layers, cfg.period(), cfg.dtype) == \
        (8, 4, "float32")
    params = prog.seeded_params(SMALL, cfg, seed)
    leaves = ref.leaf_specs(SMALL)
    for layer in (1, 5, 6):                 # place 1 twice, place 2 once
        want = weights.make_layer(seed, leaves, layer, "float32")
        place = params["blocks"][layer % 4]
        for name, a in want.items():
            got = place[name][layer // 4]
            assert isinstance(place[name], tuple) == \
                name.startswith("mlp.experts_"), name
            assert np.array_equal(np.asarray(got), np.asarray(a)), name
    ids = np.random.default_rng(5).integers(1, SMALL["vocab_size"],
                                            100).tolist()
    want = _reference_logits(SMALL, seed, ids)
    low = _reference_logits(SMALL, seed, ids, precision="int8")
    control = want.max(-1) - np.take_along_axis(
        want, low.argmax(-1)[:, None], -1)[:, 0]
    assert np.abs(want).max() > 1.0 and np.abs(low - want).max() > 1e-2
    assert control.max() > 1e-3 and (control > 0).mean() > 0.01


# ---- the configuration's files ----

def test_spec_validate_is_empty_with_the_new_files(root=ROOT):
    bench = spec.benchmark(root)
    assert spec.validate(bench, root) == []
    assert len(bench["configs"]) >= 8 and len(bench["workloads"]) >= 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = spec.load_cell(CELL, root)
    assert cell.kind == "closed_loop_serve" and cell.chips == 1
    names = {m["name"] for m in cell.per_layer}
    assert names == set(cell.extras["reports"]["per_layer"]) == REPORTED
    assert {m["name"] for m in cell.end_to_end} == {"serve_total_tok_s",
                                                    "setup_s"}
    new = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert {(m["layer"], m["moves"], m["source"], m["unit"])
            for m in new.values()} == {
        ("kernels", "serve_total_tok_s", "device_trace", "%")}
    assert all(m["workloads"] == [CELL] for m in new.values())
    assert new["gmm_counted_roofline_pct.batch"]["better"] == "higher"
    assert new["gmm_share_pct.batch"]["better"] == "lower"
    # the entries stand behind everything the benchmark had before them
    # (PR 39's came last then), in this order; NOT "last": a cell added
    # after this one must not break this case, as this one breaks
    # test_chipbench_deepseek_v32.py's (PERF.md section 7)
    def behind(entries, name, earlier):
        names = [e["name"] for e in entries]
        return names.index(name) > names.index(earlier)
    assert behind(bench["configs"], CONFIG, "deepseek-v3.2-ep16")
    assert behind(bench["workloads"], CELL, "deepseekv32-batch-docs32k")
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW[0])
    assert names[first:first + 2] == list(NEW)
    assert first > names.index("dsa_share_pct.batch")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            others = m["workloads"][:m["workloads"].index(CELL)]
            assert "deepseekv32-batch-docs32k" not in m["workloads"] \
                or "deepseekv32-batch-docs32k" in others
    # not the Mixtral cell's share of a roofline: its cost prices the row
    # bucket's entries; the cell's file says so
    gmm = next(m for m in bench["per_layer"]
               if m["name"] == "gmm_roofline_pct.batch")
    assert CELL not in gmm["workloads"]
    assert "gmm_roofline_pct.batch" in cell.extras["reports"]["not_reported"]


def test_what_the_cell_before_this_ones_case_guards_beside_last():
    """``test_chipbench_deepseek_v32.py::
    test_spec_validate_is_empty_with_the_new_files`` is expected to fail
    since this cell stands behind PR 39's (``tests/conftest.py``): four of
    its assertions say that PR 39's entries are the LAST of their lists.
    Every other assertion of that case, restated here against that cell,
    so that only those four are lost while the marker stands."""
    root, cell_name = ROOT, "deepseekv32-batch-docs32k"
    bench = spec.benchmark(root)
    assert spec.validate(bench, root) == []
    assert len(bench["configs"]) >= 7 and len(bench["workloads"]) >= 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = spec.load_cell(cell_name, root)
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
    assert all(m["workloads"] == [cell_name] for m in new.values())
    assert new["dsa_index_roofline_pct.batch"]["better"] == "higher"
    assert new["dsa_attn_roofline_pct.batch"]["better"] == "higher"
    assert new["dsa_share_pct.batch"]["better"] == "lower"
    # in place of "last": its three entries stand together, in its order
    names_in_order = [m["name"] for m in bench["per_layer"]]
    first = names_in_order.index("dsa_index_roofline_pct.batch")
    assert names_in_order[first:first + 3] == list(new)
    for name in new:
        mod = spec.load_module(root, "layer_metrics", name)
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            "kernels", "%", "serve_total_tok_s", "device_trace")
    # what that cell leaves out, each with its reason
    said = cell.extras["reports"]["not_reported"]
    for name in ("mla_attn_roofline_pct.batch", "mla_attn_share_pct.batch",
                 "paged_attn_roofline_pct.batch", "gmm_roofline_pct.batch",
                 "slot_occupancy_pct.batch"):
        assert name in said and name not in names


def test_only_the_four_last_assertions_of_that_case_fail():
    """The marker in ``tests/conftest.py`` is not strict, so by itself it
    would let any later fault of that cell's files pass unseen.  Run here,
    the marked case fails at its first "last" assertion and nowhere
    before it."""
    import test_chipbench_deepseek_v32 as before     # beside this file
    with pytest.raises(AssertionError) as failed:
        before.test_spec_validate_is_empty_with_the_new_files()
    line = failed.traceback[-1].statement
    assert str(line).strip() == \
        'assert bench["configs"][-1]["name"] == "deepseek-v3.2-ep16"'


def test_the_new_entries_keep_the_forms_validate_does_not_hold():
    bench = spec.benchmark(ROOT)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    for text in (config["why"], config["source"], cell["why"]):
        assert 1 <= len(text) <= 200
        assert text.isascii() and text.isprintable()
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["reduced"] == ["num_hidden_layers"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert all(spec.NAME_RE.match(name) for name in NEW + (CELL, CONFIG))
    assert cell["why"] == spec.load_cell(CELL, ROOT).extras["why"]


def test_the_traffic_is_the_issues_letter_for_letter():
    t = spec.load_cell(CELL, ROOT).traffic
    assert (t["name"], t["kind"], t["schedule_seed"], t["clients"],
            t["documents"]) == ("batch-generate-4k", "closed_loop_serve", 41,
                                48, 1024)
    assert t["prompt_len"] == {"dist": "uniform", "min": 128, "max": 4096}
    assert t["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert t["engine"] == {"max_batch": 48, "max_seq_len": 5120,
                           "page_size": 16, "num_pages": 15360,
                           "prefill_bucket": 64, "max_new_tokens": 1024}
    assert t["trace"] == {"offset_s": 20.0, "seconds": 3.0}
    assert (t["reference_sample"], t["sampling"], t["early_stop"]) == \
        (4, "greedy", False)
    # every request in flight fits at its longest; 16,384 B a cached token
    assert t["engine"]["num_pages"] == 48 * (4096 + 1024) // 16
    assert 15360 * 16 * 8 * 2 * 4 * 128 * 2 == 4026531840
    for text in ("4.03 GB", "UNIFORM", "ROADMAP M1", "15 %"):
        assert text in t["pool"], text
    assert {"clients", "engine", "prompt_len", "output_len", "trace"} <= \
        set(t["rehearsal"])
    # about a sixth of the list passes position 4,096 while decoding
    from chipbench.harness import schedule
    docs = schedule.requests(t)
    past = sum(d.prompt_len + d.output_len > 4096 for d in docs) / len(docs)
    assert 0.12 < past < 0.20


def test_the_model_is_the_catalogs_config_verbatim():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
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
    differs = {k for k in source if config[k] != source[k]
               or type(config[k]) is not type(source[k])}
    assert differs == set(config["reduced"]) == {"num_hidden_layers"}
    entry = next(c for c in spec.benchmark(ROOT)["configs"]
                 if c["name"] == config["name"])
    assert differs == set(entry["reduced"])
    m = _a_run(spec.load_cell(CELL, ROOT)).model
    assert {k: m[k] for k in source} == {k: config[k] for k in source}
    assert "share" not in config and "published" not in m
    assert config["depth"] == {"published": 52, "serve": 8}
    # ``spec.validate`` holds only a file with a share to whole periods:
    # this holds the file without one
    pattern = config["layer_pattern"]
    assert pattern == {"period": 4, "leading_dense": 0}
    assert config["depth"]["serve"] % pattern["period"] == 0
    assert config["sliding_window_layout"][:8] == [0, 1, 1, 1] * 2 == \
        config["rope_layout"][:8]
    assert set(config["assumed"]) >= {
        "router_input", "activation", "topk_then_softmax", "positions",
        "rotary_pairs", "secondary_experts", "no_qk_norm_no_bias",
        "torch_dtype", "moe_block_m"}
    for text in ("3,966,937,600", "7 pipeline stages", "398,627,840",
                 "0.78 GB"):
        assert text in config["deployment"], text
    for key, want in (
            ("hidden_size", 2560), ("num_attention_heads", 28),
            ("num_key_value_heads", 4), ("head_dim", 128),
            ("moe_ffn_hidden_size", 768), ("moe_num_primary_experts", 64),
            ("moe_num_active_primary_experts", 6),
            ("sliding_window_size", 4096), ("vocab_size", 151936),
            ("rope_theta", 1500000), ("rms_norm_eps", 1e-6)):
        assert config[key] == want, key


def test_the_program_reads_the_same_sizes_and_holds_them_once():
    """``Run.model`` of the cell -> the program's own configuration and the
    engine's spec: every published width, eight layers as two periods of
    four places, 3,966,937,600 parameters."""
    from chipbench.programs import smallthinker as prog
    from paddle_tpu.models.smallthinker import SmallThinkerForCausalLM
    cell = spec.load_cell(CELL, ROOT)
    m = _a_run(cell).model
    cfg = prog.model_config(m, 5120)
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.dtype,
            cfg.max_position_embeddings) == (8, 151936, "bfloat16", 5120)
    spec_ = SmallThinkerForCausalLM.decoder_spec(SimpleNamespace(config=cfg))
    assert (spec_.periods, len(spec_.pattern), spec_.windows) == \
        (2, 4, (None, 4096, 4096, 4096) * 2)
    assert [k.rope for k in spec_.pattern] == [False, True, True, True]
    assert (spec_.moe.num_experts, spec_.moe.top_k, spec_.moe.held,
            spec_.moe.block_m, spec_.moe.router_input,
            spec_.moe.activation) == (64, 6, 64, 128, "attention", "relu")
    n = ref.count_params(m, 8)
    attention = 2560 * 3584 * 2 + 2560 * 512 * 2
    experts = 64 * 3 * 2560 * 768
    assert (attention, 2560 * 64, experts) == (20971520, 163840, 377487360)
    assert n["per_layer"] == attention + 163840 + 5120 + experts == 398627840
    assert n["embed_and_head"] == 2 * 151936 * 2560 + 2560
    assert n["total"] == 3966937600                      # 7.93 GB in bf16
    assert n["active"] == n["total"] - 8 * experts * 58 // 64
    # the rehearsal keeps the shape of the thing at tiny widths
    tiny = _a_run(cell, rehearse=1).model
    assert tiny["num_attention_heads"] // tiny["num_key_value_heads"] == 3
    assert (tiny["moe_num_primary_experts"],
            tiny["moe_num_active_primary_experts"]) == (8, 3)
    assert prog.model_config(tiny, 128).period() == 4


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "chipbench", "references",
                           "smallthinker.py")) as f:
        code = f.read().split('"""', 2)[2]
    assert "paddle_tpu" not in code and "top_k" not in code
    assert "argsort" in code and "lax.scan" in code


def test_the_cells_registry_series_reach_the_drivers_snapshot():
    from chipbench.harness import registry
    always = ("serving.batch_occupancy",)
    assert registry.series_of(spec.load_cell(CELL, ROOT), always) == \
        always + ("serving.moe_held_rows", "serving.moe_rows_laid_out")


def test_the_parent_fails_at_the_top_of_the_program_file():
    """The cell's program file imports the model before anything else: a
    program that lacks it fails at once (PERF.md section 6, PR 41)."""
    path = os.path.join(ROOT, "chipbench", "programs", "smallthinker.py")
    with open(path) as f:
        lines = [ln for ln in f.read().split('"""', 2)[2].splitlines()
                 if ln.startswith(("import ", "from "))]
    first = next(i for i, ln in enumerate(lines) if "paddle_tpu" in ln)
    assert not any("chipbench" in ln for ln in lines[:first + 2])
    assert any("paddle_tpu.models.smallthinker" in ln for ln in lines)


def test_a_rehearsal_of_the_cell_runs_on_the_cpu():
    """``--rehearse 1``: the driver, the program file, the engine and the
    reference at the rehearsal sizes, kernels interpreted; in a process of
    its own (the run sets flags and the platform)."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--rehearse", "1",
         "--workload", CELL, "--seed", str(2**31 + 41), "--seconds", "2",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=540)
    assert proc.returncode == 0, proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] and last["correct"] and last["failed"] == 0
    assert last["metrics"] == {} and last["attempted"] > 4
    assert last["checks"]["served_logit_gap_mean"]["value"] < 1e-4


# ---- the cost the new reader prices with ----

def test_cost_on_hand_counted_shapes():
    """The whole-bank call of this cell, ``[12800, 2560] x [64, 2560,
    768]``, priced with 1,200 counted entries: ``2 x 1200 x 2560 x 768``
    operations; all 64 experts' banks read once (251,658,240 B), the real
    rows in and out (1,200 x (2560 + 768) x 2 B): bound by its bytes 13
    times over; the matcher's own ``rows`` is the row bucket's 4,608."""
    whole = spec.load_module(ROOT, "kernels", "grouped_matmul")
    held = spec.load_module(ROOT, "kernels", "grouped_matmul_held")
    op = tr.parse_op(
        "%gmm.26 = bf16[12800,768]{1,0} custom-call(s32[100]{0} %tg, "
        "bf16[12800,2560]{1,0} %x, bf16[64,2560,768]{2,1,0} %w), "
        "custom_call_target=\"tpu_custom_call\"", 0.0, 1.0)
    shapes = whole.match(op)
    assert held.match(op) is None
    assert (shapes["rows"], shapes["rows_laid_out"], shapes["k"],
            shapes["n"], shapes["experts"], shapes["block_m"]) == \
        (4608, 12800, 2560, 768, 64, 128)
    flops, nbytes = held.cost(shapes, 1200.0)
    assert flops == 2 * 1200 * 2560 * 768 == 4718592000
    assert nbytes == 2 * (64 * 2560 * 768 + 1200 * 2560 + 1200 * 768) \
        == 259645440
    assert 12 < (nbytes / 819e9) / (flops / 197e12) < 14
    # the accepted cost on the same call counts the bucket's rows
    assert whole.cost(shapes)[0] == 2 * 4608 * 2560 * 768


# ---- the two new readers on a trace recorded on the chip ----

def _reader(name):
    return spec.load_module(ROOT, "layer_metrics", name)


def _run_of(trace, window, want, model, cell):
    run = SimpleNamespace(trace=trace, trace_window=window, model=model,
                          cell=cell, peaks=lambda: PEAKS)
    run.results = {"step_log": want.get("step_log", []),
                   "registry": want.get("registry", {})}
    return run


@pytest.fixture(scope="module")
def piece(tmp_path_factory):
    name = "recorded_generate4k_trace"
    with open(os.path.join(DATA, name + ".json")) as f:
        want = json.load(f)
    xplane = str(tmp_path_factory.mktemp(name) / (name + ".xplane.pb"))
    with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz"), "rb") as src, \
            open(xplane, "wb") as dst:
        dst.write(src.read())
    trace = tr.load(xplane)
    return _run_of(trace, tr.window(trace, want["window_span"]), want,
                   want["model"], spec.load_cell(CELL, ROOT)), want


def test_the_new_readers_read_what_was_worked_out_apart(piece):
    """A third of a second of the cell's traced window (seven whole steps):
    a layer makes three whole-bank calls, two over ``[64, 2560, 768]`` and
    one over ``[64, 768, 2560]``, every one of 12,800 laid-out rows; the
    readers' numbers are the hand count's (raw events, closed forms:
    the recorded file's ``about``) and what the run printed on the chip."""
    run, want = piece
    lo, hi = run.trace_window
    assert hi - lo == want["window_ns"]
    whole = spec.load_module(ROOT, "kernels", "grouped_matmul")
    calls = tr.kernel_calls(run.trace, lo, hi, whole.match)
    assert len(calls) == want["gmm_calls"] == 189
    assert sum(s["n"] == 768 for _, s in calls) == want["gmm_up_calls"] \
        == 2 * want["gmm_down_calls"]
    assert {(s["rows_laid_out"], s["experts"], s["block_m"])
            for _, s in calls} == {(12800, 64, 128)}
    assert sum(op.dur for op, _ in calls) == want["gmm_calls_ns"]
    # the share's matcher sees none of them: every expert is held
    held = spec.load_module(ROOT, "kernels", "grouped_matmul_held")
    assert tr.kernel_calls(run.trace, lo, hi, held.match) == []
    said = want["readers_said_on_the_chip"]
    for name in ("gmm_counted_roofline_pct", "gmm_share_pct"):
        got = _reader(name + ".batch").read(run)
        assert got == pytest.approx(want[name], rel=1e-9), name
        assert got == pytest.approx(said[name + ".batch"]["value"],
                                    rel=1e-6), name
        assert 0 < got < 100
    # the closed forms once more, from the file's own counts
    rows = want["rows_a_layer"]
    reg = want["registry"]["serving.moe_held_rows"]
    assert rows == reg["sum"] / reg["count"] / 8
    least = sum(n * max(2 * rows * 2560 * 768 / 197e12,
                        2 * (64 * 2560 * 768 + rows * 3328) / 819e9)
                for n in (want["gmm_up_calls"], want["gmm_down_calls"]))
    assert least == pytest.approx(want["gmm_least_s"], rel=1e-12)
    assert 100 * least / (want["gmm_calls_ns"] / 1e9) == pytest.approx(
        want["gmm_counted_roofline_pct"], rel=1e-12)
    assert 100 * want["gmm_inside_ns"] / want["busy_ns"] == pytest.approx(
        want["gmm_share_pct"], rel=1e-12)
    # the rows' occupancy the accepted reader gives for the same piece
    occupancy = _reader("expert_rows_occupancy_pct.batch").read(run)
    assert occupancy == pytest.approx(
        said["expert_rows_occupancy_pct.batch"]["value"], rel=1e-6)


def test_a_program_that_counts_no_rows_reads_nothing_under_the_new_names(
        piece):
    """The parent runs no cell that names the series: without it (or with
    no grouped call in the window) the new readers return None and do not
    raise."""
    run, want = piece
    bare = _run_of(run.trace, run.trace_window, {}, run.model, run.cell)
    assert _reader("gmm_counted_roofline_pct.batch").read(bare) is None
    empty = _run_of(tr.Trace([[]], [[]], []), (0.0, 1.0), want, run.model,
                    run.cell)
    for name in NEW:
        assert _reader(name).read(empty) is None


def test_a_mixtral_window_is_read_as_before():
    """Three calls of the Mixtral cell's layer (8 experts of 14,336, a row
    bucket of 9,216 + 8 x 512 rows) in a made-up window: the accepted share
    prices the bucket's rows, as it always has, and the cell reports
    nothing under the new names (its file names no series to count by)."""
    def call(n, out, lhs, bank, start, dur):
        return tr.parse_op(
            f"%gmm.{n} = bf16[{out}]{{1,0}} custom-call(s32[26]{{0}} %tg, "
            f"bf16[{lhs}]{{1,0}} %x, bf16[{bank}]{{2,1,0}} %w), "
            "custom_call_target=\"tpu_custom_call\"", start, dur)
    ops = [call(1, "13312,14336", "13312,4096", "8,4096,14336", 0.0, 9e6),
           call(2, "13312,14336", "13312,4096", "8,4096,14336", 1e7, 9e6),
           call(3, "13312,4096", "13312,14336", "8,14336,4096", 2e7, 9e6)]
    cell = spec.load_cell("mixtral8x7b-batch-docs", ROOT)
    names = {m["name"] for m in cell.per_layer}
    assert "gmm_roofline_pct.batch" in names and not names & set(NEW)
    run = _run_of(tr.Trace([ops], [[]], []), (0.0, 3e7), {}, {}, cell)
    flops = 2 * 9216 * 4096 * 14336
    nbytes = 2 * (8 * 4096 * 14336 + 9216 * (4096 + 14336))
    least = 3 * max(flops / 197e12, nbytes / 819e9)
    assert _reader("gmm_roofline_pct.batch").read(run) == pytest.approx(
        100 * least / 0.027, rel=1e-12)
    assert _reader("gmm_counted_roofline_pct.batch").read(run) is None


# ---- the limits of ``correct`` against every run recorded on the chip ----

def _recorded_readings():
    with open(os.path.join(DATA, "recorded_generate4k_readings.jsonl")) as f:
        return [json.loads(line) for line in f]


def test_the_limits_stand_between_the_recorded_readings():
    """Every run of the cell by PR 41 on the chip (each a line of the
    recorded file, its flips counted again from that run's
    ``reference_detail`` line), through the harness's ``Checks`` and the
    limits of the cell's file as it stands: every program reading passes and
    the int8 control is refused in every run by ``served_flip_share``, the
    number the verdict rests on: the one reading whose two sides do not
    overlap (a request whose greedy text collapses reads nothing on either
    side, and the near positions it is held against tell), two times apart
    and short of three-fold: the file says so, digit for digit.  A limit
    moved past a reading fails here."""
    from chipbench import control_verdict
    from chipbench.harness.serving import readings
    cell = spec.load_cell(CELL, ROOT)
    limits = cell.extras["limits"]
    assert set(limits) == {"served_tokens_compared", "served_flip_share",
                           "served_logit_gap_mean"}
    flip = limits["served_flip_share"]
    assert (flip["gap_over"], flip["margin_under"], flip["plus"]) == (
        0.08, 0.05, 10)
    got = readings(np.asarray([0.0, 0.081, 0.079, 2.0]),
                   np.asarray([0.04, 1.9, 0.0, 0.3]), flip)
    assert (got["flips"], got["near"], got["flip_share"]) == (2, 2, 2 / 12)
    # a text without one close call reads 0, not a division by zero
    assert readings(np.asarray([0.0]), np.asarray([1.0]),
                    flip)["flip_share"] == 0.0
    sound = _recorded_readings()
    control = [r for r in sound if "control_int8" in r]
    assert len({r["seed"] for r in sound}) >= 25 and len(control) >= 17
    assert max(r["seed"] for r in sound) > 2**31
    guarded = 0
    for r in sound:
        assert control_verdict.verdict(cell, r, r["tokens"])["correct"], r
        assert r["failed"] == 0 and r["tokens"] >= 1024
        if "control_int8" in r:
            v = control_verdict.verdict(cell, r["control_int8"], r["tokens"])
            assert not v["correct"]
            assert "served_flip_share" in v["not_ok"], r["seed"]
            guarded += "served_logit_gap_mean" in v["not_ok"]
    assert guarded >= len(control) - 2      # the guard alone misses two
    for name, limit in limits.items():
        assert limit.get("from"), name
    high = max(r["flip_share"] for r in sound)
    low = min(r["control_int8"]["flip_share"] for r in control)
    assert 1.9 * high < low
    assert 1.35 * high < cell.limit("served_flip_share") < low / 1.35
    gaps = [r["gap_mean"] for r in sound]
    lows = [r["control_int8"]["gap_mean"] for r in control]
    assert 1.5 * max(gaps) < cell.limit("served_logit_gap_mean")
    text = flip["from"]
    for number in (f"{min(r['flip_share'] for r in sound):.4f}",
                   f"{high:.4f}", f"{low:.4f}",
                   f"{max(r['control_int8']['flip_share'] for r in control):.4f}",
                   f"{low / high:.2f} times",
                   f"mean gap {min(lows) / max(gaps):.2f}"):
        assert number in text, number
    text = limits["served_logit_gap_mean"]["from"]
    for number in (f"{min(gaps):.4f}", f"{max(gaps):.4f}",
                   f"{min(lows):.4f}", f"{max(lows):.4f}"):
        assert number in text, number
    # the memory a run holds: over a quarter of the chip
    assert all(r["memory_peak_bytes"] > 0.25 * 16e9 for r in sound)
