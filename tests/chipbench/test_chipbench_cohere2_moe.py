"""The ``cohere2_moe`` family (Command A+) at a small size on the CPU: the
program's engine (prefill in chunks, then decode through the paged cache)
against the plain reference, the share tied to the model, the router, and
the new configuration's files."""

import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import spec, weights  # noqa: E402
from chipbench.references import cohere2_moe as ref  # noqa: E402

KINDS = ["sliding_attention", "sliding_attention", "sliding_attention",
         "full_attention"]
# an uncut model: 16 experts, all held; a window of 24 tokens
FULL = {"hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 64, "vocab_size": 320,
        "layer_norm_eps": 1e-5, "rope_theta": 50000, "sliding_window": 24,
        "layer_types": KINDS, "num_hidden_layers": 4, "num_experts": 16,
        "num_experts_per_tok": 8, "num_shared_experts": 2,
        "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
        "shared_expert_combination_strategy": "average",
        "use_parallel_block": True, "tie_word_embeddings": True,
        "logit_scale": 1, "position_embedding_type": "rope_gptj",
        "torch_dtype": "float32"}
CHIPS = 8


def share(index: int) -> dict:
    """``Run.model`` of chip ``index`` of eight that share each layer."""
    return dict(FULL, num_experts=FULL["num_experts"] // CHIPS,
                published={"num_experts": FULL["num_experts"]},
                share={"chips": CHIPS, "index": index})


def _reference_logits(m, seed, ids, precision="highest"):
    leaves = ref.leaf_specs(m)
    flat = weights.make_flat(seed, leaves, "float32")
    return ref.sequence_logits(
        lambda l: weights.make_layer(seed, leaves, l, "float32"), flat,
        m["num_hidden_layers"], m, [ids], [list(range(len(ids)))],
        precision=precision)[0]


def _engine_logits(m, seed, ids, prefill, chunk=16):
    """The program's engine core over one sequence, as the engine drives
    it: the first ``prefill`` tokens in chunks of ``chunk`` (the last one
    ragged), the rest one token a step, every step through the paged cache
    (pages of 8, the kernel interpreted)."""
    from chipbench.programs import cohere2_moe as prog
    eng, _ = prog.build_engine(
        m, {"max_batch": 2, "max_seq_len": 128, "page_size": 8,
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


@pytest.mark.parametrize("m", [FULL, share(3)], ids=["uncut", "share_3_of_8"])
def test_engine_prefill_then_decode_equals_the_reference(m, interpreted):
    """100 tokens, a window of 24: contexts on both sides of the window,
    chunks that straddle it, then 20 decode steps.  Tolerance: both sides
    are float32; they differ by the order of their sums (softmax page by
    page online, the shared experts added up by one GEMM, experts grouped
    by tiles), 1e-5 relative at these sizes, bound at 2e-4 of the largest
    logit."""
    rng = np.random.default_rng(5)
    ids = rng.integers(1, m["vocab_size"], 100).tolist()
    seed = 2**31 + 27
    got = _engine_logits(m, seed, ids, prefill=80)
    want = _reference_logits(m, seed, ids)
    assert got.shape == want.shape == (100, m["vocab_size"])
    assert np.max(np.abs(got - want)) < 2e-4 * max(1.0, np.abs(want).max())
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.98


def test_the_window_and_the_missing_positions_are_in_the_reference():
    """Without the window, or with rotary positions on the full layer, the
    reference reads something else past the window (guards the guard)."""
    rng = np.random.default_rng(6)
    ids = rng.integers(1, FULL["vocab_size"], 64).tolist()
    want = _reference_logits(FULL, 9, ids)
    wide = _reference_logits(dict(FULL, sliding_window=4096), 9, ids)
    all_sliding = _reference_logits(
        dict(FULL, layer_types=["sliding_attention"] * 4,
             sliding_window=4096), 9, ids)
    assert np.abs(wide[:24] - want[:24]).max() < 1e-4     # inside: the same
    assert np.abs(wide[40:] - want[40:]).max() > 1e-2
    assert np.abs(all_sliding[1:] - wide[1:]).max() > 1e-2


@pytest.mark.parametrize("dispatch", ["grouped", "dense"])
def test_the_eight_shares_add_up_to_the_uncut_layer(dispatch):
    """The routed parts that the eight shares give (the program's expert
    layer, told which experts it holds) plus the shared experts counted
    once are the uncut reference's layer; the reference's own shares too."""
    from paddle_tpu.inference.generation import _moe_ffn
    from paddle_tpu.models.decoder_spec import MoeSpec
    leaves = ref.leaf_specs(FULL)
    w = weights.make_layer(11, leaves, 1, "float32")
    rng = np.random.default_rng(7)
    u = jnp.asarray(rng.normal(size=(48, FULL["hidden_size"]))
                    .astype(np.float32))
    shared = ref.shared_experts(u, w, FULL, "highest")
    want = np.asarray(ref.routed_experts(u, w, FULL, "highest") + shared)
    held = FULL["num_experts"] // CHIPS
    program = np.zeros_like(want)
    reference = np.zeros_like(want)
    counted = 0
    for i in range(CHIPS):
        mine = {k: (v[i * held:(i + 1) * held]
                    if k.startswith("mlp.experts_") else v)
                for k, v in w.items()}
        out, rows = _moe_ffn(u, mine, MoeSpec(
            num_experts=FULL["num_experts"], top_k=8, score="sigmoid",
            held=held, offset=i * held, shared=2, dispatch=dispatch,
            block_m=8))
        program += np.asarray(out)
        counted += int(rows[0])
        reference += np.asarray(ref.routed_experts(u, mine, share(i),
                                                   "highest"))
    program -= (CHIPS - 1) * np.asarray(shared)    # every chip computed them
    assert counted == 48 * 8        # every (token, choice) entry fell once
    np.testing.assert_allclose(program, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(reference + np.asarray(shared), want,
                               rtol=2e-5, atol=2e-6)


def test_router_sigmoid_top8_renormalised_over_all_chosen():
    from paddle_tpu.models.llama import _route_topk
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(40, 64)).astype(np.float32))
    gw = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32) * 0.3)
    topv, topi, _, _ = _route_topk(x, gw, 8, "sigmoid")
    want = np.asarray(ref.router_gates(x, {"mlp.gate.weight": gw}, FULL))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(topi), np.asarray(topv), axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got.sum(1), 1.0, rtol=1e-5)
    assert ((got > 0).sum(1) == 8).all()
    # by hand: the 8 largest sigmoid scores, each over their sum
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(gw))))
    top = np.sort(s, 1)[:, -8:]
    np.testing.assert_allclose(np.sort(got, 1)[:, -8:],
                               top / top.sum(1, keepdims=True), rtol=1e-5)


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


# ---- the configuration's files ----

def _config():
    return spec.load_json(os.path.join(
        ROOT, "chipbench", "configs", "command-a-plus-05-2026-ep8.json"))


def test_spec_validate_is_empty_with_the_new_files():
    assert spec.validate(spec.benchmark(ROOT), ROOT) == []
    cell = spec.load_cell("commandaplus-batch-longdocs", ROOT)
    assert cell.kind == "closed_loop_serve" and cell.chips == 1
    names = {m["name"] for m in cell.per_layer}
    assert {"gmm_held_roofline_pct.batch", "expert_rows_occupancy_pct.batch",
            "paged_attn_sliding_roofline_pct.batch",
            "paged_attn_roofline_pct.batch"} <= names
    # its match would price every laid-out row; a closed loop reads 100
    assert not names & {"gmm_roofline_pct.batch", "slot_occupancy_pct.batch"}
    assert {m["name"] for m in cell.end_to_end} == {"serve_total_tok_s",
                                                    "setup_s"}


def test_the_model_is_the_catalogs_config_verbatim():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "command-a-plus-05-2026")
    config = _config()
    assert config["source"] == row["source_url"]
    assert {k: v for k, v in config["model"].items()
            if k != "torch_dtype"} == row["config"]
    assert "torch_dtype" in config["assumed"]
    # what the driver's check reads: the file's own top level, key for key
    assert {k for k, v in row["config"].items()
            if k not in config or config[k] != v} == set(config["reduced"])


def test_the_files_top_level_is_the_source_as_this_chip_runs_it():
    """The file holds every key of the source's config.json at its TOP
    level too, as run: only what ``reduced`` names differs from ``model``
    (the source verbatim), no width among it, and it is what ``Run.model``
    hands the program."""
    config = _config()
    source = {k: v for k, v in config["model"].items() if k != "torch_dtype"}
    assert set(source) <= set(config)
    differs = {k for k in source if config[k] != source[k]
               or type(config[k]) is not type(source[k])}
    assert differs == set(config["reduced"])
    assert not [k for k in differs if spec.is_width(k)]
    entry = next(c for c in spec.benchmark(ROOT)["configs"]
                 if c["name"] == config["name"])
    assert differs == set(entry["reduced"])
    m = _a_run(spec.load_cell("commandaplus-batch-longdocs", ROOT)).model
    assert {k: m[k] for k in source} == {k: config[k] for k in source}
    assert m["published"] == {k: source[k] for k in differs}


def test_the_share_and_the_program_read_the_same_sizes():
    """``Run.model`` of the cell -> the program's own configuration: the
    router at its published width, 16 experts held from number 0 on, the
    vocabulary's slice, one period of (3 sliding, 1 full), no width cut."""
    import argparse
    from chipbench.harness import core
    from chipbench.programs import cohere2_moe as prog
    cell = spec.load_cell("commandaplus-batch-longdocs", ROOT)
    run = core.Run(cell, argparse.Namespace(
        seed=1, seconds=1, rehearse=0, control=0, trace=0), {"kind": "cpu"})
    m = run.model
    cfg = prog.model_config(m, 12416)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_offset) == (128, 16, 0)
    assert (cfg.vocab_size, cfg.num_hidden_layers, cfg.period()) == (32768, 4, 4)
    assert [k.window for k in cfg.pattern()] == [4096, 4096, 4096, None]
    assert [k.rope for k in cfg.pattern()] == [True] * 3 + [False]
    for key in ("hidden_size", "intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "num_shared_experts",
                "sliding_window"):
        assert getattr(cfg, key) == cell.config["model"][key], key
    n = ref.count_params(m, 4)
    assert n["total"] == 4 * 1149767680 + 32768 * 4096 + 4096   # 9.47 GB bf16
    pages = run.traffic["engine"]["num_pages"]
    assert pages == 16 * (12288 + 128) // 16


def _a_run(cell, rehearse=0):
    import argparse
    from chipbench.harness import core
    return core.Run(cell, argparse.Namespace(
        seed=2**31 + 5, seconds=1.0, trace=0, rehearse=rehearse, control=0),
        {"kind": "none"})


CELLS = [w["name"] for w in spec.benchmark(ROOT)["workloads"]]


@pytest.mark.parametrize("rehearse", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_run_model_with_and_without_a_share(name, rehearse):
    """What ``test_chipbench_spec.py`` states of every cell, restated now
    that one has a share (its own cases fail on that cell: PERF.md section
    7).  Without a share: the published sizes, the role's depth, the
    rehearsal's sizes on top, and nothing else.  With one: the held sizes
    over them, and ``published`` / ``share`` beside them."""
    c = spec.load_cell(name, ROOT)
    role = "train" if c.kind == "train" else "serve"
    want = dict(c.config["model"])
    want["num_hidden_layers"] = int(c.config["depth"][role])
    cut = c.config.get("share")
    if cut:
        want.update(cut[role])
    if rehearse:
        want.update(c.config.get("rehearsal_model", {}))
    got = _a_run(c, rehearse).model
    if cut and not rehearse:
        assert got.pop("published") == {
            k: c.config["model"][k] for k in c.config["reduced"]}
        assert got.pop("share") == {"chips": cut["chips"],
                                    "index": cut["index"]}
        assert got["num_experts"] * cut["chips"] \
            == c.config["model"]["num_experts"]
    elif not cut:
        assert "published" not in got and "share" not in got
        assert json.dumps(got) == json.dumps(want)
    for key, value in want.items():
        assert got[key] == value, key


def test_the_cells_own_registry_series_are_read_over_the_window():
    """``reports.registry_series`` of the cell's file reaches the driver's
    snapshot: the window's difference holds what was observed inside it
    and nothing from before, and a cell that names none gets none more."""
    from chipbench.harness import registry
    always = ("serving.queue_wait_ms", "serving.batch_occupancy")
    mine = ("serving.moe_held_rows", "serving.moe_rows_laid_out")
    for name in CELLS:
        series = registry.series_of(spec.load_cell(name, ROOT), always)
        assert series == always + (
            mine if name == "commandaplus-batch-longdocs" else ())
    series = always + mine
    held = registry.histogram("serving.moe_held_rows")
    laid = registry.histogram("serving.moe_rows_laid_out")
    held.observe(7.0)                            # before the window
    laid.observe(64.0)
    before = {s: registry.snap(s) for s in series}
    for h, l in ((570.0, 2048.0), (650.0, 2048.0), (520.0, 1920.0)):
        held.observe(h)
        laid.observe(l)
    window = {s: registry.delta(before[s], registry.snap(s)) for s in series}
    assert window["serving.moe_held_rows"]["count"] == 3
    assert window["serving.moe_held_rows"]["sum"] == 1740.0
    assert registry.mean(window["serving.moe_held_rows"]) == 580.0
    assert registry.mean(window["serving.moe_rows_laid_out"]) \
        == pytest.approx(6016.0 / 3)
    assert window["serving.queue_wait_ms"]["count"] == 0


def _recorded_readings():
    path = os.path.join(ROOT, "tests", "chipbench", "data",
                        "recorded_longdocs_readings.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f]


def test_the_limits_stand_between_the_recorded_readings():
    """Every run of the cell on the committed step programs (chip calls 4,
    7 and 8 of PR 27: 30 runs, 14 of them with ``--control 1``), through
    the harness's ``Checks`` and the limits of the cell's file as it
    stands: every program reading passes, and the int8 control is refused
    in every run but the one whose text gives int8 nothing to disagree
    with (PERF.md section 7).  A limit moved past a reading fails here."""
    from chipbench import control_verdict
    cell = spec.load_cell("commandaplus-batch-longdocs", ROOT)
    assert set(cell.extras["limits"]) == {
        "served_tokens_compared", "served_logit_gap_p99",
        "served_disagree_share"}
    runs = _recorded_readings()
    assert len(runs) == 30 and len({r["seed"] for r in runs}) == 29
    passed = []
    for r in runs:
        assert control_verdict.verdict(cell, r, r["tokens"])["correct"], r
        if "control_int8" in r:
            v = control_verdict.verdict(cell, r["control_int8"], r["tokens"])
            if v["correct"]:
                passed.append(r["seed"])
            else:
                assert "served_logit_gap_p99" in v["not_ok"]
    assert passed == [2600000099]
    # each limit lies where its ``from`` says
    sound_p99 = max(r["gap_p99"] for r in runs)
    control_p99 = sorted(r["control_int8"]["gap_p99"] for r in runs
                         if "control_int8" in r)
    p99 = cell.limit("served_logit_gap_p99")
    assert sound_p99 < 0.0441 and control_p99[1] > 0.0624
    assert p99 - sound_p99 == pytest.approx(control_p99[1] - p99, abs=1e-3)
    sound_dis = max(1 - r["greedy_agree_share"] for r in runs)
    assert sound_dis < 0.0281 < cell.limit("served_disagree_share") / 1.7


def test_control_verdict_reads_a_runs_output(tmp_path, capsys):
    """``python -m chipbench.control_verdict``: the lines of a run with
    ``--control 1`` as the chip printed them (seed 2500000004, chip call
    4), and a run without the control, which is skipped."""
    from chipbench import control_verdict
    r = next(x for x in _recorded_readings() if x["seed"] == 2500000004)
    ref = {k: r[k] for k in ("tokens", "gap_max", "gap_p99", "gap_mean",
                             "greedy_agree_share", "control_int8")}
    out = tmp_path / "run.out"
    out.write_text("\n".join([
        "a line that is no JSON",
        json.dumps({"phase": "start", "seed": r["seed"],
                    "workload": "commandaplus-batch-longdocs"}),
        json.dumps(dict(ref, phase="reference"))]) + "\n")
    plain = tmp_path / "plain.out"
    del ref["control_int8"]
    plain.write_text(json.dumps({"phase": "start", "seed": 1, "workload":
                                 "commandaplus-batch-longdocs"}) + "\n"
                     + json.dumps(dict(ref, phase="reference")) + "\n")
    assert control_verdict.main([str(out), str(plain)]) == 0
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert lines[0]["program"]["correct"] is True
    assert lines[0]["control_int8"] == {
        "correct": False, "not_ok": ["served_logit_gap_p99"],
        "checks": {"served_tokens_compared": 351.0,
                   "served_logit_gap_p99": r["control_int8"]["gap_p99"],
                   "served_disagree_share": pytest.approx(9 / 351)}}
    assert "skipped" in lines[1]
    assert lines[2] == {"runs": 1, "program_correct_and_control_refused": 1}
    assert control_verdict.main([str(plain)]) == 1         # nothing judged
