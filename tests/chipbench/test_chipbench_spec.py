"""BENCHMARK.json and the files it names: the contract's rules that can be
seen without a run, and that the harness is driven by data."""

import json
import os
import re
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]        # HERE: one test imports its neighbours

from chipbench.harness import spec  # noqa: E402

BENCH = spec.benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["per_layer"]]
with open(os.path.join(HERE, "data", "recorded_run_model.json")) as _f:
    RECORDED_MODELS = json.load(_f)["models"]     # of PR 38's parent


def test_benchmark_json_keeps_the_contracts_rules():
    assert spec.validate(BENCH, ROOT) == []
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert BENCH["command"] == ["python3", "-m", "chipbench.run"]
    assert sorted(BENCH["paths"]) == ["chipbench", "tests/chipbench"]


@pytest.mark.parametrize("entry", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_names_and_units_as_the_driver_requires(entry):
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", entry["name"])
    assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in spec.SOURCES
    for text in (entry.get("layer", "x"),):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("bad", ["tokens per s", "µs", "", "x" * 17])
def test_a_bad_unit_is_found(bad):
    b = json.loads(json.dumps(BENCH))
    b["end_to_end"][0]["unit"] = bad
    assert any("bad unit" in e for e in spec.validate(b, ROOT))


@pytest.mark.parametrize("bad", ["has space", "a/b", "a,b", "x" * 65, "-x"])
def test_a_bad_name_is_found(bad):
    b = json.loads(json.dumps(BENCH))
    b["workloads"][0]["name"] = bad
    assert any("bad name" in e for e in spec.validate(b, ROOT))


@pytest.mark.parametrize("key", [
    "intermediate_size", "moe_intermediate_size", "sliding_window",
    "head_dim", "kv_lora_rank", "num_experts_per_tok", "moe_top_k",
    "index_topk", "conv_width", "expand"])
def test_a_width_in_reduced_is_found(key):
    b = json.loads(json.dumps(BENCH))
    b["configs"][0]["reduced"].append(key)
    assert any(f"names a width {key}" in e for e in spec.validate(b, ROOT))


@pytest.mark.parametrize("key", [
    "num_hidden_layers", "vocab_size", "num_experts", "n_routed_experts",
    "num_local_experts", "num_attention_heads"])
def test_a_count_held_here_is_no_width(key):
    assert not spec.is_width(key)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_and_every_file_it_names_loads(cell, root=ROOT):
    bench = spec.benchmark(root)
    c = spec.load_cell(cell, root)
    for sub, name in (("drivers", c.kind), ("references", c.config["family"]),
                      ("programs", c.config["family"])):
        assert os.path.exists(os.path.join(root, "chipbench", sub,
                                           name + ".py")), (sub, name)
    reports = c.extras["reports"]
    assert sorted(reports["end_to_end"]) == sorted(
        m["name"] for m in c.end_to_end)
    assert sorted(reports["per_layer"]) == sorted(
        m["name"] for m in c.per_layer)
    assert c.extras["why"] == c.why
    assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1
    for limit in c.extras["limits"].values():
        assert "limit" in limit
    # what differs from the source is what the entry lists, and the file
    # keeps the rules on a cut (spec.validate_config): no width among them
    entry = next(e for e in bench["configs"] if e["name"] == c.config_name)
    assert set(c.config["reduced"]) == set(entry["reduced"])
    assert spec.validate_config(entry, c.config) == []
    assert c.config["depth"]["published"] == \
        c.config["model"]["num_hidden_layers"]
    assert "assumed" in c.config and "deployment" in c.config


@pytest.mark.parametrize("metric", METRICS)
def test_every_per_layer_metric_has_its_own_reader(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    mod = spec.load_module(ROOT, "layer_metrics", metric)
    assert callable(mod.read)
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"])


def test_published_widths_of_the_two_models():
    mistral = spec.load_json(os.path.join(
        ROOT, "chipbench/configs/mistral-7b-v0.3.json"))["model"]
    mixtral = spec.load_json(os.path.join(
        ROOT, "chipbench/configs/mixtral-8x7b-v0.1.json"))["model"]
    for m in (mistral, mixtral):
        assert (m["hidden_size"], m["intermediate_size"],
                m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"], m["num_hidden_layers"]) == (
                    4096, 14336, 32, 8, 128, 32)
        assert m["rope_theta"] == 1e6 and m["rms_norm_eps"] == 1e-5
    assert mistral["vocab_size"] == 32768 and mixtral["vocab_size"] == 32000
    assert (mixtral["num_local_experts"],
            mixtral["num_experts_per_tok"]) == (8, 2)


def test_no_cell_config_or_mix_is_named_in_any_python_file():
    names = set(CELLS) | {c["name"] for c in BENCH["configs"]} \
        | {w["traffic"] for w in BENCH["workloads"]}
    for base, _, files in os.walk(os.path.join(ROOT, "chipbench")):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(base, f)).read()
                for n in names:
                    assert n not in text, (f, n)


def test_no_topology_call_and_no_side_effect_at_import():
    for base, _, files in os.walk(os.path.join(ROOT, "chipbench")):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(base, f)).read()
                assert "get_topology_desc" not in text, f


def _copy(tmp_path):
    root = str(tmp_path / "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def _dump(obj, *path):
    with open(os.path.join(*path), "w") as f:
        json.dump(obj, f)


def _add_toy_cell(root):
    """A configuration, a mix, a cell, a driver kind and a per-layer metric
    as new files plus entries of BENCHMARK.json."""
    cb = os.path.join(root, "chipbench")
    _dump({"name": "toy-1b", "source": "https://example.org/toy",
           "family": "llama", "model": {"hidden_size": 8},
           "depth": {"published": 2, "serve": 2}, "reduced": {},
           "assumed": {}, "deployment": "a test"},
          cb, "configs", "toy-1b.json")
    _dump({"name": "chat-burst", "kind": "replay", "schedule_seed": 5,
           "arrivals": {"process": "fixed", "rate_rps": 3.0,
                        "horizon_s": 10.0},
           "prompt_len": {"dist": "fixed", "value": 9},
           "output_len": {"dist": "fixed", "value": 3}},
          cb, "traffic", "chat-burst.json")
    _dump({"name": "toy-chat-burst", "config": "toy-1b",
           "traffic": "chat-burst", "chips": 1, "why": "a test",
           "reports": {}, "limits": {"x": {"limit": 1}}},
          cb, "workloads", "toy-chat-burst.json")
    with open(os.path.join(cb, "drivers", "replay.py"), "w") as f:
        f.write("def run(r):\n    r.results['ran'] = r.cell.name\n")
    with open(os.path.join(cb, "layer_metrics", "burst_size.chat.py"),
              "w") as f:
        f.write("LAYER = 'entry'\nUNIT = 'tokens'\nMOVES = 'itl_p99_ms'\n"
                "SOURCE = 'program_counter'\n\n\ndef read(run):\n"
                "    return 8.0\n")
    bench = spec.benchmark(root)
    bench["configs"].append({
        "name": "toy-1b", "source": "https://example.org/toy",
        "file": "chipbench/configs/toy-1b.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({
        "name": "toy-chat-burst", "config": "toy-1b",
        "traffic": "chat-burst", "chips": 1, "why": "a test"})
    bench["end_to_end"][0]["workloads"].append("toy-chat-burst")
    bench["per_layer"].append({
        "name": "burst_size.chat", "unit": "tokens", "better": "lower",
        "source": "program_counter", "layer": "entry",
        "moves": "itl_p99_ms", "workloads": ["toy-chat-burst"]})
    _dump(bench, root, "BENCHMARK.json")
    return bench


# One chip's share of a toy deployment: 8 chips share each layer, so of 64
# routed experts 8 live here and of 4,096 vocabulary rows 512; one leading
# dense layer and one period of 4 of the published 1 + 3 x 4 layers.
SHARE_TOY = {
    "name": "toy-moe-share", "source": "https://example.org/toy-moe",
    "family": "toymoe",
    "deployment": "chip 3 of the 8 that share each layer of a toy model: "
                  "expert parallel and vocabulary parallel; the layers left "
                  "out would lie on further chips",
    "model": {"hidden_size": 8, "moe_intermediate_size": 4,
              "num_hidden_layers": 13, "first_k_dense_replace": 1,
              "num_experts": 64, "num_experts_per_tok": 4,
              "vocab_size": 4096, "sliding_window": 16},
    "depth": {"published": 13, "serve": 5},
    "layer_pattern": {"period": 4, "leading_dense": 1},
    "share": {"chips": 8, "index": 3,
              "how": "expert parallel (8 of 64 experts), vocabulary "
                     "parallel (512 of 4,096 rows)",
              "serve": {"num_experts": 8, "vocab_size": 512}},
    "reduced": {"num_hidden_layers": "13 -> 5: the dense layer, one period",
                "num_experts": "64 -> 8 held here; the router keeps 64",
                "vocab_size": "4096 -> 512: ids are drawn from the slice"},
    "assumed": {}, "rehearsal_model": {"hidden_size": 4},
}
# what a family's two files make of the sizes they are handed
FAMILY = ("def sizes(m):\n"
          "    return {'held': m['num_experts'], 'router': "
          "m['published']['num_experts'],\n"
          "            'vocab': m['vocab_size'], 'of': "
          "m['published']['vocab_size'],\n"
          "            'place': (m['share']['index'], m['share']['chips']),\n"
          "            'layers': m['num_hidden_layers']}\n")


def _add_share_toy(root, change=None):
    """A second toy: a configuration cut to one chip's share, with a family
    of its own (program and reference written here) and a cell on the toy
    mix.  ``change(bench, config)`` alters it before it is written."""
    cb = os.path.join(root, "chipbench")
    bench = spec.benchmark(root)
    config = json.loads(json.dumps(SHARE_TOY))
    bench["configs"].append({
        "name": "toy-moe-share", "source": SHARE_TOY["source"],
        "file": "chipbench/configs/toy-moe-share.json",
        "reduced": ["num_hidden_layers", "num_experts", "vocab_size"],
        "why": "a test: chip 3 of 8 that share a layer"})
    bench["workloads"].append({
        "name": "toy-share-burst", "config": "toy-moe-share",
        "traffic": "chat-burst", "chips": 1,
        "why": "a test; attention sees 8 x its share of the tokens"})
    bench["end_to_end"][0]["workloads"].append("toy-share-burst")
    bench["per_layer"][-1]["workloads"].append("toy-share-burst")
    if change is not None:
        change(bench, config)
    _dump(config, cb, "configs", "toy-moe-share.json")
    _dump({"name": "toy-share-burst", "config": "toy-moe-share",
           "traffic": "chat-burst", "chips": 1,
           "why": bench["workloads"][-1]["why"], "reports": {
               "registry_series": ["t9toy.expert_load"]}, "limits": {}},
          cb, "workloads", "toy-share-burst.json")
    for sub in ("programs", "references"):
        with open(os.path.join(cb, sub, "toymoe.py"), "w") as f:
            f.write(FAMILY)
    _dump(bench, root, "BENCHMARK.json")
    return bench


# A third toy, the room PR 38 made: a model of 256 routed experts that no
# chip holds as one of 8.  16 chips share each layer: 16 experts live here;
# the vocabulary is divided over 8 parts only (an eighth is the floor), so
# two chips hold each part; the source publishes 3 leading dense layers and
# one runs (leading dense layers count once); depth 1 + 4 of 61.
SHARE16_TOY = {
    "name": "toy-moe-share16", "source": "https://example.org/toy-moe16",
    "family": "toymoe16",
    "deployment": "chip 5 of the 16 that share each layer of a toy model: "
                  "16 of 256 experts, part 5 of the 8 the vocabulary is "
                  "divided over; the layers left out lie on further chips",
    "model": {"hidden_size": 8, "moe_intermediate_size": 4,
              "num_hidden_layers": 61, "first_k_dense_replace": 3,
              "n_routed_experts": 256, "num_experts_per_tok": 8,
              "n_group": 8, "topk_group": 4, "index_topk": 16,
              "vocab_size": 4096},
    "depth": {"published": 61, "serve": 5},
    "layer_pattern": {"period": 1, "leading_dense": 1,
                      "leading_key": "first_k_dense_replace"},
    "share": {"chips": 16, "index": 5,
              "how": "expert parallel over 16 (16 of 256 experts), "
                     "vocabulary parallel over 8 (512 of 4,096 rows)",
              "over": {"vocab_size": 8},
              "serve": {"n_routed_experts": 16, "vocab_size": 512}},
    "reduced": {"num_hidden_layers": "61 -> 5: one dense layer, four others",
                "n_routed_experts": "256 -> 16 held; the router keeps 256",
                "vocab_size": "4096 -> 512: ids are drawn from the slice",
                "first_k_dense_replace": "3 -> 1: counted once"},
    "assumed": {}, "rehearsal_model": {"hidden_size": 4},
}
# the harness computes no offset: the family's two files do, from ``share``
FAMILY16 = (
    "def sizes(m):\n"
    "    s = m['share']\n"
    "    return {'held': m['n_routed_experts'],\n"
    "            'router': m['published']['n_routed_experts'],\n"
    "            'experts_from': m['n_routed_experts'] * s['index'],\n"
    "            'vocab': m['vocab_size'], 'of': "
    "m['published']['vocab_size'],\n"
    "            'vocab_part': s['index'] % s['over']['vocab_size'],\n"
    "            'dense': (m['first_k_dense_replace'],\n"
    "                      m['published']['first_k_dense_replace']),\n"
    "            'place': (s['index'], s['chips']),\n"
    "            'layers': m['num_hidden_layers']}\n")
TOY16_CELL = "toy-share16-replay"


def _add_share16_toy(root, change=None):
    """The third toy with everything of its own, appended AFTER the last of
    each list: a configuration, a family, a mix, a driver kind, a cell that
    names a registry series, and a per-layer metric."""
    cb = os.path.join(root, "chipbench")
    bench = spec.benchmark(root)
    config = json.loads(json.dumps(SHARE16_TOY))
    bench["configs"].append({
        "name": "toy-moe-share16", "source": SHARE16_TOY["source"],
        "file": "chipbench/configs/toy-moe-share16.json",
        "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size",
                    "first_k_dense_replace"],
        "why": "a test: chip 5 of 16 that share a layer"})
    bench["workloads"].append({
        "name": TOY16_CELL, "config": "toy-moe-share16",
        "traffic": "replay-steady", "chips": 1,
        "why": "a test; attention sees 16 x its share of the tokens"})
    bench["end_to_end"][0]["workloads"].append(TOY16_CELL)
    bench["per_layer"].append({
        "name": "held_experts.replay", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": bench["end_to_end"][0]["name"], "workloads": [TOY16_CELL]})
    if change is not None:
        change(bench, config)
    _dump(config, cb, "configs", "toy-moe-share16.json")
    _dump({"name": "replay-steady", "kind": "replay16", "schedule_seed": 6,
           "arrivals": {"process": "fixed", "rate_rps": 3.0,
                        "horizon_s": 10.0},
           "prompt_len": {"dist": "fixed", "value": 9},
           "output_len": {"dist": "fixed", "value": 3}},
          cb, "traffic", "replay-steady.json")
    _dump({"name": TOY16_CELL, "config": "toy-moe-share16",
           "traffic": "replay-steady", "chips": 1,
           "why": bench["workloads"][-1]["why"], "reports": {
               "end_to_end": [bench["end_to_end"][0]["name"], "setup_s"],
               "per_layer": ["held_experts.replay"],
               "registry_series": ["t9toy.expert_load16"]},
           "limits": {"x": {"limit": 1}}},
          cb, "workloads", TOY16_CELL + ".json")
    with open(os.path.join(cb, "drivers", "replay16.py"), "w") as f:
        f.write("def run(r):\n    r.results['ran'] = r.cell.name\n")
    with open(os.path.join(cb, "layer_metrics", "held_experts.replay.py"),
              "w") as f:
        f.write("LAYER = 'kernels'\nUNIT = 'count'\nMOVES = %r\n"
                "SOURCE = 'program_counter'\n\n\ndef read(run):\n"
                "    return float(run.model['n_routed_experts'])\n"
                % bench["end_to_end"][0]["name"])
    for sub in ("programs", "references"):
        with open(os.path.join(cb, sub, "toymoe16.py"), "w") as f:
            f.write(FAMILY16)
    _dump(bench, root, "BENCHMARK.json")
    return bench


def _a_run(cell, rehearse=0):
    from types import SimpleNamespace
    from chipbench.harness import core
    return core.Run(cell, SimpleNamespace(seed=2**31 + 5, seconds=1.0,
                                          trace=0, rehearse=rehearse,
                                          control=0), {"kind": "none"})


def test_a_cell_a_mix_a_config_a_driver_and_a_metric_are_added_as_files(
        tmp_path):
    """New files plus entries, and no edit to a file that is there."""
    root = _copy(tmp_path)
    before = {}
    for base, _, files in os.walk(os.path.join(root, "chipbench")):
        for f in files:
            p = os.path.join(base, f)
            before[p] = open(p, "rb").read()
    _add_toy_cell(root)
    _add_share_toy(root)
    bench = _add_share16_toy(root)

    assert spec.validate(bench, root) == []
    cell = spec.load_cell("toy-chat-burst", root)
    assert (cell.kind, cell.config["family"], cell.limit("x")) == (
        "replay", "llama", 1)
    assert [m["name"] for m in cell.per_layer] == ["burst_size.chat"]
    driver = spec.load_module(root, "drivers", cell.kind)

    class R:
        results = {}
    R.cell = cell
    driver.run(R)
    assert R.results["ran"] == "toy-chat-burst"
    assert spec.load_module(root, "layer_metrics",
                            "burst_size.chat").read(R) == 8.0
    from chipbench.harness import schedule
    items = schedule.in_window(schedule.requests(cell.traffic), 10.0)
    assert len(items) == 29 and items[0].prompt_len == 9

    # the configuration cut to a chip's share: what is held, with the
    # published counts and the place among the chips beside it, reaches
    # the family's program and its reference alike
    share = spec.load_cell("toy-share-burst", root)
    run = _a_run(share)
    m = run.model
    assert (m["num_experts"], m["vocab_size"], m["num_hidden_layers"]) == (
        8, 512, 5)
    assert m["published"] == {"num_experts": 64, "vocab_size": 4096,
                              "num_hidden_layers": 13}
    assert m["share"] == {"chips": 8, "index": 3}
    # widths and the experts a token takes are the source's
    assert (m["hidden_size"], m["moe_intermediate_size"],
            m["num_experts_per_tok"], m["sliding_window"]) == (8, 4, 4, 16)
    want = {"held": 8, "router": 64, "vocab": 512, "of": 4096,
            "place": (3, 8), "layers": 5}
    for sub in ("programs", "references"):
        assert spec.load_module(root, sub, share.config["family"]).sizes(
            m) == want
    assert _a_run(share, rehearse=1).model["hidden_size"] == 4
    ids = [t for it in items for t in schedule.token_ids(
        run.seed, it.index, 200, m["vocab_size"])]
    assert 0 < min(ids) and 500 < max(ids) < 512     # drawn from the slice

    # one chip of SIXTEEN: 16 of 256 experts, the vocabulary over 8 parts,
    # one of the three leading dense layers; the published counts, the
    # place and the key's own divisor stand beside what is held
    cell16 = spec.load_cell(TOY16_CELL, root)
    run16 = _a_run(cell16)
    m = run16.model
    assert (m["n_routed_experts"], m["vocab_size"],
            m["first_k_dense_replace"], m["num_hidden_layers"]) == (
        16, 512, 1, 5)
    assert m["published"] == {
        "num_hidden_layers": 61, "n_routed_experts": 256,
        "vocab_size": 4096, "first_k_dense_replace": 3}
    assert m["share"] == {"chips": 16, "index": 5,
                          "over": {"vocab_size": 8}}
    assert (m["hidden_size"], m["moe_intermediate_size"],
            m["num_experts_per_tok"], m["n_group"], m["topk_group"],
            m["index_topk"]) == (8, 4, 8, 8, 4, 16)
    want = {"held": 16, "router": 256, "experts_from": 80, "vocab": 512,
            "of": 4096, "vocab_part": 5, "dense": (1, 3), "place": (5, 16),
            "layers": 5}
    for sub in ("programs", "references"):
        assert spec.load_module(root, sub, cell16.config["family"]).sizes(
            m) == want
    assert _a_run(cell16, rehearse=1).model["hidden_size"] == 4
    assert (cell16.kind, cell16.limit("x")) == ("replay16", 1)
    R.cell, R.model = cell16, m
    spec.load_module(root, "drivers", cell16.kind).run(R)
    assert R.results["ran"] == TOY16_CELL
    assert [e["name"] for e in cell16.per_layer] == ["held_experts.replay"]
    assert spec.load_module(root, "layer_metrics",
                            "held_experts.replay").read(R) == 16.0
    items = schedule.in_window(schedule.requests(cell16.traffic), 10.0)
    ids = [t for it in items for t in schedule.token_ids(
        run16.seed, it.index, 200, m["vocab_size"])]
    assert 0 < min(ids) and 500 < max(ids) < 512     # drawn from the 512
    # an old cell still loads, and nothing that was there has changed
    assert spec.load_cell(CELLS[0], root).name == CELLS[0]
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def _reduced(key, bench, config):
    bench["configs"][-1]["reduced"].append(key)
    config["reduced"][key] = "cut"


def _set(path, value):
    def change(bench, config):
        node = config
        for k in path[:-1]:
            node = node[k]
        if value is None:
            del node[path[-1]]
        else:
            node[path[-1]] = value
    return change


def _both(*changes):
    def change(bench, config):
        for c in changes:
            c(bench, config)
    return change


def _fewer_experts(bench, config):
    config["model"]["num_experts"] = 32
    config["share"]["serve"]["num_experts"] = 4


def _sixteen_chips(bench, config):
    """16 chips may share a layer (PR 38); a sixteenth of the vocabulary,
    and 4 of 64 experts, may not be what one of them holds."""
    config["share"].update(chips=16, serve={"num_experts": 4,
                                            "vocab_size": 256})


def _reduced_without(key):
    def change(bench, config):
        bench["configs"][-1]["reduced"].remove(key)
        del config["reduced"][key]
    return change


EIGHT, SIXTEEN = (_add_share_toy, "toy-moe-share"), \
    (_add_share16_toy, "toy-moe-share16")


@pytest.mark.parametrize("toy,change,sentence", [
    (EIGHT, lambda b, c: _reduced("moe_intermediate_size", b, c),
     "reduced names a width moe_intermediate_size"),
    (EIGHT, lambda b, c: _reduced("sliding_window", b, c),
     "reduced names a width sliding_window"),
    (EIGHT, lambda b, c: b["configs"][-1]["reduced"].remove("vocab_size"),
     "differs from BENCHMARK.json's"),
    (EIGHT, lambda b, c: c["reduced"].pop("num_experts"),
     "differs from BENCHMARK.json's"),
    (EIGHT, _set(("share", "serve", "n_group"), 1), "is no key of model"),
    (EIGHT, _set(("share", "serve", "num_experts"), 16),
     "16 x 8 chips != the published 64"),
    (EIGHT, _set(("share", "serve", "vocab_size"), 1024),
     "1024 x 8 chips != the published 4096"),
    (EIGHT, _fewer_experts, "holds 4 experts, under 8"),
    (EIGHT, _sixteen_chips,
     "share.serve.vocab_size holds 256 of 4096, under an eighth (512)"),
    (EIGHT, _sixteen_chips, "holds 4 experts, under 8"),
    (EIGHT, _set(("share", "chips"), 1), "share.chips 1 is not"),
    (EIGHT, _set(("share", "index"), 8), "share.index 8 is not one of the 8"),
    (EIGHT, _set(("share", "how"), ""), "share.how does not say"),
    (EIGHT, _set(("share", "train"), {"num_experts": 8}),
     "depth has no 'train'"),
    (EIGHT, _set(("depth", "serve"), 3), "depth.serve 3 is under the floor 5"),
    (EIGHT, _set(("depth", "serve"), 7),
     "is not 1 leading dense + whole periods"),
    (EIGHT, _set(("layer_pattern",), {"period": 0}), "layer_pattern"),
    (EIGHT, _set(("deployment",), None), "no deployment text"),
    (EIGHT, _reduced_without("vocab_size"),
     "is not depth plus the keys of share"),
    # the third toy: a divisor a key, what is held against the floors, the
    # leading dense layers counted once
    (SIXTEEN, _set(("share", "over", "vocab_size"), 5),
     "share.over.vocab_size 5 does not divide the 16 chips"),
    (SIXTEEN, _set(("share", "over", "vocab_size"), 32),
     "share.over.vocab_size 32 does not divide the 16 chips"),
    (SIXTEEN, _set(("share", "serve", "vocab_size"), 256),
     "share.serve.vocab_size 256 x 8 parts != the published 4096"),
    (SIXTEEN, _set(("share", "over"), None),
     "share.serve.vocab_size 512 x 16 chips != the published 4096"),
    (SIXTEEN, _both(_set(("share", "over", "vocab_size"), 16),
                    _set(("share", "serve", "vocab_size"), 256)),
     "share.serve.vocab_size holds 256 of 4096, under an eighth (512)"),
    (SIXTEEN, _both(_set(("model", "n_routed_experts"), 64),
                    _set(("share", "serve", "n_routed_experts"), 4)),
     "share.serve.n_routed_experts holds 4 experts, under 8"),
    (SIXTEEN, _set(("share", "over"), {"vocab_size": 8, "n_group": 8}),
     "share.over.n_group, but no role of share holds n_group"),
    (SIXTEEN, _set(("layer_pattern", "leading_dense"), 0),
     "layer_pattern.leading_dense 0 is not from 1 to the published "
     "first_k_dense_replace 3"),
    (SIXTEEN, _set(("layer_pattern", "leading_dense"), 4),
     "layer_pattern.leading_dense 4 is not from 1 to the published "
     "first_k_dense_replace 3"),
    (SIXTEEN, _set(("layer_pattern", "leading_key"), "n_dense"),
     "layer_pattern.leading_key 'n_dense' is no key of model"),
    (SIXTEEN, _reduced_without("first_k_dense_replace"),
     "is not depth plus the keys of share plus a leading_key that is cut"),
    (SIXTEEN, _both(_set(("layer_pattern", "leading_dense"), 3),
                    _set(("depth", "serve"), 7)),
     "is not depth plus the keys of share plus a leading_key that is cut"),
    (SIXTEEN, _set(("depth", "serve"), 4),
     "depth.serve 4 is under the floor 5 (1 leading dense"),
    (SIXTEEN, _set(("share",), None),
     "layer_pattern.leading_key, but the file has no share"),
], ids=lambda x: x if isinstance(x, str) else x[1] if isinstance(x, tuple)
    else "")
def test_a_share_that_breaks_a_rule_is_refused_with_its_sentence(
        tmp_path, toy, change, sentence):
    add, name = toy
    root = _copy(tmp_path)
    if add is _add_share_toy:
        _add_toy_cell(root)
    found = spec.validate(add(root, change), root)
    assert any(sentence in e for e in found), found
    assert all(name in e for e in found), found


def test_a_share_of_sixteen_that_holds_the_floors_passes(tmp_path):
    """What ``SHARE_CHIPS = (2, 8)`` refused until PR 38: 16 of 256 experts
    on 16 chips, with the vocabulary over 8 (the third toy as it stands),
    and the guide's own example, 8 of 256 on 32 chips."""
    root = _copy(tmp_path)
    assert spec.validate(_add_share16_toy(root), root) == []

    def thirty_two(bench, config):
        config["share"].update(chips=32, index=31)
        config["share"]["serve"]["n_routed_experts"] = 8
    root = _copy(tmp_path / "guide")
    assert spec.validate(_add_share16_toy(root, thirty_two), root) == []


def test_a_depth_of_three_stays_where_no_share_is_cut():
    """The floor on depth is a share's: the dense model trains at depth 3."""
    entry = next(c for c in BENCH["configs"] if "train" in spec.load_json(
        os.path.join(ROOT, c["file"]))["depth"])
    config = spec.load_json(os.path.join(ROOT, entry["file"]))
    assert config["depth"]["train"] < spec.DEPTH_FLOOR
    assert "share" not in config
    assert spec.validate_config(entry, config) == []


@pytest.mark.parametrize("rehearse", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_run_model_lays_only_what_the_cells_own_files_state(
        cell, rehearse, root=ROOT):
    """Published sizes, the role's depth, the rehearsal's sizes on top; with
    a ``share`` in the configuration's file what it holds over them and
    ``published`` / ``share`` beside them; and nothing else.  What is
    expected is read from the cell's OWN files, never from its name or its
    place (until PR 38 these cases said that NO cell has a share)."""
    c = spec.load_cell(cell, root)
    config = c.config
    role = "train" if c.kind == "train" else "serve"
    want = dict(config["model"])
    want["num_hidden_layers"] = int(config["depth"][role])
    cut = config.get("share")
    if cut is not None:
        want.update(cut.get(role, {}))
        pattern = config.get("layer_pattern", {})
        if "leading_key" in pattern:
            want[pattern["leading_key"]] = pattern["leading_dense"]
        want["published"] = {k: config["model"][k] for k in config["reduced"]}
        want["share"] = {k: cut[k] for k in ("chips", "index", "over")
                         if k in cut}
    if rehearse:
        want.update(config.get("rehearsal_model", {}))
    got = _a_run(c, rehearse).model
    assert got == want and list(got) == list(want)
    assert json.dumps(got) == json.dumps(want)
    if cut is None:
        assert "published" not in got and "share" not in got
    else:
        assert set(got["published"]) == set(config["reduced"])
        for key, held in cut.get(role, {}).items():
            parts = cut.get("over", {}).get(key, cut["chips"])
            assert held * parts == got["published"][key], key


@pytest.mark.parametrize("key", sorted(RECORDED_MODELS))
def test_run_model_of_the_files_the_benchmark_has_is_the_parents(key):
    """``Run.model`` of every cell PR 38 found, without and with
    ``--rehearse 1``, as recorded from its parent commit before ``core.py``
    was edited: ``json.dumps`` equal, so key order too."""
    cell, rehearse = key.split("|rehearse=")
    got = _a_run(spec.load_cell(cell, ROOT), int(rehearse)).model
    assert json.dumps(got) == RECORDED_MODELS[key]


def test_a_cell_names_registry_series_of_its_own(tmp_path, root=ROOT):
    """``reports.registry_series`` of a cell's file: the drivers snapshot
    those too, so a later configuration's counters need no driver edit.
    Every cell gets the series its OWN file names and none more.  (The toy's
    series is named ``t9...``: the registry is the process's, and
    ``tests/test_sentinel.py`` holds every family a worker has made to the
    catalog, throwaway ``t<digit>`` names apart.)"""
    from chipbench.harness import registry
    always = ("serving.queue_wait_ms", "serving.batch_occupancy")
    for w in spec.benchmark(root)["workloads"]:
        cell = spec.load_cell(w["name"], root)
        own = tuple(cell.extras["reports"].get("registry_series", ()))
        assert registry.series_of(cell, always) == always + own, w["name"]
    toys = _copy(tmp_path)
    _add_toy_cell(toys)
    _add_share_toy(toys)
    toy = spec.load_cell("toy-share-burst", toys)
    series = registry.series_of(toy, always)
    assert series == always + ("t9toy.expert_load",)
    before = {s: registry.snap(s) for s in series}
    h = registry.histogram("t9toy.expert_load")
    for x in (3.0, 5.0, 10.0):
        h.observe(x)
    window = {s: registry.delta(before[s], registry.snap(s)) for s in series}
    assert window["t9toy.expert_load"]["count"] == 3
    assert registry.mean(window["t9toy.expert_load"]) == 6.0
    assert window["serving.queue_wait_ms"]["count"] == 0


def test_a_cell_added_after_the_last_breaks_no_other_cells_test(tmp_path):
    """PR 38.  On a copy of the tree a configuration with a share of its
    own kind, a mix, a cell that names a registry series, a driver and a
    per-layer entry are appended AFTER the last of each list (and, as the
    PR that adds a cell would, PERF.md's proposed start-up entries list the
    cell).  Every test under ``tests/chipbench/`` that reads BENCHMARK.json
    and states something of a cell's place, share or series is then run
    against the copy, the functions themselves and not a restatement: none
    holds an entry to the end of its list, none names a cell to say what
    another lacks."""
    import test_chipbench_cohere2_moe as cohere
    import test_chipbench_falcon_h1 as falcon
    import test_chipbench_sarvam_mla as sarvam
    import test_chipbench_startup as startup
    root = _copy(tmp_path)
    bench = _add_share16_toy(root)
    assert (bench["configs"][-1]["name"], bench["workloads"][-1]["name"],
            bench["per_layer"][-1]["name"]) == (
        "toy-moe-share16", TOY16_CELL, "held_experts.replay")
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    last = '"%s"]}' % CELLS[-1]
    assert perf.count(last) == len(startup.READERS)
    with open(os.path.join(root, "PERF.md"), "w") as f:
        f.write(perf.replace(last, '"%s", "%s"]}' % (CELLS[-1], TOY16_CELL)))
    cells = [w["name"] for w in bench["workloads"]]
    assert cells == CELLS + [TOY16_CELL]

    assert spec.validate(bench, root) == []
    for cell in cells:
        test_every_cell_and_every_file_it_names_loads(cell, root)
        for rehearse in (0, 1):
            test_run_model_lays_only_what_the_cells_own_files_state(
                cell, rehearse, root)
            cohere.test_run_model_with_and_without_a_share(
                cell, rehearse, root)
    for m in bench["per_layer"]:
        assert callable(spec.load_module(root, "layer_metrics",
                                         m["name"]).read)
    test_a_cell_names_registry_series_of_its_own(tmp_path / "series", root)
    cohere.test_the_cells_own_registry_series_are_read_over_the_window(root)
    for module in (cohere, falcon, sarvam):
        module.test_spec_validate_is_empty_with_the_new_files(root)
    for metric in startup.READERS:
        startup.test_a_reader_file_is_what_its_proposed_entry_says(
            metric, root)
    startup.test_the_proposed_entries_would_pass_the_benchmarks_own_check(
        root)
    # and the grep ISSUE 38 asks for: no index from the end of a list
    for name in ("falcon_h1", "sarvam_mla", "cohere2_moe"):
        with open(os.path.join(HERE, f"test_chipbench_{name}.py")) as f:
            assert not re.search(r"bench\[[^\]]*\]\[-\d", f.read()), name


def test_an_unknown_cell_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell", ROOT)
