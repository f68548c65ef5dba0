"""BENCHMARK.json and the files it names: the contract's rules that can be
seen without a run, and that the harness is driven by data."""

import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import spec  # noqa: E402

BENCH = spec.benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["per_layer"]]


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
def test_every_cell_and_every_file_it_names_loads(cell):
    c = spec.load_cell(cell, ROOT)
    assert c.kind in ("open_loop_serve", "closed_loop_serve", "train")
    assert os.path.exists(os.path.join(ROOT, "chipbench", "drivers",
                                       c.kind + ".py"))
    assert os.path.exists(os.path.join(
        ROOT, "chipbench", "references", c.config["family"] + ".py"))
    assert os.path.exists(os.path.join(
        ROOT, "chipbench", "programs", c.config["family"] + ".py"))
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
    entry = next(e for e in BENCH["configs"] if e["name"] == c.config_name)
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
               "registry_series": ["toy.expert_load"]}, "limits": {}},
          cb, "workloads", "toy-share-burst.json")
    for sub in ("programs", "references"):
        with open(os.path.join(cb, sub, "toymoe.py"), "w") as f:
            f.write(FAMILY)
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
    bench = _add_share_toy(root)

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


def _fewer_experts(bench, config):
    config["model"]["num_experts"] = 32
    config["share"]["serve"]["num_experts"] = 4


def _sixteen_chips(bench, config):
    config["share"].update(chips=16, serve={"num_experts": 4,
                                            "vocab_size": 256})


def _reduced_without_the_vocabulary(bench, config):
    bench["configs"][-1]["reduced"].remove("vocab_size")
    del config["reduced"]["vocab_size"]


@pytest.mark.parametrize("change,sentence", [
    (lambda b, c: _reduced("moe_intermediate_size", b, c),
     "reduced names a width moe_intermediate_size"),
    (lambda b, c: _reduced("sliding_window", b, c),
     "reduced names a width sliding_window"),
    (lambda b, c: b["configs"][-1]["reduced"].remove("vocab_size"),
     "differs from BENCHMARK.json's"),
    (lambda b, c: c["reduced"].pop("num_experts"),
     "differs from BENCHMARK.json's"),
    (_set(("share", "serve", "n_group"), 1), "is no key of model"),
    (_set(("share", "serve", "num_experts"), 16),
     "16 x 8 chips != the published 64"),
    (_set(("share", "serve", "vocab_size"), 1024),
     "1024 x 8 chips != the published 4096"),
    (_fewer_experts, "holds 4 experts, under 8"),
    (_sixteen_chips, "share.chips 16 is not a whole number from 2 to 8"),
    (_set(("share", "chips"), 1), "share.chips 1 is not"),
    (_set(("share", "index"), 8), "share.index 8 is not one of the 8"),
    (_set(("share", "how"), ""), "share.how does not say"),
    (_set(("share", "train"), {"num_experts": 8}), "depth has no 'train'"),
    (_set(("depth", "serve"), 3), "depth.serve 3 is under the floor 5"),
    (_set(("depth", "serve"), 7), "is not 1 leading dense + whole periods"),
    (_set(("layer_pattern",), {"period": 0}), "layer_pattern"),
    (_set(("deployment",), None), "no deployment text"),
    (_reduced_without_the_vocabulary,
     "is not depth plus the keys of share"),
], ids=lambda x: x if isinstance(x, str) else "")
def test_a_share_that_breaks_a_rule_is_refused_with_its_sentence(
        tmp_path, change, sentence):
    root = _copy(tmp_path)
    _add_toy_cell(root)
    found = spec.validate(_add_share_toy(root, change), root)
    assert any(sentence in e for e in found), found
    assert all("toy-moe-share" in e for e in found), found


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
def test_run_model_of_a_configuration_without_a_share_is_as_before(
        cell, rehearse):
    """Published sizes, the role's depth, the rehearsal's sizes on top: and
    nothing else, for the configurations the benchmark has."""
    c = spec.load_cell(cell, ROOT)
    assert "share" not in c.config
    want = dict(c.config["model"])
    want["num_hidden_layers"] = int(
        c.config["depth"]["train" if c.kind == "train" else "serve"])
    if rehearse:
        want.update(c.config.get("rehearsal_model", {}))
    got = _a_run(c, rehearse).model
    assert got == want and list(got) == list(want)
    assert json.dumps(got) == json.dumps(want)
    assert "published" not in got and "share" not in got


def test_a_cell_names_registry_series_of_its_own(tmp_path):
    """``reports.registry_series`` of a cell's file: the drivers snapshot
    those too, so a later configuration's counters need no driver edit."""
    from chipbench.harness import registry
    root = _copy(tmp_path)
    _add_toy_cell(root)
    _add_share_toy(root)
    always = ("serving.queue_wait_ms", "serving.batch_occupancy")
    toy = spec.load_cell("toy-share-burst", root)
    series = registry.series_of(toy, always)
    assert series == always + ("toy.expert_load",)
    for cell in CELLS:                   # absent: none more
        assert registry.series_of(spec.load_cell(cell, ROOT),
                                  always) == always
    before = {s: registry.snap(s) for s in series}
    h = registry.histogram("toy.expert_load")
    for x in (3.0, 5.0, 10.0):
        h.observe(x)
    window = {s: registry.delta(before[s], registry.snap(s)) for s in series}
    assert window["toy.expert_load"]["count"] == 3
    assert registry.mean(window["toy.expert_load"]) == 6.0
    assert window["serving.queue_wait_ms"]["count"] == 0


def test_an_unknown_cell_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell", ROOT)
