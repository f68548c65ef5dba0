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


def test_a_width_in_reduced_is_found():
    b = json.loads(json.dumps(BENCH))
    b["configs"][0]["reduced"].append("intermediate_size")
    assert any("names a width" in e for e in spec.validate(b, ROOT))


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
    # widths are the published ones: only depth differs from the source
    assert set(c.config["reduced"]) == {"num_hidden_layers"}
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


def test_a_cell_a_mix_a_config_a_driver_and_a_metric_are_added_as_files(
        tmp_path):
    """New files plus entries, and no edit to a file that is there."""
    root = _copy(tmp_path)
    before = {}
    for base, _, files in os.walk(os.path.join(root, "chipbench")):
        for f in files:
            p = os.path.join(base, f)
            before[p] = open(p, "rb").read()
    cb = os.path.join(root, "chipbench")
    json.dump({"name": "toy-1b", "source": "https://example.org/toy",
               "family": "llama", "model": {"hidden_size": 8},
               "depth": {"published": 2, "serve": 2}, "reduced": {},
               "assumed": {}, "deployment": "a test"},
              open(os.path.join(cb, "configs", "toy-1b.json"), "w"))
    json.dump({"name": "chat-burst", "kind": "replay", "schedule_seed": 5,
               "arrivals": {"process": "fixed", "rate_rps": 3.0,
                            "horizon_s": 10.0},
               "prompt_len": {"dist": "fixed", "value": 9},
               "output_len": {"dist": "fixed", "value": 3}},
              open(os.path.join(cb, "traffic", "chat-burst.json"), "w"))
    json.dump({"name": "toy-chat-burst", "config": "toy-1b",
               "traffic": "chat-burst", "chips": 1, "why": "a test",
               "reports": {}, "limits": {"x": {"limit": 1}}},
              open(os.path.join(cb, "workloads", "toy-chat-burst.json"), "w"))
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
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

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
    # an old cell still loads, and nothing that was there has changed
    assert spec.load_cell(CELLS[0], root).name == CELLS[0]
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def test_an_unknown_cell_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell", ROOT)
