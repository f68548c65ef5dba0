"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

A later PR adds a configuration, a mix, a cell, a driver kind or a per-layer
metric as new files plus entries; nothing here knows any of their names.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"missing file {path}") from e
    except json.JSONDecodeError as e:
        raise SpecError(f"{path} is not JSON: {e}") from e


def load_module(root: str, subdir: str, name: str):
    """``<root>/chipbench/<subdir>/<name>.py`` as a module.  Loaded by
    path, because a metric's name may hold a dot."""
    path = os.path.join(root, "chipbench", subdir, name + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no {subdir} file for {name!r}: {path}")
    mod_name = "chipbench_%s_%s" % (subdir, re.sub(r"\W", "_", name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    extras: dict                       # the cell's own file: limits, notes
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)
    root: str = ROOT

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    def limit(self, name: str):
        try:
            return self.extras["limits"][name]["limit"]
        except KeyError as e:
            raise SpecError(f"cell {self.name}: no limit {name!r} in "
                            f"workloads/{self.name}.json") from e


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(
            f"no workload {name!r} in BENCHMARK.json (it has: "
            f"{[w['name'] for w in bench['workloads']]})")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {name}: no config {entry['config']!r}")
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(
        root, "chipbench", "traffic", entry["traffic"] + ".json"))
    extras = load_json(os.path.join(
        root, "chipbench", "workloads", name + ".json"))
    for key in ("config", "traffic", "chips"):
        if extras.get(key) != entry[key]:
            raise SpecError(
                f"workloads/{name}.json says {key}={extras.get(key)!r}, "
                f"BENCHMARK.json says {entry[key]!r}")
    return Cell(
        name=name, chips=int(entry["chips"]), why=entry["why"],
        config_name=entry["config"], traffic_name=entry["traffic"],
        config=config, traffic=traffic, extras=extras,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)


def validate(bench: dict, root: str = ROOT) -> list:
    """Every breach of the contract's rules on names, units, sources and
    files that can be seen without a run, as sentences."""
    bad = []

    def name_ok(what, s):
        if not isinstance(s, str) or not NAME_RE.match(s):
            bad.append(f"{what}: bad name {s!r}")

    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bench) != want:
        bad.append(f"keys {sorted(bench)} != {sorted(want)}")
        return bad
    for seen, group in (("config", bench["configs"]),
                        ("workload", bench["workloads"]),
                        ("metric", bench["end_to_end"] + bench["per_layer"])):
        names = [g["name"] for g in group]
        for n in names:
            name_ok(seen, n)
        if len(set(names)) != len(names):
            bad.append(f"duplicate {seen} names")
    paths = bench["paths"]
    for c in bench["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c['name']}: keys {sorted(c)}")
        if not any(c["file"].startswith(p + "/") for p in paths):
            bad.append(f"config {c['name']}: file outside paths")
        if not os.path.exists(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
        for k in c["reduced"]:
            name_ok(f"config {c['name']} reduced", k)
            if re.search(r"(_dim|_rank|hidden_size|intermediate_size|"
                         r"head_dim|experts_per_tok)$", k):
                bad.append(f"config {c['name']}: reduced names a width {k}")
    cfg_names = {c["name"] for c in bench["configs"]}
    pairs = set()
    four = 0
    for w in bench["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w['name']}: keys {sorted(w)}")
        name_ok("traffic", w["traffic"])
        if w["config"] not in cfg_names:
            bad.append(f"workload {w['name']}: unknown config")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        four += w["chips"] == 4
        if (w["config"], w["traffic"]) in pairs:
            bad.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] \
                or "\t" in w["why"]:
            bad.append(f"workload {w['name']}: why is not 1..200 chars")
    if four > max(1, len(bench["workloads"]) // 4):
        bad.append(f"{four} four-chip cells of {len(bench['workloads'])}")
    used = {w["config"] for w in bench["workloads"]}
    if used != cfg_names:
        bad.append(f"configs not used by a cell: {sorted(cfg_names - used)}")
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("no setup_s among end_to_end")
    for m in bench["end_to_end"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound",
                                      "source"}:
            bad.append(f"metric {m['name']}: keys {sorted(m)}")
        if m.get("source") not in ("host_clock", "device_trace"):
            bad.append(f"metric {m['name']}: source {m.get('source')!r}")
        if not 0 < m.get("bound", 0) <= 0.1:
            bad.append(f"metric {m['name']}: bound {m.get('bound')!r}")
    for m in bench["per_layer"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "source",
                                      "layer", "moves"}:
            bad.append(f"metric {m['name']}: keys {sorted(m)}")
        if m.get("source") not in SOURCES:
            bad.append(f"metric {m['name']}: source {m.get('source')!r}")
        if m.get("moves") not in e2e:
            bad.append(f"metric {m['name']}: moves {m.get('moves')!r}")
        if not os.path.exists(os.path.join(
                root, "chipbench", "layer_metrics", m["name"] + ".py")):
            bad.append(f"metric {m['name']}: no reader file")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT_RE.match(m.get("unit", "")):
            bad.append(f"metric {m['name']}: bad unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: better {m.get('better')!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"metric {m['name']}: unknown workload {w}")
    for w in cells:
        mine = [m for m in bench["end_to_end"] if _applies(m, w)]
        if len(mine) < 2:
            bad.append(f"workload {w}: reports no end-to-end metric "
                       "besides setup_s")
        if not any(_applies(m, w) for m in bench["per_layer"]):
            bad.append(f"workload {w}: reports no per-layer metric")
    for m in bench["per_layer"]:
        moved = next(x for x in bench["end_to_end"]
                     if x["name"] == m["moves"]) if m["moves"] in e2e else None
        if moved is None:
            continue
        for w in (m.get("workloads") or cells):
            if not _applies(moved, w):
                bad.append(f"metric {m['name']}: cell {w} does not report "
                           f"{m['moves']}")
    if not 1 <= bench["run_seconds"] <= 51:
        bad.append(f"run_seconds {bench['run_seconds']}")
    return bad
