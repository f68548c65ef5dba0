"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

A later PR adds a configuration, a mix, a cell, a driver kind or a per-layer
metric as new files plus entries; nothing here knows any of their names.

A configuration's file (``configs/<name>.json``): ``model`` holds the
source's own keys, verbatim (published counts and widths); ``depth`` the
layers run for each role (``published``, ``serve``, ``train``); ``reduced``
a sentence for every key that differs from the source, the same keys as the
entry's ``reduced`` in ``BENCHMARK.json``; ``deployment`` what it stands for.

A configuration cut to ONE CHIP'S SHARE of a stated deployment adds

    "share": {"chips": <n that share each layer>, "index": <which, 0-based>,
              "how": "<expert parallel / vocabulary parallel ..., in words>",
              "over": {<key>: <d>, ...},                (absent: every key
                                                         over ``chips``)
              "serve": {<key of model>: <held here>, ...}, "train": {...}}
    "layer_pattern": {"period": <layers in one period>,
                      "leading_dense": <count as run>,  (absent: 1 and 0)
                      "leading_key": <key of model>}    (absent: none)

with a role only where ``depth`` has it.  ``chips`` is the number that share
a layer, not the cell's ``chips``: a whole number of at least 2, bounded by
what is HELD and by nothing else (the ``model-configs`` guide's floors):

1. A held key is one of ``d`` equal parts of the published count (held x d
   = published), never a width.  ``d`` is ``chips``, or ``over[key]`` where
   the file states it: a divisor of ``chips`` (1 <= d <= chips), so each
   part is held by ``chips / d`` chips alike.  ``index`` is the place among
   the ``chips``; of a key divided over ``d`` the part held is number
   ``index % d``.  The harness computes no offset: the family's program and
   reference do, from ``share`` as ``Run.model`` hands it to them.
2. A key with ``expert`` in its name holds at least 8 (``EXPERTS_HELD_FLOOR``);
   every other held key holds at least an eighth of the published count.
3. ``leading_dense`` layers open the stack; the depth keeps them and whole
   periods, at least four layers after them.  With ``leading_key`` the source
   publishes their number under that key of ``model`` and ``leading_dense``
   is the number AS RUN (at least 1, at most the published one: leading dense
   layers count once); ``Run.model`` lays it over the key.
4. ``reduced`` is then exactly ``num_hidden_layers`` (where depth is cut),
   the keys of ``share[role]``, and ``leading_key`` where 3 cut it.

``Run.model`` (``core.py``) lays ``share[role]`` over the published sizes
and puts the source's values of every reduced key beside them under
``published``, and ``chips``, ``index`` and (where stated) ``over`` under
``share``.  The guide's example: 256 experts over 32 chips, 8 held (``chips``
32, the vocabulary ``over`` 8).  A share of 16 that no chip fits as one of 8
(256 experts of a width that puts a whole expert layer at 23 GB):

    "model": {..., "n_routed_experts": 256, "vocab_size": 4096,
              "first_k_dense_replace": 3, "num_hidden_layers": 61},
    "depth": {"published": 61, "serve": 5},
    "layer_pattern": {"period": 1, "leading_dense": 1,
                      "leading_key": "first_k_dense_replace"},
    "share": {"chips": 16, "index": 5, "how": "...", "over": {"vocab_size": 8},
              "serve": {"n_routed_experts": 16, "vocab_size": 512}},
    "reduced": {"num_hidden_layers": ..., "n_routed_experts": ...,
                "vocab_size": ..., "first_k_dense_replace": ...}

holds experts [16 x 5, 16 x 5 + 16) and part 5 % 8 of the vocabulary, and
runs 1 + 4 layers (``tests/chipbench/test_chipbench_spec.py``'s third toy).
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
# a key that may never be in ``reduced``: a width, not a count held here
WIDTH_RE = re.compile(r"(_dim|_rank|_size|_per_tok|_width)$|window|top_?k"
                      r"|expand")
ROLES = ("serve", "train")
SHARE_CHIPS_LEAST = 2
EXPERTS_HELD_FLOOR = 8         # the guide's floors are on what is HELD:
HELD_PARTS_MOST = 8            # experts by count, any other key by its eighth
DEPTH_FLOOR = 4                # layers after the leading dense ones


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


def is_width(key: str) -> bool:
    return key != "vocab_size" and bool(WIDTH_RE.search(key))


def _whole(x, least: int) -> bool:
    return isinstance(x, int) and not isinstance(x, bool) and x >= least


def validate_config(entry: dict, config: dict) -> list:
    """What a configuration's file breaches, as sentences: ``reduced``
    against its entry in ``BENCHMARK.json``, and a chip's share (the
    module's docstring) against the floors that keep it the model."""
    who = f"config {entry['name']}"
    bad = []
    reduced = set(config.get("reduced", {}))
    if reduced != set(entry["reduced"]):
        bad.append(f"{who}: reduced of the file {sorted(reduced)} differs "
                   f"from BENCHMARK.json's {sorted(entry['reduced'])}")
    bad += [f"{who}: reduced names a width {k}"
            for k in sorted(reduced - set(entry["reduced"])) if is_width(k)]
    if not str(config.get("deployment", "")).strip():
        bad.append(f"{who}: the file has no deployment text")
    share = config.get("share")
    if share is None:
        if "leading_key" in config.get("layer_pattern", {}):
            bad.append(f"{who}: layer_pattern.leading_key, but the file has "
                       "no share to publish the source's count beside")
        return bad
    model, depth = config.get("model", {}), config.get("depth", {})
    chips = share.get("chips")
    if not _whole(chips, SHARE_CHIPS_LEAST):
        bad.append(f"{who}: share.chips {chips!r} is not a whole number of "
                   f"at least {SHARE_CHIPS_LEAST}")
        return bad
    if not _whole(share.get("index"), 0) or share["index"] >= chips:
        bad.append(f"{who}: share.index {share.get('index')!r} is not one "
                   f"of the {chips} chips")
    if not str(share.get("how", "")).strip():
        bad.append(f"{who}: share.how does not say how a layer is divided")
    over = share.get("over", {})
    for key, d in over.items():
        if not _whole(d, 1) or d > chips or chips % d:
            bad.append(f"{who}: share.over.{key} {d!r} does not divide the "
                       f"{chips} chips")
    roles = [k for k in share if k not in ("chips", "index", "how", "over")]
    held_keys = set()
    for role in roles:
        if role not in ROLES or role not in depth:
            bad.append(f"{who}: share.{role}, but depth has no {role!r}")
            continue
        for key, held in share[role].items():
            held_keys.add(key)
            parts = over.get(key, chips)
            if key not in model:
                bad.append(f"{who}: share.{role}.{key} is no key of model")
            elif not _whole(parts, 1):
                continue                    # said above, of share.over
            elif not _whole(held, 1) or held * parts != model[key]:
                bad.append(f"{who}: share.{role}.{key} {held!r} x {parts} "
                           f"{'parts' if key in over else 'chips'} != the "
                           f"published {model[key]!r}")
            elif "expert" in key and held < EXPERTS_HELD_FLOOR:
                bad.append(f"{who}: share.{role}.{key} holds {held} "
                           f"experts, under {EXPERTS_HELD_FLOOR}")
            elif "expert" not in key and held * HELD_PARTS_MOST < model[key]:
                bad.append(f"{who}: share.{role}.{key} holds {held} of "
                           f"{model[key]}, under an eighth "
                           f"({model[key] / HELD_PARTS_MOST:g})")
    bad += [f"{who}: share.over.{key}, but no role of share holds {key}"
            for key in sorted(set(over) - held_keys)]
    pattern = config.get("layer_pattern", {})
    period = pattern.get("period", 1)
    dense = pattern.get("leading_dense", 0)
    leading_key = pattern.get("leading_key")
    if not _whole(period, 1) or not _whole(dense, 0):
        bad.append(f"{who}: layer_pattern {pattern!r}")
        return bad
    cut_keys = set()
    if leading_key is not None:
        if not _whole(model.get(leading_key), 1):
            bad.append(f"{who}: layer_pattern.leading_key {leading_key!r} "
                       "is no key of model that counts layers")
        elif not 1 <= dense <= model[leading_key]:
            bad.append(f"{who}: layer_pattern.leading_dense {dense} is not "
                       f"from 1 to the published {leading_key} "
                       f"{model[leading_key]}")
        elif dense != model[leading_key]:
            cut_keys.add(leading_key)
    cut = any(depth.get(r) != depth.get("published")
              for r in ROLES if r in depth)
    want = held_keys | cut_keys | ({"num_hidden_layers"} if cut else set())
    if reduced != want:
        bad.append(f"{who}: reduced {sorted(reduced)} is not depth plus "
                   f"the keys of share plus a leading_key that is cut: "
                   f"{sorted(want)}")
    floor = dense + max(DEPTH_FLOOR, period)
    for role in (r for r in ROLES if r in depth):
        d = depth[role]
        if not _whole(d, floor):
            bad.append(f"{who}: depth.{role} {d!r} is under the floor "
                       f"{floor} ({dense} leading dense + max({DEPTH_FLOOR}, "
                       f"a period of {period}))")
        elif (d - dense) % period:
            bad.append(f"{who}: depth.{role} {d} is not {dense} leading "
                       f"dense + whole periods of {period}")
    return bad


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
        for k in c["reduced"]:
            name_ok(f"config {c['name']} reduced", k)
            if is_width(k):
                bad.append(f"config {c['name']}: reduced names a width {k}")
        try:
            bad += validate_config(c, load_json(os.path.join(root,
                                                             c["file"])))
        except SpecError as e:
            bad.append(f"config {c['name']}: {e}")
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
