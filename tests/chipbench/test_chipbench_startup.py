"""The seven start-up readers (ISSUE 36) over logs recorded on the chip and
over a hand-made one: nested phases subtracted once, records after
``setup_s`` left out, the six time readers adding up to ``setup_s``, a
program without a log giving None."""

import json
import os
import re
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import spec, startup as st  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TIMES = ["setup_import_s", "setup_model_init_s", "setup_build_s",
         "setup_lower_s", "setup_compile_s", "setup_outside_program_s"]
READERS = TIMES + ["setup_compiled_programs"]
CELLS = ["mistral7b-chat-steady", "mistral7b-train-4k",
         "mixtral8x7b-batch-docs", "mistral7b-train-dp2mp2",
         "commandaplus-batch-longdocs", "sarvam105b-batch-docs16k",
         "falconh1-batch-generate"]


def read(metric, run):
    return spec.load_module(ROOT, "layer_metrics", metric).read(run)


def rec(name, start, end, depth=0, thread="MainThread", jit=None, **args):
    return {"name": name, "args": args, "start_age_s": float(start),
            "dur_s": None if end is None else float(end - start),
            "thread": thread, "depth": depth, "jit": jit or {}}


@pytest.fixture
def handmade():
    """setup_s = 40.  Import [2, 3]; model_init [5, 15]; engine_build
    [16, 20] holding stack_params [16, 18], pool_alloc [18, 19] and a pool
    program's first call [19, 19.5] (0.25 s of it the backend's); the server's
    warm-up [21, 35] on the engine's thread holding two programs: [22, 28]
    = lower [22, 25] + compile [25, 27.75] and [28, 33] = lower [28, 30] +
    compile [30, 33]; after ready: a program [41, 44] (the reference's);
    straddling ready: [39, 42]; still open: [39.5, ...]."""
    eng = "serving-engine"
    return SimpleNamespace(setup_s=40.0, startup=[
        rec("startup.import", 2, 3, began_age_s=2.0),
        rec("startup.model_init", 5, 15, family="llama", layers=12,
            params=3_000_000_000),
        rec("startup.engine_build", 16, 20, slots=32, pages=100,
            pool_bytes=1 << 30),
        rec("startup.stack_params", 16, 18, depth=1),
        rec("startup.pool_alloc", 18, 19, depth=1, pages=100),
        rec("startup.program", 19, 19.5, depth=1, program="jit_pool_cow_copy",
            cache_hit=True, cache_read_s=0.125,
            jit={"trace_n": 3, "trace_s": 0.4, "lower_n": 1, "lower_s": 0.1,
                 "compile_n": 1, "compile_s": 0.25, "cache_read_n": 1,
                 "cache_read_s": 0.125}),
        rec("startup.warm", 21, 35, thread=eng),
        rec("startup.program", 22, 28, depth=1, thread=eng,
            program="jit_serve_step_T64", T=64, rows=512, cache_hit=False,
            compile_s=2.5),
        rec("startup.lower", 22, 25, depth=2, thread=eng),
        rec("startup.compile", 25, 27.75, depth=2, thread=eng,
            cache_hit=False, compile_s=2.5),
        rec("startup.program", 28, 33, depth=1, thread=eng,
            program="jit_serve_step_T1", T=1, rows=32, cache_hit=True,
            cache_read_s=1.0),
        rec("startup.lower", 28, 30, depth=2, thread=eng),
        rec("startup.compile", 30, 33, depth=2, thread=eng, cache_hit=True,
            cache_read_s=1.0),
        rec("startup.program", 39, 42, program="jit_straddles",
            cache_hit=False, compile_s=1.0),
        rec("startup.model_init", 39.5, None, family="llama"),
        rec("startup.program", 41, 44, program="jit_reference",
            cache_hit=False, compile_s=2.0)])


@pytest.mark.parametrize("metric,value", [
    ("setup_import_s", 1.0),
    ("setup_model_init_s", 10.0),
    # engine_build 4 - the pool program's 0.5, warm 14 - the programs' 11
    ("setup_build_s", 3.5 + 3.0),
    # two lowerings, and the first call's span without the backend's 0.25
    ("setup_lower_s", 3.0 + 2.0 + 0.25),
    # two compiles, the 0.25 s a program holds outside its children, and
    # the backend's part of the first call
    ("setup_compile_s", 2.75 + 3.0 + 0.25 + 0.25),
    # 40 - (1 + 10 + 4 + 14)
    ("setup_outside_program_s", 11.0),
    ("setup_compiled_programs", 1)])
def test_each_reader_over_the_hand_made_log(handmade, metric, value):
    assert read(metric, handmade) == pytest.approx(value, abs=1e-9)


def test_nested_phases_are_subtracted_once_and_the_six_add_up(handmade):
    parts = st.split(handmade)
    assert sum(parts.values()) == pytest.approx(handmade.setup_s, abs=1e-9)
    assert list(parts) == [m[len("setup_"):-len("_s")] for m in TIMES]
    own = st.self_times(st.before_ready(handmade))
    assert sum(own) == pytest.approx(1 + 10 + 4 + 14)


def test_records_after_ready_or_still_open_are_left_out(handmade):
    kept = st.before_ready(handmade)
    assert len(kept) == 13 and all(
        r["start_age_s"] + r["dur_s"] <= 40.0 for r in kept)
    assert st.compiled_programs(handmade) == ["jit_serve_step_T64"]
    later = SimpleNamespace(setup_s=45.0, startup=handmade.startup)
    assert st.compiled_programs(later) == [
        "jit_serve_step_T64", "jit_straddles", "jit_reference"]
    assert read("setup_outside_program_s", later) == pytest.approx(
        45 - 29 - 5)           # [39, 42] and [41, 44] are one interval


def test_overlapping_threads_count_an_instant_once():
    run = SimpleNamespace(setup_s=10.0, startup=[
        rec("startup.engine_build", 1, 6),
        rec("startup.warm", 4, 9, thread="serving-engine")])
    assert read("setup_build_s", run) == pytest.approx(8.0)
    assert read("setup_outside_program_s", run) == pytest.approx(2.0)


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_a_log_gives_none(metric, monkeypatch):
    assert read(metric, SimpleNamespace(setup_s=20.0, startup=[])) is None
    assert read(metric, SimpleNamespace(
        setup_s=None, startup=[rec("startup.import", 1, 2)])) is None
    # the parent's tree: no such module
    import paddle_tpu.observability as obs
    monkeypatch.delattr(obs, "startup")
    monkeypatch.setitem(sys.modules, "paddle_tpu.observability.startup", None)
    assert read(metric, SimpleNamespace(setup_s=20.0)) is None


def test_without_records_handed_in_the_programs_own_log_is_read():
    from paddle_tpu.observability import startup
    age = startup.process_age_s()
    run = SimpleNamespace(setup_s=age)
    assert read("setup_import_s", run) == pytest.approx(
        startup.records()[0]["dur_s"])
    assert sum(read(m, run) for m in TIMES) == pytest.approx(age, abs=1e-6)


# ---------------------------------------------------------------------------
# logs recorded on the chip (my chip runs, PR 36: tests/chipbench/data)
# ---------------------------------------------------------------------------

RECORDED = ["chat_warm", "chat_cold", "dp2mp2_warm"]


def recorded(name):
    with open(os.path.join(DATA, f"recorded_startup_{name}.json")) as f:
        d = json.load(f)
    return SimpleNamespace(setup_s=d["setup_s"], startup=d["records"]), d


@pytest.mark.parametrize("name", RECORDED)
@pytest.mark.parametrize("metric", READERS)
def test_each_reader_over_a_recorded_log(name, metric):
    run, d = recorded(name)
    value = read(metric, run)
    assert value == pytest.approx(d["expect"][metric], abs=1e-6)
    assert value >= 0


@pytest.mark.parametrize("name", RECORDED)
def test_the_six_time_readers_add_up_to_setup_s(name):
    run, d = recorded(name)
    assert sum(read(m, run) for m in TIMES) == pytest.approx(
        d["setup_s"], abs=1e-3)
    assert d["device"]["platform"] == "tpu"


def test_a_warm_start_compiled_nothing_and_a_cold_one_every_program():
    warm, _ = recorded("chat_warm")
    cold, _ = recorded("chat_cold")
    programs = [r["args"]["program"] for r in st.before_ready(cold)
                if r["name"] == "startup.program"]
    assert st.compiled_programs(warm) == []
    assert st.compiled_programs(cold) == programs and len(programs) == 3
    assert read("setup_compile_s", cold) > 5 * read("setup_compile_s", warm)
    # tracing and lowering are paid on every start, cache hit or not
    assert read("setup_lower_s", warm) == pytest.approx(
        read("setup_lower_s", cold), rel=0.5)


def test_the_train_steps_first_call_is_split_by_the_backends_seconds():
    run, _ = recorded("dp2mp2_warm")
    (prog,) = [r for r in st.before_ready(run)
               if r["name"] == "startup.program"]
    assert prog["args"]["program"] == "jit_pretrain_step"
    backend = min(prog["dur_s"], prog["jit"]["compile_s"])
    assert read("setup_compile_s", run) == pytest.approx(backend)
    assert read("setup_lower_s", run) == pytest.approx(
        prog["dur_s"] - backend)


# ---------------------------------------------------------------------------
# the entries PERF.md proposes (section 7), ready for a `benchmark` issue
# ---------------------------------------------------------------------------

def proposed(root=ROOT):
    text = open(os.path.join(root, "PERF.md")).read()
    block = re.search(r"<!-- startup-readers -->\n```json\n(.*?)\n```", text,
                      re.S)
    assert block, "PERF.md section 7 holds the entries between the markers"
    return {e["name"]: e for e in json.loads(block.group(1))}


@pytest.mark.parametrize("metric", READERS)
def test_a_reader_file_is_what_its_proposed_entry_says(metric, root=ROOT):
    entry = proposed(root)[metric]
    mod = spec.load_module(root, "layer_metrics", metric)
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"])
    assert (entry["layer"], entry["moves"], entry["source"],
            entry["better"]) == ("start-up", "setup_s", "program_span",
                                 "lower")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # every cell the benchmark has, those of PR 36 among them: a PR that
    # adds a cell adds it to PERF.md's entries, not to this file
    assert entry["workloads"] == [
        w["name"] for w in spec.benchmark(root)["workloads"]]
    assert set(CELLS) <= set(entry["workloads"])
    assert mod.SOURCE in spec.SOURCES and spec.NAME_RE.match(metric)
    assert "TRACE_ONLY" not in vars(mod)


def test_the_proposed_entries_would_pass_the_benchmarks_own_check(root=ROOT):
    bench = json.loads(json.dumps(spec.benchmark(root)))
    assert spec.validate(bench, root) == []         # as it stands
    bench["per_layer"] += list(proposed(root).values())
    assert len(proposed(root)) == 7 and len(bench["per_layer"]) <= 128
    assert spec.validate(bench, root) == []
