"""The start-up log (ISSUE 36): set-up has phases, each a span and, until
the log is sealed, a record on the process's own age; jax's compile events
land in the innermost open phase; a warm step reaches none of the sites; the
step span's costlier arguments are computed only while somebody listens."""

import threading
import time
from collections import deque

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import startup
from paddle_tpu.observability.catalog import SPANS

BUCKET = 8
TRACE = "/jax/core/compile/jaxpr_trace_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"


@pytest.fixture
def log(monkeypatch):
    """A log of this test's own in the process-wide log's place (the
    listener and every site look ``startup.LOG`` up when they run)."""
    fresh = startup.StartupLog()
    monkeypatch.setattr(startup, "LOG", fresh)
    return fresh


def _tiny_engine(**kw):
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    return ContinuousBatchingEngine(
        model, max_batch=2, gen=GenerationConfig(max_new_tokens=6),
        max_seq_len=64, page_size=8, prefill_bucket=BUCKET, sync_every=4,
        **kw)


def _warm(eng):
    req = eng.submit(list(range(1, BUCKET + 4)), max_new_tokens=3)
    while not req.done:
        eng.step()
    eng.step()


def _end(rec):
    return rec["start_age_s"] + rec["dur_s"]


@pytest.fixture(scope="module")
def built():
    """One tiny engine built and warmed under a log of its own: (records,
    the process's age when the warm-up had ended)."""
    fresh, was = startup.StartupLog(), startup.LOG
    startup.LOG = fresh
    try:
        _warm(_tiny_engine())
        age = startup.process_age_s()
    finally:
        startup.LOG = was
    return fresh.records(), age


# ---------------------------------------------------------------------------
# building an engine and warming it
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,depth,count", [
    ("startup.model_init", 0, 1), ("startup.engine_build", 0, 1),
    ("startup.stack_params", 1, 1), ("startup.pool_alloc", 1, 1),
    ("startup.program", 0, 2), ("startup.lower", 1, 2),
    ("startup.compile", 1, 2)])
def test_a_build_and_its_warm_up_record_each_phase(built, name, depth, count):
    """Two families (T = bucket and T = 1) of one member each at this size:
    one ``startup.program`` a member."""
    recs, age = built
    mine = [r for r in recs if r["name"] == name]
    assert len(mine) == count
    for r in mine:
        assert r["depth"] == depth and r["thread"] == "MainThread"
        assert r["dur_s"] > 0 and 0 < r["start_age_s"] < _end(r) <= age
        assert name in SPANS


def test_the_records_stand_in_the_order_their_phases_opened(built):
    recs, _ = built
    assert [r["name"] for r in recs] == [
        "startup.model_init", "startup.engine_build", "startup.stack_params",
        "startup.pool_alloc"] + ["startup.program", "startup.lower",
                                 "startup.compile"] * 2
    starts = [r["start_age_s"] for r in recs]
    assert starts == sorted(starts)


def test_a_programs_lowering_and_compile_lie_inside_it(built):
    recs, _ = built
    build = next(r for r in recs if r["name"] == "startup.engine_build")
    for child in recs[2:4]:
        assert build["start_age_s"] <= child["start_age_s"] \
            and _end(child) <= _end(build)
    for i in (4, 7):
        prog, lower, comp = recs[i:i + 3]
        assert prog["start_age_s"] <= lower["start_age_s"] \
            and _end(lower) <= comp["start_age_s"] \
            and _end(comp) <= _end(prog)
        # tracing and lowering are the lower phase's, the backend's the
        # compile's, and the program holds no event of its own
        assert lower["jit"]["trace_n"] >= 1 and lower["jit"]["lower_n"] == 1
        assert comp["jit"]["compile_n"] == 1 and not prog["jit"]
        assert "compile_n" not in lower["jit"]
    assert [(r["args"]["program"], r["args"]["T"], r["args"]["rows"])
            for r in recs if r["name"] == "startup.program"] == [
        (f"jit_serve_step_T{BUCKET}", BUCKET, 2 * BUCKET),
        ("jit_serve_step_T1", 1, 2)]


def test_the_phases_arguments_are_what_the_catalog_says(built):
    recs, _ = built
    by = {r["name"]: r["args"] for r in recs}
    assert by["startup.model_init"] == {
        "family": "llama", "layers": 2, "params": sum(
            int(np.prod(p.shape))
            for p in LlamaForCausalLM(LlamaConfig.tiny()).parameters())}
    build = by["startup.engine_build"]
    assert (build["slots"], build["pages"]) == (2, 16)
    assert build["pool_bytes"] > 0 and by["startup.pool_alloc"] == {
        "pages": 16}
    for name, args in by.items():
        stated = {a.strip() for part in SPANS[name][3].split(",")
                  for a in part.split("|")}
        assert set(args) <= stated, (name, args)


# ---------------------------------------------------------------------------
# the persistent cache: read or compiled
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_dir(tmp_path):
    """The persistent cache in an empty directory, every program kept."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = [getattr(jax.config, n) for n in names]
    cc.reset_cache()
    for n, v in zip(names, (str(tmp_path), 0.0, 0)):
        jax.config.update(n, v)
    try:
        yield tmp_path
    finally:
        cc.reset_cache()
        for n, v in zip(names, was):
            jax.config.update(n, v)


@pytest.mark.parametrize("state", ["cold", "warm"])
def test_a_program_says_whether_the_cache_held_it(log, cache_dir, state):
    eng = _tiny_engine()
    eng._step_family(BUCKET)
    if state == "warm":         # the same programs, built again
        eng = _tiny_engine()
        eng._step_family(BUCKET)
    prog = [r for r in log.records() if r["name"] == "startup.program"][-1]
    comp = [r for r in log.records() if r["name"] == "startup.compile"][-1]
    hit = state == "warm"
    assert prog["args"]["cache_hit"] is hit and comp["args"]["cache_hit"] is hit
    key = "cache_read_s" if hit else "compile_s"
    assert comp["args"][key] > 0 and prog["args"][key] == comp["args"][key]
    assert ("cache_read_n" in comp["jit"]) is hit


# ---------------------------------------------------------------------------
# jax's events
# ---------------------------------------------------------------------------

def test_jit_events_land_in_the_innermost_open_phase(log):
    with startup.phase("startup.engine_build") as outer:
        obs._on_event_duration(TRACE, 0.25)
        with startup.compiling():
            obs._on_event_duration(COMPILE, 2.0)
            obs._on_event_duration(CACHE_READ, 0.5)
            obs._on_event_duration("/jax/some/other_duration", 9.0)
        obs._on_event_duration(TRACE, 0.5)
    build, comp = log.records()
    assert build["jit"] == {"trace_n": 2, "trace_s": 0.75}
    assert comp["jit"] == {"compile_n": 1, "compile_s": 2.0,
                           "cache_read_n": 1, "cache_read_s": 0.5}
    assert comp["args"] == {"cache_hit": True, "cache_read_s": 0.5}
    assert outer is not None and "cache_hit" not in build["args"]


def test_a_jit_event_outside_any_phase_lands_in_no_record(log):
    with startup.phase("startup.warm"):
        pass
    before = obs.backend_compiles()
    obs._on_event_duration(COMPILE, 1.0)
    assert obs.backend_compiles() == before + 1     # where it goes today
    assert [r["jit"] for r in log.records()] == [{}]


def test_an_event_on_another_thread_is_not_this_threads(log):
    seen = []

    def other():
        obs._on_event_duration(COMPILE, 1.0)
        with startup.phase("startup.warm"):
            obs._on_event_duration(TRACE, 0.125)
        seen.append(True)

    with startup.phase("startup.engine_build"):
        t = threading.Thread(target=other, name="serving-engine")
        t.start()
        t.join(30)
    assert seen and not t.is_alive()
    build, warm = log.records()
    assert build["jit"] == {} and warm["jit"] == {"trace_n": 1,
                                                  "trace_s": 0.125}
    assert (warm["thread"], warm["depth"]) == ("serving-engine", 0)


def test_a_first_call_says_compiled_when_a_module_missed_the_cache(log):
    with startup.program("jit_pretrain_step", T=16, rows=32):
        obs._on_event_duration(COMPILE, 0.25)
        obs._on_event_duration(CACHE_READ, 0.125)
        obs._on_event_duration(COMPILE, 3.0)            # no read: compiled
    (rec,) = log.records()
    assert rec["args"] == {"program": "jit_pretrain_step", "T": 16,
                           "rows": 32, "cache_hit": False, "compile_s": 3.25}


# ---------------------------------------------------------------------------
# the clock, the bound, the seal
# ---------------------------------------------------------------------------

def test_the_import_is_on_the_process_clock():
    rec = startup.LOG.records()[0]
    assert rec["name"] == "startup.import" and rec["depth"] == 0
    assert rec["args"]["began_age_s"] == rec["start_age_s"] >= 0
    assert 0 < rec["dur_s"] and _end(rec) < startup.process_age_s()
    # the age is the benchmark's: process start from /proc, within its ticks
    from chipbench.harness.core import process_age_s
    assert abs(process_age_s() - startup.process_age_s()) < 0.05
    t = time.perf_counter()
    assert startup.process_age_s(t + 1.0) - startup.process_age_s(t) == \
        pytest.approx(1.0)


def test_the_257th_record_is_counted_and_not_kept(log):
    for i in range(startup.MAX_RECORDS + 3):
        with startup.phase("startup.warm", i=i):
            pass
    recs = log.records()
    assert len(recs) == startup.MAX_RECORDS == 256 and log.overflow == 3
    assert recs[-1]["args"] == {"i": 255} and log.status()["overflow"] == 3


def test_an_open_phase_shows_without_a_duration(log):
    with startup.phase("startup.warm"):
        (rec,) = log.records()
        assert rec["dur_s"] is None and rec["start_age_s"] > 0
    assert log.records()[0]["dur_s"] > 0


def test_seal_stops_recording_and_keeps_the_spans(log):
    ring = deque(maxlen=64)
    obs.TRACER.attach_ring(ring)
    try:
        with startup.phase("startup.warm"):
            pass
        assert log.ready_age_s is None and not log.sealed
        startup.seal()
        sealed_at = log.ready_age_s
        with startup.program("jit_serve_step_T1", T=1, rows=2):
            obs._on_event_duration(COMPILE, 1.0)
        startup.seal()                                  # once
    finally:
        obs.TRACER.detach_ring()
    assert [r["name"] for r in log.records()] == ["startup.warm"]
    assert log.sealed and log.ready_age_s == sealed_at <= \
        startup.process_age_s()
    spans = [e for e in ring if e["ph"] == "X"]
    assert [e["name"] for e in spans] == ["startup.warm", "startup.program"]
    assert spans[1]["args"] == {"program": "jit_serve_step_T1", "T": 1,
                                "rows": 2}


def test_statusz_shows_the_block_sealed_at_ready(log):
    from paddle_tpu.serving import ServingServer
    srv = ServingServer(_tiny_engine(), model_name="tiny", warmup=True)
    srv.start()
    try:
        deadline = time.time() + 120
        while not srv.ready() and time.time() < deadline:
            time.sleep(0.02)
        assert srv.ready()
        block = srv.statusz()["startup"]
    finally:
        srv.close()
    assert block["sealed"] and block["overflow"] == 0
    names = [r["name"] for r in block["records"]]
    assert names[:4] == ["startup.model_init", "startup.engine_build",
                         "startup.stack_params", "startup.pool_alloc"]
    warm = block["records"][4]
    assert warm["name"] == "startup.warm" and \
        warm["thread"] == "serving-engine"
    programs = [r for r in block["records"] if r["name"] == "startup.program"]
    assert len(programs) == 2 and {r["depth"] for r in programs} == {1}
    assert all(_end(r) <= block["ready_age_s"] for r in block["records"])
    import json
    json.dumps(block)                   # /statusz is JSON


# ---------------------------------------------------------------------------
# after set-up: nothing
# ---------------------------------------------------------------------------

def test_a_warm_step_opens_no_startup_span_and_appends_no_record(log):
    eng = _tiny_engine()
    _warm(eng)
    n = len(log.records())
    ring = deque(maxlen=4096)
    obs.TRACER.attach_ring(ring)
    try:
        for i in range(3):
            eng.submit(list(range(1, 12 + i)), max_new_tokens=5)
        with obs.assert_overhead(max_compiles=0, max_syncs=20):
            for _ in range(20):
                eng.step()
    finally:
        obs.TRACER.detach_ring()
    counts = {name: sum(e["name"] == name for e in ring) for name in SPANS}
    assert counts["engine.step"] == 20 and counts["engine.dispatch"] > 10
    assert not any(c for name, c in counts.items()
                   if name.startswith("startup."))
    assert len(log.records()) == n and not log.sealed


@pytest.mark.parametrize("call", ["first", "second"])
def test_a_train_steps_first_call_is_a_program_and_no_later_one(log, call):
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep
    ps = PretrainStep(LlamaConfig.tiny(), ParallelConfig())
    state = ps.init_state(seed=0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (2, 16)).astype(np.int32)
    state, loss = ps.train_step(state, ids, ids)
    build = [r for r in log.records() if r["name"] == "startup.train_build"]
    assert [r["args"] for r in build] == [{"dp": 1, "mp": 1, "layers": 2}]
    n = len(log.records())
    if call == "second":
        state, loss = ps.train_step(state, ids, ids)
        jax.block_until_ready(loss)
        assert len(log.records()) == n
    (prog,) = [r for r in log.records() if r["name"] == "startup.program"]
    assert (prog["args"]["program"], prog["args"]["T"],
            prog["args"]["rows"]) == ("jit_pretrain_step", 16, 32)
    assert prog["args"]["cache_hit"] in (True, False)
    assert prog["jit"]["compile_n"] >= 1 and prog["jit"]["lower_n"] >= 1
    assert prog["depth"] == 0 and not any(
        r["name"] in ("startup.lower", "startup.compile")
        for r in log.records())


def test_a_speculative_programs_first_call_is_logged_once(log):
    eng = _tiny_engine(spec_decode="ngram", spec_k=4)
    g = eng.g
    first = g._spec_jit(eng.gen_cfg, 4, eng.spec.ngram_max)
    assert first.__name__ == "serve_spec_verify_K4"
    eng.submit([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=12)
    eng.run()
    programs = [r["args"]["program"] for r in log.records()
                if r["name"] == "startup.program"]
    assert programs.count("jit_serve_spec_verify_K4") == 1
    # the cache now holds the bare program: no later dispatch passes the log
    bare = g._spec_jit(eng.gen_cfg, 4, eng.spec.ngram_max)
    assert bare is not first and hasattr(bare, "lower")
    n = len(log.records())
    eng.submit([4, 5, 6, 4, 5, 6, 4, 5], max_new_tokens=12)
    eng.run()
    assert len(log.records()) == n


def test_around_keeps_the_method_and_its_result(log):
    class Thing:
        @startup.around("startup.warm", lambda self: {"n": self.n})
        def go(self, n):
            """doc"""
            self.n = n
            return n + 1

    assert Thing().go(2) == 3 and Thing.go.__doc__ == "doc"
    assert [r["args"] for r in log.records()] == [{"n": 2}]
    assert ContinuousBatchingEngine.__init__.__name__ == "__init__"


# ---------------------------------------------------------------------------
# the step span's arguments nobody reads
# ---------------------------------------------------------------------------

COUNTS = {"kv_read_tokens", "attn_rows", "page_copies"}


@pytest.mark.parametrize("listener", ["nobody", "ring", "profiler"])
def test_the_attention_counts_are_computed_while_somebody_listens(
        listener, monkeypatch, tmp_path):
    eng = _tiny_engine()
    _warm(eng)
    calls, asked = [], eng.g.attention_counts
    monkeypatch.setattr(eng.g, "attention_counts",
                        lambda t, rows: calls.append(t) or asked(t, rows))
    ring = deque(maxlen=256)
    if listener == "ring":
        obs.TRACER.attach_ring(ring)
    elif listener == "profiler":
        jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs.TRACER.listening() is (listener != "nobody")
        eng.submit(list(range(1, 12)), max_new_tokens=3)
        for _ in range(4):
            eng.step()
    finally:
        if listener == "ring":
            obs.TRACER.detach_ring()
        elif listener == "profiler":
            jax.profiler.stop_trace()
    assert not obs.TRACER.listening()
    assert bool(calls) is (listener != "nobody")
    if listener == "ring":
        steps = [e["args"] for e in ring if e["name"] == "engine.step"]
        assert len(steps) == 4 and all(COUNTS <= set(a) for a in steps)
        assert steps[0]["kv_read_tokens"] == 2 * BUCKET   # 2 layers x 8 keys
