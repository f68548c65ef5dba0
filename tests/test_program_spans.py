"""The program's own spans on the profiler's clock (ISSUE 24): live spans
with their arguments in a real ``jax.profiler`` trace, stable names of the
jitted programs, named scopes that change metadata only, the closed
vocabulary and its sinks, and what a span costs when nobody listens."""

import glob
import os
import re
import statistics
import time
from collections import deque

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine, GenerationConfig
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.catalog import SPANS
from paddle_tpu.observability.collector import (_KEEP_MARKERS,
                                                InprocTransport,
                                                SpanExporter,
                                                TraceCollector)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUCKET = 8


def _tiny_engine(**kw):
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    return ContinuousBatchingEngine(
        model, max_batch=2, gen=GenerationConfig(max_new_tokens=6),
        max_seq_len=64, page_size=8, prefill_bucket=BUCKET, **kw)


def _profiled(tmp_path, work):
    """Run ``work()`` under a real profiler session; the program's spans
    [(name, start_ns, end_ns, stats)] and every host event's name."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    spans, names = [], set()
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                names.add(e.name)
                if e.name in SPANS:
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(spans, key=lambda s: s[1]), names


def _inside(spans, outer):
    return [s for s in spans if s is not outer
            and outer[1] <= s[1] and s[2] <= outer[2]]


# ---------------------------------------------------------------------------
# the profiler's trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    """Two requests through a warm engine under the profiler, each step
    gathered as late as the bound on steps in flight (2) allows, so that
    the schedule is the same on every machine: step n's gather, at its
    start, waits for step n - 2 and delivers it alone.  By hand: A's 19
    prompt tokens go in chunks of 8, 8, 3 and B's 2 in one, so the first
    three steps are mixed (T=8) with 8+2, 8+1 and 3+1 query tokens (B
    decodes from step 2 on); the third commits A's first token, and decode
    steps (T=1, one token a row) follow.  B has its six after step 6 and
    the host learns that at the start of step 8, so B rides step 7 as a
    row too and steps 8 and 9 hold A alone; A has its six after step 8,
    which step 10 gathers: it has nothing to dispatch, waits for step 9
    (a frozen repeat) and is idle."""
    from paddle_tpu.inference import generation
    eng = _tiny_engine(metrics=True)
    eng.add_request([1, 2, 3])
    eng.run()                                   # both programs compiled
    first = eng._step_no + 1

    def work():
        eng.add_request(list(range(1, 20)))
        eng.add_request([4, 5])
        eng.run()

    with pytest.MonkeyPatch.context() as mp:
        assert generation.MAX_STEPS_IN_FLIGHT == 2
        mp.setattr(generation._InFlight, "landed", lambda self: False)
        spans, names = _profiled(tmp_path_factory.mktemp("engine"), work)
    return spans, names, first


WANT_STEPS = [("mixed", BUCKET, 2, 10), ("mixed", BUCKET, 2, 9),
              ("mixed", BUCKET, 2, 4)] + [("decode", 1, 2, 2)] * 4 \
    + [("decode", 1, 1, 1)] * 2 + [("idle", 0, 0, 0)]


def test_engine_steps_carry_the_counts_of_their_own_prompts(engine_trace):
    spans, _, first = engine_trace
    steps = [s for s in spans if s[0] == "engine.step"]
    got = [(s[3]["kind"], s[3]["T"], s[3]["rows"], s[3]["q_tokens"])
           for s in steps]
    assert got == WANT_STEPS
    assert [s[3]["step"] for s in steps] == list(
        range(first, first + len(WANT_STEPS)))
    assert all(s[3]["slots"] == 2 and s[3]["waiting"] == 0 for s in steps)


def test_a_steps_span_carries_the_rows_of_its_gemms(engine_trace):
    """``gemm_rows`` holds ``q_tokens`` and never passes ``slots x T``; an
    engine this small is under the floor, so every dispatch is dense."""
    spans, _, _ = engine_trace
    steps = [s[3] for s in spans if s[0] == "engine.step"]
    for a in steps:
        assert a["q_tokens"] <= a["gemm_rows"] <= a["slots"] * a["T"]
    assert [a["gemm_rows"] for a in steps] == [2 * BUCKET] * 3 + [2] * 6 + [0]


def test_every_step_holds_its_phases_and_gathers_the_step_two_before(
        engine_trace):
    """No cadence: every step that has two steps before it gathers the
    older of them at its start (here by waiting: ``blocked`` says why),
    and leaves the newer in flight."""
    spans, _, _ = engine_trace
    steps = [s for s in spans if s[0] == "engine.step"]
    assert len(steps) == 10
    for i, step in enumerate(steps, 1):
        inner = [s[0] for s in _inside(spans, step)]
        assert inner.count("engine.admit") == 1
        for phase in ("engine.grow", "engine.build", "engine.h2d",
                      "engine.dispatch"):
            assert inner.count(phase) == (i < 10), (i, phase, inner)
        gathers = 1 if 3 <= i < 10 else 2 if i == 10 else 0
        for phase in ("engine.drain", "engine.drain.wait",
                      "engine.drain.retire"):
            assert inner.count(phase) == gathers, (i, phase, inner)
        if gathers:                 # the gather comes first: it frees slots
            assert inner[0] == "engine.drain"
    admits = [s for s in spans if s[0] == "engine.admit"]
    assert [a[3]["admitted"] for a in admits[:2]] == [2, 0]
    drains = [s[3] for s in spans if s[0] == "engine.drain"]
    # steps 3-10 each wait for the oldest step, and the tenth, with
    # nothing to dispatch, then for the one still out
    assert [d["steps"] for d in drains] == [1] * 9
    assert [d["in_flight"] for d in drains] == [1] * 8 + [0]
    assert [d["blocked"] for d in drains] == ["bound"] * 8 + ["idle"]
    # both requests' six tokens each; B's frozen repeat of step 7 and A's
    # of step 9 are gathered after their requests' retirement and dropped
    assert [d["tokens"] for d in drains] == [1, 1, 2, 2, 2, 2, 1, 1, 0]
    retire = [s for s in spans if s[0] == "engine.drain.retire"]
    assert [r[3]["retired"] for r in retire] == [0] * 5 + [1, 0, 1, 0]
    for d in (s for s in spans if s[0] == "engine.drain"):
        # wait and retire lie inside the gather
        assert {"engine.drain.wait", "engine.drain.retire"} <= {
            s[0] for s in _inside(spans, d)}


def test_step_programs_have_stable_names(engine_trace):
    spans, names, _ = engine_trace
    programs = [s[3]["program"] for s in spans if s[0] == "engine.dispatch"]
    assert programs == [f"serve_step_T{BUCKET}"] * 3 + ["serve_step_T1"] * 6
    # the engine calls its step programs as compiled ahead of time
    assert f"PjitFunction(jit(serve_step_T{BUCKET}))" in names
    assert "PjitFunction(jit(serve_step_T1))" in names
    assert not any("_unknown" in n for n in names)


def test_every_jitted_serving_program_lowers_under_its_name():
    eng = _tiny_engine(prefix_cache=True)
    g = eng.g
    assert eng.lowered_step(BUCKET).as_text().startswith(
        f"module @jit_serve_step_T{BUCKET} ")
    for fn, name in ((g._spec_jit(eng.gen_cfg, 4, 3), "serve_spec_verify_K4"),
                     (g._fused_jit(eng.gen_cfg, 4), "serve_fused_K4"),
                     (eng._cow_jit, "pool_cow_copy")):
        assert fn.__name__ == name
    from paddle_tpu.inference.kv_spill import make_upload_program
    assert make_upload_program(g.cache).__name__ == "pool_swap_in"


def test_train_step_spans_and_program_name(tmp_path):
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep
    ps = PretrainStep(LlamaConfig.tiny(), ParallelConfig())
    state = ps.init_state(seed=0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (2, 16)).astype(np.int32)
    labels = rng.integers(0, 256, (2, 16)).astype(np.int32)
    state, _ = ps.train_step(state, ids, labels)         # compiles

    def work():
        s, loss = ps.train_step(state, ids, labels)      # host arrays
        s, loss = ps.train_step(s, *ps.shard_batch(ids, labels))
        jax.block_until_ready(loss)

    spans, names = _profiled(tmp_path, work)
    steps = [s for s in spans if s[0] == "train.step"]
    assert [s[3]["tokens"] for s in steps] == [32, 32]
    assert [sorted(x[0] for x in _inside(spans, s)) for s in steps] == [
        ["train.dispatch", "train.shard_batch"], ["train.dispatch"]]
    assert "PjitFunction(pretrain_step)" in names
    assert ps.lowered_step(state, *ps.shard_batch(ids, labels)).as_text(
        ).startswith("module @jit_pretrain_step ")


# ---------------------------------------------------------------------------
# named scopes change metadata only
# ---------------------------------------------------------------------------

def _stripped(text):
    return re.sub(r", metadata=\{[^}]*\}", "", text)


def test_named_scopes_leave_the_compiled_programs_as_they_were(monkeypatch):
    """The step programs compiled with the scopes and with every scope
    switched off are the same text once ``metadata={...}`` is removed."""
    from jax._src import source_info_util as siu
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    def compiled():
        jax.clear_caches()
        eng = _tiny_engine()
        out = {T: eng.lowered_step(T).compile().as_text()
               for T in (BUCKET, 1)}
        ps = PretrainStep(LlamaConfig.tiny(), ParallelConfig())
        state = ps.init_state(seed=0)
        batch = ps.shard_batch(np.zeros((2, 16), np.int32),
                               np.zeros((2, 16), np.int32))
        out["train"] = ps.lowered_step(state, *batch).compile().as_text()
        return out

    cm = siu.ExtendNameStackContextManager
    texts = {}
    # the persistent compile cache keys a program without its metadata, so
    # the second compile would be handed the first one's text
    from jax.experimental.compilation_cache import compilation_cache
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        for scopes in (True, False):    # one call site: same stack frames
            if not scopes:
                monkeypatch.setattr(cm, "__enter__", lambda self: None)
                monkeypatch.setattr(cm, "__exit__", lambda self, *exc: None)
            texts[scopes] = compiled()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
        monkeypatch.undo()
        jax.clear_caches()
    with_scopes, without = texts[True], texts[False]
    for scope in ("attention", "mlp", "head", "sampling", "embed",
                  "kv_write"):
        assert f"/{scope}/" in with_scopes[BUCKET], scope
    for scope in ("attention", "mlp", "head_loss", "optimizer"):
        # under autodiff a scope reads jvp(<scope>) and transpose(jvp(..))
        assert re.search(rf"[/(]{scope}[/)]", with_scopes["train"]), scope
    for key, text in with_scopes.items():
        assert "/attention/" not in without[key]
        assert _stripped(text) == _stripped(without[key]), key


# ---------------------------------------------------------------------------
# the vocabulary and its sinks
# ---------------------------------------------------------------------------

def test_every_span_site_is_in_the_vocabulary_and_back():
    sites, retroactive = set(), []
    for base, _, files in os.walk(os.path.join(ROOT, "paddle_tpu")):
        for f in files:
            if f.endswith(".py"):
                text = open(os.path.join(base, f)).read()
                sites |= set(re.findall(
                    r"\.span\(\s*\"([a-z0-9_.]+)\"", text))
                if f != "catalog.py":
                    # the start-up log's phases open their spans by name
                    # through observability/startup.py
                    sites |= set(re.findall(r"\"(startup\.[a-z_]+)\"",
                                            text))
                if 'TRACER.event("engine.step"' in text:
                    retroactive.append(f)
    assert sites == set(SPANS), sites ^ set(SPANS)
    assert not retroactive


def test_no_span_name_or_argument_value_is_a_keep_marker(engine_trace):
    spans, _, _ = engine_trace
    hay = [n for n in SPANS] + [str(v) for s in spans for v in s[3].values()
                                if isinstance(v, str)]
    for text in hay:
        assert not any(m in text.lower() for m in _KEEP_MARKERS), text


@pytest.fixture
def sinks():
    """The process tracer with a ring, a fleet export sink and the Chrome
    buffer on; everything detached afterwards."""
    col = TraceCollector()
    exp = SpanExporter(InprocTransport(col), proc="t24", tracer=obs.TRACER,
                       sample_rate=1.0)
    ring = deque(maxlen=4096)
    obs.TRACER.start()
    obs.TRACER.attach_ring(ring)
    obs.TRACER.attach_export(exp)
    try:
        yield ring, exp
    finally:
        obs.TRACER.detach_export()
        obs.TRACER.detach_ring()
        obs.TRACER.stop()


def test_phases_stay_in_the_ring_and_only_whole_steps_are_exported(sinks):
    ring, exp = sinks
    eng = _tiny_engine(metrics=True)
    eng.add_request([1, 2, 3, 4, 5])
    eng.run()
    with obs.TRACER.span("serve.idle"):
        pass
    in_ring = {e["name"] for e in ring}
    assert {"engine.step", "engine.admit", "engine.build", "engine.h2d",
            "engine.dispatch", "engine.drain", "engine.drain.wait",
            "engine.drain.retire"} <= in_ring
    assert "serve.idle" not in in_ring            # profiler only
    offered = {e["name"] for e in exp._buf}
    assert "engine.step" in offered
    assert not offered & (set(SPANS) - {"engine.step", "train.step"})
    step = next(e for e in ring if e["name"] == "engine.step")
    assert step["cat"] == "serving" and step["args"]["kind"] == "mixed"
    lanes = obs.TRACER.lane_names()
    assert {lanes[e["tid"]] for e in ring if e["name"] in SPANS
            and not e["name"].startswith("startup.")} == {"engine"}
    # the engine was built with the sinks on: its set-up is on its own lane
    assert {lanes[e["tid"]] for e in ring
            if e["name"].startswith("startup.")} == {"startup"}


def test_a_steps_span_carries_the_rows_of_its_attention_tiles(sinks,
                                                            monkeypatch):
    """``attn_rows`` against the kernel's own tile arithmetic.  With row
    tiles of 8 (the tiny model's T x group = 16 rows would be one tile) a
    slot's ``q_len x 2`` live rows round up to whole tiles: the 19-token
    prompt goes in as 8 + 8 + 3 tokens beside the 2-token prompt's chunk
    and then its decode token, and a decode step's block is 8 rows."""
    from paddle_tpu.kernels import paged_attention as pa
    monkeypatch.setattr(pa, "_ROW_TILE", 8)
    assert pa.row_tile(BUCKET, 2) == 8 and pa.row_tile(1, 2) == 8
    ring, _ = sinks
    eng = _tiny_engine()
    eng.add_request(list(range(1, 20)))
    eng.add_request([4, 5])
    eng.run()
    steps = [e["args"] for e in ring if e["name"] == "engine.step"]
    mixed = [a for a in steps if a["kind"] == "mixed"]
    assert [(a["q_tokens"], a["attn_rows"]) for a in mixed] == [
        (10, 16 + 8), (9, 16 + 8), (4, 8 + 8)]
    for a in steps:
        assert a["attn_rows"] % 8 == 0
        assert a["q_tokens"] * 2 <= a["attn_rows"] <= a["slots"] * max(
            a["T"] * 2, 8)
    assert all(a["attn_rows"] == 8 * a["rows"] for a in steps
               if a["kind"] == "decode")
    # ``page_copies``: the descriptors one layer's call starts.  A table
    # row is 8 pages (64 positions in pages of 8), so a block is the whole
    # row: 8 copies a slot with work and a context, none for a first chunk
    assert [a["page_copies"] for a in mixed] == [0, 8 + 8, 8 + 8]
    assert all(a["page_copies"] == 8 * a["rows"] for a in steps
               if a["kind"] == "decode")
    # the same sums from the kernel's module, whatever the tile
    monkeypatch.undo()
    assert pa.attn_rows([64, 1, 0, 3], 64, 4) == 3 * 256
    assert pa.attn_rows([64, 1, 17], 64, 16) == 1024 + 256 + 512
    assert pa.attn_rows([1, 1, 0], 1, 4) == 16


def test_steptimer_records_no_event_and_train_step_is_a_live_span(sinks):
    ring, _ = sinks
    t = obs.StepTimer("t24train")
    for _ in range(3):
        t.begin_step()
        t.tick(tokens=8)
    assert not [e for e in ring if e["name"].endswith(".step")]
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep
    ps = PretrainStep(LlamaConfig.tiny(), ParallelConfig())
    state = ps.init_state(seed=0)
    ids = np.zeros((2, 16), np.int32)
    ps.train_step(state, ids, ids)
    ev = [e for e in ring if e["name"] == "train.step"]
    assert len(ev) == 1 and ev[0]["args"] == {"tokens": 32}
    assert ev[0]["cat"] == "train"
    assert obs.TRACER.lane_names()[ev[0]["tid"]] == "train"


# ---------------------------------------------------------------------------
# what it costs
# ---------------------------------------------------------------------------

def test_a_span_costs_under_two_microseconds_when_nobody_listens():
    tr = obs.TRACER
    assert not tr.enabled

    def one():
        with tr.span("engine.step", step=1, slots=2) as s:
            s.set_metadata(kind="mixed", T=8, rows=2, q_tokens=10, waiting=0)

    for _ in range(1000):
        one()
    took = []
    for _ in range(10000):
        t0 = time.perf_counter_ns()
        one()
        took.append(time.perf_counter_ns() - t0)
    assert statistics.median(took) < 2000, statistics.median(took)


# what is handed in before each step, by prompt length.  The second plan
# (8 slots of 8 places, the floor of the row buckets lowered to 8: a packed
# member of 16 rows and the dense 64) walks through both members of the T=8
# family: 5 tokens -> 16 rows; 8 + 1 decoding ->
# 16; three first chunks + 2 -> 64; three new prompts + three second chunks
# + 2 -> 64; then decode-only steps (the T=1 program, 8 rows).
PLANS = {
    "two_short_prompts": (2, None, [[3, 2], [], [], [], [], []], None),
    "both_row_buckets": (8, 8, [[5], [8], [16, 16, 16], [8, 8, 8], [], []],
                         [16, 16, 64, 64, 8, 8]),
}


@pytest.mark.parametrize("listening,plan", [
    (False, "two_short_prompts"), (True, "two_short_prompts"),
    (False, "both_row_buckets")])
def test_warm_steps_compile_nothing_and_sync_nothing(monkeypatch, listening,
                                                     plan):
    from paddle_tpu.inference import generation
    # under the bound on steps in flight nothing waits for the device: a
    # step that has landed is gathered on the way, unmarked
    monkeypatch.setattr(generation, "MAX_STEPS_IN_FLIGHT", 64)
    max_batch, floor, arrivals, want_rows = PLANS[plan]
    if floor:
        monkeypatch.setattr(generation, "MIN_GEMM_ROWS", floor)
    paddle.seed(0)
    eng = ContinuousBatchingEngine(
        LlamaForCausalLM(LlamaConfig.tiny()), max_batch=max_batch,
        gen=GenerationConfig(max_new_tokens=6), max_seq_len=64, page_size=8,
        prefill_bucket=BUCKET, metrics=True)
    # the first dispatch of a T compiles its whole family
    for p in ([1, 2, 3], [4, 5]):
        eng.add_request(p)
    eng.run()
    assert sorted(eng._step_family(BUCKET)) == eng.g.row_buckets(BUCKET)
    seen = []
    inner = eng.g.gemm_rows
    monkeypatch.setattr(
        eng.g, "gemm_rows",
        lambda t, n: seen.append(inner(t, n)) or seen[-1])
    if listening:
        obs.TRACER.start()
    try:
        with obs.assert_overhead(max_compiles=0, max_syncs=0):
            for lens in arrivals:
                for n in lens:
                    eng.add_request(list(range(1, n + 1)))
                eng.step()
    finally:
        obs.TRACER.stop()
    if want_rows:
        assert seen == want_rows
        assert set(seen) >= set(eng.g.row_buckets(BUCKET))
    assert all(len(v) == 6 for v in eng.run().values())
