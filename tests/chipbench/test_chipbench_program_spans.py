"""The program's spans in the profiler's trace: nesting, self time and the
charging of idle gaps on hand-made spans; each reader's number on a trace
recorded on a v5e by PR 24, worked by hand below; the readers of PR 26
(rows multiplied, a blocked upload's launch wait, the paged kernel's share
in the batch cell) on a trace recorded by PR 26 on PR 25's program; and
nothing to read (not an error) in PR 22's recorded trace, whose program had
no spans."""

import glob
import json
import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import program_spans as ps  # noqa: E402
from chipbench.harness import spec, trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = sorted(
    os.path.basename(p)[:-3] for p in glob.glob(os.path.join(
        ROOT, "chipbench", "layer_metrics", "*.py"))
    if "TRACE_ONLY = True" in open(p).read())


def _run(xplane, window_span):
    """What a reader asks of a run, over a trace on disk."""
    run = SimpleNamespace()
    run.program_spans, run.launch_waits = ps.read_host(xplane)
    run.trace = tr.load(xplane)
    run.trace_window = tr.window(run.trace, window_span)
    return run


# ---------------------------------------------------------------------------
# hand-made spans
# ---------------------------------------------------------------------------

def _span(name, start, end, thread="engine", **stats):
    return ps.Span(name, float(start), float(end), stats, thread)


@pytest.fixture
def tree():
    """One thread: a step [0, 100] holding admit [5, 15], a drain [40, 90]
    that holds wait [45, 75] and retire [75, 85]; then idle [100, 130]
    and, adjacent to it, the next step [130, 160]; nothing in [160, 200]."""
    spans = [
        _span("engine.step", 0, 100, T=8, slots=2, q_tokens=10, kind="mixed"),
        _span("engine.admit", 5, 15),
        _span("engine.drain", 40, 90),
        _span("engine.drain.wait", 45, 75),
        _span("engine.drain.retire", 75, 85),
        _span("serve.idle", 100, 130),
        _span("engine.step", 130, 160, T=1, slots=2, q_tokens=2,
              kind="decode"),
    ]
    ps.nest(spans)
    return {(s.name, s.start): s for s in spans}, spans


def test_nesting_gives_direct_children_only(tree):
    by, _ = tree
    step = by["engine.step", 0.0]
    assert [c.name for c in step.children] == ["engine.admit",
                                               "engine.drain"]
    assert [c.name for c in by["engine.drain", 40.0].children] == [
        "engine.drain.wait", "engine.drain.retire"]
    assert by["serve.idle", 100.0].children == []
    assert by["engine.step", 130.0].children == []      # adjacent, not inside
    assert [w.start for w in ps.within(step, "engine.drain.wait")] == [45.0]


def test_self_time_is_duration_minus_what_the_children_cover(tree):
    by, _ = tree
    # the step: 100 - admit 10 - drain 50; the drain: 50 - wait 30 - retire 10
    assert ps.self_ns(by["engine.step", 0.0], 0, 200) == 40
    assert ps.self_ns(by["engine.drain", 40.0], 0, 200) == 10
    assert ps.self_ns(by["engine.drain.wait", 45.0], 0, 200) == 30
    # clipped to a window [50, 80]: the step covers 30 of it, all under
    # the drain; the drain 30 with wait [50, 75] and retire [75, 80] inside
    assert ps.self_ns(by["engine.step", 0.0], 50, 80) == 0
    assert ps.self_ns(by["engine.drain", 40.0], 50, 80) == 0
    assert ps.self_ns(by["engine.drain.wait", 45.0], 50, 80) == 25
    assert ps.self_ns(by["serve.idle", 100.0], 50, 80) == 0


def test_idle_gaps_are_cut_at_span_edges_and_go_to_the_innermost(tree):
    _, spans = tree
    # the chip is busy in [0, 10], [20, 50], [70, 120] and [150, 170]
    busy = [[0, 10], [20, 50], [70, 120], [150, 170]]
    idle = ps.idle_by_span(spans, busy, 0, 200)
    assert idle == {
        "engine.admit": 5,          # [10, 20] is cut at the admit's end, 15
        "engine.step": 5 + 20,      # its rest; and [130, 150] of [120, 150]
        "engine.drain.wait": 20,    # [50, 70], nested two deep
        "serve.idle": 10,           # [120, 130]: adjacent spans share no time
        ps.UNATTRIBUTED: 30,        # [170, 200] under no span
    }
    assert sum(idle.values()) == 200 - (10 + 30 + 50 + 20)


def test_a_gap_under_no_span_and_a_trace_without_spans():
    assert ps.idle_by_span([], [[10, 20]], 0, 30) == {ps.UNATTRIBUTED: 20}
    assert ps.innermost([], 5.0) is None
    assert ps.load(os.path.join(DATA, "recorded_trace.xplane.pb"),
                   names=()) == []


# ---------------------------------------------------------------------------
# a trace recorded on the chip, with the program's spans in it
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "recorded_program_trace.json")) as f:
        want = json.load(f)
    run = _run(os.path.join(DATA, "recorded_program_trace.xplane.pb"),
               want["window_span"])
    return run, want


def _read(run, metric):
    return spec.load_module(ROOT, "layer_metrics", metric).read(run)


# the 14 engine.step spans wholly inside the window, from the raw events:
# (duration, engine.drain.wait inside, engine.dispatch inside, q_tokens), ns.
# After a drain four launches return at once; from the fifth on a launch
# returns when the oldest in flight has finished, a device step later.
STEPS = [
    (9489770, 0, 733910, 195), (4457900, 0, 573690, 195),
    (4373840, 0, 573550, 195), (4797360, 0, 543090, 195),
    (104562273, 0, 100610353, 195), (117272593, 0, 113039063, 195),
    (118635223, 0, 114125312, 195),
    (605111514, 482938461, 115197803, 188),        # the one that drains
    (6959070, 0, 862620, 131), (4085890, 0, 508240, 131),
    (4811351, 0, 696080, 131), (4443031, 0, 559800, 131),
    (110464412, 0, 106678801, 131), (122721092, 0, 118813322, 131),
]
# the launch waits of the seven upload programs inside each step's
# engine.h2d (ExecutePrepare's start to its Acquire semaphore), ns: every
# upload found a place at once in this window
H2D_WAITS = [13460, 8200, 7300, 6870, 6610, 7690, 6570, 7782, 11490, 7230,
             7740, 29130, 6291, 7182]
# the ten runs of jit_serve_step_T64 wholly inside the window, ns
RUNS = [116919077, 117681116, 118404270, 119080486, 119742458, 120421666,
        121087463, 121848023, 122427102, 122936552]


def test_recorded_spans_and_steps(recorded):
    run, want = recorded
    assert len(run.program_spans) == want["program_spans"]
    lo, hi = run.trace_window
    assert hi - lo == want["window_ns"]
    assert len({s.thread for s in run.program_spans}) == 1
    found = ps.steps(run)
    assert [(s.dur, sum(w.dur for w in ps.within(s, ps.WAIT)),
             sum(w.dur for w in ps.within(s, ps.DISPATCH)),
             s.stats["q_tokens"]) for s in found] == STEPS
    assert {(s.stats["kind"], s.stats["T"], s.stats["slots"])
            for s in found} == {("mixed", 64, 32)}
    assert ps.unknown_modules(run) == 0


@pytest.mark.parametrize("cell", ["chat", "batch"])
def test_recorded_host_step_and_token_occupancy(recorded, cell):
    run, _ = recorded
    host = [d - w - x - u for (d, w, x, _), u in zip(STEPS, H2D_WAITS)]
    assert sum(H2D_WAITS) == 133545
    assert sum(host) == 65731224 - 133545         # 4.686 ms a step
    assert [sum(ps.upload_blocked_ns(run, h, s.start, s.end)
                for h in ps.within(s, ps.UPLOAD))
            for s in ps.steps(run)] == H2D_WAITS
    assert _read(run, f"host_step_ms.{cell}") == pytest.approx(
        65597679 / 14 / 1e6, rel=1e-12)
    # 7 x 195 + 188 + 6 x 131 = 2339 of 14 x 32 x 64 = 28672 computed
    assert sum(q for *_, q in STEPS) == 2339
    assert _read(run, f"token_occupancy_pct.{cell}") == pytest.approx(
        100 * 2339 / 28672, rel=1e-12)


def test_recorded_step_time_by_program(recorded):
    run, _ = recorded
    assert sum(RUNS) == 1200548213
    assert _read(run, "step_device_ms_mixed.chat") == pytest.approx(
        120.0548213, rel=1e-12)
    # the window holds no decode-only step: nothing to read, not a zero
    assert _read(run, "step_device_ms_decode.chat") is None


def test_recorded_host_busy_share(recorded):
    run, want = recorded
    by_hand = 100 * (want["thread_under_spans_ms"]
                     - want["thread_idle_wait_dispatch_ms"]
                     - want["h2d_launch_wait_ms"]) * 1e6 / want["window_ns"]
    assert by_hand == pytest.approx(want["host_busy_pct"], rel=1e-9)
    assert _read(run, "host_busy_pct.chat") == pytest.approx(
        want["host_busy_pct"], rel=1e-9)


@pytest.mark.parametrize("cell", ["chat", "batch"])
def test_recorded_host_bound_idle_share(recorded, cell):
    run, want = recorded
    by_hand = 100 * (want["chip_idle_under_any_span_ms"]
                     - want["chip_idle_under_wait_or_idle_ms"]) * 1e6 \
        / want["window_ns"]
    assert by_hand == pytest.approx(want["host_bound_idle_pct"], rel=1e-9)
    assert _read(run, f"host_bound_idle_pct.{cell}") == pytest.approx(
        want["host_bound_idle_pct"], rel=1e-9)
    lo, hi = run.trace_window
    first = max(lo, run.program_spans[0].start)
    last = min(hi, max(s.end for s in run.program_spans))
    busy = tr.union(tr.clipped(run.trace.ops[0], first, last))
    idle = ps.idle_by_span(run.program_spans, busy, first, last)
    total = sum(idle.values())
    assert total / 1e6 == pytest.approx(
        want["chip_idle_in_recorded_range_ms"], rel=1e-9)
    assert 1 - idle[ps.UNATTRIBUTED] / total == pytest.approx(
        want["attributed_share"], rel=1e-9)
    assert want["attributed_share"] > 0.95
    # the uploads, the retire loop and admission hold most of the idle
    top = sorted(idle, key=idle.get, reverse=True)[:3]
    assert top == ["engine.h2d", "engine.drain.retire", "engine.admit"]


def test_the_readers_run_over_a_trace_directory(recorded, tmp_path, capsys):
    """``python -m chipbench.harness.program_spans <trace dir>``."""
    import shutil
    where = tmp_path / "trace" / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "recorded_program_trace.xplane.pb"),
                where / "t.xplane.pb")
    assert ps.main([str(tmp_path / "trace")]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    got = {x["metric"]: x["value"] for x in lines if "metric" in x}
    assert sorted(got) == READERS
    assert got["token_occupancy_pct.chat"] == pytest.approx(
        100 * 2339 / 28672)
    assert got["step_device_ms_decode.chat"] is None
    detail = next(x for x in lines if x.get("name") == "host_step_ms")
    assert detail["steps_by_kind"] == {"mixed": 14}
    assert detail["dispatch_min_ms"] == pytest.approx(0.50824)


# ---------------------------------------------------------------------------
# a trace recorded on PR 25's program: packed steps, and a blocked upload
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def packed():
    with open(os.path.join(DATA, "recorded_packed_trace.json")) as f:
        want = json.load(f)
    run = _run(os.path.join(DATA, "recorded_packed_trace.xplane.pb"),
               want["window_span"])
    # what a live run has beside the trace: the host's step log, the
    # profiler's start on the host's clock, the sizes, the peaks
    run.results = {"step_log": want["step_log"]}
    run.tracer = SimpleNamespace(t_started=0.0,
                                 seconds=want["trace_seconds"])
    run.model = dict(want["heads"], sliding_window=None)
    run.traffic = {"engine": {"page_size": want["page_size"]}}
    run.cell = SimpleNamespace(root=ROOT)
    run.peaks = lambda: {"bf16_flops_per_s": 197e12,
                         "hbm_bytes_per_s": 819e9}
    return run, want


# the 16 engine.step spans wholly inside the window, from the raw events:
# (duration, engine.drain.wait inside, engine.dispatch inside, launch waits
# of the engine.h2d inside, q_tokens), ns; every one mixed, T=64, 32 slots,
# gemm_rows 512.  The first step's upload waited 75.5 ms for a place.
PACKED_STEPS = [
    (385034363, 304241404, 491060, 75533172, 187),
    (6308178, 0, 634790, 8881, 131), (4171079, 0, 533330, 8040, 131),
    (4674219, 0, 551550, 7180, 131), (4480889, 0, 565410, 8090, 131),
    (64459954, 0, 60265925, 6690, 131), (76616301, 0, 72409482, 7169, 131),
    (77235421, 0, 73217382, 10692, 131),
    (393971670, 315784220, 73751181, 7440, 131),
    (6054798, 0, 777460, 13870, 131), (4538818, 0, 564579, 7372, 131),
    (4217829, 0, 560230, 6730, 131), (4522599, 0, 508060, 5730, 131),
    (67950912, 0, 64138133, 6890, 86), (80582499, 0, 76325690, 6930, 68),
    (81088668, 0, 77141699, 8321, 68),
]


def test_packed_steps_and_their_launch_waits(packed):
    run, want = packed
    assert len(run.program_spans) == want["program_spans"]
    assert run.trace_window[1] - run.trace_window[0] == want["window_ns"]
    found = ps.steps(run)
    assert [(s.dur, sum(w.dur for w in ps.within(s, ps.WAIT)),
             sum(w.dur for w in ps.within(s, ps.DISPATCH)),
             sum(ps.upload_blocked_ns(run, h, s.start, s.end)
                 for h in ps.within(s, ps.UPLOAD)),
             s.stats["q_tokens"]) for s in found] == PACKED_STEPS
    assert {(s.stats["kind"], s.stats["T"], s.stats["slots"],
             s.stats["gemm_rows"]) for s in found} == {("mixed", 64, 32, 512)}
    # the eight launches that waited a device step: seven dispatches and
    # the one upload; every other launch found a place in under 40 us
    long = sorted(b - a for a, b in run.launch_waits if b - a > 1e6)
    assert len(long) == 8 and 59e6 < long[0] and long[-1] < 77e6
    assert max(b - a for a, b in run.launch_waits
               if b - a <= 1e6) < 40e3


@pytest.mark.parametrize("cell", ["chat", "batch"])
def test_packed_rows_multiplied_and_host_time_a_step(packed, cell):
    run, want = packed
    # 187 + 12 x 131 + 86 + 2 x 68 = 1981 tokens on 16 x 512 rows; the grid
    # is 16 x 32 x 64
    assert sum(q for *_, q in PACKED_STEPS) == 1981
    assert _read(run, f"gemm_occupancy_pct.{cell}") == pytest.approx(
        100 * 1981 / 8192, rel=1e-12)
    assert _read(run, f"token_occupancy_pct.{cell}") == pytest.approx(
        100 * 1981 / 32768, rel=1e-12)
    # the wait of the blocked upload is the chip's, not the host's
    host = [d - w - x - u for d, w, x, u, _ in PACKED_STEPS]
    assert sum(u for *_, u, _ in PACKED_STEPS) / 1e6 == pytest.approx(
        want["h2d_launch_wait_in_steps_ms"], rel=1e-12)
    assert sum(host) == 67793415                  # 4.237 ms a step
    assert max(host) < 5.7e6                      # with the wait in: 80 ms
    assert _read(run, f"host_step_ms.{cell}") == pytest.approx(
        67793415 / 16 / 1e6, rel=1e-12)


def test_packed_host_busy_share_leaves_the_launch_wait_out(packed):
    run, want = packed
    by_hand = 100 * (want["thread_under_spans_ms"]
                     - want["thread_idle_wait_dispatch_ms"]
                     - want["h2d_launch_wait_ms"]) * 1e6 / want["window_ns"]
    assert by_hand == pytest.approx(want["host_busy_pct"], rel=1e-9)
    assert 5.5 < by_hand < 5.6                    # with the wait in: 11.3
    assert _read(run, "host_busy_pct.chat") == pytest.approx(
        want["host_busy_pct"], rel=1e-9)


@pytest.mark.parametrize("cell", ["chat", "batch"])
def test_packed_paged_kernel_share_of_its_roofline(packed, cell):
    """195 calls took 0.4524 s.  A call's least time: the mean over the 17
    logged steps of the bytes (K and V of context + query at 8 KV heads x
    128 x 2 bytes each = 4,096 a token; Q and O at 32 heads = 16,384 a
    query token) over 819 GB/s, which is above its operations over 197
    TFLOP/s in every step."""
    run, want = packed
    assert len(want["step_log"]) == 17
    nbytes = [sum(4096 * (ctx + q) + 16384 * q for q, ctx in s["rows"])
              for s in want["step_log"]]
    flops = [sum(4 * 32 * 128 * (q * ctx + q * (q + 1) / 2)
                 for q, ctx in s["rows"]) for s in want["step_log"]]
    assert all(f / 197e12 < b / 819e9 for f, b in zip(flops, nbytes))
    least = want["paged_calls"] * sum(nbytes) / 17 / 819e9
    by_hand = 100 * least / (want["paged_calls_ns"] / 1e9)
    assert 0.91 < by_hand < 0.92
    assert _read(run, f"paged_attn_roofline_pct.{cell}") == pytest.approx(
        by_hand, rel=1e-9)
    # three layers of four behind a window of 512: less to read, never more
    run.model = dict(run.model, sliding_window=512, layer_types=[
        "sliding_attention"] * 3 + ["full_attention"])
    try:
        windowed = _read(run, f"paged_attn_roofline_pct.{cell}")
    finally:
        run.model = dict(want["heads"], sliding_window=None)
    assert 0.25 * by_hand < windowed < by_hand


# ---------------------------------------------------------------------------
# a program without spans: nothing to read, and no error
# ---------------------------------------------------------------------------

def test_the_vocabulary_is_the_programs_own():
    from paddle_tpu.observability.catalog import SPANS
    assert ps.vocabulary() == frozenset(SPANS)
    assert {"engine.step", "engine.drain.wait", "serve.idle"} <= set(SPANS)


@pytest.mark.parametrize("metric", READERS)
def test_every_reader_finds_nothing_in_a_trace_without_program_spans(metric):
    run = _run(os.path.join(DATA, "recorded_trace.xplane.pb"),
               "bench.window")
    assert run.program_spans == []
    reader = spec.load_module(ROOT, "layer_metrics", metric)
    assert reader.read(run) is None
    assert reader.SOURCE in spec.SOURCES
    assert reader.MOVES in {m["name"] for m in spec.benchmark(ROOT)[
        "end_to_end"]}


def test_the_eleven_readers_are_there():
    assert READERS == [
        "gemm_occupancy_pct.batch", "gemm_occupancy_pct.chat",
        "host_bound_idle_pct.batch", "host_bound_idle_pct.chat",
        "host_busy_pct.chat", "host_step_ms.batch", "host_step_ms.chat",
        "step_device_ms_decode.chat", "step_device_ms_mixed.chat",
        "token_occupancy_pct.batch", "token_occupancy_pct.chat"]
