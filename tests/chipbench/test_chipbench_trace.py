"""The reduction from the profiler's trace to numbers, on a small trace
recorded on a v5e and on hand-made intervals."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.harness import spec, trace_reduce as tr  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        want = json.load(f)
    return tr.load(os.path.join(DATA, "recorded_trace.xplane.pb")), want


def test_recorded_trace_window_and_busy_time(recorded):
    trace, want = recorded
    assert len(trace.ops) == 1                      # one chip
    lo, hi = tr.window(trace, want["window_span"])
    assert (hi - lo) / 1e9 == pytest.approx(want["window_s"], rel=1e-9)
    busy = tr.busy_s(trace, lo, hi)
    assert busy == [pytest.approx(want["busy_s"], rel=1e-6)]
    # a sum of durations would count a while's body twice: the union is less
    total = sum(o.dur for o in trace.ops[0] if lo <= o.start < hi) / 1e9
    assert total > busy[0]


def test_recorded_trace_steps_and_kernels(recorded):
    trace, want = recorded
    lo, hi = tr.window(trace, want["window_span"])
    mods = tr.step_modules(trace, lo, hi)
    assert len(mods) == want["steps"]
    assert all(m.name.startswith("jit_step(") for m in mods)
    flash = spec.load_module(ROOT, "kernels", "flash_attention")
    calls = tr.kernel_calls(trace, lo, hi, flash.match)
    kinds = sorted(s["kind"] for _, s in calls)
    assert kinds == ["dkv", "dkv", "dq", "dq", "fwd", "fwd", "fwd", "fwd"]
    assert sum(o.dur for o, _ in calls) / 1e9 == pytest.approx(
        want["kernel_s"], rel=1e-6)
    assert all(s["hq"] % s["hkv"] == 0 and s["d"] == 128 for _, s in calls)


def test_recorded_trace_breakdown(recorded):
    trace, want = recorded
    lo, hi = tr.window(trace, want["window_span"])
    top = tr.top_ops(trace, lo, hi)
    assert 1 <= len(top) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert not any(n.startswith("while ") for n, _ in top)
    gaps = tr.idle_gaps(trace, lo, hi)
    names = {n for n, _ in gaps}
    assert "bench.train_step" in names
    idle = sum(s for _, s in gaps)
    assert idle == pytest.approx(want["window_s"] - want["busy_s"], rel=1e-6)


def test_parse_op_reads_name_opcode_and_shapes():
    op = tr.parse_op(
        "%closed_call.10 = (bf16[32,8,256,128]{3,2,1,0:T(8,128)(2,1)S(1)}, "
        "f32[32,8,256,1]{3,2,1,0:T(8,128)}) custom-call(s32[32,160]{1,0:"
        "T(8,128)S(1)} %x, s32[32]{0:T(128)S(1)} %y, bf16[32,8,256,128]{3,2,"
        "1,0} %q), custom_call_target=\"tpu_custom_call\", "
        "operand_layout_constraints={s32[32,160]{1,0}}", 10.0, 5.0)
    assert (op.name, op.opcode, op.is_kernel) == (
        "closed_call.10", "custom-call", True)
    assert op.out_shapes == [("bf16", (32, 8, 256, 128)),
                             ("f32", (32, 8, 256, 1))]
    assert op.operand_shapes[0] == ("s32", (32, 160))
    assert len(op.operand_shapes) == 3          # constraints are not operands
    assert op.short() == "custom-call closed_call.10 bf16[32,8,256,128]"
    paged = spec.load_module(ROOT, "kernels", "paged_attention")
    assert paged.match(op) == {"slots": 32, "kv_heads": 8, "q_rows": 256,
                               "head_dim": 128, "dtype": "bf16"}
    w = tr.parse_op("%while.43 = (u32[], f32[1,512]{1,0}) while((u32[], "
                    "f32[1,512]) %t), condition=%c, body=%b", 0.0, 9.0)
    assert w.opcode == "while"


def _op(name, opcode, start, dur):
    return tr.Op(f"%{name} = f32[8] {opcode}()", start, dur, name=name,
                 opcode=opcode, out_shapes=[("f32", (8,))])


def test_union_and_busy_on_hand_made_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    ops = [_op("while.1", "while", 0, 100), _op("a", "fusion", 10, 20),
           _op("b", "fusion", 200, 50)]
    t = tr.Trace([ops], [[]], [])
    assert tr.busy_s(t, 0, 300) == [150 / 1e9]      # 0-100 and 200-250
    assert tr.busy_s(t, 50, 225) == [75 / 1e9]      # clipped to the window
    assert [n for n, _ in tr.top_ops(t, 0, 300)] == ["fusion b f32[8]",
                                                     "fusion a f32[8]"]


def test_idle_gaps_are_charged_to_the_innermost_bench_span():
    ops = [_op("a", "fusion", 0, 10), _op("b", "fusion", 40, 10),
           _op("c", "fusion", 90, 10)]
    spans = [tr.Span("bench.trace_window", 0, 100, {}),
             tr.Span("bench.engine_step", 5, 40, {}),
             tr.Span("bench.outer", 0, 60, {})]
    t = tr.Trace([ops], [[]], spans)
    gaps = dict(tr.idle_gaps(t, 0, 100))
    assert gaps == {"bench.engine_step": 30 / 1e9,
                    "host:no_bench_span": 40 / 1e9}


def test_exposed_collective_time():
    ops = [_op("ar.1", "all-reduce", 0, 10),            # alone: exposed
           _op("f", "fusion", 10, 10),
           _op("ar-start", "all-reduce-start", 20, 1),
           _op("g", "fusion", 21, 9),
           _op("ar-done", "all-reduce-done", 30, 5)]    # the wait: exposed
    t = tr.Trace([ops], [[]], [])
    assert tr.exposed_collective_s(t, 0, 40) == pytest.approx(16 / 1e9)


def test_a_trace_without_the_window_span_is_an_error(recorded):
    trace, _ = recorded
    with pytest.raises(ValueError):
        tr.window(trace, "bench.no_such_span")
