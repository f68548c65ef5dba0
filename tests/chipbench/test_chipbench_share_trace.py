"""The three per-layer readers PR 27 brought (the sliding-attention calls'
roofline share, the held experts' grouped GEMM priced from the program's
own row count, the occupancy of the expert tiles) on a trace recorded on a
v5e from ``commandaplus-batch-longdocs``; the numbers in the JSON beside it
were worked out apart from the readers (its ``about``).  And on a trace of
a program that has none of this: nothing to read, not an error."""

import gzip
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
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("paged_attn_sliding_roofline_pct.batch", "gmm_held_roofline_pct.batch",
       "expert_rows_occupancy_pct.batch")


def _reader(name):
    return spec.load_module(ROOT, "layer_metrics", name)


def _config():
    """The configuration's file of the cell the trace was recorded from:
    a share without a leading dense layer."""
    return spec.load_json(os.path.join(
        ROOT, "chipbench", "configs", "command-a-plus-05-2026-ep8.json"))


def _run(xplane, want, registry, model, config=None):
    run = SimpleNamespace()
    run.program_spans, run.launch_waits = ps.read_host(xplane)
    run.trace = tr.load(xplane)
    run.trace_window = tr.window(run.trace, want["window_span"])
    run.results = {"step_log": want["step_log"], "registry": registry}
    run.tracer = SimpleNamespace(t_started=0.0,
                                 seconds=want["trace_seconds"])
    run.model = model
    run.traffic = {"engine": {"page_size": want["page_size"]}}
    run.cell = SimpleNamespace(root=ROOT, config=config or {})
    run.peaks = lambda: PEAKS
    return run


@pytest.fixture(scope="module")
def share(tmp_path_factory):
    with open(os.path.join(DATA, "recorded_share_trace.json")) as f:
        want = json.load(f)
    xplane = str(tmp_path_factory.mktemp("share") / "share.xplane.pb")
    with gzip.open(os.path.join(DATA, "recorded_share_trace.xplane.pb.gz"),
                   "rb") as src, open(xplane, "wb") as dst:
        dst.write(src.read())
    return _run(xplane, want, want["registry"], want["model"],
                _config()), want


def test_the_window_and_the_calls_by_their_names(share):
    run, want = share
    lo, hi = run.trace_window
    assert hi - lo == want["window_ns"]
    sliding = spec.load_module(ROOT, "kernels", "paged_attention_sliding")
    held = spec.load_module(ROOT, "kernels", "grouped_matmul_held")
    paged = spec.load_module(ROOT, "kernels", "paged_attention")
    plain = spec.load_module(ROOT, "kernels", "grouped_matmul")
    calls = tr.kernel_calls(run.trace, lo, hi, sliding.match)
    assert len(calls) == want["sliding_calls"]
    assert sum(op.dur for op, _ in calls) == want["sliding_calls_ns"]
    assert {s["window"] for _, s in calls} == {4096}
    assert {s["q_rows"] for _, s in calls} == {64 * 128 // 8}
    # the shapes alone match the full layer's calls too; the name does not
    every = tr.kernel_calls(run.trace, lo, hi, paged.match)
    assert len(every) == want["sliding_calls"] + want["full_calls"]
    assert want["sliding_calls"] == 3 * want["full_calls"]
    gemms = tr.kernel_calls(run.trace, lo, hi, held.match)
    assert len(gemms) == want["held_gmm_calls"] == 3 * 4 * want["full_calls"]
    assert sum(op.dur for op, _ in gemms) == want["held_gmm_calls_ns"]
    assert {(s["experts"], s["k"], s["n"], s["block_m"])
            for _, s in gemms} == {(16, 4096, 4096, 128)}
    # the reader that prices M - E x bm rows finds none of them to misprice
    assert tr.kernel_calls(run.trace, lo, hi, plain.match) == []


@pytest.mark.parametrize("name,key", [
    (NEW[0], "paged_attn_sliding_roofline_pct"),
    (NEW[1], "gmm_held_roofline_pct"),
    (NEW[2], "expert_rows_occupancy_pct")])
def test_each_new_reader_gives_the_number_worked_out_apart(share, name, key):
    run, want = share
    got = _reader(name).read(run)
    assert got == pytest.approx(want[key], rel=1e-9)
    assert 0 < got < 105.0
    assert got == pytest.approx(want["readers_said_on_the_chip"][name],
                                rel=1e-9)


def test_the_held_gemms_rows_are_divided_over_the_layers_that_have_experts(
        share):
    """PR 38: the reader divides a step's held rows by the layers that HAVE
    experts, ``num_hidden_layers`` less ``layer_pattern.leading_dense`` of
    the configuration's file.  This cell has no leading layer (stated as 0,
    or not stated: the same), so it reads what the chip said under PR 27's
    reader to the last digit; were one of its four layers a leading dense
    one, the same rows would be three layers' and the share higher."""
    run, want = share
    reader = _reader(NEW[1])
    said = want["readers_said_on_the_chip"][NEW[1]]
    assert run.cell.config["layer_pattern"]["leading_dense"] == 0
    assert reader.read(run) == said
    run.cell = SimpleNamespace(root=ROOT, config={})
    assert reader.read(run) == said
    run.cell = SimpleNamespace(root=ROOT, config={
        "layer_pattern": {"period": 1, "leading_dense": 1}})
    assert said < reader.read(run) < 1.02 * said
    run.cell = SimpleNamespace(root=ROOT, config=_config())


def test_held_rows_are_never_priced_from_the_operands_rows(share):
    run, want = share
    held = spec.load_module(ROOT, "kernels", "grouped_matmul_held")
    lo, hi = run.trace_window
    _, shapes = tr.kernel_calls(run.trace, lo, hi, held.match)[0]
    rows = want["held_rows_a_call"]
    assert rows < shapes["rows_laid_out"] / 8       # an eighth at most is real
    flops, nbytes = held.cost(shapes, rows)
    assert flops == 2.0 * rows * 4096 * 4096
    assert nbytes == 2.0 * (16 * 4096 * 4096 + 2 * rows * 4096)
    assert max(flops / 197e12, nbytes / 819e9) == pytest.approx(
        want["gmm_held_least_s_a_call"], rel=1e-12)


def test_the_steps_carry_the_key_tokens_their_attention_reads(share):
    run, want = share
    steps = [s for s in ps.steps(run)]
    assert [int(s.stats["q_tokens"]) for s in steps] == [
        s["q_tokens"] for s in want["engine_steps_in_window"]]
    for s, w in zip(steps, want["engine_steps_in_window"]):
        assert int(s.stats["kv_read_tokens"]) == w["kv_read_tokens"] > 0


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_share_gives_nothing_to_read(name):
    """PR 25's program (the dense chat cell's recorded trace): no windowed
    call, no live-tile GEMM, no such registry series; each new reader
    returns None and raises nothing."""
    with open(os.path.join(DATA, "recorded_packed_trace.json")) as f:
        want = json.load(f)
    run = _run(os.path.join(DATA, "recorded_packed_trace.xplane.pb"), want,
               {}, dict(want["heads"], num_hidden_layers=12))
    assert _reader(name).read(run) is None
