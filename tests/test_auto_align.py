"""Serial-vs-distributed alignment tool (reference auto_align_tool.py:46
AutoAlignTool + find_diff_vars:382)."""

import numpy as np
import pytest

from paddle_tpu.distributed.auto_parallel.align import (AutoAlignTool,
                                                        align_pretrain_configs)


def _tools(diverge=False):
    a, b = AutoAlignTool(), AutoAlignTool()
    rng = np.random.default_rng(0)
    w = rng.standard_normal((3, 4)).astype(np.float32)
    for step in range(2):
        a.capture(step, loss=np.float32(1.0 + step),
                  params={"w": w + step})
        wb = w + step
        if diverge and step == 1:
            wb = wb + 1e-2
        b.capture(step, loss=np.float32(1.0 + step), params={"w": wb})
    return a, b


def test_aligned_runs_report_clean():
    a, b = _tools(diverge=False)
    assert AutoAlignTool.find_diff_vars(a, b) == []
    assert "aligned" in AutoAlignTool.diff_report(a, b)


def test_divergence_pinpoints_step_and_var():
    a, b = _tools(diverge=True)
    diffs = AutoAlignTool.find_diff_vars(a, b)
    assert diffs and diffs[0][0] == 1 and "w" in diffs[0][1]
    rep = AutoAlignTool.diff_report(a, b)
    assert "FIRST DIVERGENCE at step 1" in rep


def test_save_load_roundtrip(tmp_path):
    a, _ = _tools()
    a.save(str(tmp_path / "dump"))
    loaded = AutoAlignTool.load(str(tmp_path / "dump"))
    assert AutoAlignTool.find_diff_vars(a, loaded) == []


def test_missing_and_shape_mismatch_are_divergent():
    a, b = AutoAlignTool(), AutoAlignTool()
    a.capture(0, params={"w": np.zeros((2, 2), np.float32)})
    b.capture(0, params={"w": np.zeros((2, 3), np.float32),
                         "extra": np.zeros(1, np.float32)})
    diffs = AutoAlignTool.find_diff_vars(a, b)
    assert {d[1].split("[")[0].split("'")[0] for d in diffs}  # both reported
    assert all(d[2] == float("inf") for d in diffs)
    assert len(diffs) == 2


@pytest.mark.parametrize("hybrid", [dict(dp=2, mp=2), dict(dp=2),
                                    dict(mp=2), dict(dp=2, mp=2, remat=True)],
                         ids=["dp2mp2", "dp2", "mp2", "dp2mp2_remat"])
def test_pretrain_serial_vs_hybrid_aligns(hybrid):
    """The headline workflow: the SAME model under serial and dp x mp
    topologies must align step-for-step (canonical param layout).  The
    step's asynchronous sums are asked of the TPU's compiler alone: on
    these host devices every mesh compiles with no option and the sums stay
    what they were."""
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    assert PretrainStep(cfg, ParallelConfig(**hybrid))._compile_kwargs() == {}
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (8, 16)).astype("int32")
    labels = rng.integers(0, 256, (8, 16)).astype("int32")
    diffs, report = align_pretrain_configs(
        cfg, ParallelConfig(), ParallelConfig(**hybrid),
        ids, labels, steps=2, rtol=2e-3, atol=2e-4)
    assert diffs == [], report


def test_pretrain_divergence_detected():
    """Different seeds must be flagged at step 0, naming a parameter."""
    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, (4, 16)).astype("int32")
    labels = rng.integers(0, 256, (4, 16)).astype("int32")

    tools = []
    for seed in (0, 1):
        ps = PretrainStep(cfg, ParallelConfig())
        state = ps.init_state(seed=seed)
        si, sl = ps.shard_batch(ids, labels)
        t = AutoAlignTool()
        state, loss = ps.train_step(state, si, sl)
        t.capture(0, loss=loss, params=ps.canonical_state(state)["params"])
        tools.append(t)
    diffs = AutoAlignTool.find_diff_vars(*tools)
    assert diffs and diffs[0][0] == 0
    assert "FIRST DIVERGENCE" in AutoAlignTool.diff_report(*tools)
