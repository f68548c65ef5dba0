"""What the train step waits for: the all-reduces of its compiled program,
counted from the scheduled HLO (``observability/collectives.py``),
left in the registry once the program is built, and the compiler options
that make them asynchronous chosen by what the mesh is made of."""

import types

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import pretrain
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep
from paddle_tpu.observability.collectives import find_all_reduces

# cut from the dp 2 x mp 2 step compiled for a described v5e:2x2 (PR 44):
# a loop body with one synchronous sum, one asynchronous pair around the
# product that carries it (three fused computations, an all-reduce in each),
# an all-gather pair that is no all-reduce, and the entry with a combined
# sum and a start/done as the CPU and GPU compilers write them
HLO = """\
HloModule jit_pretrain_step, is_scheduled=true

%fused_start (param_0.1: bf16[2,4096,4096]) -> (bf16[2,4096,4096], bf16[2,4096,4096], s32[2]) {
  %param_0.1 = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.88 = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} all-reduce(%param_0.1), channel_id=3, replica_groups=[2,2]<=[4], to_apply=%add
}

%async_collective_fusion.522 (param_0.2: bf16[2,4096,4096]) -> bf16[2,4096,7168] {
  %param_0.2 = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.90 = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} all-reduce(%param_0.2), channel_id=3, replica_groups=[2,2]<=[4], to_apply=%add
}

%fused_done (param_0.3: bf16[2,4096,4096]) -> bf16[2,4096,4096] {
  %param_0.3 = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %all-reduce.92 = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} all-reduce(%param_0.3), channel_id=3, replica_groups=[2,2]<=[4], to_apply=%add
}

%fused_gather (param_0.4: bf16[2,2048,4096]) -> (bf16[2,2048,4096], bf16[2,4096,4096], s32[2]) {
  %param_0.4 = bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %all-gather.7 = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} all-gather(%param_0.4), channel_id=5, dimensions={1}
}

%wide.region_13.29_spmd.sunk (wide.param: (u32[], bf16[2,4096,4096])) -> (u32[], bf16[2,4096,4096]) {
  %fusion.498 = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} fusion(%bitcast.723), kind=kOutput, calls=%fused_computation.174
  %async-collective-start = (bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)}, bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)}, s32[2]{0:S(4)}) fusion(%fusion.498), kind=kCustom, calls=%fused_start
  %fusion.522 = bf16[2,4096,7168]{2,1,0:T(8,128)(2,1)} fusion(%get-tuple-element.1), kind=kOutput, calls=%async_collective_fusion.522
  %async-collective-done = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} fusion(%get-tuple-element.2), kind=kCustom, calls=%fused_done
  %async-collective-start.1 = (bf16[2,2048,4096]{2,1,0:T(8,128)(2,1)}, bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)}, s32[2]{0:S(4)}) fusion(%fusion.499), kind=kCustom, calls=%fused_gather
  %all-reduce.143 = bf16[2,4096,4096]{2,1,0:T(8,128)(2,1)} all-reduce(%convolution_add_fusion.8), channel_id=41, replica_groups=[2,2]<=[4], to_apply=%add
}

ENTRY %main.36_spmd (param: bf16[16384,4096]) -> f32[] {
  %all-reduce.53 = (bf16[1,7168,4096]{2,1,0:T(8,128)(2,1)}, bf16[1,4096,512]{2,1,0:T(8,128)(2,1)S(1)}) all-reduce(%fusion.517, %fusion.518), channel_id=11, replica_groups=[2,2]<=[2,2]T(1,0), to_apply=%add
  %all-reduce-start.2 = f32[2,4096]{1,0} all-reduce-start(%fusion.9), channel_id=12, replica_groups=[2,2]<=[4], to_apply=%add
  %all-reduce-done.2 = f32[2,4096]{1,0} all-reduce-done(%all-reduce-start.2)
}
"""


def test_all_reduces_are_found_in_scheduled_hlo():
    assert find_all_reduces(HLO) == [
        (False, False, "bf16[2,4096,4096]"),
        (True, False, "(bf16[1,7168,4096], bf16[1,4096,512])"),
        (True, True, "f32[2,4096]"),
        (False, True, "bf16[2,4096,4096]")]
    assert find_all_reduces("HloModule empty\n") == []


def _mesh_of(platform, n):
    chip = types.SimpleNamespace(platform=platform)
    return types.SimpleNamespace(size=n, devices=np.asarray([chip] * n))


@pytest.mark.parametrize("platform,n,asks", [
    ("tpu", 4, True), ("tpu", 2, True), ("tpu", 1, False),
    ("cpu", 4, False), ("cpu", 1, False), ("gpu", 4, False)])
def test_asynchronous_sums_are_asked_of_several_tpu_chips_only(platform, n,
                                                               asks):
    """Chosen by what the code observes (the mesh's size, its devices'
    platform): no flag, no environment variable, no configuration key.
    Another platform's compiler refuses the TPU compiler's names, and one
    device has no collective: both get nothing at all."""
    ps = PretrainStep.__new__(PretrainStep)
    ps.mesh = _mesh_of(platform, n)
    want = {"compiler_options": pretrain._ASYNC_SUMS} if asks else {}
    assert ps._compile_kwargs() == want


@pytest.mark.parametrize("layout,options,expect_some", [
    (dict(dp=2, mp=2), None, True), (dict(), None, False),
    (dict(pp=2, micro_batches=2), None, True),
    (dict(dp=2, grad_comm="ring"), None, True),
    (dict(dp=2, mp=2), {"xla_cpu_enable_fast_min_max": True}, True)],
    ids=["dp2mp2", "one_device", "pp2", "dp2_ring", "dp2mp2_with_an_option"])
def test_the_first_step_leaves_the_programs_sums_in_the_registry(
        layout, options, expect_some, monkeypatch):
    """``train.collectives`` / ``train.collectives_async`` after the first
    step, read from what jax already holds: no compile of its own, also
    where the program carries a compiler option (as on several TPU chips;
    here one the host's compiler knows)."""
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    ps = PretrainStep(cfg, ParallelConfig(**layout))
    if options:
        monkeypatch.setattr(ps, "_compile_kwargs",
                            lambda: {"compiler_options": options})
    state = ps.init_state(0)
    rng = np.random.default_rng(0)
    ids, labels = ps.shard_batch(
        rng.integers(0, 256, (8, 16)).astype("int32"),
        rng.integers(0, 256, (8, 16)).astype("int32"))
    for g in ("train.collectives", "train.collectives_async"):
        obs.metrics.gauge(g).set(-1)
    with obs.assert_overhead(max_compiles=1, record=True) as first:
        state, loss = ps.train_step(state, ids, labels)
    assert first.compiles == 1           # the step; its count compiled none
    total = obs.metrics.gauge("train.collectives").value
    assert (total > 0) == expect_some
    assert obs.metrics.gauge("train.collectives_async").value == 0
    with obs.assert_overhead(max_compiles=0):
        assert ps.count_collectives(state, ids, labels) == (total, 0)
        state, loss = ps.train_step(state, ids, labels)   # counts once
    assert np.isfinite(float(loss))
