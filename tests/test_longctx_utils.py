"""Ring attention (context parallelism), fleet utils (recompute, SP utils),
group_sharded API, watchdog, auto-tuner, launch CLI."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn


# ---- ring attention ----
@pytest.fixture(scope="module")
def seq_mesh():
    return Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "sep"))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_parity(rng, seq_mesh, causal):
    from paddle_tpu.kernels.flash_attention import _reference_attention
    from paddle_tpu.kernels.ring_attention import ring_attention_arrays

    B, S, H, D = 2, 32, 4, 16
    mk = lambda: jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    out = ring_attention_arrays(q, k, v, seq_mesh, "sep", causal)
    ref = _reference_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_ring_attention_grad_and_jit(rng, seq_mesh):
    from paddle_tpu.kernels.flash_attention import _reference_attention
    from paddle_tpu.kernels.ring_attention import ring_attention_arrays

    B, S, H, D = 1, 16, 2, 8
    mk = lambda: jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
    q, k, v = mk(), mk(), mk()

    g1 = jax.grad(lambda q, k, v: (
        ring_attention_arrays(q, k, v, seq_mesh, "sep", True) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: (
        _reference_attention(q, k, v, True) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-4)

    sh = NamedSharding(seq_mesh, P(None, "sep", None, None))
    qs = jax.device_put(q, sh)
    out = jax.jit(lambda q, k, v: ring_attention_arrays(
        q, k, v, seq_mesh, "sep", True))(qs, jax.device_put(k, sh),
                                         jax.device_put(v, sh))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference_attention(q, k, v, True)),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_tensor_api_fallback(rng):
    # no mesh: degrades to flash attention
    from paddle_tpu.kernels.ring_attention import ring_flash_attention

    q = paddle.to_tensor(rng.standard_normal((1, 8, 2, 8)).astype(np.float32))
    out = ring_flash_attention(q, q, q, mesh=None, causal=True)
    assert out.shape == [1, 8, 2, 8]


# ---- recompute ----
def test_recompute_parity(rng):
    from paddle_tpu.distributed.fleet.utils import recompute

    paddle.seed(5)
    layer = nn.Sequential(nn.Linear(8, 16), nn.GELU(), nn.Linear(16, 8))
    x = paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32),
                         stop_gradient=False)
    y1 = recompute(layer, x)
    y2 = layer(x)
    np.testing.assert_allclose(y1.numpy(), y2.numpy(), rtol=1e-6)
    (y1 ** 2).sum().backward()
    g_re = x.grad.numpy().copy()
    assert all(p.grad is not None for p in layer.parameters())
    x.clear_grad()
    layer.clear_gradients()
    (y2 ** 2).sum().backward()
    np.testing.assert_allclose(g_re, x.grad.numpy(), rtol=1e-5)


def test_recompute_sequential(rng):
    from paddle_tpu.distributed.fleet.utils.recompute import recompute_sequential

    paddle.seed(6)
    fns = [nn.Linear(8, 8), nn.GELU(), nn.Linear(8, 8)]
    x = paddle.to_tensor(rng.standard_normal((2, 8)).astype(np.float32),
                         stop_gradient=False)
    y = recompute_sequential({"segments": 2}, fns, x)
    ref = x
    for f in fns:
        ref = f(ref)
    np.testing.assert_allclose(y.numpy(), ref.numpy(), rtol=1e-6)


# ---- sequence-parallel utils ----
def test_sequence_parallel_linears(rng):
    import paddle_tpu.distributed.fleet as fleet
    from paddle_tpu.distributed.fleet.utils.sequence_parallel_utils import (
        ColumnSequenceParallelLinear, RowSequenceParallelLinear, all_gather,
        scatter)

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 4}
    fleet.init(is_collective=True, strategy=strategy)
    col = ColumnSequenceParallelLinear(16, 32, gather_output=False,
                                       has_bias=True)
    row = RowSequenceParallelLinear(32, 16, input_is_parallel=True,
                                    has_bias=True)
    x = paddle.to_tensor(rng.standard_normal((8, 2, 16)).astype(np.float32))
    y = row(col(scatter(x)))
    expect = (x.numpy() @ col.weight.numpy() + col.bias.numpy()) \
        @ row.weight.numpy() + row.bias.numpy()
    np.testing.assert_allclose(y.numpy(), expect, rtol=2e-4, atol=2e-5)
    g = all_gather(y)
    np.testing.assert_allclose(g.numpy(), y.numpy(), rtol=1e-6)


# ---- group_sharded ----
def test_group_sharded_parallel_levels(rng):
    import paddle_tpu.optimizer as opt
    from paddle_tpu.distributed import group_sharded_parallel
    from paddle_tpu.distributed.auto_parallel.process_mesh import set_mesh

    set_mesh(None)
    from paddle_tpu.distributed.fleet.topology import set_hcg
    set_hcg(None)
    paddle.seed(0)
    layer = nn.Linear(16, 8)
    adam = opt.AdamW(0.01, parameters=layer.parameters())
    model, optimizer, _ = group_sharded_parallel(layer, adam, "os")
    x = paddle.to_tensor(rng.standard_normal((4, 16)).astype(np.float32))
    (model(x) ** 2).mean().backward()
    optimizer.step()
    m = optimizer._accumulators["moment1"][id(layer.weight)]
    assert {s.data.shape for s in m.addressable_shards} == {(2, 8)}

    with pytest.raises(ValueError):
        group_sharded_parallel(layer, adam, "bogus")


# ---- watchdog ----
def test_watchdog_detects_hang():
    import time

    from paddle_tpu.distributed.watchdog import CommTaskManager, watch

    paddle.set_flags({"comm_timeout_s": 1})
    try:
        mgr = CommTaskManager().start()
        tid = mgr.begin("stuck_collective")
        for _ in range(40):
            if mgr.timed_out:
                break
            time.sleep(0.1)
        assert mgr.timed_out and mgr.timed_out[0].name == "stuck_collective"
        mgr.end(tid)
        mgr.shutdown()
    finally:
        paddle.set_flags({"comm_timeout_s": 600})


def test_barrier_timeout_ok():
    from paddle_tpu.distributed.watchdog import barrier_timeout

    import paddle_tpu.distributed as dist
    dist.init_parallel_env()
    assert barrier_timeout(timeout_s=30)


# ---- auto tuner ----
def test_auto_tuner_search():
    from paddle_tpu.distributed.auto_tuner import AutoTuner

    tuner = AutoTuner(8, hidden=1024, num_layers=8, heads=16, seq=512,
                      global_batch=16)
    ranked = tuner.search_all()
    assert ranked
    cfgs = [r.config for r in ranked]
    for c in cfgs:
        assert c["dp"] * c["mp"] * c["pp"] == 8
        assert 8 % c["pp"] == 0 and 16 % c["mp"] == 0
    best = tuner.tune()
    assert best is not None and best.cost == ranked[0].cost


# ---- launch ----
def test_launch_single(tmp_path):
    script = tmp_path / "train.py"
    script.write_text("import sys; print('RANK-OK', sys.argv[1:])\n")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         str(script), "--lr", "0.1"],
        capture_output=True, text=True, timeout=120,
        env={**__import__('os').environ, "JAX_PLATFORMS": "cpu"})
    assert "RANK-OK" in out.stdout and "--lr" in out.stdout
