"""Ring-collective building blocks (ISSUE 3): quantize/dequantize bounds,
stochastic-rounding unbiasedness, ring reduce-scatter / all-reduce == psum
parity on the 8-device CPU mesh (ragged tails included), determinism, and
the bytes-moved accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu  # noqa: F401
from paddle_tpu.distributed import quantized_collectives as qc


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def _ring(fn, n, *arrays, out_specs=P("dp")):
    """Run a per-device fn over an n-way 'dp' ring; inputs are [n, ...]."""
    return jax.jit(jax.shard_map(
        fn, mesh=_mesh(n), in_specs=tuple(P("dp") for _ in arrays),
        out_specs=out_specs, check_vma=False))(*arrays)


# ---------------------------------------------------------------- quantize --

@pytest.mark.parametrize("m", [256, 1024, 300, 5])  # exact and ragged tails
def test_quantize_roundtrip_error_bound(rng, m):
    x = jnp.asarray(rng.standard_normal(m).astype(np.float32)) * 3.0
    q, s = qc.quantize_blockwise(x, block=256)
    y = qc.dequantize_blockwise(q, s, m)
    assert y.shape == (m,)
    # nearest rounding: |err| <= scale/2 per block, elementwise
    scales = np.repeat(np.asarray(s), 256)[:m]
    np.testing.assert_array_less(np.abs(np.asarray(y - x)),
                                 scales / 2 + 1e-12)


def test_quantize_stochastic_error_bound_and_zero(rng):
    m = 300
    x = jnp.asarray(rng.standard_normal(m).astype(np.float32))
    q, s = qc.quantize_blockwise(x, block=64, key=jax.random.PRNGKey(0))
    y = qc.dequantize_blockwise(q, s, m)
    scales = np.repeat(np.asarray(s), 64)[:m]
    # stochastic rounding moves at most one quantization step
    np.testing.assert_array_less(np.abs(np.asarray(y - x)), scales + 1e-12)
    # exact zeros stay exact (pad rows rely on this)
    q0, s0 = qc.quantize_blockwise(jnp.zeros(128), block=64,
                                   key=jax.random.PRNGKey(1))
    assert np.all(np.asarray(q0) == 0)
    np.testing.assert_allclose(np.asarray(qc.dequantize_blockwise(q0, s0)), 0)


def test_stochastic_rounding_unbiased(rng):
    # mean over many independent draws converges to the input
    m, draws = 64, 600
    x = jnp.asarray(rng.standard_normal(m).astype(np.float32))

    def one(k):
        q, s = qc.quantize_blockwise(x, block=64, key=k)
        return qc.dequantize_blockwise(q, s, m)

    keys = jax.random.split(jax.random.PRNGKey(7), draws)
    ys = jax.vmap(one)(keys)
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    err = np.asarray(jnp.mean(ys, 0) - x)
    # SE of the mean of a +-scale/2-bounded rounding is ~scale/sqrt(12*draws)
    assert np.max(np.abs(err)) < 5 * scale / np.sqrt(12 * draws)


# -------------------------------------------------------------------- ring --

@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("m", [512, 520, 72])   # 520, 72: ragged vs 256-block
def test_ring_reduce_scatter_matches_psum_scatter(rng, n, m):
    m = -(-m // n) * n  # callers pad buckets to the ring size
    x = jnp.asarray(rng.standard_normal((n, m)).astype(np.float32))

    rs = _ring(lambda v: qc.ring_reduce_scatter(v[0], "dp", axis_size=n)[None],
               n, x)
    ref = _ring(lambda v: lax.psum_scatter(
        v[0].reshape(n, -1), "dp", scatter_dimension=0, tiled=False)[None],
        n, x)
    np.testing.assert_allclose(np.asarray(rs).reshape(-1),
                               np.asarray(ref).reshape(-1),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n", [2, 4, 8])
def test_ring_all_reduce_fp32_matches_psum(rng, n):
    m = 72 * n  # ragged against the 64-block below
    x = jnp.asarray(rng.standard_normal((n, m)).astype(np.float32))
    out = _ring(lambda v: qc.ring_all_reduce(v[0], "dp", axis_size=n)[0][None],
                n, x)
    ref = np.asarray(x).sum(0)
    for d in range(n):
        np.testing.assert_allclose(np.asarray(out)[d], ref,
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [2, 4, 8])
def test_ring_all_reduce_int8_within_quant_bound(rng, n):
    m = 72 * n
    x = jnp.asarray(rng.standard_normal((n, m)).astype(np.float32))
    key = jax.random.PRNGKey(3)

    def f(v):
        out, _ = qc.ring_all_reduce(v[0], "dp", axis_size=n, int8=True,
                                    block=64, key=key)
        return out[None]

    out = np.asarray(_ring(f, n, x))
    ref = np.asarray(x).sum(0)
    # every device must hold IDENTICAL bits (replicated params depend on it)
    for d in range(1, n):
        np.testing.assert_array_equal(out[d], out[0])
    # error: n-1 requantized hops + the all-gather quantization, each step
    # bounded by one block scale; bound conservatively via the max |partial|
    scale_bound = (np.abs(np.asarray(x)).sum(0).max() / 127.0) * (n + 1)
    assert np.max(np.abs(out[0] - ref)) <= scale_bound


def test_ring_int8_deterministic_per_step(rng):
    n, m = 4, 256 * 4
    x = jnp.asarray(rng.standard_normal((n, m)).astype(np.float32))

    def run(step):
        key = jax.random.fold_in(jax.random.PRNGKey(qc.GRAD_COMM_SEED), step)

        def f(v):
            return qc.ring_all_reduce(v[0], "dp", axis_size=n, int8=True,
                                      block=64, key=key)[0][None]

        return np.asarray(_ring(f, n, x))

    a, b = run(5), run(5)
    np.testing.assert_array_equal(a, b)          # bit-exact per step
    assert np.any(run(6) != a)                   # new step, new rounding


def test_ring_all_reduce_error_feedback_residual(rng):
    n, m = 4, 64 * 4
    x = jnp.asarray(rng.standard_normal((n, m)).astype(np.float32))
    ef = jnp.zeros((n, m // n), jnp.float32)
    key = jax.random.PRNGKey(11)

    def f(v, e):
        out, new_e = qc.ring_all_reduce(v[0], "dp", axis_size=n, int8=True,
                                        block=64, key=key,
                                        error_feedback=e[0])
        return out[None], new_e[None]

    out, new_ef = jax.jit(jax.shard_map(
        f, mesh=_mesh(n), in_specs=(P("dp"), P("dp")),
        out_specs=(P("dp"), P("dp")), check_vma=False))(x, ef)
    # the residual is exactly what the broadcast dropped: adding it back to
    # the dequantized own-chunk recovers the fp32 reduce-scatter output
    rs = _ring(lambda v: qc.ring_reduce_scatter(
        v[0], "dp", axis_size=n, int8=True, block=64, key=key)[None], n, x)
    own = np.asarray(out).reshape(n, n, -1)[np.arange(n), np.arange(n)]
    np.testing.assert_allclose(own + np.asarray(new_ef).reshape(n, -1),
                               np.asarray(rs).reshape(n, -1),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------- buckets --

def test_bucket_plan_pack_unpack_roundtrip(rng):
    leaves = [jnp.asarray(rng.standard_normal(s).astype(dt))
              for s, dt in [((3, 5), np.float32), ((7,), np.float32),
                            ((2, 2, 2), np.float16), ((11,), np.float32),
                            ((1,), np.float16)]]
    plan = qc.bucket_plan(leaves, bucket_elems=16, ring_size=4)
    # per-dtype grouping, no leaf splits, ring-divisible padding
    for b in plan:
        assert b["padded"] % 4 == 0 and b["padded"] >= b["size"]
        for i, sz in b["items"]:
            assert jnp.dtype(leaves[i].dtype) == b["dtype"]
            assert sz == leaves[i].size
    covered = sorted(i for b in plan for i, _ in b["items"])
    assert covered == list(range(len(leaves)))

    out = list(leaves)
    for b in plan:
        buf = qc.pack_bucket(leaves, b)
        assert buf.shape == (b["padded"],) and buf.dtype == jnp.float32
        qc.unpack_bucket(buf, b, leaves, out)
    for a, b_ in zip(leaves, out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-3)
        assert a.dtype == b_.dtype and a.shape == b_.shape


def test_bucket_plan_large_leaf_own_bucket():
    leaves = [jnp.zeros((100,), jnp.float32), jnp.zeros((3,), jnp.float32)]
    plan = qc.bucket_plan(leaves, bucket_elems=10, ring_size=8)
    assert len(plan) == 2 and plan[0]["items"] == [(0, 100)]
    assert plan[0]["padded"] == 104  # next multiple of 8


# ------------------------------------------------- ProcessGroup API surface --

def test_communication_quantized_all_reduce_eager(rng):
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    n = dist.get_world_size()
    x = rng.standard_normal((n, 37)).astype(np.float32)  # ragged vs block
    t = paddle_tpu.to_tensor(x.copy())
    task = dist.quantized_all_reduce(t, block=64)
    task.wait()
    out = np.asarray(t._data)
    ref = x.sum(0)
    scale = np.abs(x).sum(0).max() / 127.0 * (n + 1)
    for d in range(n):
        assert np.max(np.abs(out[d] - ref)) <= scale
        np.testing.assert_array_equal(out[d], out[0])


def test_communication_quantized_reduce_scatter_eager(rng):
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    n = dist.get_world_size()
    x = rng.standard_normal((n, n, 5)).astype(np.float32)
    t = paddle_tpu.to_tensor(x.copy())
    dist.quantized_reduce_scatter(t, block=64).wait()
    out = np.asarray(t._data)               # [n, 5]: rank d's chunk d
    ref = x.sum(0)                          # [n, 5]
    scale = np.abs(x).sum(0).max() / 127.0 * (n + 1)
    assert np.max(np.abs(out - ref)) <= scale


# -------------------------------------------------------------- accounting --

def test_bytes_moved_int8_ratio():
    n, m = 8, 1 << 20
    fp32 = qc.bytes_moved(m, n, "ring")
    i8 = qc.bytes_moved(m, n, "ring_int8", block=256)
    assert fp32 == 2 * (n - 1) * (m // n) * 4
    assert 3.8 < fp32 / i8 <= 4.0       # ~4x fewer gradient bytes
    assert qc.bytes_moved(m, 1, "ring") == 0
