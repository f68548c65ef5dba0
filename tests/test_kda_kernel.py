"""``kernels/kda.py``: the ragged gated-delta-rule update (interpreted on the
CPU) and its XLA oracle against the bare recurrence, slot by slot."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags
from paddle_tpu.kernels import kda

H, K, V = 4, 16, 32


def _tokens(seed, rows, dtype=jnp.float32):
    """Packed operands as the engine hands them: unit ``q`` and ``k`` a
    head, a log decay that runs from near none to a few tokens' half-life,
    ``beta`` in (0, 2)."""
    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(key, width):
        x = jax.random.normal(key, (rows, H, width), jnp.float32)
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = (unit(ks[0], K) * K ** -0.5).astype(dtype)
    k = unit(ks[1], K).astype(dtype)
    v = jax.random.normal(ks[2], (rows, H, V), jnp.float32).astype(dtype)
    g = -jnp.exp(jax.random.normal(ks[3], (rows, H, K)) * 2.0 - 2.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (rows, H)))
    return q, k, v, g, beta


def _by_slot(state, ops, starts, q_lens, fresh):
    """The bare recurrence a slot: (o [rows, H, V] zero where no token lies,
    state [B, H, K, V])."""
    q = ops[0]
    o = np.zeros((q.shape[0], H, V), np.float32)
    new = np.array(state, np.float32)
    for b, (s, n) in enumerate(zip(starts, q_lens)):
        if n == 0:
            continue
        s0 = jnp.zeros_like(state[b]) if fresh[b] else state[b]
        ob, sb = kda.kda_recurrence(s0, *(a[s:s + n] for a in ops))
        o[s:s + n], new[b] = ob, sb
    return o, new


def _call(interpret, *args, **kw):
    flags.set_flags({"paged_attention_interpret": bool(interpret)})
    try:
        return kda.ragged_kda_update(*args, **kw)
    finally:
        flags.set_flags({"paged_attention_interpret": False})


CASES = {
    # name: (starts, q_lens, fresh, rows, chunk)
    "one_token": ([0, 1, 2], [1, 1, 1], [0, 0, 0], 3, 1),
    "one_token_idle": ([0, 1, 2, 3], [1, 0, 1, 0], [0, 0, 1, 0], 4, 1),
    "whole_chunk": ([0], [8], [0], 8, 8),
    "packed_mix": ([0, 1, 1, 6, 7, 7], [1, 0, 5, 1, 0, 8],
                   [0, 0, 1, 0, 0, 0], 24, 8),
    "dense_grid": ([0, 8, 16, 24], [3, 0, 1, 8], [1, 0, 0, 0], 32, 8),
    "nothing_live": ([0, 0], [0, 0], [0, 0], 8, 8),
    "chunk_at_the_end": ([0, 1], [1, 7], [0, 1], 8, 8),
}


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["oracle", "interpreted"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_ragged_update_is_the_recurrence_slot_by_slot(case, interpret):
    starts, q_lens, fresh, rows, chunk = CASES[case]
    B = len(q_lens)
    ops = _tokens(3, rows)
    state = jax.random.normal(jax.random.key(9), (B, H, K, V), jnp.float32)
    want_o, want_s = _by_slot(state, ops, starts, q_lens, fresh)
    o, new = _call(interpret, state, *ops,
                   jnp.asarray(starts, jnp.int32),
                   jnp.asarray(q_lens, jnp.int32),
                   jnp.asarray(fresh, bool), chunk=chunk)
    # float32 throughout: the kernel folds beta into k and v (two roundings
    # of 2^-24 a product), nothing else differs from the lines as written
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(new), want_s, rtol=2e-5, atol=2e-6)
    idle = [b for b, n in enumerate(q_lens) if n == 0]
    np.testing.assert_array_equal(np.asarray(new)[idle],
                                  np.asarray(state)[idle])


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["oracle", "interpreted"])
def test_one_layer_of_a_whole_state_is_updated_where_it_lies(interpret):
    starts, q_lens, fresh, rows, chunk = CASES["packed_mix"]
    B = len(q_lens)
    ops = _tokens(5, rows)
    whole = jax.random.normal(jax.random.key(2), (3, B, H, K, V), jnp.float32)
    want_o, want_s = _by_slot(whole[1], ops, starts, q_lens, fresh)
    o, new = _call(interpret, whole, *ops,
                   jnp.asarray(starts, jnp.int32),
                   jnp.asarray(q_lens, jnp.int32), jnp.asarray(fresh, bool),
                   chunk=chunk, layer=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(new[1]), want_s, rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(whole[0]))
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(whole[2]))


def test_several_head_blocks_agree_with_one(monkeypatch):
    """A state block of two heads (four programs a slot pair) against the
    whole four at once."""
    starts, q_lens, fresh, rows, chunk = CASES["packed_mix"]
    ops = _tokens(7, rows)
    state = jax.random.normal(jax.random.key(4), (len(q_lens), H, K, V),
                              jnp.float32)
    args = (state, *ops, jnp.asarray(starts, jnp.int32),
            jnp.asarray(q_lens, jnp.int32), jnp.asarray(fresh, bool))
    whole = _call(True, *args, chunk=chunk)
    monkeypatch.setattr(kda, "_STATE_BLOCK_BYTES", 2 * K * V * 4)
    assert kda._heads_per_block(H, K, V) == 2
    halves = _call(True, *args, chunk=chunk)
    for a, b in zip(whole, halves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_slot_admitted_again_starts_from_zero():
    """What the last request left in a slot's state moves nothing once the
    slot is fresh."""
    ops = _tokens(11, 8)
    args = (*ops, jnp.asarray([0], jnp.int32), jnp.asarray([8], jnp.int32),
            jnp.asarray([True]))
    left = jax.random.normal(jax.random.key(1), (1, H, K, V),
                             jnp.float32) * 50.0
    for interpret in (False, True):
        a = _call(interpret, left, *args, chunk=8)
        z = _call(interpret, jnp.zeros_like(left), *args,
                  chunk=8)
        for x, y in zip(a, z):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_bf16_operands_keep_a_float32_state():
    starts, q_lens, fresh, rows, chunk = CASES["packed_mix"]
    q, k, v, g, beta = _tokens(13, rows, jnp.bfloat16)
    state = jnp.zeros((len(q_lens), H, K, V), jnp.float32)
    o, new = _call(True, state, q, k, v, g, beta,
                   jnp.asarray(starts, jnp.int32),
                   jnp.asarray(q_lens, jnp.int32), jnp.asarray(fresh, bool),
                   chunk=chunk)
    assert o.dtype == jnp.bfloat16 and new.dtype == jnp.float32
    want_o, want_s = _by_slot(state, (q, k, v, g, beta), starts, q_lens,
                              fresh)
    # the operands are bf16 (2^-9 a number), beta folded before rounding
    np.testing.assert_allclose(np.asarray(o, np.float32), want_o, atol=0.03)
    np.testing.assert_allclose(np.asarray(new), want_s, atol=0.03)


@pytest.mark.parametrize("heads,key,value,why", [
    (64, 128, 128, None), (64, 96, 128, "lanes"), (8, 128, 128, None),
    (24, 128, 128, "tiles")])
def test_geometry_the_compiled_kernel_takes(heads, key, value, why):
    got = kda.kda_geometry_error(heads, key, value)
    assert (got is None) if why is None else (why in got)


def test_refusals_of_the_wrapper():
    ops = _tokens(1, 8)
    one = jnp.zeros((2, H, K, V), jnp.float32)
    ints = (jnp.zeros((2,), jnp.int32),) * 2
    with pytest.raises(ValueError, match="one row a slot"):
        kda.ragged_kda_update(one, *ops, *ints, jnp.zeros((2,), bool),
                              chunk=1)
    with pytest.raises(ValueError, match="updated at `layer`"):
        kda.ragged_kda_update(one[None], *ops, *ints, jnp.zeros((2,), bool),
                              chunk=8)
    with pytest.raises(ValueError, match="hold no chunk"):
        kda.ragged_kda_update(one, *ops, *ints, jnp.zeros((2,), bool),
                              chunk=16)
