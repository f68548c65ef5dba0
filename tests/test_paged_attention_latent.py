"""The latent paged-attention call (``ragged_paged_attention_latent``): the
Pallas kernel in interpret mode against the XLA oracle and against the
attention written out by hand from the pool's rows, where the value is the
first ``rank`` numbers of the key and a page is read once for both."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags
from paddle_tpu.kernels import paged_attention as pa

RANK, ROPE, PAGE, HEADS = 128, 64, 16, 4
SCALE = 0.1352


@pytest.fixture
def interpret():
    flags.set_flags({"paged_attention_interpret": True})
    yield
    flags.set_flags({"paged_attention_interpret": False})


def _case(rng, b, t, table, pages, layers=3, dtype=jnp.float32):
    q_c = jnp.asarray(rng.normal(size=(b, t, HEADS, RANK)), dtype)
    q_r = jnp.asarray(rng.normal(size=(b, t, HEADS, ROPE)), dtype)
    c = jnp.asarray(rng.normal(size=(layers, pages, PAGE, RANK)), dtype)
    r = jnp.asarray(rng.normal(size=(layers, pages, PAGE // 2, 2 * ROPE)),
                    dtype)
    bt = jnp.asarray(rng.permutation(pages)[:b * table].reshape(b, table),
                     jnp.int32)
    cn = jnp.asarray(rng.normal(size=(b, t, RANK)), dtype)
    rn = jnp.asarray(rng.normal(size=(b, t, ROPE)), dtype)
    return q_c, q_r, c, r, bt, cn, rn


def _by_hand(q_c, q_r, c, r, bt, ctx, ql, cn, rn, layer):
    """Dense softmax attention of every live query row over its slot's
    cached rows and the step's own rows up to itself: numpy, float64."""
    f = np.float64
    q_c, q_r, cn, rn = (np.asarray(a, f) for a in (q_c, q_r, cn, rn))
    c_l = np.asarray(c[layer], f)
    r_l = np.asarray(pa.unpack_rope_pages(r[layer], ROPE), f)
    out = np.zeros(q_c.shape, f)
    for b in range(q_c.shape[0]):
        n = int(ctx[b])
        pages = np.asarray(bt[b])
        keys_c = c_l[pages].reshape(-1, RANK)[:n]
        keys_r = r_l[pages].reshape(-1, ROPE)[:n]
        for j in range(int(ql[b])):
            kc = np.concatenate([keys_c, cn[b, :j + 1]])
            kr = np.concatenate([keys_r, rn[b, :j + 1]])
            s = (q_c[b, j] @ kc.T + q_r[b, j] @ kr.T) * SCALE   # [H, keys]
            p = np.exp(s - s.max(-1, keepdims=True))
            out[b, j] = (p / p.sum(-1, keepdims=True)) @ kc     # value = c
    return out


@pytest.mark.parametrize("t,ql", [(8, (8, 0, 1, 5)), (1, (1, 1, 0, 1))],
                         ids=["mixed", "decode"])
def test_kernel_is_the_oracle_and_the_attention_by_hand(
        interpret, monkeypatch, rng, t, ql):
    """Ragged ``q_lens``, a slot without work, contexts that end inside a
    block (37), at its edge (64), past it (130) and an empty one, over
    blocks of four pages and row tiles of 16: the kernel, the XLA oracle
    and the attention by hand agree on every live row."""
    monkeypatch.setattr(pa, "_BLOCK_KEYS", 64)
    monkeypatch.setattr(pa, "_ROW_TILE", 16)
    q_c, q_r, c, r, bt, cn, rn = _case(rng, 4, t, 9, 40)
    ctx = jnp.asarray([0, 37, 64, 130], jnp.int32)
    ql = jnp.asarray(ql, jnp.int32)
    layer = jnp.int32(1)
    kw = dict(scale=SCALE, q_lens=ql, c_new=cn, r_new=rn, layer=layer)
    got = np.asarray(pa.ragged_paged_attention_latent(
        q_c, q_r, c, r, bt, ctx, **kw))
    flags.set_flags({"paged_attention_interpret": False})
    oracle = np.asarray(pa.ragged_paged_attention_latent(
        q_c, q_r, c, r, bt, ctx, **kw))
    hand = _by_hand(q_c, q_r, c, r, bt, ctx, ql, cn, rn, 1)
    for b in range(4):
        n = int(ql[b])
        np.testing.assert_allclose(got[b, :n], oracle[b, :n], atol=3e-5)
        np.testing.assert_allclose(got[b, :n], hand[b, :n], atol=3e-5)
    # a slot without work is written as zeros, never what memory held
    idle = int(np.argmin(np.asarray(ql)))
    assert not got[idle].any()


def test_one_layers_cache_without_own_rows(interpret, monkeypatch, rng):
    """The call on one layer's pool (no ``layer``) with nothing of the step
    folded in: decode over what is cached alone."""
    monkeypatch.setattr(pa, "_BLOCK_KEYS", 32)
    q_c, q_r, c, r, bt, _, _ = _case(rng, 2, 1, 5, 12, layers=1)
    ctx = jnp.asarray([70, 16], jnp.int32)
    got = np.asarray(pa.ragged_paged_attention_latent(
        q_c, q_r, c[0], r[0], bt, ctx, scale=SCALE))
    flags.set_flags({"paged_attention_interpret": False})
    want = np.asarray(pa.ragged_paged_attention_latent(
        q_c, q_r, c[0], r[0], bt, ctx, scale=SCALE))
    np.testing.assert_allclose(got, want, atol=3e-5)
    with pytest.raises(ValueError, match="whole pool"):
        pa.ragged_paged_attention_latent(q_c, q_r, c, r, bt, ctx, scale=SCALE)


def test_bf16_pool_rounds_the_probabilities_and_stays_close(interpret, rng):
    """A bf16 pool: operands enter the products as stored and the
    probabilities enter ``PV`` in bf16; against the float32 oracle on the
    same bf16 values the result stays within bf16's rounding."""
    q_c, q_r, c, r, bt, cn, rn = _case(rng, 2, 8, 6, 16, dtype=jnp.bfloat16)
    ctx = jnp.asarray([50, 90], jnp.int32)
    ql = jnp.asarray([8, 3], jnp.int32)
    kw = dict(scale=SCALE, q_lens=ql, c_new=cn, r_new=rn, layer=jnp.int32(2))
    got = pa.ragged_paged_attention_latent(q_c, q_r, c, r, bt, ctx, **kw)
    assert got.dtype == jnp.bfloat16
    f32 = [a.astype(jnp.float32) for a in (q_c, q_r, c, r)]
    flags.set_flags({"paged_attention_interpret": False})
    want = pa.ragged_paged_attention_latent(
        *f32, bt, ctx, scale=SCALE, q_lens=ql,
        c_new=cn.astype(jnp.float32), r_new=rn.astype(jnp.float32),
        layer=jnp.int32(2))
    for b, n in ((0, 8), (1, 3)):
        np.testing.assert_allclose(np.asarray(got[b, :n], np.float32),
                                   np.asarray(want[b, :n]), atol=0.06)


def test_a_page_is_read_once_for_key_and_value(interpret, rng):
    """Poison everything of the pool the slot's table does not name, and
    the rotary half-rows beyond the context: the result does not move, so
    keys and values come from the named pages' rows alone, the value from
    the compressed part that made the score."""
    q_c, q_r, c, r, bt, cn, rn = _case(rng, 1, 4, 3, 8, layers=1)
    ctx = jnp.asarray([40], jnp.int32)
    kw = dict(scale=SCALE, c_new=cn, r_new=rn)
    base = np.asarray(pa.ragged_paged_attention_latent(
        q_c, q_r, c[0], r[0], bt, ctx, **kw))
    named = np.asarray(bt[0])
    others = np.setdiff1d(np.arange(8), named)
    c2 = c[0].at[others].set(1e4)
    r2 = r[0].at[others].set(1e4)
    # the third named page holds tokens 32..47: 40..47 lie past the context
    c2 = c2.at[named[2], 8:].set(1e4)
    r2 = r2.at[named[2], :, ROPE:].set(1e4)
    moved = np.asarray(pa.ragged_paged_attention_latent(
        q_c, q_r, c2, r2, bt, ctx, **kw))
    np.testing.assert_allclose(moved, base, atol=1e-6)


def test_the_commit_writes_a_token_into_its_half_row(rng):
    c = jnp.zeros((2, 6, PAGE, RANK), jnp.float32)
    r = jnp.zeros((2, 6, PAGE // 2, 2 * ROPE), jnp.float32)
    slots = jnp.asarray([-1, 5, 3 * PAGE + 9, 5 * PAGE + 15, -1, 2 * PAGE],
                        jnp.int32)
    ca = jnp.asarray(rng.normal(size=(2, 6, RANK)), jnp.float32)
    ra = jnp.asarray(rng.normal(size=(2, 6, ROPE)), jnp.float32)
    c2, r2 = pa.write_latent_pages_all_layers(c, r, ca, ra, slots)
    flat_c = np.asarray(c2).reshape(2, -1, RANK)
    flat_r = np.asarray(pa.unpack_rope_pages(r2, ROPE)).reshape(2, -1, ROPE)
    written = [int(s) for s in slots if s >= 0]
    for i, s in enumerate(np.asarray(slots)):
        if s >= 0:
            assert np.array_equal(flat_c[:, s], np.asarray(ca[:, i]))
            assert np.array_equal(flat_r[:, s], np.asarray(ra[:, i]))
    rest = np.setdiff1d(np.arange(6 * PAGE), written)
    assert not flat_c[:, rest].any() and not flat_r[:, rest].any()
    # token 9 of a page: row 1, the upper lanes
    assert np.array_equal(np.asarray(r2)[:, 3, 1, ROPE:], np.asarray(ra[:, 2]))


@pytest.mark.parametrize("page,rank,rope,interp,ok", [
    (16, 512, 64, False, True), (32, 512, 64, False, True),
    (8, 512, 64, False, False), (16, 192, 64, False, False),
    (16, 512, 32, False, False), (8, 128, 64, True, True),
    (7, 128, 64, True, False)])
def test_the_geometry_rule_of_the_latent_call(page, rank, rope, interp, ok):
    why = pa.kernel_geometry_error(page, 0, latent=(rank, rope),
                                   interpret=interp)
    assert (why is None) == ok, why
    assert pa.kernel_geometry_error(16, 0, latent=(512, 64),
                                    quantized=True) is not None


def test_a_head_half_a_tile_wide_is_refused_where_the_compiler_refuses_it():
    """ROADMAP M12: ``head_dim % 128 == 64`` compiles for no TPU (the pool's
    rows cannot be sliced out in half tiles), so the rule refuses it for the
    compiled kernel; the interpreter has no tiling and keeps running it."""
    assert pa.kernel_geometry_error(16, 128) is None
    assert "multiple of 128" in pa.kernel_geometry_error(16, 64)
    assert "multiple of 128" in pa.kernel_geometry_error(16, 192)
    assert pa.kernel_geometry_error(16, 64, interpret=True) is None
    assert pa.kernel_geometry_error(16, 32, interpret=True) is not None
