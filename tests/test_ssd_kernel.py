"""``kernels/ssd.py``: the ragged state-space scan call in interpreter mode
against its XLA oracle and against the bare recurrence: ragged ``q_lens``
of 0, 1, 7, 16 and 64 in one call, a non-zero entering state, a fresh slot,
the state aliased in place, one layer of a whole state, no live slot."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags
from paddle_tpu.kernels import ssd

B, T, H, P, N, G = 6, 64, 4, 16, 32, 2
Q_LENS = (0, 1, 7, 16, 64, 0)
FRESH = (False, False, True, False, False, False)


def _operands(T=T, seed=0):
    rng = np.random.default_rng(seed)

    def r(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    return dict(state=r(B, H, P, N), x=r(B, T, H, P), Bm=r(B, T, G, N),
                Cm=r(B, T, G, N), dt=jax.nn.softplus(r(B, T, H)),
                A=-jnp.exp(r(H)), D=r(H),
                q_lens=jnp.minimum(jnp.asarray(Q_LENS, jnp.int32), T),
                fresh=jnp.asarray(FRESH))


@pytest.fixture
def interpreted():
    flags.set_flags({"paged_attention_interpret": True})
    yield
    flags.set_flags({"paged_attention_interpret": False})


def _by_recurrence(o):
    """(y, state) a slot from the bare recurrence over its live tokens."""
    out = []
    for b in range(B):
        q = int(o["q_lens"][b])
        s0 = jnp.zeros_like(o["state"][b]) if o["fresh"][b] \
            else o["state"][b]
        out.append(ssd.ssd_recurrence(
            s0, o["x"][b, :q], o["Bm"][b, :q], o["Cm"][b, :q],
            o["dt"][b, :q], o["A"], o["D"]) if q else (None, o["state"][b]))
    return out


@pytest.mark.parametrize("path", ["xla_oracle", "interpreted_kernel"])
def test_the_chunk_form_equals_the_recurrence(path, request):
    """Both forms of the call against the recurrence over each slot's live
    tokens (float32: the sums are the same numbers in another order, 64
    terms deep)."""
    if path == "interpreted_kernel":
        request.getfixturevalue("interpreted")
    o = _operands()
    y, new = ssd.ragged_ssd_update(**o)
    for b, (yr, sr) in enumerate(_by_recurrence(o)):
        q = int(o["q_lens"][b])
        if q:
            np.testing.assert_allclose(y[b, :q], yr, atol=2e-4, rtol=1e-4)
            np.testing.assert_allclose(new[b], sr, atol=2e-4, rtol=1e-4)
        else:
            assert jnp.array_equal(new[b], o["state"][b])      # untouched
            assert not jnp.any(y[b])


@pytest.mark.parametrize("T_", [1, 16, 64])
def test_the_interpreted_kernel_equals_its_oracle(interpreted, T_):
    """The kernel against the XLA oracle at the step family's buckets (T =
    1 is padded to eight rows inside the kernel)."""
    o = _operands(T_)
    y, new = ssd.ragged_ssd_update(**o)
    y0, new0 = ssd._reference_ragged_ssd_update(**o)
    live = np.arange(T_)[None, :] < np.asarray(o["q_lens"])[:, None]
    # rows past a slot's ``q_lens`` are don't-care (the kernel's one-token
    # path writes zeros there, the chunk form what the padding gives)
    np.testing.assert_allclose(np.where(live[..., None, None], y, 0),
                               np.where(live[..., None, None], y0, 0),
                               atol=5e-5, rtol=1e-5)
    np.testing.assert_allclose(new, new0, atol=5e-5, rtol=1e-5)


def test_a_fresh_slot_enters_with_zero_whatever_lay_there(interpreted):
    o = _operands(16)
    y, new = ssd.ragged_ssd_update(**o)
    o2 = dict(o, state=o["state"].at[2].set(1e6))      # slot 2 is fresh
    y2, new2 = ssd.ragged_ssd_update(**o2)
    assert jnp.array_equal(y[2], y2[2]) and jnp.array_equal(new[2], new2[2])


@pytest.mark.parametrize("path", ["xla_oracle", "interpreted_kernel"])
def test_one_layer_of_a_whole_state_is_updated_where_it_lies(path, request):
    """With ``layer`` the call takes the whole ``[layers, ...]`` state and
    changes that layer's working slots only."""
    if path == "interpreted_kernel":
        request.getfixturevalue("interpreted")
    o = _operands(16)
    y, new = ssd.ragged_ssd_update(**o)
    whole = jnp.stack([o["state"] + 1, o["state"], o["state"] - 1])
    y3, new3 = jax.jit(lambda s, ly: ssd.ragged_ssd_update(
        **dict(o, state=s), layer=ly))(whole, jnp.int32(1))
    # jitted against eager: the same sums fused differently
    np.testing.assert_allclose(y3, y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(new3[1], new, atol=1e-5, rtol=1e-5)
    assert jnp.array_equal(new3[0], whole[0])
    assert jnp.array_equal(new3[2], whole[2])
    with pytest.raises(ValueError, match="updated at `layer`"):
        ssd.ragged_ssd_update(**dict(o, state=whole))


def test_the_state_result_aliases_the_state_operand(interpreted):
    """The kernel's second result is the state operand itself
    (``input_output_aliases``): the lowered call says so, which is what
    lets the engine's donated state be updated in place."""
    o = _operands(16)
    jaxpr = str(jax.make_jaxpr(
        lambda s: ssd.ragged_ssd_update(**dict(o, state=s)))(o["state"]))
    # the state is the call's last operand (after eight scalars and five
    # blocks) and its second result
    assert "name=ragged_ssd_update" in jaxpr
    assert "input_output_aliases=((13, 1),)" in jaxpr


def test_no_live_slot_leaves_every_state_as_it_was(interpreted):
    o = _operands(16)
    o["q_lens"] = jnp.zeros((B,), jnp.int32)
    y, new = ssd.ragged_ssd_update(**o)
    assert jnp.array_equal(new, o["state"]) and not jnp.any(y)


def test_heads_of_a_program_divide_a_group_and_fit_the_block():
    assert ssd._heads_per_block(16, 128, 256) == 16      # 2 MiB: one group
    assert ssd._heads_per_block(16, 128, 512) == 8
    assert ssd._heads_per_block(3, 4096, 4096) == 1      # never under one
