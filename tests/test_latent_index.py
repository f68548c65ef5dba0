"""A learned index over a latent pool (``kernels/latent_index.py``) and the
sparse latent call (``ragged_paged_attention_latent_sparse``): each of the
three against its plain oracle (the Pallas kernels in interpret mode), the
selection's ties and edges by hand, and the sparse call against the dense
one where the set is everything."""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags
from paddle_tpu.kernels import latent_index as li
from paddle_tpu.kernels import paged_attention as pa

HEADS, DIM, PAGE = 4, 128, 16
RANK, ROPE, QH = 128, 64, 4
SCALE = 0.1352


@pytest.fixture
def interpret():
    flags.set_flags({"paged_attention_interpret": True})
    yield
    flags.set_flags({"paged_attention_interpret": False})


@pytest.fixture
def small_blocks(monkeypatch):
    """Key blocks of four pages, row tiles of 16 rows and selection chunks
    of 128 columns: several of each at test sizes."""
    monkeypatch.setattr(pa, "_BLOCK_KEYS", 64)
    monkeypatch.setattr(li, "_BLOCK_KEYS", 64)
    monkeypatch.setattr(pa, "_ROW_TILE", 16)
    monkeypatch.setattr(li, "_SELECT_CHUNK", 128)


def _index_case(rng, b, t, table, pages, layers=2):
    f32 = jnp.float32
    q = jnp.asarray(rng.normal(size=(b, t, HEADS, DIM)), f32)
    w = jnp.asarray(rng.normal(size=(b, t, HEADS)), f32)
    k = jnp.asarray(rng.normal(size=(layers, pages, PAGE, DIM)), f32)
    bt = jnp.asarray(rng.permutation(pages)[:b * table].reshape(b, table),
                     jnp.int32)
    kn = jnp.asarray(rng.normal(size=(b, t, DIM)), f32)
    return q, w, k, bt, kn


def _scores_by_hand(q, w, k, bt, ctx, ql, kn, layer):
    """``I(t, s)`` of every live query row over its slot's cached keys and
    the step's own keys up to itself: numpy, float64."""
    f = np.float64
    q, w, kn = (np.asarray(a, f) for a in (q, w, kn))
    k_l = np.asarray(k[layer], f)
    b, t = q.shape[:2]
    S = bt.shape[1] * PAGE
    out = np.full((b, t, S + t), -np.inf)
    for i in range(b):
        keys = k_l[np.asarray(bt[i])].reshape(-1, DIM)[:int(ctx[i])]
        for j in range(int(ql[i])):
            both = np.concatenate([keys, kn[i, :j + 1]])
            s = np.maximum(q[i, j] @ both.T, 0.0)           # [heads, keys]
            val = w[i, j] @ s
            out[i, j, :len(keys)] = val[:len(keys)]
            out[i, j, S:S + j + 1] = val[len(keys):]
    return out


CASES = {
    # ragged rows, a slot without work, contexts inside a block (37), at a
    # page's and a block's edge (64), past two blocks (130), empty (0)
    "mixed": (8, (8, 0, 1, 5), (0, 37, 64, 130)),
    # one-token rows
    "decode": (1, (1, 1, 0, 1), (5, 16, 64, 143)),
    # more query tokens than a tile of 8 holds
    "two_tiles": (12, (12, 3, 9, 0), (100, 0, 48, 7)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_scores_kernel_is_the_oracle_and_the_sum_by_hand(
        interpret, small_blocks, rng, case):
    t, ql, ctx = CASES[case]
    q, w, k, bt, kn = _index_case(rng, 4, t, 9, 40)
    ctx, ql = jnp.asarray(ctx, jnp.int32), jnp.asarray(ql, jnp.int32)
    kw = dict(q_lens=ql, k_new=kn, layer=jnp.int32(1))
    got = np.asarray(li.latent_index_scores(q, w, k, bt, ctx, **kw))
    flags.set_flags({"paged_attention_interpret": False})
    oracle = np.asarray(li.latent_index_scores(q, w, k, bt, ctx, **kw))
    hand = _scores_by_hand(q, w, k, bt, ctx, ql, kn, 1)
    assert got.shape == oracle.shape == hand.shape
    for b in range(4):
        n = int(ql[b])
        for a in (got, oracle):
            assert (np.isinf(a[b, :n]) == np.isinf(hand[b, :n])).all()
            live = ~np.isinf(hand[b, :n])
            np.testing.assert_allclose(a[b, :n][live], hand[b, :n][live],
                                       rtol=2e-4, atol=2e-4)


def test_scores_of_one_layers_plane_take_no_layer(rng):
    q, w, k, bt, kn = _index_case(rng, 2, 4, 3, 8)
    ctx = jnp.asarray([20, 3], jnp.int32)
    whole = li.latent_index_scores(q, w, k, bt, ctx, layer=jnp.int32(1))
    one = li.latent_index_scores(q, w, k[1], bt, ctx)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(one))
    assert whole.shape == (2, 4, 3 * PAGE)          # no own rows given
    with pytest.raises(ValueError, match="whole plane"):
        li.latent_index_scores(q, w, k, bt, ctx)
    with pytest.raises(ValueError, match="does not hold keys"):
        li.latent_index_scores(q[..., :64], w, k[1], bt, ctx)


# ---------------------------------------------------------- selection ----

def _select_by_hand(scores, k):
    """Sort each row by (-score, position), keep the first k: numpy."""
    out = np.zeros(scores.shape, bool)
    for idx in np.ndindex(scores.shape[:-1]):
        row = scores[idx]
        order = sorted(range(len(row)), key=lambda s: (-row[s], s))
        out[idx][order[:int(k[idx])]] = True
    return out


@pytest.mark.parametrize("how", ["kernel", "oracle"])
def test_selection_is_exact_and_a_tie_goes_to_the_lower_position(
        interpret, small_blocks, how):
    """Hand-made rows: a tie across the edge (three entries at the edge's
    value, one place left: the lowest position gets it), everything tied,
    zeros of both signs, negative scores, ``k`` = 1 and ``k`` = all."""
    if how == "oracle":
        flags.set_flags({"paged_attention_interpret": False})
    inf = np.inf
    n = 300                       # three chunks of 128 once padded
    rows = np.full((1, 6, n), -inf, np.float32)
    rows[0, 0, :8] = [5, 3, 3, 9, 3, 1, -2, 0]          # k 3: 9, 5, first 3
    rows[0, 1, :200] = 1.0                              # k 7: the first 7
    rows[0, 2, :6] = [0.0, -0.0, 1e-30, -1e-30, 0.0, -0.0]   # k 3
    rows[0, 3, 250:260] = -np.arange(10) - 1.0          # k 2: -1, -2
    rows[0, 4, 140] = 2.5                               # k 1
    rows[0, 5, :n] = np.arange(n) % 7                   # k n: all
    k = np.asarray([[3, 7, 3, 2, 1, n]], np.int32)
    got = np.asarray(li.latent_index_select(jnp.asarray(rows),
                                            jnp.asarray(k)))
    want = _select_by_hand(rows, k)
    np.testing.assert_array_equal(got, want)
    assert list(np.flatnonzero(got[0, 0])) == [0, 1, 3]
    assert list(np.flatnonzero(got[0, 1])) == list(range(7))
    assert list(np.flatnonzero(got[0, 2])) == [0, 1, 2]      # 1e-30, then 0s
    assert list(np.flatnonzero(got[0, 3])) == [250, 251]
    assert (got.sum(-1) == k).all()


@pytest.mark.parametrize("case", list(CASES))
def test_selection_kernel_is_the_oracle_on_the_scores_layout(
        interpret, small_blocks, rng, case):
    """On ``latent_index_scores``' own layout (positions, then the step's
    rows; -inf between a slot's context and its own rows), told which
    columns it need not read: the same sets as the oracle's sort, row for
    row, ``min(position + 1, top_k)`` entries each."""
    t, ql, ctx = CASES[case]
    q, w, k, bt, kn = _index_case(rng, 4, t, 9, 40)
    ctx, ql = jnp.asarray(ctx, jnp.int32), jnp.asarray(ql, jnp.int32)
    scores = li.latent_index_scores(q, w, k, bt, ctx, q_lens=ql, k_new=kn,
                                    layer=jnp.int32(0))
    pos = ctx[:, None] + jnp.arange(t)[None]
    for top_k in (4, 24, 1000):
        keep = jnp.minimum(pos + 1, top_k)
        got = np.asarray(li.latent_index_select(
            scores, keep, q_lens=ql, context_lens=ctx, n_new=t))
        want = np.asarray(li._reference_latent_index_select(scores, keep))
        for b in range(4):
            n = int(ql[b])
            np.testing.assert_array_equal(got[b, :n], want[b, :n])
            assert (got[b, :n].sum(-1) == np.asarray(keep[b, :n])).all()
            # nothing outside the causal set is ever chosen
            assert not got[b, :n][np.isinf(np.asarray(scores[b, :n]))].any()


# -------------------------------------------------------- sparse call ----

def _latent_case(rng, b, t, table, pages, layers=2):
    f32 = jnp.float32
    q_c = jnp.asarray(rng.normal(size=(b, t, QH, RANK)), f32)
    q_r = jnp.asarray(rng.normal(size=(b, t, QH, ROPE)), f32)
    c = jnp.asarray(rng.normal(size=(layers, pages, PAGE, RANK)), f32)
    r = jnp.asarray(rng.normal(size=(layers, pages, PAGE // 2, 2 * ROPE)),
                    f32)
    cn = jnp.asarray(rng.normal(size=(b, t, RANK)), f32)
    rn = jnp.asarray(rng.normal(size=(b, t, ROPE)), f32)
    return q_c, q_r, c, r, cn, rn


def _sparse_by_hand(q_c, q_r, c, r, bt, ctx, ql, cn, rn, sel, layer):
    """Softmax attention of every live query row over its CHOSEN rows
    alone (cached and own), the value the first ``rank`` of the key:
    numpy, float64."""
    f = np.float64
    q_c, q_r, cn, rn = (np.asarray(a, f) for a in (q_c, q_r, cn, rn))
    c_l = np.asarray(c[layer], f)
    r_l = np.asarray(pa.unpack_rope_pages(r[layer], ROPE), f)
    sel = np.asarray(sel)
    S = bt.shape[1] * PAGE
    out = np.zeros(q_c.shape, f)
    for b in range(q_c.shape[0]):
        n = int(ctx[b])
        pages = np.asarray(bt[b])
        keys_c = c_l[pages].reshape(-1, RANK)[:n]
        keys_r = r_l[pages].reshape(-1, ROPE)[:n]
        for j in range(int(ql[b])):
            pick = np.concatenate([sel[b, j, :n], sel[b, j, S:S + j + 1]])
            kc = np.concatenate([keys_c, cn[b, :j + 1]])[pick]
            kr = np.concatenate([keys_r, rn[b, :j + 1]])[pick]
            s = (q_c[b, j] @ kc.T + q_r[b, j] @ kr.T) * SCALE
            p = np.exp(s - s.max(-1, keepdims=True))
            out[b, j] = (p / p.sum(-1, keepdims=True)) @ kc
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_sparse_call_is_the_oracle_and_the_attention_by_hand(
        interpret, small_blocks, rng, case):
    """The sets a real index chose (a strict choice of 6 where the context
    allows it, so whole blocks hold none of a row's keys), the kernel's
    masked walk against the oracle and against the attention over the
    chosen rows alone, by hand."""
    t, ql, ctx = CASES[case]
    q, w, k, bt, kn = _index_case(rng, 4, t, 9, 40)
    q_c, q_r, c, r, cn, rn = _latent_case(rng, 4, t, 9, 40)
    ctx, ql = jnp.asarray(ctx, jnp.int32), jnp.asarray(ql, jnp.int32)
    layer = jnp.int32(1)
    scores = li.latent_index_scores(q, w, k, bt, ctx, q_lens=ql, k_new=kn,
                                    layer=layer)
    pos = ctx[:, None] + jnp.arange(t)[None]
    sel = li.latent_index_select(scores, jnp.minimum(pos + 1, 6), q_lens=ql,
                                 context_lens=ctx, n_new=t)
    kw = dict(scale=SCALE, q_lens=ql, c_new=cn, r_new=rn, layer=layer)
    got = np.asarray(pa.ragged_paged_attention_latent_sparse(
        q_c, q_r, c, r, bt, ctx, sel, **kw))
    flags.set_flags({"paged_attention_interpret": False})
    oracle = np.asarray(pa.ragged_paged_attention_latent_sparse(
        q_c, q_r, c, r, bt, ctx, sel, **kw))
    hand = _sparse_by_hand(q_c, q_r, c, r, bt, ctx, ql, cn, rn, sel, 1)
    for b in range(4):
        n = int(ql[b])
        np.testing.assert_allclose(got[b, :n], hand[b, :n], rtol=2e-4,
                                   atol=2e-4)
        np.testing.assert_allclose(oracle[b, :n], hand[b, :n], rtol=2e-4,
                                   atol=2e-4)


@pytest.mark.parametrize("how", ["kernel", "oracle"])
def test_with_everything_chosen_the_sparse_call_is_the_dense_one(
        interpret, small_blocks, rng, how):
    """``top_k`` at least the context: the set is the causal set, and the
    sparse call gives the dense latent call's numbers on every live row."""
    if how == "oracle":
        flags.set_flags({"paged_attention_interpret": False})
    t, ql, ctx = CASES["mixed"]
    q_c, q_r, c, r, cn, rn = _latent_case(rng, 4, t, 9, 40)
    bt = jnp.asarray(rng.permutation(40)[:36].reshape(4, 9), jnp.int32)
    ctx, ql = jnp.asarray(ctx, jnp.int32), jnp.asarray(ql, jnp.int32)
    kw = dict(scale=SCALE, q_lens=ql, c_new=cn, r_new=rn,
              layer=jnp.int32(0))
    everything = jnp.ones((4, t, 9 * PAGE + t), bool)
    sparse = np.asarray(pa.ragged_paged_attention_latent_sparse(
        q_c, q_r, c, r, bt, ctx, everything, **kw))
    dense = np.asarray(pa.ragged_paged_attention_latent(
        q_c, q_r, c, r, bt, ctx, **kw))
    for b in range(4):
        n = int(ql[b])
        np.testing.assert_allclose(sparse[b, :n], dense[b, :n], rtol=1e-6,
                                   atol=1e-6)


def test_sparse_call_refuses_a_set_of_another_shape(rng):
    q_c, q_r, c, r, cn, rn = _latent_case(rng, 2, 4, 3, 8)
    bt = jnp.zeros((2, 3), jnp.int32)
    ctx = jnp.asarray([5, 9], jnp.int32)
    with pytest.raises(ValueError, match=r"positions \(\+ own rows\)"):
        pa.ragged_paged_attention_latent_sparse(
            q_c, q_r, c[0], r[0], bt, ctx, jnp.ones((2, 4, 3 * PAGE), bool),
            scale=SCALE, c_new=cn, r_new=rn)


def test_the_third_plane_is_committed_with_the_other_two(rng):
    """``write_latent_pages_all_layers`` with an index plane: each valid
    token's index key lands in its slot of every layer, a dropped token
    (-1) nowhere, and the first two planes are what they are without it."""
    L, pages, n = 2, 5, 7
    c = jnp.zeros((L, pages, PAGE, RANK))
    r = jnp.zeros((L, pages, PAGE // 2, 2 * ROPE))
    ik = jnp.zeros((L, pages, PAGE, DIM))
    c_all = jnp.asarray(rng.normal(size=(L, n, RANK)), jnp.float32)
    r_all = jnp.asarray(rng.normal(size=(L, n, ROPE)), jnp.float32)
    i_all = jnp.asarray(rng.normal(size=(L, n, DIM)), jnp.float32)
    slots = jnp.asarray([3, 17, -1, 40, 79, -1, 8], jnp.int32)
    two = pa.write_latent_pages_all_layers(c, r, c_all, r_all, slots)
    three = pa.write_latent_pages_all_layers(c, r, c_all, r_all, slots, ik,
                                             i_all)
    assert len(two) == 2 and len(three) == 3
    for a, b in zip(two, three):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    flat = np.asarray(three[2]).reshape(L, pages * PAGE, DIM)
    for j, slot in enumerate(np.asarray(slots)):
        if slot >= 0:
            np.testing.assert_array_equal(flat[:, slot],
                                          np.asarray(i_all[:, j]))
    written = [s for s in np.asarray(slots) if s >= 0]
    rest = np.delete(flat, written, axis=1)
    assert not rest.any()
