"""The paged kernel's static ``window`` (sliding attention): the Pallas
kernel in interpret mode and the XLA oracle against a dense masked softmax,
for windows below, at and above the context, page-aligned and not."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import flags
from paddle_tpu.kernels.paged_attention import ragged_paged_attention

PAGE, MAX_PAGES = 8, 12
QH, KVH, D = 4, 2, 64
# contexts: none, inside a page, one page, page-aligned, not, long
CONTEXTS = [0, 3, 8, 16, 37, 60, 80]


def _dense(q, k_full, v_full, ctx, ql, window):
    """q [B, T, qh, d]; k/v_full [B, S, kvh, d] hold the context and then
    the step's own rows: softmax over the keys in (p - window, p]."""
    out = np.zeros(q.shape, np.float32)
    group = q.shape[2] // k_full.shape[2]
    for b in range(q.shape[0]):
        for t in range(int(ql[b])):
            p = int(ctx[b]) + t
            lo = 0 if window is None else max(0, p - window + 1)
            for h in range(q.shape[2]):
                k = k_full[b, lo:p + 1, h // group]
                v = v_full[b, lo:p + 1, h // group]
                s = k @ q[b, t, h] / math.sqrt(q.shape[3])
                w = np.exp(s - s.max())
                out[b, t, h] = (w / w.sum()) @ v
    return out


@pytest.fixture
def interpret(request):
    flags.set_flags({"paged_attention_interpret": request.param})
    yield request.param
    flags.set_flags({"paged_attention_interpret": False})


def _kernel_and_dense(window, T, contexts, max_pages):
    """The entry point's output and the dense oracle's for slots at
    ``contexts`` whose pages lie scattered in the pool."""
    rng = np.random.default_rng(0)
    B = len(contexts)
    n_pages = B * max_pages + 3
    S = max_pages * PAGE
    k_full = rng.normal(size=(B, S, KVH, D)).astype(np.float32)
    v_full = rng.normal(size=(B, S, KVH, D)).astype(np.float32)
    q = rng.normal(size=(B, T, QH, D)).astype(np.float32)
    ql = np.asarray([T if b % 2 == 0 else max(1, T // 2) for b in range(B)],
                    np.int32)
    ctx = np.asarray(contexts, np.int32)
    table = rng.permutation(n_pages)[:B * max_pages].reshape(
        B, max_pages).astype(np.int32)          # pages scattered in the pool
    # the pool as PagedKVCache lays it out, written here index by index:
    # a page holds every head's K, then every head's V
    kv = np.zeros((n_pages, 2, KVH, PAGE, D), np.float32)
    for b in range(B):
        for pos in range(int(ctx[b])):
            kv[table[b, pos // PAGE], 0, :, pos % PAGE] = k_full[b, pos]
            kv[table[b, pos // PAGE], 1, :, pos % PAGE] = v_full[b, pos]
    k_new = np.stack([k_full[b, ctx[b]:ctx[b] + T] for b in range(B)])
    v_new = np.stack([v_full[b, ctx[b]:ctx[b] + T] for b in range(B)])
    got = np.asarray(ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(kv), jnp.asarray(table),
        jnp.asarray(ctx), q_lens=jnp.asarray(ql), k_new=jnp.asarray(k_new),
        v_new=jnp.asarray(v_new), window=window))
    return got, _dense(q, k_full, v_full, ctx, ql, window), ql


@pytest.mark.parametrize("interpret", [True, False], indirect=True,
                         ids=["kernel_interpreted", "xla_oracle"])
@pytest.mark.parametrize("T", [1, 8], ids=["decode", "chunk"])
@pytest.mark.parametrize("window", [None, 1, 5, 8, 16, 21, 64, 200])
def test_window_against_a_dense_masked_softmax(window, T, interpret):
    got, want, ql = _kernel_and_dense(window, T, CONTEXTS, MAX_PAGES)
    for b in range(len(CONTEXTS)):   # rows past q_lens[b] are don't-care
        np.testing.assert_allclose(got[b, :ql[b]], want[b, :ql[b]],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("interpret", [True], indirect=True,
                         ids=["kernel_interpreted"])
@pytest.mark.parametrize("window", [100, 1025, 1200])
def test_a_windows_first_page_falls_inside_a_block(window, interpret):
    """A table of 260 pages walks in blocks of 128 pages (1,024 keys).
    The windowed walk starts at the page of the earliest query's first
    visible key, so its blocks lie anywhere against the table's: the
    window's head is masked inside the first block, a window of 1,200
    needs a second block, and the unmasked path is taken only by blocks
    that every live row sees whole."""
    contexts = [1400, 2000, 64, 0, 2060, 1025]
    got, want, ql = _kernel_and_dense(window, 8, contexts, 260)
    for b in range(len(contexts)):
        np.testing.assert_allclose(got[b, :ql[b]], want[b, :ql[b]],
                                   rtol=1e-5, atol=1e-5)


def test_a_window_is_a_whole_number_of_at_least_one():
    z = jnp.zeros((1, 1, QH, D))
    cache = jnp.zeros((4, 2, KVH, PAGE, D))
    for bad in (0, -3, 2.5):
        with pytest.raises(ValueError, match="window"):
            ragged_paged_attention(z, cache,
                                   jnp.zeros((1, 2), jnp.int32),
                                   jnp.zeros((1,), jnp.int32), window=bad)
