"""A learned index over a latent page pool (``models.decoder_spec.
LatentIndex``): the scores of every cached token for every query token, and
the exact choice of each query token's best ``top_k``.

- ``latent_index_scores``: ``I(t, s) = sum_j w_t,j ReLU(q_t,j . k_s)`` over
  a working slot's PAGED index keys (one ``[page_size, dim]`` tile a page a
  layer: the pool's third plane, ``inference/kv_cache.py``) and the step's
  own index keys, folded in causally.  The paged part is a Pallas kernel on
  the schedule of the paged-attention kernels: grid ``(slot, key block)``,
  a block of ``_BLOCK_KEYS`` keys copied page by page into VMEM (the block
  table, the context and query lengths scalar-prefetched), one ``QK^T`` a
  tile of 8 query tokens (``8 x heads`` rows) in the operands' stored
  dtype with float32 accumulation, ReLU, the float32 weights and the sum
  over a token's heads on the VPU.  A slot without work, and a block past
  a slot's context, copy and multiply nothing.
- ``latent_index_select``: the EXACT ``k`` largest of each row in float32
  (no approximate top-k: another set is another result), a tie at the edge
  going to the lower position.  No sort: a Pallas kernel holds 16 query
  tokens' rows in VMEM and finds each row's ``k``-th largest value by
  bisection over the scores' bit patterns (32 counting passes over the
  row, no byte of HBM read twice), then the position up to which the ties
  at that value are admitted (16 more).  The result is the set, as a mask
  over the positions: what the masked walk of ``ragged_paged_attention_
  latent_sparse`` reads.

Off-TPU the plain ``jnp`` oracles (``_reference_*``) run instead, as the
paged-attention kernels' do; ``FLAGS_paged_attention_interpret=1`` runs the
kernel interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags
from .paged_attention import (_BLOCK_KEYS, _I0, _SUBLANE, _lane_padded_bytes,
                              _vmem_limit)

# query tokens a tile of the scores kernel holds: their ``8 x heads`` rows
# are one ``QK^T``, their 8 rows of sums one aligned store
_TOKEN_TILE = _SUBLANE


def index_geometry_error(page_size, dim, *, interpret=False, dtype="bfloat16"):
    """The rule an index plane's geometry fails for the scores kernel, as a
    sentence, or None: a page of index keys is copied as one whole tile."""
    if interpret:
        return None
    packs = 32 // jnp.dtype(dtype).itemsize
    if page_size % packs:
        return (f"page_size ({page_size}) must be a multiple of {packs}: a "
                f"page of {jnp.dtype(dtype).name} index keys is whole "
                f"({packs}, 128) tiles")
    if dim % 128:
        return f"an index key ({dim}) must fill whole 128-lane tiles"
    return None


# --------------------------------------------------------------- scores ---

def _causal_new(scores, q_lens):
    """The step's own columns ``[B, T, T]``: -inf where key ``j`` lies
    after query ``t`` or past the slot's rows."""
    t = scores.shape[1]
    jq = jnp.arange(t)
    valid = jnp.logical_and(jq[None, :, None] >= jq[None, None, :],
                            jq[None, None, :] < q_lens[:, None, None])
    return jnp.where(valid, scores, -jnp.inf)


def _weighted_relu(q_i, w, keys):
    """``sum_h w[b, t, h] ReLU(q_i[b, t, h] . keys[b, s])`` -> float32
    ``[B, T, S]``: operands as stored, float32 accumulation and weights."""
    s = jnp.einsum("bthd,bsd->bths", q_i, keys.astype(q_i.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bths,bth->bts", jnp.maximum(s, 0.0),
                      w.astype(jnp.float32))


def _causal_layout(paged, context_lens, own):
    """``latent_index_scores``' layout from its two parts: the positions'
    scores ``[B, T, S]`` with -inf from a slot's context on, then (where
    given) the step's own columns."""
    seen = jnp.arange(paged.shape[-1])[None, :] < context_lens[:, None]
    out = jnp.where(seen[:, None, :], paged, -jnp.inf)
    return out if own is None else jnp.concatenate([out, own], axis=-1)


def _reference_latent_index_scores(q_i, w, k_cache, block_tables,
                                   context_lens, q_lens, k_new):
    """XLA oracle (one layer's plane).  q_i ``[B, T, heads, dim]``, w ``[B,
    T, heads]``, k_cache ``[P, page, dim]``, k_new ``[B, T, dim]`` or None.
    Returns float32 ``[B, T, S (+ T)]``, ``S = max_pages x page``: the
    scores of the slot's positions in order, then of the step's own rows;
    -inf outside the causal set."""
    b = q_i.shape[0]
    n_pages, page_size, dim = k_cache.shape
    keys = jnp.take(k_cache, block_tables.reshape(-1), axis=0).reshape(
        b, block_tables.shape[1] * page_size, dim)
    return _causal_layout(
        _weighted_relu(q_i, w, keys), context_lens,
        None if k_new is None
        else _causal_new(_weighted_relu(q_i, w, k_new), q_lens))


def _index_scores_kernel(*refs, page_size, ppb, heads, layered, token_tiles):
    """One (slot, key block) of the paged scores: the block's pages are
    copied into one ``[keys, dim]`` tile, then every live tile of 8 query
    tokens gets ``ReLU(Q K^T)`` times its weights, summed over each
    token's heads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    it = iter(refs)
    bt_ref, cl_ref, ql_ref = next(it), next(it), next(it)
    ly_ref = next(it) if layered else None
    q_ref, w_ref, k_hbm = next(it), next(it), next(it)
    o_ref = next(it)
    kbuf, sem = next(it), next(it)

    b, j = pl.program_id(0), pl.program_id(1)
    ctx, ql = cl_ref[b], ql_ref[b]
    i32 = np.int32
    ps_c, ppb_c, one = i32(page_size), i32(ppb), i32(1)
    tt = _TOKEN_TILE
    rows = tt * heads
    tt_c = i32(tt)
    max_tiles = i32(token_tiles)
    last_entry = i32(bt_ref.shape[1] - 1)
    p0 = j * ppb_c
    n_tiles = jnp.minimum(jax.lax.div(ql + tt_c - one, tt_c), max_tiles)

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(jnp.logical_and(n_tiles > _I0, p0 * ps_c < ctx))
    def _work():
        def page(i):
            pid = bt_ref[b, jnp.minimum(p0 + i, last_entry)]
            src = k_hbm.at[ly_ref[0], pid] if layered else k_hbm.at[pid]
            pltpu.make_async_copy(
                src, kbuf.at[pl.ds(pl.multiple_of(i * ps_c, page_size),
                                   page_size)], sem.at[_I0]).start()
            return i + one

        jax.lax.while_loop(lambda i: i < ppb_c, page, _I0)
        pltpu.make_async_copy(kbuf, kbuf, sem.at[_I0]).wait()
        k = kbuf[...]

        def tile(i, carry):
            r = pl.ds(pl.multiple_of(i * i32(rows), rows), rows)
            s = jax.lax.dot_general(
                q_ref[r, :], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # [rows, keys]
            s = jnp.maximum(s, jnp.float32(0.0)) * w_ref[r, :]
            o_ref[pl.ds(pl.multiple_of(i * tt_c, tt), tt), :] = \
                jnp.concatenate(
                    [jnp.sum(s[t * heads:(t + 1) * heads], axis=0,
                             keepdims=True) for t in range(tt)], axis=0)
            return carry

        jax.lax.fori_loop(_I0, n_tiles, tile, _I0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_latent_index_scores(q_i, w, k_cache, block_tables, context_lens,
                                q_lens, interpret, layer=None):
    """The paged part alone: float32 ``[B, T, S]``, the columns past a
    slot's context (and the rows past its query tokens) undefined but
    finite."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, heads, dim = q_i.shape
    layered = layer is not None
    n_pages, page_size, _ = k_cache.shape[-3:]
    max_pages = block_tables.shape[1]
    ppb = max(1, min(_BLOCK_KEYS // page_size, max_pages))
    keys = ppb * page_size
    n_blocks = -(-max_pages // ppb)
    Tp = -(-t // _TOKEN_TILE) * _TOKEN_TILE
    q = q_i.astype(k_cache.dtype).reshape(b, t * heads, dim)
    wf = w.astype(jnp.float32).reshape(b, t * heads, 1)
    if Tp != t:
        pad = ((0, 0), (0, (Tp - t) * heads), (0, 0))
        q, wf = jnp.pad(q, pad), jnp.pad(wf, pad)
    bt = jnp.clip(block_tables, 0, n_pages - 1).astype(jnp.int32)
    scalars = [bt, context_lens.astype(jnp.int32), q_lens.astype(jnp.int32)]
    if layered:
        scalars.append(jnp.asarray(layer, jnp.int32).reshape(1))

    def by_slot(rows, last):
        return pl.BlockSpec((None, rows, last),
                            lambda b_, j_, *_: (b_, _I0, _I0))

    rows = _TOKEN_TILE * heads
    need = _lane_padded_bytes((keys, dim), k_cache.dtype) \
        + 2 * (_lane_padded_bytes((Tp * heads, dim), q.dtype)
               + _lane_padded_bytes((Tp * heads, 1), jnp.float32)
               + _lane_padded_bytes((Tp, keys), jnp.float32)) \
        + 3 * _lane_padded_bytes((rows, keys), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_index_scores_kernel, page_size=page_size, ppb=ppb,
                          heads=heads, layered=layered,
                          token_tiles=Tp // _TOKEN_TILE),
        name="latent_index_scores",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, n_blocks),
            in_specs=[by_slot(Tp * heads, dim), by_slot(Tp * heads, 1),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, Tp, keys),
                                   lambda b_, j_, *_: (b_, _I0, j_)),
            scratch_shapes=[pltpu.VMEM((keys, dim), k_cache.dtype),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((b, Tp, n_blocks * keys),
                                       jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(need)),
        interpret=interpret,
    )(*scalars, q, wf, k_cache)
    return out[:, :t, :max_pages * page_size]


def latent_index_scores(q_i, w, k_cache, block_tables, context_lens, *,
                        q_lens=None, k_new=None, layer=None):
    """The index scores of a mixed serving step (prefill chunks and decode
    tokens in one call).

    Args:
      q_i:     [batch, T, heads, dim] index queries (rotated).
      w:       [batch, T, heads] the heads' weights (float32 in the sum).
      k_cache: [num_pages, page_size, dim] index keys (normed, rotated), or
               the whole plane [layers, ...] with ``layer``.
      block_tables, context_lens, q_lens: as ``ragged_paged_attention``.
      k_new:   [batch, T, dim]: the step's own index keys, folded in
               causally; commit them after the step.
      layer:   int32 scalar (may be traced), with the whole plane.

    Returns float32 ``[batch, T, S (+ T)]``, ``S = max_pages x page_size``:
    ``I(t, s)`` for the slot's positions in order, then for the step's own
    rows (row ``j`` is position ``context + j``); -inf outside the causal
    set (a position past the context, an own row after the query or past
    ``q_lens``).  Rows past ``q_lens[b]`` are don't-care."""
    b, t, heads, dim = q_i.shape
    if (k_cache.ndim == 4) != (layer is not None):
        raise ValueError("a whole plane [layers, ...] is read at `layer`; "
                         "one layer's plane takes none")
    page_size = k_cache.shape[-2]
    if k_cache.shape[-1] != dim:
        raise ValueError(f"index plane {k_cache.shape} does not hold keys "
                         f"of {dim}")
    ql = (q_lens if q_lens is not None
          else jnp.full((b,), t)).astype(jnp.int32)
    on_tpu = jax.default_backend() == "tpu"
    why = index_geometry_error(page_size, dim, interpret=not on_tpu,
                               dtype=k_cache.dtype)
    if on_tpu and why:
        raise ValueError(f"latent_index_scores on TPU: {why}")
    if not (on_tpu or flags.flag("paged_attention_interpret")):
        if layer is not None:
            k_cache = jax.lax.dynamic_index_in_dim(k_cache, layer, axis=0,
                                                   keepdims=False)
        with jax.named_scope("latent_index_scores"):
            return _reference_latent_index_scores(
                q_i, w, k_cache, block_tables, context_lens, ql, k_new)
    paged = _pallas_latent_index_scores(
        q_i, w, k_cache, block_tables, context_lens, ql,
        interpret=not on_tpu, layer=layer)
    with jax.named_scope("latent_index_scores"):
        return _causal_layout(
            paged, context_lens, None if k_new is None else _causal_new(
                _weighted_relu(q_i.astype(k_cache.dtype), w, k_new), ql))


# ------------------------------------------------------------ selection ---

_SELECT_TOKENS = 16      # query tokens a program holds: a bfloat16 tile
_SELECT_CHUNK = 1024     # columns a counting pass reads at a time


def _reference_latent_index_select(scores, k):
    """Oracle of the selection: a stable descending sort (a tie goes to the
    lower position), the first ``k[b, t]`` of each row kept."""
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return rank < k[..., None]


def _index_select_kernel(ql_ref, cl_ref, s_ref, k_ref, o_ref, key_ref, *,
                         own_from, chunks):
    """One tile of 16 query tokens of one slot: ``key_ref`` holds the rows
    as int32 whose signed order is the floats'; the ``k``-th largest is
    built bit by bit from the top (the largest value that at least ``k``
    entries reach), then the largest position bound under which at most
    the places left are taken by entries AT that value.  Only the chunks
    of columns that can hold a finite score are read: those under the
    slot's context, and those from chunk ``own_from`` on (the step's own
    rows); the others come out 0."""
    from jax.experimental import pallas as pl

    b, i = pl.program_id(0), pl.program_id(1)
    tt, n = s_ref.shape
    chunk = _SELECT_CHUNK
    i32 = np.int32
    n_chunks, one = i32(chunks), i32(1)
    n_ctx = jnp.minimum(jax.lax.div(cl_ref[b] + i32(chunk - 1), i32(chunk)),
                        i32(own_from))

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def cols(c):
        return pl.ds(pl.multiple_of(c * i32(chunk), chunk), chunk)

    def over_chunks(body, carry):
        """``carry = body(chunk number, carry)`` over the chunks read."""
        for first, end in ((_I0, n_ctx), (i32(own_from), n_chunks)):
            _, carry = jax.lax.while_loop(
                lambda cc, end=end: cc[0] < end,
                lambda cc: (cc[0] + one, body(cc[0], cc[1])),
                (first, carry))
        return carry

    def count(hit):
        """[tt, 1] int32: entries for which ``hit(keys, first column)``
        holds, a chunk of columns at a time; lanes are added last."""
        def body(c, acc):
            h = hit(key_ref[:, cols(c)], c * i32(chunk)).astype(jnp.int32)
            for j in range(0, chunk, 128):
                acc = acc + h[:, j:j + 128]
            return acc

        acc = over_chunks(body, jnp.zeros((tt, 128), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True, dtype=jnp.int32)

    def bits_down(first, step, start):
        """``start`` with the bits from ``first`` down set where ``step``
        keeps them."""
        def body(carry):
            bit, v = carry
            return jax.lax.shift_right_logical(bit, one), step(v, v | bit)

        return jax.lax.while_loop(lambda carry: carry[0] > _I0, body,
                                  (i32(first), start))[1]

    @pl.when(i * i32(tt) < ql_ref[b])
    def _work():
        def to_keys(c, carry):
            x = s_ref[:, cols(c)]
            x = jnp.where(x == jnp.float32(0.0), jnp.float32(0.0), x)
            bits = jax.lax.bitcast_convert_type(x, jnp.int32)
            key_ref[:, cols(c)] = bits ^ (
                jax.lax.shift_right_arithmetic(bits, i32(31))
                & i32(0x7fffffff))
            return carry

        over_chunks(to_keys, _I0)
        k = k_ref[...]                                       # [tt, 1]

        def reach(v, cand):
            n_ge = count(lambda keys, _: keys >= cand)
            return jnp.where(n_ge >= k, cand, v)

        low = jnp.full((tt, 1), np.iinfo(np.int32).min, jnp.int32)
        edge = bits_down(1 << 30, reach,
                         reach(low, jnp.zeros((tt, 1), jnp.int32)))
        left = k - count(lambda keys, _: keys > edge)

        def admit(p, cand):
            taken = count(lambda keys, c0: jnp.logical_and(
                keys == edge,
                c0 + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
                < cand))
            return jnp.where(taken <= left, cand, p)

        bound = bits_down(1 << 16, admit, jnp.zeros((tt, 1), jnp.int32))

        def write(c, carry):
            keys = key_ref[:, cols(c)]
            col = c * i32(chunk) + jax.lax.broadcasted_iota(
                jnp.int32, keys.shape, 1)
            sel = jnp.logical_or(keys > edge, jnp.logical_and(
                keys == edge, col < bound))
            o_ref[:, cols(c)] = sel.astype(o_ref.dtype)
            return carry

        over_chunks(write, _I0)


@functools.partial(jax.jit, static_argnames=("interpret", "n_new"))
def _pallas_latent_index_select(scores, k, q_lens, context_lens, interpret,
                                n_new=0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, n = scores.shape
    tt, chunk = _SELECT_TOKENS, _SELECT_CHUNK
    Tp, Np = -(-t // tt) * tt, -(-n // chunk) * chunk
    if Np >= 1 << 17:
        raise ValueError(f"latent_index_select: {n} positions a row, the "
                         "tie bound is found in 17 bits")
    s = jnp.pad(scores.astype(jnp.float32),
                ((0, 0), (0, Tp - t), (0, Np - n)),
                constant_values=-jnp.inf)
    kk = jnp.pad(k.astype(jnp.int32), ((0, 0), (0, Tp - t)),
                 constant_values=1)[..., None]

    def tile(last):
        return pl.BlockSpec((None, tt, last),
                            lambda b_, i_, *_: (b_, i_, _I0))

    if context_lens is None:        # every chunk of a row is read
        own_from, context_lens = 0, jnp.zeros((b,), jnp.int32)
    else:   # columns [context, n - n_new) of a slot's rows hold -inf
        own_from = (n - n_new) // chunk

    need = 2 * (_lane_padded_bytes((tt, Np), jnp.float32)
                + _lane_padded_bytes((tt, Np), jnp.bfloat16)) \
        + _lane_padded_bytes((tt, Np), jnp.int32)
    out = pl.pallas_call(
        functools.partial(_index_select_kernel, own_from=own_from,
                          chunks=Np // chunk),
        name="latent_index_select",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, Tp // tt),
            in_specs=[tile(Np), tile(1)],
            out_specs=tile(Np),
            scratch_shapes=[pltpu.VMEM((tt, Np), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, Tp, Np), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem_limit(need)),
        interpret=interpret,
    )(q_lens.astype(jnp.int32), context_lens.astype(jnp.int32), s, kk)
    return out[:, :t, :n] > 0


def latent_index_select(scores, k, q_lens=None, context_lens=None, n_new=0):
    """The set of each row's ``k`` largest scores, exactly.

    scores: float32 ``[batch, T, N]`` (``latent_index_scores``: -inf outside
    the causal set); k: int32 ``[batch, T]``, at most the row's finite
    scores; q_lens ``[batch]``: rows past it are don't-care (the kernel
    skips their tiles).  ``context_lens`` ``[batch]`` with ``n_new``: the
    caller's word that columns ``[context_lens[b], N - n_new)`` of slot
    ``b``'s rows are -inf (``latent_index_scores``' layout: the positions,
    then the step's ``n_new`` own rows): the kernel does not read them.
    Returns bool ``[batch, T, N]`` with ``k[b, t]`` true entries a row: the
    largest scores, a tie at the edge going to the lower position."""
    b, t, _ = scores.shape
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or flags.flag("paged_attention_interpret"):
        ql = (q_lens if q_lens is not None
              else jnp.full((b,), t)).astype(jnp.int32)
        return _pallas_latent_index_select(
            scores, k, ql, context_lens, interpret=not on_tpu, n_new=n_new)
    with jax.named_scope("latent_index_select"):
        return _reference_latent_index_select(scores, k.astype(jnp.int32))
