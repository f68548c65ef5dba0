"""Ragged paged-KV attention for TPU (Pallas) — the serving hot op.

Replaces the reference's fused decode kernels
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu and
masked_multihead_attention): each sequence's query tokens attend its whole
KV history, which lives in fixed-size *pages* scattered through a global
cache and addressed by a per-sequence block table (vLLM-style paged KV).

TPU-first design (the "Ragged Paged Attention" schedule of arxiv 2604.15464):

- **One mixed-mode kernel** serves prefill chunks AND decode tokens: the
  query operand is ``[batch, T, q_heads, head_dim]`` where T is the step's
  query-token tile (1 for pure decode, the chunk length for chunked
  prefill), with per-sequence ``q_lens`` raggedness.  The step's OWN fresh
  K/V rows (``k_new``/``v_new``, not yet committed to the cache) are folded
  in-kernel with a causal mask, so a serving step never needs a separate
  flash-attention call or an analytic current-token merge — chunked
  prefill rides the decode schedule in one ``pallas_call``.
- The grid is **(sequence,)**: one program walks a slot's whole KV once
  for ALL its KV heads, in a loop whose trip count comes from
  ``context_lens`` (and, with a window, from the first page the earliest
  query sees), so no grid step exists for pages a sequence does not have.
  A slot with ``q_lens == 0`` does nothing: no DMA, no fold, no
  normalization (its rows are written as zeros); a call has ``slots``
  such programs at most, not ``slots x kv_heads``.
- The KV cache is laid out **page-major**, ``[num_pages, 2, kv_heads,
  page_size, head_dim]`` a layer (``inference/kv_cache.py``), and stays in
  **HBM** (``pl.ANY``): every head's K, then every head's V, of one page
  is ONE contiguous run (32 KB at 4 bf16 KV heads of 128, 64 KB at 8) and
  **the unit of copy**.  A decoding slot's program is bound by ISSUING
  its copies, not by their bytes (PERF.md section 6, PRs 28 and 35), so
  a page is one descriptor, not two a KV head.  The walk goes in **blocks
  of many pages** (``_BLOCK_KEYS`` keys in whole pages, never more than
  the table's width, nor than two buffers may take of VMEM): the kernel
  DMAs the pages of the blocks a sequence's context reaches, one copy a
  page, into a ``[pages, 2, kv_heads, page_size, head_dim]`` VMEM buffer,
  **double-buffered** at block granularity — block ``j+1``'s copies are
  in flight while block ``j`` is computed.  Inside a block the KV heads
  are a loop: head ``h``'s K (and V) over the block's keys is the buffer
  read at ``[:, 0, h]`` (``[:, 1, h]``), whole ``(page_size, head_dim)``
  tiles stacked into the ``[keys, head_dim]`` operand without a relayout
  (``kernel_geometry_error``: a page is a whole number of the pool type's
  sublane tiles).  A (head, block) gets one ``QK^T``, one mask, one
  online-softmax update and one ``PV``, so the score tile is lane-dense
  and the (m, l, acc) carry is touched once per block, not once per page.
- **VMEM** follows from the static shapes (``vmem_limit_bytes``): two KV
  buffers (4 MiB at 4 bf16 KV heads and 1,024 keys, 8 MiB at 8), the
  (m, l, acc) carry and the pipeline's q, fresh-row, output and
  log-sum-exp blocks, which now span the slot's KV heads: 49 MiB for 8
  heads of 1,024 query rows, of the chip's 128.
- GQA is native: for each KV head a program holds the ``group = q_heads
  // kv_heads`` query rows of all T tokens, row ``r`` = token
  ``r // group``, so K/V pages are fetched ONCE per slot and a head's
  live rows are the prefix ``[0, q_len * group)``.  The program loops
  over **row tiles** of that prefix only (``row_tile``): a decoding slot
  inside a T = 64 step computes one tile, not ``64 * group`` rows.  The
  tiles are the inner loop (the whole (m, l, acc) carry lives in VMEM
  scratch), so the KV is read once whatever the number of tiles.
- **Operands enter the MXU as stored**: ``q`` and ``k`` in the cache's
  dtype (a bf16 x bf16 product is exact in the float32 accumulator; an
  int8 page is exact there too and its per-page scale applies to the
  score and probability columns), ``1/sqrt(d)`` on the float32 scores;
  the probabilities stay float32 into ``PV``, and m, l, the accumulator
  and the log-sum-exp are float32.
- The block table, context lengths and query lengths ride in as
  **scalar-prefetch** operands (``pltpu.PrefetchScalarGridSpec``), so page
  ids resolve before the body runs — data-dependent addressing with zero
  data-dependent control flow outside loop trip counts.  Block and tile
  sizes follow from the static shapes; no flag sets them.

Off-TPU an XLA gather+masked-softmax reference runs instead (tests use it
as the numerics oracle; ``FLAGS_paged_attention_interpret=1`` runs the real
kernel in interpreter mode).  On a TPU the kernel always runs compiled, and
a geometry it does not cover raises (``kernel_geometry_error``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags

NEG_INF = -1e30
_I0 = np.int32(0)  # index-map literal: bare 0 would be int64 under x64 mode

flags.define_flag("paged_attention_interpret", False,
                  "Run the Pallas paged-attention kernel in interpreter mode "
                  "on CPU (tests only; TPU always uses the compiled path).")

_SUBLANE = 8      # f32 sublane count — a query-row block pads to a multiple
# The two sizes of the schedule, settled on a v5e at the benchmark cells'
# geometries (PERF.md section 6, PR 28): a row tile of at most 256 query
# rows and a KV block of 1,024 keys.  Against 128 rows x 512 keys the
# 1,024-row Command A+ calls are 27-28 % shorter (a K block is loaded into
# the MXU once for twice the rows; the (m, l, acc) carry is updated half
# as often) and the Mixtral cell's 7 %; the chat cell's, whose contexts
# hardly fill one block, 7 % longer; 512 rows or 2,048 keys gain no more.
_ROW_TILE = 256
_BLOCK_KEYS = 1024
# VMEM the two KV buffers of a block may take: a page holds every KV head's
# K and V, so where a block of ``_BLOCK_KEYS`` keys would pass it (32 bf16
# KV heads: 32 MiB) the block holds fewer pages
_KV_BUFFER_BYTES = 16 << 20


# --------------------------------------------------------------- oracles ---

def _reference_paged_attention(q, k_cache, v_cache, block_tables,
                               context_lens, with_lse=False):
    """XLA oracle: gather pages, masked softmax. q: [B, qh, d]."""
    b, qh, d = q.shape
    kvh, n_pages, page_size, _ = k_cache.shape
    group = qh // kvh
    max_pages = block_tables.shape[1]

    flat = block_tables.reshape(-1)
    k = jnp.take(k_cache, flat, axis=1)          # [kvh, B*P, page, d]
    v = jnp.take(v_cache, flat, axis=1)
    k = k.reshape(kvh, b, max_pages * page_size, d)
    v = v.reshape(kvh, b, max_pages * page_size, d)

    qg = q.reshape(b, kvh, group, d).astype(jnp.float32)
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bhgd,hbsd->bhgs", qg, k.astype(jnp.float32)) * scale
    pos = jnp.arange(max_pages * page_size)
    mask = pos[None, :] < context_lens[:, None]            # [B, S]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,hbsd->bhgd", p, v.astype(jnp.float32))
    out = out.reshape(b, qh, d).astype(q.dtype)
    if not with_lse:
        return out
    lse = jax.scipy.special.logsumexp(s, axis=-1)          # [B, kvh, g]
    return out, lse.reshape(b, qh)


def _reference_ragged_paged_attention(q, k_cache, v_cache, block_tables,
                                      context_lens, q_lens=None, k_new=None,
                                      v_new=None, k_scale=None, v_scale=None,
                                      window=None):
    """XLA oracle for the mixed prefill+decode form.

    q: [B, T, qh, d]; k_new/v_new: [B, T, kvh, d] — the step's fresh rows,
    attended with an intra-step causal mask on top of the cached context.
    Rows with token index >= q_lens[b] are don't-care (finite, like the
    kernel's, but not the same values: the kernel stops at whole row
    tiles).  With ``k_scale``/``v_scale`` (int8 pool,
    one fp32 per (kv-head, page)) gathered pages are dequantized before
    the math (the kernel multiplies the int8 pages as stored and scales
    the score and probability columns: the same numbers, summed in
    another order).
    ``window``: query token j (position ``context_lens[b] + j``) sees only
    keys at positions in ``(position - window, position]``.
    Returns (out [B, T, qh, d], lse [B, T, qh]).
    """
    b, t, qh, d = q.shape
    kvh, n_pages, page_size, _ = k_cache.shape
    group = qh // kvh
    max_pages = block_tables.shape[1]
    S = max_pages * page_size
    scale = 1.0 / math.sqrt(d)

    flat = block_tables.reshape(-1)
    k = jnp.take(k_cache, flat, axis=1)        # [kvh, B*P, page, d]
    v = jnp.take(v_cache, flat, axis=1)
    if k_scale is not None:
        k = k.astype(jnp.float32) * jnp.take(
            k_scale.astype(jnp.float32), flat, axis=1)[..., None, None]
        v = v.astype(jnp.float32) * jnp.take(
            v_scale.astype(jnp.float32), flat, axis=1)[..., None, None]
    k = k.reshape(kvh, b, S, d)
    v = v.reshape(kvh, b, S, d)

    qg = q.reshape(b, t, kvh, group, d).astype(jnp.float32)
    s = jnp.einsum("btkgd,kbsd->btkgs", qg, k.astype(jnp.float32)) * scale
    pos = jnp.arange(S)
    mask = pos[None, :] < context_lens[:, None]                    # [B, S]
    if window is None:
        s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    else:
        q_pos = context_lens[:, None].astype(jnp.int32) + jnp.arange(t)
        mask = jnp.logical_and(                                 # [B, T, S]
            mask[:, None, :], pos[None, None, :] > q_pos[:, :, None] - window)
        s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
    parts_s, parts_v = [s], [v]
    if k_new is not None:
        kn = jnp.moveaxis(k_new, 2, 0).astype(jnp.float32)   # [kvh, B, T, d]
        vn = jnp.moveaxis(v_new, 2, 0).astype(jnp.float32)
        s2 = jnp.einsum("btkgd,kbjd->btkgj", qg, kn) * scale
        jq = jnp.arange(t)
        ql = (q_lens if q_lens is not None
              else jnp.full((b,), t)).astype(jnp.int32)
        causal = jq[None, :, None] >= jq[None, None, :]          # [1, T, T]
        valid = jnp.logical_and(causal, jq[None, None, :] < ql[:, None, None])
        if window is not None:
            valid = jnp.logical_and(
                valid, jq[None, :, None] - jq[None, None, :] < window)
        s2 = jnp.where(valid[:, :, None, None, :], s2, NEG_INF)
        parts_s.append(s2)
        parts_v.append(vn)
    s_all = jnp.concatenate(parts_s, axis=-1)
    p = jax.nn.softmax(s_all, axis=-1)
    v_all = jnp.concatenate(parts_v, axis=2)                  # [kvh, B, *, d]
    out = jnp.einsum("btkgs,kbsd->btkgd", p, v_all)
    out = out.reshape(b, t, qh, d).astype(q.dtype)
    lse = jax.scipy.special.logsumexp(s_all, axis=-1).reshape(b, t, qh)
    return out, lse


def pool_of_heads(k, v):
    """Head-major K and V planes ``[..., kv_heads, num_pages, page_size,
    head_dim]`` packed into the page-major form the kernel reads, ``[...,
    num_pages, 2, kv_heads, page_size, head_dim]`` (the Paddle-facing
    entry points and the tests build pools with it)."""
    return jnp.moveaxis(jnp.stack([k, v], axis=-5), -3, -5)


def heads_of_pool(kv):
    """The head-major view of a page-major pool (one layer's, or all
    layers'): ``(k, v)``, each ``[..., kv_heads, num_pages, page_size,
    head_dim]``.  The ONE place that restates the layout for the XLA
    oracles, which take head-major planes and so stay an independent check
    of it."""
    kv = jnp.moveaxis(kv, -5, -3)
    return kv[..., 0, :, :, :, :], kv[..., 1, :, :, :, :]


# ---------------------------------------------------------------- kernel ---

def _padded_rows(t, group):
    """Query rows of one (slot, KV head) block: ``t * group``, padded to
    the sublane count."""
    return -(-max(t * group, _SUBLANE) // _SUBLANE) * _SUBLANE


def row_tile(t, group):
    """Height of the kernel's query-row tiles for a step of ``t`` query
    tokens over ``group`` query heads a KV head: the whole block up to
    ``_ROW_TILE`` rows, else the largest multiple of 16 (bf16 packs 16
    sublanes) up to it that divides the block."""
    rows = _padded_rows(t, group)
    if rows <= _ROW_TILE:
        return rows
    return next((tile for tile in range(_ROW_TILE, 0, -16)
                 if rows % tile == 0), rows)


def attn_rows(q_lens, t, group):
    """Query rows one layer's call computes for each KV head: every slot
    with work covers the prefix ``[0, q_len * group)`` of its block in
    whole row tiles.  Host arithmetic (the engine's ``attn_rows``), so no
    caller has to know the tile."""
    tile = row_tile(t, group)
    return sum(-(-int(q) * group // tile) * tile for q in q_lens if q > 0)


def _pages_per_block(page_size, max_pages, page_bytes=0):
    """Pages of one KV block: ``_BLOCK_KEYS`` keys, never more than the
    block table is wide, nor than two buffers of ``page_bytes`` a page
    (every KV head's K and V) may take of VMEM."""
    ppb = min(_BLOCK_KEYS // page_size, max_pages)
    if page_bytes:
        ppb = min(ppb, _KV_BUFFER_BYTES // (2 * page_bytes))
    return max(1, ppb)


def page_copies(rows, page_size, max_pages, page_bytes=0, window=None):
    """DMA descriptors one layer's call starts for ``rows`` = [(query
    tokens, context before them)]: a working slot fetches every page of
    every block its walk reaches (whole blocks, one copy a page).  Host
    arithmetic beside ``attn_rows`` (the engine's ``page_copies``)."""
    ppb = _pages_per_block(page_size, max_pages, page_bytes)
    n = 0
    for q, ctx in rows:
        if q <= 0:
            continue
        first = 0 if window is None else max(ctx + 1 - window, 0) // page_size
        n += -(-max(-(-ctx // page_size) - first, 0) // ppb) * ppb
    return n


def _ragged_paged_attn_kernel(*refs, page_size, ppb, tile, scale, group,
                              has_new, quantized=False, window=None,
                              layered=False):
    """One slot's program: the whole walk over the slot's KV, once for ALL
    its KV heads.

    The slot's live query rows are, for every KV head, the prefix ``[0,
    q_len * group)`` of that head's block (row ``r`` = token ``r //
    group``), covered by ``n_tiles`` row tiles of ``tile`` rows; a slot
    with ``q_len == 0`` covers none and fetches nothing.  The KV is walked
    in blocks of ``ppb`` pages.  A page of the pool is ONE contiguous run
    ``[2, kv_heads, page_size, d]`` (every head's K, then every head's V)
    and ONE copy, block ``j + 1``'s copies in flight while block ``j`` is
    computed (two buffers).  Inside a block the KV heads are a loop; head
    ``h``'s K (and V) over the block's keys is read out of the buffer as
    the ``[ppb * page_size, d]`` operand it is, and every row tile takes
    one ``QK^T``, one mask, one online-softmax update and one ``PV`` per
    (head, block).  ``q`` and ``k`` enter the MXU as stored (a bf16 x bf16
    product is exact in the float32 accumulator; ``1/sqrt(d)`` is applied
    to the float32 scores); the probabilities stay float32 into ``PV``.
    After the walk each (head, tile) folds the step's own K/V rows with a
    causal mask and normalizes.  Rows of the tiles past ``n_tiles`` are
    written as zeros.

    ``quantized`` (int8 pool): the DMA moves the pages' int8 bytes and the
    per-(kv-head, page) fp32 scales ride the scalar-prefetch channel
    beside the block table.  An int8 value is exact in the MXU's operand
    dtype, so the pages are multiplied as stored and the scales apply to
    the block's score and probability COLUMNS, page by page.

    ``window`` (static; a sliding-attention layer): query token j, at
    position ``ctx + j``, sees keys in ``(ctx + j - window, ctx + j]``.
    The walk starts at the page that holds the EARLIEST query's first
    visible key (``first``): pages wholly behind it are never fetched.

    ``layered`` (static): the cache is the WHOLE pool ``[layers,
    num_pages, 2, kv_heads, page_size, head_dim]`` in HBM and the layer to
    read rides the scalar-prefetch channel, so the caller never slices a
    layer out of the pool (a slice handed to a kernel is a copy).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    it = iter(refs)
    bt_ref, cl_ref, ql_ref = next(it), next(it), next(it)
    ly_ref = next(it) if layered else None
    ksc_ref = next(it) if quantized else None
    vsc_ref = next(it) if quantized else None
    q_ref = next(it)
    knew_ref = next(it) if has_new else None
    vnew_ref = next(it) if has_new else None
    kv_hbm = next(it)
    o_ref, lse_ref = next(it), next(it)
    kvbuf, sem = next(it), next(it)
    m_ref, l_ref, acc_ref = next(it), next(it), next(it)

    b = pl.program_id(0)
    ctx = cl_ref[b]
    ql = ql_ref[b]
    kvh, rows, d = q_ref.shape
    keys = ppb * page_size
    # all int scalars must stay strongly-typed int32: python-int divisors /
    # clip bounds embed i64 literals under x64 mode, and the i64->i32
    # convert_element_type they force breaks Mosaic lowering (the round-4
    # recursion bug) — hence lax.div/lax.rem against np.int32 constants
    i32 = np.int32
    ps_c, ppb_c, tile_c, one = i32(page_size), i32(ppb), i32(tile), i32(1)
    max_tiles = i32(pl.cdiv(rows, tile))
    last_entry = i32(bt_ref.shape[1] - 1)
    n_tiles = jnp.minimum(
        jax.lax.div(ql * i32(group) + tile_c - one, tile_c), max_tiles)
    pages_total = jax.lax.div(ctx + ps_c - one, ps_c)
    if window is None:
        first = _I0
    else:
        first = jax.lax.div(jnp.maximum(ctx + i32(1 - window), _I0), ps_c)
    n_blocks = jax.lax.div(
        jnp.maximum(pages_total - first, _I0) + ppb_c - one, ppb_c)
    # q and k meet in q's dtype where the cache holds it or int8 (exact
    # there), else (a float32 cache under bf16 queries) in float32
    mxu = q_ref.dtype if kvbuf.dtype in (q_ref.dtype, jnp.int8) \
        else jnp.float32

    def rows_of(i):
        return pl.ds(pl.multiple_of(i * tile_c, tile), tile)

    def for_live_tiles(body):
        """``body(i)`` for every live row tile (a block of one tile has
        no loop: this runs where ``n_tiles > 0``)."""
        if rows == tile:
            body(_I0)
            return

        def step(i, carry):
            body(i)
            return carry

        jax.lax.fori_loop(_I0, n_tiles, step, _I0)

    def for_heads(body):
        """``body(h)`` for every KV head: a loop with a dynamic index, not
        ``kv_heads`` copies of the body (what is written out is lowered at
        every set-up).  Static bounds: a while_loop keeps the counter
        int32 under x64."""
        if kvh == 1:
            body(_I0)
            return

        def step(h):
            body(h)
            return h + one

        jax.lax.while_loop(lambda h: h < i32(kvh), step, _I0)

    def fetch(j, slot):
        """Start the copies of block ``j`` into buffer ``slot``: one DMA a
        page, every head's K and V at once.  The whole block is fetched
        whatever the context holds of it (an entry past the context, or
        past the table, names a valid page whose keys are masked), so one
        wait takes all copies.  A loop, not ``ppb`` copies written out:
        unrolled, the kernel takes ten times as long to lower, which every
        run pays for every step program."""
        p0 = first + j * ppb_c

        def page(i):
            pid = bt_ref[b, jnp.minimum(p0 + i, last_entry)]
            src = kv_hbm.at[ly_ref[0], pid] if layered else kv_hbm.at[pid]
            pltpu.make_async_copy(src, kvbuf.at[slot, i],
                                  sem.at[slot]).start()
            return i + one

        jax.lax.while_loop(lambda i: i < ppb_c, page, _I0)

    def wait(slot):
        """Wait for the ``ppb`` page copies into buffer ``slot``: a DMA
        semaphore counts bytes, and this descriptor is a block's."""
        pltpu.make_async_copy(kvbuf.at[slot], kvbuf.at[slot],
                              sem.at[slot]).wait()

    def keys_of(slot, which, h):
        """Head ``h``'s K (``which`` 0) or V (1) over the block in buffer
        ``slot``, ``[keys, d]``: a page's ``[page_size, d]`` of one head is
        whole tiles, so the pages stack without a relayout."""
        return kvbuf[slot, :, which, h].reshape(keys, d)

    def accumulate(h, r, s, v, v_scale=None):
        """Online-softmax update of row tile ``r`` of head ``h``'s (m, l,
        acc) scratch with scores ``s`` over the keys whose values are
        ``v``."""
        m_prev, l_prev = m_ref[h, r, :], l_ref[h, r, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[h, r, :] = m_new
        l_ref[h, r, :] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if v_scale is not None:
            p = p * v_scale
        # float32 p x v as stored: the compiler's mixed product (a split
        # of p into three bf16 terms by hand measured 15-35 % slower)
        acc_ref[h, r, :] = alpha * acc_ref[h, r, :] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def scores(h, r, k):
        return jax.lax.dot_general(
            q_ref[h, r, :].astype(mxu), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(scale)

    def tokens_of(i):
        """[tile, 1]: the query token of each row of tile ``i``."""
        r = i * tile_c + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        return jax.lax.div(r, jnp.full((tile, 1), group, jnp.int32))

    def column_scales(sc_ref, h, p0):
        """[1, keys]: head ``h``'s dequant scale of each key column of the
        block that starts at page ``p0``."""
        col = jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        out = jnp.zeros((1, keys), jnp.float32)
        for i in range(ppb):
            pid = bt_ref[b, jnp.minimum(p0 + i32(i), last_entry)]
            out = jnp.where(col >= i32(i * page_size), sc_ref[h, pid], out)
        return out

    def block(j, carry):
        slot = jax.lax.rem(j, i32(2))

        @pl.when(j + one < n_blocks)
        def _prefetch():
            fetch(j + one, one - slot)

        wait(slot)
        p0 = first + j * ppb_c

        # the last block's pages past the context are some other
        # sequence's: their scores are masked, but p = 0 times a V that is
        # not finite would still be NaN, so no value of theirs is kept
        def clear(i, c):
            kvbuf[slot, i, 1] = jnp.zeros((kvh, page_size, d), kvbuf.dtype)
            return c

        jax.lax.fori_loop(jnp.minimum(pages_total - p0, ppb_c), ppb_c,
                          clear, _I0)
        base = p0 * ps_c

        def head(h):
            k = keys_of(slot, 0, h).astype(mxu)             # [keys, d]
            v = keys_of(slot, 1, h)
            if quantized:        # exact, and the product bf16 pools take
                v = v.astype(jnp.bfloat16)
            k_scale = column_scales(ksc_ref, h, p0) if quantized else None
            v_scale = column_scales(vsc_ref, h, p0) if quantized else None

            def row_tile_of_block(i):
                r = rows_of(i)
                s = scores(h, r, k)
                if quantized:
                    s = s * k_scale
                pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                seen = pos < ctx
                if window is not None:
                    seen = jnp.logical_and(
                        seen, pos > ctx + tokens_of(i) - i32(window))
                accumulate(h, r, jnp.where(seen, s, jnp.float32(NEG_INF)),
                           v, v_scale)

            for_live_tiles(row_tile_of_block)

        for_heads(head)
        return carry

    def finish(h):
        def tile_of_head(i):
            r = rows_of(i)
            if has_new:   # static: compiled in only for the mixed-mode form
                s = scores(h, r, knew_ref[h].astype(mxu))    # [tile, Tp]
                jq = tokens_of(i)
                jk = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                valid = jnp.logical_and(jk <= jq, jk < ql)
                if window is not None:
                    valid = jnp.logical_and(valid, jq - jk < i32(window))
                s = jnp.where(valid, s, jnp.float32(NEG_INF))
                accumulate(h, r, s, vnew_ref[h])
            l = jnp.maximum(l_ref[h, r, :], jnp.float32(1e-30))
            o_ref[h, r, :] = (acc_ref[h, r, :] / l).astype(o_ref.dtype)
            lse_ref[h, r, :] = m_ref[h, r, :] + jnp.log(l)

        for_live_tiles(tile_of_head)

    # rows past the live tiles (all of them where the slot holds nothing)
    # are don't-care by contract: they read zeros, never what VMEM held
    @pl.when(n_tiles < max_tiles)
    def _blank():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
        lse_ref[...] = jnp.zeros(lse_ref.shape, jnp.float32)

    @pl.when(n_tiles > _I0)
    def _work():
        @pl.when(n_blocks > _I0)
        def _warmup():
            fetch(_I0, _I0)

        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        jax.lax.fori_loop(_I0, n_blocks, block, _I0)
        for_heads(finish)


def _lane_padded_bytes(shape, dtype):
    """VMEM bytes of a buffer whose last dim is padded to 128 lanes."""
    *lead, last = shape
    return int(np.prod(lead, dtype=np.int64)) * (-(-last // 128) * 128) \
        * jnp.dtype(dtype).itemsize


def _vmem_limit(need):
    """What a call asks of VMEM for ``need`` bytes of buffers it can count
    from its static shapes: a quarter more (the compiler's own temporaries),
    at least 32 MiB, and under the chip's 128."""
    return int(min(max(need * 5 // 4, 32 << 20), 110 << 20))


# jitted so that the kernel is traced once for all the call sites of one
# signature (a step program calls it once a layer of its period, every
# member of a step family again) and lowered once a program: lowering it
# is paid at every set-up, cache hit or not (PERF.md section 6, PR 28)
@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def _pallas_ragged_paged_attention(q, kv_cache, block_tables, context_lens,
                                   q_lens, k_new, v_new, interpret,
                                   k_scale=None, v_scale=None, window=None,
                                   layer=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, qh, d = q.shape
    layered = layer is not None
    n_pages, _, kvh, page_size, _ = kv_cache.shape[-5:]
    group = qh // kvh
    rows = t * group
    R = _padded_rows(t, group)

    # [B, T, qh, d] -> [B, kvh, T*group, d]: row r = token*(group) + g, so
    # one block holds every query row of the slot, a KV head's together,
    # and a head's live rows are its prefix [0, q_len * group)
    qg = q.reshape(b, t, kvh, group, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, kvh, rows, d)
    if R != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, R - rows), (0, 0)))

    page_shape = (2, kvh, page_size, d)
    ppb = _pages_per_block(page_size, block_tables.shape[1],
                           math.prod(page_shape) * kv_cache.dtype.itemsize)
    keys = ppb * page_size

    # unused table entries must still be valid page ids for the DMA
    bt = jnp.clip(block_tables, 0, n_pages - 1).astype(jnp.int32)
    cl = context_lens.astype(jnp.int32)
    ql = (q_lens if q_lens is not None
          else jnp.full((b,), t)).astype(jnp.int32)

    def block_of(block_rows, last):
        return pl.BlockSpec((None, kvh, block_rows, last),
                            lambda b_, *_: (b_, _I0, _I0, _I0))

    has_new = k_new is not None
    operands = [qg]
    in_specs = [block_of(R, d)]
    Tp = -(-t // _SUBLANE) * _SUBLANE
    if has_new:
        kn = k_new.transpose(0, 2, 1, 3)        # [B, kvh, T, d]
        vn = v_new.transpose(0, 2, 1, 3)
        if Tp != t:
            pad = ((0, 0), (0, 0), (0, Tp - t), (0, 0))
            kn, vn = jnp.pad(kn, pad), jnp.pad(vn, pad)
        operands += [kn, vn]
        in_specs += [block_of(Tp, d), block_of(Tp, d)]
    quantized = k_scale is not None
    scalars = [bt, cl, ql]
    if layered:
        scalars.append(jnp.asarray(layer, jnp.int32).reshape(1))
    if quantized:
        # one fp32 per (kv-head, page), scalar-prefetched (kvh * n_pages
        # * 4 bytes of SMEM): scalar loads at [head, page id], the same
        # dynamic-index shape as the block table beside it.  (As a plain
        # SMEM *operand* of a scalar-prefetch grid Mosaic refuses it.)
        scalars += [k_scale.astype(jnp.float32),
                    v_scale.astype(jnp.float32)]
    operands.append(kv_cache)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    tile = row_tile(t, group)
    kernel = functools.partial(
        _ragged_paged_attn_kernel, page_size=page_size, ppb=ppb,
        tile=tile, scale=1.0 / math.sqrt(d), group=group,
        has_new=has_new, quantized=quantized, window=window, layered=layered)
    scratch = [((2, ppb) + page_shape, kv_cache.dtype),
               ((kvh, R, 1), jnp.float32), ((kvh, R, 1), jnp.float32),
               ((kvh, R, d), jnp.float32)]
    # a slot's program holds every KV head's rows: the two KV buffers, the
    # (m, l, acc) carry, the pipeline's two buffers of each q, fresh-row,
    # output and log-sum-exp block (a [.., 1] column pads to 128 lanes)
    # and a tile's float32 scores, probabilities and mask.  At 8 KV heads
    # of 1,024 rows that is past the 16 MiB a call is given by default
    need = sum(_lane_padded_bytes(sh, dt) for sh, dt in scratch) \
        + 2 * (2 * _lane_padded_bytes((kvh, R, d), q.dtype)
               + _lane_padded_bytes((kvh, R, 1), jnp.float32)) \
        + (4 * _lane_padded_bytes((kvh, Tp, d), q.dtype) if has_new else 0) \
        + 3 * _lane_padded_bytes((tile, keys), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b,),
        in_specs=in_specs,
        out_specs=[block_of(R, d), block_of(R, 1)],
        scratch_shapes=[pltpu.VMEM(*scratch[0]),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM(*scratch[1]), pltpu.VMEM(*scratch[2]),
                        pltpu.VMEM(*scratch[3])],
    )
    out, lse = pl.pallas_call(
        kernel,
        # a trace tells the layer kinds apart by the window in the name
        name="ragged_paged_attention" + (
            "" if window is None else f"_w{window}"),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, kvh, R, d), q.dtype),
                   jax.ShapeDtypeStruct((b, kvh, R, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(need)),
        interpret=interpret,
    )(*scalars, *operands)
    out = out[:, :, :rows].reshape(b, kvh, t, group, d)
    out = out.transpose(0, 2, 1, 3, 4).reshape(b, t, qh, d)
    lse = lse[:, :, :rows, 0].reshape(b, kvh, t, group)
    lse = lse.transpose(0, 2, 1, 3).reshape(b, t, qh)
    return out, lse


# ----------------------------------------------------------- entry points ---

# SMEM the compiler keeps for itself and the kernel's small scalar operands
# beside the scale planes and the block table: between 4 and 8 KiB, found by
# bisecting compiles for a described v5e (PR 21) with a one-tile table
_SMEM_RESERVE_BYTES = 4 << 10


def _smem_need_bytes(kv_heads, num_pages, table_entries_padded):
    """SMEM of an int8 pool's scalar-prefetched operands: two fp32 scale
    planes whose page axis is padded to the 128-lane tile, and the int32
    block table."""
    lanes = -(-num_pages // 128) * 128
    return (2 * 4 * kv_heads * lanes + 4 * table_entries_padded
            + _SMEM_RESERVE_BYTES)


def kernel_geometry_error(page_size, head_dim, *, quantized=False,
                          kv_heads=0, num_pages=0, table_shape=(0, 0),
                          smem_bytes=None, interpret=False, latent=None,
                          dtype="bfloat16"):
    """The rule a paged-KV geometry fails for the Pallas kernel it is asked
    about, as a sentence, or None when the kernel covers it.  On a TPU a
    failing geometry raises (here at trace time, and in the engine when it
    is built); off-TPU the XLA reference takes it.

    ``smem_bytes`` is the scalar memory of the core the kernel is built
    for; None asks the attached TPU (``pltpu.get_tpu_info``) and, with no
    TPU attached, leaves the SMEM rule to the compiler.

    ``dtype`` is the pool's (an int8 pool says ``quantized`` too and is
    held to its own rule of 32): a page
    is one copy and one head's ``[page_size, head_dim]`` of it is read out
    of the block buffer as whole tiles, so ``page_size`` must be a multiple
    of the sublanes a tile of that type packs (float32 8, bfloat16 16,
    int8 32).

    ``interpret``: the kernel as the interpreter runs it (CPU tests), which
    has no tiling: a page of 8 bfloat16 rows and a head half a tile wide
    (``head_dim % 128 == 64``) pass there, while the TPU compiler refuses
    to slice the latter out of the pool (``Slice shape along dimension 3
    must be aligned to tiling (128)``) and so does this rule.

    ``latent=(rank, rope)`` asks about the latent call
    (``ragged_paged_attention_latent``; ``head_dim`` is then ignored): its
    pool is the compressed rows ``[.., page_size, rank]`` and the rotary
    keys two tokens a row ``[.., page_size / 2, 2 * rope]``, each page a
    whole number of ``(8, 128)`` tiles of both."""
    if latent is not None:
        rank, rope = latent
        if quantized:
            return "the latent call has no int8 plane"
        if page_size % 2:
            return (f"page_size ({page_size}) must be even: the rotary "
                    "keys lie two tokens a row")
        if interpret:
            return None
        if page_size % 16:
            return (f"page_size ({page_size}) must be a multiple of 16: "
                    "half a page of rotary keys is a whole sublane tile")
        if rank % 128 or (2 * rope) % 128:
            return (f"the compressed row ({rank}) and two rotary keys "
                    f"(2 x {rope}) must each fill whole 128-lane tiles")
        return None
    # f32 sublane is 8; bf16 packs 16 and int8 32 sublanes per tile: one
    # head's rows of a page must be whole tiles of the block buffer
    if page_size % 8:
        return f"page_size ({page_size}) must be a multiple of 8"
    packs = 32 // jnp.dtype(dtype).itemsize
    if not interpret and not quantized and page_size % packs:
        return (f"page_size ({page_size}) must be a multiple of {packs}: a "
                f"head's rows of a {jnp.dtype(dtype).name} page are whole "
                f"({packs}, 128) tiles of the block the kernel copies it to")
    if head_dim % 128 not in ((0, 64) if interpret else (0,)):
        return (f"head_dim must be a multiple of 128 (the compiler slices "
                f"whole lane tiles out of the pool), got {head_dim}")
    if quantized and page_size % 32:
        return (f"an int8 pool needs page_size % 32 == 0 (int8 packs 32 "
                f"sublanes per tile), got {page_size}")
    if quantized and smem_bytes is None and jax.default_backend() == "tpu":
        from jax.experimental.pallas import tpu as pltpu
        smem_bytes = pltpu.get_tpu_info().smem_capacity_bytes
    if quantized and smem_bytes is not None:
        # both scale planes ride the scalar-prefetch channel, i.e. SMEM,
        # which they share with the block table.  Bisected on a described
        # v5e (1 MiB): 32 kv heads compile up to 3968 pages, 8 up to
        # 16256, 4 up to 32512 — the next 128 pages are refused.
        rows, width = table_shape
        need = _smem_need_bytes(kv_heads, num_pages,
                                rows * (-(-width // 128) * 128))
        if need > smem_bytes:
            return (f"an int8 pool's per-(kv-head, page) fp32 scales are "
                    f"scalar-prefetched into SMEM with the block table: "
                    f"kv_heads ({kv_heads}) x num_pages ({num_pages}) x 8 "
                    f"bytes + a {rows}x{width} table need {need} of "
                    f"{smem_bytes} bytes — use fewer, larger pages or "
                    "shard kv heads (tensor_parallel)")
    return None


def ragged_paged_attention(q, kv_cache, block_tables, context_lens, *,
                           q_lens=None, k_new=None, v_new=None,
                           k_scale=None, v_scale=None, with_lse=False,
                           window=None, layer=None):
    """Mixed-mode serving attention: prefill chunks and decode tokens in one
    call over a paged KV cache.

    Args:
      q:            [batch, T, num_q_heads, head_dim] — this step's query
                    tokens (T = 1 for pure decode, the chunk length for
                    chunked prefill; sequences ragged via ``q_lens``).
      kv_cache:     [num_pages, 2, num_kv_heads, page_size, head_dim] (a
                    page holds every head's K, then every head's V:
                    ``pool_of_heads`` packs head-major planes so), or the
                    whole pool [layers, num_pages, ...] with ``layer``.
      block_tables: [batch, max_pages_per_seq] int32 page ids (pad with 0).
      context_lens: [batch] int32 — tokens ALREADY in the cache (the prior
                    context; this step's own tokens are NOT included).
      q_lens:       [batch] int32 — valid query tokens per sequence
                    (None = all T).  Output rows past q_lens[b] are
                    don't-care: the kernel computes whole row tiles over
                    the live rows and writes zeros past them, and a
                    sequence with q_lens[b] == 0 is not computed at all
                    (no DMA, no fold of k_new, no normalization).
      k_new/v_new:  [batch, T, num_kv_heads, head_dim] — the step's fresh
                    KV rows, folded in with a causal mask (token j attends
                    new tokens <= j).  They need not be written to the
                    cache before the call; commit them after the step.
      k_scale/v_scale: [num_kv_heads, num_pages] fp32 — per-(kv-head,
                    page) dequant scales of an int8 cache pool, applied
                    inside the kernel (to a block's score and
                    probability columns, page by page) — nothing
                    downstream changes shape.
      with_lse:     also return the per-query logsumexp [batch, T, q_heads]
                    (fp32) for online-softmax merging of extra keys.
      window:       static int or None — sliding attention: the query
                    token at position p sees keys in ``(p - window, p]``.
                    Pages that end before the earliest query's window are
                    skipped in the walk, not masked after the fetch, and
                    the kernel is named ``ragged_paged_attention_w<window>``.
      layer:        int32 scalar (may be traced) — with the whole pool as
                    ``kv_cache``, the layer whose pages are read: the
                    kernel indexes the pool in HBM, no layer is sliced out
                    of it (``k_scale``/``v_scale`` stay one layer's
                    [num_kv_heads, num_pages] planes).

    Returns [batch, T, num_q_heads, head_dim] (and lse when requested).
    """
    b, t, qh, d = q.shape
    if window is not None and (int(window) != window or window < 1):
        raise ValueError(f"window must be a whole number >= 1, got {window!r}")
    window = None if window is None else int(window)
    if (kv_cache.ndim == 6) != (layer is not None):
        raise ValueError("a whole pool [layers, ...] is read at `layer`; "
                         "one layer's cache takes none")
    n_pool_pages, two, kvh, page_size, _ = kv_cache.shape[-5:]
    if two != 2:
        raise ValueError(f"a page holds K and V: axis -4 of the pool must "
                         f"be 2, got {kv_cache.shape}")
    if qh % kvh:
        raise ValueError(f"q heads ({qh}) must be a multiple of kv heads ({kvh})")
    if (k_new is None) != (v_new is None):
        raise ValueError("k_new and v_new must be given together")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    on_tpu = jax.default_backend() == "tpu"
    why = kernel_geometry_error(
        page_size, d, quantized=k_scale is not None, kv_heads=kvh,
        num_pages=n_pool_pages, table_shape=block_tables.shape,
        interpret=not on_tpu, dtype=kv_cache.dtype)
    if on_tpu and why:
        # the serving hot op has no business on the XLA reference on a chip
        raise ValueError(f"ragged_paged_attention on TPU: {why}")
    if (on_tpu or flags.flag("paged_attention_interpret")) and not why:
        out, lse = _pallas_ragged_paged_attention(
            q, kv_cache, block_tables, context_lens, q_lens,
            k_new, v_new, interpret=not on_tpu, k_scale=k_scale,
            v_scale=v_scale, window=window, layer=layer)
    else:
        if layer is not None:       # the oracle takes one layer's cache
            kv_cache = jax.lax.dynamic_index_in_dim(
                kv_cache, layer, axis=0, keepdims=False)
        out, lse = _reference_ragged_paged_attention(
            q, *heads_of_pool(kv_cache), block_tables, context_lens, q_lens,
            k_new, v_new, k_scale=k_scale, v_scale=v_scale, window=window)
    return (out, lse) if with_lse else out


# ------------------------------------------------------ the latent call ---
# Latent attention (MLA, ``models.decoder_spec.LatentAttn``) over a latent
# page pool: a token's row is ``[c | k_r]`` (``rank`` + ``rope`` numbers),
# every query head attends the SAME row, and the value is the first
# ``rank`` numbers of the key.  The caller has absorbed ``W_uk`` into the
# query (``q_c``, ``rank`` wide) and applies ``W_uv`` to the result.

def unpack_rope_pages(r, rope):
    """``[..., half, 2 * rope]`` (two tokens a row: token ``t`` of a page in
    row ``t % half``, lanes ``[(t // half) * rope, + rope)``) ->
    ``[..., 2 * half, rope]``, one token a row in order."""
    lead, half = r.shape[:-2], r.shape[-2]
    r = r.reshape(lead + (half, 2, rope))
    return jnp.swapaxes(r, -3, -2).reshape(lead + (2 * half, rope))


def _reference_ragged_paged_attention_latent(q_c, q_r, c_cache, r_cache,
                                             block_tables, context_lens,
                                             q_lens, c_new, r_new, scale,
                                             selected=None):
    """XLA oracle of the latent call (one layer's pool).  q_c ``[B, T, H,
    rank]``, q_r ``[B, T, H, rope]``; c_cache ``[P, page, rank]``, r_cache
    ``[P, page / 2, 2 * rope]``; c_new ``[B, T, rank]``, r_new ``[B, T,
    rope]``.  Returns ``[B, T, H, rank]``: ``sum_j a_j c_j``.  ``selected``
    (bool ``[B, T, S (+ T)]``, the sparse call's): a query token's softmax
    runs over the columns it names alone."""
    b, t, _, rank = q_c.shape
    rope = q_r.shape[-1]
    n_pages, page_size, _ = c_cache.shape
    max_pages = block_tables.shape[1]
    S = max_pages * page_size
    f32 = jnp.float32
    flat = block_tables.reshape(-1)
    c = jnp.take(c_cache, flat, axis=0).reshape(b, S, rank).astype(f32)
    r = unpack_rope_pages(jnp.take(r_cache, flat, axis=0), rope)
    r = r.reshape(b, S, rope).astype(f32)
    qc, qr = q_c.astype(f32), q_r.astype(f32)
    s = (jnp.einsum("bthr,bsr->bths", qc, c)
         + jnp.einsum("bthd,bsd->bths", qr, r)) * scale
    mask = jnp.arange(S)[None, :] < context_lens[:, None]          # [B, S]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    parts_s, parts_v = [s], [c]
    if c_new is not None:
        cn, rn = c_new.astype(f32), r_new.astype(f32)
        s2 = (jnp.einsum("bthr,bjr->bthj", qc, cn)
              + jnp.einsum("bthd,bjd->bthj", qr, rn)) * scale
        jq = jnp.arange(t)
        ql = (q_lens if q_lens is not None
              else jnp.full((b,), t)).astype(jnp.int32)
        valid = jnp.logical_and(jq[None, :, None] >= jq[None, None, :],
                                jq[None, None, :] < ql[:, None, None])
        parts_s.append(jnp.where(valid[:, :, None, :], s2, NEG_INF))
        parts_v.append(cn)
    s = jnp.concatenate(parts_s, axis=-1)
    if selected is not None:
        s = jnp.where(selected[:, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bths,bsr->bthr", p, jnp.concatenate(parts_v, axis=1))
    return out.astype(q_c.dtype)


def _latent_attn_kernel(*refs, page_size, half, ppb, tile, scale, heads,
                        has_new, layered, sparse=False):
    """One slot's program of the latent call, on the schedule of
    ``_ragged_paged_attn_kernel``: the slot's live rows are the prefix
    ``[0, q_len * heads)`` (row ``r`` = token ``r // heads``) in row tiles
    of ``tile``; the KV is walked in blocks of ``ppb`` pages, two buffers.

    A block's pages land in ONE ``[keys, rank]`` tile of compressed rows
    and ONE ``[keys / 2, 2 * rope]`` tile of rotary keys (two tokens a row,
    as the pool holds them).  So that the score columns of both parts line
    up, a page's compressed rows are copied as its two halves: the block's
    first ``keys / 2`` rows hold every page's tokens ``[0, half)``, the
    rest their tokens ``[half, page)``, and the rotary scores are two
    products, the query's rotary part against the lower and against the
    upper lanes (``q_lo = [q_r | 0]``, ``q_hi = [0 | q_r]``), side by side.
    The order of a block's keys is the kernel's own business: the mask
    works from each column's position.  ``PV`` multiplies the SAME
    compressed tile that made the scores: a page is read once for key and
    value.  The probabilities enter ``PV`` in the pool's dtype where that
    is bfloat16 (half of this call's operations are ``PV``; the float32 x
    bf16 product costs several passes), float32 otherwise.

    ``sparse`` (``ragged_paged_attention_latent_sparse``): the walk is the
    same and every score is MASKED to the query token's chosen set.  The
    set comes as 0/1 rows a query token (``[tokens, keys]`` a block, in
    the block's own key order, copied beside the block's pages; ``[tokens,
    own rows]`` for the step's rows); a row tile's mask is the product of
    its rows' one-hot token numbers with them (exact: one term a sum).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    it = iter(refs)
    bt_ref, cl_ref, ql_ref = next(it), next(it), next(it)
    ly_ref = next(it) if layered else None
    qc_ref, qlo_ref, qhi_ref = next(it), next(it), next(it)
    cnew_ref = next(it) if has_new else None
    rnew_ref = next(it) if has_new else None
    snew_ref = next(it) if sparse and has_new else None
    c_hbm, r_hbm = next(it), next(it)
    sel_hbm = next(it) if sparse else None
    o_ref = next(it)
    cbuf, rbuf, sem = next(it), next(it), next(it)
    sbuf = next(it) if sparse else None
    m_ref, l_ref, acc_ref = next(it), next(it), next(it)

    b = pl.program_id(0)
    ctx = cl_ref[b]
    ql = ql_ref[b]
    rows, rank = qc_ref.shape
    hk = rbuf.shape[1]              # half a block's keys: two tokens a row
    keys = ppb * page_size
    i32 = np.int32
    ps_c, ppb_c, tile_c, one = i32(page_size), i32(ppb), i32(tile), i32(1)
    half_c, hk_c = i32(half), i32(hk)
    max_tiles = i32(pl.cdiv(rows, tile))
    last_entry = i32(bt_ref.shape[1] - 1)
    n_tiles = jnp.minimum(
        jax.lax.div(ql * i32(heads) + tile_c - one, tile_c), max_tiles)
    pages_total = jax.lax.div(ctx + ps_c - one, ps_c)
    n_blocks = jax.lax.div(pages_total + ppb_c - one, ppb_c)
    mxu = qc_ref.dtype if cbuf.dtype == qc_ref.dtype else jnp.float32
    p_dtype = jnp.bfloat16 if cbuf.dtype == jnp.bfloat16 else jnp.float32

    def rows_of(i):
        return pl.ds(pl.multiple_of(i * tile_c, tile), tile)

    def half_rows(i, upper=False):
        """Rows of page ``i``'s lower (or upper) half in a block's tiles."""
        start = i * half_c + (hk_c if upper else _I0)
        return pl.ds(pl.multiple_of(start, half), half)

    def for_live_tiles(body):
        if rows == tile:
            body(_I0)
            return

        def step(i, carry):
            body(i)
            return carry

        jax.lax.fori_loop(_I0, n_tiles, step, _I0)

    def fetch(j, slot):
        """Start the copies of block ``j``: a page's compressed rows as
        its two halves, its rotary keys as the one tile they are."""
        p0 = j * ppb_c

        def page(i):
            pid = bt_ref[b, jnp.minimum(p0 + i, last_entry)]
            c_pg = c_hbm.at[ly_ref[0], pid] if layered else c_hbm.at[pid]
            r_pg = r_hbm.at[ly_ref[0], pid] if layered else r_hbm.at[pid]
            for upper in (False, True):
                pltpu.make_async_copy(
                    c_pg.at[pl.ds(half if upper else 0, half)],
                    cbuf.at[slot, half_rows(i, upper)],
                    sem.at[slot, _I0]).start()
            pltpu.make_async_copy(r_pg, rbuf.at[slot, half_rows(i)],
                                  sem.at[slot, one]).start()
            return i + one

        jax.lax.while_loop(lambda i: i < ppb_c, page, _I0)
        if sparse:
            pltpu.make_async_copy(sel_hbm.at[b, j], sbuf.at[slot],
                                  sem.at[slot, i32(2)]).start()

    def wait(slot):
        bufs = ((cbuf, _I0), (rbuf, one)) + (
            ((sbuf, i32(2)),) if sparse else ())
        for buf, col in bufs:
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sem.at[slot, col]).wait()

    def chosen(i, rows01):
        """[tile, columns] bool: row tile ``i``'s rows against the 0/1
        rows a query token (``rows01 [tokens, columns]``)."""
        tok = jax.lax.broadcasted_iota(jnp.int32,
                                       (tile, rows01.shape[0]), 1)
        onehot = (tok == tokens_of(i)).astype(rows01.dtype)
        return jax.lax.dot_general(
            onehot, rows01, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) > jnp.float32(0.5)

    def accumulate(r, s, v):
        m_prev, l_prev = m_ref[r, :], l_ref[r, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if sparse:
            # a tile may find none of a row's chosen keys in a block
            p = jnp.where(s > jnp.float32(NEG_INF / 2), p, jnp.float32(0.0))
        alpha = jnp.exp(m_prev - m_new)
        m_ref[r, :] = m_new
        l_ref[r, :] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[r, :] = alpha * acc_ref[r, :] + jax.lax.dot_general(
            p.astype(p_dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def dot_t(a, k):
        return jax.lax.dot_general(
            a.astype(mxu), k.astype(mxu), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def tokens_of(i):
        r = i * tile_c + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        return jax.lax.div(r, jnp.full((tile, 1), heads, jnp.int32))

    def block(j, carry):
        slot = jax.lax.rem(j, i32(2))

        @pl.when(j + one < n_blocks)
        def _prefetch():
            fetch(j + one, one - slot)

        wait(slot)
        p0 = j * ppb_c

        # pages past the context are some other sequence's: their scores
        # are masked, their values must not be kept (0 x non-finite)
        def clear(i, c):
            for upper in (False, True):
                cbuf[slot, half_rows(i, upper), :] = jnp.zeros(
                    (half, rank), cbuf.dtype)
            return c

        jax.lax.fori_loop(jnp.minimum(pages_total - p0, ppb_c), ppb_c,
                          clear, _I0)

        c = cbuf[slot]                                      # [keys, rank]
        kr = rbuf[slot]                                     # [hk, 2 rope]
        # the position of each key column: the first hk columns are the
        # pages' lower halves, the rest their upper halves
        col = jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        upper = col >= hk_c
        j2 = jnp.where(upper, col - hk_c, col)
        half_v = jnp.full((1, keys), half, jnp.int32)
        pos = (p0 + jax.lax.div(j2, half_v)) * ps_c \
            + jax.lax.rem(j2, half_v) + jnp.where(upper, half_c, _I0)
        seen = pos < ctx

        def row_tile_of_block(i):
            r = rows_of(i)
            s = dot_t(qc_ref[r, :], c) + jnp.concatenate(
                [dot_t(qlo_ref[r, :], kr), dot_t(qhi_ref[r, :], kr)], axis=1)
            s = s * jnp.float32(scale)
            keep = seen if not sparse else jnp.logical_and(
                seen, chosen(i, sbuf[slot]))
            accumulate(r, jnp.where(keep, s, jnp.float32(NEG_INF)), c)

        for_live_tiles(row_tile_of_block)
        return carry

    def finish(i):
        r = rows_of(i)
        if has_new:
            # the step's own rows, one token a row in order; their rotary
            # keys lie in the lower lanes, so q_lo alone meets them
            s = (dot_t(qc_ref[r, :], cnew_ref[...])
                 + dot_t(qlo_ref[r, :], rnew_ref[...])) * jnp.float32(scale)
            jq = tokens_of(i)
            jk = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            valid = jnp.logical_and(jk <= jq, jk < ql)
            if sparse:
                valid = jnp.logical_and(valid, chosen(i, snew_ref[...]))
            accumulate(r, jnp.where(valid, s, jnp.float32(NEG_INF)),
                       cnew_ref[...])
        l = jnp.maximum(l_ref[r, :], jnp.float32(1e-30))
        o_ref[r, :] = (acc_ref[r, :] / l).astype(o_ref.dtype)

    @pl.when(n_tiles < max_tiles)
    def _blank():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(n_tiles > _I0)
    def _work():
        @pl.when(n_blocks > _I0)
        def _warmup():
            fetch(_I0, _I0)

        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        jax.lax.fori_loop(_I0, n_blocks, block, _I0)
        for_live_tiles(finish)


@functools.partial(jax.jit, static_argnames=("interpret", "scale"))
def _pallas_ragged_paged_attention_latent(q_c, q_r, c_cache, r_cache,
                                          block_tables, context_lens, q_lens,
                                          c_new, r_new, interpret, scale,
                                          layer=None, selected=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, heads, rank = q_c.shape
    rope = q_r.shape[-1]
    layered = layer is not None
    sparse = selected is not None
    n_pages, page_size, _ = c_cache.shape[-3:]
    rows = t * heads
    R = _padded_rows(t, heads)
    zeros = jnp.zeros_like(q_r)
    qc = q_c.reshape(b, rows, rank)
    q_lo = jnp.concatenate([q_r, zeros], axis=-1).reshape(b, rows, 2 * rope)
    q_hi = jnp.concatenate([zeros, q_r], axis=-1).reshape(b, rows, 2 * rope)
    if R != rows:
        pad = ((0, 0), (0, R - rows), (0, 0))
        qc, q_lo, q_hi = (jnp.pad(a, pad) for a in (qc, q_lo, q_hi))

    ppb = _pages_per_block(page_size, block_tables.shape[1])
    keys = ppb * page_size
    bt = jnp.clip(block_tables, 0, n_pages - 1).astype(jnp.int32)
    cl = context_lens.astype(jnp.int32)
    ql = (q_lens if q_lens is not None
          else jnp.full((b,), t)).astype(jnp.int32)

    def block_of(block_rows, last):
        return pl.BlockSpec((None, block_rows, last),
                            lambda b_, *_: (b_, _I0, _I0))

    has_new = c_new is not None
    operands = [qc, q_lo, q_hi]
    in_specs = [block_of(R, rank), block_of(R, 2 * rope),
                block_of(R, 2 * rope)]
    Tp = -(-t // _SUBLANE) * _SUBLANE
    if has_new:
        rn = jnp.concatenate([r_new, jnp.zeros_like(r_new)], axis=-1)
        cn = c_new
        if Tp != t:
            pad = ((0, 0), (0, Tp - t), (0, 0))
            cn, rn = jnp.pad(cn, pad), jnp.pad(rn, pad)
        operands += [cn, rn]
        in_specs += [block_of(Tp, rank), block_of(Tp, 2 * rope)]
    scalars = [bt, cl, ql]
    if layered:
        scalars.append(jnp.asarray(layer, jnp.int32).reshape(1))
    if sparse:
        # the chosen set as 0/1 rows a query token (16 rows at least: a
        # bfloat16 tile), a block's columns in the kernel's own key order
        # (every page's lower half, then every page's upper half)
        S = block_tables.shape[1] * page_size
        n_blocks = -(-block_tables.shape[1] // ppb)
        Tb = -(-t // 16) * 16
        half = page_size // 2
        sel = jnp.pad(selected[..., :S].astype(jnp.bfloat16),
                      ((0, 0), (0, Tb - t), (0, n_blocks * keys - S)))
        sel = sel.reshape(b, Tb, n_blocks, ppb, 2, half)
        sel = sel.transpose(0, 2, 1, 4, 3, 5).reshape(b, n_blocks, Tb, keys)
        if has_new:
            operands.append(jnp.pad(
                selected[..., S:].astype(jnp.bfloat16),
                ((0, 0), (0, Tb - t), (0, Tp - t))))
            in_specs.append(block_of(Tb, Tp))
    operands += [c_cache, r_cache]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)]
    if sparse:
        operands.append(sel)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    tile = row_tile(t, heads)
    kernel = functools.partial(
        _latent_attn_kernel, page_size=page_size, half=page_size // 2, ppb=ppb,
        tile=tile, scale=scale, heads=heads, has_new=has_new, layered=layered,
        sparse=sparse)
    scratch = [
        ((2, keys, rank), c_cache.dtype),
        ((2, keys // 2, 2 * rope), r_cache.dtype),
        ((R, 1), jnp.float32), ((R, 1), jnp.float32),
        ((R, rank), jnp.float32),
    ]
    # a prefill slot's block is heads x T rows of the whole compressed
    # width (4,096 x 512 at the published sizes): the query and output
    # blocks (two buffers each, the pipeline's), the accumulator and the
    # lane-padded m and l need more than the compiler's default scope
    need = sum(_lane_padded_bytes(sh, dt) for sh, dt in scratch) \
        + 2 * (2 * _lane_padded_bytes((R, rank), q_c.dtype)
               + 2 * _lane_padded_bytes((R, 2 * rope), q_c.dtype)) \
        + 4 * _lane_padded_bytes((Tp, rank), q_c.dtype) \
        + 3 * _lane_padded_bytes((tile, keys), jnp.float32)
    sel_scratch = []
    if sparse:
        # the block's 0/1 rows (two buffers), the own rows' (the pipeline's
        # two) and a tile's mask beside its scores
        sel_scratch = [pltpu.VMEM((2, Tb, keys), jnp.bfloat16)]
        need += 2 * _lane_padded_bytes((Tb, keys), jnp.bfloat16) \
            + 2 * _lane_padded_bytes((Tb, Tp), jnp.bfloat16) \
            + 2 * _lane_padded_bytes((tile, keys), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b,),
        in_specs=in_specs,
        out_specs=block_of(R, rank),
        scratch_shapes=[
            pltpu.VMEM(*scratch[0]), pltpu.VMEM(*scratch[1]),
            pltpu.SemaphoreType.DMA((2, 3 if sparse else 2)),
            *sel_scratch,
            pltpu.VMEM(*scratch[2]), pltpu.VMEM(*scratch[3]),
            pltpu.VMEM(*scratch[4]),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="ragged_paged_attention_latent_sparse" if sparse
        else "ragged_paged_attention_latent",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, R, rank), q_c.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(need)),
        interpret=interpret,
    )(*scalars, *operands)
    return out[:, :rows].reshape(b, t, heads, rank)


def ragged_paged_attention_latent(q_c, q_r, c_cache, r_cache, block_tables,
                                  context_lens, *, scale, q_lens=None,
                                  c_new=None, r_new=None, layer=None,
                                  selected=None):
    """Mixed-mode serving attention over a LATENT page pool (prefill chunks
    and decode tokens in one call), in the absorbed form.

    Args:
      q_c:     [batch, T, heads, rank]: ``W_uk^T q_nope``, the query's
               content part carried into the compressed space.
      q_r:     [batch, T, heads, rope]: the query's rotary part, rotated.
      c_cache: [num_pages, page_size, rank] compressed rows (normed), or
               the whole pool [layers, ...] with ``layer``.
      r_cache: [num_pages, page_size / 2, 2 * rope] rotary keys, two
               tokens a row (``PagedKVCache``), or the whole pool.
      block_tables, context_lens, q_lens: as ``ragged_paged_attention``.
      scale:   static float: what the scores are multiplied by (the
               model's ``(nope + rope)^-0.5`` times its yarn factor).
      c_new/r_new: [batch, T, rank] / [batch, T, rope]: the step's own
               rows, folded in causally; commit them after the step.
      layer:   int32 scalar (may be traced), with the whole pool.
      selected: None, or bool [batch, T, S (+ T)]: the sparse call
               (``ragged_paged_attention_latent_sparse``, which says what
               it holds).

    Returns ``u`` [batch, T, heads, rank]: ``sum_j a_j c_j`` for each head,
    to which the caller applies ``W_uv``.  Rows past ``q_lens[b]`` are
    don't-care (zeros past the live row tiles)."""
    b, t, heads, rank = q_c.shape
    rope = q_r.shape[-1]
    if (c_cache.ndim == 4) != (layer is not None):
        raise ValueError("a whole pool [layers, ...] is read at `layer`; "
                         "one layer's cache takes none")
    if (c_new is None) != (r_new is None):
        raise ValueError("c_new and r_new must be given together")
    page_size = c_cache.shape[-2]
    if c_cache.shape[-1] != rank or r_cache.shape[-2:] != (page_size // 2,
                                                          2 * rope):
        raise ValueError(
            f"latent pool {c_cache.shape} / {r_cache.shape} does not hold "
            f"rows of {rank} + {rope} for pages of {page_size}")
    n = block_tables.shape[1] * page_size + (0 if c_new is None else t)
    if selected is not None and selected.shape != (b, t, n):
        raise ValueError(f"selected {selected.shape} is not [batch, T, "
                         f"positions (+ own rows)] = {(b, t, n)}")
    on_tpu = jax.default_backend() == "tpu"
    why = kernel_geometry_error(page_size, 0, latent=(rank, rope),
                                interpret=not on_tpu)
    if on_tpu and why:
        raise ValueError(f"ragged_paged_attention_latent on TPU: {why}")
    if (on_tpu or flags.flag("paged_attention_interpret")) and not why:
        return _pallas_ragged_paged_attention_latent(
            q_c, q_r, c_cache, r_cache, block_tables, context_lens, q_lens,
            c_new, r_new, interpret=not on_tpu, scale=float(scale),
            layer=layer, selected=selected)
    if layer is not None:
        c_cache, r_cache = (jax.lax.dynamic_index_in_dim(
            a, layer, axis=0, keepdims=False) for a in (c_cache, r_cache))
    return _reference_ragged_paged_attention_latent(
        q_c, q_r, c_cache, r_cache, block_tables, context_lens, q_lens,
        c_new, r_new, float(scale), selected=selected)


def ragged_paged_attention_latent_sparse(q_c, q_r, c_cache, r_cache,
                                         block_tables, context_lens,
                                         selected, *, scale, q_lens=None,
                                         c_new=None, r_new=None, layer=None):
    """``ragged_paged_attention_latent`` in which query token ``t``'s softmax
    runs over its CHOSEN positions alone (a learned index's set,
    ``kernels/latent_index.py``).

    ``selected``: bool ``[batch, T, S (+ T)]``, ``S = max_pages x
    page_size``: the slot's positions in order, then (with ``c_new``) the
    step's own rows; a position outside the causal set counts as not
    chosen whatever it says.  Everything else as the dense call's.

    The form built is the MASKED WALK: the slot's pages are walked as the
    dense call walks them (whole pages, one copy each: a gathered row
    would be a descriptor of 1 KB, and the walk is bound by issuing
    copies, PERF.md section 6) and each score is masked to the set, so
    the products are those of the dense call while the softmax and the
    result are the sparse one's."""
    return ragged_paged_attention_latent(
        q_c, q_r, c_cache, r_cache, block_tables, context_lens, scale=scale,
        q_lens=q_lens, c_new=c_new, r_new=r_new, layer=layer,
        selected=selected)


def paged_attention(q, k_cache, v_cache, block_tables, context_lens,
                    with_lse=False):
    """Single-token decode attention over a paged KV cache.

    The T=1, no-fresh-rows form of :func:`ragged_paged_attention` (kept as
    the stable decode API, head-major K and V planes as the reference's
    kernel takes them, packed into a page-major pool here at the boundary:
    a copy, and no serving step calls it; the reference oracle for it is
    ``_reference_paged_attention``).

    Args:
      q:            [batch, num_q_heads, head_dim] — this step's query.
      k_cache:      [num_kv_heads, num_pages, page_size, head_dim].
      v_cache:      same shape as k_cache.
      block_tables: [batch, max_pages_per_seq] int32 page ids (pad with 0).
      context_lens: [batch] int32 — number of cache tokens to attend.
      with_lse:     also return the per-query logsumexp ([batch, q_heads],
                    fp32) so the caller can merge extra keys (e.g. the
                    current token, which need not be written to the cache
                    before the call) via online-softmax combination.

    Returns [batch, num_q_heads, head_dim] (and lse when requested).
    """
    res = ragged_paged_attention(q[:, None], pool_of_heads(k_cache, v_cache),
                                 block_tables, context_lens,
                                 with_lse=with_lse)
    if with_lse:
        out, lse = res
        return out[:, 0], lse[:, 0]
    return res[:, 0]


# ----------------------------------------------------------- cache writes ---

def write_kv_pages(kv_cache, k_new, v_new, slot_mapping):
    """Scatter new KV rows into one layer's paged cache.

    kv_cache: [num_pages, 2, kv_heads, page_size, head_dim]; k_new/v_new:
    [n_tokens, kv_heads, head_dim]; slot_mapping: [n_tokens] int32 flat
    slots (page_id * page_size + offset; -1 = drop the token).  Returns
    the updated cache.  Donate it under jit and XLA performs the scatter
    in place.
    """
    n_pages, _, _, page_size, _ = kv_cache.shape
    slots = slot_mapping.astype(jnp.int32)
    # dropped tokens (-1) are redirected out of range; mode="drop" elides them
    page = jnp.where(slots >= 0, slots // page_size, n_pages)
    rows = jnp.stack([k_new, v_new], axis=1).astype(kv_cache.dtype)
    return kv_cache.at[page, :, :, slots % page_size].set(rows, mode="drop")


def write_kv_pages_all_layers(kv_cache, k_all, v_all, slot_mapping):
    """Commit every layer's new KV rows, token by token, in place.

    kv_cache: [layers, num_pages, 2, kv_heads, page_size, head_dim];
    k_all/v_all: [layers, n_tokens, kv_heads, head_dim]; slot_mapping:
    [n_tokens] (-1 = drop).  All layers share the slot vector and the
    commit happens once at the end of the step, so the cache stays strictly
    read-before-write: attention reads the pre-step cache and XLA aliases
    the donated buffer in place.

    A loop of ``dynamic_update_slice`` over the step's VALID tokens (those
    with a slot, taken first), not one scatter: for a scatter along the
    token axis XLA's TPU layout assignment moves the whole pool into a
    token-major layout and back (four copies of the pool a step: 3.25 GB of
    transients beside a 3.25 GB pool, and a third of the dense chat step,
    v5e, PR 27).  The window is the token's WHOLE page, ``[layers, 1, 2,
    kv_heads, page_size, head_dim]``, read, one row of every head's K and V
    replaced, and written back: ONE dynamic index, whole tiles, and XLA
    leaves the pool where it lies.  A window of the one row alone (the page
    AND the offset dynamic) made it move the pool into a layout with the
    heads beside ``head_dim`` and back, two pool-shaped copies a step
    (compiled for a described v5e, PR 35: ``tests/test_chip_compile.py``);
    a row is a part of a ``(16, 128)`` tile, so the hardware rewrites the
    tile either way.
    """
    L, n_pages, _, kvh, page_size, d = kv_cache.shape
    slots = slot_mapping.astype(jnp.int32)
    rows = jnp.stack([k_all, v_all], axis=2).astype(kv_cache.dtype)
    valid = slots >= 0
    order = jnp.argsort(jnp.logical_not(valid), stable=True).astype(jnp.int32)
    ps_c = jnp.int32(page_size)

    at = jnp.arange(page_size, dtype=jnp.int32)[:, None]     # [page, 1]

    def commit(i, kv):
        src = order[i]
        dst = slots[src]
        page = jax.lax.div(dst, ps_c)
        row = jax.lax.dynamic_slice_in_dim(rows, src, 1, axis=1)
        old = jax.lax.dynamic_slice_in_dim(kv, page, 1, axis=1)
        new = jnp.where(at == jax.lax.rem(dst, ps_c),
                        row.reshape(L, 1, 2, kvh, 1, d), old)
        return jax.lax.dynamic_update_slice_in_dim(kv, new, page, axis=1)

    return jax.lax.fori_loop(
        jnp.int32(0), valid.sum().astype(jnp.int32), commit, kv_cache)


def write_latent_pages_all_layers(c_cache, r_cache, c_all, r_all,
                                  slot_mapping, i_cache=None, i_all=None):
    """Commit every layer's new latent rows, token by token, in place: the
    latent pool's ``write_kv_pages_all_layers`` (a loop of
    ``dynamic_update_slice`` over the step's valid tokens, for the reason
    given there).

    c_cache ``[layers, num_pages, page_size, rank]``; r_cache ``[layers,
    num_pages, page_size / 2, 2 * rope]``; c_all ``[layers, n_tokens,
    rank]``, r_all ``[layers, n_tokens, rope]``; slot_mapping ``[n_tokens]``
    (``page * page_size + offset``; -1 = drop).  A token's rotary key goes
    into its half of the row it shares (read, one half replaced, written
    back whole: the update stays aligned to the lanes).

    ``i_cache`` ``[layers, num_pages, page_size, dim]`` with ``i_all``
    ``[layers, n_tokens, dim]``: the pool's third plane (a learned index's
    keys), committed by the same loop; three arrays come back."""
    L, n_pages, page_size, rank = c_cache.shape
    half, rope = page_size // 2, r_all.shape[-1]
    flat_c = c_cache.reshape(L, n_pages * page_size, rank)
    flat_r = r_cache.reshape(L, n_pages * half, 2 * rope)
    slots = slot_mapping.astype(jnp.int32)
    cn = c_all.astype(flat_c.dtype)
    rn = jnp.concatenate([r_all, r_all], axis=-1).astype(flat_r.dtype)
    valid = slots >= 0
    order = jnp.argsort(jnp.logical_not(valid), stable=True).astype(jnp.int32)
    lane_upper = jnp.arange(2 * rope) >= rope

    planes = (flat_c, flat_r)
    if i_cache is not None:
        planes += (i_cache.reshape(L, n_pages * page_size, -1),)
        i_new = i_all.astype(i_cache.dtype)

    def commit(i, cr):
        fc, fr, *fi = cr
        src = order[i]
        dst = slots[src]
        fc = jax.lax.dynamic_update_slice_in_dim(
            fc, jax.lax.dynamic_slice_in_dim(cn, src, 1, axis=1), dst, axis=1)
        fi = [jax.lax.dynamic_update_slice_in_dim(
            f, jax.lax.dynamic_slice_in_dim(i_new, src, 1, axis=1), dst,
            axis=1) for f in fi]
        offset = dst % page_size
        row = (dst // page_size) * half + offset % half
        old = jax.lax.dynamic_slice_in_dim(fr, row, 1, axis=1)
        new = jnp.where(lane_upper == (offset >= half),
                        jax.lax.dynamic_slice_in_dim(rn, src, 1, axis=1), old)
        fr = jax.lax.dynamic_update_slice_in_dim(fr, new, row, axis=1)
        return (fc, fr, *fi)

    flat_c, flat_r, *flat_i = jax.lax.fori_loop(
        jnp.int32(0), valid.sum().astype(jnp.int32), commit, planes)
    return (flat_c.reshape(c_cache.shape), flat_r.reshape(r_cache.shape),
            *(f.reshape(i_cache.shape) for f in flat_i))


def _requantize_pages(flat, fresh, lslot, new_scale_shape):
    """Shared K/V half of the quantized commit: scatter fresh fp32 rows
    into the dequantized gathered pages, recompute each page's absmax
    scale, requantize.  ``flat``: [L, kvh, G*page, d] fp32 (G gathered
    pages); returns (int8 pages [L, kvh, G, page, d], scales [L, kvh, G]).
    """
    L, kvh, _, d = flat.shape
    G, page = new_scale_shape
    flat = flat.at[:, :, lslot].set(fresh, mode="drop")
    pages = flat.reshape(L, kvh, G, page, d)
    amax = jnp.max(jnp.abs(pages), axis=(3, 4))            # [L, kvh, G]
    scales = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(pages / scales[..., None, None]),
                 -127.0, 127.0).astype(jnp.int8)
    return q, scales


def write_kv_pages_all_layers_quantized(kv_cache, k_scale, v_scale,
                                        k_all, v_all, positions, q_lens,
                                        block_tables, max_len):
    """The int8 pool's batched all-layer commit: quantize fresh K/V per
    page on the way in (EQuARX-style blockwise int8 + fp32 absmax scales,
    one scale per (layer, kv-head, page)).

    Because the scale is page-granular, the commit is a page-level
    read-modify-write: gather the pages this step's tokens land in,
    dequantize with the old scales, insert the fresh fp32 rows, recompute
    each page's absmax scale, requantize, and scatter pages + scales
    back.  Rows never share a write page (COW privatizes shared pages
    before any write), so per-row page windows cannot collide.  Rounding
    is round-to-nearest — the commit is bit-deterministic, and a page
    whose scale did not change requantizes its old rows to exactly the
    same int8 bytes.

    Rows of a touched page PAST the sequence's post-step extent are
    zeroed before the absmax: a recycled page may still hold a previous
    occupant's bytes (pages are never scrubbed on free), and without the
    mask a large-magnitude predecessor would inflate the new occupant's
    scale arbitrarily — the stale region is unreachable through
    ``context_lens`` anyway, so zeroing it is free and keeps the error
    bound relative to the page's OWN live content.

    kv_cache: [L, n_pages, 2, kvh, page, d] int8; k_scale/v_scale:
    [L, kvh, n_pages] fp32; k_all/v_all: [L, B*T, kvh, d] fresh rows;
    positions/q_lens: [B] (write cursor / valid tokens per row);
    block_tables: [B, W].  Returns the three updated arrays.  The window's
    pages are worked on head-major (``heads_of_pool`` of the gathered
    pages, a few pages a slot), beside the scale planes' layout.
    """
    L, n_pages, _, kvh, page, d = kv_cache.shape
    B, W = block_tables.shape
    T = k_all.shape[1] // B
    # a T-token run starting anywhere in a page straddles at most Pmax
    # pages; gathering exactly that window keeps the RMW O(B * Pmax)
    Pmax = 1 + (max(T - 1, 0) + page - 1) // page

    pos0 = positions.astype(jnp.int32)
    offs = jnp.arange(T, dtype=jnp.int32)
    pos = pos0[:, None] + offs[None, :]                    # [B, T]
    pos_c = jnp.minimum(pos, max_len - 1)
    valid = jnp.logical_and(offs[None, :] < q_lens[:, None],
                            pos < max_len)                 # [B, T]
    first = jnp.minimum(pos0, max_len - 1) // page         # [B]

    # touched pages per row: page-list indices [first, first + npg)
    ntok = jnp.sum(valid.astype(jnp.int32), axis=1)        # [B]
    off0 = jnp.minimum(pos0, max_len - 1) % page
    npg = jnp.where(ntok > 0, (off0 + ntok + page - 1) // page, 0)
    j = jnp.arange(Pmax, dtype=jnp.int32)
    touched = j[None, :] < npg[:, None]                    # [B, Pmax]
    plist = jnp.minimum(first[:, None] + j[None, :], W - 1)
    page_ids = jnp.take_along_axis(block_tables.astype(jnp.int32),
                                   plist, axis=1)          # [B, Pmax]
    flat_pid = jnp.where(touched, page_ids, n_pages).reshape(-1)
    safe_pid = jnp.minimum(flat_pid, n_pages - 1)

    # gather + dequant the write window
    # [L, kvh, B*Pmax, page, d] each
    kg, vg = heads_of_pool(jnp.take(kv_cache, safe_pid, axis=1))
    ksg = jnp.take(k_scale, safe_pid, axis=2)  # [L, kvh, B*Pmax]
    vsg = jnp.take(v_scale, safe_pid, axis=2)
    # live-extent mask: row r of window page j holds a valid token iff
    # its global position is below the sequence's post-step extent —
    # everything past it is a recycled page's stale bytes, zeroed so it
    # cannot inflate the absmax scale of the new occupant's rows
    r = jnp.arange(page, dtype=jnp.int32)
    gpos = ((first[:, None] + j[None, :]) * page)[:, :, None] \
        + r[None, None, :]                                 # [B, Pmax, page]
    live = (gpos < (pos0 + ntok)[:, None, None]).reshape(
        1, 1, B * Pmax * page, 1).astype(jnp.float32)
    kf = (kg.astype(jnp.float32) * ksg[..., None, None]).reshape(
        L, kvh, B * Pmax * page, d) * live
    vf = (vg.astype(jnp.float32) * vsg[..., None, None]).reshape(
        L, kvh, B * Pmax * page, d) * live

    # fresh rows land at window-local slots (invalid tokens -> drop)
    b_ix = jnp.arange(B, dtype=jnp.int32)[:, None]
    rel = pos_c // page - first[:, None]                   # [B, T]
    lslot = jnp.where(valid,
                      (b_ix * Pmax + rel) * page + pos_c % page,
                      B * Pmax * page).reshape(B * T)
    kn = jnp.swapaxes(k_all, 1, 2).astype(jnp.float32)     # [L, kvh, B*T, d]
    vn = jnp.swapaxes(v_all, 1, 2).astype(jnp.float32)

    kq, ks_new = _requantize_pages(kf, kn, lslot, (B * Pmax, page))
    vq, vs_new = _requantize_pages(vf, vn, lslot, (B * Pmax, page))

    # untouched window entries were routed to n_pages: scatter drops them
    kv_cache = kv_cache.at[:, flat_pid].set(pool_of_heads(kq, vq),
                                            mode="drop")
    k_scale = k_scale.at[:, :, flat_pid].set(ks_new, mode="drop")
    v_scale = v_scale.at[:, :, flat_pid].set(vs_new, mode="drop")
    return kv_cache, k_scale, v_scale
