"""Ragged paged-KV attention for TPU (Pallas) — the serving hot op.

Replaces the reference's fused decode kernels
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu and
masked_multihead_attention): each sequence's query tokens attend its whole
KV history, which lives in fixed-size *pages* scattered through a global
cache and addressed by a per-sequence block table (vLLM-style paged KV).

TPU-first design (the "Ragged Paged Attention" shape of arxiv 2604.15464):

- **One mixed-mode kernel** serves prefill chunks AND decode tokens: the
  query operand is ``[batch, T, q_heads, head_dim]`` where T is the step's
  query-token tile (1 for pure decode, the chunk length for chunked
  prefill), with per-sequence ``q_lens`` raggedness.  The step's OWN fresh
  K/V rows (``k_new``/``v_new``, not yet committed to the cache) are folded
  in-kernel with a causal mask, so a serving step never needs a separate
  flash-attention call or an analytic current-token merge — chunked
  prefill rides the decode schedule in one ``pallas_call``.
- The KV cache is laid out **head-major**, ``[kv_heads, num_pages,
  page_size, head_dim]``, and stays in **HBM** (``pl.ANY``): the kernel
  itself DMAs exactly the pages a sequence owns into a two-slot VMEM ring,
  **double-buffered** — page ``p+1``'s copy is started while page ``p`` is
  being computed (the same overlap pattern as the grouped_matmul fused
  gather).  The buffer slot is ``p % 2`` with p the *absolute* page index,
  so the prefetch chain continues across page-chunk grid steps with no
  warm-up bubble after the first page.
- The grid is **(sequence, kv_head, page_chunk)** with per-sequence
  ``context_lens`` raggedness: a chunk wholly beyond a sequence's context
  issues NO DMA and no compute — HBM traffic and FLOPs are O(context),
  never O(max_context).
- The block table, context lengths and query lengths ride in as
  **scalar-prefetch** operands (``pltpu.PrefetchScalarGridSpec``), so page
  ids resolve before the body runs — data-dependent addressing with zero
  data-dependent control flow outside ``fori_loop`` trip counts.
- GQA is native: each program holds the ``group = q_heads // kv_heads``
  query rows of all T tokens for one KV head (``T * group`` MXU rows), so
  K/V pages are fetched ONCE per group, not per query head.
- Online softmax (m, l, acc) carries across the page-chunk axis in VMEM
  scratch, which persists along the innermost grid dimension.

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
flags.define_flag("paged_attention_pages_per_chunk", 8,
                  "KV pages per page-chunk grid step of the ragged "
                  "paged-attention kernel. Chunks wholly beyond a "
                  "sequence's context are skipped (no DMA, no compute); "
                  "within a chunk pages are double-buffered.")

_SUBLANE = 8  # f32 sublane count — query-row tiles pad to a multiple


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
    Rows with token index >= q_lens[b] are don't-care (garbage-but-finite,
    exactly like the kernel).  With ``k_scale``/``v_scale`` (int8 pool,
    one fp32 per (kv-head, page)) gathered pages are dequantized before
    the math — the same dequant the kernel does on its VMEM slot.
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


# ---------------------------------------------------------------- kernel ---

def _ragged_paged_attn_kernel(*refs, page_size, ppc, scale, t, group,
                              has_new, quantized=False, window=None,
                              layered=False):
    """One (sequence, kv_head, page_chunk) program.

    Double-buffered page loop over this chunk's live pages (slot = absolute
    page index % 2, so the prefetch chain crosses chunk boundaries); the
    final chunk folds the step's fresh K/V rows with a causal mask and
    normalizes.

    ``quantized`` (int8 pool): the DMA moves the page's int8 bytes (4x
    fewer than fp32) and the per-(kv-head, page) fp32 scales ride the
    scalar-prefetch channel beside the block table — dequant happens on
    the VMEM slot right after ``wait()``, so the online-softmax math
    stays fp32 and nothing above the kernel changes shape.

    ``window`` (static; a sliding-attention layer): query token j, at
    position ``ctx + j``, sees keys in ``(ctx + j - window, ctx + j]``.
    The page walk starts at the page that holds the EARLIEST query's
    first visible key (``first``): pages wholly behind it are never
    fetched, and chunk ``c`` covers pages ``first + c * ppc`` on, so the
    grid needs only the chunks a window can span.

    ``layered`` (static): the caches are the WHOLE pool ``[layers,
    kv_heads, num_pages, page_size, head_dim]`` in HBM and the layer to
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
    k_hbm, v_hbm = next(it), next(it)
    o_ref, lse_ref = next(it), next(it)
    kbuf, vbuf, sem = next(it), next(it), next(it)
    m_ref, l_ref, acc_ref = next(it), next(it), next(it)

    b = pl.program_id(0)
    h = pl.program_id(1)
    c = pl.program_id(2)
    n_c = pl.num_programs(2)
    ctx = cl_ref[b]
    # all int scalars must stay strongly-typed int32: python-int divisors /
    # clip bounds embed i64 literals under x64 mode, and the i64->i32
    # convert_element_type they force breaks Mosaic lowering (the round-4
    # recursion bug) — hence lax.div/lax.rem against np.int32 constants
    ps_c = np.int32(page_size)
    pages_total = jax.lax.div(ctx + ps_c - np.int32(1), ps_c)
    if window is None:
        first = _I0
    else:
        first = jax.lax.div(
            jnp.maximum(ctx + np.int32(1 - window), _I0), ps_c)
    start = first + c * np.int32(ppc)
    n_here = jnp.minimum(jnp.maximum(pages_total - start, _I0),
                         np.int32(ppc))

    def page_of(hbm, p):
        return hbm.at[ly_ref[0], h, bt_ref[b, p]] if layered \
            else hbm.at[h, bt_ref[b, p]]

    def k_copy(p, slot):
        return pltpu.make_async_copy(
            page_of(k_hbm, p), kbuf.at[slot], sem.at[slot, _I0])

    def v_copy(p, slot):
        return pltpu.make_async_copy(
            page_of(v_hbm, p), vbuf.at[slot], sem.at[slot, np.int32(1)])

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    # chain warm-up: only the very first live page of a (seq, head) visit
    # has no chunk before it to have prefetched it
    @pl.when(jnp.logical_and(c == 0, pages_total > first))
    def _warmup():
        slot0 = jax.lax.rem(first, np.int32(2))
        k_copy(first, slot0).start()
        v_copy(first, slot0).start()

    def _accumulate(s, v):
        """Online-softmax update of the (m, l, acc) scratch carry."""
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(n_here > 0)
    def _pages():
        q = q_ref[...].astype(jnp.float32) * jnp.float32(scale)  # [R, d]

        def body(i, carry):
            p = start + i
            slot = jax.lax.rem(p, np.int32(2))
            nxt = p + np.int32(1)

            # prefetch page p+1 (possibly the NEXT chunk's first page)
            # while p's arrival is awaited and computed on
            @pl.when(nxt < pages_total)
            def _prefetch():
                nslot = jax.lax.rem(nxt, np.int32(2))
                k_copy(nxt, nslot).start()
                v_copy(nxt, nslot).start()

            k_copy(p, slot).wait()
            v_copy(p, slot).wait()
            k = kbuf[slot].astype(jnp.float32)                 # [page, d]
            v = vbuf[slot].astype(jnp.float32)
            if quantized:   # static: dequant on the VMEM slot post-wait
                pid = bt_ref[b, p]
                k = k * ksc_ref[h, pid]      # SMEM scalar load, dynamic id
                v = v * vsc_ref[h, pid]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            pos = p * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            seen = pos < ctx
            if window is not None:    # static: query row r is token r // group
                q_pos = ctx + jax.lax.div(
                    jax.lax.broadcasted_iota(jnp.int32, s.shape, 0),
                    jnp.full(s.shape, group, jnp.int32))
                seen = jnp.logical_and(seen, pos > q_pos - np.int32(window))
            s = jnp.where(seen, s, jnp.float32(NEG_INF))
            _accumulate(s, v)
            return carry

        # int32 literals: a bare python 0 is an i64 under x64 mode, and an
        # i64->i32 convert inside the kernel breaks Mosaic lowering
        jax.lax.fori_loop(_I0, n_here.astype(jnp.int32), body, _I0)

    @pl.when(c == n_c - 1)
    def _finalize():
        if has_new:   # static: compiled in only for the mixed-mode form
            q = q_ref[...].astype(jnp.float32) * jnp.float32(scale)
            kn = knew_ref[...].astype(jnp.float32)             # [Tp, d]
            vn = vnew_ref[...].astype(jnp.float32)
            s = jax.lax.dot_general(q, kn, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            jq = jax.lax.div(
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0),
                jnp.full(s.shape, group, jnp.int32))
            jk = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            valid = jnp.logical_and(jk <= jq, jk < ql_ref[b])
            if window is not None:
                valid = jnp.logical_and(valid, jq - jk < np.int32(window))
            s = jnp.where(valid, s, jnp.float32(NEG_INF))
            _accumulate(s, vn)
        l = jnp.maximum(l_ref[...], jnp.float32(1e-30))
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _pallas_ragged_paged_attention(q, k_cache, v_cache, block_tables,
                                   context_lens, q_lens, k_new, v_new,
                                   interpret, k_scale=None, v_scale=None,
                                   window=None, layer=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, qh, d = q.shape
    layered = layer is not None
    kvh, n_pages, page_size, _ = k_cache.shape[-4:]
    group = qh // kvh
    max_pages = block_tables.shape[1]
    rows = t * group
    R = -(-max(rows, _SUBLANE) // _SUBLANE) * _SUBLANE

    # [B, T, qh, d] -> [B, kvh, T*group, d]: row r = token*(group) + g, so
    # one MXU tile holds every query row sharing this program's KV head
    qg = q.reshape(b, t, kvh, group, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, kvh, rows, d)
    if R != rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, R - rows), (0, 0)))

    ppc = max(1, min(int(flags.flag("paged_attention_pages_per_chunk")),
                     max_pages))
    # a window's keys span at most ceil((window - 1) / page) + 1 pages
    live_pages = max_pages if window is None else min(
        max_pages, -(-(window - 1) // page_size) + 1)
    n_chunks = -(-live_pages // ppc)

    # unused table entries must still be valid page ids for the DMA
    bt = jnp.clip(block_tables, 0, n_pages - 1).astype(jnp.int32)
    cl = context_lens.astype(jnp.int32)
    ql = (q_lens if q_lens is not None
          else jnp.full((b,), t)).astype(jnp.int32)

    has_new = k_new is not None
    operands = [qg]
    in_specs = [pl.BlockSpec((None, None, R, d),
                             lambda b_, h, c, *_: (b_, h, _I0, _I0))]
    if has_new:
        Tp = -(-t // _SUBLANE) * _SUBLANE
        kn = k_new.transpose(0, 2, 1, 3)        # [B, kvh, T, d]
        vn = v_new.transpose(0, 2, 1, 3)
        if Tp != t:
            pad = ((0, 0), (0, 0), (0, Tp - t), (0, 0))
            kn, vn = jnp.pad(kn, pad), jnp.pad(vn, pad)
        spec = pl.BlockSpec((None, None, Tp, d),
                            lambda b_, h, c, *_: (b_, h, _I0, _I0))
        operands += [kn, vn]
        in_specs += [spec, spec]
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
    operands += [k_cache, v_cache]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)]

    kernel = functools.partial(
        _ragged_paged_attn_kernel, page_size=page_size, ppc=ppc,
        scale=1.0 / math.sqrt(d), t=t, group=group, has_new=has_new,
        quantized=quantized, window=window, layered=layered)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, kvh, n_chunks),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, None, R, d),
                         lambda b_, h, c, *_: (b_, h, _I0, _I0)),
            pl.BlockSpec((None, None, R, 1),
                         lambda b_, h, c, *_: (b_, h, _I0, _I0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, page_size, d), k_cache.dtype),
            pltpu.VMEM((2, page_size, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, d), jnp.float32),
        ],
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
            dimension_semantics=("parallel", "parallel", "arbitrary")),
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
                          smem_bytes=None):
    """The rule a paged-KV geometry fails for the Pallas kernel, as a
    sentence, or None when the kernel covers it.  On a TPU a failing
    geometry raises (here at trace time, and in the engine when it is
    built); off-TPU the XLA reference takes it.

    ``smem_bytes`` is the scalar memory of the core the kernel is built
    for; None asks the attached TPU (``pltpu.get_tpu_info``) and, with no
    TPU attached, leaves the SMEM rule to the compiler."""
    # f32 sublane is 8; bf16 packs 16 — page_size must tile the sublane
    # dim.  int8 packs 32 sublanes per tile, so a quantized pool needs
    # page_size % 32 == 0 to keep each page a whole-tile DMA.
    if page_size % 8:
        return f"page_size ({page_size}) must be a multiple of 8"
    if head_dim % 128 not in (0, 64):
        return f"head_dim % 128 must be 0 or 64, got head_dim {head_dim}"
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


def ragged_paged_attention(q, k_cache, v_cache, block_tables, context_lens,
                           *, q_lens=None, k_new=None, v_new=None,
                           k_scale=None, v_scale=None, with_lse=False,
                           window=None, layer=None):
    """Mixed-mode serving attention: prefill chunks and decode tokens in one
    call over a paged KV cache.

    Args:
      q:            [batch, T, num_q_heads, head_dim] — this step's query
                    tokens (T = 1 for pure decode, the chunk length for
                    chunked prefill; sequences ragged via ``q_lens``).
      k_cache:      [num_kv_heads, num_pages, page_size, head_dim], or the
                    whole pool [layers, num_kv_heads, ...] with ``layer``.
      v_cache:      same shape as k_cache.
      block_tables: [batch, max_pages_per_seq] int32 page ids (pad with 0).
      context_lens: [batch] int32 — tokens ALREADY in the cache (the prior
                    context; this step's own tokens are NOT included).
      q_lens:       [batch] int32 — valid query tokens per sequence
                    (None = all T).  Output rows past q_lens[b] are
                    don't-care.
      k_new/v_new:  [batch, T, num_kv_heads, head_dim] — the step's fresh
                    KV rows, folded in with a causal mask (token j attends
                    new tokens <= j).  They need not be written to the
                    cache before the call; commit them after the step.
      k_scale/v_scale: [num_kv_heads, num_pages] fp32 — per-(kv-head,
                    page) dequant scales of an int8 cache pool.  Pages
                    are dequantized inside the kernel (on the VMEM slot,
                    right after the DMA wait) — nothing downstream
                    changes shape.
      with_lse:     also return the per-query logsumexp [batch, T, q_heads]
                    (fp32) for online-softmax merging of extra keys.
      window:       static int or None — sliding attention: the query
                    token at position p sees keys in ``(p - window, p]``.
                    Pages that end before the earliest query's window are
                    skipped in the walk, not masked after the fetch, and
                    the kernel is named ``ragged_paged_attention_w<window>``.
      layer:        int32 scalar (may be traced) — with the whole pool as
                    ``k_cache``/``v_cache``, the layer whose pages are
                    read: the kernel indexes the pool in HBM, no layer is
                    sliced out of it (``k_scale``/``v_scale`` stay one
                    layer's [num_kv_heads, num_pages] planes).

    Returns [batch, T, num_q_heads, head_dim] (and lse when requested).
    """
    b, t, qh, d = q.shape
    if window is not None and (int(window) != window or window < 1):
        raise ValueError(f"window must be a whole number >= 1, got {window!r}")
    window = None if window is None else int(window)
    if (k_cache.ndim == 5) != (layer is not None):
        raise ValueError("a whole pool [layers, ...] is read at `layer`; "
                         "one layer's cache takes none")
    kvh, n_pool_pages, page_size, _ = k_cache.shape[-4:]
    if qh % kvh:
        raise ValueError(f"q heads ({qh}) must be a multiple of kv heads ({kvh})")
    if (k_new is None) != (v_new is None):
        raise ValueError("k_new and v_new must be given together")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    on_tpu = jax.default_backend() == "tpu"
    why = kernel_geometry_error(
        page_size, d, quantized=k_scale is not None, kv_heads=kvh,
        num_pages=n_pool_pages, table_shape=block_tables.shape)
    if on_tpu and why:
        # the serving hot op has no business on the XLA reference on a chip
        raise ValueError(f"ragged_paged_attention on TPU: {why}")
    if (on_tpu or flags.flag("paged_attention_interpret")) and not why:
        out, lse = _pallas_ragged_paged_attention(
            q, k_cache, v_cache, block_tables, context_lens, q_lens,
            k_new, v_new, interpret=not on_tpu, k_scale=k_scale,
            v_scale=v_scale, window=window, layer=layer)
    else:
        if layer is not None:       # the oracle takes one layer's cache
            k_cache, v_cache = (jax.lax.dynamic_index_in_dim(
                c, layer, axis=0, keepdims=False) for c in (k_cache, v_cache))
        out, lse = _reference_ragged_paged_attention(
            q, k_cache, v_cache, block_tables, context_lens, q_lens,
            k_new, v_new, k_scale=k_scale, v_scale=v_scale, window=window)
    return (out, lse) if with_lse else out


def paged_attention(q, k_cache, v_cache, block_tables, context_lens,
                    with_lse=False):
    """Single-token decode attention over a paged KV cache.

    The T=1, no-fresh-rows form of :func:`ragged_paged_attention` (kept as
    the stable decode API; the reference oracle for it is
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
    res = ragged_paged_attention(q[:, None], k_cache, v_cache, block_tables,
                                 context_lens, with_lse=with_lse)
    if with_lse:
        out, lse = res
        return out[:, 0], lse[:, 0]
    return res[:, 0]


# ----------------------------------------------------------- cache writes ---

def write_kv_pages(k_cache, v_cache, k_new, v_new, slot_mapping):
    """Scatter new KV rows into the paged cache.

    k_new/v_new: [n_tokens, kv_heads, head_dim]; slot_mapping: [n_tokens]
    int32 flat slots (page_id * page_size + offset; -1 = drop the token).
    Returns updated (k_cache, v_cache).  Donate the caches under jit and
    XLA performs the scatter in place.
    """
    kvh, n_pages, page_size, d = k_cache.shape
    flat_k = k_cache.reshape(kvh, n_pages * page_size, d)
    flat_v = v_cache.reshape(kvh, n_pages * page_size, d)
    slots = slot_mapping.astype(jnp.int32)
    # dropped tokens (-1) are redirected out of range; mode="drop" elides them
    safe = jnp.where(slots >= 0, slots, n_pages * page_size)
    kn = jnp.swapaxes(k_new, 0, 1).astype(flat_k.dtype)   # [kvh, n, d]
    vn = jnp.swapaxes(v_new, 0, 1).astype(flat_v.dtype)
    flat_k = flat_k.at[:, safe].set(kn, mode="drop")
    flat_v = flat_v.at[:, safe].set(vn, mode="drop")
    return (flat_k.reshape(k_cache.shape), flat_v.reshape(v_cache.shape))


def write_kv_pages_all_layers(k_cache, v_cache, k_all, v_all, slot_mapping):
    """Commit every layer's new KV rows, token by token, in place.

    k_cache/v_cache: [layers, kv_heads, num_pages, page_size, head_dim];
    k_all/v_all: [layers, n_tokens, kv_heads, head_dim]; slot_mapping:
    [n_tokens] (-1 = drop).  All layers share the slot vector and the
    commit happens once at the end of the step, so the cache stays strictly
    read-before-write: attention reads the pre-step cache and XLA aliases
    the donated buffers in place.

    A loop of ``dynamic_update_slice`` over the step's VALID tokens (those
    with a slot, taken first), not one scatter: for a scatter along the
    token axis XLA's TPU layout assignment moves the whole pool into a
    token-major layout and back (four copies of the pool a step: 3.25 GB of
    transients beside a 3.25 GB pool, and a third of the dense chat step,
    v5e, PR 27), where an update of one ``[layers, kv_heads, 1, head_dim]``
    window leaves the pool where it lies.
    """
    L, kvh, n_pages, page_size, d = k_cache.shape
    flat_k = k_cache.reshape(L, kvh, n_pages * page_size, d)
    flat_v = v_cache.reshape(L, kvh, n_pages * page_size, d)
    slots = slot_mapping.astype(jnp.int32)
    kn = jnp.swapaxes(k_all, 1, 2).astype(flat_k.dtype)   # [L, kvh, n, d]
    vn = jnp.swapaxes(v_all, 1, 2).astype(flat_v.dtype)
    valid = slots >= 0
    order = jnp.argsort(jnp.logical_not(valid), stable=True).astype(jnp.int32)

    def commit(i, kv):
        fk, fv = kv
        src = order[i]
        dst = slots[src]
        fk = jax.lax.dynamic_update_slice_in_dim(
            fk, jax.lax.dynamic_slice_in_dim(kn, src, 1, axis=2), dst, axis=2)
        fv = jax.lax.dynamic_update_slice_in_dim(
            fv, jax.lax.dynamic_slice_in_dim(vn, src, 1, axis=2), dst, axis=2)
        return fk, fv

    flat_k, flat_v = jax.lax.fori_loop(
        jnp.int32(0), valid.sum().astype(jnp.int32), commit, (flat_k, flat_v))
    return (flat_k.reshape(k_cache.shape), flat_v.reshape(v_cache.shape))


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


def write_kv_pages_all_layers_quantized(k_cache, v_cache, k_scale, v_scale,
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

    k_cache/v_cache: [L, kvh, n_pages, page, d] int8; k_scale/v_scale:
    [L, kvh, n_pages] fp32; k_all/v_all: [L, B*T, kvh, d] fresh rows;
    positions/q_lens: [B] (write cursor / valid tokens per row);
    block_tables: [B, W].  Returns the four updated arrays.
    """
    L, kvh, n_pages, page, d = k_cache.shape
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
    kg = jnp.take(k_cache, safe_pid, axis=2)   # [L, kvh, B*Pmax, page, d]
    vg = jnp.take(v_cache, safe_pid, axis=2)
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
    k_cache = k_cache.at[:, :, flat_pid].set(kq, mode="drop")
    v_cache = v_cache.at[:, :, flat_pid].set(vq, mode="drop")
    k_scale = k_scale.at[:, :, flat_pid].set(ks_new, mode="drop")
    v_scale = v_scale.at[:, :, flat_pid].set(vs_new, mode="drop")
    return k_cache, v_cache, k_scale, v_scale
