"""Flash attention for TPU (Pallas), forward + backward.

Replaces paddle/phi/kernels/gpu/flash_attn_kernel.cu:587 (forward) and
paddle/phi/kernels/gpu/flash_attn_grad_kernel.cu (backward); the feature
surface (GQA, attention mask, varlen) mirrors the reference flash_attn
signature.  Design is the online-softmax blocked algorithm mapped to TPU:

- **KV streaming via the grid**: the KV-block loop is the innermost grid
  dimension, with the online-softmax state (m, l, acc) carried in VMEM
  scratch across it.  VMEM holds one Q block + one KV block at a time, so
  sequence length is bounded by HBM, not VMEM — 16k+ contexts work.
- **Causal skipping**: KV blocks entirely above the diagonal are skipped
  with `pl.when`, and their index maps are clamped to the last needed
  block so Mosaic's consecutive-same-block DMA elision makes the skipped
  fetches free.  Causal costs ~half of full attention, as it should.
- **GQA in-kernel**: the grid iterates query heads and the K/V index maps
  select `h // group`, so grouped K/V are never materialized per q-head
  (the bwd dK/dV kernel emits per-q-head partials, summed over each group
  outside — one [g] reduction instead of a host-side repeat).
- **Masking modes**, composable with causal: an additive fp32 mask
  ([b, h|1, sq, sk], streamed blockwise — the reference's attn_mask), and
  a segment mode (int seg ids per token, O(T) memory) which gives the
  packed/varlen block-diagonal mask without any [T, T] materialization.

Layout convention matches the paddle API: [batch, seq, heads, head_dim].
Falls back to an XLA-fused reference on CPU (tests) — same math; set
``FLAGS_flash_attention_interpret=1`` to run the Pallas kernels in
interpreter mode on CPU (used by tests to validate the exact kernel code).
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags
from ..core.tensor import Tensor
from ..ops._prim import apply_op

NEG_INF = -1e30
_I0 = np.int32(0)

flags.define_flag("flash_attention_interpret", False,
                  "Run the Pallas flash-attention kernels in interpreter mode "
                  "on CPU (tests only; TPU always uses the compiled path).")


# --------------------------------------------------------------------------
# XLA reference (CPU fallback + numerics oracle)
# --------------------------------------------------------------------------

def _reference_attention(q, k, v, causal, mask=None, seg_q=None, seg_k=None,
                         drop_p=0.0, seed=None):
    out, _ = _reference_attention_lse(q, k, v, causal, mask, seg_q, seg_k,
                                      drop_p, seed)
    return out


def _reference_attention_lse(q, k, v, causal, mask=None, seg_q=None,
                             seg_k=None, drop_p=0.0, seed=None):
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32)   # [b, h, sq, d]
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    group = qh.shape[1] // kh.shape[1]
    if group > 1:
        kh = jnp.repeat(kh, group, axis=1)
        vh = jnp.repeat(vh, group, axis=1)
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if mask is not None:
        scores = scores + mask.astype(jnp.float32)
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(cm, scores, NEG_INF)
    if seg_q is not None:
        sm = seg_q[:, :, None] == seg_k[:, None, :]          # [b, sq, sk]
        scores = jnp.where(sm[:, None], scores, NEG_INF)
    lse = jax.scipy.special.logsumexp(scores, axis=-1)       # [b, h, sq]
    probs = jnp.exp(scores - lse[..., None])
    if drop_p:
        seed_u32 = jnp.asarray(seed, jnp.float32).reshape(()).astype(
            jnp.uint32)
        keep = _drop_keep_dense(probs.shape, seed_u32, drop_p)
        probs = jnp.where(keep, probs, 0.0) * (1.0 / (1.0 - drop_p))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype), lse


# --------------------------------------------------------------------------
# kernel helpers
# --------------------------------------------------------------------------

def _apply_masks(s, i, j, *, block_q, block_kv, causal, diag_off,
                 mask_blk, segq_blk, segk_blk):
    """Additive mask + causal + segment masking on one score block."""
    if mask_blk is not None:
        s = s + mask_blk
    if causal:
        q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(q_pos + diag_off >= k_pos, s, jnp.float32(NEG_INF))
    if segq_blk is not None:
        s = jnp.where(segq_blk == jnp.swapaxes(segk_blk, 0, 1), s,
                      jnp.float32(NEG_INF))
    return s


def _needed(i, block_q, block_kv, diag_off):
    """Last KV block index a causal q-block i touches.

    The divisor must be an explicit int32: inside a Pallas kernel trace a
    bare Python int reaching ``jnp.floor_divide``'s nested jit becomes an
    int64 literal, and Mosaic's convert_element_type lowering recurses
    forever on 64->32-bit signed casts (jax 0.9 lowering.py:_convert_helper).
    """
    return jnp.floor_divide(i * block_q + block_q - 1 + diag_off,
                            jnp.int32(block_kv))


def _seed_u32(seed_ref):
    """f32 seed scalar -> u32 for the hash. Mosaic has no f32->u32 cast;
    go through int32 (fptosi) then reinterpret 32->32 (exact: seed < 2^23)."""
    return seed_ref[0, 0].astype(jnp.int32).astype(jnp.uint32)


def _drop_keep(shape, seed_u32, b, h, row0, col0, drop_p):
    """Deterministic keep-mask for one score block.

    Counter-based stateless RNG (the threefry/philox family's shape, with a
    murmur3-finalizer mix): each (seed, batch, head, GLOBAL row, GLOBAL col)
    position hashes to 32 bits compared against drop_p.  Keying on global
    positions — not block indices — makes the mask invariant to retiling
    (the autotuner may pick different blocks for fwd and a rerun) and
    trivially identical across the three kernels.  Pure uint32 jnp math, so
    it runs identically under Mosaic, interpret mode, and the dense
    reference path (reference flash_attn dropout:
    paddle/phi/kernels/gpu/flash_attn_kernel.cu:53).
    """
    rows = jax.lax.broadcasted_iota(jnp.uint32, shape, 0) + jnp.uint32(row0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, shape, 1) + jnp.uint32(col0)
    bits = _drop_mix(rows, cols, seed_u32, jnp.uint32(b), jnp.uint32(h))
    return bits >= jnp.uint32(min(int(drop_p * (1 << 32)), (1 << 32) - 1))


def _drop_mix(rows, cols, seed_u32, b_u32, h_u32):
    z = (rows * jnp.uint32(2654435761)) ^ (cols * jnp.uint32(1013904223))
    z = z ^ (seed_u32 * jnp.uint32(2246822519)) \
          ^ (b_u32 * jnp.uint32(3266489917)) \
          ^ (h_u32 * jnp.uint32(668265263))
    z ^= z >> 16
    z *= jnp.uint32(2246822519)
    z ^= z >> 13
    z *= jnp.uint32(3266489917)
    z ^= z >> 16
    return z


def _drop_keep_dense(shape4, seed_u32, drop_p):
    """The same keep-mask over a dense [b, h, sq, sk] score tensor — used by
    the reference (non-Pallas) path so both paths drop identical positions."""
    rows = jax.lax.broadcasted_iota(jnp.uint32, shape4, 2)
    cols = jax.lax.broadcasted_iota(jnp.uint32, shape4, 3)
    bs = jax.lax.broadcasted_iota(jnp.uint32, shape4, 0)
    hs = jax.lax.broadcasted_iota(jnp.uint32, shape4, 1)
    bits = _drop_mix(rows, cols, seed_u32, bs, hs)
    return bits >= jnp.uint32(min(int(drop_p * (1 << 32)), (1 << 32) - 1))




# --------------------------------------------------------------------------
# forward kernel: grid (b, hq, q_blocks, kv_blocks) — kv innermost
# --------------------------------------------------------------------------

def _fa_fwd_kernel(*refs, block_q, block_kv, causal, scale, q_len, kv_len,
                   has_mask, has_seg, drop_p=0.0):
    from jax.experimental import pallas as pl

    it = iter(refs)
    q_ref = next(it)
    k_ref = next(it)
    v_ref = next(it)
    mask_ref = next(it) if has_mask else None
    segq_ref = next(it) if has_seg else None
    segk_ref = next(it) if has_seg else None
    seed_ref = next(it) if drop_p else None
    o_ref = next(it)
    lse_ref = next(it)
    m_sc, l_sc, acc_sc = next(it), next(it), next(it)

    bb = pl.program_id(0)
    hh = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)
    n_j = pl.num_programs(3)
    diag_off = kv_len - q_len

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, m_sc.dtype)
        l_sc[...] = jnp.zeros(l_sc.shape, l_sc.dtype)
        acc_sc[...] = jnp.zeros(acc_sc.shape, acc_sc.dtype)

    run = True if not causal else \
        (j <= _needed(i, block_q, block_kv, diag_off))

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * jnp.float32(scale)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _apply_masks(
            s, i, j, block_q=block_q, block_kv=block_kv, causal=causal,
            diag_off=diag_off,
            mask_blk=mask_ref[...] if has_mask else None,
            segq_blk=segq_ref[...] if has_seg else None,
            segk_blk=segk_ref[...] if has_seg else None)
        m_prev, l_prev = m_sc[...], l_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_sc[...] = m_new
        # dropout hits the PROBABILITIES (post-softmax): l keeps the
        # undropped sum (that is the softmax normalizer), acc gets the
        # masked/rescaled probs — so out = dropout(softmax(s)) @ v exactly
        l_sc[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if drop_p:
            keep = _drop_keep(p.shape,
                              _seed_u32(seed_ref),
                              bb, hh, i * block_q, j * block_kv, drop_p)
            p = jnp.where(keep, p, jnp.float32(0.0)) * jnp.float32(1.0 / (1.0 - drop_p))
        acc_sc[...] = alpha * acc_sc[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == n_j - 1)
    def _finalize():
        l = jnp.maximum(l_sc[...], jnp.float32(1e-30))
        o_ref[...] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[...] = m_sc[...] + jnp.log(l)


# --------------------------------------------------------------------------
# backward kernels (FlashAttention-2: dQ kernel + per-q-head dK/dV kernel)
# --------------------------------------------------------------------------

def _fa_bwd_dq_kernel(*refs, block_q, block_kv, causal, scale, q_len, kv_len,
                      has_mask, has_seg, drop_p=0.0):
    from jax.experimental import pallas as pl

    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (next(it) for _ in
                                                       range(6))
    mask_ref = next(it) if has_mask else None
    segq_ref = next(it) if has_seg else None
    segk_ref = next(it) if has_seg else None
    seed_ref = next(it) if drop_p else None
    dq_ref = next(it)
    acc_sc = next(it)

    bb = pl.program_id(0)
    hh = pl.program_id(1)
    i = pl.program_id(2)
    j = pl.program_id(3)
    n_j = pl.num_programs(3)
    diag_off = kv_len - q_len

    @pl.when(j == 0)
    def _init():
        acc_sc[...] = jnp.zeros(acc_sc.shape, acc_sc.dtype)

    run = True if not causal else \
        (j <= _needed(i, block_q, block_kv, diag_off))

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * jnp.float32(scale)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...]
        delta = delta_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _apply_masks(
            s, i, j, block_q=block_q, block_kv=block_kv, causal=causal,
            diag_off=diag_off,
            mask_blk=mask_ref[...] if has_mask else None,
            segq_blk=segq_ref[...] if has_seg else None,
            segk_blk=segk_ref[...] if has_seg else None)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if drop_p:
            # dP = mask/(1-p) o (dO V^T); delta = rowsum(dO o O) is already
            # the dropped-P inner product, so the softmax-bwd form is intact
            keep = _drop_keep(p.shape,
                              _seed_u32(seed_ref),
                              bb, hh, i * block_q, j * block_kv, drop_p)
            dp = jnp.where(keep, dp, jnp.float32(0.0)) * jnp.float32(1.0 / (1.0 - drop_p))
        ds = p * (dp - delta)
        acc_sc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_j - 1)
    def _finalize():
        dq_ref[...] = (acc_sc[...] * jnp.float32(scale)).astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(*refs, block_q, block_kv, causal, scale, q_len,
                       kv_len, has_mask, has_seg, drop_p=0.0):
    """Grid (b, hq, kv_blocks, q_blocks): per-Q-HEAD dK/dV partials for one
    KV block, streaming Q blocks; group partials are summed outside."""
    from jax.experimental import pallas as pl

    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (next(it) for _ in
                                                       range(6))
    mask_ref = next(it) if has_mask else None
    segq_ref = next(it) if has_seg else None
    segk_ref = next(it) if has_seg else None
    seed_ref = next(it) if drop_p else None
    dk_ref, dv_ref = next(it), next(it)
    dk_sc, dv_sc = next(it), next(it)

    bb = pl.program_id(0)
    hh = pl.program_id(1)
    kv_idx = pl.program_id(2)
    jq = pl.program_id(3)
    n_q = pl.num_programs(3)
    diag_off = kv_len - q_len

    @pl.when(jq == 0)
    def _init():
        dk_sc[...] = jnp.zeros(dk_sc.shape, dk_sc.dtype)
        dv_sc[...] = jnp.zeros(dv_sc.shape, dv_sc.dtype)

    # q block jq touches this kv block iff its LAST row reaches it
    run = True if not causal else \
        (jq * block_q + block_q - 1 + diag_off >= kv_idx * block_kv)

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * jnp.float32(scale)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...]
        delta = delta_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _apply_masks(
            s, jq, kv_idx, block_q=block_q, block_kv=block_kv, causal=causal,
            diag_off=diag_off,
            mask_blk=mask_ref[...] if has_mask else None,
            segq_blk=segq_ref[...] if has_seg else None,
            segk_blk=segk_ref[...] if has_seg else None)
        p = jnp.exp(s - lse)
        if drop_p:
            keep = _drop_keep(p.shape,
                              _seed_u32(seed_ref),
                              bb, hh, jq * block_q, kv_idx * block_kv,
                              drop_p)
            inv = jnp.float32(1.0 / (1.0 - drop_p))
            pd = jnp.where(keep, p, jnp.float32(0.0)) * inv
        else:
            pd = p
        dv_sc[...] += jax.lax.dot_general(
            pd, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if drop_p:
            dp = jnp.where(keep, dp, jnp.float32(0.0)) * inv
        ds = p * (dp - delta)
        # q is pre-scaled, so this carries the `scale` factor already
        dk_sc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jq == n_q - 1)
    def _finalize():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------

def _pallas_mode():
    """Returns 'tpu' (compiled), 'interpret' (CPU tests) or None (fallback)."""
    if jax.default_backend() == "tpu":
        return "tpu"
    if flags.flag("flash_attention_interpret"):
        return "interpret"
    return None


def _blocks_for(sq, sk, d):
    """Block sizes if the shape fits the Pallas path, else None."""
    block_q = min(flags.flag("flash_attention_block_q"), sq)
    block_kv = min(flags.flag("flash_attention_block_kv"), sk)
    if sq % block_q or sk % block_kv or (d % 128 and d not in (64, 96)):
        return None
    return block_q, block_kv


def _heads_first(x):
    return jnp.swapaxes(x, 1, 2)             # [b, s, h, d] -> [b, h, s, d]


def _specs_common(has_mask, has_seg, mask_heads, group, blocks, sq, sk, d,
                  causal, dkv_layout=False, with_seed=False):
    """(in_specs for q,k,v[,mask][,segq,segk][,seed]) given the masking modes.
    Index-map convention: grid = (b, h, X, Y).  With causal, the streamed
    operand's block index is clamped to the last/first needed block, so the
    skipped iterations re-fetch the same block and Mosaic elides the DMA —
    causal skipping costs no bandwidth."""
    from jax.experimental import pallas as pl

    block_q, block_kv = blocks
    g = np.int32(max(group, 1))
    diag_off = sk - sq

    if not dkv_layout:          # fwd/dq: X = q block i, Y = kv block j
        def jc(i, j):           # clamped kv block index
            if not causal:
                return j
            return jnp.minimum(j, _needed(i, block_q, block_kv, diag_off))
        qmap = lambda b, h, i, j: (b, h, i, _I0)
        kvmap = lambda b, h, i, j: (b, h // g, jc(i, j), _I0)
        mmap = (lambda b, h, i, j: (b, _I0 if mask_heads == 1 else h,
                                    i, jc(i, j)))
        sqmap = lambda b, h, i, j: (b, i, _I0)
        skmap = lambda b, h, i, j: (b, jc(i, j), _I0)
    else:                       # dkv: X = kv block, Y = q block (streamed)
        def qc(kv, jq):         # clamp to the first q block that reaches kv
            if not causal:
                return jq
            first = jnp.floor_divide(
                jnp.maximum((kv * block_kv - diag_off - block_q + 1), 0),
                jnp.int32(block_q))  # int32 divisor: see _needed
            return jnp.maximum(jq, first)
        qmap = lambda b, h, kv, jq: (b, h, qc(kv, jq), _I0)
        kvmap = lambda b, h, kv, jq: (b, h // g, kv, _I0)
        mmap = (lambda b, h, kv, jq: (b, _I0 if mask_heads == 1 else h,
                                      qc(kv, jq), kv))
        sqmap = lambda b, h, kv, jq: (b, qc(kv, jq), _I0)
        skmap = lambda b, h, kv, jq: (b, kv, _I0)

    specs = [
        pl.BlockSpec((None, None, block_q, d), qmap),
        pl.BlockSpec((None, None, block_kv, d), kvmap),
        pl.BlockSpec((None, None, block_kv, d), kvmap),
    ]
    if has_mask:
        specs.append(pl.BlockSpec((None, None, block_q, block_kv), mmap))
    if has_seg:
        specs.append(pl.BlockSpec((None, block_q, 1), sqmap))
        specs.append(pl.BlockSpec((None, block_kv, 1), skmap))
    if with_seed:
        specs.append(pl.BlockSpec((1, 1), lambda *_: (0, 0)))
    return specs, qmap


def _prep_mask_segs(mask, seg_q, seg_k, drop_p=0.0, seed=None):
    has_mask = mask is not None
    has_seg = seg_q is not None
    mask_heads = mask.shape[1] if has_mask else 0
    extra = []
    if has_mask:
        extra.append(mask.astype(jnp.float32))
    if has_seg:
        # float32 carries segment ids exactly below 2^24; keeps every
        # kernel operand a float (simplest Mosaic layout path)
        extra.append(seg_q.astype(jnp.float32)[:, :, None])
        extra.append(seg_k.astype(jnp.float32)[:, :, None])
    if drop_p:
        # seed < 2^24 rides as float32 like the segment ids
        extra.append(jnp.asarray(seed, jnp.float32).reshape(1, 1))
    return has_mask, has_seg, mask_heads, extra


def _fa_pallas_forward(q, k, v, causal, mask, seg_q, seg_k, blocks, mode,
                       drop_p=0.0, seed=None):
    from jax.experimental import pallas as pl

    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    block_q, block_kv = blocks
    scale = 1.0 / math.sqrt(d)
    has_mask, has_seg, mask_heads, extra = _prep_mask_segs(
        mask, seg_q, seg_k, drop_p, seed)

    kernel = functools.partial(
        _fa_fwd_kernel, block_q=block_q, block_kv=block_kv, causal=causal,
        scale=scale, q_len=sq, kv_len=sk, has_mask=has_mask, has_seg=has_seg,
        drop_p=drop_p)
    in_specs, qmap = _specs_common(has_mask, has_seg, mask_heads, group,
                                   blocks, sq, sk, d, causal,
                                   with_seed=bool(drop_p))
    return _fwd_call(kernel, b, hq, sq, sk, d, blocks, in_specs, qmap,
                     q, k, v, extra, mode)


def _fwd_call(kernel, b, hq, sq, sk, d, blocks, in_specs, qmap, q, k, v,
              extra, mode):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_q, block_kv = blocks
    qf, kf, vf = _heads_first(q), _heads_first(k), _heads_first(v)
    return pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(b, hq, sq // block_q, sk // block_kv),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, None, block_q, d), qmap),
            pl.BlockSpec((None, None, block_q, 1), qmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=(mode == "interpret"),
    )(qf, kf, vf, *extra)


def _fa_pallas_backward(q, k, v, out, lse, g, causal, mask, seg_q, seg_k,
                        blocks, mode, drop_p=0.0, seed=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    block_q, block_kv = blocks
    scale = 1.0 / math.sqrt(d)
    has_mask, has_seg, mask_heads, extra = _prep_mask_segs(
        mask, seg_q, seg_k, drop_p, seed)

    qf, kf, vf = _heads_first(q), _heads_first(k), _heads_first(v)
    of, gf = _heads_first(out), _heads_first(g)
    delta = jnp.sum(of.astype(jnp.float32) * gf.astype(jnp.float32),
                    axis=-1, keepdims=True)          # [b, hq, sq, 1]

    common = dict(block_q=block_q, block_kv=block_kv, causal=causal,
                  scale=scale, q_len=sq, kv_len=sk, has_mask=has_mask,
                  has_seg=has_seg, drop_p=drop_p)

    # ---- dQ: grid (b, hq, q_blocks, kv_blocks) ----
    in_specs, qmap = _specs_common(has_mask, has_seg, mask_heads, group,
                                   blocks, sq, sk, d, causal,
                                   with_seed=bool(drop_p))
    # q,k,v + do,lse,delta share q-block/row indexing
    rowmap = qmap
    dq_specs = in_specs[:3] + [
        pl.BlockSpec((None, None, block_q, d), qmap),
        pl.BlockSpec((None, None, block_q, 1), rowmap),
        pl.BlockSpec((None, None, block_q, 1), rowmap),
    ] + in_specs[3:]
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, **common),
        name="flash_dq",
        grid=(b, hq, sq // block_q, sk // block_kv),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((None, None, block_q, d), qmap),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=(mode == "interpret"),
    )(qf, kf, vf, gf, lse, delta, *extra)

    # ---- dK/dV: grid (b, hq, kv_blocks, q_blocks), per-q-head partials ----
    in_specs2, qmap2 = _specs_common(has_mask, has_seg, mask_heads, group,
                                     blocks, sq, sk, d, causal,
                                     dkv_layout=True, with_seed=bool(drop_p))
    dkv_specs = in_specs2[:3] + [
        pl.BlockSpec((None, None, block_q, d), qmap2),
        pl.BlockSpec((None, None, block_q, 1), qmap2),
        pl.BlockSpec((None, None, block_q, 1), qmap2),
    ] + in_specs2[3:]
    outmap = lambda bb, h, kv, jq: (bb, h, kv, _I0)
    dk_p, dv_p = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, **common),
        name="flash_dkv",
        grid=(b, hq, sk // block_kv, sq // block_q),
        in_specs=dkv_specs,
        out_specs=[pl.BlockSpec((None, None, block_kv, d), outmap),
                   pl.BlockSpec((None, None, block_kv, d), outmap)],
        out_shape=[jax.ShapeDtypeStruct((b, hq, sk, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, hq, sk, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, d), jnp.float32)],
        interpret=(mode == "interpret"),
    )(qf, kf, vf, gf, lse, delta, *extra)

    # sum q-head partials within each KV group
    dk = dk_p.reshape(b, hkv, group, sk, d).sum(axis=2)
    dv = dv_p.reshape(b, hkv, group, sk, d).sum(axis=2)

    unf = lambda x: jnp.swapaxes(x, 1, 2)
    return (unf(dq), unf(dk).astype(k.dtype), unf(dv).astype(v.dtype))


# --------------------------------------------------------------------------
# custom_vjp plumbing.  mask / seg operands are non-differentiable data:
# their cotangents are zeros.
# --------------------------------------------------------------------------

_NO_MASK = None


def _fa_supported(q, k, causal, mask, seg_q):
    """(mode, blocks) of the Pallas path, or (None, None) for the XLA
    reference — which on a TPU is logged and counted, never silent."""
    mode = _pallas_mode()
    if mode is None:
        return None, None
    blocks = _blocks_for(q.shape[1], k.shape[1], q.shape[-1])
    why = None
    if q.dtype == jnp.float64:
        why = "float64 operands"
    elif blocks is None:
        why = ("seq lengths must divide into FLAGS_flash_attention_block_q/"
               "_kv blocks and head_dim be 64, 96 or a multiple of 128")
    else:
        if mode == "tpu":
            blocks = _tuned_blocks(q, k, causal, mask, seg_q, blocks)
        if mask is not None and (mask.shape[-2] % blocks[0]
                                 or mask.shape[-1] % blocks[1]):
            why = f"mask shape {mask.shape} does not tile blocks {blocks}"
    if why is not None:
        _note_reference_on_tpu(why, (q.shape, k.shape, str(q.dtype)))
        return None, None
    return mode, blocks


_LOG = logging.getLogger("paddle_tpu.kernels")
_REFERENCE_SEEN: set = set()


def _note_reference_on_tpu(why, shapes):
    """Flash attention is reached from the general ``nn`` API with shapes
    the kernels do not cover, so the XLA reference stays legal on a chip —
    but never silent: every such trace bumps
    ``kernels.reference_fallbacks{kernel=flash_attention}`` and the first
    one per (rule, shapes) logs the rule that failed.  Off-TPU the
    reference is the normal path (tests) and nothing is recorded."""
    if jax.default_backend() != "tpu":
        return
    from .. import observability as _obs
    _obs.counter("kernels.reference_fallbacks",
                 kernel="flash_attention").inc()
    if (why, shapes) not in _REFERENCE_SEEN:
        _REFERENCE_SEEN.add((why, shapes))
        _LOG.warning("flash_attention: XLA reference instead of the Pallas "
                     "kernels on TPU for %s — %s", shapes, why)


def _tuned_blocks(q, k, causal, mask, seg_q, default):
    """Measured (block_q, block_kv) from the persistent autotune cache.

    Key is the full kernel configuration (shape bucket x dtype x masking
    mode x device kind).  On a cold cache with tuning enabled, candidates
    are timed via standalone compiled probes on dummy data — legal even
    when this is reached inside an outer trace, since shapes are static and
    each probe is its own top-level dispatch.  Forward and backward share
    the chosen tiling (the backward re-derives it through the same cache
    key), so the custom_vjp pair stays consistent.
    """
    from . import autotune

    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    key = autotune.make_key(
        "flash_fwd", sq=sq, sk=sk, d=d, hq=hq, hkv=hkv,
        dt=str(q.dtype), causal=int(bool(causal)),
        m=int(mask is not None), s=int(seg_q is not None))
    cands = [c for c in autotune.flash_attention_candidates(sq, sk, d)
             if mask is None or
             (mask.shape[-2] % c[0] == 0 and mask.shape[-1] % c[1] == 0)]

    def bench(blocks):
        import numpy as np_

        if jax.default_backend() != "tpu":
            return None   # cross-lowering on CPU: nothing to measure on
        rng = np_.random.default_rng(0)
        shape_q = (min(b, 1), sq, hq, d)
        qq = jnp.asarray(rng.standard_normal(shape_q), q.dtype)
        kk = jnp.asarray(
            rng.standard_normal((min(b, 1), sk, hkv, d)), q.dtype)
        vv = jnp.asarray(
            rng.standard_normal((min(b, 1), sk, hkv, d)), q.dtype)

        fn = jax.jit(lambda a, b_, c: _fa_pallas_forward(
            a, b_, c, causal, None, None, None, blocks, "tpu")[0])

        def timed():
            # jaxlint: disable=JL002 -- autotune timing harness: blocking is the measurement, runs at tuning time only
            jax.block_until_ready(fn(qq, kk, vv))
        return timed

    return autotune.lookup_or_tune(key, cands, bench, default)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fa_core(q, k, v, causal, drop_p, mask, seg_q, seg_k, seed):
    out, _ = _fa_core_fwd(q, k, v, causal, drop_p, mask, seg_q, seg_k, seed)
    return out


def _fa_core_fwd(q, k, v, causal, drop_p, mask, seg_q, seg_k, seed):
    mode, blocks = _fa_supported(q, k, causal, mask, seg_q)
    if mode is None:
        out, lse = _reference_attention_lse(q, k, v, causal, mask, seg_q,
                                            seg_k, drop_p, seed)
        return out, (q, k, v, mask, seg_q, seg_k, seed, None, None)
    out, lse = _fa_pallas_forward(q, k, v, causal, mask, seg_q, seg_k,
                                  blocks, mode, drop_p, seed)
    return jnp.swapaxes(out, 1, 2), (q, k, v, mask, seg_q, seg_k, seed,
                                     jnp.swapaxes(out, 1, 2), lse)


def _fa_core_bwd(causal, drop_p, res, g):
    q, k, v, mask, seg_q, seg_k, seed, out, lse = res
    zeros = lambda t: None if t is None else jnp.zeros_like(t)
    if out is None:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _reference_attention(q_, k_, v_, causal, mask,
                                                    seg_q, seg_k, drop_p,
                                                    seed), q, k, v)
        dq, dk, dv = vjp(g)
        return dq, dk, dv, zeros(mask), zeros(seg_q), zeros(seg_k), \
            zeros(seed)
    mode, blocks = _fa_supported(q, k, causal, mask, seg_q)
    dq, dk, dv = _fa_pallas_backward(q, k, v, out, lse, g, causal, mask,
                                     seg_q, seg_k, blocks, mode, drop_p,
                                     seed)
    return dq, dk, dv, zeros(mask), zeros(seg_q), zeros(seg_k), zeros(seed)


_fa_core.defvjp(_fa_core_fwd, _fa_core_bwd)


def _flash_attention_arrays(q, k, v, causal, mask=None, seg_q=None,
                            seg_k=None, drop_p=0.0, seed=None):
    if drop_p and seed is None:
        raise ValueError("flash attention dropout requires a seed")
    return _fa_core(q, k, v, causal, float(drop_p), mask, seg_q, seg_k,
                    seed if drop_p else jnp.zeros((1, 1), jnp.float32))


def _split_over_mesh(mesh, causal, q_shape, kv_heads):
    """The unmasked call as a prim split by hand over a multi-device mesh.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), so
    the call is split over what attention is independent in: batch over
    'dp', heads over 'sep'/'mp' (kv groups stay whole per shard); any
    other axis sees the call replicated."""
    from jax.sharding import PartitionSpec

    size = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch = "dp" if size.get("dp", 1) > 1 else None
    heads = tuple(a for a in ("sep", "mp") if size.get(a, 1) > 1)
    n = int(np.prod([size[a] for a in heads]))
    if q_shape[0] % size.get("dp", 1) or kv_heads % n:
        raise ValueError(
            f"flash attention under mesh {size}: batch ({q_shape[0]}) must "
            f"divide over dp and kv heads ({kv_heads}) over {heads}")
    spec = PartitionSpec(batch, None, heads or None, None)
    # check_vma=False: a pallas_call's outputs carry no varying-axes type
    return jax.shard_map(
        lambda q, k, v: _flash_attention_arrays(q, k, v, causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)


def flash_attention(query, key, value, causal=False, attn_mask=None,
                    dropout=0.0, training=True, rng_name=None, mesh=None):
    """Tensor-level flash attention, layout [b, s, h, d].

    ``mesh``: the multi-device mesh the call is traced under, if any.
    Where the Pallas kernels run they are then split over it by hand
    (``_split_over_mesh``); the XLA reference needs nothing, GSPMD
    partitions it.

    GQA-native: key/value may have fewer heads (a divisor of the query
    heads).  ``attn_mask``: additive fp32 mask [b, 1|h, sq, sk] (reference
    flash_attn attn_mask surface), streamed blockwise by the kernel.
    ``dropout``: attention-probability dropout rate applied in-kernel
    (reference flash_attn_kernel.cu:53); active when ``training``.  The
    keep-mask is a counter-based hash of (seed, batch, head, position) —
    deterministic given the paddle RNG state, invariant to tiling, and
    identical between the fused and reference paths.
    """
    args = [query, key, value] + ([attn_mask] if attn_mask is not None else [])
    args = tuple(a if isinstance(a, Tensor) else Tensor(a) for a in args)
    drop_p = float(dropout) if training else 0.0

    if mesh is not None and mesh.size > 1 and _pallas_mode() is not None:
        if drop_p or attn_mask is not None:
            raise NotImplementedError(
                "flash_attention(mesh=...) splits only the unmasked, "
                "dropout-free call over a mesh")
        prim = _split_over_mesh(mesh, causal, args[0].shape,
                                args[1].shape[2])
    elif drop_p:
        from ..core.random import next_key

        # one seed per call from the paddle RNG stream (< 2^24: rides as
        # float32 through the custom_vjp like the segment ids)
        seed = jax.random.randint(next_key(), (1, 1), 0, 1 << 23
                                  ).astype(jnp.float32)
        args = args + (Tensor(seed),)
        if attn_mask is not None:
            def prim(q, k, v, m, sd):
                return _flash_attention_arrays(q, k, v, causal, mask=m,
                                               drop_p=drop_p, seed=sd)
        else:
            def prim(q, k, v, sd):
                return _flash_attention_arrays(q, k, v, causal,
                                               drop_p=drop_p, seed=sd)
    elif attn_mask is not None:
        def prim(q, k, v, m):
            return _flash_attention_arrays(q, k, v, causal, mask=m)
    else:
        def prim(q, k, v):
            return _flash_attention_arrays(q, k, v, causal)
    return apply_op("flash_attention", prim, args)


# --------------------------------------------------------------------------
# varlen (unpadded) attention — segment-aware Pallas path
# --------------------------------------------------------------------------

def _segments_from_cu(cu, total):
    """cu_seqlens [B+1] -> (segment id, position-in-segment) per token."""
    tok = jnp.arange(total)
    seg = jnp.searchsorted(cu[1:], tok, side="right")
    pos = tok - cu[seg]
    return seg, pos


def flash_attn_varlen(q, k, v, cu_seqlens_q, cu_seqlens_k, causal=False):
    """Unpadded variable-length attention (reference ops.yaml:
    flash_attn_unpadded / flash_attn_varlen_qkvpacked).

    q/k/v: [total_tokens, heads, dim] — sequences packed back-to-back;
    cu_seqlens: [batch+1] cumulative lengths.  Tokens attend only within
    their own segment, causally if requested.

    Runs the segment-masking mode of the Pallas flash kernels: per-token
    int segment ids (O(total) memory) are streamed beside the Q/KV blocks
    and compared in-kernel, so no [T, T] mask is ever materialized — the
    blocked online-softmax is identical to the padded path.  With causal,
    global positions order tokens inside each segment (packing preserves
    order), so the plain causal test composes with the segment test; this
    requires cu_seqlens_q == cu_seqlens_k (self-attention packing), the
    reference's varlen training case.
    """
    def prim(q_, k_, v_, cq, ck):
        tq, h, d = q_.shape
        tk = k_.shape[0]
        if causal:
            # causal ordering uses global packed positions, valid only for
            # identical q/k packings — reject what we cannot honor
            if tq != tk or cq.shape != ck.shape:
                raise ValueError(
                    "flash_attn_varlen(causal=True) requires identical "
                    "q/k packings (cu_seqlens_q == cu_seqlens_k)")
            try:                     # value check only when concrete
                # jaxlint: disable=JL002 -- eager-only API validation; under jit the tracer except-path skips the sync
                same = bool(jnp.all(cq == ck))
            except jax.errors.TracerBoolConversionError:
                same = True
            if not same:
                raise ValueError(
                    "flash_attn_varlen(causal=True): cu_seqlens_q and "
                    "cu_seqlens_k differ")
        seg_q, _ = _segments_from_cu(cq, tq)
        seg_k, _ = _segments_from_cu(ck, tk)
        # float32 ids: exact below 2^24, and float primals keep the
        # custom_vjp cotangent plumbing uniform
        out = _flash_attention_arrays(
            q_[None], k_[None], v_[None], causal,
            seg_q=seg_q[None].astype(jnp.float32),
            seg_k=seg_k[None].astype(jnp.float32))
        return out[0]

    return apply_op("flash_attn_varlen",
                    prim,
                    tuple(a if isinstance(a, Tensor) else Tensor(a)
                          for a in (q, k, v, cu_seqlens_q, cu_seqlens_k)))


flash_attn_unpadded = flash_attn_varlen
