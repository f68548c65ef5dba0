"""The gated delta rule of the serving step (Kimi Delta Attention: linear
attention with a decay a CHANNEL), ragged over slots: prefill chunks and
decode tokens in one call, the recurrent state updated in place.

One head keeps a float32 state ``S`` (``[key_dim, value_dim]``) a slot.  For
the slot's next tokens ``t = 1..n`` with ``q_t``, ``k_t`` (``key_dim``, both
L2-normalised, ``q`` scaled), ``v_t`` (``value_dim``), the log decay ``g_t <=
0`` a channel of the key and ``beta_t`` a head:

    S' = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T            (the delta rule)
    o_t = S_t^T q_t

The kernel runs these lines as written, token by token on the state held in
VMEM, in float32: a decode slot's one token and a chunk's ``n`` alike (the
chunk form, the WY transform of the delta rule with the decay applied in
sub-blocks, is not written: PERF.md section 7).  ``beta`` is folded into the
operands before the call (``k sqrt(beta)`` and ``v sqrt(beta)`` give the same
``S_t``), so the kernel sees four token operands and no scalar a head.

- The step's tokens come PACKED: ``q, k, v, g`` are ``[rows, heads, width]``
  and slot ``b``'s tokens lie in rows ``[starts[b], starts[b] + q_lens[b])``
  (the engine's packed member: ``starts = cumsum(q_lens) - q_lens``; its
  dense ``[B, T]`` grid: ``starts = b T``).  ``rows`` is a leading, untiled
  axis, so a block may begin at any row: a decode slot's token is a block of
  ONE row, a chunk's tokens a block of ``chunk`` rows at an element offset
  (``pl.Element``), and no ``[slots, chunk]`` grid of operands is ever
  written out.  A slot with one token names the chunk block of the chunk slot
  before it, so the pipeline fetches no chunk block for it.
- One program a (block of heads, slot), the slots innermost.  The state is
  the pipeline's own block ``[heads of the block, key_dim, value_dim]``, in
  and out, and the output ALIASES the input: the call writes a slot's new
  state where the old one lay.  An idle slot (``q_lens[b] == 0``) names the
  block of the live slot before it (the first live one where none precedes):
  the pipeline neither fetches nor writes back a block whose index did not
  change, so an idle slot moves no byte of state (``kernels/ssd.py``'s
  scheme).  With no live slot at all every program names block 0 and copies
  it through.
- ``fresh[b]`` (the slot's first chunk): ``S_0`` is read as zero, whatever
  the last request left there.
- ``layer`` (an int32 device scalar) with the whole ``[layers, ...]`` state:
  the layer's blocks are indexed where they lie.
- The results come back in two arrays the wrapper gathers the packed rows
  from: a decode slot's row in ``[slots, heads, value_dim]``, a chunk's rows
  in its own block of ``[slots, chunk, heads, value_dim]`` (of which only
  chunk slots' blocks are ever written).

``FLAGS_paged_attention_interpret`` (the serving step's kernels interpreted
on the CPU: tests and the benchmark's rehearsal) runs this kernel in
interpreter mode; otherwise the CPU takes the XLA oracle beside it, the same
recurrence by ``lax.scan`` over a chunk's positions, all slots at once.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags
from . import paged_attention as _paged  # noqa: F401  (defines the flag)

_I0 = np.int32(0)  # index-map literal: bare 0 would be int64 under x64 mode
_LANES = 128
_PACKED_ROWS = 16   # rows of one tile of a 16-bit operand
# the state block of one program, in and out and two buffers each, stays
# under this (16 heads x 128 x 128 float32 at the published Solar-Open2
# sizes)
_STATE_BLOCK_BYTES = 1 << 20


def _heads_per_block(heads: int, key_dim: int, value_dim: int) -> int:
    """Heads of one program: the largest divisor of ``heads`` whose float32
    state block stays under ``_STATE_BLOCK_BYTES``, whole 16-row tiles of
    the token operands where one exists."""
    per = key_dim * value_dim * 4
    fits = [n for n in range(heads, 0, -1) if heads % n == 0
            and (n * per <= _STATE_BLOCK_BYTES or n == 1)]
    return next((n for n in fits if n % _PACKED_ROWS == 0 or n == heads),
                fits[0])


def kda_geometry_error(heads: int, key_dim: int,
                       value_dim: int) -> Optional[str]:
    """Why the compiled kernel does not take these sizes, or None."""
    if key_dim % _LANES or value_dim % _LANES:
        return (f"key_dim={key_dim} and value_dim={value_dim} must be "
                f"multiples of the {_LANES} lanes")
    hb = _heads_per_block(heads, key_dim, value_dim)
    if hb % _PACKED_ROWS and hb != heads:
        return (f"{heads} heads of {key_dim} x {value_dim} have no block of "
                f"whole {_PACKED_ROWS}-row tiles under "
                f"{_STATE_BLOCK_BYTES} bytes of state")
    return None


def l2_normalised(a):
    """``a / sqrt(sum a^2 + 1e-6)`` over the last axis, in float32: what
    ``q`` and ``k`` are a head before the delta rule reads them."""
    a = a.astype(jnp.float32)
    return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)


def rows_of_slots(starts, q_lens, rows: int):
    """``(slot [rows], position in the slot's tokens [rows], live [rows])``
    of every packed row; ``starts`` is non-decreasing."""
    i32 = jnp.int32
    r = jnp.arange(rows, dtype=i32)
    slot = jnp.clip((r[:, None] >= starts[None, :]).sum(axis=1).astype(i32)
                    - 1, 0, starts.shape[0] - 1)
    t = r - jnp.take(starts, slot)
    return slot, t, jnp.logical_and(t >= 0, t < jnp.take(q_lens, slot))


def _reference_ragged_kda_update(state, q, k, v, g, beta, starts, q_lens,
                                 fresh, chunk):
    """The XLA oracle, float32: the recurrence as written, one position of
    every slot's tokens a step of a ``lax.scan``.  ``state`` is one layer's
    ``[B, H, K, V]``; a slot without work keeps its state, a row that holds
    no token reads ``o = 0``."""
    f32 = jnp.float32
    R = q.shape[0]
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state.astype(f32))

    def step(s, t):
        live = t < q_lens                                   # [B]
        row = jnp.clip(starts + t, 0, R - 1)
        qt, kt, vt, gt = (jnp.take(a, row, axis=0).astype(f32)
                          for a in (q, k, v, g))            # [B, H, *]
        bt = jnp.take(beta, row, axis=0).astype(f32)        # [B, H]
        sd = s * jnp.exp(gt)[..., None]
        u = vt - jnp.einsum("bhkv,bhk->bhv", sd, kt, precision="highest")
        new = sd + (bt[..., None] * kt)[..., None] * u[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", new, qt, precision="highest")
        keep = live[:, None, None, None]
        return jnp.where(keep, new, s), (
            jnp.where(live[:, None, None], o, 0.0),
            jnp.where(live, row, R))

    new, (o, at) = jax.lax.scan(step, s0, jnp.arange(chunk, dtype=jnp.int32))
    out = jnp.zeros((R,) + o.shape[2:], f32).at[at.reshape(-1)].set(
        o.reshape((-1,) + o.shape[2:]), mode="drop")
    work = (q_lens > 0)[:, None, None, None]
    return out.astype(q.dtype), jnp.where(work, new, state)


def _kda_kernel(*refs, hb, tp, layered, chunked):
    """One (block of ``hb`` heads, slot): see the module's docstring.
    Scalar prefetch: q_lens, fresh, whether any slot is live, each chunk
    slot's offset inside its chunk block, then what the index maps alone
    read (and the layer); blocks: one row of q, k, v, g ``[1, hb, width]``,
    (``chunked``) ``tp`` rows of each ``[tp, hb, width]``, the state ``[hb,
    K, V]``; out: one row ``[hb, V]``, (``chunked``) ``[tp, hb, V]``, the
    state."""
    from jax.experimental import pallas as pl

    n_scalar = 10 if layered else 9
    ql_ref, fresh_ref, any_ref, off_ref = refs[:4]
    ops = refs[n_scalar:]
    q1, k1, v1, g1 = ops[:4]
    if chunked:
        qc, kc, vc, gc, s_ref, o1_ref, o2_ref, so_ref = ops[4:]
    else:
        s_ref, o1_ref, so_ref = ops[4:]
    f32, i32 = jnp.float32, np.int32
    b = pl.program_id(1)
    ql = ql_ref[b]
    K, V = s_ref.shape[1:]
    eye = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    key_rows = jax.lax.broadcasted_iota(jnp.int32, (hb, K), 0)
    value_rows = jax.lax.broadcasted_iota(jnp.int32, (hb, V), 0)

    def column(r):
        """``[1, K]`` -> ``[K, 1]``: the diagonal of its broadcast."""
        return jnp.sum(jnp.where(eye, jnp.broadcast_to(r, (K, K)), f32(0)),
                       axis=1, keepdims=True)

    @pl.when(jnp.logical_and(ql == 0, any_ref[0] == 0))
    def _through():                        # no slot is live: block 0 as is
        so_ref[...] = s_ref[...]

    def enter():
        s0 = s_ref[...]
        so_ref[...] = jnp.where(fresh_ref[b] == 0, s0, jnp.zeros_like(s0))

    def token(qt, kt, vt, gt):
        """One token through every head of the block, on the state in
        ``so_ref``: ``[hb, V]`` float32, the token's ``o``."""
        qt, kt, vt = qt.astype(f32), kt.astype(f32), vt.astype(f32)
        at = jnp.exp(gt.astype(f32))

        def head(c):
            h, acc = c
            mine = key_rows == h

            def pick(tile, rows=mine):
                return jnp.sum(jnp.where(rows, tile, f32(0)), axis=0,
                               keepdims=True)

            k_col = column(pick(kt))
            s = so_ref[h] * column(pick(at))
            u = pick(vt, value_rows == h) \
                - jnp.sum(s * k_col, axis=0, keepdims=True)
            s = s + k_col * u
            so_ref[h] = s
            o = jnp.sum(s * column(pick(qt)), axis=0, keepdims=True)
            return h + i32(1), jnp.where(value_rows == h,
                                         jnp.broadcast_to(o, (hb, V)), acc)

        # static bounds: a while_loop keeps the counter int32 under x64
        return jax.lax.while_loop(lambda c: c[0] < i32(hb), head,
                                  (_I0, jnp.zeros((hb, V), f32)))[1]

    @pl.when(ql == 1)
    def _one_token():
        enter()
        o1_ref[...] = token(q1[0], k1[0], v1[0], g1[0]).astype(o1_ref.dtype)

    if chunked:
        @pl.when(ql > 1)
        def _chunk():
            enter()
            off = off_ref[b]

            def step(t):
                at = off + t
                o2_ref[t] = token(qc[at], kc[at], vc[at],
                                  gc[at]).astype(o2_ref.dtype)
                return t + i32(1)

            jax.lax.while_loop(lambda t: t < ql, step, _I0)


def _named(mask):
    """For every slot the slot whose block its program names: itself where
    ``mask``, else the last one before it where ``mask``, else the first
    such, else (none at all) 0."""
    idx = jnp.arange(mask.shape[0], dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(mask, idx, -1))
    return jnp.where(before >= 0, before,
                     jnp.argmax(mask).astype(jnp.int32)).astype(jnp.int32)


def _pallas_ragged_kda_update(state, q, k, v, g, beta, starts, q_lens, fresh,
                              chunk, layer, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32, i32 = jnp.float32, jnp.int32
    R, H, K = q.shape
    V = v.shape[-1]
    B = q_lens.shape[0]
    layered = layer is not None
    chunked = chunk > 1
    hb = _heads_per_block(H, K, V)
    tp = int(chunk)
    ql = q_lens.astype(i32)
    starts = starts.astype(i32)
    # the delta rule with beta folded into its operands
    rb = jnp.sqrt(beta.astype(f32))[..., None]
    k = (k.astype(f32) * rb).astype(q.dtype)
    v = (v.astype(f32) * rb).astype(q.dtype)
    g = g.astype(f32)
    live, many = ql > 0, ql > 1
    chunk_at = jnp.clip(starts, 0, max(R - tp, 0))
    scalars = [ql, fresh.astype(i32), jnp.any(live).astype(i32).reshape(1),
               starts - chunk_at,
               _named(live), jnp.clip(starts, 0, R - 1),
               jnp.take(chunk_at, _named(many)), _named(ql == 1),
               _named(many)]
    if layered:
        scalars.append(jnp.asarray(layer, i32).reshape(1))

    def state_index(j, b, *s):
        at = (s[4][b], j, _I0, _I0)
        return (s[-1][0],) + at if layered else at

    def one_row(width):
        return pl.BlockSpec((1, hb, width), lambda j, b, *s: (s[5][b], j, _I0))

    def chunk_rows(width):
        E = pl.Element
        return pl.BlockSpec(
            (E(tp), E(hb), E(width)),
            lambda j, b, *s: (s[6][b], j * np.int32(hb), _I0))

    state_spec = pl.BlockSpec(
        ((None,) if layered else ()) + (None, hb, K, V), state_index)
    o1_spec = pl.BlockSpec((None, hb, V), lambda j, b, *s: (s[7][b], j, _I0))
    o2_spec = pl.BlockSpec((None, tp, hb, V),
                           lambda j, b, *s: (s[8][b], _I0, j, _I0))
    tokens = [q, k, v, g]
    widths = [K, K, V, K]
    operands = tokens + (tokens if chunked else []) + [state]
    in_specs = [one_row(w) for w in widths] \
        + ([chunk_rows(w) for w in widths] if chunked else []) + [state_spec]
    out_specs = [o1_spec] + ([o2_spec] if chunked else []) + [state_spec]
    out_shape = [jax.ShapeDtypeStruct((B, H, V), q.dtype)] \
        + ([jax.ShapeDtypeStruct((B, tp, H, V), q.dtype)] if chunked else []) \
        + [jax.ShapeDtypeStruct(state.shape, state.dtype)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=(H // hb, B),
        in_specs=in_specs, out_specs=out_specs)
    # the pipeline's two buffers of the state block in and out, of the token
    # blocks, and room for a head's temporaries
    need = 4 * hb * K * V * 4 + 4 * tp * hb * (3 * K + 2 * V) * 4 + (8 << 20)
    *outs, new = pl.pallas_call(
        functools.partial(_kda_kernel, hb=hb, tp=tp, layered=layered,
                          chunked=chunked),
        name="ragged_kda_update",
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases={len(scalars) + len(operands) - 1:
                              len(out_shape) - 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(max(need, 32 << 20))),
        interpret=interpret,
    )(*scalars, *operands)
    if not chunked:                    # row b is slot b's
        return jnp.where(live[:, None, None], outs[0],
                         jnp.zeros((), q.dtype)), new
    slot, t, has = rows_of_slots(starts, ql, R)
    o1, o2 = outs
    o = jnp.where((jnp.take(ql, slot) == 1)[:, None, None],
                  jnp.take(o1, slot, axis=0),
                  jnp.take(o2.reshape(B * tp, H, V),
                           slot * tp + jnp.clip(t, 0, tp - 1), axis=0))
    return jnp.where(has[:, None, None], o, jnp.zeros((), q.dtype)), new


def ragged_kda_update(state, q, k, v, g, beta, starts, q_lens, fresh, *,
                      chunk: int, layer=None):
    """The slots' next tokens through the delta rule, the state updated in
    place.

    Args:
      state:  ``[slots, heads, key_dim, value_dim]`` float32, or the whole
              ``[layers, slots, ...]`` with ``layer``.
      q, k:   ``[rows, heads, key_dim]``: the step's packed tokens, both
              L2-normalised a head, ``q`` scaled.  ``q``'s type is the type
              of the kernel's operands and of the result.
      v:      ``[rows, heads, value_dim]``.
      g:      ``[rows, heads, key_dim]`` float32, <= 0: the log decay.
      beta:   ``[rows, heads]`` float32, >= 0.
      starts: ``[slots]`` int32, non-decreasing: the row of each slot's
              first token.
      q_lens: ``[slots]`` int32: valid tokens a slot (at most ``chunk``),
              0 = untouched.
      fresh:  ``[slots]`` bool: the slot's entering state counts as zero.
      chunk:  the most tokens a slot may hold (static).  1: a decode step,
              ``rows == slots`` and row ``b`` is slot ``b``'s.
      layer:  int32 scalar (may be traced), with the whole state.

    Returns ``(o [rows, heads, value_dim] in q's type, the state)``; a row
    that holds no token reads zero."""
    if (state.ndim == 5) != (layer is not None):
        raise ValueError("a whole state [layers, ...] is updated at `layer`;"
                         " one layer's state takes none")
    R, B = q.shape[0], q_lens.shape[0]
    if chunk == 1 and R != B:
        raise ValueError(f"a decode step (chunk 1) has one row a slot: "
                         f"{R} rows, {B} slots")
    if R < chunk:
        raise ValueError(f"{R} rows hold no chunk of {chunk} tokens")
    if chunk == 1:
        starts = jnp.arange(B, dtype=jnp.int32)
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        why = kda_geometry_error(q.shape[1], q.shape[2], v.shape[2])
        if why:
            raise ValueError(f"ragged_kda_update: {why}")
    if on_tpu or flags.flag("paged_attention_interpret"):
        return _pallas_ragged_kda_update(state, q, k, v, g, beta, starts,
                                         q_lens, fresh, int(chunk), layer,
                                         interpret=not on_tpu)
    one = state if layer is None else jax.lax.dynamic_index_in_dim(
        state, layer, axis=0, keepdims=False)
    o, new = _reference_ragged_kda_update(
        one, q, k, v, g, beta, starts.astype(jnp.int32),
        q_lens.astype(jnp.int32), fresh, int(chunk))
    if layer is not None:
        new = jax.lax.dynamic_update_index_in_dim(state, new, layer, axis=0)
    return o, new


def kda_recurrence(state, q, k, v, g, beta):
    """The bare recurrence over ONE slot's ``T`` tokens by ``lax.scan``, in
    float32: ``state [H, K, V]``, ``q``/``k``/``g`` ``[T, H, K]``, ``v [T,
    H, V]``, ``beta [T, H]`` -> ``(o [T, H, V], state)``.  What the kernel
    and its oracle are held against."""
    f32 = jnp.float32

    def step(s, t):
        qt, kt, vt, gt, bt = t
        s = s * jnp.exp(gt)[..., None]
        u = vt - jnp.einsum("hkv,hk->hv", s, kt, precision="highest")
        s = s + (bt[:, None] * kt)[..., None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision="highest")

    state, o = jax.lax.scan(step, state.astype(f32), tuple(
        a.astype(f32) for a in (q, k, v, g, beta)))
    return o, state
