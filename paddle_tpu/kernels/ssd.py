"""The state-space scan of the serving step (Mamba-2's "SSD" recurrence),
ragged over slots: prefill chunks and decode tokens in one call, the
recurrent state updated in place.

One head keeps a float32 state ``S`` (``[head_dim, state]``) a slot.  For
the slot's next tokens ``t = 1..q`` with inputs ``x_t`` (``head_dim``),
``B_t`` and ``C_t`` (``state`` wide, shared by the heads of a group), the
step size ``dt_t > 0`` and the head's ``A < 0``, ``D``:

    a_t = exp(dt_t A)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T              (the recurrence)
    y_t = S_t C_t + D x_t

The same numbers for the ``q`` tokens at once, entering with ``S_0``
(``l_t = sum_{s<=t} dt_s A``, so every exponent below is <= 0):

    y_t = exp(l_t) S_0 C_t + sum_{s<=t} exp(l_t - l_s) dt_s (C_t . B_s) x_s
          + D x_t
    S_q = exp(l_q) S_0 + sum_s exp(l_q - l_s) dt_s x_s B_s^T

which is three matrix products over the chunk.  The kernel computes this
form for a slot with more than one token, in float32 but for the operands
of the products, which meet in ``x``'s type (the state is READ in that type
for ``S_0 C_t``; what is kept and added to stays float32); a slot with ONE
token (a decode row) runs the recurrence itself, elementwise in float32:
the state never passes through the MXU (1.67 ms a call of 128 decode slots
at the published sizes against 1.73 in the chunk form, 1.31 at the chip's
bandwidth: v5e, PR 34).  A slot with ``q_lens[b] == 0`` (finished, frozen,
no request) is not computed and its state is neither read nor written.

- One program a (block of heads, slot), the slots innermost.  The state is
  the pipeline's own block ``[heads of the block, head_dim, state]``, in and
  out, and the output ALIASES the input (``input_output_aliases``): the
  call writes a slot's new state where the old one lay, and no second copy
  of the state exists.  An idle slot's program names the block of the live
  slot before it (``src``; the first live one where none precedes): the
  pipeline neither fetches nor writes back a block whose index did not
  change, so an idle slot moves no byte of state.  With no live slot at all
  every program names block 0 and copies it through.
- ``fresh[b]`` (the slot's first chunk): ``S_0`` is read as zero, whatever
  the last request left there.
- ``layer`` (an int32 device scalar) with the whole ``[layers, ...]`` state:
  the layer's blocks are indexed where they lie (the engine's layer scan
  carries the whole state and this call updates one layer of it).

``FLAGS_paged_attention_interpret`` (the serving step's kernels interpreted
on the CPU: tests and the benchmark's rehearsal) runs this kernel in
interpreter mode; otherwise the CPU takes the XLA oracle beside it, the
same chunk form over all slots at once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags
from . import paged_attention as _paged  # noqa: F401  (defines the flag)

NEG_INF = -1e30
_I0 = np.int32(0)  # index-map literal: bare 0 would be int64 under x64 mode
_SUBLANE = 8
# the state block of one program, in and out and two buffers each, stays
# under this (16 heads x 128 x 256 float32 = 2 MiB a buffer at the
# published Falcon-H1 sizes, one group's heads)
_STATE_BLOCK_BYTES = 2 << 20


def chunk_decays(dt, A, q_lens):
    """``(dt, l)`` float32 ``[B, T, H]``: the step sizes with the places
    past ``q_lens[b]`` zeroed (a token that is not there neither decays nor
    adds), and ``l_t = sum_{s<=t} dt_s A``."""
    T = dt.shape[1]
    live = jnp.arange(T, dtype=jnp.int32)[None, :] < q_lens[:, None]
    dt = jnp.where(live[..., None], dt.astype(jnp.float32), 0.0)
    return dt, jnp.cumsum(dt * A.astype(jnp.float32), axis=1)


def _reference_ragged_ssd_update(state, x, Bm, Cm, dt, A, D, q_lens, fresh):
    """The XLA oracle: the chunk form over every slot and head at once.
    ``state`` is one layer's ``[B, H, P, N]``; a slot without work keeps
    its state (and reads ``y = 0``)."""
    f32 = jnp.float32
    B, T, H, P = x.shape
    G = Bm.shape[2]
    dt, l = chunk_decays(dt, A, q_lens)
    work = q_lens > 0
    s0 = jnp.where(fresh[:, None, None, None], 0.0, state)
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2)                      # [B, T, H, N]
    Ch = jnp.repeat(Cm, rep, axis=2)
    mxu = x.dtype
    tri = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    # [B, H, t, s]: exp(l_t - l_s) dt_s for s <= t
    lt = l.transpose(0, 2, 1)
    m = jnp.exp(jnp.where(tri, lt[..., :, None] - lt[..., None, :],
                          -jnp.inf)) * dt.transpose(0, 2, 1)[..., None, :]
    g = jnp.einsum("bthn,bshn->bhts", Ch, Bh, preferred_element_type=f32)
    ys = jnp.einsum("bthn,bhpn->bthp", Ch, s0.astype(mxu),
                    preferred_element_type=f32) * jnp.exp(l)[..., None]
    yi = jnp.einsum("bhts,bshp->bthp", (g * m).astype(mxu), x,
                    preferred_element_type=f32)
    y = ys + yi + D.astype(f32)[None, None, :, None] * x.astype(f32)
    last = l[:, -1]                                       # [B, H]
    w = jnp.exp(last[:, None] - l) * dt                   # [B, T, H]
    ds = jnp.einsum("bthp,bthn->bhpn",
                    (x.astype(f32) * w[..., None]).astype(mxu), Bh,
                    preferred_element_type=f32)
    new = jnp.exp(last)[..., None, None] * s0 + ds
    y = jnp.where(work[:, None, None, None], y, 0.0).astype(x.dtype)
    return y, jnp.where(work[:, None, None, None], new, state)


def _heads_per_block(heads_per_group, head_dim, state):
    """Heads of one program: the largest divisor of a group's heads whose
    float32 state block stays under ``_STATE_BLOCK_BYTES``."""
    per = head_dim * state * 4
    return next(n for n in range(heads_per_group, 0, -1)
                if heads_per_group % n == 0
                and (n * per <= _STATE_BLOCK_BYTES or n == 1))


def _ssd_kernel(*refs, hb, tp, layered):
    """One (block of ``hb`` heads, slot): see the module's docstring.
    Scalar prefetch: q_lens, src, fresh, whether any slot is live, D, each
    (slot, head)'s ``l_q``, ``exp(l_q)`` and first step size (and the
    layer); blocks: l and dt ``[hb, tp]``, x ``[hb, tp, P]``, B and C
    ``[tp, N]``, the state ``[hb, P, N]``; out: y ``[hb, tp, P]``, the
    state."""
    from jax.experimental import pallas as pl

    n_scalar = 9 if layered else 8
    ql_ref, _src, fresh_ref, any_ref, d_ref, lq_ref, aq_ref, dt0_ref = \
        refs[:8]
    heads = d_ref.shape[0]
    l_ref, dt_ref, x_ref, b_ref, c_ref, s_ref, y_ref, so_ref = \
        refs[n_scalar:]
    f32, i32 = jnp.float32, np.int32
    j = pl.program_id(0)
    b = pl.program_id(1)
    ql = ql_ref[b]
    mxu = x_ref.dtype
    nt = (((1,), (1,)), ((), ()))          # a @ b^T
    tn = (((0,), (0,)), ((), ()))          # a^T @ b

    @pl.when(ql == 0)
    def _idle():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

        @pl.when(any_ref[0] == 0)
        def _through():                    # no slot is live: block 0 as is
            so_ref[...] = s_ref[...]

    def heads_of_the_block(head):
        # static bounds: a while_loop keeps the counter int32 under x64
        jax.lax.while_loop(lambda h: h < i32(hb),
                           lambda h: (head(h), h + i32(1))[1], _I0)

    @pl.when(ql == 1)
    def _one_token():
        """A decode slot: the recurrence itself, elementwise in float32.
        The state never passes through the MXU (where loading it as an
        operand, not the one row, is the cost); ``x`` is turned into a
        column and ``y`` back into a row by the diagonal of a broadcast."""
        P = x_ref.shape[2]
        r_ = jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
        c_ = jax.lax.broadcasted_iota(jnp.int32, (P, P), 1)
        eye = r_ == c_
        first = jax.lax.broadcasted_iota(jnp.int32, (tp, 1), 0) == 0
        b_row = b_ref[0:1, :].astype(f32)                    # [1, N]
        c_row = c_ref[0:1, :].astype(f32)
        keep = fresh_ref[b] == 0

        def head(h):
            at = b * i32(heads) + j * i32(hb) + h
            s0 = s_ref[h]
            s0 = jnp.where(keep, s0, jnp.zeros_like(s0))
            x_row = x_ref[h][0:1, :].astype(f32)             # [1, P]
            x_col = jnp.sum(jnp.where(eye, jnp.broadcast_to(x_row, (P, P)),
                                      f32(0)), axis=1, keepdims=True)
            new = aq_ref[at] * s0 + (dt0_ref[at] * x_col) * b_row
            so_ref[h] = new
            y_col = jnp.sum(new * c_row, axis=1, keepdims=True) \
                + d_ref[j * i32(hb) + h] * x_col             # [P, 1]
            y_row = jnp.sum(jnp.where(eye, jnp.broadcast_to(y_col, (P, P)),
                                      f32(0)), axis=0, keepdims=True)
            y_ref[h] = jnp.where(first, jnp.broadcast_to(y_row, (tp, P)),
                                 f32(0)).astype(y_ref.dtype)

        heads_of_the_block(head)

    @pl.when(ql > 1)
    def _work():
        bm = b_ref[...]
        cm = c_ref[...]
        g = jax.lax.dot_general(cm, bm, nt, preferred_element_type=f32)
        row = jax.lax.broadcasted_iota(jnp.int32, (tp, tp), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (tp, tp), 1)
        tri, eye = col <= row, col == row
        keep = fresh_ref[b] == 0

        def column(r):
            """``[1, tp]`` -> ``[tp, 1]``: the diagonal of its broadcast."""
            return jnp.sum(jnp.where(eye, jnp.broadcast_to(r, (tp, tp)), f32(0)),
                           axis=1, keepdims=True)

        def head(h):
            l_row = l_ref[pl.ds(h, 1), :]
            dt_row = dt_ref[pl.ds(h, 1), :]
            l_col, dt_col = column(l_row), column(dt_row)
            at = j * i32(hb) + h                  # the head's number
            l_q = lq_ref[b * i32(heads) + at]     # scalars: a [1, 1] vector
            #                                       does not broadcast both ways
            m = jnp.exp(jnp.where(tri, l_col - l_row, f32(NEG_INF))) * dt_row
            s0 = s_ref[h]
            s0 = jnp.where(keep, s0, jnp.zeros_like(s0))
            xh = x_ref[h]
            xf = xh.astype(f32)
            ys = jax.lax.dot_general(cm, s0.astype(mxu), nt,
                                     preferred_element_type=f32)
            yi = jax.lax.dot_general((g * m).astype(mxu), xh,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=f32)
            y = ys * jnp.exp(l_col) + yi + d_ref[at] * xf
            y_ref[h] = y.astype(y_ref.dtype)
            w_col = jnp.exp(l_q - l_col) * dt_col
            ds = jax.lax.dot_general((xf * w_col).astype(mxu), bm, tn,
                                     preferred_element_type=f32)
            so_ref[h] = aq_ref[b * i32(heads) + at] * s0 + ds

        heads_of_the_block(head)


def _pallas_ragged_ssd_update(state, x, Bm, Cm, dt, A, D, q_lens, fresh,
                              layer, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    layered = layer is not None
    hb = _heads_per_block(H // G, P, N)
    blocks_per_group = (H // G) // hb
    tp = -(-max(T, _SUBLANE) // _SUBLANE) * _SUBLANE
    i32 = jnp.int32
    ql = q_lens.astype(i32)
    dt, l = chunk_decays(dt, A, ql)

    def tokens_last(a, mode):      # [B, T, H] -> [B, H, tp]
        a = a.transpose(0, 2, 1)
        return jnp.pad(a, ((0, 0), (0, 0), (0, tp - T)), mode=mode) \
            if tp != T else a

    l_q, dt_first = l[:, -1].reshape(B * H), dt[:, 0].reshape(B * H)
    # the padding repeats l's last value (no decay) and dt is zero there
    l, dt = tokens_last(l, "edge"), tokens_last(dt, "constant")

    def rows_second(a):            # [B, T, n, w] -> [B, n, tp, w]
        a = a.transpose(0, 2, 1, 3)
        return jnp.pad(a, ((0, 0), (0, 0), (0, tp - T), (0, 0))) \
            if tp != T else a

    xs, bs, cs = rows_second(x), rows_second(Bm), rows_second(Cm)
    # an idle slot names the live slot before it, else the first live one,
    # else (no live slot) block 0
    idx = jnp.arange(B, dtype=i32)
    live = ql > 0
    before = jax.lax.cummax(jnp.where(live, idx, -1))
    first = jnp.argmax(live).astype(i32)
    src = jnp.where(before >= 0, before, first).astype(i32)
    scalars = [ql, src, fresh.astype(i32),
               jnp.any(live).astype(i32).reshape(1), D.astype(jnp.float32),
               l_q, jnp.exp(l_q), dt_first]
    if layered:
        scalars.append(jnp.asarray(layer, i32).reshape(1))

    def state_index(j, b, _ql, src_ref, *rest):
        at = (src_ref[b], j, _I0, _I0)
        return (rest[-1][0],) + at if layered else at

    def group_index(j, b, *_):
        g = j if blocks_per_group == 1 else \
            jax.lax.div(j, np.int32(blocks_per_group))
        return b, g, _I0, _I0

    state_spec = pl.BlockSpec(
        ((None,) if layered else ()) + (None, hb, P, N), state_index)
    steps_spec = pl.BlockSpec((None, hb, tp), lambda j, b, *_: (b, j, _I0))
    rows_spec = pl.BlockSpec((None, hb, tp, P),
                             lambda j, b, *_: (b, j, _I0, _I0))
    group_spec = pl.BlockSpec((None, None, tp, N), group_index)
    operands = [l, dt, xs, bs, cs, state]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(H // hb, B),
        in_specs=[steps_spec, steps_spec, rows_spec, group_spec, group_spec,
                  state_spec],
        out_specs=[rows_spec, state_spec],
    )
    # the pipeline's two buffers of the state block in and out, and room
    # for a head's temporaries
    need = 4 * hb * P * N * 4 + (8 << 20)
    y, new = pl.pallas_call(
        functools.partial(_ssd_kernel, hb=hb, tp=tp, layered=layered),
        name="ragged_ssd_update",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, tp, P), x.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={len(scalars) + len(operands) - 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(max(need, 32 << 20))),
        interpret=interpret,
    )(*scalars, *operands)
    return y[:, :, :T].transpose(0, 2, 1, 3), new


def ragged_ssd_update(state, x, Bm, Cm, dt, A, D, q_lens, fresh, *,
                      layer=None):
    """The slots' next tokens through the scan, the state updated in place.

    Args:
      state:  ``[slots, heads, head_dim, state]`` float32, or the whole
              ``[layers, slots, ...]`` with ``layer``.
      x:      ``[slots, T, heads, head_dim]``: the step's inputs (T = 1 for
              pure decode, the chunk length for a mixed step).
      Bm, Cm: ``[slots, T, groups, state]``; head ``h`` reads group
              ``h // (heads / groups)``.
      dt:     ``[slots, T, heads]``, > 0 (after softplus).
      A, D:   ``[heads]``; ``A`` < 0.
      q_lens: ``[slots]`` int32: valid tokens a slot, 0 = untouched.
      fresh:  ``[slots]`` bool: the slot's entering state counts as zero.
      layer:  int32 scalar (may be traced), with the whole state.

    Returns ``(y [slots, T, heads, head_dim] in x's type, the state)``.
    Rows of ``y`` past ``q_lens[b]`` are don't-care (zero for a slot
    without work)."""
    if (state.ndim == 5) != (layer is not None):
        raise ValueError("a whole state [layers, ...] is updated at `layer`;"
                         " one layer's state takes none")
    H, G = x.shape[2], Bm.shape[2]
    if H % G:
        raise ValueError(f"heads ({H}) must be a multiple of groups ({G})")
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu or flags.flag("paged_attention_interpret"):
        return _pallas_ragged_ssd_update(state, x, Bm, Cm, dt, A, D, q_lens,
                                         fresh, layer, interpret=not on_tpu)
    one = state if layer is None else jax.lax.dynamic_index_in_dim(
        state, layer, axis=0, keepdims=False)
    y, new = _reference_ragged_ssd_update(one, x, Bm, Cm, dt, A, D, q_lens,
                                          fresh)
    if layer is not None:
        new = jax.lax.dynamic_update_index_in_dim(state, new, layer, axis=0)
    return y, new


def ssd_recurrence(state, x, Bm, Cm, dt, A, D):
    """The bare recurrence over ONE slot's ``T`` tokens by ``lax.scan``, in
    float32: ``state [H, P, N]``, ``x [T, H, P]``, ``Bm``/``Cm`` ``[T, G,
    N]``, ``dt [T, H]`` -> ``(y [T, H, P], state)``.  What the chunk form
    and the kernel are held against."""
    f32 = jnp.float32
    rep = x.shape[1] // Bm.shape[1]

    def step(s, t):
        xt, bt, ct, dtt = t
        bt, ct = jnp.repeat(bt, rep, axis=0), jnp.repeat(ct, rep, axis=0)
        s = jnp.exp(dtt * A)[:, None, None] * s \
            + dtt[:, None, None] * xt[:, :, None] * bt[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, ct,
                             precision="highest") + D[:, None] * xt

    state, y = jax.lax.scan(step, state.astype(f32), (
        x.astype(f32), Bm.astype(f32), Cm.astype(f32), dt.astype(f32)))
    return y, state
