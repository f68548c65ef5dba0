"""Operations and bytes of one call of the state-space scan kernel
(``paddle_tpu.kernels.ssd.ragged_ssd_update``): every slot with work reads
its float32 recurrent state, folds its tokens in and writes the state back
where it lay.

In the trace it is the ``tpu_custom_call`` whose HLO instruction carries the
kernel's ``name=`` (``ragged_ssd_update``) and whose SECOND result is the
float32 state ``[layers, slots, heads, head_dim, state]`` (or one layer's,
without the first axis), aliased to an operand of the same shape; the first
result is ``y [slots, heads, T, head_dim]``.  Matched by that name AND
those shapes.

The work of a call depends on values, not shapes: each slot's query length.
The driver logs them per step (``rows``); ``cost`` takes one step's rows.
"""

import re

NAME = "ssd_update"
_NAME = re.compile(r"^ragged_ssd_update(\.\d+)?$")


def match(op):
    """Shapes of the call if ``op`` is this kernel, else None."""
    if not _NAME.match(op.name) or len(op.out_shapes) != 2:
        return None
    (dt, y), (sdt, state) = op.out_shapes
    if sdt != "f32" or len(state) not in (4, 5) or len(y) != 4 \
            or (sdt, state) not in op.operand_shapes:
        return None
    slots, heads, head_dim, width = state[-4:]
    if y[0] != slots or y[1] != heads or y[3] != head_dim:
        return None
    return {"slots": slots, "heads": heads, "head_dim": head_dim,
            "state": width, "q_rows": y[2], "dtype": dt}


def cost(rows, heads: int, head_dim: int, state: int, groups: int,
         dtype_bytes: int = 2):
    """(flops, bytes) one layer's call needs for ``rows`` = [(q_len,
    context_len)].  A slot with ``q_len`` > 0 reads and writes its state,
    ``2 x heads x head_dim x state x 4`` bytes (8,388,608 at 32 x 128 x
    256), reads its tokens' ``x`` (``heads x head_dim``), ``B`` and ``C``
    (``groups x state`` each) and ``dt`` (``heads`` float32) and writes
    their ``y``; a slot without work moves nothing.  The operations are the
    RECURRENCE's own, the least any form does: a token a head decays the
    state, adds ``dt x B^T`` and reads ``S C``, ``5 x head_dim x state``;
    a chunk form that multiplies more reads lower."""
    flops = nbytes = 0.0
    for q, _ctx in rows:
        if q <= 0:
            continue
        flops += 5.0 * q * heads * head_dim * state
        nbytes += 2.0 * heads * head_dim * state * 4 \
            + q * ((2 * heads * head_dim + 2 * groups * state) * dtype_bytes
                   + heads * 4)
    return flops, nbytes
