"""Operations and bytes of one call of the gated-delta-rule kernel
(``paddle_tpu.kernels.kda.ragged_kda_update``): every slot with work reads
its float32 recurrent state, folds its tokens in and writes the state back
where it lay.

In the trace it is the ``tpu_custom_call`` whose HLO instruction carries the
kernel's ``name=`` (``ragged_kda_update``) and whose LAST result is the
float32 state ``[layers, slots, heads, key_dim, value_dim]`` (or one
layer's, without the first axis), aliased to an operand of the same shape;
the first result is a decode slot's row ``[slots, heads, value_dim]`` and,
in a step program that holds chunks, the second the chunks' rows ``[slots,
chunk, heads, value_dim]``.  Matched by that name AND those shapes.

The work of a call depends on values, not shapes: each slot's query length.
The driver logs them per step (``rows``); ``cost`` takes one step's rows.
"""

import re

NAME = "kda_update"
_NAME = re.compile(r"^ragged_kda_update(\.\d+)?$")


def match(op):
    """Shapes of the call if ``op`` is this kernel, else None."""
    if not _NAME.match(op.name) or len(op.out_shapes) not in (2, 3):
        return None
    (dt, one), *chunks, (sdt, state) = op.out_shapes
    if sdt != "f32" or len(state) not in (4, 5) or len(one) != 3 \
            or (sdt, state) not in op.operand_shapes:
        return None
    slots, heads, key_dim, value_dim = state[-4:]
    if one != (slots, heads, value_dim):
        return None
    chunk = 1
    if chunks:
        (_, rows), = chunks
        if len(rows) != 4 or (rows[0],) + tuple(rows[2:]) != tuple(one):
            return None
        chunk = rows[1]
    return {"slots": slots, "heads": heads, "key_dim": key_dim,
            "value_dim": value_dim, "chunk": chunk, "dtype": dt}


def cost(rows, heads: int, key_dim: int, value_dim: int,
         dtype_bytes: int = 2):
    """(flops, bytes) one layer's call needs for ``rows`` = [(q_len,
    context_len)].  A slot with ``q_len`` > 0 reads and writes its state,
    ``2 x heads x key_dim x value_dim x 4`` bytes (8,388,608 at 64 x 128 x
    128), reads its tokens' ``q``, ``k`` (``heads x key_dim`` each), ``v``
    (``heads x value_dim``) and float32 log decay (``heads x key_dim``;
    ``beta`` comes folded into ``k`` and ``v``) and writes their ``o``; a
    slot without work moves nothing.  The operations are the RECURRENCE's
    own, the least any form does: a token a head decays the state, reads
    ``S'^T k``, adds the rank-one update and reads ``S^T q``, ``7 x key_dim
    x value_dim``; a chunk form that multiplies more reads lower."""
    flops = nbytes = 0.0
    for q, _ctx in rows:
        if q <= 0:
            continue
        flops += 7.0 * q * heads * key_dim * value_dim
        nbytes += 2.0 * heads * key_dim * value_dim * 4 \
            + q * heads * ((2 * key_dim + 2 * value_dim) * dtype_bytes
                           + key_dim * 4)
    return flops, nbytes
