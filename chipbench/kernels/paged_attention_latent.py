"""Operations and bytes of one call of the LATENT paged-attention kernel
(``paddle_tpu.kernels.paged_attention.ragged_paged_attention_latent``):
``heads`` query heads over ONE row ``[c | k_r]`` (``rank`` + ``rope``
numbers) a cached token, the value being the first ``rank`` of the key.

In the trace it is the ``tpu_custom_call`` whose HLO instruction carries the
kernel's ``name=`` (``ragged_paged_attention_latent``), whose first operand
is the int32 block table ``[slots, pages_per_seq]`` and whose ONE result is
``[slots, T x heads, rank]`` (no log-sum-exp: the per-head kernel's matcher,
``kernels/paged_attention.py``, wants two results and does not see it).
Matched by that name AND those shapes.

The work of a call depends on values, not shapes: each slot's query length
and the context it attends to.  The driver logs them per step (``rows``);
``cost`` takes one step's rows.
"""

import re

NAME = "paged_attention_latent"
_NAME = re.compile(r"^ragged_paged_attention_latent(\.\d+)?$")


def match(op):
    """Shapes of the call if ``op`` is this kernel, else None."""
    if not _NAME.match(op.name) or len(op.out_shapes) != 1 \
            or not op.operand_shapes:
        return None
    (dt, out), = op.out_shapes
    table_dt, table = op.operand_shapes[0]
    if table_dt != "s32" or len(table) != 2 or len(out) != 3 \
            or table[0] != out[0]:
        return None
    # the operands after the scalars: q_c [slots, rows, rank], then the
    # query's rotary part against the lower lanes [slots, rows, 2 x rope]
    wide = [s for d, s in op.operand_shapes
            if d == dt and len(s) == 3 and s[:2] == out[:2]]
    if len(wide) < 2 or wide[0] != out:
        return None
    return {"slots": out[0], "q_rows": out[1], "rank": out[2],
            "rope": wide[1][2] // 2, "dtype": dt}


def cost(rows, heads: int, rank: int, rope: int, dtype_bytes: int = 2):
    """(flops, bytes) one layer's call needs for ``rows`` = [(q_len,
    context_len)], context counted BEFORE this step's tokens.  Each query
    token attends to the context plus the step's tokens up to itself, with
    every head: ``rows x keys x ((rank + rope) + rank) x 2`` operations
    (scores over the whole row, values over its first ``rank``).  A cached
    token's row is read ONCE for key and value, ``(rank + rope) x
    dtype_bytes`` (1,152 B at 512 + 64 in bf16); the query rows
    (``rank + rope`` wide, absorbed) are read and the output rows
    (``rank`` wide) written once."""
    flops = nbytes = 0.0
    for q, ctx in rows:
        if q <= 0:
            continue
        attended = q * ctx + q * (q + 1) / 2.0          # causal inside q
        flops += 2.0 * heads * attended * ((rank + rope) + rank)
        nbytes += (ctx + q) * (rank + rope) * dtype_bytes \
            + q * heads * ((rank + rope) + rank) * dtype_bytes
    return flops, nbytes
