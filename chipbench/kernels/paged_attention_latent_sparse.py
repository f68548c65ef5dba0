"""Operations and bytes of one call of the SPARSE latent paged-attention
kernel (``paddle_tpu.kernels.paged_attention.ragged_paged_attention_latent_
sparse``): ``heads`` query heads over ONE row ``[c | k_r]`` (``rank`` +
``rope`` numbers) a cached token, the softmax of a query token running over
the keys a learned index chose for it alone.

In the trace it is the ``tpu_custom_call`` whose HLO instruction carries the
kernel's ``name=`` (``ragged_paged_attention_latent_sparse``), whose first
operand is the int32 block table ``[slots, pages_per_seq]`` and whose ONE
result is ``[slots, T x heads, rank]``.  Matched by that name AND those
shapes: the dense latent call's matcher (``kernels/
paged_attention_latent.py``) anchors its name at both ends and does not see
it, nor this one that.

The cost prices the SELECTED keys alone, whatever the call multiplies: the
form built walks every page of a slot and masks (PERF.md section 6, PR 39),
so it reads low, and honestly so.
"""

import re

NAME = "paged_attention_latent_sparse"
_NAME = re.compile(r"^ragged_paged_attention_latent_sparse(\.\d+)?$")


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
    wide = [s for d, s in op.operand_shapes
            if d == dt and len(s) == 3 and s[:2] == out[:2]]
    if len(wide) < 2 or wide[0] != out:
        return None
    return {"slots": out[0], "q_rows": out[1], "rank": out[2],
            "rope": wide[1][2] // 2, "dtype": dt}


def selected(rows, top_k: int) -> float:
    """Keys chosen in one layer for ``rows`` = [(q_len, context_len)]:
    ``min(position + 1, top_k)`` a query token."""
    n = 0.0
    for q, ctx in rows:
        low = min(max(top_k - ctx, 0), q)     # positions that choose all
        n += low * ctx + low * (low + 1) / 2.0 + (q - low) * top_k
    return n


def cost(rows, heads: int, rank: int, rope: int, top_k: int,
         dtype_bytes: int = 2):
    """(flops, bytes) one layer's call needs for ``rows``: every head of a
    query token meets its selected keys, ``selected x heads x ((rank +
    rope) + rank) x 2`` operations; a selected row is read once a query
    token, ``(rank + rope) x dtype_bytes`` (1,152 B at 512 + 64 in bf16);
    the query rows (``rank + rope`` wide, absorbed) are read and the output
    rows (``rank`` wide) written once."""
    q_tokens = sum(q for q, _ in rows if q > 0)
    chosen = selected([r for r in rows if r[0] > 0], top_k)
    flops = 2.0 * heads * chosen * ((rank + rope) + rank)
    nbytes = chosen * (rank + rope) * dtype_bytes \
        + q_tokens * heads * ((rank + rope) + rank) * dtype_bytes
    return flops, nbytes
