"""Operations and bytes of one call of the ragged paged-attention kernel
(``paddle_tpu.kernels.paged_attention.ragged_paged_attention``).

In the trace the kernel has no name of its own (``kernel_metadata={}``); it
is the ``tpu_custom_call`` whose first operand is the int32 block table
``[slots, pages_per_seq]`` and whose result is the pair (output
``[slots, kv_heads, T * group, d]``, its float32 log-sum-exp).

The work of a call depends on values, not shapes: each slot's query length
and the context it attends to.  The driver logs them per step (``rows``);
``cost`` takes one step's rows.
"""

NAME = "paged_attention"


def match(op):
    """Shapes of the call if ``op`` is this kernel, else None."""
    if len(op.out_shapes) != 2 or not op.operand_shapes:
        return None
    (dt, out), (ldt, lse) = op.out_shapes
    table_dt, table = op.operand_shapes[0]
    if table_dt != "s32" or len(table) != 2 or len(out) != 4 \
            or ldt != "f32" or lse[-1] != 1 or table[0] != out[0]:
        return None
    return {"slots": out[0], "kv_heads": out[1], "q_rows": out[2],
            "head_dim": out[3], "dtype": dt}


def cost(rows, q_heads: int, kv_heads: int, head_dim: int,
         dtype_bytes: int = 2, window: int | None = None,
         page_size: int = 1):
    """(flops, bytes) one layer's call needs for ``rows`` = [(q_len,
    context_len)], context counted BEFORE this step's tokens.  Each query
    token attends to the context plus the step's tokens up to itself; the
    kernel reads each attended K and V element once per KV head, reads Q
    and writes O once.  With ``window`` (a sliding-attention layer) a query
    token attends to at most ``window`` tokens ending at itself, and the K
    and V read are the pages of ``page_size`` tokens that hold what the
    row's first query token attends to and all that follows."""
    flops = nbytes = 0.0
    for q, ctx in rows:
        if q <= 0:
            continue
        if window is None:
            attended = q * ctx + q * (q + 1) / 2.0      # causal inside q
            kv_tokens = ctx + q
        else:
            # the first k query tokens still see everything before them
            k = min(q, max(0, window - ctx))
            attended = k * ctx + k * (k + 1) / 2.0 + (q - k) * float(window)
            first = max(0, ctx + 1 - window)            # of the first query
            kv_tokens = ctx + q - first // page_size * page_size
        flops += 4.0 * q_heads * head_dim * attended    # QK^T and PV
        nbytes += 2.0 * kv_heads * kv_tokens * head_dim * dtype_bytes \
            + 2.0 * q_heads * q * head_dim * dtype_bytes
    return flops, nbytes
