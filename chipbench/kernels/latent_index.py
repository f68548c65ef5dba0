"""Operations and bytes of a learned index's work in ONE layer of a step
(``paddle_tpu.kernels.latent_index``): scoring every cached and own token
for every query token, and choosing each query token's best ``top_k``.

In the trace they are two ``tpu_custom_call``s by their kernels' ``name=``:
``latent_index_scores`` (first operand the int32 block table ``[slots,
pages_per_seq]``; the index queries ``[slots, tokens x heads, dim]``; ONE
float32 result ``[slots, tokens, keys]``) and ``latent_index_select``
(float32 scores ``[slots, tokens, positions]`` in, ONE bfloat16 result of
the same shape out).  Matched by name AND shapes; ``match`` says which of
the two a call is (``kind``).  The plain XLA operations between them (the
step's own rows' scores, the causal masks, the padding) carry no name in
the profiler's events and are not found: their time is left out.

The work depends on values, not shapes: each slot's query length and the
context before it.  The driver logs them per step (``rows``); ``cost`` takes
one step's rows and prices scoring AND choosing together, at the least any
form does.
"""

import re

NAME = "latent_index"
_SCORES = re.compile(r"^latent_index_scores(\.\d+)?$")
_SELECT = re.compile(r"^latent_index_select(\.\d+)?$")


def match(op):
    """Shapes of the call if ``op`` is one of the two kernels, else None."""
    if len(op.out_shapes) != 1 or not op.operand_shapes:
        return None
    (dt, out), = op.out_shapes
    if len(out) != 3:
        return None
    first_dt, first = op.operand_shapes[0]
    if _SCORES.match(op.name):
        if dt != "f32" or first_dt != "s32" or len(first) != 2 \
                or first[0] != out[0]:
            return None
        # the index queries: [slots, tokens x heads, dim], not float32
        wide = [s for d, s in op.operand_shapes
                if d != "f32" and d != "s32" and len(s) == 3
                and s[0] == out[0] and s[1] % out[1] == 0]
        if not wide:
            return None
        return {"kind": "scores", "slots": out[0], "tokens": out[1],
                "heads": wide[0][1] // out[1], "dim": wide[0][2]}
    if _SELECT.match(op.name):
        scores = [s for d, s in op.operand_shapes if d == "f32" and s == out]
        if dt != "bf16" or not scores:
            return None
        return {"kind": "select", "slots": out[0], "tokens": out[1],
                "positions": out[2]}
    return None


def cost(rows, heads: int, dim: int, top_k: int, dtype_bytes: int = 2):
    """(flops, bytes) one layer's scoring and choosing need for ``rows`` =
    [(q_len, context_len)], context counted BEFORE this step's tokens: a
    query token scores the context and the step's tokens up to itself with
    every index head, ``pairs x heads x dim x 2`` operations; each working
    slot's index keys are read ONCE (``dim x dtype_bytes`` a token: 256 B),
    the index queries and their float32 weights are read, and ``top_k``
    int32 positions a query token come out: the least any form does, so a
    later fusion of the two reads higher and never over 100 %."""
    flops = nbytes = 0.0
    for q, ctx in rows:
        if q <= 0:
            continue
        pairs = q * ctx + q * (q + 1) / 2.0
        flops += 2.0 * pairs * heads * dim
        nbytes += (ctx + q) * dim * dtype_bytes \
            + q * heads * (dim * dtype_bytes + 4) + q * top_k * 4
    return flops, nbytes
