"""Operations and bytes of one call of the flash-attention kernels
(``paddle_tpu.kernels.flash_attention``): forward, dQ and dK/dV.

In the trace they are the ``tpu_custom_call``s whose first three operands
are Q ``[b, hq, s, d]``, K and V ``[b, hkv, s, d]`` (the kernel's layout).
Forward has exactly those three and returns (O, log-sum-exp); the dQ kernel
returns one ``[b, hq, s, d]``; the dK/dV kernel returns a pair of them (per
query head, summed over the group afterwards).

Counted per call, causal (half the square): forward 2 matmuls (QK^T, PV);
dQ 3 (QK^T again, dO V^T, dS K); dK/dV 4 (QK^T again, dO V^T, P^T dO,
dS^T Q).  Recomputation by ``remat`` is a call like any other: it is in the
trace, so it is in both the time and the operations.
"""

NAME = "flash_attention"
MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def match(op):
    if len(op.operand_shapes) < 3 or not op.out_shapes:
        return None
    (_, q), (_, k), (_, v) = op.operand_shapes[:3]
    if len(q) != 4 or len(k) != 4 or k != v or q[0] != k[0] \
            or q[3] != k[3] or q[1] % k[1]:
        return None
    outs = [dims for _, dims in op.out_shapes]
    if len(op.operand_shapes) == 3 and len(outs) == 2 and outs[0] == q:
        kind = "fwd"
    elif len(outs) == 1 and outs[0] == q:
        kind = "dq"
    elif len(outs) == 2 and outs[0] == outs[1] and outs[0][2:] == q[2:]:
        kind = "dkv"
    else:
        return None
    return {"kind": kind, "b": q[0], "hq": q[1], "hkv": k[1], "sq": q[2],
            "sk": k[2], "d": q[3]}


def cost(shapes, dtype_bytes: int = 2, causal: bool = True):
    b, hq, hkv = shapes["b"], shapes["hq"], shapes["hkv"]
    sq, sk, d = shapes["sq"], shapes["sk"], shapes["d"]
    square = sq * sk / 2.0 if causal else float(sq * sk)
    flops = MATMULS[shapes["kind"]] * 2.0 * b * hq * square * d
    q_like, kv_like = b * hq * sq * d, b * hkv * sk * d
    tensors = {"fwd": 2 * q_like + 2 * kv_like,            # Q, O; K, V
               "dq": 4 * q_like + 2 * kv_like,             # Q, O, dO, dQ
               "dkv": 3 * q_like + 2 * kv_like + 2 * q_like}[shapes["kind"]]
    return flops, float(dtype_bytes) * tensors
