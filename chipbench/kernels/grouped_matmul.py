"""Operations and bytes of one call of the grouped (ragged, expert-sorted)
matmul kernel (``paddle_tpu.kernels.grouped_matmul``, ``gmm``).

In the trace it is the ``tpu_custom_call`` with the operands (int32 tile ->
expert table ``[M / bm]``, rows ``[M, K]``, expert bank ``[E, K, N]``) and the
result ``[M, N]``.  ``M = ceil(F / bm) * bm + E * bm`` rows are laid out for
``F`` routed (token, choice) entries (``sorted_dispatch_plan``), so the rows
the algorithm needs are at most ``M - E * bm``: the padding is not counted.
"""

NAME = "grouped_matmul"


def match(op):
    if len(op.out_shapes) != 1 or len(op.operand_shapes) < 3:
        return None
    (tdt, tiles), (_, lhs), (wdt, bank) = op.operand_shapes[:3]
    (_, out), = op.out_shapes
    if tdt != "s32" or len(tiles) != 1 or len(lhs) != 2 or len(bank) != 3 \
            or len(out) != 2 or lhs[1] != bank[1] or out != (lhs[0], bank[2]):
        return None
    m, k = lhs
    e, _, n = bank
    bm = m // tiles[0]
    return {"rows_laid_out": m, "rows": max(m - e * bm, 0), "k": k, "n": n,
            "experts": e, "block_m": bm, "dtype": wdt}


def cost(shapes, dtype_bytes: int = 2):
    """(flops, bytes): 2 * rows * K * N operations; every expert's [K, N]
    weights are read once (each expert owns at least one tile), the rows
    read and the result written once."""
    r, k, n, e = shapes["rows"], shapes["k"], shapes["n"], shapes["experts"]
    return 2.0 * r * k * n, float(dtype_bytes) * (e * k * n + r * k + r * n)
