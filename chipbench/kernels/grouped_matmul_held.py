"""Operations and bytes of one call of the grouped matmul kernel where the
chip holds a SHARE of the experts (``gmm(..., live_tiles=)``).

In the trace it is the ``tpu_custom_call`` with the operands (int32 tile ->
expert table ``[M / bm]``, int32 live-tile count ``[1]``, rows ``[M, K]``,
expert bank ``[E, K, N]``) and the result ``[M, N]``.  ``M`` rows are laid
out for every (token, choice) entry of the step, but only the entries that
fell on the ``E`` held experts are real and only their tiles are
multiplied, so the rows are NEVER priced from ``M``: the caller hands in
how many entries fell on held experts (the program's own count, registry
series ``serving.moe_held_rows``).
"""

NAME = "grouped_matmul_held"


def match(op):
    if len(op.out_shapes) != 1 or len(op.operand_shapes) < 4:
        return None
    (tdt, tiles), (ldt, live), (_, lhs), (wdt, bank) = op.operand_shapes[:4]
    (_, out), = op.out_shapes
    if tdt != "s32" or len(tiles) != 1 or ldt != "s32" or live != (1,) \
            or len(lhs) != 2 or len(bank) != 3 or len(out) != 2 \
            or lhs[1] != bank[1] or out != (lhs[0], bank[2]):
        return None
    m, k = lhs
    e, _, n = bank
    return {"rows_laid_out": m, "k": k, "n": n, "experts": e,
            "block_m": m // tiles[0], "dtype": wdt}


def cost(shapes, rows: float, dtype_bytes: int = 2):
    """(flops, bytes) for ``rows`` real entries: 2 * rows * K * N
    operations; every held expert's [K, N] weights are read once (each owns
    at least one tile), the real rows read and their results written."""
    k, n, e = shapes["k"], shapes["n"], shapes["experts"]
    return 2.0 * rows * k * n, float(dtype_bytes) * (e * k * n + rows * k
                                                     + rows * n)
