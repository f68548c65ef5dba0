"""Grouped (ragged) expert matmul — the MoE compute kernel.

Reference surface: the fused/cutlass grouped-GEMM MoE kernels under
paddle/phi/kernels/fusion/ (moe_gemm/, fused_moe_op.h) and their API
python/paddle/incubate/nn/functional/fused_moe.py — experts run one GEMM
over just their own tokens instead of a capacity-padded dense batch.

TPU-native design (megablocks-style, built for the MXU):

- Tokens are pre-sorted by expert id OUTSIDE the kernel (an XLA sort);
  each expert's rows live in a contiguous, tile-aligned span of the
  ``[M, K]`` operand, so every ``bm`` row-tile belongs to exactly ONE
  expert.  ``tile_groups[i]`` names that expert; it rides the scalar-
  prefetch channel (`pltpu.PrefetchScalarGridSpec`) so the index map can
  DMA the right expert's weight block — data-dependent weight selection
  with zero data-dependent control flow inside the kernel.
- ``gmm``: out[m] = lhs[m] @ rhs[group(m)] with an fp32 VMEM accumulator
  over k-steps.  ``tgmm`` (the weight-grad transpose) accumulates
  lhs^T @ rhs into out[group]: the m grid dim is innermost, so each
  expert's output block is visited in consecutive steps and flushed at
  the group boundary — the revisit pattern Mosaic requires.
- Expert FLOPs scale with the actual tokens-per-expert (plus <=1 tile of
  per-expert alignment padding), not with a capacity bound: the
  capacity-dispatch formulations pay ~capacity_factor extra FLOPs and
  drop overflow tokens; this path pays <=E*bm pad rows and drops nothing.
- Tile selection: explicit ``bn``/``bk`` arguments win, then a measured
  ``kernels.autotune`` cache entry for the exact (kind, shape, dtype),
  then the sweep flags (defaults only), then 512-with-divisibility.

``grouped_matmul`` wraps both in a ``custom_vjp`` (dlhs via gmm against
the transposed weights, drhs via tgmm), so the kernel trains.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags

flags.define_flag("grouped_matmul_interpret", False,
                  "Run the Pallas grouped-matmul kernels in interpreter "
                  "mode on CPU (tests).")
flags.define_flag("grouped_matmul_bn", 0,
                  "Default grouped-matmul output-column tile when the "
                  "caller does not pass one and no autotune cache entry "
                  "exists (0 = the 512-with-divisibility default). "
                  "Explicit bn arguments always take precedence.")
flags.define_flag("grouped_matmul_bk", 0,
                  "Default grouped-matmul contraction tile (0 = default); "
                  "explicit bk arguments always take precedence.")


def _mode(interpret=None):
    """'tpu' (compiled), 'interpret' (CPU tests) or None (XLA reference,
    off-TPU only: on a chip these kernels compile or raise)."""
    if jax.default_backend() == "tpu":
        if interpret:
            raise ValueError("grouped matmul: interpret mode is for CPU "
                             "tests; the backend is a TPU")
        return "tpu"
    if interpret is not None:
        return "interpret" if interpret else "tpu"
    if flags.flag("grouped_matmul_interpret"):
        return "interpret"
    return None


def _pick_block(dim: int, want: int) -> int:
    """Largest power-of-two tile <= want that divides dim (>=128 for the
    lane dim by construction: callers pad K/N to 128 multiples)."""
    b = want
    while b > 128 and dim % b:
        b //= 2
    if dim % b:
        raise ValueError(f"dim {dim} not divisible by a tile <= {want}")
    return b


def validate_tile_flags(*dims):
    """Fail fast when a FLAGS_grouped_matmul_bn/_bk sweep value cannot
    tile every operand dim the forward AND backward kernels will see (the
    backward swaps the output/contraction roles of H and I, so a flag
    that only fits the forward would error mid-backward, on TPU only).
    Called from ``grouped_matmul`` / the MoE FFN entry points; explicit
    bn/bk arguments bypass the flags entirely."""
    for name in ("grouped_matmul_bn", "grouped_matmul_bk"):
        want = flags.flag(name)
        if not want:
            continue
        for d in dims:
            try:
                _pick_block(d, want)
            except ValueError:
                raise ValueError(
                    f"FLAGS_{name}={want} cannot tile operand dim {d} "
                    f"(forward+backward dims {tuple(dims)}); pass explicit "
                    "bn/bk to override the flag, or unset it") from None


# ------------------------------------------------------ tile selection ---

def _resolve_tiles(kind, M, K, N, E, bm, dtype, bn, bk, mode):
    """(bn, bk) for a kernel call: explicit args > autotune cache (and
    on-chip measurement when tuning is enabled) > sweep flags > 512."""
    if bn is None or bk is None:
        from . import autotune
        key = autotune.make_key(f"grouped_matmul_{kind}", M=M, K=K, N=N,
                                E=E, bm=bm, dtype=jnp.dtype(dtype).name)
        tuned = autotune.lookup(key)
        if tuned is None and mode == "tpu" and autotune.enabled():
            tuned = _tune(kind, key, M, K, N, E, bm, dtype)
        dbn = flags.flag("grouped_matmul_bn") or 512
        dbk = flags.flag("grouped_matmul_bk") or 512
        if tuned is not None:
            dbn, dbk = int(tuned[0]), int(tuned[1])
        if bn is None:
            bn = dbn
        if bk is None:
            bk = dbk
    return _pick_block(N, bn), _pick_block(K, bk)


def _tune(kind, key, M, K, N, E, bm, dtype):
    """Measure candidate (bn, bk) tiles on the attached chip (outside the
    ongoing trace — each probe is its own jitted call on dummy operands,
    the autotune module's re-entrant dispatch contract)."""
    from . import autotune

    if jax.default_backend() != "tpu":
        return None   # cross-lowering on CPU: nothing to measure on
    cands = autotune.grouped_matmul_candidates(
        M, K, N, itemsize=jnp.dtype(dtype).itemsize, bm=bm,
        kind="tgmm" if kind == "tgmm" else "gmm")
    if not cands:
        return None
    tg = ((jnp.arange(M // bm) * E) // (M // bm)).astype(jnp.int32)
    lhs = jnp.ones((M, K), dtype)

    if kind == "tgmm":
        rhs = jnp.ones((M, N), dtype)

        def bench(cand):
            bn_, bk_ = cand
            f = jax.jit(lambda a, b: tgmm(a, b, tg, E, bm=bm, bn=bn_,
                                          bk=bk_))
            # compile outside the timer; blocking IS the measurement
            # jaxlint: disable=JL002 -- autotune timing harness runs at tuning time, not in the engine step
            f(lhs, rhs).block_until_ready()
            return lambda: f(lhs, rhs).block_until_ready()  # jaxlint: disable=JL002 -- autotune timing harness, see above
    else:
        trans = kind == "gmm_t"
        rhs = jnp.ones((E, N, K) if trans else (E, K, N), dtype)

        def bench(cand):
            bn_, bk_ = cand
            f = jax.jit(lambda a, b: gmm(a, b, tg, bm=bm, bn=bn_, bk=bk_,
                                         trans_rhs=trans))
            # jaxlint: disable=JL002 -- autotune timing harness runs at tuning time, not in the engine step
            f(lhs, rhs).block_until_ready()
            return lambda: f(lhs, rhs).block_until_ready()  # jaxlint: disable=JL002 -- autotune timing harness, see above

    return autotune.lookup_or_tune(key, cands, bench, None)


# ------------------------------------------------------------------ gmm ---

def _gmm_kernel(*refs, nk, trans_rhs, skips):
    from jax.experimental import pallas as pl

    # scalar prefetch first: tile_groups, then the live-tile count of a
    # call that is handed one
    groups_ref, *live_ref = refs[:-4]
    lhs_ref, rhs_ref, out_ref, acc_ref = refs[-4:]
    # read outside every pl.when: interpret mode has no rule for it inside
    i, kk = pl.program_id(0), pl.program_id(2)

    def tile():
        @pl.when(kk == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        lhs = lhs_ref[...]
        dims = (((1,), (1,)), ((), ())) if trans_rhs \
            else (((1,), (0,)), ((), ()))
        acc_ref[...] += jax.lax.dot_general(
            lhs, rhs_ref[...], dims, preferred_element_type=jnp.float32)

        @pl.when(kk == nk - 1)
        def _flush():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    if not skips:
        tile()
    else:
        # a dead row tile holds no expert's rows: nothing is multiplied and
        # (the index maps park on one block) nothing moves.  The count says
        # which they are where there is one, else the table
        pl.when(i < live_ref[0][0] if live_ref else groups_ref[i] >= 0)(tile)


def gmm(lhs, rhs, tile_groups, *, bm=512, bn=None, bk=None, trans_rhs=False,
        interpret=None, live_tiles=None, dead_in_table=False):
    """Grouped matmul: ``out[m, :] = lhs[m, :] @ rhs[tile_groups[m//bm]]``.

    lhs: [M, C] with rows grouped by expert, group spans bm-aligned.
    rhs: [E, C, O] ([E, O, C] when ``trans_rhs``).
    tile_groups: [M//bm] int32, nondecreasing, expert id per row-tile.
    bn/bk: explicit tiles win over the autotune cache and the sweep
    flags (see ``_resolve_tiles``).  Returns [M, O] in lhs.dtype.

    The row tiles after the last expert's rows are dead: skipped, their
    blocks neither fetched nor multiplied, their rows of the result left
    unwritten (callers never read them: ``take_sentinel_rows`` on live
    positions).  At least one tile must be live.  A call is told which
    they are in one of two ways, and multiplies every tile if in neither:

    live_tiles: optional int32 scalar (a device value) — only the first
    ``live_tiles`` row tiles hold rows some expert owns (a layer that
    holds a share of the experts sorts the entries of the others last).

    dead_in_table: ``tile_groups`` itself says so (a plan made with
    ``masked_dispatch_plan``): a dead tile's entry is
    ``-(last live tile) - 1``.  The operands stay a call's without either.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, C = lhs.shape
    E = rhs.shape[0]
    O = rhs.shape[1] if trans_rhs else rhs.shape[2]
    if M % bm:
        raise ValueError(f"M ({M}) must be a multiple of bm ({bm})")
    mode = _mode(interpret)
    if mode is None:
        # a dead tile's rows are never read: any expert serves
        return _gmm_reference(lhs, rhs, jnp.maximum(tile_groups, 0)
                              if dead_in_table else tile_groups, bm=bm,
                              trans_rhs=trans_rhs)
    bn, bk = _resolve_tiles("gmm_t" if trans_rhs else "gmm", M, C, O, E,
                            bm, lhs.dtype, bn, bk, mode)
    nk = C // bk

    if live_tiles is not None and dead_in_table:
        raise ValueError("gmm: live_tiles and dead_in_table both name the "
                         "dead tiles; pass one")
    scalars = [tile_groups.astype(jnp.int32)]
    if live_tiles is not None:
        scalars.append(jnp.asarray(live_tiles, jnp.int32).reshape(1))
    skips = live_tiles is not None or dead_in_table
    if skips:
        nj = O // bn

        def park(fn):
            """``fn``'s block for a live tile; for every dead tile the last
            live tile's last block, so that consecutive dead steps name one
            block and the pipeline moves nothing."""
            def index_map(i, j, k, g, *live):
                # np.int32 constants: a bare python int is an i64 under
                # x64 mode, and the convert breaks Mosaic lowering
                if live:
                    is_dead = i >= live[0][0]
                    i_ = jnp.minimum(i, live[0][0] - np.int32(1))
                    pick = functools.partial(jnp.where, is_dead)
                else:
                    # bare primitives, not ``jnp.where`` (a traced jit of
                    # its own): an index map is traced three times a call,
                    # in every call of every layer of every step program.
                    # The count's form keeps it: its programs are the ones
                    # two cells were measured on (ROADMAP D15)
                    gi = g[i]
                    is_dead = jax.lax.lt(gi, np.int32(0))
                    pick = functools.partial(jax.lax.select, is_dead)
                    i_ = pick(jax.lax.sub(np.int32(-1), gi), i)
                return fn(i_, pick(np.int32(nj - 1), j),
                          pick(np.int32(nk - 1), k), g)
            return index_map
    else:
        def park(fn):
            return fn

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(M // bm, O // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), park(lambda i, j, k, g: (i, k))),
            pl.BlockSpec((None, bn, bk),
                         park(lambda i, j, k, g: (g[i], j, k)))
            if trans_rhs else
            pl.BlockSpec((None, bk, bn),
                         park(lambda i, j, k, g: (g[i], k, j)))],
        out_specs=pl.BlockSpec((bm, bn), park(lambda i, j, k, g: (i, j))),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kernel = functools.partial(_gmm_kernel, nk=nk, trans_rhs=trans_rhs,
                               skips=skips)
    return pl.pallas_call(
        kernel,
        name="gmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, O), lhs.dtype),
        # the dead tiles all park on one result block, which only
        # consecutive steps of one core may revisit
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if skips else "parallel",
                                 "parallel", "arbitrary")),
        interpret=(mode == "interpret"),
    )(*scalars, lhs, rhs)


# ----------------------------------------------------------------- tgmm ---

def _tgmm_kernel(group_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *, nm):
    from jax.experimental import pallas as pl

    m = pl.program_id(2)
    g_here = group_ref[m]
    # neighbor-row clamps stay np.int32: a bare python 0 is an i64 under
    # x64 mode and the i64->i32 convert breaks Mosaic (the PR 2 class)
    first = jnp.logical_or(m == 0,
                           group_ref[jnp.maximum(m - 1, np.int32(0))]
                           != g_here)
    last = jnp.logical_or(
        m == nm - 1,
        group_ref[jnp.minimum(m + 1, np.int32(nm - 1))] != g_here)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lhs, rhs = lhs_ref[...], rhs_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        lhs, rhs, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def tgmm(lhs, rhs, tile_groups, num_groups, *, bm=512, bn=None, bk=None,
         interpret=None):
    """Transposed grouped matmul (the weight gradient):
    ``out[e] = sum over e's rows of lhs[m, :]^T @ rhs[m, :]``.

    lhs: [M, K]; rhs: [M, N]; both row-grouped as in gmm.
    A group owning zero tiles gets an explicitly zeroed output block (the
    kernel only writes blocks it visits; the mask below covers truncated
    dispatch plans where a tail expert's span was cut).  Returns
    [E, K, N] in lhs.dtype.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = lhs.shape
    N = rhs.shape[1]
    if M % bm:
        raise ValueError(f"M ({M}) must be a multiple of bm ({bm})")
    mode = _mode(interpret)
    if mode is None:
        return _tgmm_reference(lhs, rhs, tile_groups, num_groups, bm=bm)
    bn, bk = _resolve_tiles("tgmm", M, K, N, num_groups, bm, lhs.dtype,
                            bn, bk, mode)
    nm = M // bm

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(K // bk, N // bn, nm),          # m innermost: consecutive
        in_specs=[                            # visits per expert block
            pl.BlockSpec((bm, bk), lambda k, j, i, g: (i, k)),
            pl.BlockSpec((bm, bn), lambda k, j, i, g: (i, j))],
        out_specs=pl.BlockSpec((None, bk, bn),
                               lambda k, j, i, g: (g[i], k, j)),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_tgmm_kernel, nm=nm),
        name="tgmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_groups, K, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=(mode == "interpret"),
    )(tile_groups.astype(jnp.int32), lhs, rhs)
    visited = jnp.zeros((num_groups,), bool).at[tile_groups].set(True)
    return jnp.where(visited[:, None, None], out, 0)


# ------------------------------------------------- XLA reference (CPU) ---

def _gmm_reference(lhs, rhs, tile_groups, *, bm, trans_rhs=False):
    """Oracle/CPU fallback: gather each row-tile's expert weights and run
    one batched matmul — M*K*N flops (no E-fold masking), fp32 accum."""
    M, C = lhs.shape
    T = M // bm
    w = jnp.take(rhs, tile_groups.astype(jnp.int32), axis=0)
    eq = "tbc,toc->tbo" if trans_rhs else "tbc,tco->tbo"
    out = jnp.einsum(eq, lhs.reshape(T, bm, C), w,
                     preferred_element_type=jnp.promote_types(
                         lhs.dtype, jnp.float32))
    return out.reshape(M, -1).astype(lhs.dtype)


def _tgmm_reference(lhs, rhs, tile_groups, num_groups, *, bm):
    M = lhs.shape[0]
    T = M // bm
    per_tile = jnp.einsum("tbk,tbn->tkn", lhs.reshape(T, bm, -1),
                          rhs.reshape(T, bm, -1),
                          preferred_element_type=jnp.promote_types(
                              lhs.dtype, jnp.float32))
    out = jax.ops.segment_sum(per_tile, tile_groups.astype(jnp.int32),
                              num_segments=num_groups)
    return out.astype(lhs.dtype)


# ------------------------------------------------------- dispatch plan ---

def take_sentinel_rows(buf, idx):
    """Gather rows of ``buf`` treating any index >= ``buf.shape[0]`` as
    the dispatch maps' dropped/pad SENTINEL: those positions read an
    exact zero row (and their AD transpose writes nowhere real).  Every
    dispatch/combine gather of the MoE paths goes through this one
    helper so the drop-to-zero semantics stay single-sourced."""
    pad = jnp.zeros((1,) + buf.shape[1:], buf.dtype)
    z = jnp.concatenate([buf, pad], axis=0)
    return jnp.take(z, jnp.minimum(idx, buf.shape[0]), axis=0)


def capacity_dispatch_plan(expert_ids, gate_vals, num_groups, capacity):
    """k-major capacity dispatch maps — the "gather" formulation shared by
    ``models.llama.moe_mlp_forward`` and the incubate ``MoELayer``.

    expert_ids/gate_vals: [N, K] top-k routing.  Slot priority is k-major
    (every token's first choice beats any second choice); position within
    an expert's buffer is the cumsum rank among entries routed to it;
    entries ranked past ``capacity`` drop.  Returns
    (inv [E*capacity + 1], slot [K*N], gate_keep [K*N], keep [K*N]):
    ``inv[b]`` = token id in buffer slot b (N = empty sentinel);
    ``slot[f]`` = buffer slot of k-major flat entry f (E*capacity = drop
    sentinel — gather combines through :func:`take_sentinel_rows`);
    ``gate_keep`` = combine weight, zeroed for drops."""
    N, K = expert_ids.shape
    i32 = jnp.int32
    idx_flat = expert_ids.T.reshape(K * N).astype(i32)
    val_flat = gate_vals.T.reshape(K * N).astype(jnp.float32)
    oh = jax.nn.one_hot(idx_flat, num_groups, dtype=jnp.float32)
    pos = jnp.sum(jnp.cumsum(oh, axis=0) * oh - oh, axis=-1).astype(i32)
    keep = pos < capacity
    slot = jnp.where(keep, idx_flat * capacity + pos,
                     num_groups * capacity)
    inv = jnp.full((num_groups * capacity + 1,), N, i32) \
        .at[slot].set(jnp.tile(jnp.arange(N, dtype=i32), K))
    return inv, slot, val_flat * keep.astype(jnp.float32), keep


def _dispatch_plan(expert_ids, num_groups, bm, real=None):
    """The two plans below: their three results, each expert's tiles and
    each expert's entries."""
    F = expert_ids.shape[0]
    M = -(-F // bm) * bm + num_groups * bm
    i32 = jnp.int32
    expert_ids = expert_ids.astype(i32)
    if real is not None:
        # a dropped entry sorts behind every expert's and is counted nowhere
        expert_ids = jnp.where(real, expert_ids, num_groups)
    order = jnp.argsort(expert_ids, stable=True)
    e_sorted = jnp.take(expert_ids, order)
    counts = jnp.bincount(expert_ids, length=num_groups)
    tiles = jnp.maximum(-(-counts // bm), 1)
    padded = tiles * bm
    starts = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    offsets = jnp.concatenate(
        [jnp.zeros((1,), padded.dtype), jnp.cumsum(padded)[:-1]])
    r = jnp.arange(F, dtype=i32)
    dest = (offsets[e_sorted] + (r - starts[e_sorted])).astype(i32)
    if real is not None:
        # row M is out of bounds: the scatter below drops it
        dest = jnp.where(e_sorted < num_groups, dest, M)
    inv_flat = jnp.full((M,), F, i32).at[dest].set(order.astype(i32))
    pos = jnp.zeros((F,), i32).at[order].set(dest)
    ends = jnp.cumsum(padded)
    tile_groups = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(M // bm) * bm, side="right"),
        num_groups - 1).astype(i32)
    return inv_flat, pos, tile_groups, tiles, counts


def sorted_dispatch_plan(expert_ids, num_groups, bm):
    """Build the gather maps for a grouped-GEMM dispatch.

    expert_ids: [F] int32 — the expert choice per (token, k) flat entry.
    Returns (inv_flat [M], pos [F], tile_groups [M // bm]) where
    M = ceil(F/bm)*bm + num_groups*bm (static):

    - ``inv_flat[p]`` = flat entry id occupying padded-buffer row p, or F
      for alignment-padding rows (callers gather against a zero row).
    - ``pos[f]`` = padded-buffer row of flat entry f.
    - ``tile_groups[i]`` = expert owning row-tile i (nondecreasing; every
      expert owns >= 1 tile, which ``tgmm`` requires).

    Rows are grouped by expert in stable order, each expert padded to a
    bm multiple (>= bm), so both dispatch and un-dispatch are pure
    GATHERS — the backward of each is the other, so no serialized
    scatter-adds appear anywhere in the MoE step (the scatters here are
    1 int32 word per row, vectorized).
    """
    return _dispatch_plan(expert_ids, num_groups, bm)[:3]


def masked_dispatch_plan(expert_ids, real, num_groups, bm):
    """``sorted_dispatch_plan`` over the entries that belong to a token.

    real: [F] bool (a serving step's row bucket holds rows without a
    token).  The other entries are dropped before rows are laid out: such
    an entry takes no row and its ``pos`` is the sentinel M
    (``take_sentinel_rows`` reads zero).  M stays what it is without a
    mask.  Returns (inv_flat, pos, tile_groups, live_tiles, counts): the
    row tiles after the last expert's are dead and the table says so itself,
    ``tile_groups[t] = -(last live tile) - 1``, which is what
    ``gmm(dead_in_table=True)`` skips them by; ``live_tiles`` counts the
    tiles before them, an int32 device scalar; ``counts [num_groups]`` are
    each expert's real entries.
    """
    inv_flat, pos, tile_groups, tiles, counts = _dispatch_plan(
        expert_ids, num_groups, bm, real)
    # the sum of the experts' tiles, not ``ends[-1] // bm``: under x64 a
    # division of a device int64 compiles for a second on the chip's host
    live_tiles = tiles.sum().astype(jnp.int32)
    tile_groups = jnp.where(jnp.arange(tile_groups.shape[0]) < live_tiles,
                            tile_groups, -live_tiles)
    return inv_flat, pos, tile_groups, live_tiles, counts


# ------------------------------------------------------ differentiable ---

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def grouped_matmul(lhs, rhs, tile_groups, num_groups, bm=512, bn=None,
                   bk=None):
    """Differentiable grouped matmul: ``gmm`` forward; backward runs
    ``gmm`` against the transposed expert weights (dlhs) and ``tgmm``
    (drhs).  All three are ragged — the gradient FLOPs also scale with
    actual tokens-per-expert."""
    if bn is None or bk is None:
        validate_tile_flags(lhs.shape[1], rhs.shape[2])
    return gmm(lhs, rhs, tile_groups, bm=bm, bn=bn, bk=bk)


def _grouped_matmul_fwd(lhs, rhs, tile_groups, num_groups, bm, bn, bk):
    if bn is None or bk is None:
        # flag-overridden tiles must fit BOTH the forward (bn|O, bk|C) and
        # backward (bn|C, bk|O via trans_rhs + tgmm) operand shapes
        validate_tile_flags(lhs.shape[1], rhs.shape[2])
    out = gmm(lhs, rhs, tile_groups, bm=bm, bn=bn, bk=bk)
    return out, (lhs, rhs, tile_groups)


def _grouped_matmul_bwd(num_groups, bm, bn, bk, res, dy):
    lhs, rhs, tile_groups = res
    # dlhs[m] = dy[m] @ rhs[g]^T — rhs's [E, C, O] is exactly the
    # trans_rhs=[E, out, contract] layout for this product
    dlhs = gmm(dy, rhs, tile_groups, bm=bm, bn=bn, bk=bk, trans_rhs=True)
    drhs = tgmm(lhs, dy, tile_groups, num_groups, bm=bm, bn=bn, bk=bk)
    return (dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype),
            np.zeros(tile_groups.shape, jax.dtypes.float0))


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
