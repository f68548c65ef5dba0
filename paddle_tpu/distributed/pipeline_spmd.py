"""SPMD pipeline parallelism — GPipe / interleaved (VPP) / 1F1B schedules
over a mesh axis.

Reference mechanism: FleetExecutor interceptors / PipelineParallel schedules
(pipeline_parallel.py:575 forward_backward_pipeline, :1174 interleave/VPP) with
NCCL p2p (p2p_communication.py:573).  TPU-native redesign: the pipeline IS a
collective program — stage parameters are stacked on a leading dim sharded
over the 'pp' mesh axis, and one `shard_map`ped `lax.scan` advances the
wavefront with `lax.ppermute` stage-to-stage transfers over ICI.  Every stage
computes every tick (SPMD), so bubbles are idle-compute, and the schedules
trade off differently than their MPMD ancestors:

* ``gpipe``      — forward scan, XLA AD produces the reversed backward
                   wavefront.  Fewest lockstep ticks (M+S-1 fwd / M+S-1 bwd)
                   but activation residuals grow with M.
* ``interleave`` — circular schedule, the VPP analog: each device holds
                   ``v`` layer chunks (device s owns chunks {r*S+s}), and
                   microbatches circulate v rounds.  Fill/drain shrinks from
                   (S-1) full-stage ticks to (S-1) chunk ticks — a v× smaller
                   bubble, exactly Megatron-VPP's ratio.
* ``1f1b``       — manual one-forward-one-backward schedule with
                   recompute-from-checkpoint (pipeline_1f1b_grads): live
                   activation checkpoints are capped at 2S-1 microbatches per
                   device, independent of M (GPipe stores M+S-1).  The
                   schedule of choice when M >> S; costs loss-fn compute on
                   every stage's backward tick (SPMD lockstep has no
                   last-stage-only work).

Other mesh axes (dp/mp/...) stay *auto*: GSPMD keeps partitioning each
stage's internals (Megatron TP etc.) inside the manual pp axis.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_apply(mesh, axis: str, stage_fn: Callable, stage_params: Any,
                   microbatches, *consts, virtual: int = 1):
    """Run a forward pipeline over `axis` (differentiable; XLA AD gives the
    reversed backward wavefront — the GPipe schedule, or circular/VPP when
    ``virtual > 1``).

    Args:
      mesh: the hybrid `jax.sharding.Mesh` (must contain `axis`).
      axis: pipeline mesh-axis name (e.g. 'pp'), size S.
      stage_fn: `(params_slice, x, *consts) -> y` — one stage's (or, with
        virtual>1, one chunk's) compute; `params_slice` leaves have the
        stacked leading dims removed; y must have x's shape/dtype.
      stage_params: pytree with leaves stacked `[S, ...]` (sharded P(axis));
        with virtual=v, `[S*v, ...]` where row `s*v + r` holds the chunk that
        stage s runs in round r (i.e. layer group `r*S + s` — see
        `interleave_chunk_order`).
      microbatches: `[M, mb, ...]` activations fed to stage 0.
      consts: broadcast arrays (e.g. rope tables) replicated to every stage.
      virtual: chunks per device (VPP degree v).  1 = plain GPipe.

    Returns `[M, mb, ...]` outputs of the final chunk (replicated over pp).
    """
    S = mesh.shape[axis]
    if S == 1:
        def body(carry, mb):
            x = mb
            for r in range(virtual):
                p_r = jax.tree_util.tree_map(lambda l: l[r], stage_params)
                x = stage_fn(p_r, x, *consts)
            return carry, x

        _, out = lax.scan(body, 0, microbatches)
        return out

    if virtual == 1:
        return _gpipe(mesh, axis, S, stage_fn, stage_params, microbatches,
                      *consts)
    return _circular(mesh, axis, S, virtual, stage_fn, stage_params,
                     microbatches, *consts)


def _gpipe(mesh, axis, S, stage_fn, stage_params, microbatches, *consts):
    M = microbatches.shape[0]
    perm = [(i, (i + 1) % S) for i in range(S)]

    def per_stage(params_local, micro, *cs):
        # params_local leaves: [1, ...] — this stage's block stack
        params = jax.tree_util.tree_map(lambda l: l[0], params_local)
        s = lax.axis_index(axis)
        # carries become device-varying after the first ppermute; mark them so
        state = lax.pcast(jnp.zeros_like(micro[0]), (axis,), to="varying")
        out_buf = lax.pcast(jnp.zeros_like(micro), (axis,), to="varying")

        def tick(carry, t):
            state, out_buf = carry
            x0 = lax.dynamic_index_in_dim(micro, jnp.clip(t, 0, M - 1), 0,
                                          keepdims=False)
            x = jnp.where(s == 0, x0, state)
            y = stage_fn(params, x, *cs)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            valid = jnp.logical_and(t - (S - 1) >= 0, s == S - 1)
            out_buf = jnp.where(
                valid,
                lax.dynamic_update_index_in_dim(out_buf, y, out_idx, 0),
                out_buf)
            state = lax.ppermute(y, axis, perm)
            return (state, out_buf), None

        (state, out_buf), _ = lax.scan(tick, (state, out_buf),
                                       jnp.arange(M + S - 1))
        # replicate the last stage's buffer so downstream (loss) code sees a
        # full array on every pp rank (an S-hop broadcast over ICI)
        mask = (s == S - 1).astype(out_buf.dtype)
        return lax.psum(out_buf * mask, axis)

    in_specs = (jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                P()) + tuple(P() for _ in consts)
    return jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), axis_names={axis}, check_vma=True,
                         )(stage_params, microbatches, *consts)


def interleave_chunk_order(S: int, v: int):
    """Row order for stacking chunk params: row s*v + r must hold layer group
    g = r*S + s, so a [S*v] leading dim sharded over the S-way axis gives
    device s exactly its v round-chunks in round order."""
    return [r * S + s for s in range(S) for r in range(v)]


def _circular(mesh, axis, S, v, stage_fn, stage_params, microbatches, *consts):
    """Circular (interleaved/VPP) schedule: microbatch m, round r is processed
    by stage (g mod S) with chunk params row r, at tick i = r*M + m + s.
    Requires M >= S so a round-(r) activation has always arrived at stage 0
    before tick r*M + m (produced at (r-1)*M + m + S - 1)."""
    M = microbatches.shape[0]
    if M < S:
        raise ValueError(
            f"interleaved pipeline needs microbatches ({M}) >= stages ({S})")
    T = v * M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    def per_stage(params_local, micro, *cs):
        # params_local leaves: [v, ...] — this stage's chunks in round order
        s = lax.axis_index(axis)
        state = lax.pcast(jnp.zeros_like(micro[0]), (axis,), to="varying")
        out_buf = lax.pcast(jnp.zeros_like(micro), (axis,), to="varying")
        circ = lax.pcast(jnp.zeros_like(micro), (axis,), to="varying")

        def tick(carry, i):
            state, out_buf, circ = carry
            f = i - s                          # global work index
            m = jnp.clip(f, 0, v * M - 1) % M  # microbatch
            r = jnp.clip(f, 0, v * M - 1) // M  # round
            valid = jnp.logical_and(f >= 0, f < v * M)

            # stage 0 consumed a circulating activation that arrived from
            # stage S-1 via ppermute LAST tick and was parked in circ
            x0_new = lax.dynamic_index_in_dim(micro, m, 0, keepdims=False)
            x0_circ = lax.dynamic_index_in_dim(circ, m, 0, keepdims=False)
            x0 = jnp.where(r == 0, x0_new, x0_circ)
            x = jnp.where(s == 0, x0, state)

            p_r = jax.tree_util.tree_map(
                lambda l: lax.dynamic_index_in_dim(l, r, 0, keepdims=False),
                params_local)
            y = stage_fn(p_r, x, *cs)

            # last stage, final round: emit; otherwise circulate
            emit = jnp.logical_and(valid,
                                   jnp.logical_and(s == S - 1, r == v - 1))
            out_buf = jnp.where(
                emit, lax.dynamic_update_index_in_dim(out_buf, y, m, 0),
                out_buf)
            state = lax.ppermute(y, axis, perm)

            # park the activation that just arrived at stage 0 (sent by stage
            # S-1, which at tick i worked on f' = i - (S-1)) for its next round
            mp = jnp.clip(i - (S - 1), 0, v * M - 1) % M
            park = jnp.logical_and(s == 0,
                                   jnp.logical_and(i - (S - 1) >= 0,
                                                   i - (S - 1) < v * M - M))
            circ = jnp.where(
                park, lax.dynamic_update_index_in_dim(circ, state, mp, 0),
                circ)
            return (state, out_buf, circ), None

        (state, out_buf, circ), _ = lax.scan(tick, (state, out_buf, circ),
                                             jnp.arange(T))
        mask = (s == S - 1).astype(out_buf.dtype)
        return lax.psum(out_buf * mask, axis)

    in_specs = (jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                P()) + tuple(P() for _ in consts)
    return jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), axis_names={axis}, check_vma=True,
                         )(stage_params, microbatches, *consts)


def pipeline_1f1b_grads(mesh, axis: str, stage_fn: Callable,
                        loss_fn: Callable, stage_params: Any, loss_params: Any,
                        microbatches, labels, *consts):
    """One-forward-one-backward schedule with manual gradient plumbing.

    Per-device live activation checkpoints are capped at W = 2S-1
    microbatches (GPipe-by-AD stores M+S-1 scan residuals), at the cost of
    running `loss_fn` on every stage during backward ticks (SPMD lockstep).
    The backward recomputes each stage's forward from its checkpointed input
    (Megatron-style recompute), so `stage_fn` need not be remat'd by the
    caller.

    Timing (tick t): stage s forwards microbatch f = t - s and backwards
    microbatch b = t - (2S - 1 - s); cotangents hop s+1 -> s via reverse
    ppermute.  Total ticks 2S + M - 1.

    Args:
      stage_fn: `(stage_params_slice, x, *consts) -> y`.
      loss_fn: `(y, labels_mb, loss_params) -> scalar` — per-microbatch loss
        applied after the LAST stage (e.g. final norm + lm head + CE).  Must
        return the SUM-convention loss for correct accumulation; the caller
        divides by M.
      stage_params: leaves `[S, ...]` sharded P(axis).
      loss_params: pytree, replicated.
      microbatches: `[M, mb...]`; labels: `[M, ...]` per-microbatch labels.

    Returns `(total_loss, d_stage_params, d_loss_params, d_microbatches)`
    where total_loss is the sum over microbatches (divide by M for the mean).
    """
    S = mesh.shape[axis]
    M = microbatches.shape[0]

    if S == 1:
        params = jax.tree_util.tree_map(lambda l: l[0], stage_params)

        def body(carry, xs):
            loss_acc, gp_acc, glp_acc = carry
            mb, lbl = xs

            def f(p, lp, mb_):
                return loss_fn(stage_fn(p, mb_, *consts), lbl, lp)

            l, (gp, glp, dmb) = jax.value_and_grad(f, argnums=(0, 1, 2))(
                params, loss_params, mb)
            return (loss_acc + l,
                    jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), gp_acc, gp),
                    jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), glp_acc, glp),
                    ), dmb.astype(microbatches.dtype)

        zero_p = jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape[1:], jnp.float32), stage_params)
        zero_lp = jax.tree_util.tree_map(
            lambda l: jnp.zeros(l.shape, jnp.float32), loss_params)
        (loss, gp, glp), dmicro = lax.scan(
            body, (jnp.float32(0.0), zero_p, zero_lp), (microbatches, labels))
        gp = jax.tree_util.tree_map(lambda l: l[None], gp)
        return loss, gp, glp, dmicro

    W = 2 * S - 1                       # ring slots for in-flight checkpoints
    T = 2 * S + M - 1
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    def per_stage(params_local, micro, lbls, lparams, *cs):
        params = jax.tree_util.tree_map(lambda l: l[0], params_local)
        s = lax.axis_index(axis)
        mb_shape = micro[0]

        def vary(x):
            return lax.pcast(x, (axis,), to="varying")

        # mark loss params device-varying BEFORE the per-tick vjp: the
        # cotangent of an invariant input inside a manual region is auto-
        # psummed across the axis — correct, but that is a hidden per-tick
        # allreduce of head-sized grads.  Varying-typed inputs keep local
        # cotangents; we reduce once after the scan.
        lparams = jax.tree_util.tree_map(vary, lparams)

        fwd_carry = vary(jnp.zeros_like(mb_shape))
        bwd_carry = vary(jnp.zeros_like(mb_shape))
        inbuf = vary(jnp.zeros((W,) + mb_shape.shape, mb_shape.dtype))
        dmicro = vary(jnp.zeros_like(micro))
        gacc = jax.tree_util.tree_map(
            lambda l: vary(jnp.zeros(l.shape, jnp.float32)), params)
        glp_acc = jax.tree_util.tree_map(
            lambda l: vary(jnp.zeros(l.shape, jnp.float32)), lparams)
        loss_acc = vary(jnp.float32(0.0))

        def tick(carry, t):
            (fwd_carry, bwd_carry, inbuf, dmicro, gacc, glp_acc,
             loss_acc) = carry

            # backward checkpoint must be read BEFORE the forward stores:
            # at stage 0, mb f's slot is reused by mb f + (2S-1) in the same
            # tick that consumes it
            b = t - (2 * S - 1 - s)
            b_valid = jnp.logical_and(b >= 0, b < M)
            bc = jnp.clip(b, 0, M - 1)
            xb = lax.dynamic_index_in_dim(inbuf, bc % W, 0, keepdims=False)

            # ---- forward half: microbatch f = t - s ----
            f = t - s
            f_valid = jnp.logical_and(f >= 0, f < M)
            fc = jnp.clip(f, 0, M - 1)
            x0 = lax.dynamic_index_in_dim(micro, fc, 0, keepdims=False)
            x = jnp.where(s == 0, x0, fwd_carry)
            y = stage_fn(params, x, *cs)
            inbuf = jnp.where(
                f_valid,
                lax.dynamic_update_index_in_dim(inbuf, x, fc % W, 0), inbuf)

            # ---- backward half ----
            lbl_b = lax.dynamic_index_in_dim(lbls, bc, 0, keepdims=False)

            def fwd_and_loss(p, x_, lp):
                y_ = stage_fn(p, x_, *cs)
                return y_, loss_fn(y_, lbl_b, lp)

            (_, loss_b), vjp = jax.vjp(fwd_and_loss, params, xb, lparams)
            is_last = (s == S - 1)
            # seed: last stage pulls back d(loss)=1; others pull back the
            # cotangent from the next stage.  Linearity of vjp zeroes the
            # loss-path (resp. y-path) contributions automatically.
            gy_seed = jnp.where(jnp.logical_or(is_last,
                                               jnp.logical_not(b_valid)),
                                jnp.zeros_like(y), bwd_carry).astype(y.dtype)
            gl_seed = jnp.where(jnp.logical_and(is_last, b_valid),
                                jnp.float32(1.0), jnp.float32(0.0))
            gp, dx, glp = vjp((gy_seed, gl_seed))

            gacc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), gacc, gp)
            glp_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), glp_acc, glp)
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(is_last, b_valid), loss_b, 0.0)

            # stage 0's dx is the cotangent of the embedded microbatch
            dmicro = jnp.where(
                jnp.logical_and(s == 0, b_valid),
                lax.dynamic_update_index_in_dim(
                    dmicro, dx.astype(dmicro.dtype), bc, 0),
                dmicro)

            fwd_carry = lax.ppermute(y, axis, fwd_perm)
            bwd_carry = lax.ppermute(dx.astype(mb_shape.dtype), axis,
                                     bwd_perm)
            return (fwd_carry, bwd_carry, inbuf, dmicro, gacc, glp_acc,
                    loss_acc), None

        carry = (fwd_carry, bwd_carry, inbuf, dmicro, gacc, glp_acc, loss_acc)
        carry, _ = lax.scan(tick, carry, jnp.arange(T))
        _, _, _, dmicro, gacc, glp_acc, loss_acc = carry

        # stage grads stay sharded [1, ...] over pp; everything else reduces
        gacc = jax.tree_util.tree_map(lambda l: l[None], gacc)
        loss = lax.psum(loss_acc, axis)
        glp = jax.tree_util.tree_map(lambda l: lax.psum(l, axis), glp_acc)
        dmicro = lax.psum(
            dmicro * (s == 0).astype(dmicro.dtype), axis)
        return loss, gacc, glp, dmicro

    in_specs = (jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                P(), P(), jax.tree_util.tree_map(lambda _: P(), loss_params),
                ) + tuple(P() for _ in consts)
    out_specs = (P(), jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                 jax.tree_util.tree_map(lambda _: P(), loss_params), P())
    return jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={axis}, check_vma=True,
                         )(stage_params, microbatches, labels, loss_params,
                           *consts)


def zbh1_schedule(S: int, M: int):
    """The ZBH1 work layout: per (stage, tick), which of F/B/W units run.

    Mirrors the reference zero-bubble pass
    (python/paddle/distributed/passes/pipeline_scheduler_pass/
    pipeline_zero_bubble.py:62 ZBH1: split the weight-grad W out of the
    combined backward B so W fills the cooldown bubble).  Unit timing:
      F(f) at tick t = f + s
      B(b) at tick t = b + (2S - 1 - s)   (input-grad only — the
                                           inter-stage dependency chain)
      W(w) at tick t = w + (2S - 1)       (weight-grad, deferred s ticks
                                           after its B — stage 0 runs W
                                           with B, stage S-1 defers most)
    Total ticks 2S + M - 1; each stage does M F, M B and M W units, and
    every W lands in a slot where plain 1F1B idles its weight-grad work.
    Returns {(s, t): set of ('F'|'B'|'W', microbatch)}.
    """
    table = {}
    T = 2 * S + M - 1
    for s in range(S):
        for t in range(T):
            units = set()
            f = t - s
            if 0 <= f < M:
                units.add(("F", f))
            b = t - (2 * S - 1 - s)
            if 0 <= b < M:
                units.add(("B", b))
            w = t - (2 * S - 1)
            if 0 <= w < M:
                units.add(("W", w))
            if units:
                table[(s, t)] = units
    return table


def pipeline_zbh1_grads(mesh, axis: str, stage_fn: Callable,
                        loss_fn: Callable, stage_params: Any, loss_params: Any,
                        microbatches, labels, *consts):
    """Zero-bubble H1 schedule: 1F1B with the weight-grad (W) split from the
    input-grad (B) and deferred into the cooldown slots.

    Reference: pipeline_zero_bubble.py:62 (ZBH1).  The B pass pulls back
    ONLY the activation cotangent (the inter-stage critical path: XLA DCEs
    the dθ computations out of it); the W pass replays the stage vjp for
    the saved (checkpointed input, received cotangent) pair s ticks later
    and accumulates dθ/d(loss params).  Stage 0 defers nothing; stage S-1
    defers W by S-1 ticks — exactly the paper's triangle of W fills.

    In this SPMD lockstep runtime every stage executes every tick, so the
    tick count (2S + M - 1, `zbh1_schedule`) matches plain 1F1B and the
    split's wall-clock value comes from XLA overlapping the off-critical-
    path W matmuls with the cotangent ppermute inside each tick; the
    schedule structure (and its MPMD benefit, for a future multi-executable
    runtime) is the reference's.  Costs one extra forward recompute per
    microbatch vs combined 1F1B.

    Same contract as `pipeline_1f1b_grads`.
    """
    S = mesh.shape[axis]
    M = microbatches.shape[0]
    if S == 1:
        return pipeline_1f1b_grads(mesh, axis, stage_fn, loss_fn,
                                   stage_params, loss_params, microbatches,
                                   labels, *consts)

    W_ring = 2 * S - 1
    T = 2 * S + M - 1
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    def per_stage(params_local, micro, lbls, lparams, *cs):
        params = jax.tree_util.tree_map(lambda l: l[0], params_local)
        s = lax.axis_index(axis)
        mb_shape = micro[0]

        def vary(x):
            return lax.pcast(x, (axis,), to="varying")

        lparams = jax.tree_util.tree_map(vary, lparams)

        fwd_carry = vary(jnp.zeros_like(mb_shape))
        bwd_carry = vary(jnp.zeros_like(mb_shape))
        inbuf = vary(jnp.zeros((W_ring,) + mb_shape.shape, mb_shape.dtype))
        gybuf = vary(jnp.zeros((W_ring,) + mb_shape.shape, mb_shape.dtype))
        glbuf = vary(jnp.zeros((W_ring,), jnp.float32))
        dmicro = vary(jnp.zeros_like(micro))
        gacc = jax.tree_util.tree_map(
            lambda l: vary(jnp.zeros(l.shape, jnp.float32)), params)
        glp_acc = jax.tree_util.tree_map(
            lambda l: vary(jnp.zeros(l.shape, jnp.float32)), lparams)
        loss_acc = vary(jnp.float32(0.0))

        def tick(carry, t):
            (fwd_carry, bwd_carry, inbuf, gybuf, glbuf, dmicro, gacc,
             glp_acc, loss_acc) = carry

            # ---- reads first: ring slots are reused within the tick ----
            b = t - (2 * S - 1 - s)
            b_valid = jnp.logical_and(b >= 0, b < M)
            bc = jnp.clip(b, 0, M - 1)
            xb = lax.dynamic_index_in_dim(inbuf, bc % W_ring, 0,
                                          keepdims=False)

            w = t - (2 * S - 1)
            w_valid = jnp.logical_and(w >= 0, w < M)
            wc = jnp.clip(w, 0, M - 1)
            xw = lax.dynamic_index_in_dim(inbuf, wc % W_ring, 0,
                                          keepdims=False)
            gyw_saved = lax.dynamic_index_in_dim(gybuf, wc % W_ring, 0,
                                                 keepdims=False)
            glw_saved = lax.dynamic_index_in_dim(glbuf, wc % W_ring, 0,
                                                 keepdims=False)

            # ---- forward: F(f = t - s) ----
            f = t - s
            f_valid = jnp.logical_and(f >= 0, f < M)
            fc = jnp.clip(f, 0, M - 1)
            x0 = lax.dynamic_index_in_dim(micro, fc, 0, keepdims=False)
            x = jnp.where(s == 0, x0, fwd_carry)
            y = stage_fn(params, x, *cs)
            inbuf = jnp.where(
                f_valid,
                lax.dynamic_update_index_in_dim(inbuf, x, fc % W_ring, 0),
                inbuf)

            # ---- B pass: input-grad only (critical path) ----
            lbl_b = lax.dynamic_index_in_dim(lbls, bc, 0, keepdims=False)

            def fwd_loss_x(x_):
                y_ = stage_fn(params, x_, *cs)
                return y_, loss_fn(y_, lbl_b, lparams)

            (_, loss_b), vjp_x = jax.vjp(fwd_loss_x, xb)
            is_last = (s == S - 1)
            gy_seed = jnp.where(jnp.logical_or(is_last,
                                               jnp.logical_not(b_valid)),
                                jnp.zeros_like(y), bwd_carry).astype(y.dtype)
            gl_seed = jnp.where(jnp.logical_and(is_last, b_valid),
                                jnp.float32(1.0), jnp.float32(0.0))
            (dx,) = vjp_x((gy_seed, gl_seed))
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(is_last, b_valid), loss_b, 0.0)
            dmicro = jnp.where(
                jnp.logical_and(s == 0, b_valid),
                lax.dynamic_update_index_in_dim(
                    dmicro, dx.astype(dmicro.dtype), bc, 0),
                dmicro)

            # save the B seed for the deferred W pass
            gybuf = jnp.where(
                b_valid,
                lax.dynamic_update_index_in_dim(
                    gybuf, gy_seed.astype(mb_shape.dtype), bc % W_ring, 0),
                gybuf)
            glbuf = jnp.where(
                b_valid,
                lax.dynamic_update_index_in_dim(glbuf, gl_seed, bc % W_ring,
                                                0),
                glbuf)

            # ---- W pass: weight-grad W(w = t - (2S-1)) ----
            # stage 0 has zero deferral (w == b there): use the fresh seed
            gyw = jnp.where(s == 0, gy_seed.astype(mb_shape.dtype),
                            gyw_saved)
            glw = jnp.where(s == 0, gl_seed, glw_saved)
            xw_eff = jnp.where(s == 0, xb, xw)

            def fwd_loss_p(p_, lp_):
                y_ = stage_fn(p_, xw_eff, *cs)
                lblw = lax.dynamic_index_in_dim(lbls, wc, 0, keepdims=False)
                lblw = jnp.where(s == 0, lbl_b, lblw)
                return y_, loss_fn(y_, lblw, lp_)

            _, vjp_p = jax.vjp(fwd_loss_p, params, lparams)
            gp, glp = vjp_p((gyw.astype(y.dtype), glw))
            do_w = jnp.where(s == 0, b_valid, w_valid)
            gacc = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(do_w, g.astype(jnp.float32), 0.0),
                gacc, gp)
            glp_acc = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(do_w, g.astype(jnp.float32), 0.0),
                glp_acc, glp)

            fwd_carry = lax.ppermute(y, axis, fwd_perm)
            bwd_carry = lax.ppermute(dx.astype(mb_shape.dtype), axis,
                                     bwd_perm)
            return (fwd_carry, bwd_carry, inbuf, gybuf, glbuf, dmicro, gacc,
                    glp_acc, loss_acc), None

        carry = (fwd_carry, bwd_carry, inbuf, gybuf, glbuf, dmicro, gacc,
                 glp_acc, loss_acc)
        carry, _ = lax.scan(tick, carry, jnp.arange(T))
        (_, _, _, _, _, dmicro, gacc, glp_acc, loss_acc) = carry

        gacc = jax.tree_util.tree_map(lambda l: l[None], gacc)
        loss = lax.psum(loss_acc, axis)
        glp = jax.tree_util.tree_map(lambda l: lax.psum(l, axis), glp_acc)
        dmicro = lax.psum(dmicro * (s == 0).astype(dmicro.dtype), axis)
        return loss, gacc, glp, dmicro

    in_specs = (jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                P(), P(), jax.tree_util.tree_map(lambda _: P(), loss_params),
                ) + tuple(P() for _ in consts)
    out_specs = (P(), jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                 jax.tree_util.tree_map(lambda _: P(), loss_params), P())
    return jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={axis}, check_vma=True,
                         )(stage_params, microbatches, labels, loss_params,
                           *consts)


def pipeline_zbvpp_grads(mesh, axis: str, stage_fn: Callable,
                         loss_fn: Callable, stage_params: Any,
                         loss_params: Any, microbatches, labels, *consts,
                         virtual: int = 1):
    """Zero-bubble x virtual-pipeline (ZBVPP) schedule with manual grads.

    Reference: pipeline_zero_bubble.py:151
    (``PipelineZeroBubbleVirtualPipelinePass``) — the interleaved-VPP
    schedule with each backward split into B (input-grad, the inter-stage
    critical path) and W (weight-grad, deferred into bubble slots).

    SPMD lockstep layout (same runtime model as `pipeline_zbh1_grads`):
    stage s holds ``virtual`` chunk rows in round order
    (`interleave_chunk_order`); unit (microbatch m, chunk r) timing is

      F at tick  t = r*M + m + s                       (circular forward)
      B at tick  t = vM + (v-1-r)*M + m + (S-1-s)      (mirrored wavefront)
      W at tick  t = B + s = vM + (v-1-r)*M + m + S-1  (stage-proportional
                                                        deferral; stage 0
                                                        runs W with B)

    over T = 2vM + S - 1 ticks.  Chunk hand-offs ride the same ring
    ppermutes as the interleave schedule, with activations parked at stage 0
    (forward, chunk r -> r+1) and cotangents parked at stage S-1 (backward,
    chunk r+1 -> r).  As with ZBH1, every stage computes every tick in this
    lockstep runtime, so the B/W split's wall-clock value comes from XLA
    overlapping the off-critical-path W work with the cotangent ppermute;
    the schedule structure is the reference's.  Saved inputs/seeds are
    buffered per unit ([v*M] slots — the lockstep analog of the reference's
    per-chunk activation queues).

    Requires M >= S and S >= 2 (use `pipeline_zbh1_grads` for S == 1).
    Same contract as `pipeline_1f1b_grads`; ``stage_params`` leaves lead
    with the S*virtual chunk-row dim.
    """
    S = mesh.shape[axis]
    v = int(virtual)
    M = microbatches.shape[0]
    if S == 1:
        raise ValueError("zbvpp needs pp >= 2; use schedule='zbh1' for pp=1")
    if M < S:
        raise ValueError(f"zbvpp needs microbatches ({M}) >= stages ({S})")
    U = v * M
    T = 2 * U + S - 1
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    def per_stage(params_local, micro, lbls, lparams, *cs):
        # params_local leaves: [v, ...] — this stage's chunks in round order
        s = lax.axis_index(axis)
        mb_shape = micro[0]

        def vary(x):
            return lax.pcast(x, (axis,), to="varying")

        lparams = jax.tree_util.tree_map(vary, lparams)

        def chunk(tree, r):
            return jax.tree_util.tree_map(
                lambda l: lax.dynamic_index_in_dim(l, r, 0, keepdims=False),
                tree)

        fwd_carry = vary(jnp.zeros_like(mb_shape))
        bwd_carry = vary(jnp.zeros_like(mb_shape))
        circ_f = vary(jnp.zeros_like(micro))            # stage-0 fwd parking
        park_b = vary(jnp.zeros_like(micro))            # stage-(S-1) bwd park
        inbuf = vary(jnp.zeros((U,) + mb_shape.shape, mb_shape.dtype))
        gybuf = vary(jnp.zeros((U,) + mb_shape.shape, mb_shape.dtype))
        glbuf = vary(jnp.zeros((U,), jnp.float32))
        dmicro = vary(jnp.zeros_like(micro))
        gacc = jax.tree_util.tree_map(
            lambda l: vary(jnp.zeros(l.shape, jnp.float32)), params_local)
        glp_acc = jax.tree_util.tree_map(
            lambda l: vary(jnp.zeros(l.shape, jnp.float32)), lparams)
        loss_acc = vary(jnp.float32(0.0))

        def tick(carry, t):
            (fwd_carry, bwd_carry, circ_f, park_b, inbuf, gybuf, glbuf,
             dmicro, gacc, glp_acc, loss_acc) = carry

            # ---- F unit: f = t - s ----
            f = t - s
            f_valid = jnp.logical_and(f >= 0, f < U)
            fc = jnp.clip(f, 0, U - 1)
            r_f, m_f = fc // M, fc % M
            x0_new = lax.dynamic_index_in_dim(micro, m_f, 0, keepdims=False)
            x0_circ = lax.dynamic_index_in_dim(circ_f, m_f, 0, keepdims=False)
            x0 = jnp.where(r_f == 0, x0_new, x0_circ)
            x_in = jnp.where(s == 0, x0, fwd_carry)
            y = stage_fn(chunk(params_local, r_f), x_in, *cs)
            inbuf = jnp.where(
                f_valid,
                lax.dynamic_update_index_in_dim(inbuf, x_in, fc, 0), inbuf)

            # ---- B unit: k_b = t - vM - (S-1-s) ----
            k_b = t - U - (S - 1 - s)
            b_valid = jnp.logical_and(k_b >= 0, k_b < U)
            kb = jnp.clip(k_b, 0, U - 1)
            r_b, m_b = v - 1 - kb // M, kb % M
            u_b = r_b * M + m_b
            xb = lax.dynamic_index_in_dim(inbuf, u_b, 0, keepdims=False)
            p_b = chunk(params_local, r_b)
            lbl_b = lax.dynamic_index_in_dim(lbls, m_b, 0, keepdims=False)

            def fwd_loss_x(x_):
                y_ = stage_fn(p_b, x_, *cs)
                return y_, loss_fn(y_, lbl_b, lparams)

            (_, loss_b), vjp_x = jax.vjp(fwd_loss_x, xb)
            is_loss_unit = jnp.logical_and(s == S - 1, r_b == v - 1)
            parked = lax.dynamic_index_in_dim(park_b, m_b, 0, keepdims=False)
            upstream = jnp.where(s == S - 1, parked, bwd_carry)
            gy_seed = jnp.where(
                jnp.logical_or(is_loss_unit, jnp.logical_not(b_valid)),
                jnp.zeros_like(upstream), upstream).astype(y.dtype)
            gl_seed = jnp.where(jnp.logical_and(is_loss_unit, b_valid),
                                jnp.float32(1.0), jnp.float32(0.0))
            (dx,) = vjp_x((gy_seed, gl_seed))
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(is_loss_unit, b_valid), loss_b, 0.0)
            dmicro = jnp.where(
                jnp.logical_and(jnp.logical_and(s == 0, r_b == 0), b_valid),
                lax.dynamic_update_index_in_dim(
                    dmicro, dx.astype(dmicro.dtype), m_b, 0),
                dmicro)
            gybuf = jnp.where(
                b_valid,
                lax.dynamic_update_index_in_dim(
                    gybuf, gy_seed.astype(mb_shape.dtype), u_b, 0), gybuf)
            glbuf = jnp.where(
                b_valid,
                lax.dynamic_update_index_in_dim(glbuf, gl_seed, u_b, 0),
                glbuf)

            # ---- W unit: k_w = t - vM - (S-1), stage-independent ----
            k_w = t - U - (S - 1)
            w_valid = jnp.logical_and(k_w >= 0, k_w < U)
            kw = jnp.clip(k_w, 0, U - 1)
            r_w, m_w = v - 1 - kw // M, kw % M
            u_w = r_w * M + m_w
            # stage 0 defers nothing (k_w == k_b there): use the fresh pair
            xw = jnp.where(
                s == 0, xb,
                lax.dynamic_index_in_dim(inbuf, u_w, 0, keepdims=False))
            gyw = jnp.where(
                s == 0, gy_seed.astype(mb_shape.dtype),
                lax.dynamic_index_in_dim(gybuf, u_w, 0, keepdims=False))
            glw = jnp.where(
                s == 0, gl_seed,
                lax.dynamic_index_in_dim(glbuf, u_w, 0, keepdims=False))
            rw_eff = jnp.where(s == 0, r_b, r_w)
            p_w = chunk(params_local, rw_eff)
            lbl_w = lax.dynamic_index_in_dim(lbls, m_w, 0, keepdims=False)
            lbl_w = jnp.where(s == 0, lbl_b, lbl_w)

            def fwd_loss_p(p_, lp_):
                y_ = stage_fn(p_, xw, *cs)
                return y_, loss_fn(y_, lbl_w, lp_)

            _, vjp_p = jax.vjp(fwd_loss_p, p_w, lparams)
            gp, glp = vjp_p((gyw.astype(y.dtype), glw))
            do_w = jnp.where(s == 0, b_valid, w_valid)
            gacc = jax.tree_util.tree_map(
                lambda a, g: lax.dynamic_update_index_in_dim(
                    a,
                    lax.dynamic_index_in_dim(a, rw_eff, 0, keepdims=False)
                    + jnp.where(do_w, g.astype(jnp.float32), 0.0),
                    rw_eff, 0),
                gacc, gp)
            glp_acc = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(do_w, g.astype(jnp.float32), 0.0),
                glp_acc, glp)

            # ---- ring hand-offs + chunk-transition parking ----
            fwd_carry = lax.ppermute(y, axis, fwd_perm)
            bwd_carry = lax.ppermute(dx.astype(mb_shape.dtype), axis,
                                     bwd_perm)
            # stage 0 parks the activation arriving from stage S-1's F
            # (unit f' = t - (S-1), chunks 0..v-2) for its next round
            fp = t - (S - 1)
            fpc = jnp.clip(fp, 0, U - 1)
            park_f = jnp.logical_and(
                s == 0, jnp.logical_and(fp >= 0, fp < U - M))
            circ_f = jnp.where(
                park_f,
                lax.dynamic_update_index_in_dim(circ_f, fwd_carry, fpc % M,
                                                0),
                circ_f)
            # stage S-1 parks the cotangent arriving from stage 0's B
            # (unit k_b0 = t - vM - (S-1), chunks v-1..1) for chunk r-1
            kb0 = t - U - (S - 1)
            kb0c = jnp.clip(kb0, 0, U - 1)
            r0 = v - 1 - kb0c // M
            park_bk = jnp.logical_and(
                s == S - 1,
                jnp.logical_and(jnp.logical_and(kb0 >= 0, kb0 < U), r0 >= 1))
            park_b = jnp.where(
                park_bk,
                lax.dynamic_update_index_in_dim(park_b, bwd_carry, kb0c % M,
                                                0),
                park_b)
            return (fwd_carry, bwd_carry, circ_f, park_b, inbuf, gybuf,
                    glbuf, dmicro, gacc, glp_acc, loss_acc), None

        carry = (fwd_carry, bwd_carry, circ_f, park_b, inbuf, gybuf, glbuf,
                 dmicro, gacc, glp_acc, loss_acc)
        carry, _ = lax.scan(tick, carry, jnp.arange(T))
        (_, _, _, _, _, _, _, dmicro, gacc, glp_acc, loss_acc) = carry

        loss = lax.psum(loss_acc, axis)
        glp = jax.tree_util.tree_map(lambda l: lax.psum(l, axis), glp_acc)
        dmicro = lax.psum(dmicro * (s == 0).astype(dmicro.dtype), axis)
        return loss, gacc, glp, dmicro

    in_specs = (jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                P(), P(), jax.tree_util.tree_map(lambda _: P(), loss_params),
                ) + tuple(P() for _ in consts)
    out_specs = (P(), jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                 jax.tree_util.tree_map(lambda _: P(), loss_params), P())
    return jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={axis}, check_vma=True,
                         )(stage_params, microbatches, labels, loss_params,
                           *consts)


def num_pipeline_ticks(num_micro: int, num_stages: int, virtual: int = 1,
                       schedule: str = "gpipe") -> int:
    if schedule in ("1f1b", "zbh1"):
        return 2 * num_stages + num_micro - 1
    if schedule == "zbvpp":
        return 2 * virtual * num_micro + num_stages - 1
    if virtual > 1:
        return virtual * num_micro + num_stages - 1
    return num_micro + num_stages - 1
