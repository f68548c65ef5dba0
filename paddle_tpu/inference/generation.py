"""Autoregressive generation over a paged KV cache — the serving decode loop.

The TPU-native counterpart of the reference's fused-multi-transformer serving
path (paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu +
masked_multihead_attention + AnalysisPredictor,
paddle/fluid/inference/api/analysis_predictor.h:105).

Structure — ONE jitted step function serves every serving phase:

- ``_step_fn`` is the single fused engine step: derive write slots in-jit
  from the block table, run every layer through the mixed-mode
  ``ragged_paged_attention`` kernel (the step's own K/V rows fold in with a
  causal mask — no separate prefill kernel, no analytic current-token
  merge), commit all layers' fresh KV in ONE pass at the end (the cache
  stays strictly read-only until then, which is what lets XLA alias the
  donated pool in place), then sample.  The layer loop is a ``lax.scan``
  over whole periods of the model's layer pattern (``models/
  decoder_spec.py``; a stack of identical layers is the period of one),
  over one stack of weights for each place in the period; the kernel
  reads the pool by layer number (and the grouped GEMMs each layer's own
  expert banks, which a model hands out unstacked and the scan's body
  picks by the period's number), and each layer's new K/V row is emitted
  as a scan output.
- The step is compiled per (sampling config, T) where T is the query-token
  bucket: T=1 is pure decode, T=prefill_bucket is a chunked-prefill /
  mixed step.  Both compile once; **warm steps never recompile** (asserted
  by ``paddle_tpu.jit.assert_no_recompiles`` in the serving tests) and all
  state arrays are fixed ``[max_batch]`` buckets.
- The mixed step multiplies only the tokens it holds: everything that is
  per token runs over a packed ``[rows, H]`` array of the step's live
  tokens, ``rows`` the smaller of two row buckets (``row_buckets``) that
  holds them; only the attention kernel keeps its ``[B, T]`` tile.
  A bucket's programs are one family under one name, compiled together
  when the engine first dispatches that T.
- Prefill IS the step: prompts stream through T-sized chunks with
  per-sequence ``q_lens`` raggedness, so a prefill chunk and concurrent
  decode rows ride one ``pallas_call`` (the ragged-paged-attention shape).
- A stack may state a state-space mixer beside its attention
  (``LayerKind.ssm``): a slot then holds a fixed recurrent state besides
  its pages (``kv_cache.RecurrentState``, riding with the pool, donated
  with it).  The layer scan CARRIES that state and each layer's
  ``ragged_ssd_update`` call updates its slots in place; a slot's state is
  zeroed on the device by the step that runs its first chunk and is not
  touched by a step in which the slot has no work.  A place whose token
  mixer is a recurrence INSTEAD of attention (``LayerKind.linear``, gated
  delta-rule linear attention: ``kernels/kda.py``) keeps such a state and
  NO pages: the pool and the commit count the layers that have pages, the
  recurrent state those that have a state (``kv_cache.LayerPlanes``).
- EOS / budget / capacity tracking lives ON DEVICE (``finished``,
  ``gen_counts``, ``budgets``): the host loop is one async jit dispatch
  per step.  Each dispatch starts the copy of ITS OWN results to the
  host, and every ``step()`` gathers the steps that have landed, oldest
  first, without waiting; the host waits for the device only when
  ``MAX_STEPS_IN_FLIGHT`` steps are out, when it has nothing to dispatch,
  or when a caller needs a settled engine (``_gather``).  There is no
  cadence: a token reaches its request one step after it was sampled.
  (``LlamaGenerator.generate``'s own loop probes every ``sync_every``
  steps; no engine reads that number.)

Static shapes throughout: fixed [max_batch] rows, fixed chunk buckets, a
fixed block-table width and two row buckets keep the compile count at
three programs per sampling config.
"""

from __future__ import annotations

import functools
import math
import time
from collections import Counter, deque
from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import flags
from .. import observability as _obs
from ..observability import startup as _startup
from ..kernels.grouped_matmul import take_sentinel_rows
from ..kernels.paged_attention import ragged_paged_attention
from ..kernels.paged_attention_latent import (
    latent_row_tile, ragged_paged_attention_latent,
    ragged_paged_attention_latent_sparse)
from ..kernels.paged_geometry import (attn_rows, kernel_geometry_error,
                                      page_copies)
from ..kernels.paged_pool_writes import (write_kv_pages_all_layers,
                                         write_kv_pages_all_layers_quantized,
                                         write_latent_pages_all_layers)
from ..kernels.latent_index import latent_index_scores, latent_index_select
from ..kernels.rms_norm import layer_norm_fp32, rms_norm_fp32
from ..kernels.kda import (kda_geometry_error, l2_normalised,
                           ragged_kda_update, rows_of_slots)
from ..kernels.ssd import ragged_ssd_update
from ..models.decoder_spec import EXPERT_BANKS
from . import speculative as _sp
from .kv_cache import LayerPlanes, PagedKVCache, RecurrentState

# The serving tensor-parallel mesh axis (FLAGS_serving_tensor_parallel).
# Every axis-name string reaching a shard_map-wrapped body must come
# from this constant (jaxlint JL008): a hard-coded "mp" that drifts from
# the mesh construction is a silent wrong-axis collective.
MP_AXIS = "mp"

# The fewest rows a mixed step's per-token GEMMs are compiled for.  Not a
# setting: under peak FLOP/s / HBM bandwidth rows (v5e: 197e12 / 819e9 =
# 240) a bf16 GEMM is bound by reading its weights, which costs the same
# at any row count, so a smaller bucket buys compile time and no speed.
MIN_GEMM_ROWS = 256
# The packed member of a step family holds a quarter of the dense grid.
# One packed member, not a ladder of them: every member is lowered and
# loaded before the first step runs, cache hit or not, and the Mixtral
# step costs 1.1 s a member warm (v5e, PR 25: with four members `setup_s`
# read 25.8 s for 20.1, with three 21.0 for 17.9).  A quarter: a mixed step
# holds a few prefilling slots' chunks and one token of each other slot,
# so steady traffic stays under it; a burst takes the dense member.
PACKED_GRID_SHARE = 4
# The most engine steps in flight: dispatched, their results not yet
# gathered by the host.  Not a setting: one step has to be queued behind
# the one that runs for the device never to wait for the host (the host's
# work a step is a fifth of a device step or less in every cell), and a
# second queued step bought nothing on the chip (PERF.md section 6, PR
# 37), while every step in flight is one more a finished slot waits to be
# refilled and one more whose tokens the host has not counted.
MAX_STEPS_IN_FLIGHT = 2
# Why a gather waits for the device: the queue is at its bound, there is
# nothing to dispatch, or a caller needs a settled engine.
GATHER_BLOCKS = ("bound", "idle", "settle")


def _pack_plan(ql, T, rows):
    """The gather maps between a step's ``[B, T]`` query places and the
    ``rows`` packed rows its per-token work runs over — derived on the
    device from ``ql`` like the write slots are from the block table.

    Slot b's tokens fill packed rows ``[starts[b], starts[b] + ql[b])``,
    ``starts = cumsum(ql) - ql``; rows from ``sum(ql)`` on are padding.
    Returns ``(live [rows], src [rows], dst [B, T])``: ``src[p]`` the flat
    ``b * T + t`` place of packed row p, ``dst[b, t]`` the packed row of a
    place, ``rows`` for a place past ``ql[b]`` (callers read zero there).
    Both ways are pure gathers (the ``sorted_dispatch_plan`` idiom)."""
    i32 = jnp.int32
    ql = ql.astype(i32)
    ends = jnp.cumsum(ql)
    starts = ends - ql
    p = jnp.arange(rows, dtype=i32)
    live = p < ends[-1]
    b = jnp.minimum((p[:, None] >= ends[None, :]).sum(axis=1),
                    ql.shape[0] - 1).astype(i32)
    t = jnp.clip(p - jnp.take(starts, b), 0, T - 1)
    offs = jnp.arange(T, dtype=i32)
    dst = jnp.where(offs[None, :] < ql[:, None],
                    starts[:, None] + offs[None, :], rows)
    return live, b * T + t, dst


def _cow_copy_pages(cache, src, dst, page_axes=(1,)):
    """Whole-page KV copies src[i] -> dst[i] across every layer/head (the
    prefix cache's copy-on-write privatization).  Entries with src < 0
    are no-ops: their dst is routed out of bounds, which scatter drops.
    Jitted once per engine over the fixed [max_batch] pair bucket and
    donated like the step, so warm hit admissions never recompile.

    ``cache`` is the pool tuple — ``(kv,)`` float, ``(kv, k_scale,
    v_scale)`` int8 (an int8 COW moves 4x fewer bytes), a latent pool's
    ``(k, v)`` — and ``page_axes`` the axis of each array that counts
    pages (``PagedKVCache.page_axes``), so one loop copies them all."""
    valid = src >= 0
    s = jnp.maximum(src, 0)
    out = []
    for arr, page_axis in zip(cache, page_axes):
        lead = (slice(None),) * page_axis
        d = jnp.where(valid, dst, arr.shape[page_axis])
        out.append(arr.at[lead + (d,)].set(jnp.take(arr, s, axis=page_axis),
                                           mode="drop"))
    return tuple(out)


@dataclass
class GenerationConfig:
    max_new_tokens: int = 128
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0            # 0 = disabled
    top_p: float = 1.0        # 1.0 = disabled
    eos_token_id: Optional[int] = None
    seed: int = 0

    def _key(self):
        return (self.do_sample, self.temperature, self.top_k, self.top_p,
                self.eos_token_id)


def _rope_bt(x, cos, sin):
    """Rotary embedding with per-(row, token) tables.

    x: [B, T, h, d]; cos/sin: [B, T, d/2]."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


def _index_inputs(y, c_q, lp, ix, eps, cos, sin):
    """What a learned index (``models.decoder_spec.LatentIndex``) scores
    with, from a layer's normed input ``y`` and query latent ``c_q`` (both
    ``[B, T, width]``): the index queries ``[B, T, heads, dim]``, the ONE
    index key a token ``[B, T, dim]`` (LayerNorm with weight and bias), both
    rotated on their first ``ix.rope`` numbers, and the heads' weights ``[B,
    T, heads]``."""
    def rotated(a):
        return jnp.concatenate([_rope_bt(a[..., :ix.rope], cos, sin),
                                a[..., ix.rope:]], axis=-1)

    q_i = rotated((c_q @ lp["self_attn.indexer.wq_b.weight"]).reshape(
        *c_q.shape[:-1], ix.heads, ix.dim))
    k_i = rotated(layer_norm_fp32(
        y @ lp["self_attn.indexer.wk.weight"],
        lp["self_attn.indexer.k_norm.weight"], eps,
        bias=lp["self_attn.indexer.k_norm.bias"])[..., None, :])[..., 0, :]
    return q_i, k_i, y @ lp["self_attn.indexer.weights_proj.weight"]


def _scaled(a, scale: float):
    """``a`` times a model's constant multiplier, the product taken in
    float32 and rounded once (1.0: ``a`` itself, nothing traced)."""
    if scale == 1.0:
        return a
    return (a.astype(jnp.float32) * scale).astype(a.dtype)


def _moe_choice(y, lp, moe):
    """The router of an expert mixture on the tensor it reads, ``y [..., H]``
    (``MoeSpec.router_input``: the FFN's normed input or the attention's):
    ``(topv, topi)``, each ``[N, top_k]`` over the flattened rows: the
    gates in float32 (softmax or sigmoid scores, the ``top_k`` largest,
    renormalised; a selecting bias, a gate scale and a group-limited choice
    where ``moe`` states them) and the experts chosen.  It depends on ``y``
    and the router's weights alone, so a model whose router reads the
    attention's input makes it before the attention call."""
    from ..models import llama as _llama

    with jax.named_scope("router"):
        topv, topi, _, _ = _llama._route_topk(
            y.reshape(-1, y.shape[-1]), lp["mlp.gate.weight"], moe.top_k,
            moe.score, bias=lp["mlp.gate.bias"] if moe.select_bias else None,
            scale=moe.gate_scale, groups=moe.groups,
            groups_kept=moe.groups_kept)
    return topv, topi


def _moe_ffn(y, lp, moe, mp_shards=None, live=None, layer=None):
    """Routed expert mixture for the serving path where router and experts
    read ONE tensor ``y``: ``_moe_experts`` on ``_moe_choice``'s choice
    (the rows are flattened once, for both)."""
    xf = y.reshape(-1, y.shape[-1])
    out, rows = _moe_experts(xf, lp, moe, _moe_choice(xf, lp, moe),
                             mp_shards=mp_shards, live=live, layer=layer)
    return out.reshape(y.shape), rows


def _moe_experts(y, lp, moe, choice, mp_shards=None, live=None, layer=None):
    """The experts of a routed mixture on THEIR input ``y`` for a choice
    already made (``choice = (topv, topi)`` of ``_moe_choice``, over the
    same rows) (reference: incubate fused_moe inference semantics), one
    function for every family: ``moe`` (``models.decoder_spec.MoeSpec``)
    states the experts held here, their gated activation (``W_down
    (act(W_gate y) * (W_up y))``, SiLU or ReLU) and the shared experts
    (SwiGLU).  Returns ``(out, rows)``,
    ``rows`` int32 ``[entries that fell on held experts, rows of the tiles
    laid out for them]`` where this chip holds a share of the experts or
    the grouped path runs on one device (every expert held: the entries of
    the rows that hold a token, and a third number, the entries of the
    fullest expert), else None.

    - grouped (``moe.dispatch == "grouped"``): the expert-sorted ragged-GEMM
      path shared with training (``models.llama._grouped_ffn``) — each
      expert runs over exactly its own rows, E/top_k-fold fewer FFN FLOPs
      than the dense mixture.  Serves prefill chunks AND decode steps: the
      row tile shrinks to fit the actual (token, choice) entry count so a
      decode batch doesn't pay a full ``block_m`` of padding per expert.
    - dense: every held expert runs under a lax.scan over all rows,
      combined with top-k gate weights — exact routing, no capacity,
      transients bounded to one expert.

    A share of the experts (``moe.partial``): the router keeps its
    published width; entries routed to experts this chip does not hold
    are dropped BEFORE rows are laid out (they sort behind the held
    experts' tiles, which alone are multiplied: ``gmm(live_tiles=)``) and
    a gate keeps the value it has over all ``top_k`` chosen.  Dropless:
    every entry of a held expert is computed, whatever their number.

    ``live`` (bool, one a row of ``y``; both grouped arms on one device,
    not the tensor-parallel one): rows that hold no token (the padding of
    a step's row bucket, the empty places of the dense grid) are routed
    nowhere and counted nowhere.  Where every expert is held their entries
    are dropped in the plan (``masked_dispatch_plan``) and the tile
    table names the tiles left without rows, which ``gmm`` skips.

    ``layer`` (an int32 device scalar; the engine's layer scan): ``lp``'s
    three expert banks are then tuples, one ``[E, ...]`` bank for each
    layer of the place, and the layer is number ``layer`` of them.  The
    experts run under a ``lax.switch`` over the layers, each branch on its
    own layer's arrays: a whole array is read where it lies, while a layer
    indexed out of a stack would be written out first for the kernel.

    ``mp_shards`` > 1 (tensor-parallel serving, inside a shard_map body):
    each shard runs the grouped path over its own E/mp expert bank —
    non-owned (token, choice) entries route to a local discard group
    whose rows the combine's sentinel read returns as zero — and the
    partial outputs are all-gathered and summed in fixed shard order.
    Bit-identical to the single-device mixture: a token has at most
    ``top_k`` nonzero expert terms, every other shard contributes an
    exact +0.0, and IEEE addition of two values is order-insensitive
    bitwise for top_k <= 2 (the caller only enables sharding then).
    SiLU experts only: this arm runs training's differentiable
    ``_grouped_ffn``.
    """
    from ..models import llama as _llama

    topv, topi = choice
    act = _llama._GATE_ACTIVATIONS[moe.activation]
    shape = y.shape
    xf = y.reshape(-1, shape[-1])
    N = xf.shape[0]
    top_k, E, held = moe.top_k, moe.num_experts, moe.held
    rows = None
    banks = tuple(lp[name] for name in EXPERT_BANKS)

    def on_banks(fn):
        """``fn(w_gate, w_up, w_down)`` on this layer's banks."""
        if layer is None:
            return fn(*banks)
        return jax.lax.switch(layer, [functools.partial(fn, *ws)
                                      for ws in zip(*banks)])

    if moe.partial:
        local = topi - moe.offset
        own = jnp.logical_and(local >= 0, local < held)       # [N, k]
        if live is not None:
            own = jnp.logical_and(own, live.reshape(N, 1))
    if moe.dispatch == "grouped":
        from ..kernels.grouped_matmul import (masked_dispatch_plan,
                                              sorted_dispatch_plan)

        # decode batches carry a handful of rows: shrink the row tile to
        # the 8-row sublane multiple that covers them (same math, less pad)
        bm = max(8, min(moe.block_m, -(-N * top_k // 8) * 8))
        if mp_shards and mp_shards > 1:
            if moe.activation != "silu":
                raise ValueError(
                    f"{moe.activation!r} experts under tensor parallelism: "
                    "the sharded arm runs training's _grouped_ffn, whose "
                    "backward is written for \"silu\" alone")
            E_loc = E // mp_shards
            my = jax.lax.axis_index(MP_AXIS)
            own = (topi // E_loc) == my
            # non-owned entries dispatch to local expert E_loc — a
            # discard group appended to the shard's bank purely as a
            # sort destination; its rows never reach the combine
            local_e = jnp.where(own, topi % E_loc, E_loc).reshape(N * top_k)
            inv, pos, tg = sorted_dispatch_plan(local_e, E_loc + 1, bm)
            M = inv.shape[0]
            own_flat = own.reshape(N * top_k)
            inv = jnp.where(
                (inv < N * top_k)
                & jnp.take(own_flat, jnp.minimum(inv, N * top_k - 1)),
                inv, N * top_k)
            keep = (pos < M) & own_flat
            gates = topv * keep.reshape(N, top_k)
            pos = jnp.where(keep, pos, M)      # sentinel row reads zero
            tg = jnp.minimum(tg, E_loc - 1)

            def _loc(w):
                return jax.lax.dynamic_slice_in_dim(
                    w, my * E_loc, E_loc, axis=0)

            with jax.named_scope("experts"):
                part = on_banks(lambda wg, wu, wd: _llama._grouped_ffn(
                    xf, _loc(wg), _loc(wu), _loc(wd),
                    gates, inv, pos, tg, E_loc, top_k, bm))
            parts = jax.lax.all_gather(part, MP_AXIS, axis=0)  # [mp, N, H]
            # explicit left-assoc shard-order sum — NEVER psum, whose
            # reduction order XLA leaves unspecified
            out = parts[0]
            for s in range(1, mp_shards):
                out = out + parts[s]
        elif moe.partial:
            with jax.named_scope("experts"):
                F = N * top_k
                # entries of experts held elsewhere sort into a discard
                # group behind the held experts' tiles
                local_e = jnp.where(own, local, held).reshape(F)
                inv, pos, tg = sorted_dispatch_plan(local_e, held + 1, bm)
                M = inv.shape[0]
                per = jnp.bincount(local_e, length=held + 1)[:held]
                live_rows = (jnp.maximum(-(-per // bm), 1) * bm).sum() \
                    .astype(jnp.int32)
                inv = jnp.where(jnp.arange(M) < live_rows, inv, F)
                own_flat = own.reshape(F)
                pos = jnp.where(own_flat, pos, M)  # sentinel row reads zero
                out = on_banks(lambda wg, wu, wd: _llama._grouped_ffn_fwd(
                    xf, wg, wu, wd, topv * own, inv, pos,
                    jnp.minimum(tg, held - 1), held, top_k, bm,
                    live_tiles=live_rows // bm,
                    activation=moe.activation)[0])
                rows = jnp.stack([own_flat.sum().astype(jnp.int32),
                                  live_rows])
        else:
            with jax.named_scope("experts"):
                # every expert is held: only the entries of rows without
                # a token are dropped, and the table names the dead tiles
                real = jnp.ones((N, top_k), bool) if live is None else \
                    jnp.broadcast_to(live.reshape(N, 1), (N, top_k))
                inv, pos, tg, live_tiles, per_expert = masked_dispatch_plan(
                    topi.reshape(N * top_k), real.reshape(N * top_k), E, bm)
                out = on_banks(lambda wg, wu, wd: _llama._grouped_ffn_fwd(
                    xf, wg, wu, wd, topv * real, inv, pos, tg, E, top_k, bm,
                    dead_in_table=True, activation=moe.activation)[0])
                rows = jnp.stack([real.sum().astype(jnp.int32),
                                  live_tiles * bm,
                                  per_expert.max().astype(jnp.int32)])
    else:
        with jax.named_scope("router"):
            comb = jnp.zeros((N, E), jnp.float32).at[
                jnp.arange(N)[:, None], topi].set(topv)
            if moe.partial:
                comb = comb[:, moe.offset:moe.offset + held]
                rows = jnp.stack([own.sum().astype(jnp.int32),
                                  jnp.int32(N * held)])

        def step(acc, ex):
            h = act(xf @ ex["wg"]) * (xf @ ex["wu"])
            return acc + ex["c"][:, None].astype(acc.dtype) \
                * (h @ ex["wd"]), None

        with jax.named_scope("experts"):
            acc0 = jnp.zeros(xf.shape, xf.dtype)
            out = on_banks(lambda wg, wu, wd: jax.lax.scan(step, acc0, {
                "wg": wg, "wu": wu, "wd": wd,
                "c": comb.T.astype(xf.dtype)})[0])
    if moe.shared:
        # the shared experts, side by side along the width ([H, S * I] and
        # [S * I, H]): ordinary dense GEMMs over the same rows, whose
        # down-projection adds the S outputs up
        with jax.named_scope("shared_experts"):
            act = jax.nn.silu(xf @ lp["mlp.shared_gate_proj.weight"]) * \
                (xf @ lp["mlp.shared_up_proj.weight"])
            sh = act @ lp["mlp.shared_down_proj.weight"]
            out = out + sh * jnp.asarray(1.0 / moe.shared, sh.dtype)
    return out.reshape(shape), rows


def _filter_logits(logits, gc: GenerationConfig):
    """Temperature / top-k / top-p logit filtering ([N, V] fp32)."""
    logits = logits / max(gc.temperature, 1e-6)
    if gc.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -gc.top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if gc.top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with mass >= top_p (always >= 1 token)
        cutoff_idx = jnp.sum(cum < gc.top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_l, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


@jax.named_scope("sampling")
def _sample(logits, key, pos, gc: GenerationConfig):
    """logits: [N, V] fp32, pos: [N] int32 → [N] int32 (traced; gc
    fields are static).

    Sampling keys are POSITIONAL (ISSUE 15 satellite): row n draws with
    ``fold_in(key, pos[n])`` where ``pos`` is the sequence index of the
    token being sampled and ``key`` is the engine's never-advancing
    ``jax.random.key(seed)``.  A draw is therefore a pure function of
    (seed, token index, logits) — batch composition, step count, drain
    cadence, and cross-replica replay never perturb a request's sampled
    stream, which is exactly what lets a journaled failover resume (and
    a migrated sampled session) continue seed-deterministically on a
    survivor with the same config.  Greedy ignores the key entirely."""
    if not gc.do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _filter_logits(logits, gc)
    keys = jax.vmap(lambda p: jax.random.fold_in(key, p))(pos)
    draw = jax.vmap(lambda k, row: jax.random.categorical(k, row))
    return draw(keys, logits).astype(jnp.int32)


class LlamaGenerator:
    """Batch text generation with paged KV for any decoder that states its
    ``decoder_spec()`` and hands out its ``serving_params()``
    (``models/decoder_spec.py``): what differs between the families (layer
    pattern, norm, block form, rotary pairing, windows, head size, router,
    shared and held experts, tied head) is data of the model here."""

    def __init__(self, model, *, max_batch: int = 8,
                 max_seq_len: Optional[int] = None, page_size: int = 32,
                 cache_dtype: Optional[str] = None,
                 prefill_bucket: int = 64, sync_every: int = 8,
                 num_pages: Optional[int] = None,
                 tensor_parallel: Optional[int] = None):
        self.config = model.config
        c = self.spec = model.decoder_spec()
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len or model.config.max_position_embeddings
        # tensor-parallel serving (FLAGS_serving_tensor_parallel): tp > 1
        # shards the whole fused step over the `mp` mesh axis — attention
        # by kv-head, grouped MoE by expert, everything else replicated —
        # with per-shard KV page storage under host-global page ids
        if tensor_parallel is None:
            tensor_parallel = int(flags.flag("serving_tensor_parallel") or 1)
        tp = max(int(tensor_parallel), 1)
        la = c.latent
        if tp > 1 and la is not None:
            raise ValueError(
                "tensor_parallel > 1 shards the pool by KV head; a latent "
                "pool has none (inference/kv_cache.py)")
        if tp > 1 and c.state_mixer is not None:
            raise ValueError(
                "tensor_parallel > 1 shards the pool by KV head; the "
                "recurrent state beside it has no sharded layout "
                "(inference/kv_cache.py)")
        if tp > 1:
            if len(jax.devices()) < tp:
                raise ValueError(
                    f"tensor_parallel={tp} needs {tp} devices, have "
                    f"{len(jax.devices())}")
            if c.num_kv_heads % tp or c.num_heads % tp:
                raise ValueError(
                    f"tensor_parallel={tp} must divide num_kv_heads="
                    f"{c.num_kv_heads} and num_heads={c.num_heads}")
            self.mesh = jax.sharding.Mesh(
                np.asarray(jax.devices()[:tp]), (MP_AXIS,))
        else:
            self.mesh = None
        self.tp = tp
        # grouped MoE shards by expert only where the discard-group
        # combine is provably bit-exact (top_k <= 2: at most two nonzero
        # terms per token, IEEE pairwise-commutative) and the bank
        # divides; otherwise the mixture stays replicated under tp (as it
        # does where this chip holds a share of the experts already)
        moe = c.moe
        self._moe_shards = tp if (
            tp > 1 and moe is not None and moe.dispatch == "grouped"
            and moe.top_k <= 2 and not moe.partial
            and moe.activation == "silu"
            and moe.num_experts % tp == 0) else None
        # where ``_moe_ffn`` counts them the step also returns how many
        # entries fell on held experts and the rows laid out for them
        self.counts_moe_rows = moe is not None and (
            moe.partial or (moe.dispatch == "grouped"
                            and not self._moe_shards))
        self._layers_by_window = tuple(Counter(c.windows).items())
        dtype = str(model.config.dtype)
        if cache_dtype is None:
            # FLAGS_kv_cache_dtype: "auto" follows the model dtype;
            # "int8" turns on the quantized memory plane (ISSUE 13)
            fd = flags.flag("kv_cache_dtype")
            cache_dtype = None if fd == "auto" else fd
        cache_dtype = {"fp32": "float32", "bf16": "bfloat16"}.get(
            cache_dtype, cache_dtype)
        page_size = int(page_size)
        self.page_size = page_size
        self.prefill_bucket = min(prefill_bucket, self.max_seq_len)
        # generate()'s all-done probe alone reads it: the engine has no cadence
        self.sync_every = sync_every
        self.pages_per_seq = -(-self.max_seq_len // page_size)

        # the model's own layout for the engine's scan: a tuple with one
        # dict of [periods, ...] stacks for each place in the layer pattern
        with _startup.phase("startup.stack_params"):
            self.params = model.serving_params()
        if len(self.params["blocks"]) != len(c.pattern):
            raise ValueError(
                f"serving_params() has {len(self.params['blocks'])} block "
                f"stacks, the layer pattern {len(c.pattern)} places")
        if len(self.params.get("leading", ())) != len(c.leading):
            raise ValueError(
                f"serving_params() has {len(self.params.get('leading', ()))}"
                f" leading layers, the spec states {len(c.leading)}")
        if self.mesh is not None:
            # the step's shard_map takes the weights replicated: place them
            # on every device of the mesh ONCE, or each dispatch re-copies
            # the first device's (uncommitted) arrays to the other shards
            self.params = jax.device_put(
                self.params, jax.sharding.NamedSharding(
                    self.mesh, jax.sharding.PartitionSpec()))
        # the KV pool: ``num_pages`` may be smaller than the dense
        # max_batch x pages_per_seq worst case — sequences share the pool
        # through the free-list allocator; admission blocks on pressure
        # and a sequence whose mid-decode growth finds the pool dry is
        # finalized early (engine._drain caps its output) — never a crash
        self.num_pages = num_pages or max_batch * self.pages_per_seq
        if jax.default_backend() == "tpu":
            # the step's attention is the Pallas kernel or nothing on a
            # chip: refuse a geometry it does not cover now, not mid-trace
            pool_dtype = str(cache_dtype or dtype)
            why = kernel_geometry_error(
                page_size, c.head_dim, quantized=pool_dtype == "int8",
                dtype=pool_dtype,
                kv_heads=c.num_kv_heads // tp,
                num_pages=self.num_pages,
                table_shape=(max_batch, self.pages_per_seq),
                latent=None if la is None else (la.rank, la.rope))
            if why:
                raise ValueError(
                    f"engine geometry is not served by the paged-attention "
                    f"kernel on TPU: {why}")
        if jax.default_backend() == "tpu" and c.linear is not None:
            why = kda_geometry_error(c.linear.heads, c.linear.key_dim,
                                     c.linear.value_dim)
            if why:
                raise ValueError(
                    f"the linear-attention places are not served by the "
                    f"delta-rule kernel on TPU: {why}")
        if c.leading and str(cache_dtype or dtype) == "int8" \
                and la is None:
            raise ValueError("an int8 pool's scale planes are scanned by "
                             "whole periods: no leading layers")
        if c.state_mixer is not None and str(cache_dtype or dtype) == "int8":
            raise ValueError(
                "inference/kv_cache.py: kv_cache_dtype int8 quantises "
                "pages; a stack with a recurrent state is served with a "
                "float pool (auto, bf16 or fp32)")
        # a uniform pool over the layers that keep pages: each keeps every
        # page, whatever its window (pages behind a sliding layer's window
        # are held and never read); a latent stack's holds one row a token
        # a layer, no head axis.  A linear-attention place keeps none: its
        # layers own no plane of the pool (``LayerPlanes``)
        self.planes = LayerPlanes.of(c)
        latent = None if la is None else (la.rank, la.rope)
        # a learned index keeps one key a token a layer beside that row
        index = None if c.index is None else c.index.dim
        # what a slot holds besides pages (a mixer's state and convolution
        # rows, over the layers that have one): fixed, by slot, riding with
        # the pool
        with _startup.phase("startup.pool_alloc", pages=self.num_pages):
            recurrent = None if c.state_mixer is None else RecurrentState(
                c.state_mixer, c.state_layers, max_batch, dtype)
            self.cache = PagedKVCache(
                num_layers=c.page_layers,
                num_pages=self.num_pages,
                page_size=page_size, num_kv_heads=c.num_kv_heads,
                head_dim=c.head_dim, dtype=cache_dtype or dtype,
                mesh=self.mesh, axis=MP_AXIS, latent=latent,
                recurrent=recurrent, index=index)
        self.state_bytes_per_slot = 0 if recurrent is None else \
            RecurrentState.bytes_per_slot(c.state_mixer, c.state_layers,
                                          dtype)
        # host-global pool bytes (all shards) — advertised via stats() /
        # /statusz so the router's capacity-weighted placement can rank
        # heterogeneous replicas
        self.pool_bytes = self.num_pages * PagedKVCache.bytes_per_page(
            c.page_layers, c.num_kv_heads, page_size,
            c.head_dim, cache_dtype or dtype, latent=latent, index=index)
        # what ONE descriptor of the paged call moves: a page's K and V of
        # every KV head this shard holds, one layer (a latent call copies a
        # page's compressed rows as two halves and its rotary keys apart:
        # the largest of the three)
        itemsize = jnp.dtype(self.cache.dtype).itemsize
        self.kv_copy_bytes = (
            page_size // 2 * la.rank if la is not None
            else 2 * (c.num_kv_heads // tp) * page_size * c.head_dim) \
            * itemsize
        if _obs.metrics_enabled():
            from ..observability import metrics as _metrics
            _metrics.gauge("serving.kv_copy_bytes").set(self.kv_copy_bytes)
            _metrics.gauge("serving.tp.degree").set(tp)
            _metrics.gauge("serving.tp.shard_pool_bytes").set(
                self.pool_bytes // tp)
            _metrics.gauge("serving.kv_bytes_per_token").set(
                self.pool_bytes // (self.num_pages * page_size))
            _metrics.gauge("serving.state_bytes_per_slot").set(
                self.state_bytes_per_slot)
            _metrics.gauge("serving.index_bytes_per_token").set(
                c.num_layers * (index or 0) * itemsize)
        # over the part of a head that rotates, with the yarn blend where
        # the spec states one (``DecoderSpec.rope_tables``)
        cos, sin = map(jnp.asarray, c.rope_tables(self.max_seq_len))
        self._cos, self._sin = cos, sin
        self._jit_cache = {}
        self._metrics_on = _obs.metrics_enabled()

    def kv_read_tokens(self, rows) -> int:
        """Key tokens the step's attention reads for ``rows`` = [(query
        tokens, context before them)], summed over the layers with each
        layer's window applied (a windowed layer reads from the first key
        its earliest query token sees)."""
        n = 0
        for window, layers in self._layers_by_window:
            for q, ctx in rows:
                first = 0 if window is None else max(0, ctx + 1 - window)
                n += layers * (ctx + q - first)
        return n

    def attn_rows(self, t, rows) -> int:
        """Query rows the paged kernel's row tiles cover for each KV head
        in one layer's call of a step with bucket ``t`` over ``rows`` =
        [(query tokens, context before them)]: the kernel's own tile
        arithmetic (the latent call's own tile in a latent stack), so
        ``q_tokens x group / attn_rows`` is the occupancy its tiles see."""
        c = self.spec
        group = c.num_heads // c.num_kv_heads
        tile = None if c.latent is None else latent_row_tile(
            t, group, c.index is not None)
        return attn_rows([q for q, _ in rows], t, group, tile)

    def page_copies(self, rows) -> int:
        """DMA descriptors one layer's paged call starts for ``rows`` =
        [(query tokens, context before them)]: the kernel's own host
        arithmetic (working slots x the blocks their walk reaches x the
        pages of a block, whole blocks whatever the context holds of them),
        for a layer that sees the whole context where the stack has one,
        else the layer with the widest window.  A latent call starts three
        copies a page (its compressed rows as two halves, its rotary
        keys)."""
        windows = [w for w, _ in self._layers_by_window]
        n = page_copies(rows, self.page_size, self.pages_per_seq,
                        0 if self.spec.latent is not None
                        else self.kv_copy_bytes,
                        None if None in windows else max(windows))
        return 3 * n if self.spec.latent is not None else n

    def index_counts(self, rows) -> tuple:
        """What a learned index does in ONE layer of a step over ``rows`` =
        [(query tokens, context before them)]: the pairs ``(t, s)`` scored
        (``q x ctx + q (q + 1) / 2`` a working slot) and the keys chosen
        (``min(position + 1, top_k)`` a query token)."""
        top_k = self.spec.index.top_k
        pairs = chosen = 0
        for q, ctx in rows:
            pairs += q * ctx + q * (q + 1) // 2
            # positions ctx .. ctx + q - 1: those under top_k choose all
            low = min(max(top_k - ctx, 0), q)
            chosen += low * ctx + low * (low + 1) // 2 + (q - low) * top_k
        return pairs, chosen

    def attention_counts(self, t, rows) -> dict:
        """The three above as ``engine.step``'s arguments (and a learned
        index's two).  Each is a loop over the working slots: the step
        computes them only while somebody listens
        (``Tracer.listening``)."""
        out = {"kv_read_tokens": self.kv_read_tokens(rows),
               "attn_rows": self.attn_rows(t, rows),
               "page_copies": self.page_copies(rows)}
        if self.spec.index is not None:
            out["index_pairs"], out["selected_keys"] = \
                self.index_counts(rows)
        return out

    def _head_logits(self, params, h):
        """float32 logits of hidden states ``h [..., H]``: the head, or the
        embedding itself where the model ties them (no transposed copy)."""
        with jax.named_scope("head"):
            if "head" in params:
                logits = (h @ params["head"]).astype(jnp.float32)
            else:
                logits = jnp.einsum("...h,vh->...v", h,
                                    params["embed"]).astype(jnp.float32)
        return _scaled(logits, self.spec.logit_scale)

    def _tp_jit(self, fn, name, n_in, n_out, out_cache_idx):
        """jit one engine program under ``name`` (a profiler trace's ``XLA
        Modules`` line then reads ``jit_<name>``, whatever ``fn`` is
        wrapped in), shard_map-wrapping it over the ``mp``
        mesh when tensor-parallel: the cache tuple (arg 1 in, index
        ``out_cache_idx`` out) rides the pool's per-shard kv-head specs,
        every other operand — weights, tokens, masks, the PRNG key — is
        replicated.  Still ONE jitted program per bucket; pool donation
        passes through jit(shard_map) unchanged, so warm tp steps keep
        the 0-compile / 0-sync contract.

        ``check_vma=False`` on purpose: the body holds ``pallas_call``s
        (whose outputs carry no varying-axes type) and rebuilds every
        replicated output itself with ``all_gather``, which the checker
        cannot follow; tp=1 vs tp=N bit-match tests guard it instead."""
        if self.tp > 1:
            from jax.sharding import PartitionSpec
            rep = PartitionSpec()
            cspec = self.cache.pspecs
            in_specs = tuple(cspec if i == 1 else rep for i in range(n_in))
            out_specs = tuple(cspec if i == out_cache_idx else rep
                              for i in range(n_out))
            fn = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
        return jax.jit(_obs.tracing.named(fn, name), donate_argnums=(1,))

    def pool_jit(self, fn, name, n_extra):
        """jit a pool-maintenance program ``fn(cache, *extras) -> cache``
        (COW page copies, spill swap-ins) under ``name``, with the pool
        donated — shard_map-wrapped like the step when tensor-parallel,
        extras replicated."""
        if self.tp > 1:
            from jax.sharding import PartitionSpec
            rep = PartitionSpec()
            cspec = self.cache.pspecs
            fn = jax.shard_map(fn, mesh=self.mesh,
                               in_specs=(cspec,) + (rep,) * n_extra,
                               out_specs=cspec, check_vma=False)
        return jax.jit(_obs.tracing.named(fn, name), donate_argnums=(0,))

    def row_buckets(self, t: int) -> List[int]:
        """The row counts a q bucket's step program is compiled for: a
        ``PACKED_GRID_SHARE``-th of the dense ``max_batch * t`` grid, not
        under ``MIN_GEMM_ROWS``, and the grid itself (T=1 and small
        engines: the grid alone)."""
        full = self.max_batch * t
        packed = max(MIN_GEMM_ROWS, full // PACKED_GRID_SHARE)
        return [packed, full] if packed < full else [full]

    def gemm_rows(self, t: int, q_tokens: int) -> int:
        """Rows of the step's per-token GEMMs: the smallest of
        ``row_buckets(t)`` that holds ``q_tokens``."""
        return next(n for n in self.row_buckets(t) if n >= q_tokens)

    def _step_jit(self, gc: GenerationConfig, t: int, track_recent=False,
                  rows: Optional[int] = None):
        """The fused serving step, jitted for (sampling config, q bucket,
        GEMM rows).  ``rows`` is one of ``row_buckets(t)``; ``None`` or
        ``max_batch * t`` is the dense program, with no pack or unpack
        operation in it.  Every member of a bucket's family carries the
        same name.  ``track_recent`` (ngram spec engines) threads the
        drafter's recent-token ring through the step as extra chained
        state."""
        rows = min(rows or self.max_batch * t, self.max_batch * t)
        key = (gc._key(), t, bool(track_recent), rows)
        if key not in self._jit_cache:
            import functools
            track = bool(track_recent)
            self._jit_cache[key] = self._tp_jit(
                functools.partial(self._step_fn, gc, t, track, rows),
                f"serve_step_T{t}",
                n_in=13 if track else 12,
                n_out=(8 if track else 7) + int(self.counts_moe_rows),
                out_cache_idx=5)
        return self._jit_cache[key]

    def _spec_jit(self, gc: GenerationConfig, k: int, nmax: int):
        """The T=K speculative verify step (ISSUE 9, ngram mode), jitted
        per (sampling config, K, drafter context) — K is bucketed, so
        warm spec steps never recompile."""
        key = ("spec", gc._key(), k, nmax)
        if key not in self._jit_cache:
            import functools
            self._jit_cache[key] = self._first_call_logged(key, self._tp_jit(
                functools.partial(self._spec_verify_fn, gc, k, nmax),
                f"serve_spec_verify_K{k}",
                n_in=13, n_out=11, out_cache_idx=9), k)
        return self._jit_cache[key]

    def _first_call_logged(self, key, jitted, T: int):
        """``jitted`` for ``_jit_cache[key]``: its first call (tracing,
        lowering, the compile or the cache's read) runs as a
        ``startup.program`` phase and leaves the bare program in the cache,
        so no later dispatch passes through here."""
        def first(*operands):
            with _startup.program("jit_" + jitted.__name__, T=T,
                                  rows=self.max_batch * T):
                out = jitted(*operands)
            self._jit_cache[key] = jitted
            return out
        first.__name__, first.lower = jitted.__name__, jitted.lower
        return first

    # ---- the shared transformer core of every serving step ----
    def _forward_tokens(self, params, cache, tokens, ql, positions,
                        block_tables, rows=None):
        """Run the whole model over this step's query tokens: derive write
        slots in-jit from the block table, stream every layer through the
        mixed-mode ``ragged_paged_attention`` kernel (the step's own K/V
        rows fold in causally), commit all layers' fresh KV in ONE pass,
        and return the final-norm hidden states for ALL T
        positions, the updated pool and (where ``_moe_ffn`` counts them,
        else None) the step's MoE row counts.  Callers own freeze semantics, sampling and
        bookkeeping — this core is shared verbatim by the plain step and the
        T=K speculative verify step, so a prefill chunk, a decode token
        and a draft verification are literally the same program shape.

        tokens: [B, T] int32 (don't-care cols may hold drafter pad values
        — embedding lookups clip, and their slots are routed to -1 / not
        attended).  ql: [B] valid tokens per row (0 = inert row).
        positions: [B] cache tokens BEFORE this step (the write cursor).
        cache: the pool tuple — (kv,) float, or (kv, ks, vs) for
        the int8 plane (per-(layer, kv-head, page) fp32 scales), a latent
        pool's (c, r): pages
        dequantize inside the kernel and the commit requantizes per
        page, so the two modes share this whole function.

        ``rows`` < B * T (a static bucket that holds ``sum(ql)``; the
        mixed step only) packs the step's live tokens: everything that is
        per token — embedding, norms, projections, rope, the MLP or MoE
        FFN — runs over ``[1, rows, H]`` instead of the ``[B, T, H]``
        grid.  Only the kernel's operands are gathered into their
        ``[B, T]`` places (rows past ``ql[b]`` are don't-care there
        either way), so the kernel and the commit see today's shapes; the
        hidden states come back in their places too, zero past ``ql[b]``.
        """
        c = self.spec
        B, T = tokens.shape
        page = self.page_size
        ssm_state = conv_state = None
        if c.state_mixer is not None:
            # the slots' recurrent state rides last (``PagedKVCache.arrays``)
            *cache, ssm_state, conv_state = cache
        quant = len(cache) == 3
        ks = vs = None
        if c.latent is not None:
            # the compressed rows, the rotary keys, (the index keys)
            kc, vc, *ic = cache
        elif quant:
            kc, ks, vs = cache      # the one page-major pool, its scales
        else:
            kc, = cache
        tp = self.tp
        if tp > 1:
            # inside the shard_map body: this shard's contiguous head
            # blocks.  q heads group per kv head, so slicing kv heads
            # [i*kvh_l, (i+1)*kvh_l) takes exactly the q heads
            # [i*qh_l, (i+1)*qh_l) that attend to them — the all_gather
            # on the head axis reassembles the oracle's layout bitwise
            shard = jax.lax.axis_index(MP_AXIS)
            qh_l = c.num_heads // tp
            kvh_l = c.num_kv_heads // tp

        # token positions & write slots, derived in-jit from the block table
        offs = jnp.arange(T, dtype=jnp.int32)
        pos = positions[:, None].astype(jnp.int32) + offs[None, :]   # [B, T]
        pos_c = jnp.minimum(pos, self.max_seq_len - 1)
        page_ids = jnp.take_along_axis(block_tables, pos_c // page, axis=1)
        has_token = offs[None, :] < ql[:, None]
        valid = jnp.logical_and(has_token, pos < self.max_seq_len)
        slots = jnp.where(valid, page_ids * page + pos_c % page,
                          -1).reshape(B * T)

        ctx_prev = jnp.minimum(positions, self.max_seq_len).astype(jnp.int32)
        toks = jnp.clip(tokens, 0, params["embed"].shape[0] - 1)
        packed = rows is not None and rows < B * T
        if packed:
            live, src, dst = _pack_plan(ql, T, rows)

            def pack(a):       # [B, T, ...] -> [1, rows, ...], padding zero
                # a select on the gathered rows, not a zero row appended
                # to the grid: it fuses, the grid is never copied
                with jax.named_scope("token_pack"):
                    a = jnp.take(a.reshape((B * T,) + a.shape[2:]), src,
                                 axis=0)
                    keep = live.reshape((rows,) + (1,) * (a.ndim - 1))
                    return jnp.where(keep, a, jnp.zeros((), a.dtype))[None]

            def unpack(a):     # [1, rows, ...] -> [B, T, ...], sentinel zero
                with jax.named_scope("token_unpack"):
                    return take_sentinel_rows(a[0], dst)

            # ids and positions are packed, not their lookups: the
            # embedding and the rope tables are then read rows times
            toks, pos_c = pack(toks), pack(pos_c)
        cos = jnp.take(self._cos, pos_c, axis=0)          # [R0, R1, d/2]
        sin = jnp.take(self._sin, pos_c, axis=0)
        with jax.named_scope("embed"):
            h = _scaled(jnp.take(params["embed"], toks, axis=0),
                        c.embed_scale)                    # [R0, R1, H]
        if packed:
            h = jnp.where(live[None, :, None], h, jnp.zeros((), h.dtype))
        R0, R1 = h.shape[:2]              # B, T, or 1, rows when packed

        moe = c.moe
        norm_fn = rms_norm_fp32 if c.norm == "rms" else layer_norm_fp32

        def latent_attention(x, lp, la, layer, ix=None):
            """A latent layer's attention in the absorbed form: ``W_uk``
            carried into the query, ``heads`` query heads over ONE row
            ``[c | k_r]`` of the pool, ``W_uv`` applied to the call's
            result: (the block's output, this step's c rows, k_r rows).
            Serves prefill chunks and decodes alike: expanding the context
            for a chunk costs ``2 L rank heads (nope + value)`` whatever
            the chunk's length, the absorbed scores ``2 T L heads (rank -
            nope) x 2`` more than the expanded ones; they cross at ``T =
            rank (nope + value) / (2 (rank - nope))`` tokens a slot,
            whatever the number of heads: 171 at rank 512 and nope = value
            = 128 (both latent configurations served), over the chunk.

            ``la.q_rank``: the heads are made from the query latent ``c_q
            = RMSNorm(W_dq y)``.  ``ix`` (a learned index): the step's
            index keys ride with the rotary keys (``[k_r | k_i]`` comes
            back as the second of the step's rows), every cached token is
            scored for every query token, each query token's best
            ``ix.top_k`` are chosen exactly and the call's softmax runs
            over those alone."""
            y = norm_fn(x, lp["input_layernorm.weight"], c.norm_eps)
            if la.q_rank is None:
                q = y @ lp["self_attn.q_proj.weight"]
            else:
                c_q = rms_norm_fp32(y @ lp["self_attn.q_a_proj.weight"],
                                    lp["self_attn.q_a_layernorm.weight"],
                                    c.norm_eps)
                q = c_q @ lp["self_attn.q_b_proj.weight"]
            q = q.reshape(R0, R1, c.num_heads, la.nope + la.rope)
            ckr = y @ lp["self_attn.kv_a_proj_with_mqa.weight"]
            c_new = rms_norm_fp32(ckr[..., :la.rank],
                                  lp["self_attn.kv_a_layernorm.weight"],
                                  c.norm_eps)
            r_new = _rope_bt(ckr[..., None, la.rank:], cos, sin)[..., 0, :]
            q_r = _rope_bt(q[..., la.nope:], cos, sin)
            w_uk = lp["self_attn.k_up_proj.weight"].reshape(
                la.rank, c.num_heads, la.nope)
            q_c = jnp.einsum("abhd,rhd->abhr", q[..., :la.nope], w_uk)
            if packed:
                q_c, q_r = unpack(q_c), unpack(q_r)
                c_new, r_new = unpack(c_new), unpack(r_new)
            if ix is None:
                u = ragged_paged_attention_latent(
                    q_c, q_r, kc, vc, block_tables, ctx_prev,
                    scale=c.softmax_scale, q_lens=ql, c_new=c_new,
                    r_new=r_new, layer=layer)
            else:
                q_i, k_i, w_i = _index_inputs(y, c_q, lp, ix, c.norm_eps,
                                              cos, sin)
                if packed:
                    q_i, k_i, w_i = unpack(q_i), unpack(k_i), unpack(w_i)
                scores = latent_index_scores(
                    q_i, w_i, ic[0], block_tables, ctx_prev, q_lens=ql,
                    k_new=k_i, layer=layer)
                chosen = latent_index_select(
                    scores, jnp.minimum(pos + 1, ix.top_k), q_lens=ql,
                    context_lens=ctx_prev, n_new=T)
                u = ragged_paged_attention_latent_sparse(
                    q_c, q_r, kc, vc, block_tables, ctx_prev, chosen,
                    scale=c.softmax_scale, q_lens=ql, c_new=c_new,
                    r_new=r_new, layer=layer)
                r_new = jnp.concatenate([r_new, k_i], axis=-1)
            if packed:
                u = pack(u)
            w_uv = lp["self_attn.v_up_proj.weight"].reshape(
                la.rank, c.num_heads, la.value)
            attn = jnp.einsum("abhr,rhd->abhd", u, w_uv).reshape(R0, R1, -1)
            return attn @ lp["self_attn.o_proj.weight"], c_new, r_new

        if c.state_mixer is not None:
            # a slot whose first chunk this is: what the last request left
            # in its recurrent state counts as zero
            fresh = jnp.logical_and(positions == 0, ql > 0)
        if c.linear is not None:
            # where each slot's tokens lie among the rows the per-token work
            # runs over: the packed rows, or the [B, T] grid row by row
            row_start = (jnp.cumsum(ql) - ql) if packed \
                else jnp.arange(B, dtype=jnp.int32) * T
            row_slot, row_t, _ = rows_of_slots(row_start, ql, R0 * R1)

        def ssm_mixer(y, lp, mx, layer, state, carried):
            """A place's state-space mixer on the normed input ``y``, beside
            its attention: (the branch's output, the whole state with this
            layer's slots updated in place, this layer's new convolution
            rows).  Everything per token (the projections, the gate, the
            norm) runs over the packed rows; the convolution and the scan
            see the slots' ``[B, T]`` places, as attention does.  A slot
            without work (``ql == 0``) keeps its state and its rows."""
            f32 = jnp.float32
            d, cw, n = mx.inner, mx.conv_width, mx.groups * mx.state
            zxd = _scaled(y, mx.in_scale) @ lp["mamba.in_proj.weight"]
            if any(k != 1.0 for k in mx.zone_scales):
                kz, kx, kb, kc, kd = mx.zone_scales
                zones = np.repeat(np.asarray([kz, kx, kb, kc, kd], np.float32),
                                  [d, d, n, n, mx.heads])
                zxd = (zxd.astype(f32) * zones).astype(zxd.dtype)
            z, xbc, dt = zxd[..., :d], zxd[..., d:d + cw], zxd[..., d + cw:]
            if packed:
                xbc, dt = unpack(xbc), unpack(dt)
            with jax.named_scope("conv"):
                # the slot's carried rows, then the step's: token t reads
                # rows t .. t + conv - 1 of them
                carried = jnp.where(fresh[:, None, None],
                                    jnp.zeros((), carried.dtype), carried)
                ext = jnp.concatenate([carried, xbc], axis=1)
                w = lp["mamba.conv1d.weight"].astype(f32)     # [conv, cw]
                acc = lp["mamba.conv1d.bias"].astype(f32)
                for j in range(mx.conv):
                    acc = acc + w[j] * ext[:, j:j + T].astype(f32)
                xbc = jax.nn.silu(acc).astype(xbc.dtype)
                # the last conv - 1 rows of what the slot has now seen
                last = ql[:, None] + jnp.arange(mx.conv - 1, dtype=jnp.int32)
                carried = jnp.take_along_axis(ext, last[:, :, None], axis=1)
            x = xbc[..., :d].reshape(B, T, mx.heads, mx.head_dim)
            b_in = xbc[..., d:d + n].reshape(B, T, mx.groups, mx.state)
            c_in = xbc[..., d + n:].reshape(B, T, mx.groups, mx.state)
            dt = jax.nn.softplus(dt.astype(f32)
                                 + lp["mamba.dt_bias"].astype(f32))
            yk, state = ragged_ssd_update(
                state, x, b_in, c_in, dt,
                -jnp.exp(lp["mamba.A_log"].astype(f32)),
                lp["mamba.D"].astype(f32), ql, fresh, layer=layer)
            yk = yk.reshape(B, T, d)
            if packed:
                yk = pack(yk)
            # gated, then RMS-normed over each group's numbers
            g = yk.astype(f32) * jax.nn.silu(z.astype(f32))
            g = rms_norm_fp32(
                g.reshape(R0, R1, mx.groups, d // mx.groups),
                lp["mamba.norm.weight"].reshape(mx.groups, d // mx.groups),
                c.norm_eps).reshape(R0, R1, d).astype(y.dtype)
            return _scaled(g @ lp["mamba.out_proj.weight"], mx.out_scale), \
                state, carried

        def delta_mixer(y, lp, mx, layer, state, carried):
            """A linear place's token mixer (``DeltaMixer``) on the normed
            input ``y``, in attention's stead: (the branch's output, the
            whole state with this layer's slots updated in place, this
            layer's new convolution rows).  EVERYTHING runs over the rows
            the per-token work runs over, the convolution and the
            recurrence too: the kernel takes the step's tokens packed and
            finds each slot's by ``row_start`` and ``ql``, so no ``[B, T]``
            grid of its operands is written out.  A slot without work
            keeps its state and its rows."""
            f32 = jnp.float32
            R, n = R0 * R1, mx.conv - 1
            hk, hv = mx.heads * mx.key_dim, mx.heads * mx.value_dim
            y = y.reshape(R, -1)
            qkv = y @ lp["linear_attn.qkv_proj.weight"]    # [R, q | k | v]
            with jax.named_scope("conv"):
                # token t of a slot reads its own row and the conv - 1
                # before it: rows of this step where the slot has them,
                # else the slot's carried ones
                carried = jnp.where(fresh[:, None, None],
                                    jnp.zeros((), carried.dtype), carried)
                w = lp["linear_attn.conv1d.weight"].astype(f32)  # [conv, C]
                old = carried.astype(f32)
                acc = w[n] * qkv.astype(f32)
                if T == 1:
                    # a decode step: row b is slot b's one token, which
                    # reads its own row and the slot's carried ones (the
                    # gathers and the scatter below were 18 % of a decode
                    # step of the long-generation cell: PERF.md section 6)
                    acc = acc + sum(w[j] * old[:, j] for j in range(n))
                    carried = jnp.where(
                        (ql > 0)[:, None, None], jnp.concatenate(
                            [carried[:, 1:], qkv[:, None]], axis=1), carried)
                else:
                    for back in range(1, n + 1):
                        acc = acc + w[n - back] * jnp.where(
                            (row_t >= back)[:, None],
                            jnp.roll(qkv, back, axis=0),
                            jnp.zeros((), qkv.dtype)).astype(f32)
                    # a slot's first conv - 1 tokens read carried rows too:
                    # token t the rows t .. conv - 2, under the first taps
                    heads_of = jnp.stack([sum(
                        w[j - t] * old[:, j] for j in range(t, n))
                        for t in range(n)], axis=1)            # [B, n, C]
                    first = jnp.arange(n, dtype=jnp.int32)[None, :]
                    acc = acc.at[jnp.where(first < ql[:, None],
                                           row_start[:, None] + first,
                                           R).reshape(-1)].add(
                        heads_of.reshape(B * n, -1), mode="drop")
                    # the last conv - 1 rows of what the slot has now seen
                    at = ql[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
                    carried = jnp.where(
                        (at >= n)[:, :, None],
                        jnp.take(qkv, jnp.clip(row_start[:, None] + at - n,
                                               0, R - 1), axis=0),
                        jnp.take_along_axis(carried, jnp.clip(
                            at, 0, n - 1)[:, :, None], axis=1))
                mixed = jax.nn.silu(acc).astype(qkv.dtype)

            q = l2_normalised(mixed[:, :hk].reshape(
                R, mx.heads, mx.key_dim)) * mx.key_dim ** -0.5
            k = l2_normalised(mixed[:, hk:2 * hk].reshape(
                R, mx.heads, mx.key_dim))
            v = mixed[:, 2 * hk:].reshape(R, mx.heads, mx.value_dim)
            with jax.named_scope("gates"):
                fa = (y @ lp["linear_attn.f_a_proj.weight"]) \
                    @ lp["linear_attn.f_b_proj.weight"]
                g = -jnp.exp(lp["linear_attn.A_log"].astype(f32))[
                    None, :, None] * jax.nn.softplus(
                    fa.astype(f32).reshape(R, mx.heads, mx.key_dim)
                    + lp["linear_attn.dt_bias"].astype(f32).reshape(
                        mx.heads, mx.key_dim))
                beta = jax.nn.sigmoid(
                    (y @ lp["linear_attn.b_proj.weight"]).astype(f32)) \
                    * (2.0 if mx.neg_eigval else 1.0)
                og = (y @ lp["linear_attn.g_a_proj.weight"]) \
                    @ lp["linear_attn.g_b_proj.weight"]
            with jax.named_scope("kda"):
                o, state = ragged_kda_update(
                    state, q.astype(y.dtype), k, v, g, beta, row_start, ql,
                    fresh, chunk=T, layer=layer)
            # RMS-normed a head with a learned weight, then the gate
            o = o.astype(f32)
            o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                  + c.norm_eps) \
                * lp["linear_attn.o_norm.weight"].astype(f32)
            o = (o.reshape(R, hv) * jax.nn.sigmoid(og.astype(f32))) \
                .astype(y.dtype).reshape(R0, R1, hv)
            return o @ lp["linear_attn.o_proj.weight"], state, carried

        # the router reads the attention's normed input: a layer makes its
        # choice before the attention call and hands it past it
        routes_early = moe is not None and moe.router_input == "attention" \
            and not c.parallel_block        # there the two are one tensor

        def ffn(y, lp, kind, bank_layer, choice=None):
            """A place's FFN on its normed input: the spec's expert mixture
            (on ``choice`` where the layer's router has made it already),
            or a dense gated MLP where the spec has none or the place says
            so: (output, MoE rows or None)."""
            if moe is not None and not kind.dense_ffn:
                # the rows that hold a token, as a packed step's ``live``:
                # a token past ``max_seq_len`` has no place in the pool and
                # is routed all the same (its slot's logits are read).  A
                # share's arm is handed ``valid``, as it always was: its
                # step programs are the ones its cells were measured on
                grid = valid if moe.partial else has_token
                kw = dict(mp_shards=self._moe_shards,
                          live=live if packed else grid, layer=bank_layer)
                if choice is None:
                    return _moe_ffn(y, lp, moe, **kw)
                return _moe_experts(y, lp, moe, choice, **kw)
            act = jax.nn.silu(_scaled(y @ lp["mlp.gate_proj.weight"],
                                      c.mlp_gate_scale)) * \
                (y @ lp["mlp.up_proj.weight"])
            return _scaled(act @ lp["mlp.down_proj.weight"],
                           c.mlp_out_scale), None

        def one_layer(x, lp, kind, layer, ksl, vsl, bank_layer,
                      state=None, conv_rows=None, state_layer=None):
            """A decoder layer of ``kind`` whose plane of the pool is
            ``layer`` (READ-ONLY; the kernel takes the whole pool and the
            layer, no layer is sliced out of it) and whose plane of the
            recurrent state is ``state_layer``: (x, this step's k, v (None
            twice for a linear place, which keeps no pages), MoE rows, and
            where the place has a mixer the whole recurrent state with this
            layer's updated in place and the layer's new convolution rows,
            else None twice).
            ``bank_layer``: None, or ``lp``'s expert banks are its place's
            unstacked layers and this layer is that one of them."""
            if kind.linear is not None:
                with jax.named_scope("linear_attn"):
                    y = norm_fn(x, lp["input_layernorm.weight"], c.norm_eps)
                    a, state, conv_rows = delta_mixer(
                        y, lp, kind.linear, state_layer, state, conv_rows)
                    x = x + a
                with jax.named_scope("moe" if moe is not None else "mlp"):
                    y = norm_fn(x, lp["post_attention_layernorm.weight"],
                                c.norm_eps)
                    f, n_rows = ffn(y, lp, kind, bank_layer)
                return x + f, None, None, n_rows, state, conv_rows
            if kind.latent is not None:
                with jax.named_scope("attention"):
                    a, k, v = latent_attention(x, lp, kind.latent, layer,
                                               kind.index)
                    x = x + a
                with jax.named_scope("mlp" if kind.dense_ffn else "moe"):
                    y = norm_fn(x, lp["post_attention_layernorm.weight"],
                                c.norm_eps)
                    f, n_rows = ffn(y, lp, kind, bank_layer)
                return x + f, k, v, n_rows, None, None
            with jax.named_scope("attention"):
                y = norm_fn(x, lp["input_layernorm.weight"], c.norm_eps)
            choice = None
            if routes_early and not kind.dense_ffn:
                # a scope of its own: a device trace tells this router from
                # the experts under "moe" and from the attention around it
                with jax.named_scope("moe_router"):
                    choice = _moe_choice(y, lp, moe)
            with jax.named_scope("attention"):
                ya = _scaled(y, c.attn_in_scale)
                q = (ya @ lp["self_attn.q_proj.weight"]).reshape(
                    R0, R1, c.num_heads, c.head_dim)
                k = _scaled(ya @ lp["self_attn.k_proj.weight"],
                            c.key_scale).reshape(
                    R0, R1, c.num_kv_heads, c.head_dim)
                v = (ya @ lp["self_attn.v_proj.weight"]).reshape(
                    R0, R1, c.num_kv_heads, c.head_dim)
                if kind.rope:
                    q = _rope_bt(q, cos, sin)
                    k = _rope_bt(k, cos, sin)
                if packed:
                    q, k, v = unpack(q), unpack(k), unpack(v)
                # prior context from the paged cache + this step's own rows
                # (causal), one mixed-mode kernel call; the fresh rows are
                # committed to the cache only at the end of the step.  Under
                # tp the pool kc is already this shard's heads of every page
                # (per-shard storage), q/k/v slice to
                # the matching head block, and each shard's kernel DMAs only
                # its own heads' pages; the head-axis all_gather restores the
                # full [B, T, qh, d] activation for the replicated o_proj
                if tp > 1:
                    q_a = jax.lax.dynamic_slice_in_dim(
                        q, shard * qh_l, qh_l, axis=2)
                    k_a = jax.lax.dynamic_slice_in_dim(
                        k, shard * kvh_l, kvh_l, axis=2)
                    v_a = jax.lax.dynamic_slice_in_dim(
                        v, shard * kvh_l, kvh_l, axis=2)
                else:
                    q_a, k_a, v_a = q, k, v
                attn = ragged_paged_attention(q_a, kc, block_tables,
                                              ctx_prev, q_lens=ql,
                                              k_new=k_a, v_new=v_a,
                                              k_scale=ksl, v_scale=vsl,
                                              window=kind.window,
                                              layer=layer)
                if tp > 1:
                    attn = jax.lax.all_gather(attn, MP_AXIS, axis=2,
                                              tiled=True)
                attn = attn.reshape(B, T, -1)
                if packed:
                    attn = pack(attn)
                if kind.out_gate:
                    with jax.named_scope("out_gate"):
                        attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(
                            (ya @ lp["self_attn.g_proj.weight"]).astype(
                                jnp.float32))).astype(attn.dtype)
                a = _scaled(attn @ lp["self_attn.o_proj.weight"],
                            c.attn_out_scale)
                if not c.parallel_block:
                    x = x + a
            if kind.ssm is not None:
                with jax.named_scope("ssm"):
                    s, state, conv_rows = ssm_mixer(
                        y, lp, kind.ssm, state_layer, state, conv_rows)
                    x = x + s
            with jax.named_scope("moe" if moe is not None else "mlp"):
                if not c.parallel_block:     # else the FFN reads the same y
                    y = norm_fn(x, lp["post_attention_layernorm.weight"],
                                c.norm_eps)
                f, n_rows = ffn(y, lp, kind, bank_layer, choice)
                x = x + a + f if c.parallel_block else x + f
            return x, k, v, n_rows, state, conv_rows

        # the scan runs over whole periods of the layer pattern, a period's
        # layers unrolled inside; what is scanned is one [periods, ...]
        # stack a place (and the int8 scale planes, split [periods, places])
        P = len(c.pattern)
        # a place's plane among its period's planes of the pool, and of the
        # recurrent state (a linear place has none of the first, a place
        # without a mixer none of the second)
        page_of = {p: i for i, p in enumerate(c.page_places)}
        state_of = {p: i for i, p in enumerate(c.state_places)}

        def by_period(a, places=P):
            return None if a is None else \
                a.reshape((c.periods, places) + a.shape[1:])

        # a scanned stack is sliced, and a slice that feeds a custom call
        # (``gmm``) is written out first: a copy of the layer's expert banks
        # a layer a step.  So a model may hand out a place's banks unstacked
        # (a tuple, one array a layer); those stay out of the scan, which
        # closes over them and hands them down with the period's number
        unstacked = [{n: a for n, a in lp.items() if isinstance(a, tuple)}
                     for lp in params["blocks"]]
        scanned = [{n: a for n, a in lp.items() if n not in own}
                   for lp, own in zip(params["blocks"], unstacked)]

        def period(carry, xs):
            # the recurrent state is CARRIED (the scan's call updates one
            # layer of it in place); the convolution rows are small and
            # go in and out a layer at a time
            x, *state = carry
            r, blocks, ksp, vsp, convp = xs
            ks_new, vs_new, conv_new, n_rows = [], [], [], None
            for p, kind in enumerate(c.pattern):
                layer = r * P + p
                if c.leading:
                    layer = layer + len(c.leading)
                # where every place keeps pages (a state) the layer's
                # number is its plane of the pool (of the state)
                state_layer = layer if len(state_of) == P else \
                    r * len(state_of) + state_of.get(p, 0)
                if len(page_of) != P:
                    layer = r * len(page_of) + page_of.get(p, 0)
                mixes = bool(state) and p in state_of
                x, k, v, n, *mixed = one_layer(
                    x, {**blocks[p], **unstacked[p]}, kind, layer,
                    None if ksp is None else ksp[p],
                    None if vsp is None else vsp[p],
                    r if unstacked[p] else None,
                    *((state[0], convp[state_of[p]], state_layer)
                      if mixes else ()))
                if p in page_of:
                    ks_new.append(k)
                    vs_new.append(v)
                if mixes:
                    state = [mixed[0]]
                    conv_new.append(mixed[1])
                if n is not None:
                    n_rows = n if n_rows is None else n_rows + n
            return (x, *state), (jnp.stack(ks_new), jnp.stack(vs_new), n_rows,
                                 jnp.stack(conv_new) if state else None)

        # leading layers (a shape of their own) run once, unrolled, first
        lead_k, lead_v = [], []
        for i, kind in enumerate(c.leading):
            h, k, v = one_layer(h, params["leading"][i], kind,
                                jnp.int32(i), None, None, None)[:3]
            lead_k.append(k)
            lead_v.append(v)
        xs = (jnp.arange(c.periods, dtype=jnp.int32), scanned,
              by_period(ks), by_period(vs),
              by_period(conv_state, len(state_of)))
        carry = (h,) if ssm_state is None else (h, ssm_state)
        if c.periods == 1:
            # nothing to scan over: the one period runs in line on the
            # stacks' only slice (a view, where a scan's slices of stacked
            # expert banks are copies)
            carry, ys = period(carry, jax.tree_util.tree_map(
                lambda a: a[0], xs))
            k_all, v_all, moe_rows, conv_all = jax.tree_util.tree_map(
                lambda a: a[None], ys)
        else:
            carry, (k_all, v_all, moe_rows, conv_all) = jax.lax.scan(
                period, carry, xs)
        h = carry[0]
        if moe_rows is not None:
            moe_rows = moe_rows.sum(axis=0)        # over the periods
        L = c.page_layers
        if c.latent is not None:
            # [periods, places, B, T, width] -> [layers, B * T, width], the
            # leading layers' rows first
            def layers_first(lead, a):
                a = a.reshape((-1,) + a.shape[2:])
                if lead:
                    a = jnp.concatenate([jnp.stack(lead), a])
                return a.reshape(L, B * T, a.shape[-1])

            k_all, v_all = layers_first(lead_k, k_all), \
                layers_first(lead_v, v_all)
            third = ()
            if ic:      # the index keys rode with the rotary keys
                rope = c.latent.rope
                third = (ic[0], v_all[..., rope:])
                v_all = v_all[..., :rope]
            with jax.named_scope("attention"), jax.named_scope("kv_write"):
                out_cache = write_latent_pages_all_layers(
                    kc, vc, k_all, v_all, slots, *third)
            h = norm_fn(h, params["norm"], c.norm_eps)
            return (unpack(h) if packed else h), out_cache, moe_rows
        kvh, dh = c.num_kv_heads, c.head_dim
        k_all = k_all.reshape(L, B * T, kvh, dh)
        v_all = v_all.reshape(L, B * T, kvh, dh)
        if tp > 1:
            # each shard commits only its own heads' fresh rows to its
            # local page planes (the int8 path below then computes its
            # per-(layer, local-head, page) scale rows from the same
            # bytes the oracle would — absmax is per-head, so the
            # gathered global planes are bit-identical at any tp)
            k_all = jax.lax.dynamic_slice_in_dim(
                k_all, shard * kvh_l, kvh_l, axis=2)
            v_all = jax.lax.dynamic_slice_in_dim(
                v_all, shard * kvh_l, kvh_l, axis=2)
        if len(page_of) != P:
            # the commit waits for the last layer: where the LAST layers
            # keep no pages, the page layers' fresh rows depend on no call
            # that reads the pool, and without an order between that read
            # and this write XLA copies the whole pool in and out of the
            # commit's loop (compiled for a described v5e, PR 46)
            h, kc = jax.lax.optimization_barrier((h, kc))
        with jax.named_scope("attention"), jax.named_scope("kv_write"):
            if quant:
                # quantize fresh K/V per page on the way in (page-level
                # RMW: the absmax scale covers every row of the page)
                out_cache = write_kv_pages_all_layers_quantized(
                    kc, ks, vs, k_all, v_all, positions, ql,
                    block_tables, self.max_seq_len)
            else:
                out_cache = (write_kv_pages_all_layers(kc, k_all, v_all,
                                                       slots),)
        if ssm_state is not None:
            out_cache += (carry[1],
                          conv_all.reshape(conv_state.shape))

        h = norm_fn(h, params["norm"], c.norm_eps)
        return (unpack(h) if packed else h), out_cache, moe_rows

    # ---- the ONE engine step ----
    def _step_fn(self, gc, T, track_recent, rows, params, cache, tokens,
                 q_lens, positions, finished, decode_mask, commit_mask,
                 counts, budgets, block_tables, key, recent=None):
        """One fused serving step: admit (slots derived in-jit) →
        ragged attention over every layer → ONE batched KV commit → sample.

        tokens:      [B, T] — this step's query tokens (decode rows use
                     column 0; prefill rows their prompt chunk).
        q_lens:      [B] — valid tokens per row (0 = idle row).
        positions:   [B] — cache tokens BEFORE this step (write cursor).
        decode_mask: [B] — rows whose column-0 token is generated output
                     (EOS is only checked on generated tokens, never on
                     prompt tokens).
        commit_mask: [B] — rows whose sample this step is a real generated
                     token (decode rows + the final prompt chunk).
        counts/budgets: [B] — generated-so-far / max_new_tokens per row;
                     the budget freeze happens on device.
        recent:      [B, nmax] (``track_recent`` only) — the ngram
                     drafter's ring of last committed tokens, appended to
                     on every committing row so the verify step's context
                     is exact even across prefill/mixed steps.
        All of it device-resident and chained between calls — the host
        loop is sync-free.
        """
        if gc.eos_token_id is not None:
            finished = jnp.logical_or(
                finished,
                jnp.logical_and(decode_mask, tokens[:, 0] == gc.eos_token_id))
        # a sequence that filled the cache freezes (no slot rewrite)
        finished = jnp.logical_or(finished, positions >= self.max_seq_len)
        ql = jnp.where(finished, 0, q_lens).astype(jnp.int32)

        h, cache, moe_rows = self._forward_tokens(
            params, cache, tokens, ql, positions, block_tables, rows)
        last_ix = jnp.maximum(ql - 1, 0)
        last = jnp.take_along_axis(h, last_ix[:, None, None], axis=1)[:, 0]
        logits = self._head_logits(params, last)
        # positional sampling keys: the token being sampled lands at
        # sequence index positions + ql; the chained key never advances
        # (determinism across batch shapes and replicas — see _sample)
        sampled = _sample(logits, key, positions + ql, gc)
        last_in = jnp.take_along_axis(tokens, last_ix[:, None], axis=1)[:, 0]
        out_tokens = jnp.where(finished, last_in, sampled)
        new_positions = jnp.where(
            finished, positions,
            jnp.minimum(positions + ql, self.max_seq_len))
        committed = jnp.logical_and(commit_mask, jnp.logical_not(finished))
        counts = counts + jnp.where(committed, 1, 0)
        finished = jnp.logical_or(finished, counts >= budgets)
        out = (out_tokens, new_positions, finished, jnp.all(finished),
               counts, cache, key)
        if track_recent:
            recent = _sp.shift_append(recent, out_tokens[:, None],
                                      committed.astype(jnp.int32))
            out = out + (recent,)
        # last, where this chip holds a share of the experts: [entries on
        # held experts, rows laid out], read when the step is gathered
        return out + (moe_rows,) if self.counts_moe_rows else out

    # ---- ISSUE 9: the T=K speculative verify step (ngram mode) ----
    def _spec_verify_fn(self, gc, K, nmax, params, cache, last_tok, recent,
                        hist, hist_len, positions, finished, counts,
                        budgets, write_caps, block_tables, key):
        """One speculative decode dispatch: draft K-1 tokens on device
        from the history table, verify all of them in ONE mixed-mode
        T=K forward, commit the longest accepted prefix plus the bonus
        token, and roll back everything else — all device-resident.

        Rollback is positional: rejected rows' KV was written but
        ``positions`` only advances by the commit count, so the ragged
        kernel (which masks by context length) can never read a stale
        row, and the cursor overwrites it in place when real tokens reach
        it.  Greedy outputs bit-match sequential decoding because a
        draft is only accepted when it EQUALS the verifier's own argmax.

        Returns (sampled [B,K], n_commit [B], drafted [B], last_tok,
        positions, finished, all_done, counts, recent, cache, key).
        """
        if gc.eos_token_id is not None:
            # EOS on the chained input token: the prefill handoff case —
            # the final prompt chunk's sample is EOS-checked here exactly
            # like the plain decode step checks its column-0 input
            finished = jnp.logical_or(finished,
                                      last_tok == gc.eos_token_id)
        finished = jnp.logical_or(finished, positions >= self.max_seq_len)
        drafts, draft_len = _sp.lookup_drafts(hist, hist_len, recent, K,
                                              nmax)
        # structural write-coverage guarantee: never write past the pages
        # the block table actually owns (``write_caps`` = tokens covered),
        # whatever the host's growth managed under pool pressure — a
        # capped row just commits fewer tokens this dispatch and resumes
        cap_room = jnp.maximum(write_caps - positions, 0)
        ql = jnp.where(finished, 0,
                       jnp.minimum(1 + draft_len, cap_room)).astype(jnp.int32)
        drafted = jnp.maximum(ql - 1, 0)          # drafts actually dispatched
        tokens = jnp.concatenate([last_tok[:, None], drafts], axis=1)

        h, cache, _ = self._forward_tokens(params, cache, tokens, ql,
                                           positions, block_tables)
        B = tokens.shape[0]
        logits = self._head_logits(params, h)                  # [B, K, V]
        # one positional key per (row, slot): slot j samples the token
        # at sequence index positions + j + 1 — token-level sequential
        # sampling semantics (greedy ignores the keys entirely)
        pos_k = positions[:, None] + \
            jnp.arange(K, dtype=jnp.int32)[None, :] + 1
        sampled = _sample(logits.reshape(B * K, -1), key,
                          pos_k.reshape(B * K), gc).reshape(B, K)

        n_commit = _sp.accept_length(tokens, sampled, ql)
        if gc.eos_token_id is not None:
            n_commit, hit_eos = _sp.eos_clamp(sampled, n_commit,
                                              gc.eos_token_id)
            finished = jnp.logical_or(finished, hit_eos)
        n_commit = jnp.minimum(n_commit, jnp.maximum(budgets - counts, 0))
        n_commit = jnp.minimum(n_commit,
                               jnp.maximum(self.max_seq_len - positions, 0))
        counts = counts + n_commit
        finished = jnp.logical_or(finished, counts >= budgets)
        positions = positions + n_commit
        finished = jnp.logical_or(finished, positions >= self.max_seq_len)

        picked = jnp.take_along_axis(
            sampled, jnp.maximum(n_commit - 1, 0)[:, None], axis=1)[:, 0]
        last_tok = jnp.where(n_commit > 0, picked, last_tok)
        recent = _sp.shift_append(recent, sampled, n_commit)
        return (sampled, n_commit, drafted, last_tok, positions, finished,
                jnp.all(finished), counts, recent, cache, key)

    # ---- host loop ----
    def generate(self, prompts: Sequence[Sequence[int]],
                 gen: Optional[GenerationConfig] = None) -> List[List[int]]:
        """prompts: per-sequence token-id lists → generated ids (no prompt)."""
        gen = gen or GenerationConfig()
        B = len(prompts)
        MB = self.max_batch
        if B > MB:
            raise ValueError(f"batch {B} > max_batch {MB}")
        alloc = self.cache.allocator
        lens = np.asarray([len(p) for p in prompts], np.int32)
        seq_ids = list(range(B))
        for i, p in enumerate(prompts):
            alloc.allocate(seq_ids[i], len(p))
        bt_width = self.pages_per_seq
        bt = np.zeros((MB, bt_width), np.int32)
        bt[:B] = alloc.block_table(seq_ids, max_pages=bt_width)
        bt_dev = jnp.asarray(bt)

        key = jax.random.key(gen.seed)
        i32 = jnp.int32
        positions = jnp.zeros((MB,), i32)
        finished = jnp.asarray(np.arange(MB) >= B)        # pad rows inert
        counts = jnp.zeros((MB,), i32)
        budgets_np = np.zeros((MB,), np.int32)
        budgets_np[:B] = gen.max_new_tokens
        budgets = jnp.asarray(budgets_np)
        no_mask = jnp.zeros((MB,), bool)
        all_mask = jnp.ones((MB,), bool)
        first = jnp.zeros((MB,), i32)

        # chunked prefill: prompts stream through the step in fixed
        # T-sized chunks (one compile, any prompt length)
        T = self.prefill_bucket
        n_chunks = max(1, -(-int(lens.max()) // T))
        for ci in range(n_chunks):
            s0 = ci * T
            chunk = np.zeros((MB, T), np.int32)
            ql = np.zeros((MB,), np.int32)
            for i, p in enumerate(prompts):
                n = min(max(len(p) - s0, 0), T)
                ql[i] = n
                if n:
                    chunk[i, :n] = np.asarray(p[s0:s0 + n], np.int32)
            commit = np.zeros((MB,), bool)
            commit[:B] = (lens > s0) & (lens <= s0 + T)   # prompt ends here
            # the engine's rule: the smallest row bucket that holds the
            # chunk's tokens (all rows prefill together here, so it is the
            # dense grid or near it; jit caches each member once)
            step_p = self._step_jit(gen, T,
                                    rows=self.gemm_rows(T, int(ql.sum())))
            out, positions, finished, _ad, counts, cache, key = step_p(
                self.params, self.cache.arrays, jnp.asarray(chunk),
                jnp.asarray(ql), positions, finished, no_mask,
                jnp.asarray(commit), counts, budgets, bt_dev, key)[:7]
            self.cache.update(*cache)
            first = jnp.where(jnp.asarray(commit), out, first)

        # device-resident decode loop (sync-free; one dispatch per step)
        step_d = self._step_jit(gen, 1)
        ql1 = jnp.ones((MB,), i32)
        tokens = first
        collected = [first]                  # device arrays, synced at end

        # host-side upper bound of each sequence's written length: grows
        # every step regardless of finished (finished lives on device) —
        # page allocation is safe-by-overestimate, <= 1 spare page per seq
        host_lens = lens.copy()
        steps_until_sync = self.sync_every
        for _ in range(gen.max_new_tokens - 1):
            if int(np.min(host_lens)) >= self.max_seq_len:
                break                        # every sequence is at capacity
            # grow pages ahead of any boundary crossing; re-upload the
            # table only when it changed
            grew = False
            for i in range(B):
                if host_lens[i] < self.max_seq_len and \
                        host_lens[i] % self.page_size == 0 and \
                        alloc.context_len(seq_ids[i]) <= host_lens[i]:
                    alloc.extend(seq_ids[i],
                                 min(self.page_size,
                                     self.max_seq_len - host_lens[i]))
                    grew = True
            if grew:
                bt[:B] = alloc.block_table(seq_ids, max_pages=bt_width)
                bt_dev = jnp.asarray(bt)

            tokens, positions, finished, all_done, counts, cache, key = \
                step_d(self.params, self.cache.arrays, tokens[:, None],
                       ql1, positions, finished, all_mask, all_mask,
                       counts, budgets, bt_dev, key)[:7]
            self.cache.update(*cache)
            collected.append(tokens)
            host_lens = np.minimum(host_lens + 1, self.max_seq_len)

            steps_until_sync -= 1
            if gen.eos_token_id is not None and steps_until_sync <= 0:
                steps_until_sync = self.sync_every
                if self._metrics_on:
                    _obs.count_sync()
                if bool(all_done):           # single scalar device sync
                    break

        for s in seq_ids:
            alloc.free(s)

        # one bulk transfer, then trim to the first EOS per sequence
        if self._metrics_on:
            _obs.count_sync()
        mat = np.asarray(jnp.stack(collected, axis=1))     # [MB, steps]
        out: List[List[int]] = []
        for i in range(B):
            row = mat[i].tolist()
            if gen.eos_token_id is not None and gen.eos_token_id in row:
                row = row[:row.index(gen.eos_token_id) + 1]
            limit = self.max_seq_len - int(lens[i])
            out.append(row[:max(1, limit)])
        return out


def generate(model, prompts, gen: Optional[GenerationConfig] = None,
             **kw) -> List[List[int]]:
    """One-shot convenience: build a generator sized to the request."""
    gen = gen or GenerationConfig()
    max_len = max(len(p) for p in prompts) + gen.max_new_tokens
    g = LlamaGenerator(model, max_batch=len(prompts),
                       max_seq_len=min(
                           max(64, max_len),
                           model.config.max_position_embeddings), **kw)
    return g.generate(prompts, gen)


class Request:
    """One in-flight generation request of the continuous-batching engine.

    The ``t_*`` fields are host ``perf_counter`` stamps of the request's
    lifecycle (enqueue → admission → first token → last token), recorded
    by the engine's observability instrumentation at dispatch/gather time —
    never via a device sync.

    ``trace_id`` is the caller's trace-context id (the HTTP front door's
    response id, ISSUE 6): when set, the request's lifecycle spans ride a
    trace lane named after it, so one request is ONE correlated track from
    HTTP accept through engine retire in the exported Chrome trace."""

    __slots__ = ("req_id", "prompt", "max_new_tokens", "output", "done",
                 "t_enqueue", "t_admit", "t_first", "t_last", "n_emitted",
                 "trace_id")

    def __init__(self, req_id, prompt, max_new_tokens, trace_id=None):
        self.req_id = req_id
        self.prompt = list(prompt)
        self.max_new_tokens = max_new_tokens
        self.output: List[int] = []
        self.done = False
        self.t_enqueue = None
        self.t_admit = None
        self.t_first = None
        self.t_last = None
        self.n_emitted = 0
        self.trace_id = trace_id


class _InFlight:
    """One dispatched step's OWN results on their way to the host: the
    arrays that step returned (never a chained "latest" value, which a
    later step in flight would make the gather wait for) and the request
    every row held when it was dispatched, so a token gathered after its
    slot was handed on is credited to the request that made it or to none.

    ``kind`` "step": ``out`` [B] sampled tokens, ``commit`` the host's
    [B] commit marks; "spec": ``out`` [B, K], ``commit`` the device's [B]
    commit counts, ``drafted`` [B] or None.  ``finished`` [B] as that step
    left it; ``moe_rows`` [entries on held experts, rows laid out(, the
    fullest expert's entries)] where the step counts them; ``t`` the host's dispatch stamp."""

    __slots__ = ("kind", "out", "commit", "drafted", "finished",
                 "moe_rows", "reqs", "t")

    def __init__(self, kind, out, commit, drafted, finished, moe_rows,
                 reqs, t):
        self.kind = kind
        self.out = out
        self.commit = commit
        self.drafted = drafted
        self.finished = finished
        self.moe_rows = moe_rows
        self.reqs = reqs
        self.t = t
        # the copies start now, behind the step: by the time the host
        # asks, the bytes are there or on their way
        for a in (out, commit, drafted, finished, moe_rows):
            if isinstance(a, jax.Array):
                a.copy_to_host_async()

    def landed(self) -> bool:
        """Whether the step has run: a gather of it would not wait for
        the device (one program made all its arrays, so one is asked)."""
        return self.out.is_ready()

    def to_host(self) -> None:
        """The arrays as numpy values; waits for the step if it has not
        landed."""
        self.out = np.asarray(self.out)
        self.commit = np.asarray(self.commit)
        self.finished = np.asarray(self.finished)
        if self.drafted is not None:
            self.drafted = np.asarray(self.drafted)
        if self.moe_rows is not None:
            self.moe_rows = np.asarray(self.moe_rows)

    def commits_ahead(self, b: int, k: int) -> int:
        """The most tokens this step may yet commit for row ``b``."""
        return k if self.kind == "spec" else int(self.commit[b])


class _ServingMetrics:
    """Resolved registry handles for the serving hot path (one dict lookup
    per series at engine construction, plain attribute access per step)."""

    __slots__ = ("requests", "completed", "tokens", "prefill_tokens",
                 "queue_wait", "ttft", "itl", "queue_depth", "queue_now",
                 "occupancy", "steps", "drains", "gather_blocked",
                 "steps_in_flight", "pages_in_use",
                 "peak_pages", "active_seqs", "cached_pages",
                 "evictable_pages", "spec_drafted", "spec_accepted",
                 "spec_rejected", "accept_len", "digest_epoch",
                 "moe_held_rows", "moe_rows_laid_out", "moe_expert_rows_max",
                 "state_resets", "index_pairs", "selected_keys")

    def __init__(self):
        m = _obs.metrics
        # speculative decoding (ISSUE 9): drafted/accepted/rejected token
        # counters + per-dispatch accepted-prefix-length histogram, all
        # folded in when the step is gathered
        self.spec_drafted = m.counter("serving.spec.drafted_tokens")
        self.spec_accepted = m.counter("serving.spec.accepted_tokens")
        self.spec_rejected = m.counter("serving.spec.rejected_tokens")
        self.accept_len = m.histogram(
            "serving.spec.accept_len",
            bounds=[0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0])
        # one observation a step, the sum over the layers, folded in when
        # the step is gathered — (token, choice) entries that fell on held
        # experts
        # (every expert is held unless the chip holds a share) and the
        # rows of the tiles the grouped GEMM laid out for them
        rows_bounds = [float(2 ** i) for i in range(4, 21)]
        self.moe_held_rows = m.histogram("serving.moe_held_rows",
                                         bounds=rows_bounds)
        self.moe_rows_laid_out = m.histogram("serving.moe_rows_laid_out",
                                             bounds=rows_bounds)
        # the fullest expert's entries, summed over the layers as the two
        # above (every expert held, grouped dispatch on one device)
        self.moe_expert_rows_max = m.histogram(
            "serving.moe_expert_rows_max", bounds=rows_bounds)
        # slots whose recurrent state the device zeroes at their first
        # chunk (a stack with a state-space mixer; else it stays 0)
        self.state_resets = m.counter("serving.state_resets")
        # a learned index's work in one layer of a step (one observation a
        # plain step of a stack that has one): the pairs scored, the keys
        # chosen
        pair_bounds = [float(4 ** i) for i in range(2, 14)]
        self.index_pairs = m.histogram("serving.index_pairs",
                                       bounds=pair_bounds)
        self.selected_keys = m.histogram("serving.selected_keys",
                                         bounds=pair_bounds)
        self.requests = m.counter("serving.requests_total")
        self.completed = m.counter("serving.requests_completed")
        self.tokens = m.counter("serving.tokens_generated")
        self.prefill_tokens = m.counter("serving.prefill_tokens")
        self.queue_wait = m.histogram("serving.queue_wait_ms")
        self.ttft = m.histogram("serving.ttft_ms")
        self.itl = m.histogram("serving.itl_ms")
        self.queue_depth = m.histogram("serving.queue_depth")
        self.queue_now = m.gauge("serving.queue_depth_now")
        self.occupancy = m.histogram("serving.batch_occupancy")
        self.steps = m.counter("serving.steps")
        self.drains = m.counter("serving.drains")
        # gathers that had to wait for the device, by what made them
        self.gather_blocked = {
            why: m.counter("serving.gather_blocked", reason=why)
            for why in GATHER_BLOCKS}
        self.steps_in_flight = m.histogram(
            "serving.steps_in_flight",
            bounds=[float(i) for i in range(1, 9)])
        self.pages_in_use = m.gauge("serving.pages_in_use")
        self.peak_pages = m.gauge("serving.peak_pages_in_use")
        self.active_seqs = m.gauge("serving.active_seqs")
        self.cached_pages = m.gauge("serving.prefix_cached_pages")
        self.evictable_pages = m.gauge("serving.prefix_evictable_pages")
        self.digest_epoch = m.gauge("serving.prefix_digest_epoch")

    def update_pool(self, stats: dict) -> None:
        """Fold the allocator/prefix-cache gauges in from engine.stats()
        (called at every gather — the existing host touch point)."""
        self.pages_in_use.set(stats["pages_in_use"])
        self.peak_pages.set(stats["peak_in_use"])
        self.active_seqs.set(stats["active_seqs"])
        if "prefix_cached_pages" in stats:
            self.cached_pages.set(stats["prefix_cached_pages"])
            self.evictable_pages.set(stats["prefix_evictable_pages"])
            self.digest_epoch.set(stats.get("prefix_digest_epoch", 0))


class ContinuousBatchingEngine:
    """vLLM-style continuous batching over the fused serving step
    (reference product surface: the fused multi-transformer serving stack,
    analysis_predictor + block_multihead_attention).

    Single-step design: admission does NOT run a separate prefill program —
    newly admitted prompts stream through the SAME jitted step as decode,
    in ``prefill_bucket``-sized chunks, while already-running rows keep
    decoding in the same call (their single token rides column 0 of the
    chunk bucket).  Two program families per sampling config (T=1
    decode-only steps and T=bucket mixed steps, the latter a packed and a
    dense member), each compiled whole at its first dispatch; every warm
    step reuses them — telemetry-asserted zero recompiles.

    EOS / budget / capacity freezing happens on device.  Every dispatch
    sends its own results towards the host and every ``step()`` gathers the
    steps that have landed (``_gather``): their tokens go to their requests,
    finished requests are retired (their pages back to the pool) and waiting
    ones admitted, one step after the device made the token.  The host
    waits for the device only with ``MAX_STEPS_IN_FLIGHT`` steps out, with
    nothing to dispatch, or when asked for a settled engine (``_drain``), so
    steady state runs one async dispatch per step and the device always has
    the next step queued.

    With ``prefix_cache=True`` (or ``FLAGS_prefix_cache``) admission
    consults the radix prefix cache (``inference/prefix_cache.py``): a
    prompt's longest cached page-aligned prefix is attached to its block
    table by reference (zero prefill compute and zero KV writes for those
    tokens — chunked prefill starts at the first uncached token), a
    fully-cached prompt privatizes its final page copy-on-write, retired
    sequences park their prompt pages in an LRU pool evicted only under
    memory pressure, and rows that matched pages a concurrent producer is
    still writing idle until the producer's prefill passes them.  Cache
    off is bit-identical to the uncached engine; greedy outputs with the
    cache on bit-match the cache-off oracle.
    """

    @_startup.around("startup.engine_build", lambda self: {
        "slots": self.B, "pages": self.g.num_pages,
        "pool_bytes": self.g.pool_bytes})
    def __init__(self, model, *, max_batch: int = 8,
                 gen: Optional[GenerationConfig] = None,
                 prefix_cache: Optional[bool] = None,
                 metrics: Optional[bool] = None,
                 spec_decode=None, spec_k: Optional[int] = None,
                 spec_ngram_max: Optional[int] = None,
                 kv_spill_pages: Optional[int] = None, **kw):
        self.gen_cfg = gen or GenerationConfig()
        self.g = LlamaGenerator(model, max_batch=max_batch, **kw)
        B = max_batch
        self.B = B
        i32 = jnp.int32
        self.key = jax.random.key(self.gen_cfg.seed)
        self.tokens = jnp.zeros((B,), i32)          # last sampled per slot
        self.positions = jnp.zeros((B,), i32)
        self.finished = jnp.ones((B,), bool)        # inactive == finished
        self.counts = jnp.zeros((B,), i32)
        self._budgets_np = np.zeros((B,), np.int32)   # host mirror
        self.budgets = jnp.asarray(self._budgets_np)
        self.slot_req: List[Optional[Request]] = [None] * B
        self.prompt_pos = np.zeros((B,), np.int64)  # prompt tokens consumed
        self.host_lens = np.zeros((B,), np.int64)
        self.waiting: "deque[Request]" = deque()
        self.completed: dict = {}            # req_id -> generated tokens
        self._next_id = 0
        self._bt = np.zeros((B, self.g.pages_per_seq), np.int32)
        self._bt_dev = jnp.asarray(self._bt)
        self._ql1 = jnp.ones((B,), i32)
        # the steps in flight, oldest first: dispatched, their results
        # on the way to the host, not yet gathered (plain and speculative
        # dispatches alike; at most MAX_STEPS_IN_FLIGHT after a dispatch)
        self._pending: List[_InFlight] = []
        self._step_no = 0               # running number of step() calls
        # per-slot hard cap on VALID generated tokens, set when a sequence
        # freezes early (KV pool ran dry mid-decode): the steps in flight
        # keep emitting frozen repeats, which the gather trims here
        self._gen_cap: List[Optional[int]] = [None] * B
        # ---- observability (ISSUE 5): per-request lifecycle telemetry —
        # TTFT/ITL/queue/occupancy histograms + pool gauges, all host-
        # timestamped at dispatch and folded in when a step is gathered
        # (no added device syncs; warm steps tested compile/sync-free)
        if metrics is None:
            metrics = _obs.metrics_enabled()
        self._obs: Optional[_ServingMetrics] = \
            _ServingMetrics() if metrics else None
        # ---- per-phase step attribution (ISSUE 10): every dispatch is
        # classified by program shape (prefill chunk / decode / spec
        # verify / COW copy / drain) — stamp() on the hot path
        # is one list append; durations, histograms and EWMA baselines
        # all fold at the gather
        self.attribution: Optional[_obs.StepAttribution] = \
            _obs.StepAttribution() if metrics else None
        # ---- prefix cache (ISSUE 4): radix-shared KV pages ----
        if prefix_cache is None:
            prefix_cache = flags.flag("prefix_cache")
        self.prefix_cache = None
        # per-slot admission leftovers: nodes a row must wait on before its
        # first prefill chunk (the producer row is still writing them) and
        # the COW page copies to dispatch once the row is cleared to start
        self._gate: List[tuple] = [()] * B
        self._cow_pairs: List[List[tuple]] = [[] for _ in range(B)]
        # ---- speculative decoding (ISSUE 9) ----
        # resolved once; the verify program is jitted per (sampling
        # config, K) so every warm spec step reuses it
        self.spec = _sp.resolve_spec_config(spec_decode, spec_k,
                                            spec_ngram_max)
        self._spec_counts = {"spec_steps": 0, "spec_committed_tokens": 0,
                             "spec_drafted_tokens": 0,
                             "spec_accepted_tokens": 0,
                             "spec_rejected_tokens": 0}
        if self.spec is not None:
            # host-owned history table (rebuilt at admission/gather only)
            # + the device-resident recent-token ring the steps maintain
            self._hist = _sp.SpecHistory(B, self.g.max_seq_len)
            self._recent = jnp.full((B, self.spec.ngram_max),
                                    int(_sp.CTX_PAD), jnp.int32)
        else:
            self._hist = None
            self._recent = None
        # ngram spec engines thread the drafter's recent-token ring
        # through EVERY step (prefill commits update it too), so the
        # verify step's context is exact when the row reaches decode
        self._track_recent = self._recent is not None
        if self.g.spec.state_mixer is not None:
            # what cannot follow a recurrent state is refused now
            if prefix_cache:
                raise ValueError(
                    "inference/prefix_cache.py: a prefix hit hands a "
                    "request pages, and a stack with a recurrent state "
                    "needs the STATE at the boundary too, which no page "
                    "holds: build the engine with prefix_cache off")
            if self.spec is not None:
                raise ValueError(
                    "inference/speculative.py: a rejected draft cannot be "
                    "taken out of a recurrent state (the tail rollback "
                    "moves a cursor over pages): build the engine with "
                    "spec_decode off")
        self._families: dict = {}      # T -> {GEMM rows: compiled step}
        # the compiled step programs return carried state committed to
        # their device (mesh-replicated, out_specs P(), under tp).  Seed
        # the carried arrays with the SAME sharding, or the first step
        # flips their layout and the second admission wave re-specializes
        # every eager op AND the step program (warm contract: 0 compiles)
        if self.g.tp > 1:
            rep = jax.sharding.NamedSharding(
                self.g.mesh, jax.sharding.PartitionSpec())
        else:
            rep = jax.sharding.SingleDeviceSharding(
                next(iter(self.key.devices())))
        self.tokens, self.positions, self.finished, self.counts, \
            self.key = jax.device_put(
                (self.tokens, self.positions, self.finished,
                 self.counts, self.key), rep)
        if self._recent is not None:
            self._recent = jax.device_put(self._recent, rep)
        # per-row write caps for the spec programs (tokens the block
        # table covers): cached device array, refreshed only when an
        # allocation/truncation/admission changed it — the same
        # dirty-flag pattern as _bt_dev, so warm spec steps upload nothing
        self._caps_dev = jnp.zeros((B,), jnp.int32)
        self._caps_dirty = True
        self.spill = None
        self.last_stats: dict = self.stats()
        if prefix_cache:
            from .prefix_cache import PrefixCache
            self.prefix_cache = PrefixCache(
                self.g.cache.allocator, self.g.page_size,
                min_pages=flags.flag("prefix_cache_min_pages"))
            self._cow_jit = self.g.pool_jit(
                functools.partial(_cow_copy_pages,
                                  page_axes=self.g.cache.page_axes),
                "pool_cow_copy", n_extra=2)
            # warm the copy program with an all-no-op call so the first
            # cache hit (and every later one) stays zero-recompile
            none = jnp.full((B,), -1, jnp.int32)
            with _startup.program("jit_pool_cow_copy"):
                self.g.cache.update(*self._cow_jit(self.g.cache.arrays,
                                                   none, none))
            # ---- host-RAM spill tier (ISSUE 13): LRU-evicted prefix
            # pages spill to a pinned-host ring instead of dropping, and
            # admission swaps them back asynchronously — eviction becomes
            # a DMA instead of a re-prefill
            if kv_spill_pages is None:
                kv_spill_pages = flags.flag("kv_spill_pages")
            if kv_spill_pages and kv_spill_pages > 0:
                from .kv_spill import HostSpillPool
                self.spill = HostSpillPool(self.g.cache,
                                           int(kv_spill_pages))
                self.prefix_cache.set_spill(self.spill)
                # warm the swap-in upload program (out-of-range page ->
                # dropped scatter) so a warm swap-in never compiles
                self.spill.warm()
            self.last_stats = self.stats()

    # ---- public api ----
    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None,
               trace_id: Optional[str] = None) -> Request:
        """Enqueue a request and return its live ``Request`` object (the
        HTTP front door streams tokens by watching ``req.output`` grow at
        every step's gather).  ``trace_id`` threads the caller's trace
        context through the request's lifecycle spans."""
        rid = self._next_id
        self._next_id += 1
        req = Request(rid, prompt,
                      max_new_tokens or self.gen_cfg.max_new_tokens,
                      trace_id=trace_id)
        self.waiting.append(req)
        if self._obs is not None:
            req.t_enqueue = time.perf_counter()
            self._obs.requests.inc()
            self._obs.queue_now.set(len(self.waiting))
        return req

    def add_request(self, prompt: Sequence[int],
                    max_new_tokens: Optional[int] = None) -> int:
        return self.submit(prompt, max_new_tokens).req_id

    def cancel_waiting(self, req: Request) -> bool:
        """Retire a request still in the WAITING queue — never admitted,
        holding no pages, zero prefill spent (the queue-expiry shedding
        seam, ISSUE 15).  Returns False once admission has already
        picked it up (too late to shed for free)."""
        try:
            self.waiting.remove(req)
        except ValueError:
            return False
        req.done = True
        if self._obs is not None:
            self._obs.queue_now.set(len(self.waiting))
        return True

    def has_work(self) -> bool:
        return bool(self.waiting) or any(r is not None for r in self.slot_req)

    def step_operands(self, T: int) -> tuple:
        """The fused step program's operands for q bucket ``T`` as
        ``jax.ShapeDtypeStruct``s (shapes, dtypes and shardings of what
        ``step()`` passes), so the program can be lowered or compiled
        without running it.  The same for every GEMM row count."""
        sds = jax.ShapeDtypeStruct
        B = self.B

        def like(a):
            return sds(a.shape, a.dtype, sharding=a.sharding)

        ivec, bvec = sds((B,), jnp.int32), sds((B,), jnp.bool_)
        ops = (jax.tree_util.tree_map(like, self.g.params),
               jax.tree_util.tree_map(like, tuple(self.g.cache.arrays)),
               sds((B, T), jnp.int32), ivec, like(self.positions),
               like(self.finished), bvec, bvec, like(self.counts), ivec,
               sds(self._bt.shape, jnp.int32), like(self.key))
        return ops + ((like(self._recent),) if self._track_recent else ())

    def lowered_step(self, T: int, rows: Optional[int] = None):
        """``jax.stages.Lowered`` of the fused step program ``step()``
        dispatches for q bucket ``T`` and ``rows`` GEMM rows (default: the
        dense grid).  ``as_text()`` shows whether the paged kernel is in
        it; ``compile().memory_analysis()`` what it takes."""
        return self.g._step_jit(self.gen_cfg, T, self._track_recent,
                                rows).lower(*self.step_operands(T))

    def _step_family(self, T: int) -> dict:
        """``{GEMM rows: compiled step program}`` of q bucket ``T``, the
        whole family compiled when its first member is asked for: no later
        step may compile (the warm contract), whichever bucket its
        ``q_tokens`` fall in.  Compiled ahead of time from
        ``step_operands``: nothing runs, no operand is held twice.  One
        after the other: on threads four cold compiles took 12.3 s for
        19.4, but four reads from the persistent cache 1.8 s for 1.0
        (v5e, PR 25), and a server starts warm more often."""
        fam = self._families.get(T)
        if fam is None:
            fam = self._families[T] = {
                n: self._compiled_step(T, n) for n in self.g.row_buckets(T)}
        return fam

    def _compiled_step(self, T: int, rows: int):
        """One member of a family, built under the start-up log's phases:
        tracing and lowering (Python, paid on every start), then the
        backend's compile or the persistent cache's read, and the load."""
        with _startup.program(f"jit_serve_step_T{T}", T=T, rows=rows):
            with _startup.phase("startup.lower"):
                lowered = self.lowered_step(T, rows)
            with _startup.compiling():
                return lowered.compile()

    def run(self) -> dict:
        """Drive to completion; returns {req_id: generated tokens} for every
        request completed so far (incl. during earlier manual step() calls)."""
        while self.has_work():
            self.step()
        self._drain("idle")
        return dict(self.completed)

    # ---- engine step ----
    def step(self) -> List[Request]:
        """Gather the steps that have landed, admit what fits, run ONE
        fused device step.  Returns requests retired by this call."""
        self._step_no += 1
        with _obs.TRACER.span("engine.step", step=self._step_no,
                              slots=self.B) as span:
            return self._step(span)

    def _step(self, span) -> List[Request]:
        """``step`` inside its ``engine.step`` span.  Each phase of the
        host's work runs under a span of its own (``catalog.SPANS``), so
        nothing between two device launches lies outside a named one."""
        tracer = _obs.TRACER
        # first what has landed (and, at the bound, the oldest step): the
        # slots it frees are admitted into below.  Requests retired here
        # or by a mid-step emergency drain (pool pressure under
        # speculative overestimate) ride this call's return — callers
        # stream completions off it
        early_done: List[Request] = self._gather()
        with tracer.span("engine.admit") as sp:
            admitted = self._admit()
            sp.set_metadata(admitted=admitted, waiting=len(self.waiting))
        if all(r is None for r in self.slot_req):
            span.set_metadata(kind="idle", T=0, rows=0, q_tokens=0,
                              gemm_rows=0, kv_read_tokens=0, attn_rows=0,
                              page_copies=0, waiting=len(self.waiting))
            return early_done + self._drain("idle")
        g = self.g
        B = self.B
        if self.prefix_cache is not None:
            self._open_gates()
        prompt_rows = [b for b in range(B)
                       if self.slot_req[b] is not None and not self._gate[b]
                       and self.prompt_pos[b] < len(self.slot_req[b].prompt)]
        # ISSUE 9: decode-only steps ride the speculative lane — ONE
        # dispatch verifies/commits up to K tokens per row.  Mixed steps
        # (prefill chunks in flight, or prefix-gated rows whose shared
        # pages are still being produced) use the plain bucket step.
        spec_lane = (self.spec is not None and not prompt_rows
                     and not any(self._gate)
                     and any(r is not None for r in self.slot_req))
        T = g.prefill_bucket if prompt_rows else 1
        if spec_lane:
            # the device may commit up to K tokens per row this dispatch:
            # bump the host-side length bound FIRST so the shared growth
            # loop below covers every position the step can write
            # (safe-by-overestimate; the gather resyncs the bound to the
            # device's true commit count, plus what the steps still in
            # flight may commit, and rolls surplus pages back)
            for b in range(B):
                req = self.slot_req[b]
                if req is not None and self.prompt_pos[b] >= len(req.prompt):
                    self.host_lens[b] = min(
                        int(self.host_lens[b]) + self.spec.k, g.max_seq_len)

        with tracer.span("engine.grow") as sp:
            grown = self._grow_pages(early_done)
            sp.set_metadata(pages=grown)

        if spec_lane:
            # ---- speculative lane: the ngram verify step ----
            with tracer.span("engine.h2d") as sp:
                sp.set_metadata(arrays=self._upload_tables(grown)
                                + self._upload_caps())
            rows = sum(r is not None for r in self.slot_req)
            k = int(self.spec.k)
            span.set_metadata(
                kind="spec", T=k, rows=rows, q_tokens=rows * k,
                gemm_rows=B * k, waiting=len(self.waiting))
            if tracer.listening():
                span.set_metadata(**g.attention_counts(k, [
                    (k, max(int(self.host_lens[b]) - k, 0))
                    for b in range(B) if self.slot_req[b] is not None]))
            out_mat, ncommit, dlen = self._dispatch_spec()
            t_step = time.perf_counter()
            self._in_flight(_InFlight("spec", out_mat, ncommit, dlen,
                                      self.finished, None,
                                      list(self.slot_req), t_step))
            if self.attribution is not None:
                # committed-token counts are device-resident until the
                # gather; credit_tokens() supplies them there
                self.attribution.stamp("spec_verify", k, t_step)
            if self._obs is not None:
                o = self._obs
                o.steps.inc()
                o.occupancy.observe(rows / B)
                o.queue_depth.observe(len(self.waiting))
                o.queue_now.set(len(self.waiting))
            return early_done

        with tracer.span("engine.build"):
            ql = np.zeros((B,), np.int32)
            decode = np.zeros((B,), bool)
            commit = np.zeros((B,), bool)
            chunk = np.zeros((B, T), np.int32)
            rows = 0
            attends = []         # (query tokens, context before them) a row
            for b in range(B):
                req = self.slot_req[b]
                if req is None:
                    continue
                rows += 1
                if self._gate[b]:
                    # gated: this row's matched prefix pages are still
                    # being written by their producer row — idle until
                    # they're ready
                    continue
                rem = len(req.prompt) - int(self.prompt_pos[b])
                attends.append((min(max(rem, 1), T), int(self.host_lens[b])))
                if rem > 0:                      # prefill chunk
                    n = min(rem, T)
                    ql[b] = n
                    chunk[b, :n] = np.asarray(
                        req.prompt[self.prompt_pos[b]:self.prompt_pos[b] + n],
                        np.int32)
                    commit[b] = n == rem         # consumes the final token
                    self.prompt_pos[b] += n
                    self.host_lens[b] += n
                else:                            # decode row
                    ql[b] = 1
                    decode[b] = True
                    commit[b] = True
                    self.host_lens[b] += 1
            q_tokens = int(ql.sum())

        with tracer.span("engine.h2d") as sp:
            sp.set_metadata(arrays=4 + self._upload_tables(grown))
            tokens_in = jnp.asarray(chunk)
            dm = jnp.asarray(decode)
            ql_dev = jnp.asarray(ql)
            commit_dev = jnp.asarray(commit)
            if T == 1:
                tokens_in = jnp.where(dm[:, None], self.tokens[:, None],
                                      tokens_in)
            else:
                tokens_in = tokens_in.at[:, 0].set(
                    jnp.where(dm, self.tokens, tokens_in[:, 0]))

        # the host knows q_tokens exactly and the device-side freeze can
        # only lower it: the smallest row bucket that holds it
        gemm_rows = g.gemm_rows(T, q_tokens)
        track = self._track_recent
        step = self._step_family(T)[gemm_rows]
        span.set_metadata(kind="mixed" if T > 1 else "decode", T=int(T),
                          rows=rows, q_tokens=q_tokens, gemm_rows=gemm_rows,
                          waiting=len(self.waiting))
        if tracer.listening():
            # three loops over the working slots, for a reader's sake alone
            counts = g.attention_counts(T, attends)
            span.set_metadata(**counts)
            if g.spec.index is not None and self._obs is not None:
                self._obs.index_pairs.observe(float(counts["index_pairs"]))
                self._obs.selected_keys.observe(
                    float(counts["selected_keys"]))
            if g.spec.state_mixer is not None:
                # the slots whose recurrent state the step's scan calls
                # read and write (those with work), and the tokens they scan
                span.set_metadata(ssm_slots=int((ql > 0).sum()),
                                  ssm_tokens=q_tokens)
        with tracer.span("engine.dispatch", program=f"serve_step_T{T}"):
            out = step(g.params, g.cache.arrays, tokens_in, ql_dev,
                       self.positions, self.finished, dm, commit_dev,
                       self.counts, self.budgets, self._bt_dev, self.key,
                       *((self._recent,) if track else ()))
            (self.tokens, self.positions, self.finished, _all_done,
             self.counts, cache, self.key) = out[:7]
            if track:
                self._recent = out[7]
            g.cache.update(*cache)
        # the step's own arrays start for the host now; the dispatch
        # stamp rides with them (a request's first gap opens there)
        t_step = time.perf_counter()
        self._in_flight(_InFlight(
            "step", self.tokens, commit, None, self.finished,
            out[-1] if g.counts_moe_rows else None, list(self.slot_req),
            t_step))
        if self.attribution is not None:
            # a mixed step (prefill chunks in flight) is the prefill-
            # chunk program shape; T=1 is pure decode.  Tokens = query
            # tokens this dispatch processed (prompt chunk + decode cols)
            self.attribution.stamp("prefill" if T > 1 else "decode",
                                   int(T), t_step, q_tokens)
        if self._obs is not None:
            o = self._obs
            o.steps.inc()
            o.occupancy.observe(rows / B)
            o.queue_depth.observe(len(self.waiting))
            o.queue_now.set(len(self.waiting))
            n_prefill = q_tokens - int(decode.sum())
            if n_prefill:
                o.prefill_tokens.inc(n_prefill)
        if self.prefix_cache is not None:
            # this step's prefill writes are now dispatched: pages wholly
            # below each row's prompt cursor are safe for later steps of
            # other rows to read (device execution is dispatch-ordered)
            for b in range(B):
                req = self.slot_req[b]
                if req is not None and ql[b] > 0 and not decode[b]:
                    self.prefix_cache.note_progress(
                        req.req_id, int(self.prompt_pos[b]))
        return early_done

    def _in_flight(self, entry: _InFlight) -> None:
        """One more step dispatched: its results wait to be gathered."""
        self._pending.append(entry)
        if self._obs is not None:
            self._obs.steps_in_flight.observe(len(self._pending))

    def _grow_pages(self, early_done: List[Request]) -> int:
        """Grow pages BEFORE the step: every position this step writes
        must already be inside the allocated table (prompts are allocated
        in full at admission; decode rows may cross a page boundary
        here).  Returns the pages allocated; requests an emergency drain
        retired on the way are appended to ``early_done``."""
        g = self.g
        alloc = g.cache.allocator
        grown = 0
        for b in range(self.B):
            req = self.slot_req[b]
            if req is None or self.prompt_pos[b] < len(req.prompt):
                continue
            while alloc.context_len(req.req_id) <= int(self.host_lens[b]) \
                    and alloc.context_len(req.req_id) < g.max_seq_len:
                if alloc.available_pages == 0:
                    if self.spec is not None and self._pending:
                        # the speculative overestimate may be what holds
                        # the pool: settle now — with nothing in flight
                        # the gather resyncs host lengths to the device's
                        # own and rolls surplus tail pages back — then
                        # retry this row's growth (at most once: nothing
                        # is in flight afterwards)
                        early_done.extend(self._drain())
                        if self.slot_req[b] is None:
                            break
                        continue
                    # pool ran dry mid-decode (undersized num_pages):
                    # finalize THIS sequence early instead of raising —
                    # freeze it on device (no further writes) and cap its
                    # valid output at what was generated before this step
                    if self._gen_cap[b] is None:
                        n = len(req.output)
                        for e in self._pending:
                            if e.reqs[b] is not req:
                                continue     # a predecessor's repeats
                            if e.kind != "step":
                                # degraded path (pool exhausted): the
                                # exact cap needs the in-flight spec
                                # commit counts — one marked sync
                                _obs.count_sync()
                            n += int(e.commit[b])
                        self._gen_cap[b] = n
                        self.finished = self.finished.at[b].set(True)
                    break
                alloc.extend(req.req_id,
                             min(g.page_size,
                                 g.max_seq_len
                                 - alloc.context_len(req.req_id)))
                self._bt[b] = alloc.block_table(
                    [req.req_id], max_pages=g.pages_per_seq)[0]
                grown += 1
        return grown

    def _upload_tables(self, grown: int) -> int:
        """Upload the block table when this step grew it, and mark the
        speculative lane's write caps stale; the arrays uploaded."""
        if not grown:
            return 0
        self._upload_bt()
        return 1

    def _upload_bt(self) -> None:
        """The block table to the device, as a COPY: steps in flight keep
        the table they were handed, and ``jnp.asarray`` of a numpy array
        may share its memory, so the host's own must stay free to change
        (a slot handed on, a tail rolled back) under them."""
        self._bt_dev = jnp.asarray(self._bt.copy())
        self._caps_dirty = True

    # ---- prefix-cache gates: rows waiting on producer prefill ----
    def _open_gates(self):
        """Clear gates whose matched pages became ready, and dispatch the
        newly-cleared rows' pending COW page copies BEFORE this step's
        pallas call reads them.  Producers advance every step, so every
        gate opens in bounded time."""
        starting = []
        for b in range(self.B):
            if self._gate[b] and all(x.ready for x in self._gate[b]):
                self._gate[b] = ()
            if not self._gate[b] and self._cow_pairs[b]:
                starting.extend(self._cow_pairs[b])
                self._cow_pairs[b] = []
        if starting:
            src = np.full((self.B,), -1, np.int32)
            dst = np.full((self.B,), -1, np.int32)
            for i, (s, d) in enumerate(starting):
                src[i], dst[i] = s, d
            with _obs.TRACER.span("engine.dispatch",
                                  program=self._cow_jit.__name__):
                self.g.cache.update(*self._cow_jit(
                    self.g.cache.arrays, jnp.asarray(src), jnp.asarray(dst)))
            if self.attribution is not None:
                self.attribution.stamp("cow_copy", 0)

    # ---- ISSUE 9: the speculative dispatch (decode-only batches) ----
    def _dispatch_spec(self):
        """Dispatch ONE speculative step, the T=K ngram verify program.
        Everything the step consumes beyond the chained engine state is
        either static (K, sampling config) or refreshed by the gather (the
        history table, uploaded again when a gathered step extended it),
        so the warm spec loop reads nothing back from the device.

        Returns the in-flight entry's payload ``(out [B, K], n_commit [B],
        drafted [B])`` — device arrays, materialized at the gather.
        """
        g = self.g
        spec = self.spec
        hist, hist_len = self._hist.device_arrays()
        step = g._spec_jit(self.gen_cfg, spec.k, spec.ngram_max)
        with _obs.TRACER.span("engine.dispatch", program=step.__name__):
            (out, ncommit, dlen, self.tokens, self.positions,
             self.finished, _all_done, self.counts, self._recent, cache,
             self.key) = step(
                g.params, g.cache.arrays, self.tokens, self._recent,
                hist, hist_len, self.positions, self.finished,
                self.counts, self.budgets, self._caps_dev,
                self._bt_dev, self.key)
            g.cache.update(*cache)
        return out, ncommit, dlen

    def _upload_caps(self) -> int:
        """Per-row write caps of the speculative lane: tokens the block
        table actually covers — the device clamps ql against them, so a
        step can NEVER scatter into pages the row does not own (pad
        entries point at page 0).  Cached: only an allocation /
        truncation / admission refreshes it; the arrays uploaded."""
        if not self._caps_dirty:
            return 0
        alloc = self.g.cache.allocator
        caps = np.zeros((self.B,), np.int32)
        for b in range(self.B):
            req = self.slot_req[b]
            if req is not None:
                caps[b] = alloc.context_len(req.req_id)
        self._caps_dev = jnp.asarray(caps)
        self._caps_dirty = False
        return 1

    # ---- serving telemetry ----
    def stats(self) -> dict:
        """Pool + prefix-cache telemetry (refreshed at every gather into
        ``last_stats``).  With the cache off, every prefix counter is 0."""
        s = self.g.cache.allocator.stats()
        s["kv_cache_dtype"] = ("int8" if self.g.cache.quantized
                               else str(self.g.cache.dtype))
        # capacity advertisement (tensor-parallel serving): /statusz
        # carries these so the router's capacity-weighted placement can
        # rank heterogeneous fleets (a tp=4 replica outranks tp=1)
        s["tp"] = self.g.tp
        s["pool_bytes"] = self.g.pool_bytes
        s["prefix_cache_enabled"] = self.prefix_cache is not None
        if self.prefix_cache is not None:
            s["prefix_cached_pages"] = self.prefix_cache.cached_pages()
            s["prefix_evictable_pages"] = self.prefix_cache.evictable_pages()
            s["prefix_spilled_pages"] = self.prefix_cache.spilled_pages()
            s["prefix_digest_epoch"] = self.prefix_cache.digest_epoch
        # session-migration books (ISSUE 14; present once the engine has
        # exported or imported at least one snapshot)
        mc = getattr(self, "_migration_counts", None)
        if mc is not None:
            s.update(mc)
        s["kv_spill_enabled"] = self.spill is not None
        if self.spill is not None:
            s.update(self.spill.stats())
        s["spec_decode_enabled"] = self.spec is not None
        if self.spec is not None:
            s["spec_mode"] = self.spec.mode
            s["spec_k"] = self.spec.k
            s.update(self._spec_counts)
        return s

    def inflight_requests(self, top_k: int = 8) -> List[dict]:
        """Oldest in-flight requests (busy slots + waiting queue) with
        their trace ids — the ``/statusz`` hung-request table (ISSUE 10
        satellite): a request stuck in prefill or starved in the queue is
        findable by id and age without exporting a trace dump.

        Read-only over host state, safe to call from the statusz thread
        while the engine thread runs (worst case a row retires mid-walk
        and simply drops out of the next scrape)."""
        now = time.perf_counter()

        def row(req: Request, state: str, slot) -> dict:
            t0 = req.t_enqueue
            return {"req_id": req.req_id, "trace_id": req.trace_id,
                    "state": state, "slot": slot,
                    "age_s": None if t0 is None else round(now - t0, 3),
                    "prompt_tokens": len(req.prompt),
                    "generated": len(req.output)}

        rows = []
        for b in range(self.B):
            req = self.slot_req[b]
            if req is None:
                continue
            state = "prefill" if self.prompt_pos[b] < len(req.prompt) \
                else "decode"
            rows.append(row(req, state, b))
        for req in list(self.waiting):
            rows.append(row(req, "queued", None))
        rows.sort(key=lambda r: -(r["age_s"] or 0.0))
        return rows[:top_k]

    def prefix_digest(self, max_entries: Optional[int] = None,
                      since: Optional[str] = None):
        """Prefix-residency digest for router placement (ISSUE 7): the
        chain hashes of this engine's indexed KV pages plus the page
        geometry a router needs to compute matching hashes for an
        incoming prompt (``prefix_cache.block_hashes``).  ``None`` with
        the cache off — a digest-less replica scores zero expected hits
        and degrades to pure load-based placement.

        ``since="<gen>:<epoch>"`` (ISSUE 14 delta sync) asks for only
        the adds/evictions after a previously confirmed epoch: the
        answer is ``mode="delta"`` with ``adds``/``dels`` lists when the
        change log still covers that epoch and the generation nonce
        matches this cache instance, else ``mode="full"`` with the
        whole (truncated) set — the caller resyncs and re-confirms."""
        cache = self.prefix_cache
        if cache is None:
            return None
        if max_entries is None:
            max_entries = flags.flag("router_digest_max")
        out = {"page_size": self.g.page_size,
               "algo": "blake2b8-chain",
               "gen": cache.digest_gen,
               "epoch": cache.digest_epoch,
               # spill-aware scoring (ISSUE 16 satellite): the digest
               # subset demoted to the host ring, shipped in FULL every
               # poll (bounded by the spill ring; spill transitions
               # don't change index membership, so the delta log can't
               # carry them)
               "spilled": cache.spilled_hashes()}
        # digest sketch (ISSUE 19): past the size threshold the exact
        # hash list (O(resident pages) bytes) gives way to the counting-
        # Bloom membership bitmap (m/8 bytes, flat).  Sketch mode ships
        # whole every poll — no epochs to confirm, so delta sync is
        # moot at this size.
        sk = cache.sketch_wire()
        if (sk is not None and sk["n"] >
                int(flags.flag("router_digest_sketch_threshold"))):
            out.update(mode="sketch", sketch=sk, count=sk["n"])
            return out
        if since:
            gen, _, ep = str(since).partition(":")
            if gen == cache.digest_gen:
                try:
                    delta = cache.digest_delta(int(ep))
                except ValueError:
                    delta = None
                if delta is not None:
                    adds, dels = delta
                    out.update(mode="delta", adds=adds, dels=dels)
                    return out
        out.update(mode="full", hashes=cache.digest(max_entries))
        return out

    # ---- gather: the steps in flight, to the host as they land ----
    def _drain(self, reason: str = "settle") -> List[Request]:
        """The blocking form of ``_gather``: wait for every step in
        flight, so that the host's books are the device's (``reason``:
        "settle" for a caller that needs that, "idle" when there is
        nothing to dispatch)."""
        return self._gather(block=reason)

    def _gather(self, block: Optional[str] = None) -> List[Request]:
        """Deliver the steps in flight that have landed, oldest first, and
        retire what they finished.  Waits for the device only as far as
        it must: for the oldest step when ``MAX_STEPS_IN_FLIGHT`` are out
        (the next dispatch needs room), for all of them when ``block``
        names why (``GATHER_BLOCKS``).  Never for a newer step than it
        delivers: an entry holds its own step's arrays."""
        pending = self._pending
        n = 0
        while n < len(pending) and pending[n].landed():
            n += 1
        need = len(pending) if block is not None \
            else len(pending) - MAX_STEPS_IN_FLIGHT + 1
        blocked = ""
        if n < need:
            blocked, n = block or "bound", need
        if not n:
            return []
        window = pending[:n]
        del pending[:n]
        with _obs.TRACER.span("engine.drain", steps=n,
                              in_flight=len(pending),
                              blocked=blocked) as span:
            done, n_tokens, held_rows = self._deliver(window, blocked)
            span.set_metadata(tokens=n_tokens, held_rows=held_rows)
        return done

    def _deliver(self, window: List[_InFlight], blocked: str) -> tuple:
        """Gathered steps to the host and into their requests: (requests
        retired, tokens delivered, entries that fell on held experts: 0
        where the step does not count them)."""
        obs = self._obs
        attr = self.attribution
        t_gather0 = time.perf_counter() if attr is not None else None
        if blocked:
            _obs.count_sync()        # the host waits for the device here
            if obs is not None:
                obs.gather_blocked[blocked].inc()
        # per-array host transfers, started at dispatch; NOT a device-side
        # stack: the window's length varies and a jnp.stack would compile
        # one executable per distinct length — breaking the warm loop's
        # zero-recompile contract
        with _obs.TRACER.span("engine.drain.wait"):
            for e in window:
                e.to_host()
        # the moment these steps' tokens became visible to the host —
        # the only progress of the device the host can observe
        t_ready = time.perf_counter()
        held_rows = 0
        for e in window:
            if e.moe_rows is not None:
                held, laid_out, *fullest = e.moe_rows
                held_rows += int(held)
                if obs is not None:
                    obs.moe_held_rows.observe(float(held))
                    obs.moe_rows_laid_out.observe(float(laid_out))
                    if fullest and _obs.TRACER.listening():
                        obs.moe_expert_rows_max.observe(float(fullest[0]))
        if obs is not None:
            obs.drains.inc()
        self._fold_spec_metrics(window)
        if attr is not None:
            # fold the dispatch stamps since the last gather (the final
            # one closes against this gather's entry time) AFTER the spec
            # token credits landed in _fold_spec_metrics
            attr.fold(t_gather0)
        with _obs.TRACER.span("engine.drain.retire") as span:
            done, n_tokens, bt_dirty = self._retire_rows(window, t_ready)
            span.set_metadata(retired=len(done))
        if bt_dirty:
            self._upload_bt()
        self.last_stats = self.stats()
        if obs is not None:
            obs.update_pool(self.last_stats)
        if attr is not None:
            # the gather IS a phase: the host's wait for the device, if
            # any, plus retire bookkeeping
            attr.observe_host("drain", time.perf_counter() - t_gather0)
        return done, n_tokens, held_rows

    def _retire_rows(self, window: List[_InFlight],
                     t_ready: float) -> tuple:
        """Per-row bookkeeping of the gathered steps, one step after the
        other: tokens into the request that made them, latency stamps,
        trims, retirement.  Returns (requests retired, tokens delivered,
        whether a block-table row changed)."""
        obs = self._obs
        done: List[Request] = []
        n_tokens = 0
        eos = self.gen_cfg.eos_token_id
        resync = {}          # speculative rows still running: slot -> req
        for e in window:
            spec = e.kind == "spec"
            fin = e.finished
            for b, req in enumerate(e.reqs):
                if req is None or req.done:
                    # an empty slot, or a step dispatched past its
                    # request's retirement: what the row holds is a
                    # frozen repeat, and belongs to no request (least of
                    # all to the one the slot was handed to since)
                    continue
                prev_len = len(req.output)
                # committed tokens of this step: a plain step contributes
                # its column-0 sample where the host marked the row
                # committing; a spec step its device-computed accepted
                # prefix (frozen rows: 0 tokens)
                if spec:
                    new_tok = [int(v) for v in e.out[b, :int(e.commit[b])]]
                else:
                    new_tok = [int(e.out[b])] if e.commit[b] else []
                req.output.extend(new_tok)
                n_tokens += len(new_tok)
                # cap = what physically fits in the cache (max_seq minus
                # the prompt), further lowered if the KV pool ran dry
                # mid-decode
                cap = max(1, self.g.max_seq_len - len(req.prompt))
                if self._gen_cap[b] is not None:
                    cap = min(cap, max(1, self._gen_cap[b]))
                if obs is not None and new_tok:
                    # TTFT/ITL are stamped HERE, when the token reaches
                    # the host: the only moment of the device's progress
                    # the host can see.  A plain step brings one token, so
                    # an observation is the true gap since the request's
                    # previous token; a speculative step's n tokens share
                    # the span.  Commits the trims below drop — past the
                    # budget, past cache capacity, or frozen repeats after
                    # a device-side EOS — are not real tokens and must
                    # not be timed
                    room = min(max(0, req.max_new_tokens - prev_len),
                               max(0, cap - prev_len))
                    if eos is not None and eos in new_tok:
                        room = min(room, new_tok.index(eos) + 1)
                    n_timed = min(len(new_tok), room)
                    if n_timed:
                        # a request's first token has no previous one: its
                        # span opens where its step was dispatched
                        since = req.t_last if req.t_last is not None \
                            else e.t
                        gap_ms = (t_ready - since) / n_timed * 1e3
                        if req.t_first is None:
                            req.t_first = t_ready
                            base = req.t_enqueue \
                                if req.t_enqueue is not None else e.t
                            obs.ttft.observe((t_ready - base) * 1e3)
                            n_timed -= 1
                        for _ in range(n_timed):
                            obs.itl.observe(gap_ms)
                        req.t_last = t_ready
                # device freeze repeats the last token once finished —
                # trim to the true capacity/EOS/budget boundary host-side
                if len(req.output) > cap:
                    req.output = req.output[:cap]
                if eos is not None and eos in req.output:
                    req.output = req.output[:req.output.index(eos) + 1]
                elif len(req.output) >= req.max_new_tokens:
                    req.output = req.output[:req.max_new_tokens]
                elif len(req.output) < cap and not fin[b]:
                    if obs is not None and len(req.output) > req.n_emitted:
                        obs.tokens.inc(len(req.output) - req.n_emitted)
                        req.n_emitted = len(req.output)
                    if self.spec is not None and \
                            self.prompt_pos[b] >= len(req.prompt):
                        resync[b] = req
                    if self._hist is not None and new_tok:
                        # the drafter's n-gram table grows ONLY here, from
                        # tokens the host has gathered — never a read of
                        # the device for its sake
                        self._hist.extend_row(b, new_tok)
                    continue                     # still running
                self._retire(b, req, done)
        # after the whole window: a row's bound must count every gathered
        # commit before a page is given back
        bt_dirty = False
        for b, req in resync.items():
            if not req.done:
                bt_dirty |= self._rollback_tail(b, req)
        return done, n_tokens, bt_dirty

    def _retire(self, b: int, req: Request, done: List[Request]) -> None:
        """Slot ``b``'s request is finished: its books closed, its pages
        back to the pool, the slot free for the next admission."""
        obs = self._obs
        req.done = True
        if obs is not None:
            if len(req.output) > req.n_emitted:
                obs.tokens.inc(len(req.output) - req.n_emitted)
                req.n_emitted = len(req.output)
            obs.completed.inc()
            if _obs.TRACER.enabled and req.t_enqueue is not None:
                # retroactive lifecycle spans: queued -> prefill ->
                # decode.  With a trace context (HTTP front door) the
                # lane IS the request id — one correlated track from
                # accept to retire; otherwise the slot's lane.
                tr = _obs.TRACER
                t_adm = req.t_admit or req.t_enqueue
                t_f = req.t_first if req.t_first is not None else t_adm
                t_l = req.t_last if req.t_last is not None else t_f
                lane = req.trace_id or f"slot{b}"
                rid = req.req_id
                ctx = {"trace_id": req.trace_id, "slot": b} \
                    if req.trace_id else {"slot": b}
                # component tag for the fleet collector (ISSUE 20):
                # the serving server stamps its identity on the
                # engine so multi-engine processes (the in-proc
                # disagg bench, tests) still assemble one track per
                # logical replica
                proc = getattr(self, "trace_proc", None)
                if proc:
                    ctx["proc"] = proc
                tr.event(f"req{rid}.queued", req.t_enqueue,
                         t_adm - req.t_enqueue, cat="serving",
                         tid=lane, args=ctx)
                tr.event(f"req{rid}.prefill", t_adm, t_f - t_adm,
                         cat="serving", tid=lane,
                         args={**ctx, "prompt_tokens": len(req.prompt)})
                tr.event(f"req{rid}.decode", t_f, t_l - t_f,
                         cat="serving", tid=lane,
                         args={**ctx, "generated": len(req.output)})
        if self.prefix_cache is not None:
            # retiring drops the sequence's node refs: its cached
            # prefix pages fall to the LRU free-pool (evicted only
            # when admission actually needs the memory)
            self.prefix_cache.release(req.req_id)
        self.g.cache.allocator.free(req.req_id)
        self.slot_req[b] = None
        self._gen_cap[b] = None
        # the device froze the row with the step that made its last
        # token, so no step in flight writes its pages; said again for a
        # slot whose step was the newest dispatched
        self.finished = self.finished.at[b].set(True)
        self.completed[req.req_id] = req.output
        done.append(req)

    def _fold_spec_metrics(self, window) -> None:
        """Fold the gathered steps' speculative telemetry into the engine
        books and the registry (drafted/accepted/rejected token counters +
        the accept_len histogram) — from host values, at the gather."""
        if self.spec is None:
            return
        obs = self._obs
        n_spec = c_tot = d_tot = a_tot = r_tot = 0
        for e in window:
            if e.kind != "spec":
                continue
            n_spec += 1
            cm, dl = e.commit, e.drafted
            for b in range(self.B):
                n = int(cm[b])
                d = int(dl[b])
                if n <= 0 and d <= 0:
                    continue
                acc = min(max(n - 1, 0), d)
                c_tot += n
                d_tot += d
                a_tot += acc
                r_tot += d - acc
                if obs is not None and n > 0:
                    # accepted drafts per dispatch
                    obs.accept_len.observe(float(n - 1))
        if not n_spec:
            return
        if self.attribution is not None and c_tot:
            self.attribution.credit_tokens("spec_verify", c_tot)
        sc = self._spec_counts
        sc["spec_steps"] += n_spec
        sc["spec_committed_tokens"] += c_tot
        sc["spec_drafted_tokens"] += d_tot
        sc["spec_accepted_tokens"] += a_tot
        sc["spec_rejected_tokens"] += r_tot
        if obs is not None:
            if d_tot:
                obs.spec_drafted.inc(d_tot)
            if a_tot:
                obs.spec_accepted.inc(a_tot)
            if r_tot:
                obs.spec_rejected.inc(r_tot)

    def _rollback_tail(self, b: int, req: Request) -> bool:
        """Block-table tail rollback (ISSUE 9): resync the host length
        bound to the device's true commit count, plus the most the steps
        still in flight may commit for this request (up to ``k`` a
        speculative one), and release surplus tail pages the speculative
        overestimate grew for tokens that were then rejected: no page a
        step in flight may write is given back.  ``PageAllocator.truncate``
        is refcount-aware, so only THIS sequence's references drop —
        prefix-shared and COW pages can never be yanked from a sibling.  K tokens of headroom stay
        allocated so the steady state doesn't thrash truncate/extend.
        Returns True when the row's block table changed."""
        g = self.g
        k = self.spec.k
        bound = min(len(req.prompt) + len(req.output) + sum(
            e.commits_ahead(b, k) for e in self._pending
            if e.reqs[b] is req), g.max_seq_len)
        self.host_lens[b] = bound
        alloc = g.cache.allocator
        keep = min(bound + k, g.max_seq_len)
        if alloc.context_len(req.req_id) > keep + g.page_size:
            alloc.truncate(req.req_id, keep)
            self._bt[b] = alloc.block_table(
                [req.req_id], max_pages=g.pages_per_seq)[0]
            return True
        return False

    # ---- admission (host-known free slots only; frees appear at gathers) ----
    def _admit(self) -> int:
        """Waiting requests into free slots; how many were admitted."""
        free = [b for b in range(self.B) if self.slot_req[b] is None]
        if not free or not self.waiting:
            return 0
        g = self.g
        alloc = g.cache.allocator
        cache = self.prefix_cache
        admitted = []
        starts = np.zeros((self.B,), np.int32)
        while free and self.waiting:
            req = self.waiting[0]
            # truncate ONCE here; every later length (pages, host_lens,
            # positions) derives from the truncated prompt
            req.prompt = req.prompt[: g.max_seq_len - 1]
            dense_need = -(-len(req.prompt) // g.page_size)
            # prefix match: the longest cached page-aligned prefix trims
            # both the fresh-page demand and the prefill chunk schedule
            plan = cache.plan(req.prompt) if cache is not None else None
            need = plan.fresh_pages if plan is not None else dense_need
            # matched-but-idle pages are about to be pinned, not evicted:
            # they cannot double-count as reclaimable supply
            avail = alloc.available_pages - (
                plan.idle_matched if plan is not None else 0)
            if plan is not None and plan.nodes and avail < need \
                    and len(free) == self.B and not admitted:
                # nothing is running: prefer admitting from scratch (and
                # letting reclaim evict the cache) over waiting forever
                plan = None
                need, avail = dense_need, alloc.available_pages
            if avail < need:
                if len(free) == self.B and not admitted \
                        and dense_need > alloc.num_pages:
                    raise MemoryError(
                        f"prompt needs {dense_need} pages but the pool only "
                        f"has {alloc.num_pages}; raise num_pages or "
                        "page_size")
                break                         # wait for pages to free up
            self.waiting.popleft()
            b = free.pop(0)
            if plan is not None:
                try:
                    # pin before any reclaim runs; spilled matches swap
                    # back in here (host->device upload, dispatch-only)
                    cache.attach(plan)
                except MemoryError:
                    # swap-in raced out of pages — retry next admission
                    self.waiting.appendleft(req)
                    free.insert(0, b)
                    break
                shared = [x.page for x in plan.nodes]
            else:
                shared = ()
            try:
                alloc.allocate(req.req_id, len(req.prompt),
                               shared_pages=shared)
            except MemoryError:
                # evictable estimate raced a concurrent structure change —
                # roll back and retry this request at the next admission
                if plan is not None:
                    cache.detach(plan)
                self.waiting.appendleft(req)
                free.insert(0, b)
                break
            if plan is not None:
                self._cow_pairs[b] = cache.admit(req.req_id, req.prompt,
                                                 plan)
                self._gate[b] = tuple(plan.wait)
                starts[b] = plan.start
            else:
                self._gate[b] = ()
                self._cow_pairs[b] = []
            admitted.append((b, req))
        if not admitted:
            return 0
        mask = np.zeros((self.B,), bool)
        budgets = self._budgets_np
        if self._obs is not None:
            now = time.perf_counter()
            for _, req in admitted:
                req.t_admit = now
                if req.t_enqueue is not None:
                    self._obs.queue_wait.observe(
                        (now - req.t_enqueue) * 1e3)
            self._obs.queue_now.set(len(self.waiting))
            if g.spec.state_mixer is not None:
                self._obs.state_resets.inc(len(admitted))
        for b, req in admitted:
            self.slot_req[b] = req
            self.prompt_pos[b] = int(starts[b])
            self.host_lens[b] = int(starts[b])
            mask[b] = True
            budgets[b] = req.max_new_tokens
            self._bt[b] = alloc.block_table(
                [req.req_id], max_pages=g.pages_per_seq)[0]
        m = jnp.asarray(mask)
        zero = jnp.zeros((), jnp.int32)
        # rows with a prefix hit start mid-prompt: their write cursor and
        # RoPE positions begin at the first uncached token
        self.positions = jnp.where(m, jnp.asarray(starts), self.positions)
        self.counts = jnp.where(m, zero, self.counts)
        self.budgets = jnp.asarray(budgets.astype(np.int32))
        self.finished = jnp.where(m, jnp.zeros((), bool), self.finished)
        self._upload_bt()
        if self._hist is not None:
            # seed the drafter (ISSUE 9): the full prompt into the
            # history table, the prompt tail into the device recent ring
            # — the context the first verify step's drafts match against
            nmax = self.spec.ngram_max
            rec_np = np.full((self.B, nmax), int(_sp.CTX_PAD), np.int32)
            for b, req in admitted:
                self._hist.reset_row(b, req.prompt)
                rec_np[b] = _sp.recent_window(req.prompt, nmax)
            self._recent = jnp.where(m[:, None], jnp.asarray(rec_np),
                                     self._recent)
        return len(admitted)
