"""Llama-2/3 decoder family — the flagship model (BASELINE.md configs 2-3).

Reference behavior surface: PaddleNLP's LlamaForCausalLM built on the
framework's TP layers (python/paddle/distributed/fleet/layers/mpu/
mp_layers.py) and fused ops (python/paddle/incubate/nn/functional:
fused_rotary_position_embedding, swiglu, fused_rms_norm; flash attention
paddle/phi/kernels/gpu/flash_attn_kernel.cu:587).

TPU-first design decisions:
- bf16 params/compute by default (MXU native), fp32 RMSNorm accumulation;
- attention via the Pallas flash-attention kernel ([b, s, h, d] layout);
- GQA by grouped KV heads (repeated at attention time, XLA keeps it fused);
- sharding is a *plan*, not wired into layers: `llama_shard_plan` lays
  weights/activations over a hybrid mesh (mp = Megatron TP, dp = batch,
  sep = sequence) and GSPMD emits the Megatron collective schedule —
  the model code itself stays single-device jax.
- `jax.checkpoint` rematerialisation per decoder layer (the reference's
  recompute pass) is applied by the trainer via `recompute=True` configs.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..kernels.flash_attention import flash_attention
from ..nn import functional as F
from ..nn.layer import Layer, LayerList
from ..observability import startup as _startup
from ..ops._prim import apply_op


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # MoE (Mixtral-style: every layer's MLP becomes a top-k expert mixture;
    # 0 experts = dense).  Reference surface: incubate MoELayer
    # (python/paddle/incubate/distributed/models/moe/moe_layer.py:263) and
    # BASELINE.md config 5.
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_weight: float = 0.01
    # "grouped" (the default): expert-sorted ragged GEMM Pallas kernels —
    # no capacity padding, no drops on one chip; on a dp x ep x mp mesh it
    # runs the shard_map formulation (replicated router + ragged local
    # GEMM + one psum, capacity-bounded per shard).  "gather": int32
    # scatter + row gather (global capacity) and "einsum": GShard/t5x
    # one-hot matmul dispatch (per-group capacity) are kept as reference
    # oracles for parity tests (tests/test_moe_dispatch_parity.py).
    moe_dispatch: str = "grouped"
    moe_groups: int = 0          # einsum only: token groups (0 -> batch dim)
    moe_block_m: int = 512       # grouped only: row-tile (group alignment)
    # parallel knobs (consumed by llama_shard_plan / trainer)
    tensor_parallel: bool = False
    sequence_parallel: bool = False
    recompute: bool = False

    def __post_init__(self):
        if self.num_key_value_heads is None:
            self.num_key_value_heads = self.num_attention_heads
        if self.moe_dispatch not in ("gather", "einsum", "grouped"):
            raise ValueError(
                f"moe_dispatch must be 'gather', 'einsum' or 'grouped', "
                f"got {self.moe_dispatch!r}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, max_position_embeddings=128,
                    dtype="float32")
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**{**dict(hidden_size=4096, intermediate_size=11008,
                                     num_hidden_layers=32, num_attention_heads=32), **kw})

    @staticmethod
    def llama2_13b(**kw) -> "LlamaConfig":
        return LlamaConfig(**{**dict(hidden_size=5120, intermediate_size=13824,
                                     num_hidden_layers=40, num_attention_heads=40), **kw})

    @staticmethod
    def mixtral_tiny(**kw) -> "LlamaConfig":
        """Mixtral-shaped MoE test config (BASELINE.md config 5 family)."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, max_position_embeddings=128,
                    moe_num_experts=4, moe_top_k=2, dtype="float32",
                    # tiny token counts: a 512-row tile would pad the
                    # grouped dispatch ~10x; 16 keeps M within ~1.3x of
                    # the routed entries (TPU bench configs keep 512)
                    moe_block_m=16)
        base.update(kw)
        return LlamaConfig(**base)

    def _per_layer_params(self) -> Tuple[int, int]:
        """(dense per-layer params, expert-bank per-layer params)."""
        h, i = self.hidden_size, self.intermediate_size
        kvh = self.num_key_value_heads * self.head_dim
        attn = h * h + 2 * h * kvh + h * h + 2 * h
        if self.moe_num_experts:
            gate = h * self.moe_num_experts
            return attn + gate, self.moe_num_experts * 3 * h * i
        return attn + 3 * h * i, 0

    def num_params(self) -> int:
        dense, experts = self._per_layer_params()
        emb = self.vocab_size * self.hidden_size * \
            (1 if self.tie_word_embeddings else 2)
        return self.num_hidden_layers * (dense + experts) + emb + \
            self.hidden_size

    def num_active_params(self) -> int:
        """Params touched per token (MoE: only top_k of E experts) — the
        N in the 6*N*T MFU formula for sparse models (BASELINE.md)."""
        if not self.moe_num_experts:
            return self.num_params()
        dense, experts = self._per_layer_params()
        active = experts * self.moe_top_k // self.moe_num_experts
        emb = self.vocab_size * self.hidden_size * \
            (1 if self.tie_word_embeddings else 2)
        return self.num_hidden_layers * (dense + active) + emb + \
            self.hidden_size


def _rope_cos_sin(seq_len: int, head_dim: int, theta: float, dtype):
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    t = np.arange(seq_len, dtype=np.float32)
    freqs = np.outer(t, inv_freq)                      # [s, d/2]
    return (jnp.asarray(np.cos(freqs), dtype=dtype),
            jnp.asarray(np.sin(freqs), dtype=dtype))


def apply_rotary_pos_emb(x, cos, sin):
    """Rotate pairs (x[..., ::2], x[..., 1::2]) — fused by XLA; the slot of
    the reference's fused_rotary_position_embedding.  x: [b, s, h, d]."""
    # cos/sin: [s, d/2] -> broadcast over batch and heads
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    # interleave back; rotate in fp32 (cos/sin tables), return input dtype
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


def swiglu(gate, up):
    """reference: python/paddle/incubate/nn/functional/swiglu.py."""
    return jax.nn.silu(gate) * up


# ---- segment/context parallelism (the reference's SEP axis) ----
#
# DeepSpeed-Ulysses expressed as GSPMD resharding: activations live
# seq-sharded over 'sep'; around attention q/k/v are re-constrained to
# HEAD-sharded (full sequence locally) and the output back to seq-sharded.
# GSPMD lowers each constraint switch to the all-to-all the reference's
# SegmentParallel groups perform explicitly (fleet/meta_parallel/
# segment_parallel.py:26 + topology 'sep' axis, SURVEY.md §5.7).
_SEP_MESH = None


class context_parallel:
    """Activate sep-axis attention resharding while tracing a model whose
    activations are sharded P(dp, 'sep', ...) on the sequence dim."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        global _SEP_MESH
        self._prev = _SEP_MESH
        _SEP_MESH = self.mesh
        return self

    def __exit__(self, *exc):
        global _SEP_MESH
        _SEP_MESH = self._prev
        return False


def _sep_constrain(x, spec_entries):
    """with_sharding_constraint against the active sep mesh (no-op when
    context parallelism is inactive)."""
    if _SEP_MESH is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_SEP_MESH, PartitionSpec(*spec_entries)))


class LlamaRMSNorm(Layer):
    """fp32-accumulating RMSNorm (fused_rms_norm slot)."""

    def __init__(self, hidden_size: int, eps: float, dtype):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(
            [hidden_size], default_initializer=lambda shape, dt: jnp.ones(shape, dt))
        self.eps = eps

    def forward(self, x):
        from ..kernels.rms_norm import rms_norm_fp32
        return apply_op("llama_rms_norm",
                        lambda v, w: rms_norm_fp32(v, w, self.eps),
                        (x, self.weight))


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        c = config
        self.config = c
        hd = c.head_dim
        init = _scaled_init(c.hidden_size)
        self.q_proj = _ParamLinear(c.hidden_size, c.num_attention_heads * hd, c.dtype, init)
        self.k_proj = _ParamLinear(c.hidden_size, c.num_key_value_heads * hd, c.dtype, init)
        self.v_proj = _ParamLinear(c.hidden_size, c.num_key_value_heads * hd, c.dtype, init)
        self.o_proj = _ParamLinear(c.num_attention_heads * hd, c.hidden_size, c.dtype, init)

    def forward(self, hidden, cos, sin):
        c = self.config
        # cos/sin are rope tables consumed inside raw-array prims
        cos = cos._data if isinstance(cos, Tensor) else cos
        sin = sin._data if isinstance(sin, Tensor) else sin
        b, s = hidden.shape[0], hidden.shape[1]
        q = self.q_proj(hidden).reshape([b, s, c.num_attention_heads, c.head_dim])
        k = self.k_proj(hidden).reshape([b, s, c.num_key_value_heads, c.head_dim])
        v = self.v_proj(hidden).reshape([b, s, c.num_key_value_heads, c.head_dim])

        def rope_prim(qa, ka):
            return (apply_rotary_pos_emb(qa, cos, sin),
                    apply_rotary_pos_emb(ka, cos, sin))

        q, k = apply_op("fused_rope", rope_prim, (q, k))
        if _SEP_MESH is not None:
            # Ulysses switch: seq-sharded -> head-sharded (GSPMD emits the
            # sep all-to-all); attention then sees the full sequence with
            # heads/sep per device
            def to_heads(qa, ka, va):
                return (_sep_constrain(qa, ("dp", None, "sep", None)),
                        _sep_constrain(ka, ("dp", None, "sep", None)),
                        _sep_constrain(va, ("dp", None, "sep", None)))

            q, k, v = apply_op("sep_all2all_qkv", to_heads, (q, k, v))
        # GQA is native in the kernel: grouped K/V go in un-repeated, so
        # K/V residuals and backward bandwidth stay heads/kv_heads smaller
        # (PretrainStep names its multi-device mesh on the template layer)
        out = flash_attention(q, k, v, causal=True,
                              mesh=getattr(self, "_attn_mesh", None))
        if _SEP_MESH is not None:
            out = apply_op(
                "sep_all2all_out",
                lambda oa: _sep_constrain(oa, ("dp", "sep", None, None)),
                (out,))
        out = out.reshape([b, s, c.num_attention_heads * c.head_dim])
        return self.o_proj(out)


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        c = config
        init = _scaled_init(c.hidden_size)
        self.gate_proj = _ParamLinear(c.hidden_size, c.intermediate_size, c.dtype, init)
        self.up_proj = _ParamLinear(c.hidden_size, c.intermediate_size, c.dtype, init)
        self.down_proj = _ParamLinear(c.intermediate_size, c.hidden_size, c.dtype,
                                      _scaled_init(c.intermediate_size))

    def forward(self, x):
        gate = self.gate_proj(x)
        up = self.up_proj(x)
        act = apply_op("swiglu", lambda g, u: swiglu(g, u), (gate, up))
        return self.down_proj(act)


def moe_mlp_forward(x, gate_w, w_gate, w_up, w_down, *, top_k,
                    capacity_factor, eval_capacity=False):
    """Capacity-bounded top-k expert mixture over a SwiGLU FFN — the
    compiled-step MoE math (reference mechanism surface: MoELayer +
    global_scatter/gather capacity alltoall, moe_layer.py:263 /
    moe_utils.py:20,:153; gating per GShard/Mixtral).

    TPU-first formulation: scatter-add dispatch into a static
    ``[E, capacity, H]`` buffer and gather-combine — static shapes, no
    [N, E, C] one-hot dispatch tensor (O(N*E*C) memory), no host control
    flow.  Under GSPMD with the expert dim sharded over the 'ep' mesh axis
    XLA lowers the scatter/gather into the EP collectives.

    x: [B, S, H]; gate_w: [H, E]; w_gate/w_up: [E, H, I]; w_down: [E, I, H].
    Returns (y [B, S, H], aux_loss scalar fp32, stats fp32 [2]) where
    stats = [kept_frac (routed tokens that fit capacity), imbalance
    (busiest expert's first-choice token share x E; 1.0 = uniform)] —
    the expert-load-balance evidence BASELINE config 5 asks to report.
    """
    B, S, H = x.shape
    E = gate_w.shape[-1]
    N = B * S
    k = top_k
    xf = x.reshape(N, H)

    # GShard top-k routing + load-balancing aux (shared router)
    topv, topi, aux, ce = _route_topk(xf, gate_w, k)

    cap = max(1, int(N * k * capacity_factor / E))
    # Dispatch = scatter the scalar TOKEN id per slot, then gather rows from
    # xf: slots are unique by construction (cumsum position within expert),
    # so a row scatter-add is equivalent — but TPU lowers row scatters to
    # serialized per-row updates, while an int32 scatter + row gather stays
    # vectorized (1 word/slot scattered, [N+1, H] touched instead of
    # 2*[kN, H]).  The k-major slot/inv maps (and their drop sentinels)
    # are single-sourced in kernels.grouped_matmul.capacity_dispatch_plan.
    from ..kernels.grouped_matmul import (capacity_dispatch_plan,
                                          take_sentinel_rows)
    inv, slot, gate_keep, keep = capacity_dispatch_plan(topi, topv, E, cap)
    expert_in = take_sentinel_rows(xf, inv[:-1]).reshape(E, cap, H)

    h1 = jax.nn.silu(jnp.einsum("ech,ehi->eci", expert_in, w_gate)) * \
        jnp.einsum("ech,ehi->eci", expert_in, w_up)
    out_e = jnp.einsum("eci,eih->ech", h1, w_down).reshape(E * cap, H)

    gathered = take_sentinel_rows(out_e, slot)
    yf = gathered * gate_keep[:, None].astype(x.dtype)
    y = yf.reshape(k, N, H).sum(axis=0).reshape(B, S, H)
    stats = jnp.stack([keep.mean().astype(jnp.float32),
                       ce.max() * jnp.float32(E)])
    return y, aux, stats


def moe_mlp_forward_einsum(x, gate_w, w_gate, w_up, w_down, *, top_k,
                           capacity_factor, groups=0):
    """GShard/t5x-style one-hot einsum MoE dispatch (reference mechanism
    surface as moe_mlp_forward; public TPU pattern: gshard/t5x MoE layers).

    Dispatch AND combine are einsum contractions against a [G, n, E, cap]
    one-hot combine tensor, so both directions (and both AD transposes) are
    MXU matmuls — no scatter anywhere, at the cost of the dispatch
    contraction's extra FLOPs (~2*n*E*cap*H per group vs 3 FFN matmuls).
    Capacity is per token-group of n = N/G (GShard semantics; G=1
    reproduces the global-capacity routing of moe_mlp_forward exactly).

    Shapes as moe_mlp_forward; returns (y, aux_loss, stats[2]).
    """
    B, S, H = x.shape
    E = gate_w.shape[-1]
    N = B * S
    k = top_k
    G = groups or B
    if N % G:
        raise ValueError(f"moe_groups ({G}) must divide tokens ({N})")
    n = N // G
    xg = x.reshape(G, n, H)

    logits = (xg.astype(jnp.float32) @ gate_w.astype(jnp.float32))  # [G,n,E]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                  # [G, n, k]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # GShard aux on the flat batch (same formula as moe_mlp_forward)
    pf = probs.reshape(N, E)
    me = pf.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[topi[..., 0].reshape(N)].add(1.0) / N
    aux = E * jnp.sum(me * ce)

    cap = max(1, int(n * k * capacity_factor / E))
    # k-major priority within each group: first choices claim slots first
    idx = jnp.swapaxes(topi, 1, 2).reshape(G, k * n)      # [G, kn]
    gate_v = jnp.swapaxes(topv, 1, 2).reshape(G, k * n).astype(jnp.float32)
    oh = jax.nn.one_hot(idx, E, dtype=jnp.float32)        # [G, kn, E]
    pos = jnp.sum(jnp.cumsum(oh, axis=1) * oh - oh, axis=-1).astype(jnp.int32)
    keep = pos < cap

    # combine[g, n, e, c]: gate weight where token n routes to (e, c);
    # built per choice (k outer products of [G,n,E] x [G,n,cap]) to keep
    # the transient at [G, n, E, cap] rather than k times that
    combine = jnp.zeros((G, n, E, cap), jnp.float32)
    for kk in range(k):
        sl = slice(kk * n, (kk + 1) * n)
        w = (gate_v[:, sl] * keep[:, sl])[..., None, None]    # [G, n, 1, 1]
        combine = combine + w * (oh[:, sl, :, None] *
                                 jax.nn.one_hot(pos[:, sl], cap,
                                                dtype=jnp.float32)[:, :, None])
    dispatch = (combine > 0).astype(x.dtype)              # [G, n, E, cap]

    expert_in = jnp.einsum("gnec,gnh->egch", dispatch, xg)    # [E,G,cap,H]
    ei = expert_in.reshape(E, G * cap, H)
    h1 = jax.nn.silu(jnp.einsum("exh,ehi->exi", ei, w_gate)) * \
        jnp.einsum("exh,ehi->exi", ei, w_up)
    out_e = jnp.einsum("exi,eih->exh", h1, w_down)            # [E,G*cap,H]
    out_e = out_e.reshape(E, G, cap, H)
    y = jnp.einsum("gnec,egch->gnh", combine.astype(x.dtype), out_e)

    kept_frac = (keep.sum() / jnp.float32(k * N)).astype(jnp.float32)
    stats = jnp.stack([kept_frac, ce.max() * jnp.float32(E)])
    return y.reshape(B, S, H), aux, stats


def _route_topk(xf, gate_w, k, score="softmax", bias=None, scale=1.0,
                groups=None, groups_kept=None):
    """Shared top-k router: returns (gate weights [N, k], expert ids
    [N, k], GShard aux loss, first-choice load ce [E]).  Scores are the
    ``score`` function ("softmax" or "sigmoid") of the float32 logits; the
    gates are the k largest, divided by their sum.  With ``bias`` (float32
    ``[E]``) the k chosen are those with the largest ``score + bias``: the
    bias selects and is not in the gate.  ``scale`` multiplies the
    normalised gates.  ``groups`` (with ``groups_kept``): the choice is
    group-limited: the experts lie in ``groups`` equal runs, a group is
    ranked by the sum of its two largest ``score + bias``, and the k are
    chosen inside the best ``groups_kept`` groups alone."""
    N = xf.shape[0]
    E = gate_w.shape[-1]
    logits = xf.astype(jnp.float32) @ gate_w.astype(jnp.float32)  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1) if score == "softmax" \
        else jax.nn.sigmoid(logits)
    if groups is not None:
        by = probs if bias is None else probs + bias.astype(jnp.float32)
        rank = jax.lax.top_k(by.reshape(N, groups, E // groups),
                             min(2, E // groups))[0].sum(-1)   # [N, groups]
        _, kept = jax.lax.top_k(rank, groups_kept)
        keep = (kept[:, :, None] == jnp.arange(groups)).any(axis=1)
        by = jnp.where(jnp.repeat(keep, E // groups, axis=1), by, -jnp.inf)
        _, topi = jax.lax.top_k(by, k)
        topv = jnp.take_along_axis(probs, topi, axis=-1)
    elif bias is None:
        topv, topi = jax.lax.top_k(probs, k)
    else:
        _, topi = jax.lax.top_k(probs + bias.astype(jnp.float32), k)
        topv = jnp.take_along_axis(probs, topi, axis=-1)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        topv = topv * jnp.float32(scale)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[topi[:, 0]].add(1.0) / N
    aux = E * jnp.sum(me * ce)
    return topv, topi, aux, ce


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _grouped_ffn(xf, w_gate, w_up, w_down, gates, inv_flat, pos,
                 tile_groups, E, k, bm):
    """Grouped-GEMM SwiGLU expert mixture over pre-sorted tokens.

    xf [N, H]; w_gate/w_up [E, H, I]; w_down [E, I, H]; gates [N, k] fp32
    combine weights; inv_flat/pos/tile_groups from
    ``sorted_dispatch_plan``.  Dispatch and combine are GATHERS and the
    hand-written VJP keeps them gathers in reverse (the AD transpose of a
    gather is a scatter-add, which TPU serializes row-by-row — the
    whole point of carrying both maps is never to emit one).

    ``pos`` entries >= M (the padded-buffer row count) are a DROPPED-
    entry sentinel: combine and the dx gather go through a zero-extended
    buffer, so dropped (token, choice) entries contribute exactly zero in
    both directions (the capacity-overflow semantics of the sharded
    path; single-device plans never emit the sentinel).
    """
    y, _ = _grouped_ffn_fwd(xf, w_gate, w_up, w_down, gates, inv_flat,
                            pos, tile_groups, E, k, bm)
    return y


def _padded_rows(buf, tok_of, scale=None):
    """``buf``'s rows laid out as the dispatch plan's padded buffer
    (``[M, ...]``: row ``p`` is ``buf[tok_of[p]]``), each times its
    ``scale[p]`` where one is given — the operand ``gmm``/``tgmm`` take."""
    out = jnp.take(buf, tok_of, axis=0)
    if scale is not None:
        out = out * scale[:, None].astype(out.dtype)
    return out


# a gated expert's activation by its name in ``MoeSpec.activation``
_GATE_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _grouped_ffn_fwd(xf, w_gate, w_up, w_down, gates, inv_flat, pos,
                     tile_groups, E, k, bm, live_tiles=None,
                     dead_in_table=False, activation="silu"):
    """The forward pass and its residuals.  Serving, forward only: ``gmm``
    is told which row tiles hold no expert's rows and are not multiplied,
    by ``live_tiles`` (a share of the experts) or by the table itself
    (``dead_in_table``: a plan that dropped the rows without a token);
    their ``pos`` entries are the sentinel, so they read zero; and the
    gate's ``activation`` is what the model states (``MoeSpec.activation``:
    "relu" for ReGLU experts).  The VJP below differentiates "silu" alone:
    training reaches this function through ``_grouped_ffn``, which hands it
    no other."""
    from ..kernels.grouped_matmul import (gmm, take_sentinel_rows,
                                          validate_tile_flags)

    N, H = xf.shape
    # sweep flags must tile H AND I: the backward swaps their roles
    validate_tile_flags(H, w_gate.shape[2])
    xz = jnp.concatenate([xf, jnp.zeros((1, H), xf.dtype)], axis=0)
    tok_of = jnp.where(inv_flat < N * k, inv_flat // k, N)
    dead = dict(live_tiles=live_tiles, dead_in_table=dead_in_table)
    h_g = gmm(_padded_rows(xz, tok_of), w_gate, tile_groups, bm=bm, **dead)
    h_u = gmm(_padded_rows(xz, tok_of), w_up, tile_groups, bm=bm, **dead)
    # over all M rows: a dead tile's rows of ``h_g`` / ``h_u`` were never
    # written and may hold anything, NaN included.  Nothing reads what comes
    # of them: the down projection skips the same tiles, and ``pos`` points
    # at live rows or the sentinel alone (the plan's scatter; held by
    # tests/test_moe_dispatch_parity.py::test_rows_without_a_token_...)
    a = _GATE_ACTIVATIONS[activation](h_g) * h_u
    o = gmm(a, w_down, tile_groups, bm=bm, **dead)        # [M, H]
    # combine gather: sentinel pos >= M (dropped entries) reads zero
    o_pos = take_sentinel_rows(o, pos).reshape(N, k, H)
    y = (o_pos * gates[..., None].astype(o.dtype)).sum(axis=1)
    # h_g/h_u/o ride as residuals: under the training configs' remat the
    # whole block is recomputed anyway (storing is free there), and
    # without remat this saves re-running 3 of the 9 grouped GEMMs
    return y, (xf, w_gate, w_up, w_down, gates, inv_flat, pos, tile_groups,
               h_g, h_u, o)


def _grouped_ffn_bwd(E, k, bm, res, dy):
    from ..kernels.grouped_matmul import gmm, take_sentinel_rows, tgmm

    (xf, w_gate, w_up, w_down, gates, inv_flat, pos, tile_groups,
     h_g, h_u, o) = res
    N, H = xf.shape
    xz = jnp.concatenate([xf, jnp.zeros((1, H), xf.dtype)], axis=0)
    tok_of = jnp.where(inv_flat < N * k, inv_flat // k, N)
    sg = jax.nn.silu(h_g)
    a = sg * h_u

    o_pos = take_sentinel_rows(o, pos).reshape(N, k, H)
    d_gates = (o_pos.astype(jnp.float32)
               * dy[:, None, :].astype(jnp.float32)).sum(-1)  # [N, k]

    # d(combine): do[p] = gate(p) * dy[token(p)] — both gathers
    gate_pad = take_sentinel_rows(
        gates.reshape(N * k).astype(dy.dtype), inv_flat)        # [M]
    dy_z = jnp.concatenate([dy, jnp.zeros((1, H), dy.dtype)], axis=0)

    da = gmm(_padded_rows(dy_z, tok_of, gate_pad), w_down, tile_groups,
             bm=bm, trans_rhs=True)                           # [M, I]
    sig = jax.nn.sigmoid(h_g.astype(jnp.float32)).astype(h_g.dtype)
    dsilu = sig + h_g * sig * (1 - sig)
    dh_g = da * h_u * dsilu
    dh_u = da * sg
    dw_d = tgmm(a, _padded_rows(dy_z, tok_of, gate_pad), tile_groups, E,
                bm=bm)
    dw_g = tgmm(_padded_rows(xz, tok_of), dh_g, tile_groups, E, bm=bm)
    dw_u = tgmm(_padded_rows(xz, tok_of), dh_u, tile_groups, E, bm=bm)
    dx_pad = gmm(dh_g, w_gate, tile_groups, bm=bm, trans_rhs=True) + \
        gmm(dh_u, w_up, tile_groups, bm=bm, trans_rhs=True)   # [M, H]
    # d(dispatch): token t accumulates its k buffer rows — a gather;
    # dropped entries read the sentinel zero row (exactly-zero gradient)
    dxf = take_sentinel_rows(dx_pad, pos).reshape(N, k, H).sum(axis=1)

    f0 = lambda t: np.zeros(t.shape, jax.dtypes.float0)
    return (dxf.astype(xf.dtype), dw_g.astype(w_gate.dtype),
            dw_u.astype(w_up.dtype), dw_d.astype(w_down.dtype),
            d_gates.astype(gates.dtype), f0(inv_flat), f0(pos),
            f0(tile_groups))


_grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


def moe_mlp_forward_grouped(x, gate_w, w_gate, w_up, w_down, *, top_k,
                            block_m=512):
    """Grouped-GEMM (megablocks-style) MoE — the fast single-chip path
    (reference: the fused/cutlass grouped MoE GEMMs under
    paddle/phi/kernels/fusion/ + incubate fused_moe).

    Tokens are sorted by expert and each expert runs ONE ragged GEMM over
    exactly its own tokens (``kernels.grouped_matmul``): no capacity
    bound, no dropped tokens, <= E*block_m rows of tile-alignment padding
    instead of the ~capacity_factor x N*k padded rows the capacity
    formulations compute.  Shapes/returns as ``moe_mlp_forward``
    (kept_frac is 1.0 by construction — nothing drops).
    """
    B, S, H = x.shape
    E = gate_w.shape[-1]
    N = B * S
    k = top_k
    xf = x.reshape(N, H)

    topv, topi, aux, ce = _route_topk(xf, gate_w, k)

    from ..kernels.grouped_matmul import sorted_dispatch_plan
    inv_flat, pos, tile_groups = sorted_dispatch_plan(
        topi.reshape(N * k), E, block_m)
    y = _grouped_ffn(xf, w_gate, w_up, w_down, topv, inv_flat, pos,
                     tile_groups, E, k, block_m)
    stats = jnp.stack([jnp.float32(1.0), ce.max() * jnp.float32(E)])
    return y.reshape(B, S, H), aux, stats


def moe_mlp_forward_grouped_sharded(x, gate_w, w_gate, w_up, w_down, *,
                                    mesh, top_k, block_m=512,
                                    capacity_factor=1.5,
                                    axes=("dp", "ep", "mp")):
    """Grouped-GEMM MoE under an explicit dp x ep x mp mesh (shard_map).

    Key structural fact: activations are REPLICATED over 'ep' (they shard
    over dp only), so expert parallelism needs no all-to-all transport —
    every ep shard recomputes the (cheap) router identically, packs only
    the (token, choice) pairs owned by ITS expert bank through the ragged
    grouped GEMM, and one ``psum`` over (ep, mp) combines the partial
    outputs (mp is partial from the down-projection's sharded
    contraction).  The reference reaches the same routing with
    global_scatter/global_gather alltoalls (moe_layer.py:263); on a TPU
    mesh the replicated-activation form trades those two collectives for
    one psum.

    Per-shard compute is bounded by ``capacity_factor``: the packed
    buffer holds ~ k*N*cf/ep rows, overflow drops exactly like the
    capacity formulations (kept_frac in stats reports it).  Weight specs:
    w_gate/w_up P(ep, None, mp), w_down P(ep, mp, None), gate P().
    """
    from jax.sharding import PartitionSpec as P

    from ..kernels.grouped_matmul import sorted_dispatch_plan

    dp_axis, ep_axis, mp_axis = axes
    B, S, H = x.shape
    E = gate_w.shape[-1]
    ep = mesh.shape[ep_axis]
    E_loc = E // ep
    k = top_k
    N_loc = (B // mesh.shape[dp_axis]) * S
    bm = block_m
    # static per-shard row budget (+ per-expert alignment slack)
    m_cap = -(-int(N_loc * k * capacity_factor / ep) // bm) * bm \
        + E_loc * bm

    def local(xb, gw, wg, wu, wd):
        b, s, h = xb.shape
        n = b * s
        xf = xb.reshape(n, h)
        # the router runs on the PRISTINE values (vma tracked by jax's own
        # primitives, so gw's dp-psum transpose is automatic); the custom-
        # vjp FFN gets operands explicitly pcast to varying instead —
        # shard_map AD cannot see inside a custom vjp, and the cast's
        # transpose is what emits the replicated axes' psums on dx / dw
        topv, topi, aux_local, ce = _route_topk(xf, gw, k)
        aux = jax.lax.pmean(aux_local, dp_axis)

        my = jax.lax.axis_index(ep_axis)
        own = (topi // E_loc) == my                      # [n, k]
        # foreign choices route to a trailing discard group so they sort
        # LAST; owned groups pack first and survive the truncation
        local_e = jnp.where(own, topi % E_loc, E_loc).reshape(n * k)
        inv, pos, tg = sorted_dispatch_plan(local_e, E_loc + 1, bm)
        M_loc = min(m_cap, inv.shape[0])
        # discard rows (and owned overflow beyond M_loc) become zero rows
        # with zero gates: they contribute nothing in either direction
        own_flat = own.reshape(n * k)
        inv_t = jnp.where(
            (inv < n * k)
            & jnp.take(own_flat, jnp.minimum(inv, n * k - 1)),
            inv, n * k)[:M_loc]
        keep = (pos < M_loc) & own_flat
        gates = topv * keep.reshape(n, k)
        # dropped (token, choice) entries go to the M_loc SENTINEL row:
        # _grouped_ffn combines/backpropagates them through a zero-
        # extended buffer, so they get exactly-zero output AND gradient.
        # (Clamping to M_loc-1 instead — the pre-fix behavior — silently
        # accumulated a real kept row's dx into unrelated tokens under
        # capacity overflow.)
        pos_t = jnp.where(keep.reshape(n * k), pos, M_loc)
        tg_t = jnp.minimum(tg[:M_loc // bm], E_loc - 1)
        def vary(t, axes):
            return jax.lax.pcast(t, axes, to="varying")
        xf_v = vary(xf, (ep_axis, mp_axis))         # x replicated there
        wg_v, wu_v, wd_v = (vary(t, (dp_axis,))
                            for t in (wg, wu, wd))    # weights: over dp
        gates_v = vary(gates, (mp_axis,))           # ep-varying already
        y = _grouped_ffn(xf_v, wg_v, wu_v, wd_v, gates_v, inv_t, pos_t,
                         tg_t, E_loc, k, bm)
        y = jax.lax.psum(y, (ep_axis, mp_axis))
        kept = jax.lax.pmean(
            jax.lax.psum(keep.sum(), ep_axis) / jnp.float32(k * n),
            dp_axis)
        stats = jnp.stack([kept.astype(jnp.float32),
                           jax.lax.pmean(ce.max(), dp_axis)
                           * jnp.float32(E)])
        return y.reshape(b, s, h), aux, stats

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp_axis, None, None), P(),
                  P(ep_axis, None, mp_axis), P(ep_axis, None, mp_axis),
                  P(ep_axis, mp_axis, None)),
        out_specs=(P(dp_axis, None, None), P(), P()),
        check_vma=True,   # the casts above feed the checker and AD
    )(x, gate_w, w_gate, w_up, w_down)


class LlamaMoEMLP(Layer):
    """Mixtral-style MoE FFN block (drop-in for LlamaMLP when
    config.moe_num_experts > 0).  Expert banks are single stacked
    parameters [E, H, I] so the 'ep' mesh axis shards them directly."""

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        c = config
        self.config = c
        E, H, I = c.moe_num_experts, c.hidden_size, c.intermediate_size
        init_h = _scaled_init(H)
        init_i = _scaled_init(I)
        self.gate = _ParamLinear(H, E, c.dtype, init_h)
        self.experts_gate = self.create_parameter(
            [E, H, I], default_initializer=init_h)
        self.experts_up = self.create_parameter(
            [E, H, I], default_initializer=init_h)
        self.experts_down = self.create_parameter(
            [E, I, H], default_initializer=init_i)
        self._last_aux = None
        self._last_stats = None
        # set by PretrainStep when dispatch='grouped' runs on a >1-device
        # dp x ep x mp mesh: routes through the shard_map formulation
        self._grouped_mesh = None

    def forward(self, x):
        c = self.config

        def prim(xa, gw, wg, wu, wd):
            if c.moe_dispatch == "grouped" and self._grouped_mesh is not None:
                return moe_mlp_forward_grouped_sharded(
                    xa, gw, wg, wu, wd, mesh=self._grouped_mesh,
                    top_k=c.moe_top_k, block_m=c.moe_block_m,
                    capacity_factor=c.moe_capacity_factor)
            if c.moe_dispatch == "einsum":
                return moe_mlp_forward_einsum(
                    xa, gw, wg, wu, wd, top_k=c.moe_top_k,
                    capacity_factor=c.moe_capacity_factor,
                    groups=c.moe_groups)
            if c.moe_dispatch == "grouped":
                return moe_mlp_forward_grouped(
                    xa, gw, wg, wu, wd, top_k=c.moe_top_k,
                    block_m=c.moe_block_m)
            return moe_mlp_forward(
                xa, gw, wg, wu, wd, top_k=c.moe_top_k,
                capacity_factor=c.moe_capacity_factor)

        y, aux, stats = apply_op("moe_mlp", prim,
                                 (x, self.gate.weight, self.experts_gate,
                                  self.experts_up, self.experts_down))
        self._last_aux = aux
        self._last_stats = stats
        return y


class _ParamLinear(Layer):
    """Bias-free linear with explicit init (Llama uses no biases)."""

    def __init__(self, in_f, out_f, dtype, init):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter([in_f, out_f], default_initializer=init)

    def forward(self, x):
        return F.linear(x, self.weight, None)


def _scaled_init(fan_in):
    std = 1.0 / math.sqrt(fan_in)

    def init(shape, dtype):
        from ..core.random import next_key
        return (jax.random.normal(next_key(), shape, jnp.float32) * std).astype(dtype)

    return init


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMoEMLP(config) if config.moe_num_experts \
            else LlamaMLP(config)
        self.input_layernorm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps,
                                            config.dtype)
        self.post_attention_layernorm = LlamaRMSNorm(config.hidden_size,
                                                     config.rms_norm_eps, config.dtype)
        self._config = config

    def forward(self, hidden, cos, sin):
        with jax.named_scope("attention"):
            h = hidden + self.self_attn(self.input_layernorm(hidden),
                                        cos, sin)
        with jax.named_scope("moe" if self._config.moe_num_experts
                             else "mlp"):
            return h + self.mlp(self.post_attention_layernorm(h))


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = _Embedding(config.vocab_size, config.hidden_size,
                                       config.dtype)
        self.layers = LayerList([LlamaDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps, config.dtype)

    def forward(self, input_ids):
        c = self.config
        seq = input_ids.shape[1]
        cos, sin = _rope_cos_sin(seq, c.head_dim, c.rope_theta,
                                 jnp.float32)
        h = self.embed_tokens(input_ids)
        h = _seq_constrain(h, c)
        for layer in self.layers:
            if c.recompute:
                h = _remat_layer(layer, h, cos, sin)
            else:
                h = layer(h, cos, sin)
        return self.norm(h)


class _Embedding(Layer):
    def __init__(self, vocab, hidden, dtype):
        super().__init__(dtype=dtype)
        self.weight = self.create_parameter(
            [vocab, hidden], default_initializer=_scaled_init(hidden))

    def forward(self, ids):
        return F.embedding(ids, self.weight)


def _remat_layer(layer, h, cos, sin):
    """jax.checkpoint over one decoder layer (reference: recompute pass —
    python/paddle/distributed/passes/auto_parallel_recompute.py)."""
    params = [p for p in layer.parameters()]

    def pure(h_arr, *p_arrs):
        saved = [p._data for p in params]
        try:
            for p, a in zip(params, p_arrs):
                p._data = a
            out = layer(Tensor(h_arr), cos, sin)
            return out._data if isinstance(out, Tensor) else out
        finally:
            for p, a in zip(params, saved):
                p._data = a

    return apply_op("recompute_layer",
                    jax.checkpoint(pure),
                    tuple([h] + params))


def _seq_constrain(h, config: LlamaConfig):
    """Sequence-parallel activation layout: shard [b, s, h] as (dp, sep)
    when a hybrid mesh is active (reference: sequence_parallel_utils.py and
    the sep axis — SURVEY.md §5.7; on TPU one sharding constraint replaces
    both scatter/gather mechanisms)."""
    if not config.sequence_parallel:
        return h
    from ..distributed.fleet.topology import get_hcg
    hcg = get_hcg()
    if hcg is None:
        return h
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(hcg.global_mesh, P("dp", "sep", None))
    return apply_op("sp_constrain",
                    lambda v: jax.lax.with_sharding_constraint(v, sh), (h,))


def _model_init(family: str):
    """A served model's constructor as the start-up log's
    ``startup.model_init`` phase: the random initialisation that a
    launcher's checkpoint or a caller's own weights then replace."""
    return _startup.around("startup.model_init", lambda self: {
        "family": family, "layers": self.config.num_hidden_layers,
        "params": sum(math.prod(p.shape) for p in self.parameters())})


class LlamaForCausalLM(Layer):
    @_model_init("llama")
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = _ParamLinear(config.hidden_size, config.vocab_size,
                                        config.dtype, _scaled_init(config.hidden_size))

    def forward(self, input_ids, labels=None):
        h = self.llama(input_ids)
        if self.lm_head is None:
            logits = F.linear(h, self.llama.embed_tokens.weight.T, None)
        else:
            logits = self.lm_head(h)
        if labels is not None:
            loss = F.cross_entropy(
                logits.astype("float32").reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]))
            return logits, loss
        return logits

    # ---- what the serving engine asks of a model (decoder_spec.py) ----
    def decoder_spec(self):
        """The degenerate case of the engine's layer pattern: a period of
        one full-attention rotary layer, RMS norm, sequential residuals."""
        from .decoder_spec import DecoderSpec, LayerKind, MoeSpec
        c = self.config
        moe = MoeSpec(num_experts=c.moe_num_experts, top_k=c.moe_top_k,
                      dispatch="grouped" if c.moe_dispatch == "grouped"
                      else "dense", block_m=c.moe_block_m) \
            if c.moe_num_experts else None
        return DecoderSpec(
            pattern=(LayerKind(),), periods=c.num_hidden_layers,
            num_heads=c.num_attention_heads,
            num_kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
            norm="rms", norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta,
            moe=moe)

    def serving_params(self):
        """The engine's parameter tree: the layers stacked ``[L, ...]`` (a
        copy beside the per-layer parameters this model trains with), but
        for a MoE layer's expert banks, which go unstacked: a tuple of the
        layers' own arrays.  The grouped GEMMs are custom calls, and a layer
        sliced out of a stack would be copied for them a layer a step."""
        from ..utils import extract_params, stack_params
        from .decoder_spec import EXPERT_BANKS
        layers = [extract_params(l) for l in self.llama.layers]
        blocks = stack_params([{n: a for n, a in lp.items()
                                if n not in EXPERT_BANKS} for lp in layers])
        blocks.update({n: tuple(lp[n] for lp in layers)
                       for n in EXPERT_BANKS if n in layers[0]})
        head = (self.lm_head.weight._data if self.lm_head is not None
                else self.llama.embed_tokens.weight._data.T)
        return {"embed": self.llama.embed_tokens.weight._data, "head": head,
                "norm": self.llama.norm.weight._data, "blocks": (blocks,)}


# ---- sharding plan ----
def llama_shard_plan(model: LlamaForCausalLM, mesh=None):
    """Lay the model's weights over the hybrid mesh (Megatron TP schedule,
    reference mp_layers.py; SURVEY.md §7.1 'TP mpu layers' row):

      q/k/v_proj, gate/up_proj : Shard(out_dim)  over 'mp'  (column-parallel)
      o_proj, down_proj        : Shard(in_dim)   over 'mp'  (row-parallel)
      embed_tokens, lm_head    : Shard(vocab dim) over 'mp' (vocab-parallel)
      norms                    : replicated

    GSPMD then emits the canonical TP collectives.  Pipeline/dp placement
    comes from batch sharding + (optionally) PipelineLayer staging.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    if mesh is None:
        from ..distributed.fleet.topology import get_hcg
        hcg = get_hcg()
        if hcg is None:
            return model
        mesh = hcg.global_mesh
    if "mp" not in mesh.axis_names or mesh.shape["mp"] == 1:
        return model

    def put(p, spec):
        if not isinstance(p._data, jax.core.Tracer):
            p._data = jax.device_put(p._data, NamedSharding(mesh, spec))

    put(model.llama.embed_tokens.weight, P("mp", None))
    if model.lm_head is not None:
        put(model.lm_head.weight, P(None, "mp"))
    for layer in model.llama.layers:
        put(layer.self_attn.q_proj.weight, P(None, "mp"))
        put(layer.self_attn.k_proj.weight, P(None, "mp"))
        put(layer.self_attn.v_proj.weight, P(None, "mp"))
        put(layer.self_attn.o_proj.weight, P("mp", None))
        put(layer.mlp.gate_proj.weight, P(None, "mp"))
        put(layer.mlp.up_proj.weight, P(None, "mp"))
        put(layer.mlp.down_proj.weight, P("mp", None))
        put(layer.input_layernorm.weight, P(None))
        put(layer.post_attention_layernorm.weight, P(None))
    put(model.llama.norm.weight, P(None))
    return model
