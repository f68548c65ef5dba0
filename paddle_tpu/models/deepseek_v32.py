"""DeepSeek-V3.2 decoders (``model_type: deepseek_v32``): latent attention
with a query latent, a learned index that chooses the keys each query token
reads (sparse attention), leading dense layers before the expert layers,
group-limited bias-selected sigmoid experts with one shared expert.

One layer, ``x`` the residual stream (pre-norm, sequential residuals, RMS
norms), ``y = RMSNorm(x)``, for a token at position ``t`` and a cached token
``s <= t``:

- the query latent ``c_q = RMSNorm(W_dq y)`` (``q_lora_rank``); ``q_h =
  W_uq,h c_q`` = ``[q_nope (qk_nope_head_dim) | q_rope (qk_rope_head_dim)]``
  for each of ``num_attention_heads`` heads, rotary on ``q_rope``;
- ``[c | k_r] = W_dkv y``; ``c = RMSNorm(c)`` (``kv_lora_rank``); rotary on
  the ONE ``k_r`` every head shares (interleaved pairs, ``yarn``
  frequencies: ``decoder_spec.RopeYarn``);
- **the index**: ``q_i,j = W_iq,j c_q`` (``index_n_heads`` of
  ``index_head_dim``, rotary on the first ``qk_rope_head_dim``); ``k_i =
  LayerNorm(W_ik y)`` (weight and bias; ONE key a token a layer, rotary on
  its first ``qk_rope_head_dim``, cached beside ``[c | k_r]``); ``w = W_iw
  y``; ``I(t, s) = sum_j w_t,j ReLU(q_i,t,j . k_i,s)`` in float32; ``S_t``
  = the ``min(t + 1, index_topk)`` positions ``s <= t`` with the largest
  ``I(t, s)`` (a tie at the edge goes to the lower position);
- head ``h``: ``k_h = [W_uk,h c | k_r]``, ``v_h = W_uv,h c``; the softmax
  of ``q_h . k_h`` times ``(nope + rope)^-0.5 x mscale^2`` runs over ``s in
  S_t`` ALONE; ``a = W_o [o_1 .. o_H]``; ``x <- x + a``;
- ``y = RMSNorm(x)``; the first ``first_k_dense_replace`` layers: a dense
  SiLU-gated MLP of ``intermediate_size``.  The others: ``s = sigmoid(W_r
  y)`` over ``n_routed_experts`` in float32; the ``n_group`` groups are
  ranked by the sum of their two largest ``s + b``, the best ``topk_group``
  kept, inside them the ``num_experts_per_tok`` largest ``s + b`` chosen
  (the bias selects and is not in the gate); ``g_e = routed_scaling_factor
  x s_e / sum of the chosen s``; ``f = sum g_e expert_e(y)``
  (``moe_intermediate_size``) + the one shared expert; ``x <- x + f``;
- after the last layer an RMSNorm and an untied head.

The checkpoint's multi-token-prediction module (``num_nextn_predict_layers``)
is not built: plain decoding does not run it.

The serving engine computes the ABSORBED form of the attention over a latent
pool of three planes (``inference/generation.py``, ``kernels/
latent_index.py``); ``forward`` here is the expanded one above.  The
parameters are laid out as ``sarvam_mla``'s: the leading dense layers one
dict each, the expert layers one ``[layers, ...]`` stack a leaf with the
expert banks one array a layer; a chip that holds a share of the experts
(``experts_held`` of ``n_routed_experts``, from ``expert_offset``) keeps the
router at its published width.
"""

from __future__ import annotations

from dataclasses import dataclass, field, fields
from typing import Optional

import jax.numpy as jnp

from ..nn.layer import Layer, LayerList
from ..ops._prim import apply_op
from .cohere2_moe import _adopt, _ones
from .decoder_spec import (DecoderSpec, LatentAttn, LatentIndex, LayerKind,
                           MoeSpec, RopeYarn)
from .llama import _model_init, _scaled_init
from .sarvam_mla import _Layers, _expanded_attention, _run_stack


def _zeros(shape, dtype):
    return jnp.zeros(shape, dtype)


@dataclass
class DeepseekV32Config:
    """The source's own keys (``config.json`` of a ``deepseek_v32`` model),
    with the sizes of DeepSeek-V3.2 as defaults; ``vocab_size``,
    ``num_hidden_layers``, ``first_k_dense_replace`` and ``experts_held``
    are what is held and run here."""
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432         # the leading dense layers' MLP
    moe_intermediate_size: int = 2048      # one expert's width
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=lambda: {
        "type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096})
    n_routed_experts: int = 256            # the router's width
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    topk_method: str = "noaux_tc"
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 163840
    dtype: str = "bfloat16"
    # this chip's share of each layer's experts (not keys of the source)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    moe_dispatch: str = "grouped"
    moe_block_m: int = 128                 # many narrow experts: PR 27

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        for key, want, why in (
                ("topk_method", "noaux_tc",
                 "the choice computed is the group-limited one with a "
                 "selection bias"),
                ("scoring_func", "sigmoid",
                 "the router's scores are sigmoids"),
                ("n_shared_experts", 1,
                 "one shared expert is added ungated"),
                ("attention_bias", False,
                 "the attention's projections have no bias"),
                ("norm_topk_prob", True,
                 "the chosen scores are divided by their sum"),
                ("hidden_act", "silu", "the MLPs are SiLU-gated"),
                ("tie_word_embeddings", False, "the head is untied")):
            if getattr(self, key) != want:
                raise ValueError(
                    f"deepseek_v32: {key}={getattr(self, key)!r} is not "
                    f"computed (only {want!r}: {why})")
        if (self.rope_scaling or {}).get("type") != "yarn":
            raise ValueError(
                f"deepseek_v32: rope_scaling type "
                f"{(self.rope_scaling or {}).get('type')!r} is not computed "
                "(only 'yarn')")
        if self.index_head_dim % 128:
            raise ValueError(
                f"deepseek_v32: index_head_dim={self.index_head_dim} is not "
                "computed (only a multiple of 128: an index key fills whole "
                "lanes of its plane of the pool)")
        if not 0 <= self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError("deepseek_v32: first_k_dense_replace must "
                             "leave at least one expert layer")

    @classmethod
    def from_source(cls, source: dict, num_experts: Optional[int] = None,
                    **over) -> "DeepseekV32Config":
        """From the model's published ``config.json`` keys, under their own
        names (others are ignored: they say nothing this file computes),
        ``over`` on top; ``num_experts``: the router's width where
        ``source`` states the experts HELD under ``n_routed_experts``."""
        known = {f.name for f in fields(cls)}
        kw = {k: v for k, v in source.items() if k in known and v is not None}
        if "torch_dtype" in source:
            kw["dtype"] = source["torch_dtype"]
        if num_experts is not None:
            kw["n_routed_experts"] = num_experts
        kw.update(over)
        return cls(**kw)

    @staticmethod
    def tiny(**kw) -> "DeepseekV32Config":
        """Test size: one dense layer and two expert layers; the rotary
        part keeps its 64 numbers, an index key its 128; sizes at which a
        context over 32 tokens makes the choice strict and a group of
        experts is dropped."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_hidden_layers=3,
                    first_k_dense_replace=1, num_attention_heads=4,
                    q_lora_rank=32, kv_lora_rank=128, index_n_heads=4,
                    index_head_dim=128, index_topk=32, n_routed_experts=8,
                    num_experts_per_tok=2, n_group=4, topk_group=2,
                    max_position_embeddings=256, dtype="float32",
                    moe_block_m=8, rope_scaling={
                        "type": "yarn", "factor": 4, "beta_fast": 32,
                        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 64})
        base.update(kw)
        return DeepseekV32Config(**base)

    @staticmethod
    def deepseek_v32_ep16(index: int = 0, **kw) -> "DeepseekV32Config":
        """Chip ``index`` of the 16 that share each layer of a pipeline
        stage of DeepSeek-V3.2 (``chipbench/configs/deepseek-v3.2-ep16.
        json``): 16 of the 256 routed experts, an eighth of the vocabulary,
        one leading dense layer and four expert layers."""
        base = dict(num_hidden_layers=5, first_k_dense_replace=1,
                    vocab_size=16160, experts_held=16,
                    expert_offset=16 * index)
        base.update(kw)
        return DeepseekV32Config(**base)

    # ---- what the engine reads ----
    def latent(self) -> LatentAttn:
        return LatentAttn(rank=self.kv_lora_rank, nope=self.qk_nope_head_dim,
                          rope=self.qk_rope_head_dim, value=self.v_head_dim,
                          q_rank=self.q_lora_rank)

    def index(self) -> LatentIndex:
        return LatentIndex(heads=self.index_n_heads, dim=self.index_head_dim,
                           rope=self.qk_rope_head_dim, top_k=self.index_topk)

    def rope_yarn(self) -> RopeYarn:
        rs = self.rope_scaling
        return RopeYarn(
            factor=float(rs["factor"]),
            original=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs.get("beta_fast", 32)),
            beta_slow=float(rs.get("beta_slow", 1)),
            mscale=float(rs.get("mscale", 1)),
            mscale_all_dim=float(rs.get("mscale_all_dim", 0)))

    def moe_spec(self) -> MoeSpec:
        return MoeSpec(
            num_experts=self.n_routed_experts, top_k=self.num_experts_per_tok,
            score="sigmoid", held=self.experts_held,
            offset=self.expert_offset, shared=self.n_shared_experts,
            dispatch="grouped" if self.moe_dispatch == "grouped" else "dense",
            block_m=self.moe_block_m, select_bias=True,
            gate_scale=float(self.routed_scaling_factor),
            groups=self.n_group, groups_kept=self.topk_group)


def layer_leaves(c: DeepseekV32Config, dense: bool) -> list:
    """``[(name, per-layer shape, initializer, dtype)]`` of one layer: the
    attention and the index every layer has, then a dense MLP or the expert
    mixture."""
    H, heads = c.hidden_size, c.num_attention_heads
    rank, qr, dt = c.kv_lora_rank, c.q_lora_rank, c.dtype
    qw = heads * (c.qk_nope_head_dim + c.qk_rope_head_dim)
    vw = heads * c.v_head_dim
    ih, idim = c.index_n_heads, c.index_head_dim
    out = [
        ("self_attn.q_a_proj.weight", (H, qr), _scaled_init(H), dt),
        ("self_attn.q_a_layernorm.weight", (qr,), _ones, dt),
        ("self_attn.q_b_proj.weight", (qr, qw), _scaled_init(qr), dt),
        ("self_attn.kv_a_proj_with_mqa.weight",
         (H, rank + c.qk_rope_head_dim), _scaled_init(H), dt),
        ("self_attn.kv_a_layernorm.weight", (rank,), _ones, dt),
        # kv_b_proj's two halves, apart: the engine absorbs the first
        # into the query and applies the second after the call
        ("self_attn.k_up_proj.weight", (rank, heads * c.qk_nope_head_dim),
         _scaled_init(rank), dt),
        ("self_attn.v_up_proj.weight", (rank, vw), _scaled_init(rank), dt),
        ("self_attn.o_proj.weight", (vw, H), _scaled_init(vw), dt),
        ("self_attn.indexer.wq_b.weight", (qr, ih * idim), _scaled_init(qr),
         dt),
        ("self_attn.indexer.wk.weight", (H, idim), _scaled_init(H), dt),
        ("self_attn.indexer.k_norm.weight", (idim,), _ones, dt),
        ("self_attn.indexer.k_norm.bias", (idim,), _zeros, dt),
        ("self_attn.indexer.weights_proj.weight", (H, ih), _scaled_init(H),
         dt),
        ("input_layernorm.weight", (H,), _ones, dt),
        ("post_attention_layernorm.weight", (H,), _ones, dt),
    ]
    if dense:
        I = c.intermediate_size
        return out + [
            ("mlp.gate_proj.weight", (H, I), _scaled_init(H), dt),
            ("mlp.up_proj.weight", (H, I), _scaled_init(H), dt),
            ("mlp.down_proj.weight", (I, H), _scaled_init(I), dt)]
    I, held = c.moe_intermediate_size, c.experts_held
    return out + [
        ("mlp.gate.weight", (H, c.n_routed_experts), _scaled_init(H), dt),
        # float32 whatever the model's dtype: it is added to float32 scores
        ("mlp.gate.bias", (c.n_routed_experts,), _zeros, "float32"),
        ("mlp.experts_gate", (held, H, I), _scaled_init(H), dt),
        ("mlp.experts_up", (held, H, I), _scaled_init(H), dt),
        ("mlp.experts_down", (held, I, H), _scaled_init(I), dt),
        ("mlp.shared_gate_proj.weight", (H, I), _scaled_init(H), dt),
        ("mlp.shared_up_proj.weight", (H, I), _scaled_init(H), dt),
        ("mlp.shared_down_proj.weight", (I, H), _scaled_init(I), dt)]


class DeepseekV32ForCausalLM(Layer):
    """The model; ``ContinuousBatchingEngine(model, ...)`` takes it as it
    takes the other families.  ``params`` (the layout of
    ``serving_params()``): a caller's own arrays, adopted as the model's
    parameters instead of drawing random ones."""

    @_model_init("deepseek_v32")
    def __init__(self, config: DeepseekV32Config,
                 params: Optional[dict] = None):
        super().__init__(dtype=config.dtype)
        c = self.config = config

        def init(name, drawn):
            return drawn if params is None else _adopt(params, name, c.dtype)

        scaled = _scaled_init(c.hidden_size)
        self.embed_tokens = self.create_parameter(
            [c.vocab_size, c.hidden_size],
            default_initializer=init("embed", scaled))
        self.norm = self.create_parameter(
            [c.hidden_size], default_initializer=init("norm", _ones))
        self.lm_head = self.create_parameter(
            [c.hidden_size, c.vocab_size],
            default_initializer=init("head", scaled))
        k = c.first_k_dense_replace
        lead = [None] * k if params is None else params["leading"]
        if len(lead) != k:
            raise ValueError(f"params has {len(lead)} leading layers, "
                             f"first_k_dense_replace is {k}")
        self.leading = LayerList([
            _Layers(c, True, 1, g, layer_leaves(c, True)) for g in lead])
        self.experts = _Layers(
            c, False, c.num_hidden_layers - k,
            None if params is None else params["blocks"][0],
            layer_leaves(c, False))

    # ---- what the serving engine asks of a model (decoder_spec.py) ----
    def decoder_spec(self) -> DecoderSpec:
        c = self.config
        la, ix = c.latent(), c.index()
        k = c.first_k_dense_replace
        return DecoderSpec(
            pattern=(LayerKind(latent=la, index=ix),),
            periods=c.num_hidden_layers - k,
            leading=(LayerKind(latent=la, index=ix, dense_ffn=True),) * k,
            num_heads=c.num_attention_heads, num_kv_heads=1,
            head_dim=c.qk_nope_head_dim + c.qk_rope_head_dim, norm="rms",
            norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta,
            rope_yarn=c.rope_yarn(), moe=c.moe_spec())

    def serving_params(self) -> dict:
        """The parameters themselves (no copy)."""
        return {"embed": self.embed_tokens._data, "norm": self.norm._data,
                "head": self.lm_head._data,
                "leading": tuple(l.arrays() for l in self.leading),
                "blocks": (self.experts.arrays(),)}

    # ---- the whole sequence at once (no cache): evaluation, tests ----
    def forward(self, input_ids):
        spec = self.decoder_spec()
        params = self.serving_params()
        return apply_op("deepseek_v32_forward",
                        lambda ids: _forward(spec, params, ids), (input_ids,))


def _forward(spec: DecoderSpec, params: dict, ids):
    """ids [b, s] -> float32 logits [b, s, V]: the EXPANDED attention (every
    head's own key and value made from ``c``) as a dense softmax masked to
    each query token's chosen set, the serving path's own expert mixture
    (``sarvam_mla._run_stack``)."""
    from ..inference.generation import _index_inputs, _rope_bt
    from ..kernels.latent_index import (_reference_latent_index_select,
                                        _weighted_relu)
    from ..kernels.rms_norm import rms_norm_fp32 as norm

    la, ix, H = spec.latent, spec.index, spec.num_heads
    b, s = ids.shape
    cos, sin = (jnp.broadcast_to(jnp.asarray(t)[None], (b, s, la.rope // 2))
                for t in spec.rope_tables(s))
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    keep = jnp.broadcast_to(jnp.minimum(jnp.arange(s) + 1, ix.top_k), (b, s))

    def attend(y, lp):
        c_q = norm(y @ lp["self_attn.q_a_proj.weight"],
                   lp["self_attn.q_a_layernorm.weight"], spec.norm_eps)
        q = (c_q @ lp["self_attn.q_b_proj.weight"]).reshape(
            b, s, H, la.nope + la.rope)
        ckr = y @ lp["self_attn.kv_a_proj_with_mqa.weight"]
        c = norm(ckr[..., :la.rank], lp["self_attn.kv_a_layernorm.weight"],
                 spec.norm_eps)
        k_r = _rope_bt(ckr[..., None, la.rank:], cos, sin)[..., 0, :]
        q_i, k_i, w_i = _index_inputs(y, c_q, lp, ix, spec.norm_eps, cos,
                                      sin)
        score = _weighted_relu(q_i, w_i, k_i)
        chosen = _reference_latent_index_select(
            jnp.where(seen, score, -jnp.inf), keep)
        return _expanded_attention(spec, lp, q, c, k_r, cos, sin,
                                   chosen[:, None])

    return _run_stack(spec, params, ids, attend)
