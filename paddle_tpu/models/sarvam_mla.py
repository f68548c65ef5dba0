"""Sarvam-MLA decoders (``model_type: sarvam_mla``, e.g. Sarvam-105B):
latent attention over one compressed row a token, a leading dense layer
before the expert layers, bias-selected sigmoid experts with one shared
expert.

One layer, ``x`` the residual stream (pre-norm, sequential residuals, RMS
norms), for a token at position ``p``:

- ``y = RMSNorm(x)``;
- ``q = W_q y``: ``num_attention_heads`` heads of ``q_head_dim`` =
  ``[q_nope (qk_nope_head_dim) | q_rope (qk_rope_head_dim)]`` (no low-rank
  query path: the config has no ``q_lora_rank``);
- ``[c | k_r] = W_dkv y`` (``kv_lora_rank + qk_rope_head_dim``);
  ``c = RMSNorm(c)`` (``kv_a_layernorm``; ``use_qk_norm`` read as the norm
  of the compressed projection, the only reading under which the cache can
  hold ``c``); ``k_r = RoPE(k_r, p)``: ONE rotary key shared by all heads;
- ``q_rope = RoPE(q_rope, p)``; rotary on the interleaved pairs with
  ``deepseek_yarn`` frequencies (``decoder_spec.RopeYarn``);
- head ``h``: ``k_h = [W_uk,h c | k_r]``, ``v_h = W_uv,h c``
  (``v_head_dim``); causal softmax of ``q_h . k_h`` times
  ``q_head_dim^-0.5 x mscale^2``; ``a = W_o [o_1 .. o_H]``; ``x <- x + a``;
- ``y = RMSNorm(x)``; the first ``first_k_dense_replace`` layers: a dense
  SiLU-gated MLP of ``intermediate_size``.  The others: ``s = sigmoid(W_r
  y)`` over ``num_experts`` in float32; the ``num_experts_per_tok`` experts
  with the largest ``s_e + b_e`` are chosen (the bias selects and is not in
  the gate); ``g_e = routed_scaling_factor x s_e / sum of the chosen s``;
  ``f = sum g_e expert_e(y)`` (``moe_intermediate_size``) + the
  ``num_shared_experts`` shared experts; ``x <- x + f``;
- after the last layer an RMSNorm and an untied head.

The serving engine computes the ABSORBED form of the same attention
(``W_uk`` carried into the query, ``W_uv`` applied after the call:
``inference/generation.py``); ``forward`` here is the expanded one above.

The parameters exist once, as the engine scans them: the leading dense
layers one dict each, the expert layers one ``[layers, ...]`` stack a leaf
but for the expert banks, which are one array a layer (a custom call reads
a whole array where it lies; ``decoder_spec.EXPERT_BANKS``).  A chip that
holds a share of the experts (``experts_held`` of ``num_experts``, from
``expert_offset``) keeps the router at its published width.
"""

from __future__ import annotations

from dataclasses import dataclass, field, fields
from typing import Optional

import jax
import jax.numpy as jnp

from ..nn.layer import Layer, LayerList
from ..ops._prim import apply_op
from .cohere2_moe import _adopt, _ones
from .decoder_spec import (EXPERT_BANKS, DecoderSpec, LatentAttn, LayerKind,
                           MoeSpec, RopeYarn)
from .llama import _model_init, _scaled_init


@dataclass
class SarvamMlaConfig:
    """The source's own keys (``config.json`` of a ``sarvam_mla`` model),
    with the sizes of Sarvam-105B as defaults; ``vocab_size``,
    ``num_hidden_layers`` and ``experts_held`` are what is held here."""
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 16384         # the leading dense layers' MLP
    moe_intermediate_size: int = 2048      # one expert's width
    num_hidden_layers: int = 32
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    q_head_dim: int = 192
    v_head_dim: int = 128
    head_dim: int = 576                    # the cached row
    use_qk_norm: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=lambda: {
        "type": "deepseek_yarn", "factor": 40, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096})
    num_experts: int = 128                 # the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    moe_router_enable_expert_bias: bool = True
    routed_scaling_factor: float = 2.5
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 131072
    dtype: str = "bfloat16"
    # this chip's share of each layer's experts (not keys of the source)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    moe_dispatch: str = "grouped"
    moe_block_m: int = 128                 # many narrow experts: PR 27

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts
        for key, want in (
                ("hidden_act", "silu"), ("tie_word_embeddings", False),
                ("q_head_dim", self.qk_nope_head_dim + self.qk_rope_head_dim),
                ("head_dim", self.kv_lora_rank + self.qk_rope_head_dim)):
            if getattr(self, key) != want:
                raise ValueError(f"sarvam_mla: {key}={getattr(self, key)!r} "
                                 f"is not supported (only {want!r})")
        if self.rope_scaling and \
                self.rope_scaling.get("type") != "deepseek_yarn":
            raise ValueError(f"sarvam_mla: rope_scaling "
                             f"{self.rope_scaling.get('type')!r} is not "
                             "supported (only 'deepseek_yarn')")
        if not 0 <= self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError("sarvam_mla: first_k_dense_replace must leave "
                             "at least one expert layer")

    @classmethod
    def from_source(cls, source: dict, **over) -> "SarvamMlaConfig":
        """From the model's published ``config.json`` keys (others are
        ignored: they say nothing this file computes), ``over`` on top."""
        known = {f.name for f in fields(cls)}
        kw = {k: v for k, v in source.items() if k in known and v is not None}
        if "torch_dtype" in source:
            kw["dtype"] = source["torch_dtype"]
        kw.update(over)
        return cls(**kw)

    @staticmethod
    def tiny(**kw) -> "SarvamMlaConfig":
        """Test size: one dense layer and two expert layers; the rotary
        part keeps its 64 numbers and the query head its 192."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_hidden_layers=3,
                    num_attention_heads=4, kv_lora_rank=128, head_dim=192,
                    num_experts=8, num_experts_per_tok=4,
                    max_position_embeddings=256, dtype="float32",
                    moe_block_m=8, rope_scaling={
                        "type": "deepseek_yarn", "factor": 4,
                        "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                        "mscale_all_dim": 1,
                        "original_max_position_embeddings": 64})
        base.update(kw)
        return SarvamMlaConfig(**base)

    @staticmethod
    def sarvam_105b(**kw) -> "SarvamMlaConfig":
        """sarvam-105b as published (106 B parameters: far more than one
        chip holds; a deployment gives each chip a share)."""
        return SarvamMlaConfig(**kw)

    # ---- what the engine reads ----
    def latent(self) -> LatentAttn:
        return LatentAttn(rank=self.kv_lora_rank, nope=self.qk_nope_head_dim,
                          rope=self.qk_rope_head_dim, value=self.v_head_dim)

    def rope_yarn(self) -> Optional[RopeYarn]:
        rs = self.rope_scaling
        if not rs:
            return None
        return RopeYarn(
            factor=float(rs["factor"]),
            original=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs.get("beta_fast", 32)),
            beta_slow=float(rs.get("beta_slow", 1)),
            mscale=float(rs.get("mscale", 1)),
            mscale_all_dim=float(rs.get("mscale_all_dim", 0)))

    def moe_spec(self) -> MoeSpec:
        return MoeSpec(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            score="sigmoid", held=self.experts_held,
            offset=self.expert_offset, shared=self.num_shared_experts,
            dispatch="grouped" if self.moe_dispatch == "grouped" else "dense",
            block_m=self.moe_block_m,
            select_bias=bool(self.moe_router_enable_expert_bias),
            gate_scale=float(self.routed_scaling_factor))


def layer_leaves(c: SarvamMlaConfig, dense: bool) -> list:
    """``[(name, per-layer shape, initializer, dtype)]`` of one layer: the
    attention every layer has, then a dense MLP or the expert mixture."""
    H, heads = c.hidden_size, c.num_attention_heads
    rank, dt = c.kv_lora_rank, c.dtype
    qw, vw = heads * c.q_head_dim, heads * c.v_head_dim
    out = [
        ("self_attn.q_proj.weight", (H, qw), _scaled_init(H), dt),
        ("self_attn.kv_a_proj_with_mqa.weight",
         (H, rank + c.qk_rope_head_dim), _scaled_init(H), dt),
        ("self_attn.kv_a_layernorm.weight", (rank,), _ones, dt),
        # kv_b_proj's two halves, apart: the engine absorbs the first
        # into the query and applies the second after the call
        ("self_attn.k_up_proj.weight", (rank, heads * c.qk_nope_head_dim),
         _scaled_init(rank), dt),
        ("self_attn.v_up_proj.weight", (rank, vw), _scaled_init(rank), dt),
        ("self_attn.o_proj.weight", (vw, H), _scaled_init(vw), dt),
        ("input_layernorm.weight", (H,), _ones, dt),
        ("post_attention_layernorm.weight", (H,), _ones, dt),
    ]
    if dense:
        I = c.intermediate_size
        return out + [
            ("mlp.gate_proj.weight", (H, I), _scaled_init(H), dt),
            ("mlp.up_proj.weight", (H, I), _scaled_init(H), dt),
            ("mlp.down_proj.weight", (I, H), _scaled_init(I), dt)]
    I, S, held = c.moe_intermediate_size, c.num_shared_experts, c.experts_held
    out += [("mlp.gate.weight", (H, c.num_experts), _scaled_init(H), dt)]
    if c.moe_router_enable_expert_bias:
        # float32 whatever the model's dtype: it is added to float32 scores
        out += [("mlp.gate.bias", (c.num_experts,),
                 lambda shape, d: jnp.zeros(shape, d), "float32")]
    out += [("mlp.experts_gate", (held, H, I), _scaled_init(H), dt),
            ("mlp.experts_up", (held, H, I), _scaled_init(H), dt),
            ("mlp.experts_down", (held, I, H), _scaled_init(I), dt)]
    if S:
        out += [("mlp.shared_gate_proj.weight", (H, S * I),
                 _scaled_init(H), dt),
                ("mlp.shared_up_proj.weight", (H, S * I), _scaled_init(H),
                 dt),
                ("mlp.shared_down_proj.weight", (S * I, H), _scaled_init(I),
                 dt)]
    return out


class _Layers(Layer):
    """The parameters of the leading dense layers (``dense``: one set of
    arrays a layer, no layer axis) or of all the expert layers (one
    ``[layers, ...]`` stack a leaf, the expert banks one array a layer)."""

    def __init__(self, c, dense: bool, n: int, given: Optional[dict],
                 leaves=None):
        super().__init__(dtype=c.dtype)
        self.banks = () if dense else EXPERT_BANKS
        self.n = n
        # ``leaves``: another family's, in ``layer_leaves``' form
        for name, shape, make, dt in leaves or layer_leaves(c, dense):
            if name in self.banks:
                for l in range(n):
                    init = make if given is None else _adopt(
                        {name: given[name][l]}, name, dt)
                    self.add_parameter(f"{name}.{l}", self.create_parameter(
                        list(shape), dtype=dt, default_initializer=init))
                continue
            full = list(shape) if dense else [n, *shape]
            init = make if given is None else _adopt(given, name, dt)
            self.add_parameter(name, self.create_parameter(
                full, dtype=dt, default_initializer=init))

    def arrays(self) -> dict:
        out = {name: p._data for name, p in self._parameters.items()
               if name.rsplit(".", 1)[0] not in self.banks}
        for name in self.banks:
            out[name] = tuple(self._parameters[f"{name}.{l}"]._data
                              for l in range(self.n))
        return out


class SarvamMlaForCausalLM(Layer):
    """The model; ``ContinuousBatchingEngine(model, ...)`` takes it as it
    takes the other families.  ``params`` (the layout of
    ``serving_params()``): a caller's own arrays, adopted as the model's
    parameters instead of drawing random ones."""

    @_model_init("sarvam_mla")
    def __init__(self, config: SarvamMlaConfig,
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
        self.leading = LayerList([_Layers(c, True, 1, g) for g in lead])
        self.experts = _Layers(c, False, c.num_hidden_layers - k,
                               None if params is None
                               else params["blocks"][0])

    # ---- what the serving engine asks of a model (decoder_spec.py) ----
    def decoder_spec(self) -> DecoderSpec:
        c = self.config
        la = c.latent()
        k = c.first_k_dense_replace
        return DecoderSpec(
            pattern=(LayerKind(latent=la),),
            periods=c.num_hidden_layers - k,
            leading=(LayerKind(latent=la, dense_ffn=True),) * k,
            num_heads=c.num_attention_heads, num_kv_heads=1,
            head_dim=c.q_head_dim, norm="rms", norm_eps=c.rms_norm_eps,
            rope_theta=c.rope_theta, rope_yarn=c.rope_yarn(),
            moe=c.moe_spec())

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
        return apply_op("sarvam_mla_forward",
                        lambda ids: _forward(spec, params, ids), (input_ids,))


def _expanded_attention(spec: DecoderSpec, lp, q, c, k_r, cos, sin, keep):
    """The EXPANDED latent attention of every head (its own key ``[W_uk c |
    k_r]`` and value ``W_uv c`` made from ``c``): q ``[b, s, H, nope +
    rope]`` (its rotary part not yet rotated), c ``[b, s, rank]``, k_r ``[b,
    s, rope]`` (rotated); a dense softmax over the keys ``keep`` names
    (bool, broadcast against ``[b, H, s, s]``) -> ``[b, s, H x value]``."""
    from ..inference.generation import _rope_bt

    la, H = spec.latent, spec.num_heads
    b, s = c.shape[:2]
    f32 = jnp.float32
    q_r = _rope_bt(q[..., la.nope:], cos, sin)
    k_n = (c @ lp["self_attn.k_up_proj.weight"]).reshape(b, s, H, la.nope)
    v = (c @ lp["self_attn.v_up_proj.weight"]).reshape(b, s, H, la.value)
    sc = (jnp.einsum("bihd,bjhd->bhij", q[..., :la.nope].astype(f32),
                     k_n.astype(f32))
          + jnp.einsum("bihd,bjd->bhij", q_r.astype(f32),
                       k_r.astype(f32))) * spec.softmax_scale
    p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhij,bjhd->bihd", p, v.astype(f32)).reshape(b, s, -1)


def _run_stack(spec: DecoderSpec, params: dict, ids, attend):
    """ids [b, s] -> float32 logits [b, s, V] of a latent stack laid out as
    this file's (leading dense layers, then the expert layers' stacks):
    ``attend(y, lp)`` is a layer's attention on its normed input, before
    ``W_o``; the FFN is the serving path's own (``generation._moe_ffn``)."""
    from ..inference.generation import _moe_ffn
    from ..kernels.rms_norm import rms_norm_fp32 as norm

    def layer(x, lp, kind, bank_layer):
        y = norm(x, lp["input_layernorm.weight"], spec.norm_eps)
        x = x + attend(y, lp).astype(x.dtype) @ lp["self_attn.o_proj.weight"]
        y = norm(x, lp["post_attention_layernorm.weight"], spec.norm_eps)
        if kind.dense_ffn:
            f = (jax.nn.silu(y @ lp["mlp.gate_proj.weight"])
                 * (y @ lp["mlp.up_proj.weight"])) @ lp["mlp.down_proj.weight"]
        else:
            f, _ = _moe_ffn(y, lp, spec.moe, layer=bank_layer)
        return x + f

    x = jnp.take(params["embed"], ids, axis=0)
    for kind, lp in zip(spec.leading, params["leading"]):
        x = layer(x, lp, kind, None)
    (kind,), (stack,) = spec.pattern, params["blocks"]
    for r in range(spec.periods):
        lp = {n: a if isinstance(a, tuple) else a[r]
              for n, a in stack.items()}
        x = layer(x, lp, kind, jnp.int32(r))
    h = norm(x, params["norm"], spec.norm_eps)
    return (h @ params["head"]).astype(jnp.float32)


def _forward(spec: DecoderSpec, params: dict, ids):
    """ids [b, s] -> float32 logits [b, s, V]: the EXPANDED attention (every
    head's own key and value made from ``c``), dense masked softmax, the
    serving path's own expert mixture (``generation._moe_ffn``)."""
    from ..inference.generation import _rope_bt
    from ..kernels.rms_norm import rms_norm_fp32 as norm

    la, H = spec.latent, spec.num_heads
    b, s = ids.shape
    cos, sin = (jnp.broadcast_to(jnp.asarray(t)[None], (b, s, la.rope // 2))
                for t in spec.rope_tables(s))
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def attend(y, lp):
        q = (y @ lp["self_attn.q_proj.weight"]).reshape(
            b, s, H, la.nope + la.rope)
        ckr = y @ lp["self_attn.kv_a_proj_with_mqa.weight"]
        c = norm(ckr[..., :la.rank], lp["self_attn.kv_a_layernorm.weight"],
                 spec.norm_eps)
        k_r = _rope_bt(ckr[..., None, la.rank:], cos, sin)[..., 0, :]
        return _expanded_attention(spec, lp, q, c, k_r, cos, sin, seen)

    return _run_stack(spec, params, ids, attend)
