"""Solar-Open2 decoders (``model_type: solar_open2``): gated delta-rule
linear attention with a decay a channel (Kimi Delta Attention, "KDA") on
three layers in four, a gated softmax layer WITHOUT positions on the fourth,
sigmoid-routed experts with a selection bias and one shared expert in every
layer.

Pre-norm, sequential residuals, RMS norms: ``x <- x + mix(norm1(x))``, ``x
<- x + moe(norm2(x))``.  Layer ``l`` is a SOFTMAX layer where ``l`` is in
``gqa_layers`` (0, 4, 8, ...: one layer a period of ``gqa_interval + 1``),
else a LINEAR layer.  ``u = norm1(x)``:

- **linear (KDA)**, ``linear_attn_config``'s ``num_heads`` of ``head_dim``:
  ``[q | k | v] = W_qkv u``; a causal depthwise convolution of
  ``short_conv_kernel_size`` taps, no bias, over all of it, then SiLU; ``q``
  and ``k`` L2-normalised a head, ``q`` times ``head_dim^-0.5``; the log
  decay a CHANNEL ``a_t = -exp(A_log[h]) x softplus(W_fb (W_fa u) +
  dt_bias)`` (``kda_use_full_proj: false``: through a rank of ``head_dim``);
  ``beta_t = 2 sigmoid(W_b u)`` a head (``kda_allow_neg_eigval``: the factor
  2); per head the float32 state ``S`` (key x value), zero at position 0:
  ``S' = diag(exp(a_t)) S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T
  k_t)^T``, ``o_t = S_t^T q_t``; ``RMSNorm_head(o_t) * sigmoid(W_gb (W_ga
  u))``, then ``W_o``;
- **softmax**: ``q = W_q u`` (``num_attention_heads`` of ``head_dim``), ``k,
  v`` (``num_key_value_heads``), NO rotary and no other position
  (``use_rope: false``), causal softmax at ``head_dim^-0.5`` over the whole
  context, times ``sigmoid(W_g u)`` elementwise (``use_gqa_gate``), then
  ``W_o``;
- **experts, every layer** (``first_k_dense_replace`` 0): ``s = sigmoid(W_r
  h)`` over ``n_routed_experts`` in float32, the ``num_experts_per_tok``
  with the largest ``s + b`` chosen (the bias selects and is not in the
  gate), ``g_e = routed_scaling_factor x s_e / sum of the chosen s``, SwiGLU
  experts of ``moe_intermediate_size``, plus one shared SwiGLU expert of
  ``n_shared_experts x moe_intermediate_size`` added whole;
- after the last layer an RMSNorm and an untied head.

What the config does not say (the gate rank, SiLU after the convolution, the
L2 norm, ``A_log`` a head and ``dt_bias`` a channel, the output gate's form,
sigmoid scores with a selection bias) follows Kimi Linear's KDA and the
family's earlier router; ``chipbench/configs/solar-open2-250b-ep8.json``
lists each under ``assumed``.

The serving engine runs a linear place through ``kernels/kda.py`` on a
recurrent state that rides with the pool (``inference/kv_cache.py``); such a
place keeps NO pages.  ``forward`` here is the same equations over a whole
sequence, no cache.  The parameters are laid out one dict a place of the
period (a softmax place's leaves, then three linear places'), each a
``[periods, ...]`` stack a leaf with the expert banks one array a layer; a
chip that holds a share of the experts (``experts_held`` of
``n_routed_experts``, from ``expert_offset``) keeps the router at its
published width.
"""

from __future__ import annotations

from dataclasses import dataclass, field, fields
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.layer import Layer, LayerList
from ..ops._prim import apply_op
from .cohere2_moe import _adopt, _ones
from .decoder_spec import DecoderSpec, DeltaMixer, LayerKind, MoeSpec
from .falcon_h1 import _zeros
from .llama import _model_init, _scaled_init
from .sarvam_mla import _Layers


@dataclass
class SolarOpen2Config:
    """The source's own keys (``config.json`` of a ``solar_open2`` model),
    with the sizes of Solar-Open2-250B as defaults; ``vocab_size``,
    ``num_hidden_layers`` and ``experts_held`` are what is held and run
    here."""
    vocab_size: int = 196608
    hidden_size: int = 4096
    intermediate_size: int = 10240         # the dense width: no layer uses it
    moe_intermediate_size: int = 1280      # one expert's width
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    linear_attn_config: dict = field(default_factory=lambda: {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None})
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0            # inert: use_rope is false
    partial_rotary_factor: float = 1.0     # inert likewise
    use_rope: bool = False
    gqa_interval: int = 3
    gqa_layers: Optional[Tuple[int, ...]] = None
    use_gqa_gate: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    first_k_dense_replace: int = 0
    n_routed_experts: int = 320            # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 1048576
    dtype: str = "bfloat16"
    # this chip's share of each layer's experts (not keys of the source)
    experts_held: Optional[int] = None
    expert_offset: int = 0
    moe_dispatch: str = "grouped"
    moe_block_m: int = 128                 # many narrow experts: PR 27

    def __post_init__(self):
        L, p = self.num_hidden_layers, self.gqa_interval + 1
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts
        if self.gqa_layers is None:
            self.gqa_layers = tuple(range(0, L, p))
        # the published list names every softmax layer of the 48: cut to the
        # depth held
        self.gqa_layers = tuple(int(l) for l in self.gqa_layers if l < L)
        # what this model file does not compute is refused, not ignored
        for key, want, why in (
                ("use_rope", False,
                 "the softmax layers carry no positional embedding"),
                ("kda_use_full_proj", False,
                 "the decay and the output gate go through a rank of "
                 "head_dim, not a full projection"),
                ("first_k_dense_replace", 0,
                 "every layer's FFN is the expert mixture"),
                ("norm_topk_prob", True,
                 "the chosen scores are divided by their sum"),
                ("hidden_act", "silu", "the experts are SiLU-gated"),
                ("tie_word_embeddings", False, "the head is untied")):
            if getattr(self, key) != want:
                raise ValueError(
                    f"solar_open2: {key}={getattr(self, key)!r} is not "
                    f"computed (only {want!r}: {why})")
        if self.gqa_layers != tuple(range(0, L, p)):
            raise ValueError(
                f"solar_open2: gqa_layers={list(self.gqa_layers)} is not "
                f"one softmax layer opening every period of gqa_interval + "
                f"1 = {p} layers ({list(range(0, L, p))}): only that "
                "pattern is served")
        if L % p or L < p:
            raise ValueError(
                f"solar_open2: num_hidden_layers {L} is not whole periods "
                f"of {p} layers (one softmax layer, {p - 1} linear ones)")
        if self.n_shared_experts < 1:
            raise ValueError("solar_open2: n_shared_experts "
                             f"{self.n_shared_experts}: one shared expert "
                             "(of n x moe_intermediate_size) is added whole")
        la = self.linear_attn_config
        if la.get("num_kv_heads") not in (None, la["num_heads"]):
            raise ValueError(
                f"solar_open2: linear_attn_config.num_kv_heads="
                f"{la['num_kv_heads']!r} is not computed (only null: every "
                "linear head has its own key and value)")

    @classmethod
    def from_source(cls, source: dict, num_experts: Optional[int] = None,
                    **over) -> "SolarOpen2Config":
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
    def tiny(**kw) -> "SolarOpen2Config":
        """Test size that keeps the shape of the thing: two periods of
        (softmax, linear, linear, linear), a query group of 2, linear heads
        whose key is narrower than a softmax head, 8 experts of which 2 are
        chosen."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_hidden_layers=8,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=32, linear_attn_config={
                        "short_conv_kernel_size": 4, "head_dim": 16,
                        "num_heads": 4, "num_kv_heads": None},
                    n_routed_experts=8, num_experts_per_tok=2,
                    max_position_embeddings=256, dtype="float32",
                    moe_block_m=8)
        base.update(kw)
        return SolarOpen2Config(**base)

    @staticmethod
    def solar_open2_250b(depth: int = 4, share: int = 8, index: int = 0,
                         **kw) -> "SolarOpen2Config":
        """Chip ``index`` of the ``share`` that hold each layer of a
        pipeline stage of Solar-Open2-250B
        (``chipbench/configs/solar-open2-250b-ep8.json``): ``depth`` of its
        48 layers (whole periods of four), ``320 / share`` of the routed
        experts and part ``index % 8`` of the vocabulary.  ``share`` 1:
        every expert and the whole vocabulary (250 B parameters: no chip
        holds it)."""
        vocab_parts = min(share, 8)
        base = dict(num_hidden_layers=depth,
                    vocab_size=196608 // vocab_parts,
                    experts_held=320 // share,
                    expert_offset=(320 // share) * index)
        base.update(kw)
        return SolarOpen2Config(**base)

    # ---- what the engine reads ----
    def period(self) -> int:
        return self.gqa_interval + 1

    def mixer(self) -> DeltaMixer:
        la = self.linear_attn_config
        return DeltaMixer(
            heads=la["num_heads"], key_dim=la["head_dim"],
            value_dim=la["head_dim"], conv=la["short_conv_kernel_size"],
            gate_rank=la["head_dim"], neg_eigval=self.kda_allow_neg_eigval)

    def pattern(self) -> Tuple[LayerKind, ...]:
        return (LayerKind(rope=False, out_gate=self.use_gqa_gate),) \
            + (LayerKind(linear=self.mixer()),) * self.gqa_interval

    def moe_spec(self) -> MoeSpec:
        return MoeSpec(
            num_experts=self.n_routed_experts, top_k=self.num_experts_per_tok,
            score="sigmoid", held=self.experts_held,
            offset=self.expert_offset, shared=1,
            dispatch="grouped" if self.moe_dispatch == "grouped" else "dense",
            block_m=self.moe_block_m, select_bias=True,
            gate_scale=float(self.routed_scaling_factor))


# what the recurrence reads in float32 whatever the model's type
FLOAT32_LEAVES = ("linear_attn.A_log", "linear_attn.dt_bias",
                  "mlp.gate.bias")


def _moe_leaves(c: SolarOpen2Config) -> list:
    H, I, held = c.hidden_size, c.moe_intermediate_size, c.experts_held
    S, dt = c.n_shared_experts * c.moe_intermediate_size, c.dtype
    return [
        ("input_layernorm.weight", (H,), _ones, dt),
        ("post_attention_layernorm.weight", (H,), _ones, dt),
        ("mlp.gate.weight", (H, c.n_routed_experts), _scaled_init(H), dt),
        # float32 whatever the model's dtype: it is added to float32 scores
        ("mlp.gate.bias", (c.n_routed_experts,), _zeros, "float32"),
        ("mlp.experts_gate", (held, H, I), _scaled_init(H), dt),
        ("mlp.experts_up", (held, H, I), _scaled_init(H), dt),
        ("mlp.experts_down", (held, I, H), _scaled_init(I), dt),
        ("mlp.shared_gate_proj.weight", (H, S), _scaled_init(H), dt),
        ("mlp.shared_up_proj.weight", (H, S), _scaled_init(H), dt),
        ("mlp.shared_down_proj.weight", (S, H), _scaled_init(S), dt)]


def layer_leaves(c: SolarOpen2Config, linear: bool) -> list:
    """``[(name, per-layer shape, initializer, dtype)]`` of one layer: a
    linear place's mixer or a softmax place's attention, then the norms and
    the expert mixture every layer has.  Weights are ``[in, out]``; the
    convolution is ``[taps, channels]``."""
    H, dt = c.hidden_size, c.dtype
    if not linear:
        q = c.num_attention_heads * c.head_dim
        kv = c.num_key_value_heads * c.head_dim
        mix = [("self_attn.q_proj.weight", (H, q), _scaled_init(H), dt),
               ("self_attn.k_proj.weight", (H, kv), _scaled_init(H), dt),
               ("self_attn.v_proj.weight", (H, kv), _scaled_init(H), dt),
               ("self_attn.o_proj.weight", (q, H), _scaled_init(q), dt)]
        if c.use_gqa_gate:
            mix.append(("self_attn.g_proj.weight", (H, q), _scaled_init(H),
                        dt))
        return mix + _moe_leaves(c)
    mx = c.mixer()
    hk, hv, r = mx.heads * mx.key_dim, mx.heads * mx.value_dim, mx.gate_rank
    return [
        ("linear_attn.qkv_proj.weight", (H, mx.conv_width), _scaled_init(H),
         dt),
        ("linear_attn.conv1d.weight", (mx.conv, mx.conv_width),
         _scaled_init(mx.conv), dt),
        ("linear_attn.f_a_proj.weight", (H, r), _scaled_init(H), dt),
        ("linear_attn.f_b_proj.weight", (r, hk), _scaled_init(r), dt),
        ("linear_attn.A_log", (mx.heads,), _zeros, "float32"),
        ("linear_attn.dt_bias", (hk,), _zeros, "float32"),
        ("linear_attn.b_proj.weight", (H, mx.heads), _scaled_init(H), dt),
        ("linear_attn.g_a_proj.weight", (H, r), _scaled_init(H), dt),
        ("linear_attn.g_b_proj.weight", (r, hv), _scaled_init(r), dt),
        ("linear_attn.o_norm.weight", (mx.value_dim,), _ones, dt),
        ("linear_attn.o_proj.weight", (hv, H), _scaled_init(hv), dt),
    ] + _moe_leaves(c)


class SolarOpen2ForCausalLM(Layer):
    """The model; ``ContinuousBatchingEngine(model, ...)`` takes it as it
    takes the other families.  ``params`` (the layout of
    ``serving_params()``): a caller's own arrays, adopted as the model's
    parameters instead of drawing random ones, so that a build holds the
    weights once."""

    @_model_init("solar_open2")
    def __init__(self, config: SolarOpen2Config,
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
        places = [None] * c.period() if params is None else params["blocks"]
        if len(places) != c.period():
            raise ValueError(f"params has {len(places)} block stacks, the "
                             f"layer pattern {c.period()} places")
        periods = c.num_hidden_layers // c.period()
        self.blocks = LayerList([
            _Layers(c, False, periods, given,
                    leaves=layer_leaves(c, linear=p > 0))
            for p, given in enumerate(places)])

    # ---- what the serving engine asks of a model (decoder_spec.py) ----
    def decoder_spec(self) -> DecoderSpec:
        c = self.config
        return DecoderSpec(
            pattern=c.pattern(), periods=c.num_hidden_layers // c.period(),
            num_heads=c.num_attention_heads,
            num_kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
            norm="rms", norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta,
            moe=c.moe_spec())

    def serving_params(self) -> dict:
        """The parameters themselves (no copy): one dict a place, its
        stacks ``[periods, ...]`` and its expert banks a tuple of
        ``periods`` arrays."""
        return {"embed": self.embed_tokens._data, "norm": self.norm._data,
                "head": self.lm_head._data,
                "blocks": tuple(b.arrays() for b in self.blocks)}

    # ---- the whole sequence at once (no cache): evaluation, tests ----
    def forward(self, input_ids):
        spec = self.decoder_spec()
        params = self.serving_params()
        return apply_op("solar_open2_forward",
                        lambda ids: _forward(spec, params, ids), (input_ids,))


def _linear_mix(spec: DecoderSpec, mx: DeltaMixer, lp: dict, u):
    """A linear place's mixer over whole sequences ``u [b, s, H]``: the
    convolution from zeros before the sequence, the bare recurrence from a
    zero state."""
    from ..kernels.kda import kda_recurrence, l2_normalised as unit

    f32 = jnp.float32
    b, s = u.shape[:2]
    hk = mx.heads * mx.key_dim
    qkv = (u @ lp["linear_attn.qkv_proj.weight"]).astype(f32)
    ext = jnp.pad(qkv, ((0, 0), (mx.conv - 1, 0), (0, 0)))
    w = lp["linear_attn.conv1d.weight"].astype(f32)
    mixed = jax.nn.silu(sum(w[j] * ext[:, j:j + s] for j in range(mx.conv)))
    q = unit(mixed[..., :hk].reshape(b, s, mx.heads, mx.key_dim)) \
        * mx.key_dim ** -0.5
    k = unit(mixed[..., hk:2 * hk].reshape(b, s, mx.heads, mx.key_dim))
    v = mixed[..., 2 * hk:].reshape(b, s, mx.heads, mx.value_dim)
    fa = ((u @ lp["linear_attn.f_a_proj.weight"])
          @ lp["linear_attn.f_b_proj.weight"]).astype(f32)
    g = -jnp.exp(lp["linear_attn.A_log"].astype(f32))[None, None, :, None] \
        * jax.nn.softplus(
            fa.reshape(b, s, mx.heads, mx.key_dim)
            + lp["linear_attn.dt_bias"].astype(f32).reshape(mx.heads,
                                                            mx.key_dim))
    beta = jax.nn.sigmoid((u @ lp["linear_attn.b_proj.weight"]).astype(f32)) \
        * (2.0 if mx.neg_eigval else 1.0)
    zero = jnp.zeros((mx.heads, mx.key_dim, mx.value_dim), f32)
    o = jax.vmap(lambda *t: kda_recurrence(zero, *t)[0])(
        q.astype(u.dtype), k.astype(u.dtype), v.astype(u.dtype), g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + spec.norm_eps) \
        * lp["linear_attn.o_norm.weight"].astype(f32)
    og = ((u @ lp["linear_attn.g_a_proj.weight"])
          @ lp["linear_attn.g_b_proj.weight"]).astype(f32)
    o = (o.reshape(b, s, -1) * jax.nn.sigmoid(og)).astype(u.dtype)
    return o @ lp["linear_attn.o_proj.weight"]


def _forward(spec: DecoderSpec, params: dict, ids):
    """ids [b, s] -> float32 logits [b, s, V]: dense masked attention
    without positions and its gate, the bare delta-rule recurrence, the
    serving path's own expert mixture (``generation._moe_ffn``)."""
    from ..inference.generation import _moe_ffn
    from ..kernels.rms_norm import rms_norm_fp32 as norm

    f32 = jnp.float32
    b, s = ids.shape
    group = spec.num_heads // spec.num_kv_heads
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    x = jnp.take(params["embed"], ids, axis=0)
    for r in range(spec.periods):
        for kind, place in zip(spec.pattern, params["blocks"]):
            lp = {n: a[r] for n, a in place.items()}
            u = norm(x, lp["input_layernorm.weight"], spec.norm_eps)
            if kind.linear is not None:
                x = x + _linear_mix(spec, kind.linear, lp, u)
            else:
                q = (u @ lp["self_attn.q_proj.weight"]).reshape(
                    b, s, spec.num_kv_heads, group, spec.head_dim)
                k = (u @ lp["self_attn.k_proj.weight"]).reshape(
                    b, s, spec.num_kv_heads, spec.head_dim)
                v = (u @ lp["self_attn.v_proj.weight"]).reshape(
                    b, s, spec.num_kv_heads, spec.head_dim)
                sc = jnp.einsum("bikgd,bjkd->bkgij", q.astype(f32),
                                k.astype(f32)) * spec.softmax_scale
                p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
                a = jnp.einsum("bkgij,bjkd->bikgd", p,
                               v.astype(f32)).reshape(b, s, -1)
                if kind.out_gate:
                    a = a * jax.nn.sigmoid(
                        (u @ lp["self_attn.g_proj.weight"]).astype(f32))
                x = x + a.astype(x.dtype) @ lp["self_attn.o_proj.weight"]
            y = norm(x, lp["post_attention_layernorm.weight"], spec.norm_eps)
            x = x + _moe_ffn(y, lp, spec.moe)[0]
    h = norm(x, params["norm"], spec.norm_eps)
    return (h @ params["head"]).astype(f32)
