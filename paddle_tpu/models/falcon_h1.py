"""Falcon-H1 decoders (``model_type: falcon_h1``): every block runs a
Mamba-2 state-space mixer BESIDE grouped-query attention, from one norm.

One layer, ``h`` the residual stream of the token at position ``p``,
``u = RMSNorm_in(h)``:

- attention: ``q = W_q (u x attention_in_multiplier)``, ``k =
  key_multiplier x W_k (u x attention_in_multiplier)``, ``v`` likewise
  without the key's multiplier; rotary over the whole head (interleaved
  pairs, ``rope_theta``, no scaling); causal softmax at ``head_dim^-0.5``;
  ``a = attention_out_multiplier x W_o [heads]``;
- the mixer: ``[z | xBC | dt] = (W_in (u x ssm_in_multiplier)) * m``, ``m``
  the five zones ``z, x, B, C, dt`` times ``ssm_multipliers``; ``xBC`` goes
  through a causal depthwise convolution of ``mamba_d_conv`` taps with bias
  and SiLU; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t`` with ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)`` (``B``, ``C`` shared by the heads of a group); ``y *
  silu(z)`` RMS-normed over each group with a learned weight
  (``mamba_rms_norm``, not ``mamba_norm_before_gate``); ``s =
  ssm_out_multiplier x W_out``;
- ``h' = h + a + s``; then ``h'' = h' + mlp_multipliers[1] x W_down(W_up v *
  silu(mlp_multipliers[0] x W_gate v))``, ``v = RMSNorm_ff(h')``;
- ``h_0 = embedding_multiplier x E[token]``; ``logits = lm_head_multiplier
  x W_head RMSNorm_f(h_L)``, the head untied.

The multipliers are constants of the parametrisation; the engine applies
them where these lines do (``DecoderSpec``'s scales, ``SsmMixer``'s), the
weights are as stored.  The parameters exist ONCE, as the ``[layers, ...]``
stacks the serving engine scans (``serving_params()`` hands out these very
arrays); what a slot keeps between steps is pages AND a recurrent state
(``inference/kv_cache.py::RecurrentState``).
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.layer import Layer
from ..ops._prim import apply_op
from .cohere2_moe import _adopt, _ones
from .decoder_spec import DecoderSpec, LayerKind, SsmMixer
from .llama import _model_init, _rope_cos_sin, _scaled_init

# what the scan reads in float32 whatever the model's type
FLOAT32_LEAVES = ("mamba.dt_bias", "mamba.A_log", "mamba.D")


@dataclass
class FalconH1Config:
    """The source's own keys (``config.json`` of a ``falcon_h1`` model),
    with the sizes of Falcon-H1-34B-Instruct as defaults."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"
    attention_bias: bool = False
    mlp_bias: bool = False
    projectors_bias: bool = False
    attn_layer_indices: Optional[tuple] = None
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    # how the source's kernel blocks the scan's sum, not part of the
    # mathematics: the engine's chunk is its ``prefill_bucket``
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 5.656854249492381
    attention_in_multiplier: float = 1.0
    key_multiplier: float = 0.011048543456039804
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    ssm_out_multiplier: float = 0.08838834764831845
    mlp_multipliers: Tuple[float, ...] = (0.1767766952966369,
                                          0.011160714285714284)
    lm_head_multiplier: float = 0.0078125
    dtype: str = "bfloat16"

    def __post_init__(self):
        self.ssm_multipliers = tuple(float(x) for x in self.ssm_multipliers)
        self.mlp_multipliers = tuple(float(x) for x in self.mlp_multipliers)
        # what this model file does not compute is refused, not ignored
        for key, want in (("attention_bias", False), ("mlp_bias", False),
                          ("projectors_bias", False),
                          ("mamba_proj_bias", False),
                          ("mamba_conv_bias", True), ("mamba_rms_norm", True),
                          ("mamba_norm_before_gate", False),
                          ("hidden_act", "silu"), ("rope_scaling", None),
                          ("attn_layer_indices", None),
                          ("tie_word_embeddings", False)):
            if getattr(self, key) != want:
                raise ValueError(f"falcon_h1: {key}={getattr(self, key)!r} "
                                 f"is not supported (only {want!r})")
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"falcon_h1: mamba_d_ssm ({self.mamba_d_ssm}) is not "
                f"mamba_n_heads x mamba_d_head ({self.mamba_n_heads} x "
                f"{self.mamba_d_head})")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("falcon_h1: five ssm_multipliers (z, x, B, C, "
                             "dt) and two mlp_multipliers (gate, down)")

    @classmethod
    def from_source(cls, source: dict, **over) -> "FalconH1Config":
        """From the model's published ``config.json`` keys (others are
        ignored: they say nothing this file computes), ``over`` on top."""
        known = {f.name for f in fields(cls)}
        kw = {k: v for k, v in source.items() if k in known}
        if "torch_dtype" in source:
            kw["dtype"] = source["torch_dtype"]
        kw.update(over)
        return cls(**kw)

    @staticmethod
    def tiny(**kw) -> "FalconH1Config":
        """Test size that keeps the shape of the thing: 2 groups, a group
        of 5 query heads a KV head, a state wider than a head."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    num_hidden_layers=2, num_attention_heads=5,
                    num_key_value_heads=1, head_dim=32, mamba_d_ssm=64,
                    mamba_n_heads=4, mamba_d_head=16, mamba_d_state=32,
                    mamba_n_groups=2, max_position_embeddings=256,
                    dtype="float32")
        base.update(kw)
        return FalconH1Config(**base)

    @staticmethod
    def falcon_h1_34b(**kw) -> "FalconH1Config":
        """Falcon-H1-34B-Instruct as published (33.6 B parameters)."""
        return FalconH1Config(**kw)

    def mixer(self) -> SsmMixer:
        return SsmMixer(
            heads=self.mamba_n_heads, head_dim=self.mamba_d_head,
            state=self.mamba_d_state, groups=self.mamba_n_groups,
            conv=self.mamba_d_conv, in_scale=self.ssm_in_multiplier,
            zone_scales=self.ssm_multipliers,
            out_scale=self.ssm_out_multiplier)


def _zeros(shape, dtype):
    return jnp.zeros(shape, dtype)


def layer_leaves(c: FalconH1Config) -> list:
    """One layer's parameters: (name, shape, initializer, dtype).  Weights
    are ``[in, out]``; the convolution is ``[taps, channels]``."""
    H, I = c.hidden_size, c.intermediate_size
    q = c.num_attention_heads * c.head_dim
    kv = c.num_key_value_heads * c.head_dim
    mx = c.mixer()
    dt, f32 = c.dtype, "float32"
    return [
        ("self_attn.q_proj.weight", (H, q), _scaled_init(H), dt),
        ("self_attn.k_proj.weight", (H, kv), _scaled_init(H), dt),
        ("self_attn.v_proj.weight", (H, kv), _scaled_init(H), dt),
        ("self_attn.o_proj.weight", (q, H), _scaled_init(q), dt),
        ("input_layernorm.weight", (H,), _ones, dt),
        ("post_attention_layernorm.weight", (H,), _ones, dt),
        ("mamba.in_proj.weight", (H, mx.in_width), _scaled_init(H), dt),
        ("mamba.conv1d.weight", (mx.conv, mx.conv_width),
         _scaled_init(mx.conv), dt),
        ("mamba.conv1d.bias", (mx.conv_width,), _zeros, dt),
        ("mamba.dt_bias", (mx.heads,), _zeros, f32),
        ("mamba.A_log", (mx.heads,), _zeros, f32),
        ("mamba.D", (mx.heads,), _ones, f32),
        ("mamba.norm.weight", (mx.inner,), _ones, dt),
        ("mamba.out_proj.weight", (mx.inner, H), _scaled_init(mx.inner), dt),
        ("mlp.gate_proj.weight", (H, I), _scaled_init(H), dt),
        ("mlp.up_proj.weight", (H, I), _scaled_init(H), dt),
        ("mlp.down_proj.weight", (I, H), _scaled_init(I), dt),
    ]


class _Stack(Layer):
    """The parameters of all layers at once: every array is ``[layers,
    ...]`` (the engine scans over them)."""

    def __init__(self, c: FalconH1Config, given: Optional[dict]):
        super().__init__(dtype=c.dtype)
        for name, shape, make, dt in layer_leaves(c):
            if given is not None:
                make = _adopt(given, name, dt)
            self.add_parameter(name, self.create_parameter(
                [c.num_hidden_layers, *shape], dtype=dt,
                default_initializer=make))

    def arrays(self) -> dict:
        return {name: p._data for name, p in self._parameters.items()}


class FalconH1ForCausalLM(Layer):
    """The model; ``ContinuousBatchingEngine(model, ...)`` takes it as it
    takes a ``LlamaForCausalLM``.  ``params`` (the layout of
    ``serving_params()``): a caller's own arrays, adopted as the model's
    parameters instead of drawing random ones."""

    @_model_init("falcon_h1")
    def __init__(self, config: FalconH1Config, params: Optional[dict] = None):
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
        if params is not None and len(params["blocks"]) != 1:
            raise ValueError(f"params has {len(params['blocks'])} block "
                             "stacks, the layer pattern 1 place")
        self.layers = _Stack(c, None if params is None
                             else params["blocks"][0])

    # ---- what the serving engine asks of a model (decoder_spec.py) ----
    def decoder_spec(self) -> DecoderSpec:
        c = self.config
        gate, down = c.mlp_multipliers
        return DecoderSpec(
            pattern=(LayerKind(ssm=c.mixer()),), periods=c.num_hidden_layers,
            num_heads=c.num_attention_heads,
            num_kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
            norm="rms", norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta,
            embed_scale=c.embedding_multiplier,
            attn_in_scale=c.attention_in_multiplier,
            key_scale=c.key_multiplier,
            attn_out_scale=c.attention_out_multiplier,
            mlp_gate_scale=gate, mlp_out_scale=down,
            logit_scale=c.lm_head_multiplier)

    def serving_params(self) -> dict:
        """The parameters themselves (no copy): one dict of stacks."""
        return {"embed": self.embed_tokens._data, "norm": self.norm._data,
                "head": self.lm_head._data,
                "blocks": (self.layers.arrays(),)}

    # ---- the whole sequence at once (no cache): evaluation, tests ----
    def forward(self, input_ids):
        spec = self.decoder_spec()
        params = self.serving_params()
        return apply_op("falcon_h1_forward",
                        lambda ids: _forward(spec, params, ids), (input_ids,))


def _forward(spec: DecoderSpec, params: dict, ids):
    """ids [b, s] -> float32 logits [b, s, V]: dense masked attention and
    the bare recurrence over the whole sequence, no cache."""
    from ..inference.generation import _rope_bt, _scaled
    from ..kernels.rms_norm import rms_norm_fp32
    from ..kernels.ssd import ssd_recurrence

    f32 = jnp.float32
    mx = spec.ssm
    b, s = ids.shape
    g = spec.num_heads // spec.num_kv_heads
    d, n = mx.inner, mx.groups * mx.state
    cos, sin = _rope_cos_sin(s, spec.head_dim, spec.rope_theta, f32)
    cos, sin = (jnp.broadcast_to(t[None], (b,) + t.shape) for t in (cos, sin))
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    kz, kx, kb, kc, kd = mx.zone_scales
    x = _scaled(jnp.take(params["embed"], ids, axis=0), spec.embed_scale)
    stack, = params["blocks"]
    for r in range(spec.periods):
        lp = {k: v[r] for k, v in stack.items()}
        u = rms_norm_fp32(x, lp["input_layernorm.weight"], spec.norm_eps)
        ua = _scaled(u, spec.attn_in_scale)
        q = (ua @ lp["self_attn.q_proj.weight"]).reshape(
            b, s, spec.num_heads, spec.head_dim)
        k = _scaled(ua @ lp["self_attn.k_proj.weight"],
                    spec.key_scale).reshape(
            b, s, spec.num_kv_heads, spec.head_dim)
        v = (ua @ lp["self_attn.v_proj.weight"]).reshape(
            b, s, spec.num_kv_heads, spec.head_dim)
        q, k = _rope_bt(q, cos, sin), _rope_bt(k, cos, sin)
        qg = q.reshape(b, s, spec.num_kv_heads, g, spec.head_dim)
        sc = jnp.einsum("bikgd,bjkd->bkgij", qg.astype(f32),
                        k.astype(f32)) * spec.softmax_scale
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        a = jnp.einsum("bkgij,bjkd->bikgd", p, v.astype(f32))
        a = _scaled(a.reshape(b, s, -1).astype(x.dtype)
                    @ lp["self_attn.o_proj.weight"], spec.attn_out_scale)
        zxd = (_scaled(u, mx.in_scale) @ lp["mamba.in_proj.weight"]) \
            .astype(f32)
        z, xbc, dt = zxd[..., :d] * kz, zxd[..., d:d + mx.conv_width], \
            zxd[..., d + mx.conv_width:] * kd
        xbc = jnp.concatenate([xbc[..., :d] * kx, xbc[..., d:d + n] * kb,
                               xbc[..., d + n:] * kc], -1).astype(x.dtype)
        ext = jnp.pad(xbc, ((0, 0), (mx.conv - 1, 0), (0, 0))).astype(f32)
        w = lp["mamba.conv1d.weight"].astype(f32)
        acc = lp["mamba.conv1d.bias"].astype(f32) + sum(
            w[j] * ext[:, j:j + s] for j in range(mx.conv))
        xbc = jax.nn.silu(acc).astype(x.dtype)
        dt = jax.nn.softplus(dt + lp["mamba.dt_bias"].astype(f32))
        A = -jnp.exp(lp["mamba.A_log"].astype(f32))
        zero = jnp.zeros((mx.heads, mx.head_dim, mx.state), f32)
        y = jax.vmap(lambda xs, bs, cs, ds: ssd_recurrence(
            zero, xs, bs, cs, ds, A, lp["mamba.D"].astype(f32))[0])(
            xbc[..., :d].reshape(b, s, mx.heads, mx.head_dim),
            xbc[..., d:d + n].reshape(b, s, mx.groups, mx.state),
            xbc[..., d + n:].reshape(b, s, mx.groups, mx.state), dt)
        gated = y.reshape(b, s, d) * jax.nn.silu(z)
        gated = rms_norm_fp32(
            gated.reshape(b, s, mx.groups, d // mx.groups),
            lp["mamba.norm.weight"].reshape(mx.groups, d // mx.groups),
            spec.norm_eps).reshape(b, s, d).astype(x.dtype)
        x = x + a + _scaled(gated @ lp["mamba.out_proj.weight"],
                            mx.out_scale)
        u = rms_norm_fp32(x, lp["post_attention_layernorm.weight"],
                          spec.norm_eps)
        act = jax.nn.silu(_scaled(u @ lp["mlp.gate_proj.weight"],
                                  spec.mlp_gate_scale)) \
            * (u @ lp["mlp.up_proj.weight"])
        x = x + _scaled(act @ lp["mlp.down_proj.weight"], spec.mlp_out_scale)
    h = rms_norm_fp32(x, params["norm"], spec.norm_eps)
    return (h @ params["head"]).astype(f32) * spec.logit_scale
