"""SmallThinker decoders (``model_name: smallthinker_*``, e.g.
SmallThinker-21BA3B-Instruct): many small ReGLU experts, every one of them
held, behind a router that reads the ATTENTION's input, in a stack whose
full-attention layers carry no positions among windowed ones that rotate.

One layer ``l``, ``x`` the residual stream (``kind = full`` where
``sliding_window_layout[l] == 0``, which are the layers with
``rope_layout[l] == 0``: layers 0, 4, 8, ...):

- ``u = RMSNorm(x)``;
- ``r = u W_r``: the router's ``moe_num_primary_experts`` logits in
  float32, from the tensor the attention projects ("router placed before
  attention"); the ``moe_num_active_primary_experts`` largest are chosen and
  their gates are the softmax over the chosen (``norm_topk_prob``: softmax
  over all, the chosen renormalised);
- ``a = Attn(u)``: grouped-query attention, ``head_dim`` stated, no bias, no
  q/k norm.  A windowed layer rotates the interleaved pairs ``(2i, 2i+1)``
  with ``rope_theta`` and sees the last ``sliding_window_size`` keys; a full
  layer sees everything and carries NO positional embedding;
- ``h = x + a W_o``; ``z = RMSNorm(h)``;
- ``f = sum over the chosen e of g_e W_down,e (relu(W_gate,e z) * (W_up,e
  z))``: ReGLU experts ``moe_ffn_hidden_size`` wide, no shared expert;
- ``x <- h + f``; after the last layer an RMSNorm and an untied head.

The parameters exist ONCE, laid out as the serving engine scans them: for
each place of the layer pattern's period one set of ``[periods, ...]``
stacks, the place's expert banks unstacked (one ``[E, ...]`` array a layer:
``decoder_spec.EXPERT_BANKS``; the grouped GEMMs read a whole array where it
lies).
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.layer import Layer, LayerList
from ..ops._prim import apply_op
from .cohere2_moe import _adopt, _ones
from .decoder_spec import DecoderSpec, LayerKind, MoeSpec
from .llama import _model_init, _rope_cos_sin, _scaled_init
from .sarvam_mla import _Layers


@dataclass
class SmallThinkerConfig:
    """The source's own keys (``config.json`` of a SmallThinker model), with
    the sizes of SmallThinker-21BA3B-Instruct as defaults;
    ``num_hidden_layers`` is what is held here."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1500000.0
    rope_scaling: Optional[dict] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    sliding_window_size: int = 4096
    moe_ffn_hidden_size: int = 768             # one expert's width
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 16384
    dtype: str = "bfloat16"
    # not keys of the source: how entries reach the experts, and the rows of
    # one expert's tile in the grouped GEMM (as the other families of many
    # narrow experts: a step's entries spread a few dozen an expert, and
    # every expert owns at least one tile)
    moe_dispatch: str = "grouped"
    moe_block_m: int = 128

    def __post_init__(self):
        L = self.num_hidden_layers
        for key in ("rope_layout", "sliding_window_layout"):
            layout = getattr(self, key)
            if layout is None:      # a full layer without positions, then
                layout = [int(l % 4 != 0) for l in range(L)]    # three windowed
            layout = tuple(int(v) for v in layout)[:L]
            if len(layout) != L:
                raise ValueError(f"smallthinker: {key} names {len(layout)} "
                                 f"layers, num_hidden_layers is {L}")
            setattr(self, key, layout)
        # what this model file does not compute is refused, not ignored
        if not self.moe_primary_router_apply_softmax:
            raise ValueError(
                "smallthinker: moe_primary_router_apply_softmax=False (a "
                "router whose gates are not a softmax) is not supported")
        if not self.norm_topk_prob:
            raise ValueError(
                "smallthinker: norm_topk_prob=False (the chosen experts' "
                "softmax scores left unnormalised) is not supported")
        if self.rope_scaling is not None:
            raise ValueError(
                f"smallthinker: rope_scaling={self.rope_scaling!r} is not "
                "supported (only null: the plain rotary frequencies)")
        if self.tie_word_embeddings:
            raise ValueError(
                "smallthinker: tie_word_embeddings=True is not supported "
                "(the head is an array of its own)")
        if self.rope_layout != self.sliding_window_layout:
            l = next(i for i in range(L) if self.rope_layout[i]
                     != self.sliding_window_layout[i])
            raise ValueError(
                f"smallthinker: layer {l} has rope_layout "
                f"{self.rope_layout[l]} and sliding_window_layout "
                f"{self.sliding_window_layout[l]}: only windowed layers "
                "that rotate and full layers without positions are served")
        p = self.period()
        if L % p:
            raise ValueError(
                f"smallthinker: the layouts repeat every {p} layers and "
                f"num_hidden_layers {L} is not whole periods of them")

    @classmethod
    def from_source(cls, source: dict, **over) -> "SmallThinkerConfig":
        """From the model's published ``config.json`` keys (others are
        ignored: they say nothing this file computes), ``over`` on top.
        The two layouts are cut to the depth held."""
        known = {f.name for f in fields(cls)}
        kw = {k: v for k, v in source.items() if k in known
              and (v is not None or k == "rope_scaling")}
        if "torch_dtype" in source:
            kw["dtype"] = source["torch_dtype"]
        kw.update(over)
        return cls(**kw)

    @staticmethod
    def tiny(**kw) -> "SmallThinkerConfig":
        """Test size, with what is distinctive kept: a query group that is
        no power of two (6 heads over 2), 8 experts of which 3 are chosen,
        a window of 48, two periods of (full, window, window, window)."""
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=8,
                    num_attention_heads=6, num_key_value_heads=2,
                    head_dim=32, sliding_window_size=48,
                    moe_ffn_hidden_size=32, moe_num_primary_experts=8,
                    moe_num_active_primary_experts=3,
                    max_position_embeddings=256, dtype="float32",
                    moe_block_m=8)
        base.update(kw)
        return SmallThinkerConfig(**base)

    @staticmethod
    def smallthinker_21b(depth: int = 8, **kw) -> "SmallThinkerConfig":
        """SmallThinker-21BA3B-Instruct as published but for its depth:
        ``depth`` of its 52 layers, whole periods of four (52: 21.5 B
        parameters, more than a chip holds; 8: the first of seven pipeline
        stages with embedding and head, 7.93 GB)."""
        return SmallThinkerConfig(num_hidden_layers=depth, **kw)

    # ---- the layer pattern ----
    def period(self) -> int:
        """The least number of layers after which the layouts repeat (the
        depth itself where they do not: ``__post_init__`` holds the depth
        to whole periods)."""
        L, layout = self.num_hidden_layers, self.sliding_window_layout
        return next((p for p in range(1, L + 1) if all(
            layout[l] == layout[l % p] for l in range(L))), L)

    def pattern(self) -> Tuple[LayerKind, ...]:
        return tuple(
            LayerKind(window=int(self.sliding_window_size), rope=True)
            if windowed else LayerKind(window=None, rope=False)
            for windowed in self.sliding_window_layout[:self.period()])

    def moe_spec(self) -> MoeSpec:
        return MoeSpec(
            num_experts=self.moe_num_primary_experts,
            top_k=self.moe_num_active_primary_experts, score="softmax",
            dispatch="grouped" if self.moe_dispatch == "grouped" else "dense",
            block_m=self.moe_block_m, router_input="attention",
            activation="relu")


def layer_leaves(c: SmallThinkerConfig) -> list:
    """One layer's parameters as ``(name, shape, initializer, dtype)``, in
    ``sarvam_mla.layer_leaves``' form (``_Layers`` makes a place of them)."""
    H, I, E = c.hidden_size, c.moe_ffn_hidden_size, c.moe_num_primary_experts
    q, kv = c.num_attention_heads * c.head_dim, \
        c.num_key_value_heads * c.head_dim
    dt = c.dtype
    return [("self_attn.q_proj.weight", (H, q), _scaled_init(H), dt),
            ("self_attn.k_proj.weight", (H, kv), _scaled_init(H), dt),
            ("self_attn.v_proj.weight", (H, kv), _scaled_init(H), dt),
            ("self_attn.o_proj.weight", (q, H), _scaled_init(q), dt),
            ("input_layernorm.weight", (H,), _ones, dt),
            ("post_attention_layernorm.weight", (H,), _ones, dt),
            ("mlp.gate.weight", (H, E), _scaled_init(H), dt),
            ("mlp.experts_gate", (E, H, I), _scaled_init(H), dt),
            ("mlp.experts_up", (E, H, I), _scaled_init(H), dt),
            ("mlp.experts_down", (E, I, H), _scaled_init(I), dt)]


class SmallThinkerForCausalLM(Layer):
    """The model; ``ContinuousBatchingEngine(model, ...)`` takes it as it
    takes the other families.  ``params`` (the layout of
    ``serving_params()``): a caller's own arrays, adopted as the model's
    parameters instead of drawing random ones, so that a build holds the
    weights once."""

    @_model_init("smallthinker")
    def __init__(self, config: SmallThinkerConfig,
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
            _Layers(c, False, periods, given, leaves=layer_leaves(c))
            for given in places])

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
        return apply_op("smallthinker_forward",
                        lambda ids: _forward(spec, params, ids), (input_ids,))


def _forward(spec: DecoderSpec, params: dict, ids):
    """ids [b, s] -> float32 logits [b, s, V]: dense masked attention, the
    serving path's own router and experts (``generation._moe_choice`` on
    ``u``, ``generation._moe_experts`` on ``z``)."""
    from ..inference.generation import _moe_choice, _moe_experts, _rope_bt
    from ..kernels.rms_norm import rms_norm_fp32

    b, s = ids.shape
    g = spec.num_heads // spec.num_kv_heads
    cos, sin = _rope_cos_sin(s, spec.head_dim, spec.rope_theta, jnp.float32)
    cos, sin = (jnp.broadcast_to(t[None], (b,) + t.shape) for t in (cos, sin))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    x = jnp.take(params["embed"], ids, axis=0)
    for r in range(spec.periods):
        for kind, place in zip(spec.pattern, params["blocks"]):
            lp = {k: v[r] for k, v in place.items()}
            u = rms_norm_fp32(x, lp["input_layernorm.weight"], spec.norm_eps)
            choice = _moe_choice(u, lp, spec.moe)
            q = (u @ lp["self_attn.q_proj.weight"]).reshape(
                b, s, spec.num_heads, spec.head_dim)
            k = (u @ lp["self_attn.k_proj.weight"]).reshape(
                b, s, spec.num_kv_heads, spec.head_dim)
            v = (u @ lp["self_attn.v_proj.weight"]).reshape(
                b, s, spec.num_kv_heads, spec.head_dim)
            if kind.rope:
                q, k = _rope_bt(q, cos, sin), _rope_bt(k, cos, sin)
            seen = j <= i
            if kind.window is not None:
                seen = jnp.logical_and(seen, i - j < kind.window)
            qg = q.reshape(b, s, spec.num_kv_heads, g, spec.head_dim)
            sc = jnp.einsum("bikgd,bjkd->bkgij", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) / spec.head_dim ** 0.5
            p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
            a = jnp.einsum("bkgij,bjkd->bikgd", p, v.astype(jnp.float32))
            x = x + a.reshape(b, s, -1).astype(x.dtype) \
                @ lp["self_attn.o_proj.weight"]
            z = rms_norm_fp32(x, lp["post_attention_layernorm.weight"],
                              spec.norm_eps)
            x = x + _moe_experts(z, lp, spec.moe, choice)[0]
    h = rms_norm_fp32(x, params["norm"], spec.norm_eps)
    return (h @ params["head"]).astype(jnp.float32)
