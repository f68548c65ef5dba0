"""Cohere2-MoE decoders (``model_type: cohere2_moe``, e.g. Command A+): a
stack whose layers are of more than one kind.

One layer, ``x`` the residual stream (every layer alike but for the kind of
its attention):

- ``u = LayerNorm(x)``: mean subtracted, variance normalised, a weight and
  no bias; ONE norm a layer;
- ``a = Attn(u)``: grouped-query attention, ``head_dim`` stated (not
  ``hidden / heads``), no bias, no q/k norm.  A ``sliding_attention`` layer
  rotates the interleaved pairs ``(2i, 2i+1)`` (``rope_gptj``) and sees the
  last ``sliding_window`` keys; a ``full_attention`` layer sees everything
  and carries NO positional embedding;
- ``f = MoE(u)``: sigmoid router scores in float32 over ``num_experts``,
  the ``num_experts_per_tok`` largest renormalised to sum to one, SwiGLU
  experts; plus the mean (``average``) of ``num_shared_experts`` SwiGLU
  experts every token passes;
- ``x <- x + a + f`` (``use_parallel_block``: attention and experts read
  the same ``u``);
- after the last layer a LayerNorm; logits ``h E^T`` (``logit_scale`` 1)
  with the embedding ``E`` (tied).

The parameters exist ONCE, made as the stacks the serving engine scans: for
each place of the layer pattern's period one set of ``[periods, ...]``
arrays (``serving_params()`` hands out these very arrays).  A chip that
holds a share of the experts (``experts_held`` of ``num_experts``, from
``expert_offset``) keeps the router at its published width and only its own
experts' banks; what the other experts would add is left out.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn.layer import Layer, LayerList
from ..ops._prim import apply_op
from .decoder_spec import DecoderSpec, LayerKind, MoeSpec
from .llama import _model_init, _rope_cos_sin, _scaled_init


@dataclass
class Cohere2MoeConfig:
    """The source's own keys (``config.json`` of a ``cohere2_moe`` model),
    with the sizes of Command A+ as defaults; ``vocab_size`` and
    ``num_hidden_layers`` are what is held here."""
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096          # one expert's width
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rotary_pct: float = 1.0
    position_embedding_type: str = "rope_gptj"
    sliding_window: int = 4096
    layer_switch: int = 4
    layer_types: Optional[Tuple[str, ...]] = None
    num_experts: int = 128                 # the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    shared_expert_combination_strategy: str = "average"
    expert_selection_fn: str = "sigmoid"
    norm_topk_prob: bool = True
    first_k_dense_replace: int = 0
    use_parallel_block: bool = True
    use_qk_norm: bool = False
    attention_bias: bool = False
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    logit_scale: float = 1.0
    max_position_embeddings: int = 200000
    dtype: str = "bfloat16"
    # this chip's share of each layer's experts: ``experts_held`` of them
    # from ``expert_offset`` on (None: all); not keys of the source
    experts_held: Optional[int] = None
    expert_offset: int = 0
    moe_dispatch: str = "grouped"
    # rows of one expert's tile in the grouped GEMM.  128, not the Llama
    # family's 512: a step's entries spread over many narrow experts (a few
    # dozen each), and every expert owns at least one tile
    moe_block_m: int = 128

    def __post_init__(self):
        if self.layer_types is None:
            # layer_switch - 1 sliding layers, then a full one
            # (order_of_interleaved_layers: local_attn_first)
            self.layer_types = tuple(
                "full_attention" if (l + 1) % self.layer_switch == 0
                else "sliding_attention"
                for l in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)[:self.num_hidden_layers]
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        if self.experts_held is None:
            self.experts_held = self.num_experts
        # what this model file does not compute is refused, not ignored
        for key, want in (("use_qk_norm", False), ("attention_bias", False),
                          ("rotary_pct", 1.0), ("first_k_dense_replace", 0),
                          ("hidden_act", "silu"),
                          # the interleaved pairs (2i, 2i+1)
                          ("position_embedding_type", "rope_gptj"),
                          ("shared_expert_combination_strategy", "average"),
                          ("norm_topk_prob", True), ("logit_scale", 1.0)):
            if getattr(self, key) != want:
                raise ValueError(f"cohere2_moe: {key}={getattr(self, key)!r} "
                                 f"is not supported (only {want!r})")

    @classmethod
    def from_source(cls, source: dict, **over) -> "Cohere2MoeConfig":
        """From the model's published ``config.json`` keys (others are
        ignored: they say nothing this file computes), ``over`` on top."""
        known = {f.name for f in fields(cls)}
        kw = {k: v for k, v in source.items() if k in known and v is not None}
        if "torch_dtype" in source:
            kw["dtype"] = source["torch_dtype"]
        kw.update(over)
        return cls(**kw)

    @staticmethod
    def tiny(**kw) -> "Cohere2MoeConfig":
        """Test size: a period of two (one sliding layer of window 24, one
        full), 8 experts of which 4 are chosen, two shared experts."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                    num_hidden_layers=4, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=32, sliding_window=24,
                    layer_switch=2, num_experts=8, num_experts_per_tok=4,
                    num_shared_experts=2, max_position_embeddings=256,
                    dtype="float32", moe_block_m=8)
        base.update(kw)
        return Cohere2MoeConfig(**base)

    @staticmethod
    def command_a_plus(**kw) -> "Cohere2MoeConfig":
        """command-a-plus-05-2026 as published (218 B parameters: far more
        than one chip holds; a deployment gives each chip a share)."""
        return Cohere2MoeConfig(**kw)

    # ---- the layer pattern ----
    def period(self) -> int:
        """The least number of layers after which ``layer_types`` repeats."""
        L = self.num_hidden_layers
        for p in range(1, L + 1):
            if L % p == 0 and all(
                    self.layer_types[l] == self.layer_types[l % p]
                    for l in range(L)):
                return p
        return L

    def pattern(self) -> Tuple[LayerKind, ...]:
        return tuple(
            LayerKind(window=int(self.sliding_window), rope=True)
            if t == "sliding_attention" else LayerKind(window=None, rope=False)
            for t in self.layer_types[:self.period()])

    def moe_spec(self) -> MoeSpec:
        return MoeSpec(
            num_experts=self.num_experts, top_k=self.num_experts_per_tok,
            score=self.expert_selection_fn,
            held=self.experts_held, offset=self.expert_offset,
            shared=self.num_shared_experts,
            dispatch="grouped" if self.moe_dispatch == "grouped" else "dense",
            block_m=self.moe_block_m)


def _ones(shape, dtype):
    return jnp.ones(shape, dtype)


def _adopt(arrays: dict, name: str, dtype):
    """An initializer that hands out the caller's own array ``name`` (no
    copy), checked against the shape and type the model states."""
    def init(shape, dt):
        a = arrays[name]
        if tuple(a.shape) != tuple(shape) or a.dtype != jnp.dtype(dtype):
            raise ValueError(
                f"{name}: given {a.dtype}{tuple(a.shape)}, the model "
                f"states {jnp.dtype(dtype)}{tuple(shape)}")
        return a
    return init


class _StackedPlace(Layer):
    """The parameters of ONE place of the layer pattern, for all periods at
    once: every array is ``[periods, ...]`` (the engine scans over them)."""

    def __init__(self, c: Cohere2MoeConfig, given: Optional[dict]):
        super().__init__(dtype=c.dtype)
        n = c.num_hidden_layers // c.period()
        H, I, S = c.hidden_size, c.intermediate_size, c.num_shared_experts
        q, kv = c.num_attention_heads * c.head_dim, \
            c.num_key_value_heads * c.head_dim
        held = c.experts_held
        for name, shape, make in (
                ("self_attn.q_proj.weight", (H, q), _scaled_init(H)),
                ("self_attn.k_proj.weight", (H, kv), _scaled_init(H)),
                ("self_attn.v_proj.weight", (H, kv), _scaled_init(H)),
                ("self_attn.o_proj.weight", (q, H), _scaled_init(q)),
                ("input_layernorm.weight", (H,), _ones),
                ("mlp.gate.weight", (H, c.num_experts), _scaled_init(H)),
                ("mlp.experts_gate", (held, H, I), _scaled_init(H)),
                ("mlp.experts_up", (held, H, I), _scaled_init(H)),
                ("mlp.experts_down", (held, I, H), _scaled_init(I)),
                # the shared experts side by side along their width
                ("mlp.shared_gate_proj.weight", (H, S * I), _scaled_init(H)),
                ("mlp.shared_up_proj.weight", (H, S * I), _scaled_init(H)),
                ("mlp.shared_down_proj.weight", (S * I, H),
                 _scaled_init(I))):
            if S == 0 and name.startswith("mlp.shared_"):
                continue
            if given is not None:
                make = _adopt(given, name, c.dtype)
            self.add_parameter(name, self.create_parameter(
                [n, *shape], default_initializer=make))

    def arrays(self) -> dict:
        return {name: p._data for name, p in self._parameters.items()}


class CohereMoeForCausalLM(Layer):
    """The model; ``ContinuousBatchingEngine(model, ...)`` takes it as it
    takes a ``LlamaForCausalLM``.  ``params`` (the layout of
    ``serving_params()``): a caller's own arrays, adopted as the model's
    parameters instead of drawing random ones, so that a build holds the
    weights once and never a second, discarded set."""

    @_model_init("cohere2_moe")
    def __init__(self, config: Cohere2MoeConfig,
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
        self.lm_head = None if c.tie_word_embeddings else \
            self.create_parameter([c.hidden_size, c.vocab_size],
                                  default_initializer=init("head", scaled))
        places = [None] * c.period() if params is None else params["blocks"]
        if len(places) != c.period():
            raise ValueError(f"params has {len(places)} block stacks, the "
                             f"layer pattern {c.period()} places")
        self.blocks = LayerList([_StackedPlace(c, given) for given in places])

    # ---- what the serving engine asks of a model (decoder_spec.py) ----
    def decoder_spec(self) -> DecoderSpec:
        c = self.config
        return DecoderSpec(
            pattern=c.pattern(), periods=c.num_hidden_layers // c.period(),
            num_heads=c.num_attention_heads,
            num_kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
            norm="layer", norm_eps=c.layer_norm_eps,
            parallel_block=c.use_parallel_block, rope_theta=c.rope_theta,
            moe=c.moe_spec())

    def serving_params(self) -> dict:
        """The parameters themselves (no copy): one dict of stacks a place;
        a tied head is the embedding, not a transposed second array."""
        out = {"embed": self.embed_tokens._data, "norm": self.norm._data,
               "blocks": tuple(b.arrays() for b in self.blocks)}
        if self.lm_head is not None:
            out["head"] = self.lm_head._data
        return out

    # ---- the whole sequence at once (no cache): evaluation, tests ----
    def forward(self, input_ids):
        spec = self.decoder_spec()
        params = self.serving_params()
        return apply_op("cohere2_moe_forward",
                        lambda ids: _forward(spec, params, ids), (input_ids,))


def _forward(spec: DecoderSpec, params: dict, ids):
    """ids [b, s] -> float32 logits [b, s, V]: dense masked attention, the
    serving path's own expert mixture (``generation._moe_ffn``)."""
    from ..inference.generation import _moe_ffn, _rope_bt
    from ..kernels.rms_norm import layer_norm_fp32, rms_norm_fp32

    norm = rms_norm_fp32 if spec.norm == "rms" else layer_norm_fp32
    b, s = ids.shape
    g = spec.num_heads // spec.num_kv_heads
    cos, sin = _rope_cos_sin(s, spec.head_dim, spec.rope_theta, jnp.float32)
    cos, sin = (jnp.broadcast_to(t[None], (b,) + t.shape) for t in (cos, sin))
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    x = jnp.take(params["embed"], ids, axis=0)
    for r in range(spec.periods):
        for kind, stack in zip(spec.pattern, params["blocks"]):
            lp = {k: v[r] for k, v in stack.items()}
            u = norm(x, lp["input_layernorm.weight"], spec.norm_eps)
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
            a = a.reshape(b, s, -1).astype(x.dtype) \
                @ lp["self_attn.o_proj.weight"]
            if not spec.parallel_block:
                x = x + a
                u = norm(x, lp["post_attention_layernorm.weight"],
                         spec.norm_eps)
            f, _ = _moe_ffn(u, lp, spec.moe)
            x = x + a + f if spec.parallel_block else x + f
    h = norm(x, params["norm"], spec.norm_eps)
    head = params["head"] if "head" in params else params["embed"].T
    return (h @ head).astype(jnp.float32)
