"""What the serving engine needs to know of a decoder, as data.

``inference/generation.py`` runs ONE step function over every decoder
family.  A model hands it a ``DecoderSpec`` (``model.decoder_spec()``) and
its parameters laid out for the engine's scan (``model.serving_params()``);
nothing in the engine asks what class the model is.

The stack is ``periods`` repetitions of ``pattern`` (one ``LayerKind`` per
place in a period): the engine scans over whole periods with the period's
layers unrolled inside, and ``serving_params()["blocks"]`` is one dict of
``[periods, ...]`` stacks for each place.  A stack of identical layers (the
Llama family) is the period of one.

A place's expert banks (``EXPERT_BANKS``) may come unstacked instead: a tuple
of ``periods`` arrays ``[E, ...]``, one a layer.  The grouped GEMMs that read
them are custom calls, for which a layer sliced out of a stack is written
out first (a copy of the bank a layer a step); a whole array is read where
it lies, and the engine picks the layer's by the period's number.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

# a MoE layer's expert banks in ``serving_params()``, [E, H, I] twice and
# [E, I, H]: what the grouped GEMMs read whole
EXPERT_BANKS = ("mlp.experts_gate", "mlp.experts_up", "mlp.experts_down")


@dataclass(frozen=True)
class LayerKind:
    """One place in the layer pattern: what its attention sees and how its
    positions are embedded."""
    window: Optional[int] = None        # sliding attention: keys in (p - w, p]
    # rotary over the interleaved pairs (x[2i], x[2i+1]); False: the layer
    # carries no positional embedding
    rope: bool = True


@dataclass(frozen=True)
class MoeSpec:
    """A routed expert mixture.  ``num_experts`` is the router's width (the
    published count); this chip holds experts ``[offset, offset + held)``
    and computes their part of the result: entries routed elsewhere are
    left out, their gates keep the value they have over all ``top_k``
    (the chosen scores divided by their sum)."""
    num_experts: int
    top_k: int
    score: str = "softmax"              # or "sigmoid"; scores in float32
    held: Optional[int] = None          # None: all of them
    offset: int = 0
    shared: int = 0                     # dense experts every token passes,
    #                                     their outputs averaged
    dispatch: str = "dense"             # or "grouped" (expert-sorted GEMM)
    block_m: int = 128

    def __post_init__(self):
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"router score {self.score!r}")
        held = self.num_experts if self.held is None else self.held
        object.__setattr__(self, "held", held)
        if not 0 < held <= self.num_experts or self.offset < 0 \
                or self.offset + held > self.num_experts:
            raise ValueError(
                f"experts held [{self.offset}, {self.offset + held}) lie "
                f"outside the router's {self.num_experts}")

    @property
    def partial(self) -> bool:
        """Whether this chip holds a share of the experts, not all."""
        return self.held < self.num_experts


@dataclass(frozen=True)
class DecoderSpec:
    pattern: Tuple[LayerKind, ...]
    periods: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    norm: str = "rms"                   # or "layer": mean subtracted, no bias
    norm_eps: float = 1e-5
    # parallel: x + attn(u) + ffn(u) from ONE norm u; else the sequential
    # pre-norm residuals with a second norm before the FFN
    parallel_block: bool = False
    rope_theta: float = 10000.0
    moe: Optional[MoeSpec] = None       # None: a dense gated MLP

    def __post_init__(self):
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"norm {self.norm!r}")

    @property
    def num_layers(self) -> int:
        return self.periods * len(self.pattern)

    @property
    def windows(self) -> Tuple[Optional[int], ...]:
        """The window of every layer of the stack, in order."""
        return tuple(k.window for k in self.pattern) * self.periods
