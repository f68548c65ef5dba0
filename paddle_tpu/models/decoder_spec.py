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

A stack may open with layers of another shape than the period's
(``leading``: a dense MLP before the expert layers): they run once, unrolled,
before the scan, and ``serving_params()["leading"]`` is one dict a layer,
its arrays without a layer axis.

A place's expert banks (``EXPERT_BANKS``) may come unstacked instead: a tuple
of ``periods`` arrays ``[E, ...]``, one a layer.  The grouped GEMMs that read
them are custom calls, for which a layer sliced out of a stack is written
out first (a copy of the bank a layer a step); a whole array is read where
it lies, and the engine picks the layer's by the period's number.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

# a MoE layer's expert banks in ``serving_params()``, [E, H, I] twice and
# [E, I, H]: what the grouped GEMMs read whole
EXPERT_BANKS = ("mlp.experts_gate", "mlp.experts_up", "mlp.experts_down")


@dataclass(frozen=True)
class LatentAttn:
    """Latent attention (MLA): the cache holds ONE row a token a layer,
    ``[c | k_r]``: the compressed key/value ``c`` (``rank`` wide, RMS-normed)
    and one rotary key ``k_r`` (``rope`` wide) that every head shares.  A
    query head is ``[q_nope (nope) | q_rope (rope)]``; its key is
    ``[W_uk c | k_r]``, its value ``W_uv c`` (``value`` wide).  The engine
    absorbs ``W_uk`` into the query and applies ``W_uv`` after the call, so
    the kernel sees ``num_heads`` query heads over one row of the pool, the
    value being the first ``rank`` of the key."""
    rank: int
    nope: int
    rope: int
    value: int

    @property
    def row(self) -> int:
        """Numbers a cached token holds in one layer."""
        return self.rank + self.rope


@dataclass(frozen=True)
class LayerKind:
    """One place in the layer pattern: what its attention sees, how its
    positions are embedded, and what its FFN is."""
    window: Optional[int] = None        # sliding attention: keys in (p - w, p]
    # rotary over the interleaved pairs (x[2i], x[2i+1]); False: the layer
    # carries no positional embedding
    rope: bool = True
    # None: per-head K/V pages (``num_kv_heads`` x ``head_dim``)
    latent: Optional[LatentAttn] = None
    # a dense gated MLP (its width is its weights') even where the spec
    # states a ``moe``
    dense_ffn: bool = False


@dataclass(frozen=True)
class RopeYarn:
    """``deepseek_yarn`` rotary scaling: the frequencies are blended between
    ``theta^(-2i/d)`` and that over ``factor`` by a linear ramp between the
    two correction dimensions; the softmax scale is multiplied by
    ``mscale(factor, mscale_all_dim)^2`` and cos/sin by
    ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    factor: float
    original: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _mscale(factor: float, m: float) -> float:
        return 1.0 if factor <= 1 or not m else \
            0.1 * m * math.log(factor) + 1.0

    @property
    def table_scale(self) -> float:
        """What cos and sin are multiplied by."""
        return self._mscale(self.factor, self.mscale) \
            / self._mscale(self.factor, self.mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        """What ``head_dim^-0.5`` is multiplied by."""
        return self._mscale(self.factor, self.mscale_all_dim) ** 2

    def inv_freq(self, dim: int, theta: float) -> np.ndarray:
        """float32 ``[dim / 2]`` blended inverse frequencies."""
        i = np.arange(0, dim, 2, dtype=np.float32)
        extra = 1.0 / theta ** (i / dim)            # below ``low``: as is
        inter = extra / self.factor                 # above ``high``: scaled

        def correction(rotations):
            return dim * math.log(self.original / (rotations * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(correction(self.beta_fast)), 0)
        high = min(math.ceil(correction(self.beta_slow)), dim - 1)
        ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


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
    # the experts chosen are those with the largest ``score + bias``
    # (``lp["mlp.gate.bias"]``, float32 ``[num_experts]``); the bias
    # selects and is not in the gate
    select_bias: bool = False
    gate_scale: float = 1.0             # the normalised gates times this

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
    # layers that run once, unrolled, before the scan of periods
    leading: Tuple[LayerKind, ...] = ()
    rope_yarn: Optional[RopeYarn] = None

    def __post_init__(self):
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"norm {self.norm!r}")
        kinds = {k.latent for k in self.leading + self.pattern}
        if len(kinds) != 1:
            raise ValueError("one pool serves every layer: latent and "
                             "per-head attention cannot share a stack")
        if self.latent is not None and self.parallel_block:
            raise ValueError("latent attention is served with sequential "
                             "residuals only")

    @property
    def num_layers(self) -> int:
        return len(self.leading) + self.periods * len(self.pattern)

    @property
    def latent(self) -> Optional[LatentAttn]:
        """The stack's latent attention (every layer's alike), or None."""
        return self.pattern[0].latent

    @property
    def softmax_scale(self) -> float:
        """What the scores are multiplied by: ``1/sqrt`` of the query
        head's width, times the yarn factor where one is stated."""
        la = self.latent
        d = self.head_dim if la is None else la.nope + la.rope
        return d ** -0.5 * (1.0 if self.rope_yarn is None
                            else self.rope_yarn.softmax_scale)

    def rope_tables(self, seq_len: int) -> Tuple[np.ndarray, np.ndarray]:
        """float32 ``(cos, sin)``, each ``[seq_len, d / 2]``, over the part
        of a head that rotates (a latent head's ``rope`` numbers, else the
        whole head), with the yarn blend and its factor where stated."""
        dim = self.head_dim if self.latent is None else self.latent.rope
        yarn = self.rope_yarn
        inv = 1.0 / self.rope_theta ** (
            np.arange(0, dim, 2, dtype=np.float32) / dim) \
            if yarn is None else yarn.inv_freq(dim, self.rope_theta)
        freqs = np.outer(np.arange(seq_len, dtype=np.float32), inv)
        k = 1.0 if yarn is None else yarn.table_scale
        return (np.cos(freqs) * k).astype(np.float32), \
            (np.sin(freqs) * k).astype(np.float32)

    @property
    def windows(self) -> Tuple[Optional[int], ...]:
        """The window of every layer of the stack, in order."""
        return tuple(k.window for k in self.leading) \
            + tuple(k.window for k in self.pattern) * self.periods
