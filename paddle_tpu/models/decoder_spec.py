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

A ``MoeSpec`` states the router (its width, ``top_k``, softmax or sigmoid
scores, a selecting bias, a gate scale, a group-limited choice), the experts
held here (``held``, ``offset``) and the shared ones, how entries reach the
experts (``dispatch``, ``block_m``), which tensor the router reads
(``router_input``: the FFN's normed input, or the attention's) and the
experts' gated activation (``activation``: SiLU or ReLU).

A place's token mixer may be a recurrence INSTEAD of attention
(``LayerKind.linear``, a ``DeltaMixer``): such a place keeps no pages, only a
fixed state a slot, and ``DecoderSpec.page_places`` / ``state_places`` say
which places of a period hold which.

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
    value being the first ``rank`` of the key.

    ``q_rank``: the query goes through a latent of its own, ``c_q =
    RMSNorm(W_dq y)`` (``q_rank`` wide), and the heads are made from it
    (``W_uq c_q``); None: ``W_q y`` directly."""
    rank: int
    nope: int
    rope: int
    value: int
    q_rank: Optional[int] = None

    @property
    def row(self) -> int:
        """Numbers a cached token holds in one layer."""
        return self.rank + self.rope


@dataclass(frozen=True)
class LatentIndex:
    """A learned index over a latent pool (sparse attention): a place keeps
    ONE index key a token a layer, ``k_i = LayerNorm(W_ik y)`` (``dim``
    wide, weight and bias, rotary on its first ``rope`` numbers), cached
    beside ``[c | k_r]``; a query token has ``heads`` index queries ``q_i,j
    = W_iq,j c_q`` (from the query latent, rotary on the first ``rope``)
    and ``heads`` weights ``w = W_iw y``.  ``I(t, s) = sum_j w_t,j
    ReLU(q_i,t,j . k_i,s)`` in float32; the attention's softmax of query
    token ``t`` runs over the ``min(t + 1, top_k)`` positions ``s <= t``
    with the largest ``I(t, s)`` alone (a tie at the edge goes to the
    lower position)."""
    heads: int
    dim: int
    rope: int
    top_k: int


@dataclass(frozen=True)
class SsmMixer:
    """A state-space mixer (Mamba-2) that runs BESIDE a place's attention,
    from the same norm ``u``: ``[z | xBC | dt] = W_in (u x in_scale)`` with
    the five zones ``z, x, B, C, dt`` scaled by ``zone_scales``; a causal
    depthwise convolution of ``conv`` taps with bias over ``xBC`` (so a
    slot carries its last ``conv - 1`` rows), SiLU; per head the recurrence
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D
    x_t`` with ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``
    (``kernels/ssd.py``; ``heads`` of ``head_dim`` over a state ``state``
    wide, ``B`` and ``C`` shared by the heads of each of ``groups``);
    ``y * silu(z)`` RMS-normed over each group's ``heads / groups x
    head_dim`` numbers with a learned weight; ``out_scale x W_out``.

    What a slot holds for it besides pages: the float32 state ``[heads,
    head_dim, state]`` and the ``conv - 1`` rows, a layer."""
    heads: int
    head_dim: int
    state: int
    groups: int
    conv: int
    in_scale: float = 1.0
    zone_scales: Tuple[float, float, float, float, float] = (1.0,) * 5
    out_scale: float = 1.0

    def __post_init__(self):
        if self.heads % self.groups:
            raise ValueError(f"mixer heads ({self.heads}) are not a whole "
                             f"number a group ({self.groups})")

    @property
    def inner(self) -> int:
        """Width of ``x`` (and of ``z``, and of the mixer's output)."""
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Width of what is convolved: ``[x | B | C]``."""
        return self.inner + 2 * self.groups * self.state

    @property
    def in_width(self) -> int:
        return self.inner + self.conv_width + self.heads

    @property
    def state_shape(self) -> Tuple[int, int, int]:
        """One slot's float32 state in one layer."""
        return self.heads, self.head_dim, self.state

    def state_bytes(self, dtype) -> int:
        """Bytes a slot holds for ONE layer: the float32 state and the
        carried convolution rows in ``dtype``."""
        return 4 * self.heads * self.head_dim * self.state \
            + (self.conv - 1) * self.conv_width * np.dtype(dtype).itemsize


@dataclass(frozen=True)
class DeltaMixer:
    """Gated delta-rule linear attention with a decay a CHANNEL (Kimi Delta
    Attention) that is a place's token mixer IN ATTENTION'S STEAD, on the
    normed input ``u``: ``[q | k | v] = W_qkv u`` (``heads`` of ``key_dim``
    twice, of ``value_dim`` once), a causal depthwise convolution of
    ``conv`` taps without bias over all of it (so a slot carries its last
    ``conv - 1`` rows), SiLU; ``q`` and ``k`` L2-normalised a head, ``q``
    times ``key_dim^-0.5``; the log decay ``a_t = -exp(A_log[h]) x
    softplus(W_fb (W_fa u) + dt_bias)`` a channel of the key (through a
    rank of ``gate_rank``), ``beta_t = sigmoid(W_b u)`` a head, doubled
    with ``neg_eigval`` (the transition's eigenvalues then reach (-1, 1));
    per head the float32 state ``S`` (``key_dim x value_dim``), zero at
    position 0 (``kernels/kda.py``):

        S' = diag(exp(a_t)) S_{t-1}
        S_t = S' + beta_t k_t (v_t - S'^T k_t)^T        o_t = S_t^T q_t

    ``RMSNorm_head(o_t)`` (a learned weight of ``value_dim``) times
    ``sigmoid(W_gb (W_ga u))`` (through ``gate_rank`` too), then ``W_o``.

    What a slot holds for it, and it holds NO pages: the float32 state
    ``[heads, key_dim, value_dim]`` and the ``conv - 1`` rows, a layer."""
    heads: int
    key_dim: int
    value_dim: int
    conv: int
    gate_rank: int
    neg_eigval: bool = False

    @property
    def conv_width(self) -> int:
        """Width of what is convolved: ``[q | k | v]``."""
        return self.heads * (2 * self.key_dim + self.value_dim)

    @property
    def state_shape(self) -> Tuple[int, int, int]:
        """One slot's float32 state in one layer."""
        return self.heads, self.key_dim, self.value_dim

    def state_bytes(self, dtype) -> int:
        """Bytes a slot holds for ONE layer: the float32 state and the
        carried convolution rows in ``dtype``."""
        return 4 * self.heads * self.key_dim * self.value_dim \
            + (self.conv - 1) * self.conv_width * np.dtype(dtype).itemsize


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
    # a state-space mixer beside the attention: ``x + attn(u) + ssm(u)``
    ssm: Optional[SsmMixer] = None
    # a learned index over the latent pool: the attention reads the keys it
    # chooses alone
    index: Optional[LatentIndex] = None
    # the token mixer is this recurrence and NOT attention: the place has
    # no q/k/v pages (``window``, ``rope``, ``latent`` say nothing of it)
    linear: Optional[DeltaMixer] = None
    # per-head attention's result times ``sigmoid(W_g u)``, elementwise,
    # before ``W_o``
    out_gate: bool = False


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
    def linear(self) -> Optional[DeltaMixer]:
        """The stack's linear-attention mixer (every linear place's alike),
        or None."""
        return next((k.linear for k in self.pattern
                     if k.linear is not None), None)

    @property
    def state_mixer(self):
        """The mixer whose state a slot holds besides its pages (a
        ``SsmMixer`` or a ``DeltaMixer``), or None: the stack has none."""
        return self.ssm if self.ssm is not None else self.linear

    @property
    def page_places(self) -> Tuple[int, ...]:
        """The places of a period that keep pages (all but the linear
        ones), in order."""
        return tuple(p for p, k in enumerate(self.pattern)
                     if k.linear is None)

    @property
    def state_places(self) -> Tuple[int, ...]:
        """The places of a period that keep a recurrent state, in order."""
        return tuple(p for p, k in enumerate(self.pattern)
                     if k.ssm is not None or k.linear is not None)

    @property
    def page_layers(self) -> int:
        """Layers that keep pages: what the pool's first axis counts."""
        return len(self.leading) + self.periods * len(self.page_places)

    @property
    def state_layers(self) -> int:
        """Layers that keep a recurrent state."""
        return self.periods * len(self.state_places)

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
    # group-limited choice: the experts lie in ``groups`` equal groups, a
    # group is ranked by the sum of its two largest ``score + bias``, and
    # the ``top_k`` are chosen inside the best ``groups_kept`` groups
    groups: Optional[int] = None
    groups_kept: Optional[int] = None
    # the tensor the router's logits are made from: "ffn", the normed input
    # of the FFN (what the experts read), or "attention", the normed input
    # of the layer's attention (the choice is made before the attention
    # call and handed past it; in a parallel block the two are one tensor)
    router_input: str = "ffn"
    # an expert is ``W_down (act(W_gate z) * (W_up z))``: "silu" or "relu"
    activation: str = "silu"

    def __post_init__(self):
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"router score {self.score!r}")
        if self.router_input not in ("ffn", "attention"):
            raise ValueError(
                f"router input {self.router_input!r}: the router reads the "
                "normed input of the \"ffn\" or of the \"attention\"")
        if self.activation not in ("silu", "relu"):
            raise ValueError(
                f"expert activation {self.activation!r}: \"silu\" or "
                "\"relu\"")
        if (self.groups is None) != (self.groups_kept is None):
            raise ValueError("groups and groups_kept are stated together")
        if self.groups is not None and (
                self.num_experts % self.groups
                or not 0 < self.groups_kept <= self.groups
                or self.groups_kept * (self.num_experts // self.groups)
                < self.top_k):
            raise ValueError(
                f"{self.num_experts} experts in {self.groups} groups of "
                f"which {self.groups_kept} are kept do not hold "
                f"{self.top_k} choices a token")
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
    # constant multipliers of a muP-parametrised model, each applied where
    # its name says (1.0: nothing is traced): the embedding's rows; the
    # attention's input, its keys and its output; the MLP's gate
    # pre-activation and its output; the logits
    embed_scale: float = 1.0
    attn_in_scale: float = 1.0
    key_scale: float = 1.0
    attn_out_scale: float = 1.0
    mlp_gate_scale: float = 1.0
    mlp_out_scale: float = 1.0
    logit_scale: float = 1.0

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
        if self.latent is not None and self.moe is not None \
                and self.moe.router_input == "attention":
            raise ValueError("a router that reads the attention's input is "
                             "served with per-head attention only")
        # the index keys are a plane of the pool: one shape for the stack
        if len({k.index for k in self.leading + self.pattern}) != 1:
            raise ValueError("one pool serves every layer: every place of "
                             "a stack has the same index or none")
        if self.index is not None and (self.latent is None
                                       or self.latent.q_rank is None):
            raise ValueError("a learned index reads a latent pool and the "
                             "query latent (LatentAttn.q_rank)")
        if self.index is not None and self.index.rope != self.latent.rope:
            raise ValueError("the index rotates as the attention's rotary "
                             "part does: one table serves both")
        # what a slot holds besides pages is one shape for the whole stack
        if len({k.ssm for k in self.leading + self.pattern}) != 1:
            raise ValueError("one recurrent state serves every layer: "
                             "layers with and without a state-space mixer "
                             "(or two shapes of one) cannot share a stack")
        if self.ssm is not None and (self.latent is not None
                                     or self.parallel_block or self.leading):
            raise ValueError("a state-space mixer is served beside per-head "
                             "attention in a scanned stack with sequential "
                             "residuals only")
        linear = {k.linear for k in self.leading + self.pattern} - {None}
        if len(linear) > 1 or (linear and self.ssm is not None):
            raise ValueError("one recurrent state serves every layer that "
                             "has one: two state shapes cannot share a "
                             "stack")
        if linear and (self.latent is not None or self.parallel_block
                       or any(k.linear is not None for k in self.leading)):
            raise ValueError("a linear-attention place is served among "
                             "per-head attention places in a scanned stack "
                             "with sequential residuals only: no latent "
                             "place beside it, no parallel block, not among "
                             "the leading layers")
        if linear and self.moe is not None \
                and self.moe.router_input == "attention":
            raise ValueError("a router that reads the attention's input is "
                             "served where every place attends: not beside "
                             "a linear-attention place")
        if linear and not self.page_places:
            raise ValueError("a stack of linear-attention places alone has "
                             "no pool to page: at least one place of the "
                             "period keeps pages")
        if any(k.out_gate and (k.latent is not None or k.linear is not None)
               for k in self.leading + self.pattern):
            raise ValueError("an output gate is served on per-head "
                             "attention only")

    @property
    def num_layers(self) -> int:
        return len(self.leading) + self.periods * len(self.pattern)

    @property
    def latent(self) -> Optional[LatentAttn]:
        """The stack's latent attention (every layer's alike), or None."""
        return self.pattern[0].latent

    @property
    def index(self) -> Optional[LatentIndex]:
        """The stack's learned index (every layer's alike), or None."""
        return self.pattern[0].index

    @property
    def ssm(self) -> Optional[SsmMixer]:
        """The stack's state-space mixer (every layer's alike), or None:
        whether a slot holds a recurrent state besides its pages."""
        return self.pattern[0].ssm

    @property
    def linear(self) -> Optional[DeltaMixer]:
        """The stack's linear-attention mixer (every linear place's alike),
        or None."""
        return next((k.linear for k in self.pattern
                     if k.linear is not None), None)

    @property
    def state_mixer(self):
        """The mixer whose state a slot holds besides its pages (a
        ``SsmMixer`` or a ``DeltaMixer``), or None: the stack has none."""
        return self.ssm if self.ssm is not None else self.linear

    @property
    def page_places(self) -> Tuple[int, ...]:
        """The places of a period that keep pages (all but the linear
        ones), in order."""
        return tuple(p for p, k in enumerate(self.pattern)
                     if k.linear is None)

    @property
    def state_places(self) -> Tuple[int, ...]:
        """The places of a period that keep a recurrent state, in order."""
        return tuple(p for p, k in enumerate(self.pattern)
                     if k.ssm is not None or k.linear is not None)

    @property
    def page_layers(self) -> int:
        """Layers that keep pages: what the pool's first axis counts."""
        return len(self.leading) + self.periods * len(self.page_places)

    @property
    def state_layers(self) -> int:
        """Layers that keep a recurrent state."""
        return self.periods * len(self.state_places)

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
        """The window of every layer that attends (a linear place reads no
        key), in order."""
        return tuple(k.window for k in self.leading) \
            + tuple(k.window for k in self.pattern
                    if k.linear is None) * self.periods
