"""Plain reference for the ``solar_open2`` family (Solar-Open2-250B): the
layer equations as the source's ``config.json`` and the catalog's
``described_as`` give them ("gated delta-rule linear (neg. eigenvalues,
conv4); softmax NoPE GQA 64Q/8KV, 48L 3:1; 320 experts, top-8, 1 shared"), in
``jax.numpy``, float32, matmul precision "highest"; the linear layers'
recurrence is the BARE RECURRENCE by ``lax.scan`` over TOKENS (never a chunk
form), softmax attention in blocks of queries, one expert at a time; no
kernel, no cache, no batching; it imports nothing of the program and takes
nothing the program made.

One layer, ``x`` the residual stream (pre-norm, sequential residuals), ``u =
RMSNorm(x)`` (eps ``rms_norm_eps``).  Layer ``l`` is a SOFTMAX layer where
``l`` is in ``gqa_layers``, else a LINEAR layer:

    linear (KDA)  H = linear_attn_config.num_heads, d = .head_dim
                  [q | k | v] = u W_qkv                      3 x H d
                  c_t = silu(sum_{j<taps} w_j [q|k|v]_{t-taps+1+j})
                      causal, depthwise, no bias, zeros before the sequence
                  q = l2(q) d^-0.5, k = l2(k)    a head; l2(a) = a /
                      sqrt(sum a^2 + 1e-6)
                  a_t = -exp(A_log[h]) softplus((u W_fa) W_fb + dt_bias)
                      the log decay a CHANNEL of the key, through rank d
                  beta_t = 2 sigmoid(u W_b)      a head (the 2:
                      kda_allow_neg_eigval)
                  S' = diag(exp(a_t)) S_{t-1}                S_0 = 0
                  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T   key x value
                  o_t = S_t^T q_t
                  y = (RMSNorm_head(o_t) * sigmoid((u W_ga) W_gb)) W_o
                      a learned weight of d, eps rms_norm_eps
    softmax       q = u W_q (num_attention_heads x head_dim), k, v
                      (num_key_value_heads); NO rotary, no other position
                  y = ((softmax_causal(q k^T / sqrt(head_dim)) v)
                       * sigmoid(u W_g)) W_o
    x <- x + y;   h = RMSNorm(x)
    experts       s = sigmoid(h W_r)     over n_routed_experts, float32
                  the num_experts_per_tok largest s + b are chosen
                  g_e = routed_scaling_factor x s_e / sum of the chosen s
                  f = sum_e g_e SwiGLU_e(h) + the shared expert's SwiGLU(h)
    x <- x + f

and after the last layer a final RMSNorm and an untied head.

Departures from the published description, each noted where it is made:
(1) ONE CHIP'S SHARE.  The model ``m`` this file is handed is
``Run.model``: ``m["n_routed_experts"]`` experts are HELD (of the router's
``m["published"]["n_routed_experts"]``), those from ``share.index x held``
on; the routed sum runs over the held experts only, what the others would
add is left out, exactly as the program leaves it out.  ``m["vocab_size"]``
is the part of the vocabulary held (number ``share.index % 8`` of the parts;
ids and logits are over it).  Without ``published`` (an uncut model) every
expert is held.
(2) ASSUMED (the config names the mixer by its ``kda_*`` keys and says no
more; the convention is Kimi Linear's KDA): the gate rank is
``linear_attn_config.head_dim``; SiLU after the convolutions and no bias in
them; the L2 norm on ``q`` and ``k`` and the ``d^-0.5`` on ``q``; ``A_log`` a
head and ``dt_bias`` a channel; the sigmoid output gate AFTER the per-head
RMS norm.
(3) ASSUMED: the softmax layer's gate is elementwise over the heads' outputs
and applied after the softmax, before ``W_o``.
(4) ASSUMED (the family's earlier ``solar_open`` router): sigmoid scores, a
selection bias that is not in the gate; ``hidden_act`` silu; the shared
expert's width ``n_shared_experts x moe_intermediate_size``.
(5) The three projections of a linear layer are stored as one ``[H, q | k |
v]`` array and the convolution as ``[taps, channels]``: a storage layout.

``precision="int8"`` is the CONTROL (the nearest precision below bf16), as
in ``references/llama.py``: both operands of every projection rounded to 8
bits along the contracted axis.  The convolution, the recurrence, the
norms, the router's scores and the attention products stay in float32 in
the control too.  The checks must refuse it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness.weights import Leaf
from chipbench.references.deepseek_v32 import held, vocab_part  # noqa: F401
from chipbench.references.falcon_h1 import _attend_block
from chipbench.references.llama import F32, HI, _mm, _rms, _swiglu

Q_BLOCK = 512        # queries a block of attention scores holds
PAD_TO = 512         # sequences are padded to a multiple: few shapes compile

SOFTMAX_LEAVES = ("self_attn.",)
LINEAR_LEAVES = ("linear_attn.",)


# ------------------------------------------------------------ leaves ----

def widths(m: dict) -> dict:
    """The linear mixer's sizes from the source's keys."""
    la = m["linear_attn_config"]
    heads, d = la["num_heads"], la["head_dim"]
    return {"heads": heads, "d": d, "inner": heads * d,
            "conv": 3 * heads * d, "taps": la["short_conv_kernel_size"],
            "rank": d}                                   # departure 2


def is_linear(m: dict, layer: int) -> bool:
    return layer not in m["gqa_layers"]


def leaf_specs(m: dict) -> list:
    """Every parameter of the model ``m``: name, per-layer shape, std of
    its normal draw.  Weights are [in, out]; the names are the program's.
    A layer is a softmax one (the ``self_attn.`` leaves) or a linear one
    (``linear_attn.``), never both; every layer has the norms and the
    ``mlp.`` leaves.  ``A_log`` and ``dt_bias`` are drawn wide, so that a
    layer's channels spread as a trained mixer's do: a log decay a token
    from under a thousandth (a state that remembers a thousand tokens) to
    over ten (one that forgets within a token)."""
    H, V, I = m["hidden_size"], m["vocab_size"], m["moe_intermediate_size"]
    hd = m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    w = widths(m)
    S = m["n_shared_experts"] * I                        # departure 4
    width, n, _ = held(m)
    sH, sI, sR = (1.0 / math.sqrt(H), 1.0 / math.sqrt(I),
                  1.0 / math.sqrt(w["rank"]))
    return [
        Leaf("self_attn.q_proj.weight", (H, q), True, sH),
        Leaf("self_attn.k_proj.weight", (H, kv), True, sH),
        Leaf("self_attn.v_proj.weight", (H, kv), True, sH),
        Leaf("self_attn.o_proj.weight", (q, H), True, 1.0 / math.sqrt(q)),
        Leaf("self_attn.g_proj.weight", (H, q), True, sH),
        Leaf("linear_attn.qkv_proj.weight", (H, w["conv"]), True, sH),
        Leaf("linear_attn.conv1d.weight", (w["taps"], w["conv"]), True,
             1.0 / math.sqrt(w["taps"])),
        Leaf("linear_attn.f_a_proj.weight", (H, w["rank"]), True, sH),
        Leaf("linear_attn.f_b_proj.weight", (w["rank"], w["inner"]), True,
             sR),
        Leaf("linear_attn.A_log", (w["heads"],), True, 1.0),
        Leaf("linear_attn.dt_bias", (w["inner"],), True, 3.0),
        Leaf("linear_attn.b_proj.weight", (H, w["heads"]), True, sH),
        Leaf("linear_attn.g_a_proj.weight", (H, w["rank"]), True, sH),
        Leaf("linear_attn.g_b_proj.weight", (w["rank"], w["inner"]), True,
             sR),
        Leaf("linear_attn.o_norm.weight", (w["d"],), True, 0.1, ones=True),
        Leaf("linear_attn.o_proj.weight", (w["inner"], H), True,
             1.0 / math.sqrt(w["inner"])),
        Leaf("input_layernorm.weight", (H,), True, 0.1, ones=True),
        Leaf("post_attention_layernorm.weight", (H,), True, 0.1, ones=True),
        Leaf("mlp.gate.weight", (H, width), True, sH),
        # sigmoid scores lie in (0, 1); a selection bias a few hundredths
        # wide moves a token's eighth choice, as a trained one does
        Leaf("mlp.gate.bias", (width,), True, 0.05),
        Leaf("mlp.experts_gate", (n, H, I), True, sH),
        Leaf("mlp.experts_up", (n, H, I), True, sH),
        Leaf("mlp.experts_down", (n, I, H), True, sI),
        Leaf("mlp.shared_gate_proj.weight", (H, S), True, sH),
        Leaf("mlp.shared_up_proj.weight", (H, S), True, sH),
        Leaf("mlp.shared_down_proj.weight", (S, H), True,
             1.0 / math.sqrt(S)),
        Leaf("embed", (V, H), False, sH),
        Leaf("head", (H, V), False, sH),
        Leaf("norm", (H,), False, 0.1, ones=True)]


def layer_leaves(m: dict, linear: bool) -> list:
    """The stacked leaves a linear (or a softmax) layer has."""
    other = SOFTMAX_LEAVES if linear else LINEAR_LEAVES
    return [lf for lf in leaf_specs(m)
            if lf.stacked and not lf.name.startswith(other)]


def count_params(m: dict, layers: int) -> dict:
    """Parameters held here and parameters a token touches here (its share
    of the top-k experts: k x held / router width on average)."""
    def total(leaves):
        return sum(int(np.prod(lf.shape)) for lf in leaves)

    lin, soft = total(layer_leaves(m, True)), total(layer_leaves(m, False))
    flat = total(lf for lf in leaf_specs(m) if not lf.stacked)
    width, _, _ = held(m)
    bank = total(lf for lf in layer_leaves(m, True)
                 if lf.name.startswith("mlp.experts_"))
    n_lin = sum(is_linear(m, l) for l in range(layers))
    n_soft = layers - n_lin
    idle = bank - bank * m["num_experts_per_tok"] // width
    total_ = n_lin * lin + n_soft * soft + flat
    return {"total": total_, "active": total_ - layers * idle,
            "linear_layer": lin, "softmax_layer": soft, "experts": bank,
            "per_layer": lin, "embed_and_head": flat}


# ------------------------------------------------------------- maths ----

def _unit(a):
    """L2-normalised along the last axis (departure 2)."""
    return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)


def recurrence(q, k, v, g, beta, s0=None):
    """The bare delta rule over one sequence from ``S_0`` (zero where not
    given): q, k, g [S, heads, K], v [S, heads, V], beta [S, heads] ->
    (o [S, heads, V], the final state [heads, K, V]), float32."""
    heads, K = q.shape[1:]
    if s0 is None:
        s0 = jnp.zeros((heads, K, v.shape[-1]), F32)

    def step(s, t):
        qt, kt, vt, gt, bt = t
        s = s * jnp.exp(gt)[..., None]
        u = vt - jnp.einsum("hkv,hk->hv", s, kt, precision=HI)
        s = s + (bt[:, None] * kt)[..., None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=HI)

    state, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, state


def mixer_inputs(u, w, m, precision):
    """What the recurrence of a linear layer reads, from the normed input
    ``u [S, H]``: (q, k, v, g, beta)."""
    S = u.shape[0]
    wd = widths(m)
    heads, d, taps = wd["heads"], wd["d"], wd["taps"]
    qkv = _mm(u, w["linear_attn.qkv_proj.weight"], precision)
    ext = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    taps_w = w["linear_attn.conv1d.weight"].astype(F32)
    acc = jnp.zeros_like(qkv)
    for j in range(taps):
        acc = acc + taps_w[j] * ext[j:j + S]
    c = jax.nn.silu(acc)
    q, k, v = (c[:, i * wd["inner"]:(i + 1) * wd["inner"]].reshape(
        S, heads, d) for i in range(3))
    q, k = _unit(q) * d ** -0.5, _unit(k)
    fa = _mm(_mm(u, w["linear_attn.f_a_proj.weight"], precision),
             w["linear_attn.f_b_proj.weight"], precision)
    g = -jnp.exp(w["linear_attn.A_log"].astype(F32))[None, :, None] \
        * jax.nn.softplus(fa.reshape(S, heads, d)
                          + w["linear_attn.dt_bias"].astype(F32).reshape(
                              heads, d))
    beta = jax.nn.sigmoid(_mm(u, w["linear_attn.b_proj.weight"], precision))
    if m["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    return q, k, v, g, beta


def mixer(u, w, m, precision):
    """The linear layer's token mixer on the normed input: u [S, H] -> [S,
    H]."""
    S = u.shape[0]
    o, _ = recurrence(*mixer_inputs(u, w, m, precision))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + m["rms_norm_eps"]) \
        * w["linear_attn.o_norm.weight"].astype(F32)
    gate = jax.nn.sigmoid(_mm(
        _mm(u, w["linear_attn.g_a_proj.weight"], precision),
        w["linear_attn.g_b_proj.weight"], precision))
    return _mm(o.reshape(S, -1) * gate, w["linear_attn.o_proj.weight"],
               precision)


def attention(u, w, m, precision):
    """The softmax layer's token mixer on the normed input: u [S, H] (S a
    multiple of Q_BLOCK) -> [S, H], one head and one block of queries at a
    time (``falcon_h1._attend_block``: causal softmax at ``1/sqrt(d)``;
    no position enters it, and none is applied here)."""
    S = u.shape[0]
    hq, hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    q = _mm(u, w["self_attn.q_proj.weight"], precision).reshape(S, hq, d)
    k = _mm(u, w["self_attn.k_proj.weight"], precision).reshape(S, hkv, d)
    v = _mm(u, w["self_attn.v_proj.weight"], precision).reshape(S, hkv, d)
    nb = S // Q_BLOCK
    firsts = jnp.arange(nb) * Q_BLOCK
    group = hq // hkv
    kh = jnp.repeat(k.transpose(1, 0, 2), group, axis=0)   # [hq, S, d]
    vh = jnp.repeat(v.transpose(1, 0, 2), group, axis=0)

    def head(args):
        q1, k1, v1 = args
        return jax.lax.map(lambda a: _attend_block(a[0], k1, v1, a[1]),
                           (q1.reshape(nb, Q_BLOCK, d), firsts))

    out = jax.lax.map(head, (q.transpose(1, 0, 2), kh, vh))
    out = out.reshape(hq, S, d).transpose(1, 0, 2).reshape(S, hq * d)
    if m["use_gqa_gate"]:                                  # departure 3
        out = out * jax.nn.sigmoid(
            _mm(u, w["self_attn.g_proj.weight"], precision))
    return _mm(out, w["self_attn.o_proj.weight"], precision)


def router_gates(x, w, m):
    """[S, router width] float32: g_e of the chosen experts, 0 elsewhere.
    Scores in float32 at "highest" whatever the precision (departure 4)."""
    scores = jax.nn.sigmoid(_mm(x, w["mlp.gate.weight"], "highest"))
    by = scores + w["mlp.gate.bias"].astype(F32)      # selects, not gated
    _, topi = jax.lax.top_k(by, m["num_experts_per_tok"])
    topv = jnp.take_along_axis(scores, topi, axis=-1)
    topv = m["routed_scaling_factor"] * topv / topv.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], topi].set(topv)


def routed_experts(x, w, m, precision):
    """The part of the routed sum that the experts held here give
    (departure 1)."""
    _, n, first = held(m)
    gate = router_gates(x, w, m)[:, first:first + n]       # [S, held]

    def one(acc, args):          # one expert at a time, summed as they come
        wg, wu, wd, g = args
        return acc + _swiglu(x, wg, wu, wd, precision) * g[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros(x.shape, F32), (
        w["mlp.experts_gate"], w["mlp.experts_up"], w["mlp.experts_down"],
        gate.T))
    return out


def shared_expert(x, w, precision):
    return _swiglu(x, w["mlp.shared_gate_proj.weight"],
                   w["mlp.shared_up_proj.weight"],
                   w["mlp.shared_down_proj.weight"], precision)


def layer(w, x, m, linear, precision="highest"):
    """One decoder layer on one sequence: x [S, H] float32."""
    eps = m["rms_norm_eps"]
    u = _rms(x, w["input_layernorm.weight"], eps)
    x = x + (mixer if linear else attention)(u, w, m, precision)
    h = _rms(x, w["post_attention_layernorm.weight"], eps)
    return x + routed_experts(h, w, m, precision) \
        + shared_expert(h, w, precision)


def head_logits(flat, x, m, precision="highest"):
    """Final norm and the untied head: x [N, H] -> logits [N, V] float32
    over the part of the vocabulary held (departure 1)."""
    return _mm(_rms(x, flat["norm"], m["rms_norm_eps"]), flat["head"],
               precision)


# ----------------------------------------------------------- serving ----

def sequence_logits(get_layer, flat, layers, m, seqs, positions,
                    precision="highest"):
    """Logits of the reference at chosen positions of whole sequences; the
    surface of ``references/llama.py::sequence_logits`` (layers outermost,
    one layer's weights at a time; sequences padded at the END to a
    multiple of ``PAD_TO``: causal attention, a causal convolution and a
    forward recurrence never see the padding; positions to a multiple of
    64)."""
    def pad(ids):
        n = -(-len(ids) // PAD_TO) * PAD_TO
        return np.asarray(list(ids) + [0] * (n - len(ids)), np.int32)

    emb = jax.jit(lambda e, ids: jnp.take(e, ids, axis=0).astype(F32))
    xs = [emb(flat["embed"], pad(s)) for s in seqs]
    steps = {linear: jax.jit(lambda w, x, linear=linear:
                             layer(w, x, m, linear, precision))
             for linear in {is_linear(m, l) for l in range(layers)}}
    for l in range(layers):
        linear = is_linear(m, l)
        w = get_layer(l)
        # a layer is handed every stacked leaf; it reads its own kind's
        w = {lf.name: w[lf.name] for lf in layer_leaves(m, linear)}
        xs = [steps[linear](w, x) for x in xs]
        del w
    fin = jax.jit(lambda f, x, pos: head_logits(
        f, jnp.take(x, pos, axis=0), m, precision))
    out = []
    for x, p in zip(xs, positions):
        padded = list(p) + [p[-1]] * (-len(p) % 64)
        out.append(np.asarray(fin(flat, x, np.asarray(padded, np.int32)))
                   [:len(p)])
    return out
