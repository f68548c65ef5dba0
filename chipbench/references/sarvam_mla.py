"""Plain reference for the ``sarvam_mla`` family (Sarvam-105B): the layer
equations as the source's ``config.json`` and the catalog's ``described_as``
give them, in ``jax.numpy``, float32, matmul precision "highest", the
EXPANDED form of latent attention (every head's own key and value are made
from the compressed row); no kernel, no cache, no batching; it imports
nothing of the program and takes nothing the program made.

One layer (``x`` the residual stream, pre-norm, sequential residuals), for
the token at position ``p``:

    y = RMSNorm(x)                      eps = rms_norm_eps
    q = y Wq                            heads x q_head_dim =
                                        [q_nope (qk_nope) | q_rope (qk_rope)]
    [c | k_r] = y Wdkv                  kv_lora_rank + qk_rope_head_dim
    c = RMSNorm(c)                      kv_a_layernorm
    k_r = RoPE(k_r, p), q_rope = RoPE(q_rope, p)
                                        interleaved pairs, deepseek_yarn:
                                        inv_freq blended between
                                        theta^(-2i/d) and that over `factor`
                                        by the linear ramp between the two
                                        correction dimensions; cos and sin
                                        times mscale(factor, mscale) /
                                        mscale(factor, mscale_all_dim)
    k_h = [c Wuk_h | k_r], v_h = c Wuv_h         for every head h
    a = Wo [softmax_causal(q_h k_h^T x scale) v_h]_h
                                        scale = q_head_dim^-0.5 x
                                        mscale(factor, mscale_all_dim)^2
    x <- x + a;  y = RMSNorm(x)
    layers [0, first_k_dense_replace):  f = SwiGLU(y), width intermediate_size
    the others:
      s = sigmoid(y Wr)                 over num_experts, float32
      chosen = the num_experts_per_tok largest of s + b
      g_e = routed_scaling_factor x s_e / sum of the chosen s
      f = sum_e g_e SwiGLU_e(y) + sum of the shared experts' SwiGLU(y)
    x <- x + f

and after the last layer a final RMSNorm and an untied head.

Departures from the published description, each noted where it is made:
(1) ONE CHIP'S SHARE.  The model ``m`` this file is handed is
``Run.model``: ``m["num_experts"]`` experts are HELD (of the router's
``m["published"]["num_experts"]``), those from ``share.index x held`` on;
the routed sum runs over the held experts only, what the others would add
is left out, exactly as the program leaves it out, and that partial result
goes on to the next layer.  ``m["vocab_size"]`` is the slice of the
vocabulary held: embedding, head, logits and the traffic's ids are over it.
Without ``published`` (an uncut model) every expert is held.
(2) ASSUMED: the router's score is sigmoid and the chosen scores are
normalised before ``routed_scaling_factor`` (the family whose keys these
are is defined so; the config has no ``n_group`` / ``topk_group``, so no
group-limited choice).
(3) ASSUMED: ``use_qk_norm: true`` is the RMSNorm on the compressed
projection (``kv_a_layernorm``), the query having no low-rank path: a
per-head norm of the expanded key would not be linear in ``c``, and no
latent cache could hold it.  A real checkpoint could overturn this.
(4) ASSUMED: rotary acts on the interleaved pairs (2i, 2i+1); the
half-rotation is the same map under a fixed permutation of columns, which
seeded weights make immaterial.
(5) ``kv_b_proj`` is kept as its two halves, ``k_up_proj`` ([rank, heads x
qk_nope]) and ``v_up_proj`` ([rank, heads x v_head]): a storage layout.

``precision="int8"`` is the CONTROL (the nearest precision below bf16), as
in ``references/llama.py``: both operands of every projection rounded to 8
bits along the contracted axis.  The router's scores stay in float32
"highest" in the control too.  The checks must refuse it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness.weights import Leaf
from chipbench.references.llama import F32, HI, PAD_TO, _mm, _rms, _swiglu

Q_BLOCK = 512        # queries a block of attention scores holds (PAD_TO % it)
DENSE_LEAVES = ("mlp.gate_proj.weight", "mlp.up_proj.weight",
                "mlp.down_proj.weight")


# ------------------------------------------------------------ leaves ----

def held(m: dict) -> tuple:
    """(router width, experts held, the first held expert's number)."""
    n = int(m["num_experts"])
    width = int(m.get("published", {}).get("num_experts", n))
    index = int(m.get("share", {}).get("index", 0))
    return width, n, index * n


def leaf_specs(m: dict) -> list:
    """Every parameter of the model ``m``: name, per-layer shape, std of
    its normal draw.  Weights are [in, out]; the names are the program's.
    A layer is dense (``DENSE_LEAVES``) or holds the router, the experts
    and the shared experts (the other ``mlp.`` leaves), never both."""
    H, V = m["hidden_size"], m["vocab_size"]
    heads, rank = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    Id, I = m["intermediate_size"], m["moe_intermediate_size"]
    width, n, _ = held(m)
    S = int(m["num_shared_experts"])
    sH, sR = 1.0 / math.sqrt(H), 1.0 / math.sqrt(rank)
    sI = 1.0 / math.sqrt(I)
    out = [
        Leaf("self_attn.q_proj.weight", (H, heads * (nope + rope)), True, sH),
        Leaf("self_attn.kv_a_proj_with_mqa.weight", (H, rank + rope), True,
             sH),
        Leaf("self_attn.kv_a_layernorm.weight", (rank,), True, 0.1,
             ones=True),
        Leaf("self_attn.k_up_proj.weight", (rank, heads * nope), True, sR),
        Leaf("self_attn.v_up_proj.weight", (rank, heads * vd), True, sR),
        Leaf("self_attn.o_proj.weight", (heads * vd, H), True,
             1.0 / math.sqrt(heads * vd)),
        Leaf("input_layernorm.weight", (H,), True, 0.1, ones=True),
        Leaf("post_attention_layernorm.weight", (H,), True, 0.1, ones=True),
        Leaf("mlp.gate_proj.weight", (H, Id), True, sH),
        Leaf("mlp.up_proj.weight", (H, Id), True, sH),
        Leaf("mlp.down_proj.weight", (Id, H), True, 1.0 / math.sqrt(Id)),
        Leaf("mlp.gate.weight", (H, width), True, sH),
    ]
    if m.get("moe_router_enable_expert_bias"):
        # sigmoid scores lie in (0, 1); a selection bias a few hundredths
        # wide moves a token's eighth choice, as a trained one does
        out.append(Leaf("mlp.gate.bias", (width,), True, 0.05))
    out += [Leaf("mlp.experts_gate", (n, H, I), True, sH),
            Leaf("mlp.experts_up", (n, H, I), True, sH),
            Leaf("mlp.experts_down", (n, I, H), True, sI)]
    if S:
        out += [Leaf("mlp.shared_gate_proj.weight", (H, S * I), True, sH),
                Leaf("mlp.shared_up_proj.weight", (H, S * I), True, sH),
                Leaf("mlp.shared_down_proj.weight", (S * I, H), True, sI)]
    out += [Leaf("embed", (V, H), False, sH),
            Leaf("head", (H, V), False, sH),
            Leaf("norm", (H,), False, 0.1, ones=True)]
    return out


def is_dense(m: dict, layer: int) -> bool:
    return layer < int(m.get("first_k_dense_replace", 0))


def layer_leaves(m: dict, dense: bool) -> list:
    """The stacked leaves a dense (or an expert) layer has."""
    return [lf for lf in leaf_specs(m) if lf.stacked and (
        not lf.name.startswith("mlp.") or (lf.name in DENSE_LEAVES) == dense)]


def count_params(m: dict, layers: int) -> dict:
    """Parameters held here and parameters a token touches here (its share
    of the top-k experts: k x held / router width on average)."""
    def total(leaves):
        return sum(int(np.prod(lf.shape)) for lf in leaves)

    dense, moe = total(layer_leaves(m, True)), total(layer_leaves(m, False))
    flat = total(lf for lf in leaf_specs(m) if not lf.stacked)
    width, _, _ = held(m)
    bank = total(lf for lf in layer_leaves(m, False)
                 if lf.name.startswith("mlp.experts_"))
    n_dense = min(int(m.get("first_k_dense_replace", 0)), layers)
    n_moe = layers - n_dense
    active = moe - bank + bank * m["num_experts_per_tok"] // width
    return {"total": n_dense * dense + n_moe * moe + flat,
            "active": n_dense * dense + n_moe * active + flat,
            "per_layer": moe, "dense_layer": dense, "embed_and_head": flat}


# ------------------------------------------------------------- maths ----

def _mscale(factor, m):
    return 1.0 if factor <= 1 or not m else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: dict) -> np.ndarray:
    """[dim / 2] inverse frequencies of ``deepseek_yarn``."""
    i = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / theta ** (i / dim)
    inter = extra / rs["factor"]
    orig = rs["original_max_position_embeddings"]

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def softmax_scale(m: dict) -> float:
    rs = m.get("rope_scaling") or {}
    d = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return d ** -0.5 * _mscale(rs.get("factor", 1),
                               rs.get("mscale_all_dim", 0)) ** 2


def _rope(x, m):
    """x [S, heads, d]: rotate the pairs (x[2i], x[2i+1]) by pos x freq_i."""
    S, _, d = x.shape
    rs = m.get("rope_scaling")
    theta = m["rope_theta"]
    if rs:
        inv = jnp.asarray(yarn_inv_freq(d, theta, rs))
        k = _mscale(rs["factor"], rs.get("mscale", 1)) \
            / _mscale(rs["factor"], rs.get("mscale_all_dim", 0))
    else:
        inv, k = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d)), 1.0
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :] * k, jnp.sin(ang)[:, None, :] * k
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _attend_block(q, k, v, first, scale):
    """q [Q, d] (queries at positions first .. first + Q), k [S, d],
    v [S, dv]: causal softmax attention of one block of one head."""
    s = jnp.einsum("qd,kd->qk", q, k, precision=HI) * scale
    i = first + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
    return jnp.einsum("qk,kd->qd", p, v, precision=HI)


def _attention(x, w, m, precision):
    """x [S, H] (one sequence, S a multiple of Q_BLOCK) -> [S, H]: the
    expanded form, one head and one block of queries at a time."""
    S = x.shape[0]
    heads, rank = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    q = _mm(x, w["self_attn.q_proj.weight"], precision).reshape(
        S, heads, nope + rope)
    ckr = _mm(x, w["self_attn.kv_a_proj_with_mqa.weight"], precision)
    # departure (3): the norm of the compressed projection
    c = _rms(ckr[:, :rank], w["self_attn.kv_a_layernorm.weight"],
             m["rms_norm_eps"])
    k_r = _rope(ckr[:, None, rank:], m)[:, 0]                  # [S, rope]
    q_r = _rope(q[..., nope:], m)
    k_n = _mm(c, w["self_attn.k_up_proj.weight"], precision).reshape(
        S, heads, nope)
    v = _mm(c, w["self_attn.v_up_proj.weight"], precision).reshape(
        S, heads, vd)
    qh = jnp.concatenate([q[..., :nope], q_r], -1)     # [S, heads, nope+rope]
    kh = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r[:, None], (S, heads, rope))], -1)
    nb = S // Q_BLOCK
    firsts = jnp.arange(nb) * Q_BLOCK
    scale = softmax_scale(m)

    def head(args):
        q1, k1, v1 = args                              # [S, d], [S, d], [S, dv]
        return jax.lax.map(
            lambda a: _attend_block(a[0], k1, v1, a[1], scale),
            (q1.reshape(nb, Q_BLOCK, -1), firsts))     # [blocks, Q, dv]

    out = jax.lax.map(head, (qh.transpose(1, 0, 2), kh.transpose(1, 0, 2),
                             v.transpose(1, 0, 2)))    # [heads, blocks, Q, dv]
    out = out.reshape(heads, S, vd).transpose(1, 0, 2).reshape(S, heads * vd)
    return _mm(out, w["self_attn.o_proj.weight"], precision)


def router_gates(x, w, m):
    """[S, router width] float32: g_e of the chosen experts, 0 elsewhere.
    Scores in float32 at "highest" whatever the precision (departure 2)."""
    k = m["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_mm(x, w["mlp.gate.weight"], "highest"))
    chosen_by = scores
    if m.get("moe_router_enable_expert_bias"):
        # the bias selects; it is not in the gate
        chosen_by = scores + w["mlp.gate.bias"].astype(F32)
    _, topi = jax.lax.top_k(chosen_by, k)
    topv = jnp.take_along_axis(scores, topi, axis=-1)
    topv = m.get("routed_scaling_factor", 1.0) * topv \
        / topv.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], topi].set(topv)


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


def shared_experts(x, w, m, precision):
    """The sum of the shared experts' outputs."""
    S, I = int(m["num_shared_experts"]), m["moe_intermediate_size"]
    out = jnp.zeros(x.shape, F32)
    for j in range(S):
        out = out + _swiglu(
            x, w["mlp.shared_gate_proj.weight"][:, j * I:(j + 1) * I],
            w["mlp.shared_up_proj.weight"][:, j * I:(j + 1) * I],
            w["mlp.shared_down_proj.weight"][j * I:(j + 1) * I], precision)
    return out


def layer(w, x, m, dense, precision="highest"):
    """One decoder layer on one sequence: x [S, H] float32."""
    eps = m["rms_norm_eps"]
    x = x + _attention(_rms(x, w["input_layernorm.weight"], eps), w, m,
                       precision)
    y = _rms(x, w["post_attention_layernorm.weight"], eps)
    if dense:
        return x + _swiglu(y, w["mlp.gate_proj.weight"],
                           w["mlp.up_proj.weight"], w["mlp.down_proj.weight"],
                           precision)
    return x + routed_experts(y, w, m, precision) \
        + shared_experts(y, w, m, precision)


def head_logits(flat, x, m, precision="highest"):
    """Final norm and the untied head: x [N, H] -> logits [N, V] float32."""
    return _mm(_rms(x, flat["norm"], m["rms_norm_eps"]), flat["head"],
               precision)


# ----------------------------------------------------------- serving ----

def sequence_logits(get_layer, flat, layers, m, seqs, positions,
                    precision="highest"):
    """Logits of the reference at chosen positions of whole sequences; the
    surface of ``references/llama.py::sequence_logits`` (layers outermost,
    one layer's weights at a time; sequences padded at the END to a
    multiple of ``PAD_TO``, positions to a multiple of 64)."""
    def pad(ids):
        n = -(-len(ids) // PAD_TO) * PAD_TO
        return np.asarray(list(ids) + [0] * (n - len(ids)), np.int32)

    emb = jax.jit(lambda e, ids: jnp.take(e, ids, axis=0).astype(F32))
    xs = [emb(flat["embed"], pad(s)) for s in seqs]
    steps = {dense: jax.jit(lambda w, x, dense=dense:
                            layer(w, x, m, dense, precision))
             for dense in {is_dense(m, l) for l in range(layers)}}
    for l in range(layers):
        dense = is_dense(m, l)
        w = get_layer(l)
        # a layer is handed every stacked leaf; it reads its own kind's
        w = {lf.name: w[lf.name] for lf in layer_leaves(m, dense)}
        xs = [steps[dense](w, x) for x in xs]
        del w
    fin = jax.jit(lambda f, x, pos: head_logits(
        f, jnp.take(x, pos, axis=0), m, precision))
    out = []
    for x, p in zip(xs, positions):
        padded = list(p) + [p[-1]] * (-len(p) % 64)
        out.append(np.asarray(fin(flat, x, np.asarray(padded, np.int32)))
                   [:len(p)])
    return out
