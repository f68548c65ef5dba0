"""Plain reference for the ``deepseek_v32`` family (DeepSeek-V3.2): the layer
equations as the source's ``config.json`` and the catalog's ``described_as``
give them, in ``jax.numpy``, float32, matmul precision "highest", the
EXPANDED form of latent attention (every head's own key and value are made
from the compressed row), its OWN index scores and its OWN selection; no
kernel, no cache, no batching; it imports nothing of the program and takes
nothing the program made.

One layer (``x`` the residual stream, pre-norm, sequential residuals), for
the token at position ``t`` and a token ``s <= t``:

    y = RMSNorm(x)                      eps = rms_norm_eps
    c_q = RMSNorm(y Wdq)                q_lora_rank (the query latent)
    q_h = c_q Wuq_h                     [q_nope (qk_nope) | q_rope (qk_rope)]
    [c | k_r] = y Wdkv                  kv_lora_rank + qk_rope_head_dim
    c = RMSNorm(c)
    k_r = RoPE(k_r, t), q_rope = RoPE(q_rope, t)
                                        interleaved pairs, yarn: inv_freq
                                        blended between theta^(-2i/d) and
                                        that over `factor` by the linear
                                        ramp between the two correction
                                        dimensions; cos and sin times
                                        mscale(factor, mscale) /
                                        mscale(factor, mscale_all_dim)
    the index:
      q_i,j = c_q Wiq_j                 index_n_heads x index_head_dim,
                                        RoPE on the first qk_rope_head_dim
      k_i = LayerNorm(y Wik)            weight and bias; ONE key a token,
                                        RoPE on its first qk_rope_head_dim
      w = y Wiw                         index_n_heads numbers
      I(t, s) = sum_j w_t,j ReLU(q_i,t,j . k_i,s)
      S_t = the min(t + 1, index_topk) positions s <= t with the largest
            I(t, s); a tie at the edge goes to the lower position
    k_h = [c Wuk_h | k_r], v_h = c Wuv_h         for every head h
    a = Wo [softmax over s in S_t of (q_h k_h^T x scale) v_h]_h
                                        scale = (qk_nope + qk_rope)^-0.5 x
                                        mscale(factor, mscale_all_dim)^2
    x <- x + a;  y = RMSNorm(x)
    layers [0, first_k_dense_replace):  f = SwiGLU(y), width intermediate_size
    the others:
      s = sigmoid(y Wr)                 over n_routed_experts, float32
      a group (n_group equal runs of experts) is ranked by the sum of its
      two largest s + b; the best topk_group groups are kept; inside them
      the num_experts_per_tok largest s + b are chosen
      g_e = routed_scaling_factor x s_e / sum of the chosen s
      f = sum_e g_e SwiGLU_e(y) + the shared expert's SwiGLU(y)
    x <- x + f

and after the last layer a final RMSNorm and an untied head.  The
checkpoint's multi-token-prediction module is not run (the configuration's
``not_run``).

Departures from the published description, each noted where it is made:
(1) ONE CHIP'S SHARE.  The model ``m`` this file is handed is
``Run.model``: ``m["n_routed_experts"]`` experts are HELD (of the router's
``m["published"]["n_routed_experts"]``), those from ``share.index x held``
on; the routed sum runs over the held experts only, what the others would
add is left out, exactly as the program leaves it out.  ``m["vocab_size"]``
is the part of the vocabulary held (number ``share.index % share.over.
vocab_size`` of the parts; ids and logits are over it).  Without
``published`` (an uncut model) every expert is held.
(2) ASSUMED: the index in the model's dtype where the source holds fp8; the
source's Hadamard rotation of ``q_i`` and ``k_i`` left out (orthogonal: no
dot product changes); the positive constants on ``w`` left out (no choice
changes); the FIRST ``qk_rope_head_dim`` of an index head rotate, on
interleaved pairs (a fixed permutation under seeded weights).
(3) ASSUMED: RMSNorm on ``c_q`` and on ``c`` (``q_a_layernorm``,
``kv_a_layernorm``); LayerNorm's eps is ``rms_norm_eps``.
(4) ``kv_b_proj`` is kept as its two halves (``k_up_proj``, ``v_up_proj``).

``precision="int8"`` is the CONTROL (the nearest precision below bf16), as
in ``references/llama.py``: both operands of every projection rounded to 8
bits along the contracted axis.  The router's scores, the index scores and
the attention products stay in float32 "highest" in the control too.  The
checks must refuse it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness.weights import Leaf
from chipbench.references.llama import F32, HI, _mm, _rms, _swiglu
from chipbench.references.sarvam_mla import (DENSE_LEAVES, _rope, head_logits,
                                             is_dense, softmax_scale)

Q_BLOCK = 512        # queries a block of scores holds (PAD_TO % it)
PAD_TO = 4096        # sequences are padded to a multiple: few shapes compile


# ------------------------------------------------------------ leaves ----

def held(m: dict) -> tuple:
    """(router width, experts held, the first held expert's number)."""
    n = int(m["n_routed_experts"])
    width = int(m.get("published", {}).get("n_routed_experts", n))
    index = int(m.get("share", {}).get("index", 0))
    return width, n, index * n


def vocab_part(m: dict) -> int:
    """Which part of the vocabulary this chip holds: ``index`` modulo the
    parts the vocabulary is divided over."""
    share = m.get("share", {})
    over = int(share.get("over", {}).get("vocab_size",
                                         share.get("chips", 1)))
    return int(share.get("index", 0)) % over


def leaf_specs(m: dict) -> list:
    """Every parameter of the model ``m``: name, per-layer shape, std of
    its normal draw.  Weights are [in, out]; the names are the program's.
    A layer is dense (``DENSE_LEAVES``) or holds the router, the experts
    and the shared expert (the other ``mlp.`` leaves), never both."""
    H, V = m["hidden_size"], m["vocab_size"]
    heads, rank, qr = (m["num_attention_heads"], m["kv_lora_rank"],
                       m["q_lora_rank"])
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    ih, idim = m["index_n_heads"], m["index_head_dim"]
    Id, I = m["intermediate_size"], m["moe_intermediate_size"]
    width, n, _ = held(m)
    sH, sR, sQ = (1.0 / math.sqrt(H), 1.0 / math.sqrt(rank),
                  1.0 / math.sqrt(qr))
    sI = 1.0 / math.sqrt(I)
    return [
        Leaf("self_attn.q_a_proj.weight", (H, qr), True, sH),
        Leaf("self_attn.q_a_layernorm.weight", (qr,), True, 0.1, ones=True),
        Leaf("self_attn.q_b_proj.weight", (qr, heads * (nope + rope)), True,
             sQ),
        Leaf("self_attn.kv_a_proj_with_mqa.weight", (H, rank + rope), True,
             sH),
        Leaf("self_attn.kv_a_layernorm.weight", (rank,), True, 0.1,
             ones=True),
        Leaf("self_attn.k_up_proj.weight", (rank, heads * nope), True, sR),
        Leaf("self_attn.v_up_proj.weight", (rank, heads * vd), True, sR),
        Leaf("self_attn.o_proj.weight", (heads * vd, H), True,
             1.0 / math.sqrt(heads * vd)),
        Leaf("self_attn.indexer.wq_b.weight", (qr, ih * idim), True, sQ),
        Leaf("self_attn.indexer.wk.weight", (H, idim), True, sH),
        Leaf("self_attn.indexer.k_norm.weight", (idim,), True, 0.1,
             ones=True),
        Leaf("self_attn.indexer.k_norm.bias", (idim,), True, 0.1),
        Leaf("self_attn.indexer.weights_proj.weight", (H, ih), True, sH),
        Leaf("input_layernorm.weight", (H,), True, 0.1, ones=True),
        Leaf("post_attention_layernorm.weight", (H,), True, 0.1, ones=True),
        Leaf("mlp.gate_proj.weight", (H, Id), True, sH),
        Leaf("mlp.up_proj.weight", (H, Id), True, sH),
        Leaf("mlp.down_proj.weight", (Id, H), True, 1.0 / math.sqrt(Id)),
        Leaf("mlp.gate.weight", (H, width), True, sH),
        # sigmoid scores lie in (0, 1); a selection bias a few hundredths
        # wide moves a token's eighth choice, as a trained one does
        Leaf("mlp.gate.bias", (width,), True, 0.05),
        Leaf("mlp.experts_gate", (n, H, I), True, sH),
        Leaf("mlp.experts_up", (n, H, I), True, sH),
        Leaf("mlp.experts_down", (n, I, H), True, sI),
        Leaf("mlp.shared_gate_proj.weight", (H, I), True, sH),
        Leaf("mlp.shared_up_proj.weight", (H, I), True, sH),
        Leaf("mlp.shared_down_proj.weight", (I, H), True, sI),
        Leaf("embed", (V, H), False, sH),
        Leaf("head", (H, V), False, sH),
        Leaf("norm", (H,), False, 0.1, ones=True)]


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

def _layer_norm(x, w, b, eps):
    x = x.astype(F32)
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32) + b.astype(F32)


def _rope_first(x, m):
    """x [S, heads, d]: rotary on the first ``qk_rope_head_dim`` of ``d``
    (departure 2), with the attention's own frequencies."""
    r = m["qk_rope_head_dim"]
    return jnp.concatenate([_rope(x[..., :r], m), x[..., r:]], axis=-1)


def index_scores(q_i, w, k_i, first):
    """[Q, S] float32: ``I(t, s)`` of the queries at positions ``first ..
    first + Q`` (q_i [Q, heads, d], w [Q, heads]) against every key (k_i
    [S, d]), one index head at a time; -inf where ``s > t``."""
    def one(acc, args):
        qj, wj = args                              # [Q, d], [Q]
        s = jnp.einsum("qd,kd->qk", qj, k_i, precision=HI)
        return acc + wj[:, None] * jnp.maximum(s, 0.0), None

    Q, S = q_i.shape[0], k_i.shape[0]
    out, _ = jax.lax.scan(one, jnp.zeros((Q, S), F32),
                          (q_i.transpose(1, 0, 2), w.T))
    t = first + jnp.arange(Q)[:, None]
    return jnp.where(jnp.arange(S)[None, :] <= t, out, -jnp.inf)


def chosen_set(scores, first, top_k):
    """bool [Q, S]: each row's ``min(t + 1, top_k)`` largest scores (``t =
    first + row``), a tie at the edge going to the lower position.  A sort
    gives the value at the edge; the ties at that value are admitted in
    order of position."""
    Q = scores.shape[0]
    k = jnp.minimum(first + jnp.arange(Q) + 1, top_k)
    edge = jnp.take_along_axis(-jnp.sort(-scores, axis=-1),
                               (k - 1)[:, None], axis=-1)       # [Q, 1]
    above = scores > edge
    tie = scores == edge
    left = k - above.sum(-1)
    return jnp.logical_or(above, jnp.logical_and(
        tie, jnp.cumsum(tie, axis=-1) <= left[:, None]))


def _attend_block(q, k, v, chosen, scale):
    """q [Q, d], k [S, d], v [S, dv], chosen bool [Q, S]: softmax attention
    of one block of one head over each query's chosen keys alone."""
    s = jnp.einsum("qd,kd->qk", q, k, precision=HI) * scale
    p = jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), axis=-1)
    return jnp.einsum("qk,kd->qd", p, v, precision=HI)


def _attention(x, w, m, precision):
    """x [S, H] (one sequence, S a multiple of Q_BLOCK) -> [S, H]: the
    chosen sets first (a block of queries at a time), then the expanded
    form one head at a time (its own projections made inside the loop: a
    33k-token sequence's 128 heads never exist together), each head's
    output carried through its rows of ``W_o`` and summed."""
    S, H = x.shape
    heads, rank = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    ih, idim = m["index_n_heads"], m["index_head_dim"]
    eps = m["rms_norm_eps"]
    top_k = m["index_topk"]
    nb = S // Q_BLOCK
    firsts = jnp.arange(nb) * Q_BLOCK
    scale = softmax_scale(m)

    # departure (3): the norms of the two latents
    c_q = _rms(_mm(x, w["self_attn.q_a_proj.weight"], precision),
               w["self_attn.q_a_layernorm.weight"], eps)
    ckr = _mm(x, w["self_attn.kv_a_proj_with_mqa.weight"], precision)
    c = _rms(ckr[:, :rank], w["self_attn.kv_a_layernorm.weight"], eps)
    k_r = _rope(ckr[:, None, rank:], m)[:, 0]                  # [S, rope]

    q_i = _rope_first(_mm(c_q, w["self_attn.indexer.wq_b.weight"],
                          precision).reshape(S, ih, idim), m)
    k_i = _rope_first(_layer_norm(
        _mm(x, w["self_attn.indexer.wk.weight"], precision),
        w["self_attn.indexer.k_norm.weight"],
        w["self_attn.indexer.k_norm.bias"], eps)[:, None], m)[:, 0]
    w_i = _mm(x, w["self_attn.indexer.weights_proj.weight"], precision)
    chosen = jax.lax.map(
        lambda a: chosen_set(index_scores(a[0], a[1], k_i, a[2]), a[2],
                             top_k),
        (q_i.reshape(nb, Q_BLOCK, ih, idim), w_i.reshape(nb, Q_BLOCK, ih),
         firsts))                                          # [nb, Q, S] bool

    def head(acc, args):
        wq, wk, wv, wo = args       # [qr, nope+rope] [rank, nope] .. [vd, H]
        q = _mm(c_q, wq, precision)
        q = jnp.concatenate([q[:, :nope],
                             _rope(q[:, None, nope:], m)[:, 0]], -1)
        k = jnp.concatenate([_mm(c, wk, precision), k_r], -1)
        v = _mm(c, wv, precision)
        out = jax.lax.map(
            lambda a: _attend_block(a[0], k, v, a[1], scale),
            (q.reshape(nb, Q_BLOCK, -1), chosen))          # [nb, Q, vd]
        return acc + _mm(out.reshape(S, vd), wo, precision), None

    qr = c_q.shape[1]
    out, _ = jax.lax.scan(head, jnp.zeros((S, H), F32), (
        w["self_attn.q_b_proj.weight"].reshape(qr, heads, nope + rope)
        .transpose(1, 0, 2),
        w["self_attn.k_up_proj.weight"].reshape(rank, heads, nope)
        .transpose(1, 0, 2),
        w["self_attn.v_up_proj.weight"].reshape(rank, heads, vd)
        .transpose(1, 0, 2),
        w["self_attn.o_proj.weight"].reshape(heads, vd, H)))
    return out


def router_gates(x, w, m):
    """[S, router width] float32: g_e of the chosen experts, 0 elsewhere.
    Scores in float32 at "highest" whatever the precision."""
    k, groups, kept = (m["num_experts_per_tok"], m["n_group"],
                       m["topk_group"])
    scores = jax.nn.sigmoid(_mm(x, w["mlp.gate.weight"], "highest"))
    S, E = scores.shape
    by = scores + w["mlp.gate.bias"].astype(F32)      # selects, not gated
    rank = jnp.sort(by.reshape(S, groups, E // groups), axis=-1)[
        ..., -2:].sum(-1)                             # [S, groups]
    _, best = jax.lax.top_k(rank, kept)
    keep = jnp.zeros((S, groups), bool).at[
        jnp.arange(S)[:, None], best].set(True)
    by = jnp.where(jnp.repeat(keep, E // groups, axis=1), by, -jnp.inf)
    _, topi = jax.lax.top_k(by, k)
    topv = jnp.take_along_axis(scores, topi, axis=-1)
    topv = m["routed_scaling_factor"] * topv / topv.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[
        jnp.arange(S)[:, None], topi].set(topv)


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


def _by_rows(fn, x):
    """``fn`` over blocks of ``PAD_TO`` rows of x [S, H] (a 33k-token
    sequence's 18,432-wide intermediate never exists whole)."""
    S = x.shape[0]
    if S <= PAD_TO or S % PAD_TO:
        return fn(x)
    return jax.lax.map(fn, x.reshape(S // PAD_TO, PAD_TO, -1)).reshape(
        S, -1)


def shared_expert(x, w, precision):
    return _by_rows(lambda r: _swiglu(
        r, w["mlp.shared_gate_proj.weight"], w["mlp.shared_up_proj.weight"],
        w["mlp.shared_down_proj.weight"], precision), x)


def layer(w, x, m, dense, precision="highest"):
    """One decoder layer on one sequence: x [S, H] float32."""
    eps = m["rms_norm_eps"]
    x = x + _attention(_rms(x, w["input_layernorm.weight"], eps), w, m,
                       precision)
    y = _rms(x, w["post_attention_layernorm.weight"], eps)
    if dense:
        return x + _by_rows(lambda r: _swiglu(
            r, w["mlp.gate_proj.weight"], w["mlp.up_proj.weight"],
            w["mlp.down_proj.weight"], precision), y)
    return x + routed_experts(y, w, m, precision) \
        + shared_expert(y, w, precision)


# ----------------------------------------------------------- serving ----

def sequence_logits(get_layer, flat, layers, m, seqs, positions,
                    precision="highest"):
    """Logits of the reference at chosen positions of whole sequences; the
    surface of ``references/llama.py::sequence_logits`` (layers outermost,
    one layer's weights at a time; sequences padded at the END to a
    multiple of ``PAD_TO``, positions to a multiple of 64)."""
    pad_to = PAD_TO if max(len(s) for s in seqs) > PAD_TO else Q_BLOCK

    def pad(ids):
        n = -(-len(ids) // pad_to) * pad_to
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
