"""Plain reference for the ``cohere2_moe`` family (Command A+): the layer
equations as the source's ``config.json`` and the catalog's ``described_as``
give them, in ``jax.numpy``, float32, matmul precision "highest"; no kernel,
no cache, no batching; it imports nothing of the program and takes nothing
the program made.

One layer (``x`` the residual stream; all layers alike but for the kind of
their attention, ``layer_types[l]``):

    u = LayerNorm(x)          mean subtracted, variance normalised,
                              eps = layer_norm_eps, a weight, no bias
    a = Attn_l(u)             q = u Wq (heads x head_dim), k = u Wk,
                              v = u Wv (kv heads x head_dim), no bias, no
                              q/k norm; softmax(q k^T / sqrt(head_dim)) v;
                              then Wo.  "sliding_attention": rotary over the
                              whole head on the interleaved pairs (2i, 2i+1)
                              (rope_gptj), theta = rope_theta, keys j with
                              i - sliding_window < j <= i.
                              "full_attention": causal over everything, NO
                              positional embedding.
    s = sigmoid(u Wr)         router scores over num_experts, float32
    g_e = s_e / sum of the num_experts_per_tok largest s   (norm_topk_prob)
    routed = sum_e g_e W_down,e (silu(u W_gate,e) * (u W_up,e))
    shared = (1 / n) sum_{j<n} SwiGLU^shared_j(u)          ("average")
    x <- x + a + routed + shared                            (parallel block)

and after the last layer a final LayerNorm and ``logit_scale * h E^T`` with
the embedding ``E`` (tied).

Departures from the published description, each noted where it is made:
(1) ONE CHIP'S SHARE.  The model ``m`` this file is handed is
``Run.model``: ``m["num_experts"]`` experts are HELD (of the router's
``m["published"]["num_experts"]``), those from ``share.index x held`` on;
the routed sum runs over the held experts only, what the others would add
is left out, exactly as the program leaves it out, and that partial result
goes on to the next layer.  ``m["vocab_size"]`` is the slice of the
vocabulary held: embedding, logits and the traffic's ids are over it.
Without ``published`` (an uncut model) every expert is held.
(2) The four shared experts lie side by side in one array along their
width (``[H, n x I]``, ``[n x I, H]``): a storage layout; each is computed
and averaged on its own here.
(3) The vision tower is left out (the catalog holds the language model).

``precision="int8"`` is the CONTROL (the nearest precision below bf16),
as in ``references/llama.py``: both operands of every projection rounded to
8 bits along the contracted axis.  The router's scores stay in float32
"highest" in the control too (the configuration states them in float32).
The checks must refuse it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness.weights import Leaf
from chipbench.references.llama import F32, HI, PAD_TO, _mm, _rope, _swiglu

Q_BLOCK = 512        # queries a block of attention scores holds (PAD_TO % it)


# ------------------------------------------------------------ leaves ----

def held(m: dict) -> tuple:
    """(router width, experts held, the first held expert's number)."""
    n = int(m["num_experts"])
    width = int(m.get("published", {}).get("num_experts", n))
    index = int(m.get("share", {}).get("index", 0))
    return width, n, index * n


def leaf_specs(m: dict) -> list:
    """Every parameter of the model ``m``: name, per-layer shape, std of
    its normal draw.  Weights are [in, out]; the names are the program's."""
    H, I, V = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    hd = m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    width, n, _ = held(m)
    S = int(m["num_shared_experts"])
    sH, sI = 1.0 / math.sqrt(H), 1.0 / math.sqrt(I)
    out = [
        Leaf("self_attn.q_proj.weight", (H, q), True, sH),
        Leaf("self_attn.k_proj.weight", (H, kv), True, sH),
        Leaf("self_attn.v_proj.weight", (H, kv), True, sH),
        Leaf("self_attn.o_proj.weight", (q, H), True, 1.0 / math.sqrt(q)),
        Leaf("input_layernorm.weight", (H,), True, 0.1, ones=True),
        Leaf("mlp.gate.weight", (H, width), True, sH),
        Leaf("mlp.experts_gate", (n, H, I), True, sH),
        Leaf("mlp.experts_up", (n, H, I), True, sH),
        Leaf("mlp.experts_down", (n, I, H), True, sI),
    ]
    if S:
        out += [Leaf("mlp.shared_gate_proj.weight", (H, S * I), True, sH),
                Leaf("mlp.shared_up_proj.weight", (H, S * I), True, sH),
                Leaf("mlp.shared_down_proj.weight", (S * I, H), True, sI)]
    out += [Leaf("embed", (V, H), False, sH),
            Leaf("norm", (H,), False, 0.1, ones=True)]
    if not m.get("tie_word_embeddings", True):
        out.append(Leaf("head", (H, V), False, sH))
    return out


def count_params(m: dict, layers: int) -> dict:
    """Parameters held here and parameters a token touches here (its share
    of the top-k experts: k x held / router width on average)."""
    per = {lf.name: int(np.prod(lf.shape)) for lf in leaf_specs(m)}
    flat = sum(per[k] for k in ("embed", "norm", "head") if k in per)
    stacked = sum(per.values()) - flat
    width, n, _ = held(m)
    bank = sum(per[k] for k in ("mlp.experts_gate", "mlp.experts_up",
                                "mlp.experts_down"))
    active = stacked - bank + bank * m["num_experts_per_tok"] // width
    return {"total": layers * stacked + flat,
            "active": layers * active + flat,
            "per_layer": stacked, "embed_and_head": flat}


# ------------------------------------------------------------- maths ----

def _layer_norm(x, w, eps):
    x = x.astype(F32)
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _attend_block(q, k, v, first, window):
    """q [G, Q, d] (queries at positions first .. first + Q), k/v [S, d]:
    causal softmax attention of one block of queries of one KV group; with
    ``window`` a query i keeps keys j, i - window < j <= i."""
    G, Q, d = q.shape
    S = k.shape[0]
    s = jnp.einsum("gqd,kd->gqk", q, k, precision=HI) / math.sqrt(d)
    i = first + jnp.arange(Q)[:, None]
    j = jnp.arange(S)[None, :]
    mask = j <= i
    if window is not None:
        mask = jnp.logical_and(mask, i - j < window)
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("gqk,kd->gqd", p, v, precision=HI)


def _attention(x, w, m, kind, precision):
    """x [S, H] (one sequence, S a multiple of Q_BLOCK) -> [S, H].  The
    scores exist for one block of queries of one KV group at a time."""
    S = x.shape[0]
    hq, hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    q = _mm(x, w["self_attn.q_proj.weight"], precision).reshape(S, hq, d)
    k = _mm(x, w["self_attn.k_proj.weight"], precision).reshape(S, hkv, d)
    v = _mm(x, w["self_attn.v_proj.weight"], precision).reshape(S, hkv, d)
    sliding = kind == "sliding_attention"
    if sliding:      # a full-attention layer carries no positional embedding
        theta = m["rope_theta"]
        q, k = _rope(q, theta), _rope(k, theta)
    window = int(m["sliding_window"]) if sliding else None
    nb = S // Q_BLOCK
    # [kv, blocks, G, Q, d]
    qg = q.reshape(nb, Q_BLOCK, hkv, hq // hkv, d).transpose(2, 0, 3, 1, 4)
    firsts = jnp.arange(nb) * Q_BLOCK

    def group(args):
        qh, kh, vh = args
        return jax.lax.map(
            lambda a: _attend_block(a[0], kh, vh, a[1], window),
            (qh, firsts))                                  # [blocks, G, Q, d]

    out = jax.lax.map(group, (qg, k.transpose(1, 0, 2),
                              v.transpose(1, 0, 2)))   # [kv, blocks, G, Q, d]
    out = out.transpose(1, 3, 0, 2, 4).reshape(S, hq * d)
    return _mm(out, w["self_attn.o_proj.weight"], precision)


def router_gates(x, w, m):
    """[S, router width] float32: g_e of the chosen experts, 0 elsewhere.
    Scores in float32 at "highest" whatever the precision."""
    k = m["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_mm(x, w["mlp.gate.weight"], "highest"))
    topv, topi = jax.lax.top_k(scores, k)
    if m.get("norm_topk_prob", True):
        topv = topv / topv.sum(-1, keepdims=True)
    return jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], topi].set(topv)


def routed_experts(x, w, m, precision):
    """The part of the routed sum that the experts held here give."""
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
    """The mean of the shared experts' outputs ("average")."""
    S, I = int(m["num_shared_experts"]), m["intermediate_size"]
    if not S:
        return jnp.zeros(x.shape, F32)
    if m.get("shared_expert_combination_strategy", "average") != "average":
        raise ValueError("only the average of the shared experts is known")
    outs = [_swiglu(x, w["mlp.shared_gate_proj.weight"][:, j * I:(j + 1) * I],
                    w["mlp.shared_up_proj.weight"][:, j * I:(j + 1) * I],
                    w["mlp.shared_down_proj.weight"][j * I:(j + 1) * I],
                    precision) for j in range(S)]
    return sum(outs) / S


def layer(w, x, m, kind, precision="highest"):
    """One decoder layer of ``kind`` on one sequence: x [S, H] float32."""
    u = _layer_norm(x, w["input_layernorm.weight"], m["layer_norm_eps"])
    return (x + _attention(u, w, m, kind, precision)
            + routed_experts(u, w, m, precision)
            + shared_experts(u, w, m, precision))


def head_logits(flat, x, m, precision="highest"):
    """Final norm and the tied head: x [N, H] -> logits [N, V] float32."""
    h = _layer_norm(x, flat["norm"], m["layer_norm_eps"])
    head = flat["head"] if "head" in flat else flat["embed"].T
    return _mm(h, head, precision) * m.get("logit_scale", 1)


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

    kinds = m["layer_types"]
    emb = jax.jit(lambda e, ids: jnp.take(e, ids, axis=0).astype(F32))
    xs = [emb(flat["embed"], pad(s)) for s in seqs]
    steps = {kind: jax.jit(lambda w, x, kind=kind:
                           layer(w, x, m, kind, precision))
             for kind in set(kinds[:layers])}
    for l in range(layers):
        w = get_layer(l)
        xs = [steps[kinds[l]](w, x) for x in xs]
        del w
    fin = jax.jit(lambda f, x, pos: head_logits(
        f, jnp.take(x, pos, axis=0), m, precision))
    out = []
    for x, p in zip(xs, positions):
        padded = list(p) + [p[-1]] * (-len(p) % 64)
        out.append(np.asarray(fin(flat, x, np.asarray(padded, np.int32)))
                   [:len(p)])
    return out
