"""Plain reference for the ``smallthinker`` family
(SmallThinker-21BA3B-Instruct): the layer equations as the source's
``config.json`` and the catalog's ``described_as`` give them, in
``jax.numpy``, float32, matmul precision "highest"; no kernel, no cache, no
batching, one expert at a time; it imports nothing of the program and takes
nothing the program made.

One layer ``l`` (``x`` the residual stream):

    u = RMSNorm(x; w_in, rms_norm_eps)
    r = u W_r                 moe_num_primary_experts logits, float32: the
                              router reads u, the ATTENTION's input
    S = the moe_num_active_primary_experts largest of r (a sort; a tie goes
        to the lower number);  g_S = softmax(r_S)
                              (= softmax over all, the chosen renormalised:
                              moe_primary_router_apply_softmax,
                              norm_topk_prob)
    q, k, v = u W_q, u W_k, u W_v   heads x head_dim, kv heads x head_dim,
                              no bias, no q/k norm
    q, k = rope(q), rope(k)   where rope_layout[l] == 1, theta = rope_theta,
                              no scaling; else as they are
    a = softmax(q k^T / sqrt(head_dim)) v over keys s <= p, and
        s > p - sliding_window_size where sliding_window_layout[l] == 1
    h = x + a W_o
    z = RMSNorm(h; w_post, rms_norm_eps)
    f = sum over e in S of g_e W_down,e (relu(W_gate,e z) * (W_up,e z))
    x' = h + f

and after the last layer a final RMSNorm and an untied head.

Departures from the published description, each a key of the configuration
file's ``assumed`` and noted where it is made: the router's input is the
output of the input norm (the tensor the attention projects: "router placed
before attention"); the gate's activation is ReLU ("sparse ReGLU"); top-k
first, then the softmax over the chosen; no secondary experts (``config``
has keys for primary ones only); rotary pairs are the interleaved ones
(x[2i], x[2i+1]), as ``references/llama.py`` documents: Hugging Face's
half-rotation is the same map under a fixed permutation of each head's
columns of W_q and W_k, which seeded random weights make immaterial.

``ASSUMED`` holds the two that no key of the source states; a test that
wants to see what each of them moves hands ``layer`` another.

``precision="int8"`` is the CONTROL (the nearest precision below bf16), as
in ``references/llama.py``: both operands of every projection rounded to 8
bits along the contracted axis.  The router's logits stay in float32
"highest" in the control too (the configuration states them in float32).
The checks must refuse it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness.weights import Leaf
from chipbench.references.llama import F32, HI, PAD_TO, _mm, _rms, _rope

Q_BLOCK = 512        # queries a block of attention scores holds (PAD_TO % it)

# what the catalog's description says in words and no key of the source
# states: which tensor the router reads ("attention": u, the attention's
# normed input; "ffn": z, the experts' own) and the gate's activation
ASSUMED = {"router_input": "attention", "activation": "relu"}
_ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


# ------------------------------------------------------------ leaves ----

def leaf_specs(m: dict) -> list:
    """Every parameter of the model ``m``: name, per-layer shape, std of
    its normal draw.  Weights are [in, out]; the names are the program's."""
    H, I, V = m["hidden_size"], m["moe_ffn_hidden_size"], m["vocab_size"]
    hd, E = m["head_dim"], m["moe_num_primary_experts"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    sH, sI = 1.0 / math.sqrt(H), 1.0 / math.sqrt(I)
    return [
        Leaf("self_attn.q_proj.weight", (H, q), True, sH),
        Leaf("self_attn.k_proj.weight", (H, kv), True, sH),
        Leaf("self_attn.v_proj.weight", (H, kv), True, sH),
        Leaf("self_attn.o_proj.weight", (q, H), True, 1.0 / math.sqrt(q)),
        Leaf("input_layernorm.weight", (H,), True, 0.1, ones=True),
        Leaf("post_attention_layernorm.weight", (H,), True, 0.1, ones=True),
        Leaf("mlp.gate.weight", (H, E), True, sH),
        Leaf("mlp.experts_gate", (E, H, I), True, sH),
        Leaf("mlp.experts_up", (E, H, I), True, sH),
        Leaf("mlp.experts_down", (E, I, H), True, sI),
        Leaf("embed", (V, H), False, sH),
        Leaf("head", (H, V), False, sH),
        Leaf("norm", (H,), False, 0.1, ones=True),
    ]


def count_params(m: dict, layers: int) -> dict:
    """Parameters held and parameters a token touches (its chosen
    experts)."""
    per = {lf.name: int(np.prod(lf.shape)) for lf in leaf_specs(m)}
    flat = per["embed"] + per["head"] + per["norm"]
    stacked = sum(per.values()) - flat
    bank = sum(per[k] for k in ("mlp.experts_gate", "mlp.experts_up",
                                "mlp.experts_down"))
    active = stacked - bank + bank * m["moe_num_active_primary_experts"] \
        // m["moe_num_primary_experts"]
    return {"total": layers * stacked + flat,
            "active": layers * active + flat,
            "per_layer": stacked, "embed_and_head": flat}


# ------------------------------------------------------------- maths ----

def _attend_block(q, k, v, first, window):
    """q [G, Q, d] (queries at positions first .. first + Q), k/v [S, d]:
    causal softmax attention of one block of queries of one KV group; with
    ``window`` a query p keeps keys s, p - window < s <= p."""
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


def _attention(u, w, m, rotates: bool, windowed: bool, precision):
    """u [S, H] (one sequence, S a multiple of Q_BLOCK) -> [S, H].  The
    scores exist for one block of queries of one KV group at a time, so a
    long sequence fits beside the layer's weights."""
    S = u.shape[0]
    hq, hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    q = _mm(u, w["self_attn.q_proj.weight"], precision).reshape(S, hq, d)
    k = _mm(u, w["self_attn.k_proj.weight"], precision).reshape(S, hkv, d)
    v = _mm(u, w["self_attn.v_proj.weight"], precision).reshape(S, hkv, d)
    if rotates:          # a layer with rope_layout 0 carries no positions
        theta = m["rope_theta"]
        q, k = _rope(q, theta), _rope(k, theta)
    window = int(m["sliding_window_size"]) if windowed else None
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


def router_gates(y, w, m):
    """[S, experts] float32: g_e of the chosen experts, 0 elsewhere, from
    the tensor ``y`` the router reads.  Logits in float32 at "highest"
    whatever the precision; the chosen are the largest by a stable sort of
    the negated logits (a tie goes to the lower number)."""
    k = m["moe_num_active_primary_experts"]
    if not m.get("moe_primary_router_apply_softmax", True):
        raise ValueError("only a router whose gates are a softmax is known")
    logits = _mm(y, w["mlp.gate.weight"], "highest")
    topi = jnp.argsort(-logits, axis=-1, stable=True)[:, :k]
    chosen = jnp.take_along_axis(logits, topi, axis=-1)
    if m.get("norm_topk_prob", True):
        g = jax.nn.softmax(chosen, axis=-1)      # over the chosen alone
    else:                                # over all, the chosen as they are
        g = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), topi, -1)
    return jnp.zeros_like(logits).at[
        jnp.arange(y.shape[0])[:, None], topi].set(g)


def _glu(z, wg, wu, wd, act, precision):
    return _mm(act(_mm(z, wg, precision)) * _mm(z, wu, precision), wd,
               precision)


def routed_experts(z, gate, w, act, precision):
    """sum_e g_e Expert_e(z): one expert at a time over every row, summed
    as they come (an expert nobody chose adds zeros)."""
    def one(acc, args):
        wg, wu, wd, g = args
        return acc + _glu(z, wg, wu, wd, act, precision) * g[:, None], None

    out, _ = jax.lax.scan(one, jnp.zeros(z.shape, F32), (
        w["mlp.experts_gate"], w["mlp.experts_up"], w["mlp.experts_down"],
        gate.T))
    return out


def layer(w, x, m, rotates: bool, windowed: bool, precision="highest",
          assumed=None):
    """One decoder layer on one sequence: x [S, H] float32."""
    assumed = ASSUMED if assumed is None else assumed
    eps = m["rms_norm_eps"]
    u = _rms(x, w["input_layernorm.weight"], eps)
    early = assumed["router_input"] == "attention"
    if early:
        gate = router_gates(u, w, m)
    h = x + _attention(u, w, m, rotates, windowed, precision)
    z = _rms(h, w["post_attention_layernorm.weight"], eps)
    if not early:
        gate = router_gates(z, w, m)
    return h + routed_experts(z, gate, w,
                              _ACTIVATIONS[assumed["activation"]], precision)


def head_logits(flat, x, m, precision="highest"):
    """Final norm and the untied head: x [N, H] -> logits [N, V] float32."""
    return _mm(_rms(x, flat["norm"], m["rms_norm_eps"]), flat["head"],
               precision)


# ----------------------------------------------------------- serving ----

def sequence_logits(get_layer, flat, layers, m, seqs, positions,
                    precision="highest", assumed=None):
    """Logits of the reference at chosen positions of whole sequences; the
    surface of ``references/llama.py::sequence_logits`` (layers outermost,
    one layer's weights at a time; sequences padded at the END to a
    multiple of ``PAD_TO``, positions to a multiple of 64)."""
    def pad(ids):
        n = -(-len(ids) // PAD_TO) * PAD_TO
        return np.asarray(list(ids) + [0] * (n - len(ids)), np.int32)

    kinds = list(zip(m["rope_layout"], m["sliding_window_layout"]))[:layers]
    emb = jax.jit(lambda e, ids: jnp.take(e, ids, axis=0).astype(F32))
    xs = [emb(flat["embed"], pad(s)) for s in seqs]
    steps = {kind: jax.jit(lambda w, x, kind=kind: layer(
        w, x, m, bool(kind[0]), bool(kind[1]), precision, assumed))
        for kind in set(kinds)}
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
