"""Plain reference for the Llama family of decoders (Mistral, Mixtral).

Straightforward ``jax.numpy`` in float32, matmul precision "highest", no
kernels, no cache, no batching tricks; it imports nothing of the program and
takes nothing the program made.  Pre-norm decoder: RMSNorm, grouped-query
causal attention with rotary embeddings, SwiGLU MLP or a top-k mixture of
SwiGLU experts (softmax over all experts, top-k, renormalised: Mixtral's
router), final RMSNorm, untied head.

Departure from the published description, noted: rotary pairs are the
interleaved ones (x[2i], x[2i+1]), as the program lays its heads out;
Hugging Face's half-rotation is the same map under a fixed permutation of
each head's columns of W_q and W_k, which seeded random weights make
immaterial.

``precision="int8"`` is the CONTROL (the nearest precision below bf16): both
operands of every projection, and of its two backward products, are rounded
to 8 bits along the contracted axis (absmax scales) before the float32
product.  The checks must refuse it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness.weights import Leaf

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# ------------------------------------------------------------ leaves ----

def leaf_specs(m: dict) -> list:
    """Every parameter of the model ``m`` (a configuration file's sizes):
    name, per-layer shape, std of its normal draw.  Weights are [in, out]."""
    H, I, V = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    hd = m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    E = int(m.get("num_local_experts") or 0)
    sH, sI = 1.0 / math.sqrt(H), 1.0 / math.sqrt(I)
    out = [
        Leaf("self_attn.q_proj.weight", (H, q), True, sH),
        Leaf("self_attn.k_proj.weight", (H, kv), True, sH),
        Leaf("self_attn.v_proj.weight", (H, kv), True, sH),
        Leaf("self_attn.o_proj.weight", (q, H), True, 1.0 / math.sqrt(q)),
        Leaf("input_layernorm.weight", (H,), True, 0.1, ones=True),
        Leaf("post_attention_layernorm.weight", (H,), True, 0.1, ones=True),
    ]
    if E:
        out += [Leaf("mlp.gate.weight", (H, E), True, sH),
                Leaf("mlp.experts_gate", (E, H, I), True, sH),
                Leaf("mlp.experts_up", (E, H, I), True, sH),
                Leaf("mlp.experts_down", (E, I, H), True, sI)]
    else:
        out += [Leaf("mlp.gate_proj.weight", (H, I), True, sH),
                Leaf("mlp.up_proj.weight", (H, I), True, sH),
                Leaf("mlp.down_proj.weight", (I, H), True, sI)]
    out += [Leaf("embed", (V, H), False, sH),
            Leaf("head", (H, V), False, sH),
            Leaf("norm", (H,), False, 0.1, ones=True)]
    return out


def count_params(m: dict, layers: int) -> dict:
    """Parameters held and parameters a token touches (top-k experts)."""
    per = {lf.name: int(np.prod(lf.shape)) for lf in leaf_specs(m)}
    stacked = sum(n for k, n in per.items()
                  if k not in ("embed", "head", "norm"))
    flat = per["embed"] + per["head"] + per["norm"]
    E = int(m.get("num_local_experts") or 0)
    active = stacked
    if E:
        bank = sum(per[k] for k in ("mlp.experts_gate", "mlp.experts_up",
                                    "mlp.experts_down"))
        active = stacked - bank + bank * m["num_experts_per_tok"] // E
    return {"total": layers * stacked + flat,
            "active": layers * active + flat,
            "per_layer": stacked, "embed_and_head": flat}


# ------------------------------------------------------------- maths ----

def _q8(x, axis):
    """Round to 8 bits along ``axis``: symmetric, scaled by the absmax."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@jax.custom_vjp
def _mm_int8(a, w):
    """a [S, K] @ w [K, N] as an int8 matmul would compute it: both
    operands of the product, AND both operands of each of the two backward
    products, rounded to 8 bits along the contracted axis."""
    return jnp.matmul(_q8(a, -1), _q8(w, -2), precision=HI)


def _mm_int8_fwd(a, w):
    return _mm_int8(a, w), (a, w)


def _mm_int8_bwd(res, g):
    a, w = res
    da = jnp.matmul(_q8(g, -1), _q8(w, -1).T, precision=HI)
    dw = jnp.matmul(_q8(a, -2).T, _q8(g, -2), precision=HI)
    return da, dw


_mm_int8.defvjp(_mm_int8_fwd, _mm_int8_bwd)


def _mm(a, w, precision):
    a, w = a.astype(F32), w.astype(F32)
    if precision == "int8":
        return _mm_int8(a, w)
    return jnp.matmul(a, w, precision=HI)


def _rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, theta):
    """x [S, heads, d]: rotate the pairs (x[2i], x[2i+1]) by pos * freq_i."""
    S, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _attend_group(q, k, v):
    """q [G, S, d], k/v [S, d]: causal softmax attention of one KV group."""
    S, d = k.shape
    s = jnp.einsum("gqd,kd->gqk", q, k, precision=HI) / math.sqrt(d)
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("gqk,kd->gqd", p, v, precision=HI)


def _attention(x, w, m, precision):
    """x [S, H] (one sequence) -> [S, H]."""
    S = x.shape[0]
    hq, hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    q = _mm(x, w["self_attn.q_proj.weight"], precision).reshape(S, hq, d)
    k = _mm(x, w["self_attn.k_proj.weight"], precision).reshape(S, hkv, d)
    v = _mm(x, w["self_attn.v_proj.weight"], precision).reshape(S, hkv, d)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    qg = q.reshape(S, hkv, hq // hkv, d).transpose(1, 2, 0, 3)  # [kv,G,S,d]
    out = jax.lax.map(
        lambda a: jax.checkpoint(_attend_group)(*a),
        (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))       # [kv,G,S,d]
    out = out.transpose(2, 0, 1, 3).reshape(S, hq * d)
    return _mm(out, w["self_attn.o_proj.weight"], precision)


def _swiglu(x, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(x, wg, precision)) * _mm(x, wu, precision),
               wd, precision)


def _mlp(x, w, m, precision):
    E = int(m.get("num_local_experts") or 0)
    if not E:
        return _swiglu(x, w["mlp.gate_proj.weight"], w["mlp.up_proj.weight"],
                       w["mlp.down_proj.weight"], precision)
    k = m["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm(x, w["mlp.gate.weight"], "highest"), -1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / topv.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], topi].set(topv)         # [S, E]

    def one(args):
        wg, wu, wd, g = args
        return _swiglu(x, wg, wu, wd, precision) * g[:, None]

    ys = jax.lax.map(one, (w["mlp.experts_gate"], w["mlp.experts_up"],
                           w["mlp.experts_down"], gate.T))
    return ys.sum(0)


def layer(w, x, m, precision="highest"):
    """One decoder layer on one sequence: x [S, H] float32 -> [S, H]."""
    eps = m["rms_norm_eps"]
    h = x + _attention(_rms(x, w["input_layernorm.weight"], eps), w, m,
                       precision)
    return h + _mlp(_rms(h, w["post_attention_layernorm.weight"], eps), w, m,
                    precision)


def head_logits(flat, x, m, precision="highest"):
    """Final norm and head: x [N, H] -> logits [N, V] float32."""
    return _mm(_rms(x, flat["norm"], m["rms_norm_eps"]), flat["head"],
               precision)


# ----------------------------------------------------------- serving ----

PAD_TO = 1024       # sequences are padded to a multiple: few shapes compile


def sequence_logits(get_layer, flat, layers, m, seqs, positions,
                    precision="highest"):
    """Logits of the reference at chosen positions of whole sequences.

    ``seqs``: lists of token ids (prompt + served tokens); ``positions[i]``
    the positions of sequence i whose next-token logits are wanted.
    ``get_layer(l)`` hands out layer l's weights (made from the seed, never
    the program's).  Layers outermost, so one layer's weights live at a time.
    Sequences are padded at the END to a multiple of ``PAD_TO`` (causal: no
    earlier position sees the padding) and the positions to a multiple of
    64, so a handful of shapes compile whatever the seed's sample is."""
    def pad(ids):
        n = -(-len(ids) // PAD_TO) * PAD_TO
        return np.asarray(list(ids) + [0] * (n - len(ids)), np.int32)

    emb = jax.jit(lambda e, ids: jnp.take(e, ids, axis=0).astype(F32))
    xs = [emb(flat["embed"], pad(s)) for s in seqs]
    step = jax.jit(lambda w, x: layer(w, x, m, precision))
    for l in range(layers):
        w = get_layer(l)
        xs = [step(w, x) for x in xs]
        del w
    fin = jax.jit(lambda f, x, pos: head_logits(
        f, jnp.take(x, pos, axis=0), m, precision))
    out = []
    for x, p in zip(xs, positions):
        padded = list(p) + [p[-1]] * (-len(p) % 64)
        out.append(np.asarray(fin(flat, x, np.asarray(padded, np.int32)))
                   [:len(p)])
    return out


# ---------------------------------------------------------- training ----

def _chunked_loss(flat_nh, x, labels, m, precision, chunks=8):
    """Mean next-token cross-entropy of x [N, H] against labels [N], the
    head applied to ``chunks`` blocks of rows so [N, V] never exists."""
    N = x.shape[0]
    while N % chunks:
        chunks //= 2
    xc = x.reshape(chunks, N // chunks, -1)
    lc = labels.reshape(chunks, N // chunks)

    @jax.checkpoint
    def one(args):
        xb, lb = args
        lg = head_logits(flat_nh, xb, m, precision)
        return (jax.nn.logsumexp(lg, -1)
                - jnp.take_along_axis(lg, lb[:, None], -1)[:, 0]).sum()

    return jax.lax.map(one, (xc, lc)).sum() / N


def adamw(p, g, mo, vo, t, hp):
    """One AdamW update in float32 (decoupled decay, bias-corrected)."""
    b1, b2 = hp["beta1"], hp["beta2"]
    mo = b1 * mo + (1 - b1) * g
    vo = b2 * vo + (1 - b2) * g * g
    u = (mo / (1 - b1 ** t)) / (jnp.sqrt(vo / (1 - b2 ** t)) + hp["eps"])
    return p - hp["learning_rate"] * (u + hp["weight_decay"] * p), mo, vo


def train_steps(seed_layer, flat0, layers, m, batches, hp, steps,
                precision="highest", devices=None):
    """Follow ``steps`` AdamW steps in float32 from the seeded weights.

    ``batches[i]`` is step i's [rows, S+1] token ids.  Backward goes layer
    by layer (``jax.vjp`` of ``layer``), each layer updated as soon as its
    gradient exists, so one layer's gradient lives at a time.  ``devices``
    spreads the layers' state round-robin (a depth one device cannot hold).
    Returns the losses, the per-leaf norms of the first gradient, and the
    per-leaf norms of the parameters' change after the last step."""
    devices = devices or [jax.devices()[0]]
    dev = lambda l: devices[l % len(devices)]            # noqa: E731
    up = lambda t, d: jax.device_put(                    # noqa: E731
        jax.tree_util.tree_map(lambda a: jnp.array(a, F32, copy=True), t), d)
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)  # noqa: E731
    P = [up(seed_layer(l), dev(l)) for l in range(layers)]
    M, V = [zeros(p) for p in P], [zeros(p) for p in P]
    flat = up(flat0, devices[0])
    fm, fv = zeros(flat), zeros(flat)

    def layers_fn(w, x):
        return jax.vmap(lambda r: layer(w, r, m, precision))(x)

    fwd = jax.jit(layers_fn)
    embed = jax.jit(lambda e, j: jnp.take(e, j, axis=0))
    sq_norms = jax.jit(lambda t: {k: jnp.sum(jnp.square(v))
                                  for k, v in t.items()})
    sq_diff = jax.jit(lambda a, b: {k: jnp.sum(jnp.square(a[k] - b[k]))
                                    for k in a})

    def update(w, g, mo, vo, t):
        new = {k: adamw(w[k], g[k], mo[k], vo[k], t, hp) for k in w}
        return tuple({k: n[i] for k, n in new.items()} for i in range(3))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def bwd_update(w, mo, vo, x, dy, t):
        _, vjp = jax.vjp(layers_fn, w, x)
        g, dx = vjp(dy)
        return update(w, g, mo, vo, t) + (dx, sq_norms(g))

    @jax.jit
    def top(flat, x, labels):
        def loss_fn(nh, x_):
            return _chunked_loss(nh, x_.reshape(-1, x_.shape[-1]),
                                 labels.reshape(-1), m, precision)
        nh = {"norm": flat["norm"], "head": flat["head"]}
        return jax.value_and_grad(loss_fn, (0, 1))(nh, x)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def bottom(flat, fm, fv, g_nh, dx0, ids, t):
        g = dict(g_nh)
        g["embed"] = jnp.zeros_like(flat["embed"]).at[ids.reshape(-1)].add(
            dx0.reshape(-1, dx0.shape[-1]))
        return update(flat, g, fm, fv, t) + (sq_norms(g),)

    def total(per_layer_sq, flat_sq):
        out = {f"blocks.{k}": math.sqrt(sum(float(d[k])
                                            for d in per_layer_sq))
               for k in per_layer_sq[0]}
        out.update({k: math.sqrt(float(v)) for k, v in flat_sq.items()})
        return out

    losses, grad_norms = [], None
    for i in range(steps):
        t = jnp.float32(i + 1)
        ids_all = np.asarray(batches[i])
        ids, labels = ids_all[:, :-1], ids_all[:, 1:]
        xs = [embed(flat["embed"], ids)]
        for l in range(layers):
            xs[-1] = jax.device_put(xs[-1], dev(l))   # layer l's own input
            xs.append(fwd(P[l], xs[-1]))
        loss, (g_nh, dx) = top(flat, jax.device_put(xs[-1], devices[0]),
                               labels)
        losses.append(float(loss))
        g_sq = [None] * layers
        for l in reversed(range(layers)):
            P[l], M[l], V[l], dx, g_sq[l] = bwd_update(
                P[l], M[l], V[l], xs[l], jax.device_put(dx, dev(l)), t)
            xs[l + 1] = None
        flat, fm, fv, f_sq = bottom(flat, fm, fv, g_nh,
                                    jax.device_put(dx, devices[0]), ids, t)
        if i == 0:
            grad_norms = total(g_sq, f_sq)
    # the parameters' change: against the seeded weights, made again
    change = total(
        [sq_diff(P[l], up(seed_layer(l), dev(l))) for l in range(layers)],
        sq_diff(flat, up(flat0, devices[0])))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
