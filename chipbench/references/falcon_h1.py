"""Plain reference for the ``falcon_h1`` family (Falcon-H1-34B-Instruct): the
layer equations as the source's ``config.json`` and the catalog's
``described_as`` give them ("parallel Mamba-2 + attention heads per block"),
in ``jax.numpy``, float32, matmul precision "highest"; the state-space
branch is the BARE RECURRENCE by ``lax.scan`` over tokens (the program
computes the chunk form: this is the other way to the same numbers),
attention in blocks of queries; no kernel, no cache, no batching; it
imports nothing of the program and takes nothing the program made.

One layer, ``h`` the residual stream of the token at position ``p``,
``u = RMSNorm_in(h)`` (eps ``rms_norm_eps``), every multiplier applied as
written:

    attention   q = W_q (u x attention_in_multiplier)        heads x head_dim
                k = key_multiplier x W_k (u x attention_in_multiplier)
                v = W_v (u x attention_in_multiplier)        kv heads
                rotary on q and k over the whole head, rope_theta, no scaling
                a = attention_out_multiplier x W_o [softmax_causal(q k^T /
                    sqrt(head_dim)) v]
    mixer       [z | xBC | dt] = (W_in (u x ssm_in_multiplier)) * m
                    widths d_ssm | d_ssm + 2 groups x d_state | heads; m the
                    zones z, x, B, C, dt times ssm_multipliers
                xBC_t = silu(b + sum_{j<d_conv} w_j xBC_{t-d_conv+1+j})
                    (causal, depthwise, zeros before the sequence)
                dt_t = softplus(dt_t + dt_bias), A = -exp(A_log)   a head
                S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T         S_0 = 0
                y_t = S_t C_t + D x_t      (heads of group g read B, C of g)
                g = RMSNorm over each group of (y * silu(z)), a learned
                    weight of d_ssm, eps rms_norm_eps
                s = ssm_out_multiplier x W_out g
    block       h' = h + a + s
                h'' = h' + mlp_multipliers[1] x W_down(W_up v *
                    silu(mlp_multipliers[0] x W_gate v)),  v = RMSNorm_ff(h')
    ends        h_0 = embedding_multiplier x E[token]
                logits = lm_head_multiplier x W_head RMSNorm_f(h_L)

Departures from the published description (the configuration's file lists
them under ``assumed``):
(1) ASSUMED: the five ``ssm_multipliers`` scale the zones in the order of
``in_proj``'s output, ``z, x, B, C, dt`` (five numbers for five zones).
(2) ASSUMED: rotary acts on the interleaved pairs (2i, 2i+1); the source's
half-rotation is the same map under a fixed permutation of columns, which
seeded weights make immaterial.
(3) The recurrent state is float32 here as everything is; the PROGRAM holds
it in float32 too (the file states so) and the convolution's rows in bf16.
(4) ASSUMED: ``dt`` has no upper clamp (the config has no
``time_step_limit``).
(5) ``attention_in_multiplier`` scales the input of all three projections
(the published value is 1: it moves nothing).
(6) The second norm is named ``post_attention_layernorm`` (the source's
``pre_ff_layernorm``) and the convolution is stored ``[taps, channels]``:
names and a storage layout.

``precision="int8"`` is the CONTROL (the nearest precision below bf16), as
in ``references/llama.py``: both operands of every projection rounded to 8
bits along the contracted axis.  The convolution, the recurrence and the
norms stay in float32 in the control too.  The checks must refuse it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness.weights import Leaf
from chipbench.references.llama import F32, HI, PAD_TO, _mm, _rms

Q_BLOCK = 512        # queries a block of attention scores holds (PAD_TO % it)


# ------------------------------------------------------------ leaves ----

def widths(m: dict) -> dict:
    """The mixer's widths from the source's keys."""
    d, g, n = m["mamba_d_ssm"], m["mamba_n_groups"], m["mamba_d_state"]
    return {"inner": d, "bc": g * n, "conv": d + 2 * g * n,
            "in": 2 * d + 2 * g * n + m["mamba_n_heads"]}


def leaf_specs(m: dict) -> list:
    """Every parameter of the model ``m``: name, per-layer shape, std of
    its normal draw.  Weights are [in, out]; the names are the program's.
    ``dt_bias`` and ``A_log`` are drawn wide, so that the heads' time
    scales spread as a trained mixer's do: ``dt x |A|`` from under a
    thousandth (a state that remembers a thousand tokens) to over ten (one
    that forgets within a token)."""
    H, I, V = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    hd = m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    w = widths(m)
    heads, taps = m["mamba_n_heads"], m["mamba_d_conv"]
    sH, sI = 1.0 / math.sqrt(H), 1.0 / math.sqrt(I)
    return [
        Leaf("self_attn.q_proj.weight", (H, q), True, sH),
        Leaf("self_attn.k_proj.weight", (H, kv), True, sH),
        Leaf("self_attn.v_proj.weight", (H, kv), True, sH),
        Leaf("self_attn.o_proj.weight", (q, H), True, 1.0 / math.sqrt(q)),
        Leaf("input_layernorm.weight", (H,), True, 0.1, ones=True),
        Leaf("post_attention_layernorm.weight", (H,), True, 0.1, ones=True),
        Leaf("mamba.in_proj.weight", (H, w["in"]), True, sH),
        Leaf("mamba.conv1d.weight", (taps, w["conv"]), True,
             1.0 / math.sqrt(taps)),
        Leaf("mamba.conv1d.bias", (w["conv"],), True, 0.5),
        Leaf("mamba.dt_bias", (heads,), True, 3.0),
        Leaf("mamba.A_log", (heads,), True, 1.0),
        Leaf("mamba.D", (heads,), True, 0.1, ones=True),
        Leaf("mamba.norm.weight", (w["inner"],), True, 0.1, ones=True),
        Leaf("mamba.out_proj.weight", (w["inner"], H), True,
             1.0 / math.sqrt(w["inner"])),
        Leaf("mlp.gate_proj.weight", (H, I), True, sH),
        Leaf("mlp.up_proj.weight", (H, I), True, sH),
        Leaf("mlp.down_proj.weight", (I, H), True, sI),
        Leaf("embed", (V, H), False, sH),
        Leaf("head", (H, V), False, sH),
        Leaf("norm", (H,), False, 0.1, ones=True),
    ]


def count_params(m: dict, layers: int) -> dict:
    """Parameters held (a dense model: a token touches them all)."""
    per = {lf.name: int(np.prod(lf.shape)) for lf in leaf_specs(m)}
    flat = per["embed"] + per["head"] + per["norm"]
    stacked = sum(per.values()) - flat
    total = layers * stacked + flat
    return {"total": total, "active": total, "per_layer": stacked,
            "embed_and_head": flat}


# ------------------------------------------------------------- maths ----

def _rope(x, theta):
    """x [S, heads, d]: rotate the pairs (x[2i], x[2i+1]) by pos x freq_i
    (departure 2)."""
    S, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _attend_block(q, k, v, first):
    """q [Q, d] (queries at positions first .. first + Q), k, v [S, d]:
    causal softmax attention of one block of one head."""
    s = jnp.einsum("qd,kd->qk", q, k, precision=HI) / math.sqrt(q.shape[-1])
    i = first + jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    p = jax.nn.softmax(jnp.where(j <= i, s, -jnp.inf), axis=-1)
    return jnp.einsum("qk,kd->qd", p, v, precision=HI)


def attention(u, w, m, precision):
    """The attention branch on the normed input: u [S, H] (S a multiple of
    Q_BLOCK) -> [S, H], one head and one block of queries at a time."""
    S = u.shape[0]
    hq, hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    ua = u * m["attention_in_multiplier"]                  # departure 5
    q = _mm(ua, w["self_attn.q_proj.weight"], precision).reshape(S, hq, d)
    k = (m["key_multiplier"]
         * _mm(ua, w["self_attn.k_proj.weight"], precision)).reshape(
        S, hkv, d)
    v = _mm(ua, w["self_attn.v_proj.weight"], precision).reshape(S, hkv, d)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
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
    return m["attention_out_multiplier"] \
        * _mm(out, w["self_attn.o_proj.weight"], precision)


def recurrence(x, b, c, dt, A, D):
    """The bare recurrence over one sequence from ``S_0 = 0``: x [S, heads,
    P], b and c [S, groups, N], dt [S, heads], A and D [heads] -> y [S,
    heads, P], float32."""
    heads, P = x.shape[1:]
    rep = heads // b.shape[1]

    def step(s, t):
        xt, bt, ct, dtt = t
        bt, ct = jnp.repeat(bt, rep, axis=0), jnp.repeat(ct, rep, axis=0)
        s = jnp.exp(dtt * A)[:, None, None] * s \
            + dtt[:, None, None] * xt[:, :, None] * bt[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, ct, precision=HI) \
            + D[:, None] * xt

    s0 = jnp.zeros((heads, P, b.shape[-1]), F32)
    return jax.lax.scan(step, s0, (x, b, c, dt))[1]


def mixer(u, w, m, precision):
    """The state-space branch on the normed input: u [S, H] -> [S, H]."""
    S = u.shape[0]
    wd = widths(m)
    d, n = wd["inner"], wd["bc"]
    heads, groups, taps = (m["mamba_n_heads"], m["mamba_n_groups"],
                           m["mamba_d_conv"])
    kz, kx, kb, kc, kd = m["ssm_multipliers"]              # departure 1
    zxd = _mm(u * m["ssm_in_multiplier"], w["mamba.in_proj.weight"],
              precision)
    z, dt = zxd[:, :d] * kz, zxd[:, d + wd["conv"]:] * kd
    xbc = jnp.concatenate([zxd[:, d:2 * d] * kx,
                           zxd[:, 2 * d:2 * d + n] * kb,
                           zxd[:, 2 * d + n:2 * d + 2 * n] * kc], -1)
    ext = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    taps_w = w["mamba.conv1d.weight"].astype(F32)
    acc = w["mamba.conv1d.bias"].astype(F32)
    for j in range(taps):
        acc = acc + taps_w[j] * ext[j:j + S]
    xbc = jax.nn.silu(acc)
    dt = jax.nn.softplus(dt + w["mamba.dt_bias"].astype(F32))  # departure 4
    y = recurrence(
        xbc[:, :d].reshape(S, heads, d // heads),
        xbc[:, d:d + n].reshape(S, groups, n // groups),
        xbc[:, d + n:].reshape(S, groups, n // groups), dt,
        -jnp.exp(w["mamba.A_log"].astype(F32)), w["mamba.D"].astype(F32))
    g = (y.reshape(S, d) * jax.nn.silu(z)).reshape(S, groups, d // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + m["rms_norm_eps"])
    g = g.reshape(S, d) * w["mamba.norm.weight"].astype(F32)
    return m["ssm_out_multiplier"] \
        * _mm(g, w["mamba.out_proj.weight"], precision)


def mlp(v, w, m, precision):
    kg, kd = m["mlp_multipliers"]
    act = _mm(v, w["mlp.up_proj.weight"], precision) * jax.nn.silu(
        kg * _mm(v, w["mlp.gate_proj.weight"], precision))
    return kd * _mm(act, w["mlp.down_proj.weight"], precision)


def layer(w, x, m, precision="highest"):
    """One decoder layer on one sequence: x [S, H] float32."""
    eps = m["rms_norm_eps"]
    u = _rms(x, w["input_layernorm.weight"], eps)
    x = x + attention(u, w, m, precision) + mixer(u, w, m, precision)
    return x + mlp(_rms(x, w["post_attention_layernorm.weight"], eps), w, m,
                   precision)


def head_logits(flat, x, m, precision="highest"):
    """Final norm and the untied head: x [N, H] -> logits [N, V] float32."""
    return m["lm_head_multiplier"] * _mm(
        _rms(x, flat["norm"], m["rms_norm_eps"]), flat["head"], precision)


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

    emb = jax.jit(lambda e, ids: m["embedding_multiplier"]
                  * jnp.take(e, ids, axis=0).astype(F32))
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
