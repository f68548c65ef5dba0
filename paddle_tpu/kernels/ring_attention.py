"""Ring attention: exact attention over sequence-sharded Q/K/V.

The reference scales sequence length by sharding the seq dim (SEP axis /
DeepSpeed-Ulysses alltoall, SURVEY.md §5.7) but has no ring attention
in-tree; on TPU the ring formulation (Liu et al., blockwise attention with
rotating KV blocks over the ICI ring) is the natural fit and supersedes both
mechanisms: each device holds a sequence shard, KV blocks hop device-to-device
via `lax.ppermute` while the local flash accumulator (running max / denom /
weighted values) folds in each block — comms overlap compute around the ring,
and memory per device stays O(S/n).

Implemented as shard_map over the sequence mesh axis with a `lax.scan` over
ring steps; reverse-mode AD differentiates through scan+ppermute, giving the
backward ring for free.  Layout matches the flash kernel: [B, S, H, D].
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, q_off, k_off, scale, causal):
    """One (q-shard, kv-block) flash contribution.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D].  Returns (s_max, p_sum, pv) with
    shapes [B, H, Sq, 1], [B, H, Sq, 1], [B, H, Sq, D] in fp32.
    """
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale   # [B,H,Sq,D]
    kh = jnp.swapaxes(k, 1, 2).astype(jnp.float32)
    vh = jnp.swapaxes(v, 1, 2).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh)
    if causal:
        q_pos = q_off + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        k_pos = k_off + lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                    # [B,H,Sq,1]
    # fully-masked rows: exp(NEG_INF - NEG_INF) would be 1; zero them instead
    safe_m = jnp.maximum(m, jnp.float32(NEG_INF / 2))
    p = jnp.exp(s - safe_m) * (s > jnp.float32(NEG_INF / 2))
    return m, jnp.sum(p, axis=-1, keepdims=True), jnp.einsum(
        "bhqk,bhkd->bhqd", p, vh)


def ring_attention_arrays(q, k, v, mesh, axis: str = "sep", causal: bool = True):
    """Exact attention with Q/K/V sequence-sharded over `axis` (jax arrays)."""
    n = mesh.shape[axis]
    if n == 1:
        from .flash_attention import _reference_attention
        return _reference_attention(q, k, v, causal)
    scale = 1.0 / math.sqrt(q.shape[-1])
    perm = [(i, (i + 1) % n) for i in range(n)]
    auto = frozenset(a for a in mesh.axis_names if a != axis)

    def per_device(ql, kl, vl):
        # ql/kl/vl: local sequence shard [B, S/n, H, D]
        idx = lax.axis_index(axis)
        s_local = ql.shape[1]
        q_off = idx * s_local
        B, Sq, H, D = ql.shape
        # carries start device-invariant (zeros) but become varying through
        # the block math/ppermute; mark them for the scan vma check
        m = lax.pcast(jnp.full((B, H, Sq, 1), NEG_INF, jnp.float32),
                      (axis,), to="varying")
        l = lax.pcast(jnp.zeros((B, H, Sq, 1), jnp.float32), (axis,),
                      to="varying")
        acc = lax.pcast(jnp.zeros((B, H, Sq, D), jnp.float32), (axis,),
                        to="varying")
        kv = (kl, vl)

        def ring_step(carry, t):
            m, l, acc, (kc, vc) = carry
            k_off = ((idx - t) % n) * s_local
            bm, bsum, bpv = _block_attn(ql, kc, vc, q_off, k_off, scale, causal)
            m_new = jnp.maximum(m, bm)
            # renormalize both accumulators onto the new max
            alpha = jnp.exp(jnp.maximum(m, jnp.float32(NEG_INF / 2))
                            - jnp.maximum(m_new, jnp.float32(NEG_INF / 2))) \
                * (m > jnp.float32(NEG_INF / 2))
            beta = jnp.exp(jnp.maximum(bm, jnp.float32(NEG_INF / 2))
                           - jnp.maximum(m_new, jnp.float32(NEG_INF / 2))) \
                * (bm > jnp.float32(NEG_INF / 2))
            l = alpha * l + beta * bsum
            acc = alpha * acc + beta * bpv
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
            return (m_new, l, acc, (kc, vc)), None

        (m, l, acc, _), _ = lax.scan(ring_step, (m, l, acc, kv),
                                     jnp.arange(n, dtype=jnp.int32))
        out = acc / jnp.maximum(l, 1e-30)
        return jnp.swapaxes(out, 1, 2).astype(ql.dtype)     # [B, S/n, H, D]

    spec = P(None, axis, None, None)
    return jax.shard_map(per_device, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names={axis},
                         check_vma=True)(q, k, v)


def ring_flash_attention(query, key, value, mesh=None, axis: str = "sep",
                         causal: bool = True):
    """Tensor-level ring attention (context parallelism).

    With no mesh/hcg the call degrades to single-device flash attention.
    """
    from ..core.tensor import Tensor
    from ..ops._prim import apply_op

    if mesh is None:
        from ..distributed.fleet.topology import get_hcg
        hcg = get_hcg()
        mesh = hcg.global_mesh if hcg is not None else None
    if mesh is None or axis not in getattr(mesh, "axis_names", ()) or \
            mesh.shape[axis] == 1:
        from .flash_attention import flash_attention
        return flash_attention(query, key, value, causal=causal)

    args = tuple(a if isinstance(a, Tensor) else Tensor(a)
                 for a in (query, key, value))
    return apply_op(
        "ring_attention",
        lambda q, k, v: ring_attention_arrays(q, k, v, mesh, axis, causal),
        args)
