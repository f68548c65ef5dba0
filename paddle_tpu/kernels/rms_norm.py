"""Shared RMSNorm primitive (fused_rms_norm slot —
paddle/phi/kernels/fusion/gpu fused_rms_norm; SURVEY.md §7.1).

One raw-array implementation used by nn.RMSNorm, models.llama.LlamaRMSNorm,
models.pretrain and incubate.nn.functional.fused_rms_norm so the fp32
accumulation / eps semantics stay in one place.  XLA fuses this into the
surrounding matmuls; a dedicated Pallas kernel is unnecessary on TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm_fp32(x, weight, eps: float, bias=None, axes=(-1,)):
    """RMSNorm with fp32 accumulation over ``axes``, returning x.dtype."""
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=axes, keepdims=True) + eps)
    out = h * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def layer_norm_fp32(x, weight, eps: float, axes=(-1,), bias=None):
    """LayerNorm (mean subtracted, variance normalised, a weight and, where
    given, a bias) with fp32 accumulation over ``axes``, returning
    x.dtype."""
    h = x.astype(jnp.float32)
    h = h - jnp.mean(h, axis=axes, keepdims=True)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=axes, keepdims=True) + eps)
    out = h * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)
