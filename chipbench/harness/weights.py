"""Weights from ``--seed``, made on the device, in the type they are served in.

A leaf's values depend on (seed, leaf name, index along its leading dims)
and on nothing else, so the program's stacked ``[L, ...]`` leaves (one jitted
call, ``make``) and the reference's one-layer-at-a-time leaves
(``make_layer``) are the same numbers without either taking them from the
other.  ``seed`` is a traced argument: every seed runs one compiled program.
"""

from __future__ import annotations

import functools
import zlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Leaf:
    """One parameter: its per-layer ``shape``, whether it is stacked over
    layers, and how it is drawn (``std`` of a normal; ``ones`` adds 1)."""
    name: str
    shape: tuple
    stacked: bool
    std: float
    ones: bool = False


def _seed_words(seed: int) -> np.ndarray:
    seed = int(seed)
    return np.asarray([seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF],
                      np.uint32)


def _leaf_key(words, name: str):
    key = jax.random.fold_in(jax.random.key(0), words[0])
    key = jax.random.fold_in(key, words[1])
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _block(key, index, leaf: Leaf, dtype):
    """The ``index``-th trailing block (last two dims, or the vector)."""
    tail = leaf.shape[-2:]
    x = jax.random.normal(jax.random.fold_in(key, index), tail,
                          jnp.float32) * leaf.std
    if leaf.ones:
        x = x + 1.0
    return x.astype(dtype)


def _leaf(words, leaf: Leaf, dtype, layers, first_layer=0):
    """``[layers, *shape]`` (stacked) or ``shape``: blocks drawn one at a
    time (``lax.map``), so the float32 transient is one block."""
    key = _leaf_key(words, leaf.name)
    lead = leaf.shape[:-2]
    per_layer = int(np.prod(lead)) if lead else 1
    n_layers = layers if leaf.stacked else 1
    idx = jnp.arange(n_layers * per_layer, dtype=jnp.uint32) \
        + jnp.asarray(first_layer, jnp.uint32) * jnp.uint32(per_layer)
    out = jax.lax.map(lambda i: _block(key, i, leaf, dtype), idx)
    full = ((n_layers,) if leaf.stacked else ()) + tuple(leaf.shape)
    return out.reshape(full)


def make(seed: int, leaves: list, layers: int, dtype, shardings=None) -> dict:
    """Every leaf, in ONE jitted call.  ``shardings`` (name -> sharding)
    places them as the program wants them."""
    dtype = jnp.dtype(dtype)

    def build(words):
        return {lf.name: _leaf(words, lf, dtype, layers) for lf in leaves}

    fn = jax.jit(build, out_shardings=shardings) if shardings is not None \
        else jax.jit(build)
    return fn(_seed_words(seed))


@functools.lru_cache(maxsize=8)
def _layer_builder(stacked: tuple, dtype):
    def build(words, first):
        return {lf.name: _leaf(words, lf, dtype, 1, first)[0]
                for lf in stacked}
    return jax.jit(build)


def make_layer(seed: int, leaves: list, layer: int, dtype) -> dict:
    """The stacked leaves of ONE layer (no layer dim) — what the reference
    asks for, layer by layer.  One compiled program for every layer."""
    stacked = tuple(lf for lf in leaves if lf.stacked)
    return _layer_builder(stacked, jnp.dtype(dtype))(
        _seed_words(seed), np.uint32(layer))


def make_flat(seed: int, leaves: list, dtype) -> dict:
    """The leaves that are not stacked (embedding, head, final norm)."""
    flat = [lf for lf in leaves if not lf.stacked]
    return make(seed, flat, 0, dtype)
