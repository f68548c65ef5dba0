"""The system under test for the ``falcon_h1`` family (Falcon-H1-34B): the
program's own model and engine, built through the launcher's parser and
``engine_kwargs`` as ``python -m paddle_tpu.serving`` builds them, carrying
the BENCHMARK's seeded weights (``harness.weights``).  Nothing here computes
a result the reference is compared with.

The model is imported at the top, before any weight is made: a program
that lacks it (the parent of the PR that brought it) fails at once."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from paddle_tpu.models.falcon_h1 import (FLOAT32_LEAVES, FalconH1Config,
                                         FalconH1ForCausalLM)

from chipbench.harness import weights
from chipbench.references.falcon_h1 import leaf_specs


@functools.lru_cache(maxsize=32)
def _one_layer_of(leaf, dtype):
    """Jitted ``(seed words, layer) -> [1, *leaf.shape]``: the leaf's values
    for one layer, the layer a traced argument (one compiled program a
    leaf, whatever the depth)."""
    return jax.jit(lambda words, layer: weights._leaf(words, leaf, dtype, 1,
                                                      layer))


def model_config(m: dict, max_positions: int) -> FalconH1Config:
    """The source's keys as ``Run.model`` hands them, read by the program's
    own ``from_source``."""
    return FalconH1Config.from_source(m, max_position_embeddings=max_positions)


def seeded_params(m: dict, cfg: FalconH1Config, seed: int) -> dict:
    """The model's parameters in the layout of ``serving_params()``, from
    ``--seed``: one ``[layers, ...]`` stack a leaf, made one leaf and layer
    at a time (the transient is one leaf), the same numbers
    ``weights.make_layer`` hands the reference; the three leaves the scan
    reads in float32 hold the bf16 draw's exact values."""
    dt = jnp.dtype(cfg.dtype)
    words = weights._seed_words(seed)
    leaves = leaf_specs(m)
    stack = {}
    for lf in (lf for lf in leaves if lf.stacked):
        a = jnp.concatenate([_one_layer_of(lf, dt)(words, np.uint32(l))
                             for l in range(cfg.num_hidden_layers)], axis=0)
        stack[lf.name] = a.astype(jnp.float32) \
            if lf.name in FLOAT32_LEAVES else a
    return dict(weights.make_flat(seed, leaves, dt), blocks=(stack,))


def build_engine(m: dict, engine: dict, seed: int):
    """Model + ``ContinuousBatchingEngine``: the model adopts the seeded
    arrays as its parameters, which ARE what the engine scans, so the
    weights exist once from the first byte on."""
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.serving.__main__ import build_parser, engine_kwargs

    argv = []
    for k, v in engine.items():
        argv += ["--" + k.replace("_", "-"), str(v)]
    args = build_parser().parse_args(argv)
    cfg = model_config(m, args.max_seq_len)
    model = FalconH1ForCausalLM(cfg, params=seeded_params(m, cfg, seed))
    kw = engine_kwargs(args)
    return ContinuousBatchingEngine(model, **kw), kw
