"""The system under test for the ``cohere2_moe`` family (Command A+): the
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
from paddle_tpu.models.cohere2_moe import (Cohere2MoeConfig,
                                           CohereMoeForCausalLM)

from chipbench.harness import weights
from chipbench.references.cohere2_moe import held, leaf_specs


def model_config(m: dict, max_positions: int) -> Cohere2MoeConfig:
    """The source's keys as ``Run.model`` hands them (the counts held here
    over the published ones), read by the program's own ``from_source``;
    the router keeps the published width."""
    width, n, first = held(m)
    source = {k: v for k, v in m.items() if k not in ("published", "share")}
    return Cohere2MoeConfig.from_source(
        source, num_experts=width, experts_held=n, expert_offset=first,
        layer_types=tuple(m["layer_types"][:m["num_hidden_layers"]]),
        max_position_embeddings=max_positions)


@functools.lru_cache(maxsize=64)
def _one_layer_of(leaf, dtype):
    """Jitted ``(seed words, layer) -> [1, *leaf.shape]``: the leaf's values
    for one layer (``weights._leaf`` draws them from (seed, name, layer)),
    the layer a traced argument so that every place and period shares one
    compiled program a leaf."""
    return jax.jit(lambda words, layer: weights._leaf(words, leaf, dtype, 1,
                                                      layer))


def seeded_params(m: dict, cfg: Cohere2MoeConfig, seed: int) -> dict:
    """The model's parameters in the layout of ``serving_params()``, from
    ``--seed``: for each place of the layer pattern the ``[periods, ...]``
    stacks of its layers, made one leaf at a time (the transient is one
    leaf), the same numbers ``weights.make_layer`` hands the reference."""
    leaves = leaf_specs(m)
    dt = jnp.dtype(cfg.dtype)
    words = weights._seed_words(seed)
    period = cfg.period()
    periods = cfg.num_hidden_layers // period
    blocks = []
    for p in range(period):
        place = {}
        for lf in (lf for lf in leaves if lf.stacked):
            made = [_one_layer_of(lf, dt)(words, np.uint32(r * period + p))
                    for r in range(periods)]
            place[lf.name] = made[0] if periods == 1 else \
                jnp.concatenate(made, axis=0)
        blocks.append(place)
    return dict(weights.make_flat(seed, leaves, dt), blocks=tuple(blocks))


def build_engine(m: dict, engine: dict, seed: int):
    """Model + ``ContinuousBatchingEngine``: the model adopts the seeded
    arrays as its parameters, which ARE the stacks the engine scans, so the
    weights exist once from the first byte on."""
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.serving.__main__ import build_parser, engine_kwargs

    argv = []
    for k, v in engine.items():
        argv += ["--" + k.replace("_", "-"), str(v)]
    args = build_parser().parse_args(argv)
    cfg = model_config(m, args.max_seq_len)
    model = CohereMoeForCausalLM(cfg, params=seeded_params(m, cfg, seed))
    kw = engine_kwargs(args)
    return ContinuousBatchingEngine(model, **kw), kw
