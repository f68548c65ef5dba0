"""The system under test for the ``smallthinker`` family
(SmallThinker-21BA3B-Instruct): the program's own model and engine, built
through the launcher's parser and ``engine_kwargs`` as ``python -m
paddle_tpu.serving`` builds them, carrying the BENCHMARK's seeded weights
(``harness.weights``).  Nothing here computes a result the reference is
compared with.

The model is imported at the top, before any weight is made: a program
that lacks it (the parent of the PR that brought it) fails at once."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from paddle_tpu.models.decoder_spec import EXPERT_BANKS
from paddle_tpu.models.smallthinker import (SmallThinkerConfig,
                                            SmallThinkerForCausalLM)

from chipbench.harness import weights
from chipbench.programs.cohere2_moe import _one_layer_of
from chipbench.references.smallthinker import leaf_specs


def model_config(m: dict, max_positions: int) -> SmallThinkerConfig:
    """The source's keys as ``Run.model`` hands them, read by the program's
    own ``from_source`` (which cuts the two layouts to the depth held)."""
    return SmallThinkerConfig.from_source(
        m, max_position_embeddings=max_positions)


def seeded_params(m: dict, cfg: SmallThinkerConfig, seed: int) -> dict:
    """The model's parameters in the layout of ``serving_params()``, from
    ``--seed``: for each place of the layer pattern the ``[periods, ...]``
    stacks of its layers and its expert banks one array a layer, made one
    leaf at a time (the transient is one leaf), the same numbers
    ``weights.make_layer`` hands the reference."""
    leaves = leaf_specs(m)
    dt = jnp.dtype(cfg.dtype)
    words = weights._seed_words(seed)
    period = cfg.period()
    periods = cfg.num_hidden_layers // period
    blocks = []
    for p in range(period):
        place = {}
        for lf in (lf for lf in leaves if lf.stacked):
            bank = lf.name in EXPERT_BANKS
            made = [_one_layer_of(lf, dt)(words, np.uint32(r * period + p))
                    for r in range(periods)]
            place[lf.name] = tuple(a[0] for a in made) if bank else \
                jnp.concatenate(made, axis=0)
        blocks.append(place)
    return dict(weights.make_flat(seed, leaves, dt), blocks=tuple(blocks))


def build_engine(m: dict, engine: dict, seed: int):
    """Model + ``ContinuousBatchingEngine``: the model adopts the seeded
    arrays as its parameters, which ARE what the engine scans and what its
    grouped GEMMs read, so the weights exist once from the first byte on."""
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.serving.__main__ import build_parser, engine_kwargs

    argv = []
    for k, v in engine.items():
        argv += ["--" + k.replace("_", "-"), str(v)]
    args = build_parser().parse_args(argv)
    cfg = model_config(m, args.max_seq_len)
    model = SmallThinkerForCausalLM(cfg, params=seeded_params(m, cfg, seed))
    kw = engine_kwargs(args)
    return ContinuousBatchingEngine(model, **kw), kw
