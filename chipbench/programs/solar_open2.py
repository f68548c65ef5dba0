"""The system under test for the ``solar_open2`` family (Solar-Open2-250B):
the program's own model and engine, built through the launcher's parser and
``engine_kwargs`` as ``python -m paddle_tpu.serving`` builds them, carrying
the BENCHMARK's seeded weights (``harness.weights``).  Nothing here computes
a result the reference is compared with.

The model is imported at the top, before any weight is made: a program
that lacks it (the parent of the PR that brought it) fails at once."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from paddle_tpu.models.decoder_spec import EXPERT_BANKS
from paddle_tpu.models.solar_open2 import (FLOAT32_LEAVES, SolarOpen2Config,
                                           SolarOpen2ForCausalLM)

from chipbench.harness import weights
from chipbench.programs.cohere2_moe import _one_layer_of
from chipbench.references.solar_open2 import (held, is_linear, layer_leaves,
                                              leaf_specs)


def model_config(m: dict, max_positions: int) -> SolarOpen2Config:
    """The source's keys as ``Run.model`` hands them (the counts held here
    over the published ones), read by the program's own ``from_source``
    (which cuts ``gqa_layers`` to the depth held); the router keeps the
    published width."""
    width, n, first = held(m)
    source = {k: v for k, v in m.items() if k not in ("published", "share")}
    return SolarOpen2Config.from_source(
        source, num_experts=width, experts_held=n, expert_offset=first,
        max_position_embeddings=max_positions)


def seeded_params(m: dict, cfg: SolarOpen2Config, seed: int) -> dict:
    """The model's parameters in the layout of ``serving_params()``, from
    ``--seed``: for each place of the layer pattern (a softmax place, then
    the linear ones) the ``[periods, ...]`` stacks of its layers' own
    leaves and its expert banks one array a layer, made one leaf and layer
    at a time (the transient is one leaf), the same numbers
    ``weights.make_layer`` hands the reference; the leaves the recurrence
    and the router read in float32 hold the bf16 draw's exact values."""
    dt = jnp.dtype(cfg.dtype)
    words = weights._seed_words(seed)
    period = cfg.period()
    periods = cfg.num_hidden_layers // period
    blocks = []
    for p in range(period):
        place = {}
        for lf in layer_leaves(m, is_linear(m, p)):
            made = [_one_layer_of(lf, dt)(words, np.uint32(r * period + p))
                    for r in range(periods)]
            if lf.name in FLOAT32_LEAVES:
                made = [a.astype(jnp.float32) for a in made]
            place[lf.name] = tuple(a[0] for a in made) \
                if lf.name in EXPERT_BANKS else jnp.concatenate(made, axis=0)
        blocks.append(place)
    return dict(weights.make_flat(seed, leaf_specs(m), dt),
                blocks=tuple(blocks))


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
    model = SolarOpen2ForCausalLM(cfg, params=seeded_params(m, cfg, seed))
    kw = engine_kwargs(args)
    return ContinuousBatchingEngine(model, **kw), kw
