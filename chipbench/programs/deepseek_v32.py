"""The system under test for the ``deepseek_v32`` family (DeepSeek-V3.2): the
program's own model and engine, built through the launcher's parser and
``engine_kwargs`` as ``python -m paddle_tpu.serving`` builds them, carrying
the BENCHMARK's seeded weights (``harness.weights``).  Nothing here computes
a result the reference is compared with.

The model is imported at the top, before any weight is made: a program
that lacks it (the parent of the PR that brought it) fails at once."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from paddle_tpu.models.decoder_spec import EXPERT_BANKS
from paddle_tpu.models.deepseek_v32 import (DeepseekV32Config,
                                            DeepseekV32ForCausalLM)

from chipbench.harness import weights
from chipbench.programs.cohere2_moe import _one_layer_of
from chipbench.references.deepseek_v32 import held, layer_leaves, leaf_specs

# the selection bias is added to float32 scores: the model keeps it in
# float32 (the values are the seeded bf16 ones, which float32 holds exactly)
FLOAT32_LEAVES = ("mlp.gate.bias",)


def model_config(m: dict, max_positions: int) -> DeepseekV32Config:
    """The source's keys as ``Run.model`` hands them (the counts held here
    over the published ones), read by the program's own ``from_source``;
    the router keeps the published width."""
    width, n, first = held(m)
    source = {k: v for k, v in m.items() if k not in ("published", "share")}
    return DeepseekV32Config.from_source(
        source, num_experts=width, experts_held=n, expert_offset=first,
        max_position_embeddings=max_positions)


def seeded_params(m: dict, cfg: DeepseekV32Config, seed: int) -> dict:
    """The model's parameters in the layout of ``serving_params()``, from
    ``--seed``: the leading dense layers one dict each, the expert layers
    one ``[layers, ...]`` stack a leaf with the expert banks one array a
    layer; made one leaf and layer at a time (the transient is one leaf),
    the same numbers ``weights.make_layer`` hands the reference."""
    dt = jnp.dtype(cfg.dtype)
    words = weights._seed_words(seed)

    def one(lf, layer):
        a = _one_layer_of(lf, dt)(words, np.uint32(layer))
        return a.astype(jnp.float32) if lf.name in FLOAT32_LEAVES else a

    k, L = cfg.first_k_dense_replace, cfg.num_hidden_layers
    leading = tuple({lf.name: one(lf, l)[0] for lf in layer_leaves(m, True)}
                    for l in range(k))
    experts = {}
    for lf in layer_leaves(m, False):
        made = [one(lf, l) for l in range(k, L)]
        experts[lf.name] = tuple(a[0] for a in made) \
            if lf.name in EXPERT_BANKS else jnp.concatenate(made, axis=0)
    return dict(weights.make_flat(seed, leaf_specs(m), dt), leading=leading,
                blocks=(experts,))


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
    model = DeepseekV32ForCausalLM(cfg, params=seeded_params(m, cfg, seed))
    kw = engine_kwargs(args)
    return ContinuousBatchingEngine(model, **kw), kw
