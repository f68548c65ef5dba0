"""The system under test for the Llama family: the program's own model,
engine, server and train step, built as its launcher builds them, carrying
the BENCHMARK's seeded weights (``harness.weights``) instead of its own
random ones.  Nothing here computes a result the reference is compared
with."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.harness import weights
from chipbench.references.llama import leaf_specs


def llama_config(m: dict, max_positions: int):
    from paddle_tpu.models.llama import LlamaConfig
    dtype = {"bfloat16": "bfloat16", "float32": "float32"}[m["torch_dtype"]]
    if m["hidden_size"] != m["num_attention_heads"] * m["head_dim"]:
        raise ValueError("LlamaConfig derives head_dim = hidden / heads; "
                         "this configuration states another")
    return LlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        max_position_embeddings=max_positions,
        rms_norm_eps=m["rms_norm_eps"], rope_theta=m["rope_theta"],
        tie_word_embeddings=bool(m.get("tie_word_embeddings", False)),
        dtype=dtype,
        moe_num_experts=int(m.get("num_local_experts") or 0),
        moe_top_k=int(m.get("num_experts_per_tok") or 2),
        moe_dispatch="grouped")


def pin_flash_tiles(pins: list) -> dict:
    """The program's flash autotune probe cannot rank tiles on the chip
    (every candidate reads the same) and the choice moves the step: pin the
    tiles of this cell's shapes (the configuration's file lists them)."""
    from paddle_tpu.kernels import autotune
    done = {}
    for p in pins:
        key = autotune.make_key(
            "flash_fwd", sq=p["sq"], sk=p["sk"], d=p["d"], hq=p["hq"],
            hkv=p["hkv"], dt="bfloat16", causal=1, m=0, s=0)
        autotune.record(key, tuple(p["tiles"]))
        done[key] = list(p["tiles"])
    return done


def build_engine(m: dict, engine: dict, seed: int):
    """Model + ``ContinuousBatchingEngine`` through the launcher's own
    parser and ``engine_kwargs`` (``python -m paddle_tpu.serving``), the
    geometry from the mix's file."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving.__main__ import build_parser, engine_kwargs

    argv = []
    for k, v in engine.items():
        argv += ["--" + k.replace("_", "-"), str(v)]
    args = build_parser().parse_args(argv)
    paddle.seed(seed & 0x7FFFFFFF)
    cfg = llama_config(m, args.max_seq_len)
    model = LlamaForCausalLM(cfg)
    leaves = leaf_specs(m)
    dt = jnp.dtype(cfg.dtype)
    for l, lyr in enumerate(model.llama.layers):
        w = weights.make_layer(seed, leaves, l, dt)
        params = dict(lyr.named_parameters())
        if set(params) != set(w):
            raise ValueError(f"the program's layer has {sorted(params)}, "
                             f"the benchmark makes {sorted(w)}")
        for name, p in params.items():
            if tuple(p.shape) != tuple(w[name].shape):
                raise ValueError(f"{name}: program {p.shape}, "
                                 f"benchmark {w[name].shape}")
            p._data = w[name]
    flat = weights.make_flat(seed, leaves, dt)
    model.llama.embed_tokens.weight._data = flat["embed"]
    model.lm_head.weight._data = flat["head"]
    model.llama.norm.weight._data = flat["norm"]
    del flat
    kw = engine_kwargs(args)
    return ContinuousBatchingEngine(model, **kw), kw


def build_server(eng, name: str):
    """The server as ``serve_forever`` builds it: warm-up on, the default
    SLO controller, flight recorder and sentinel, the process watchdog."""
    from paddle_tpu.distributed.watchdog import get_comm_task_manager
    from paddle_tpu.serving import ServingServer
    return ServingServer(eng, model_name=name, warmup=True,
                         watchdog=get_comm_task_manager())


def build_train(m: dict, layout: dict, hyper: dict, seed: int):
    """``PretrainStep``, its state (bf16 weights from the seed in one
    jitted call, placed by the step's own shardings; zero float32 AdamW
    moments) and the shardings the seeded weights were made under."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep
    from paddle_tpu.utils import extract_params

    cfg = llama_config(m, m.get("train_positions", 4096))
    pc = ParallelConfig(dp=layout.get("dp", 1), mp=layout.get("mp", 1),
                        remat=bool(layout.get("remat", True)))
    ps = PretrainStep(cfg, pc, learning_rate=hyper["learning_rate"],
                      weight_decay=hyper["weight_decay"],
                      beta1=hyper["beta1"], beta2=hyper["beta2"],
                      eps=hyper["eps"])
    L = cfg.num_hidden_layers
    leaves = leaf_specs(m)
    template = extract_params(ps._template)
    stacked = [lf for lf in leaves if lf.stacked]
    if {lf.name for lf in stacked} != set(template):
        raise ValueError(f"the program's layer has {sorted(template)}, the "
                         f"benchmark makes {sorted(lf.name for lf in stacked)}")
    dt = jnp.dtype(cfg.dtype)
    shapes = {"embed": jax.ShapeDtypeStruct((m["vocab_size"],
                                             m["hidden_size"]), dt),
              "head": jax.ShapeDtypeStruct((m["hidden_size"],
                                            m["vocab_size"]), dt),
              "norm": jax.ShapeDtypeStruct((m["hidden_size"],), dt),
              "blocks": {lf.name: jax.ShapeDtypeStruct(
                  (1, L) + tuple(lf.shape), dt) for lf in stacked}}
    sh = ps._shardings(shapes)
    made_sh = {**{lf.name: NamedSharding(
        ps.mesh, P(*list(sh["blocks"][lf.name].spec)[1:]))
        for lf in stacked},
        "embed": sh["embed"], "head": sh["head"], "norm": sh["norm"]}
    made = weights.make(seed, leaves, L, dt, shardings=made_sh)

    def assemble(made):
        params = {"embed": made["embed"], "head": made["head"],
                  "norm": made["norm"],
                  "blocks": {lf.name: made[lf.name][None] for lf in stacked}}
        zeros = lambda t, d: jax.tree_util.tree_map(          # noqa: E731
            lambda a: jnp.zeros(a.shape, d), t)
        return {"params": params, "m": zeros(params, jnp.dtype(pc.m_dtype)),
                "v": zeros(params, jnp.dtype(pc.v_dtype)),
                "step": jnp.zeros((), jnp.int32)}

    p_sh = {"embed": sh["embed"], "head": sh["head"], "norm": sh["norm"],
            "blocks": sh["blocks"]}
    state_sh = {"params": p_sh, "m": p_sh, "v": p_sh,
                "step": NamedSharding(ps.mesh, P())}
    state = jax.jit(assemble, out_shardings=state_sh,
                    donate_argnums=(0,))(made)
    return ps, state, made_sh
