"""``python -m paddle_tpu.serving`` — spawn one serving replica as a
real process (ISSUE 7 satellite; also the ``paddle-tpu-serve`` console
script).

Argparse rides on top of the existing flag system: every
``FLAGS_serving_slo_*`` / ``FLAGS_prefix_cache`` / ``FLAGS_metrics``
knob keeps working via environment or ``--set NAME=VALUE``, while the
few launch-shape decisions (bind address, model preset, engine
geometry) get first-class options.  The replica starts with
``warmup=True`` so ``/readyz`` flips to ready only after the bucket
compile — a router never routes to it cold.
"""

from __future__ import annotations

import argparse
from typing import List, Optional

from .. import flags

_LLAMA_PRESETS = ("tiny", "llama2_7b", "llama2_13b", "mixtral_tiny")
# cohere2_moe (models/cohere2_moe.py): the test size, and Command A+ as one
# chip of eight that share each layer holds it (16 of the 128 experts, an
# eighth of the vocabulary, one period of four layers: 9.5 GB in bf16)
_COHERE2_MOE_PRESETS = {
    "cohere2_moe_tiny": lambda cfg: cfg.tiny(),
    "command_a_plus_ep8": lambda cfg: cfg.command_a_plus(
        num_hidden_layers=4, experts_held=16, expert_offset=0,
        vocab_size=262144 // 8),
}
# sarvam_mla (models/sarvam_mla.py): the test size, and Sarvam-105B as one
# chip of four that share each layer holds it (32 of the 128 experts, a
# quarter of the vocabulary, the dense layer and four expert layers: 9.1 GB)
_SARVAM_MLA_PRESETS = {
    "sarvam_mla_tiny": lambda cfg: cfg.tiny(),
    "sarvam_105b_ep4": lambda cfg: cfg.sarvam_105b(
        num_hidden_layers=5, experts_held=32, expert_offset=0,
        vocab_size=262144 // 4),
}
# falcon_h1 (models/falcon_h1.py): the test size, and Falcon-H1-34B as one
# pipeline stage of four of its 72 layers holds it, with embedding and head
# (8.8 GB; a slot keeps 16.9 MB of recurrent state besides its pages)
_FALCON_H1_PRESETS = {
    "falcon_h1_tiny": lambda cfg: cfg.tiny(),
    "falcon_h1_34b_d4": lambda cfg: cfg.falcon_h1_34b(num_hidden_layers=4),
}
# deepseek_v32 (models/deepseek_v32.py): the test size, and DeepSeek-V3.2 as
# chip 0 of the 16 that share each layer holds it (16 of the 256 experts, an
# eighth of the vocabulary, one dense layer and four expert layers: 9.3 GB)
_DEEPSEEK_V32_PRESETS = {
    "deepseek_v32_tiny": lambda cfg: cfg.tiny(),
    "deepseek_v32_ep16": lambda cfg: cfg.deepseek_v32_ep16(index=0),
}
# smallthinker (models/smallthinker.py): the test size, and
# SmallThinker-21BA3B-Instruct as the first of seven pipeline stages holds
# it: 8 of its 52 layers with all 64 experts each, embedding and head (7.9 GB)
_SMALLTHINKER_PRESETS = {
    "smallthinker_tiny": lambda cfg: cfg.tiny(),
    "smallthinker_21b": lambda cfg: cfg.smallthinker_21b(depth=8),
}
# solar_open2 (models/solar_open2.py): the test size, and Solar-Open2-250B as
# chip 0 of the eight that share each layer of its first pipeline stage holds
# it: one period of four layers (a gated softmax layer, three linear-attention
# ones), 40 of the 320 experts, an eighth of the vocabulary (6.6 GB; a slot
# keeps 13.0 MB of recurrent state, and pages on one layer in four)
_SOLAR_OPEN2_PRESETS = {
    "solar_open2_tiny": lambda cfg: cfg.tiny(),
    "solar_open2_250b": lambda cfg: cfg.solar_open2_250b(depth=4, share=8),
}
_PRESETS = _LLAMA_PRESETS + tuple(_COHERE2_MOE_PRESETS) \
    + tuple(_SARVAM_MLA_PRESETS) + tuple(_FALCON_H1_PRESETS) \
    + tuple(_DEEPSEEK_V32_PRESETS) + tuple(_SMALLTHINKER_PRESETS) \
    + tuple(_SOLAR_OPEN2_PRESETS)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="paddle-tpu-serve",
        description="One paddle_tpu serving replica: OpenAI-compatible "
                    "streaming /v1/completions over the continuous-"
                    "batching engine, with /metrics, /healthz, /readyz "
                    "and /statusz.")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--preset", choices=_PRESETS, default="tiny",
                   help="model config preset (random-init weights unless "
                        "--checkpoint is given)")
    p.add_argument("--checkpoint", default=None,
                   help="optional paddle_tpu state-dict file to load "
                        "into the model (paddle.load format)")
    p.add_argument("--model-name", default=None,
                   help="name reported in completion responses "
                        "(default: the preset)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-batch", type=int, default=8,
                   help="engine slots (continuous-batching width)")
    p.add_argument("--max-seq-len", type=int, default=1024)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--prefill-bucket", type=int, default=64)
    p.add_argument("--num-pages", type=int, default=None,
                   help="KV pool pages (default: engine sizing rule)")
    p.add_argument("--tensor-parallel", type=int, default=None,
                   help="shard the fused engine step over this many "
                        "devices on the 'mp' mesh axis "
                        "(FLAGS_serving_tensor_parallel; outputs stay "
                        "bit-identical to tp=1)")
    p.add_argument("--cache-dtype", default=None,
                   choices=("auto", "fp32", "float32", "bf16", "bfloat16",
                            "int8"),
                   help="KV page-pool storage dtype "
                        "(FLAGS_kv_cache_dtype; int8 = quantized pages)")
    p.add_argument("--max-new-tokens", type=int, default=128,
                   help="default completion budget when the request "
                        "omits max_tokens")
    p.add_argument("--role", choices=("mixed", "prefill", "decode"),
                   default=None,
                   help="disaggregated-serving role advertised via "
                        "/statusz (FLAGS_serving_role for this process; "
                        "the router's phase routing keys off it)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="enable the shared-prefix KV cache "
                        "(FLAGS_prefix_cache for this process)")
    p.add_argument("--no-warmup", action="store_true",
                   help="skip the readiness warmup compile (the replica "
                        "reports ready immediately; a router may then "
                        "route onto cold compiles)")
    p.add_argument("--set", action="append", default=[],
                   metavar="NAME=VALUE", dest="flag_sets",
                   help="set any FLAGS_* by name, repeatable "
                        "(e.g. --set serving_slo_ttft_ms=500)")
    return p


def apply_flag_sets(pairs: List[str]) -> None:
    """``--set NAME=VALUE`` pairs -> ``flags.set_flags`` (which parses
    string values by each flag's registered type)."""
    updates = {}
    for pair in pairs:
        if "=" not in pair:
            raise SystemExit(f"--set expects NAME=VALUE, got {pair!r}")
        name, value = pair.split("=", 1)
        updates[name.removeprefix("FLAGS_")] = value
    try:
        flags.set_flags(updates)
    except ValueError as e:
        raise SystemExit(str(e))


def engine_kwargs(args) -> dict:
    """THE engine-kwargs dict from parsed args — the single source every
    launch path (this launcher, the fleet spawner, in-process handles)
    threads through to ``ContinuousBatchingEngine``.  New knobs land
    here ONCE; before this, two call sites passed geometry positionally
    and a knob added to one silently dropped on the other."""
    from ..inference import GenerationConfig

    kw = dict(max_batch=args.max_batch,
              gen=GenerationConfig(max_new_tokens=args.max_new_tokens),
              max_seq_len=args.max_seq_len, page_size=args.page_size,
              prefill_bucket=args.prefill_bucket)
    if args.num_pages is not None:
        kw["num_pages"] = args.num_pages
    if getattr(args, "tensor_parallel", None) is not None:
        kw["tensor_parallel"] = args.tensor_parallel
    if getattr(args, "cache_dtype", None) is not None:
        kw["cache_dtype"] = None if args.cache_dtype == "auto" \
            else args.cache_dtype
    return kw


def build_engine(args):
    """Model + engine from parsed args (import-heavy, so deferred)."""
    import paddle_tpu as paddle
    from ..inference import ContinuousBatchingEngine

    paddle.seed(args.seed)
    if args.preset in _COHERE2_MOE_PRESETS:
        from ..models.cohere2_moe import (Cohere2MoeConfig,
                                          CohereMoeForCausalLM)
        model = CohereMoeForCausalLM(
            _COHERE2_MOE_PRESETS[args.preset](Cohere2MoeConfig))
    elif args.preset in _SARVAM_MLA_PRESETS:
        from ..models.sarvam_mla import SarvamMlaConfig, SarvamMlaForCausalLM
        model = SarvamMlaForCausalLM(
            _SARVAM_MLA_PRESETS[args.preset](SarvamMlaConfig))
    elif args.preset in _DEEPSEEK_V32_PRESETS:
        from ..models.deepseek_v32 import (DeepseekV32Config,
                                           DeepseekV32ForCausalLM)
        model = DeepseekV32ForCausalLM(
            _DEEPSEEK_V32_PRESETS[args.preset](DeepseekV32Config))
    elif args.preset in _FALCON_H1_PRESETS:
        from ..models.falcon_h1 import FalconH1Config, FalconH1ForCausalLM
        model = FalconH1ForCausalLM(
            _FALCON_H1_PRESETS[args.preset](FalconH1Config))
    elif args.preset in _SMALLTHINKER_PRESETS:
        from ..models.smallthinker import (SmallThinkerConfig,
                                           SmallThinkerForCausalLM)
        model = SmallThinkerForCausalLM(
            _SMALLTHINKER_PRESETS[args.preset](SmallThinkerConfig))
    elif args.preset in _SOLAR_OPEN2_PRESETS:
        from ..models.solar_open2 import (SolarOpen2Config,
                                          SolarOpen2ForCausalLM)
        model = SolarOpen2ForCausalLM(
            _SOLAR_OPEN2_PRESETS[args.preset](SolarOpen2Config))
    else:
        from ..models.llama import LlamaConfig, LlamaForCausalLM
        model = LlamaForCausalLM(getattr(LlamaConfig, args.preset)())
    if args.checkpoint:
        state = paddle.load(args.checkpoint)
        model.set_state_dict(state)
    return ContinuousBatchingEngine(model, **engine_kwargs(args))


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    # a replica tunes no kernel while it warms up: the grouped GEMM's probe
    # runs inside the step's trace and fails there (ROADMAP D12), the
    # engine thread dies and /readyz never comes.  Off, as in every
    # benchmark cell; ``--set autotune_enable=true`` asks for it all the same
    flags.set_flags({"autotune_enable": False})
    apply_flag_sets(args.flag_sets)
    if args.prefix_cache:
        # single source of truth: the engine's prefix_cache=None default
        # reads this flag, and /statusz's flag dump stays honest
        flags.set_flags({"prefix_cache": True})
    if args.role:
        # same single-source rule as --prefix-cache: the server's
        # role=None default reads the flag
        flags.set_flags({"serving_role": args.role})
    engine = build_engine(args)
    from .server import serve_forever
    serve_forever(engine, host=args.host, port=args.port,
                  model_name=args.model_name or args.preset,
                  warmup=not args.no_warmup)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
