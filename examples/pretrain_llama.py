"""Hybrid-parallel Llama pretraining (BASELINE config 3 shape).

Single chip:   python examples/pretrain_llama.py
8-dev virtual: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
               python examples/pretrain_llama.py --dp 2 --pp 2 --mp 2 --schedule 1f1b
"""

import argparse
import sys
import os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--schedule", default="gpipe",
                    choices=["gpipe", "1f1b", "interleave", "zbh1"])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--zero3", action="store_true")
    args = ap.parse_args()

    from paddle_tpu.models.llama import LlamaConfig
    from paddle_tpu.models.pretrain import ParallelConfig, PretrainStep

    cfg = LlamaConfig(vocab_size=2048, hidden_size=args.hidden,
                      intermediate_size=args.hidden * 11 // 4,
                      num_hidden_layers=args.layers, num_attention_heads=8,
                      num_key_value_heads=4, max_position_embeddings=args.seq,
                      dtype="float32")
    pc = ParallelConfig(dp=args.dp, pp=args.pp, mp=args.mp,
                        micro_batches=2 * args.pp, schedule=args.schedule,
                        zero1=args.zero3, zero3=args.zero3, remat=True)
    ps = PretrainStep(cfg, pc)
    state = ps.init_state(seed=0)
    rng = np.random.default_rng(0)
    B = max(2 * pc.micro_batches * args.dp, 2)
    for step in range(args.steps):
        ids, labels = ps.shard_batch(
            rng.integers(0, cfg.vocab_size, (B, args.seq)).astype(np.int32),
            rng.integers(0, cfg.vocab_size, (B, args.seq)).astype(np.int32))
        t0 = time.perf_counter()
        state, loss = ps.train_step(state, ids, labels)
        print(f"step {step}: loss={float(loss):.4f} "
              f"({time.perf_counter() - t0:.2f}s)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
