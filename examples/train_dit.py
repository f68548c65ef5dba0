"""DiT diffusion training + sampling (BASELINE config 4 shape).

python examples/train_dit.py --steps 20 --sample
"""

import argparse
import sys
import os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--sample", action="store_true")
    args = ap.parse_args()

    import jax
    from paddle_tpu.models.dit import DiTConfig, DiTTrainStep

    cfg = DiTConfig(input_size=16, patch_size=2, in_channels=4,
                    hidden_size=128, depth=4, num_heads=8, num_classes=10,
                    dtype="float32")
    step = DiTTrainStep(cfg, lr=3e-4)
    state = step.init_state(seed=0)
    rng = np.random.default_rng(0)
    x0 = rng.standard_normal(
        (args.batch, 4, 16, 16)).astype("float32")
    y = rng.integers(0, 10, (args.batch,)).astype("int32")
    for i in range(args.steps):
        t = rng.integers(0, 1000, (args.batch,)).astype("int32")
        noise = rng.standard_normal(x0.shape).astype("float32")
        state, loss = step.train_step(state, *step.shard_batch(x0, t, y, noise))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i}: loss={float(loss):.4f}", flush=True)
    if args.sample:
        out = step.diffusion.ddim_sample(
            lambda x, t, yy: step.eps_fn(state["params"], x, t, yy),
            (4, 4, 16, 16), np.asarray([0, 1, 2, 3], "int32"),
            jax.random.PRNGKey(0), steps=20, guidance_scale=2.0,
            null_label=cfg.num_classes)
        print("sampled:", out.shape, "finite:", bool(np.isfinite(np.asarray(out)).all()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
