"""How far the plain reference of ``sarvam105b-batch-docs16k`` moves when
only the INPUTS of its projections are rounded to bf16 (attention, router,
norms and accumulation stay float32): a floor under what any bf16 program
can read against the float32 reference in this configuration.  The limits
of the cell's file quote its reading (``recorded_bf16_yardstick.json``
beside this file, PR 31).  On the chip, from the root of a checkout:

    chiprun -- python tests/chipbench/data/bf16_yardstick.py

(``--rehearse 1 --lengths 300 400`` tries the script on the CPU at the
configuration's rehearsal sizes; its numbers are no reading.)

Two seeded sequences of 6,000 and 12,000 tokens, the last 256 positions of
each compared: the float32 reference's largest logit minus its logit at the
rounded reference's choice, the same gap the harness compares."""
import argparse
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import chipbench.references.llama as llama_ref  # noqa: E402
from chipbench.harness import core, spec, weights  # noqa: E402
from chipbench.references import sarvam_mla as ref  # noqa: E402

CELL = "sarvam105b-batch-docs16k"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3100000101)
    ap.add_argument("--out", default="chiprun_out/micro/bf16_yardstick.json")
    ap.add_argument("--lengths", type=int, nargs="+", default=[6000, 12000])
    ap.add_argument("--rehearse", type=int, default=0,
                    help="1: the configuration's rehearsal sizes (CPU)")
    args = ap.parse_args()
    cell = spec.load_cell(CELL, spec.ROOT)
    run = core.Run(cell, argparse.Namespace(
        seed=args.seed, seconds=1.0, trace=0, rehearse=args.rehearse,
        control=0),
        {"kind": "TPU v5 lite"})
    m, seed = run.model, run.seed
    rng = np.random.default_rng(seed)
    seqs = [rng.integers(1, m["vocab_size"], n).tolist()
            for n in args.lengths]
    positions = [list(range(len(s) - 256, len(s))) for s in seqs]
    leaves = ref.leaf_specs(m)
    dt = m["torch_dtype"]
    flat = weights.make_flat(seed, leaves, dt)

    def get(layer):
        return weights.make_layer(seed, leaves, layer, dt)

    depth = m["num_hidden_layers"]
    hi = ref.sequence_logits(get, flat, depth, m, seqs, positions)

    def mm_bf16(a, w, precision):
        def rounded(x):
            return x.astype(jnp.bfloat16).astype(ref.F32)
        return jnp.matmul(rounded(a), rounded(w), precision=ref.HI)

    ref._mm = mm_bf16
    llama_ref._mm = mm_bf16          # ``_swiglu`` calls it from there
    lo = ref.sequence_logits(get, flat, depth, m, seqs, positions)
    gaps = np.concatenate([
        h.max(-1) - np.take_along_axis(h, l.argmax(-1)[:, None], -1)[:, 0]
        for h, l in zip(hi, lo)])
    top2 = np.concatenate([np.sort(h, -1)[:, -1] - np.sort(h, -1)[:, -2]
                           for h in hi])
    out = {
        "seed": args.seed,
        "tokens": int(gaps.size),
        "gap_max": float(gaps.max()),
        "gap_mean": float(gaps.mean()),
        "gap_p99": float(np.quantile(gaps, 0.99)),
        "disagree_share": float((gaps > 0).mean()),
        "logit_abs_diff_mean": float(np.mean(
            [np.abs(h - l).mean() for h, l in zip(hi, lo)])),
        "reference_top1_minus_top2_median": float(np.median(top2)),
        "reference_top1_minus_top2_p10": float(np.quantile(top2, 0.1)),
        "logit_std": float(np.mean([h.std() for h in hi]))}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
