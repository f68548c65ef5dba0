"""Serving demo: paged-KV continuous batching over a (random-weight) Llama.

python examples/serve_llama.py
"""

import sys
import os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import paddle_tpu as paddle
    from paddle_tpu.inference.generation import (ContinuousBatchingEngine,
                                                 GenerationConfig)
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    eng = ContinuousBatchingEngine(
        model, max_batch=4,
        gen=GenerationConfig(max_new_tokens=16, do_sample=True,
                             temperature=0.8, top_p=0.95),
        max_seq_len=128, page_size=16)
    rng = np.random.default_rng(0)
    ids = [eng.add_request(rng.integers(1, 250, n).tolist())
           for n in (5, 12, 3, 9, 7)]           # 5 requests over 4 slots
    results = eng.run()
    for rid in ids:
        print(f"request {rid}: {len(results[rid])} tokens -> "
              f"{results[rid][:8]}...", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
