"""Drives a whole run of a cell with the TIMED PATH BROKEN underneath (the
harness's look for a chip is skipped by the CPU rehearsal sizes): the
result's ``correct`` must come out false.

    python broken_run.py token <run arguments>    a served token is altered
                                                  where it is produced
    python broken_run.py state <run arguments>    the train step returns
                                                  its parameters unchanged
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def break_token():
    from paddle_tpu.inference import generation
    inner = generation._sample

    def altered(logits, key, pos, gc):
        return (inner(logits, key, pos, gc) + 1) % logits.shape[-1]

    generation._sample = altered


def break_state():
    from paddle_tpu.models.pretrain import PretrainStep
    inner = PretrainStep._update

    def unchanged(self, state, grads):
        new = inner(self, state, grads)
        return dict(new, params=state["params"])

    PretrainStep._update = unchanged


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    {"token": break_token, "state": break_state}[sys.argv[1]]()
    from chipbench.run import main
    sys.exit(main(sys.argv[2:]))
