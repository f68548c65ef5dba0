"""Operations a token needs in training: 6 N + attention.

Copied arithmetic (6 * N per token, N the parameters a token touches:
``PretrainStep.flops_per_token``), with the attention term that count leaves
out.  Recomputation (``remat``) is NOT counted: model FLOP/s utilization
counts the operations forward and backward require."""

from __future__ import annotations


def attention_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward QK^T and PV are 2 matmuls of 2 * s * d operations a token
    and head over a causal half; backward twice that: 3 * 4 * s/2 * hq*d
    a layer."""
    width = m["num_attention_heads"] * m["head_dim"]
    return m["num_hidden_layers"] * 6.0 * seq_len * width


def train_flops_per_token(m: dict, active_params: int, seq_len: int) -> dict:
    n6 = 6.0 * active_params
    att = attention_flops_per_token(m, seq_len)
    return {"six_n": n6, "attention": att, "total": n6 + att}
