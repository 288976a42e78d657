"""Model FLOPs of one training step of a dense decoder (PaLM, arXiv:2204.02311,
App. B): 6·N·tokens + 12·L·(heads·head_dim)·S·tokens.

N counts every matmul weight, the output head included and the input
embedding (a lookup, not a matmul) left out.  The attention term counts
the score and value products over the whole sequence, as the convention
does.  Recompute is not credited.
"""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Matmul weights of the model in a configuration file's ``model``."""
    d, h = m["d_model"], m["head_dim"]
    q, kv = m["num_heads"] * h, m["num_kv_heads"] * h
    attn = d * q + 2 * d * kv + q * d
    mlp = (3 if m["mlp_kind"] == "swiglu" else 2) * d * m["d_ff"]
    return m["num_layers"] * (attn + mlp) + d * m["vocab_size"]


def train_step_flops(m: dict, *, seq: int, batch: int) -> float:
    tokens = seq * batch
    attn = 12 * m["num_layers"] * m["num_heads"] * m["head_dim"] * seq * tokens
    return 6.0 * matmul_params(m) * tokens + attn
