"""Work counts: the operations and bytes the algorithm needs.

Each count is a function of shapes alone, so it reads the same work
whatever implementation (kernel, fusion, padding) computes it.  The
configuration dicts are the files under ``bench/configs``.
"""
from __future__ import annotations

F32 = 4


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def matmul_params_per_layer(cfg: dict) -> int:
    """Weights of one decoder layer that enter a matrix product:
    q/k/v/o projections and the three SwiGLU matrices (no biases, norms)."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * h * hd * 2 + d * kv * hd * 2
    mlp = 3 * d * cfg["intermediate_size"]
    return attn + mlp


def matmul_params(cfg: dict) -> int:
    """Matrix-product weights of the whole step: every layer and the
    output head; the embedding is a gather and does not count."""
    return (cfg["num_hidden_layers"] * matmul_params_per_layer(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Model FLOPs of forward and backward per trained token.

    6 per matrix-product weight (2 forward, 4 backward), plus the causal
    attention products: per layer and token, QK^T and PV each cost
    2 * heads * head_dim * context forward, the mean causal context of a
    ``seq_len`` sequence is (seq_len + 1) / 2, and backward doubles it
    again: 3 * 4 * heads * head_dim * (seq_len + 1) / 2.  Recomputation
    (remat) is not counted.
    """
    attn = (cfg["num_hidden_layers"] * 6 * cfg["num_attention_heads"]
            * head_dim(cfg) * (seq_len + 1))
    return 6.0 * matmul_params(cfg) + attn


def param_leaf_sizes(cfg: dict) -> list[int]:
    """Element counts of the model's parameter leaves (the untied form:
    embedding and output head are separate leaves)."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ff, v, L = cfg["intermediate_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    per_layer = [d, d,                                  # ln1, ln2
                 d * h * hd, d * kv * hd, d * kv * hd,  # wq, wk, wv
                 h * hd * d,                            # wo
                 h * hd, kv * hd, kv * hd,              # bq, bk, bv
                 d * ff, d * ff, ff * d]                # gate, up, down
    return [v * d, d * v, d] + [L * s for s in per_layer]


def dual_update_bytes(leaf_sizes) -> int:
    """HBM bytes of one prox w = w0 - z / (2 beta) over every leaf:
    read z and w0 in f32, write w in f32, at the leaves' real sizes."""
    return sum(3 * F32 * n for n in leaf_sizes)

