"""Model FLOPs of a training step, counted from shapes.

Per token, forward and backward: 6 x the matrix parameters a token
passes through (every layer's projections and the unembedding; the
embedding lookup is a gather and counts nothing), plus 12 x layers x
sequence x (heads x head size) for the attention scores and their
weighted sum, as in the PaLM paper's appendix B. Recomputation, which
the offload trainer does in the backward pass, is not counted: a step
that recomputes does more work than this, not less.
"""
from __future__ import annotations


def layer_matrix_params(c: dict) -> int:
    """Parameters of one layer's weight matrices (norm scales excluded)."""
    d, hd = c["hidden_size"], c["head_dim"]
    q = c["num_attention_heads"] * hd
    kv = c["num_key_value_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    mlp = (3 if c["mlp"] == "swiglu" else 2) * d * c["intermediate_size"]
    return attn + mlp


def flops_per_token(c: dict, seq_len: int) -> float:
    """Forward-plus-backward model FLOPs per trained token."""
    n = c["num_hidden_layers"] * layer_matrix_params(c) \
        + c["hidden_size"] * c["table_rows"]
    attn = 12 * c["num_hidden_layers"] * seq_len \
        * c["num_attention_heads"] * c["head_dim"]
    return 6.0 * n + attn
