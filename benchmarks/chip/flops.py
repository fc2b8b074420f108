"""Model FLOPs of a training step, the rule every model module counts by.

Per token, forward and backward: 6 x the matrix parameters a token
passes through (the projections of every layer, of an expert layer only
the experts the token is routed to, and the unembedding; the embedding
lookup is a gather and counts nothing), plus, for the attention scores
and their weighted sum, 6 x sequence x the sum over layers of heads x
(query-key head size + value head size): 12 x layers x sequence x
(heads x head size) where the two sizes agree, as in the PaLM paper's
appendix B. Recomputation, which the offload trainer does in the
backward pass, is not counted: a step that recomputes does more work
than this, not less. Each ``models/<model>.py`` supplies its own counts
through ``flops_per_token(c, seq_len)``.
"""
from __future__ import annotations


def per_token(matrix_params: int, attention: int, seq_len: int) -> float:
    """Forward-plus-backward model FLOPs per trained token, from the
    matrix parameters a token passes through and the sum over layers of
    heads x (query-key head size + value head size)."""
    return 6.0 * matrix_params + 6 * seq_len * attention
