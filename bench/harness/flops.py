"""Operations and bytes that the work requires, computed from shapes.

These count what the algorithm needs, never what one implementation
happens to do: no recomputation, no padding of the vocabulary or of pages,
no bytes moved twice.  A roofline share computed from them cannot pass 100%
unless the time leaves out part of the work.
"""
from __future__ import annotations


def matmul_params(s) -> int:
    """Weights that take part in a matrix product per token, for a
    ``model.Shard`` (the embedding lookup is a gather, not a product)."""
    d, hd = s.d_model, s.head_dim
    attn = d * (s.n_heads + 2 * s.n_kv_heads) * hd + s.n_heads * hd * d
    mlp = 3 * d * s.d_ff
    return s.n_layers * (attn + mlp) + d * s.vocab


def attention_flops(s, positions: int) -> float:
    """Scores and weighted values for one token that attends to
    ``positions`` keys (itself included), over all layers, forward."""
    return 4.0 * s.n_layers * s.n_heads * s.head_dim * positions


def train_flops_per_token(s, seq: int) -> float:
    """Forward and backward (3x forward) per token of a causal sequence of
    ``seq`` tokens: position t attends to t + 1 keys, (seq + 1) / 2 on
    average."""
    return 3.0 * (2.0 * matmul_params(s)
                  + attention_flops(s, (seq + 1) / 2.0))


def gossip_mix_cost(n: int, rows: int, neighbours: int) -> tuple:
    """(flops, bytes) of one fused gossip + momentum-SGD update over
    ``n`` learners' (rows, 128) float32 stores.

    Reads each learner's weights, its ``neighbours`` partners' weights, its
    gradient and momentum; writes its weights and momentum.  Per element:
    the neighbour average (2 per term), momentum (2) and the step (2)."""
    elems = n * rows * 128
    flops = elems * (2.0 * (1 + neighbours) + 4.0)
    bytes_ = elems * 4.0 * (1 + neighbours + 2 + 2)
    return flops, bytes_
