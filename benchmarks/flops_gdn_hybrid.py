"""Operations and bytes the dense hybrid of Gated DeltaNet and full-attention
layers REQUIRES (`flops.py` has the conventions: a product of [m,k] by
[k,n] is 2mkn, backward is twice forward, remat, the mask's wasted half and
any padding of a head's width do not count).

Per token, forward, with h hidden, L_g linear layers of H heads of d_k
keys and d_v values and K taps, L_f full layers of H_f heads of h / H_f:

    linear projections, L_g  2 (2 h H d_k          q, k
                                + 2 h H d_v        v, the output gate
                                + 2 h H            the decay, the write
                                                   strength
                                + H d_v h)         output
    linear convolutions, L_g 2 K (2 H d_k + H d_v)
    linear rule, L_g         7 d_k d_v H: THE RECURRENCE'S work a token and
                             head at the published widths, whatever form
                             computes it: the decay of the state d_k d_v,
                             S'^T k 2 d_k d_v, the rank-one write 2 d_k d_v,
                             S^T q 2 d_k d_v. A chunked form does more (the
                             triangular system, the products inside a
                             chunk, lanes padded to 128) and is read on
                             this count
    full projections, L_f    2 * 4 h h             q, k, v, output
    full products, L_f       the causal half of QK^T and PV: 2 s h
    MLP, every layer         2 * 3 h f
    head                     2 h v_rows at every position; the embedding
                             gather is no product

The norms (q/k, post-norm, the gated head norm), SiLU, softplus and the
sigmoids are elementwise and not counted, as `flops.py` counts no norm. The
optimizer is not counted.
"""
from __future__ import annotations

from benchmarks import flops

RULE_FLOPS = 7      # a key channel and value channel, forward (above)


def linear_projection_flops(*, hidden, heads, key_dim, value_dim,
                            taps) -> float:
    """A linear layer's seven projections and three convolutions, a
    token."""
    keys, values = heads * key_dim, heads * value_dim
    return 2.0 * (2 * hidden * keys + 2 * hidden * values + 2 * hidden * heads
                  + values * hidden) + 2.0 * taps * (2 * keys + values)


def rule_flops(*, heads, key_dim, value_dim) -> float:
    """The recurrence's work a token, forward, all heads."""
    return float(RULE_FLOPS * key_dim * value_dim * heads)


def train_flops_per_token(*, hidden, ffn, linear_layers, linear_heads,
                          key_dim, value_dim, taps, full_layers, vocab,
                          seq) -> float:
    """3 x the forward pass (backward is twice forward)."""
    linear = linear_projection_flops(
        hidden=hidden, heads=linear_heads, key_dim=key_dim,
        value_dim=value_dim, taps=taps) + rule_flops(
            heads=linear_heads, key_dim=key_dim, value_dim=value_dim)
    full = 8.0 * hidden * hidden + 2.0 * seq * hidden
    mlp = 6.0 * hidden * ffn
    forward = linear_layers * linear + full_layers * full \
        + (linear_layers + full_layers) * mlp + 2.0 * hidden * vocab
    return 3.0 * forward


def rule_pass_cost(kind: str, *, tokens: int, heads: int, key_dim: int,
                   value_dim: int, itemsize: int = 2):
    """(required FLOPs, required HBM bytes) of one layer's rule on `tokens`
    tokens at the published widths, forward ("fwd") or backward ("bwd").
    Bytes: q and k (d_k a head), v and o (d_v) once in the activations'
    width, the log-decay (one a head) and the write strength once in
    float32; the backward pass reads them and o's gradient and writes the
    five gradients, twice the forward's bytes, as its work is twice the
    forward's."""
    passes = {"fwd": 1, "bwd": 2}[kind]
    flop = passes * tokens * rule_flops(heads=heads, key_dim=key_dim,
                                        value_dim=value_dim)
    byte = passes * tokens * heads * (
        2 * (key_dim + value_dim) * itemsize + 4 + 4)
    return flop, float(byte)


least_seconds = flops.least_seconds
