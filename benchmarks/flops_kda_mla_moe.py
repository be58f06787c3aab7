"""Operations and bytes the family with linear-attention (KDA) and latent
softmax (MLA) layers REQUIRES of one chip's share (`flops.py` has the
conventions: a product of [m,k] by [k,n] is 2mkn, backward is twice
forward, remat and the mask's wasted half do not count).

Per token, forward, with h hidden, L_k KDA layers of H heads of d and K
taps, L_m MLA layers of H_m heads, r the low rank of the decay's and the
gate's projections (d):

    KDA projections, L_k     2 (3 h H d            q, k, v
                                + 2 (h r + r H d)  decay and output gate
                                + h H              write strength
                                + H d h)           output
    KDA convolutions, L_k    2 K * 3 H d
    KDA rule, L_k            7 d d H: THE RECURRENCE'S work a token and
                             head, whatever form computes it: the decay of
                             the state d d, S'^T k 2 d d, the rank-one
                             write 2 d d, S^T q 2 d d. A chunked form does
                             more (the triangular system, the products
                             inside a chunk) and a token loop does exactly
                             this; both are read on this count
    MLA projections, L_m     2 (h H_m (nope + rope) + h (kv_rank + rope)
                                + kv_rank H_m (nope + v) + H_m v h): no
                             query latent
    MLA products, L_m        the causal half of QK^T and PV:
                             s H_m (nope + rope + v)
    dense MLP, L_d           2 * 3 h f_dense
    sparse FFN, L_s          shared 2 * 3 h f_expert * shared; router 2 h E;
                             routed 2 * 3 h f_expert * k held / E (the
                             pairs balanced routing sends to the held ones)
    head                     2 h v_rows at every position; the embedding
                             gather is no product

The head norms, SiLU, softplus, the sigmoids and the gated norm are
elementwise and not counted, as `flops.py` counts no norm. The selection
biases' move is counting and a sign: nothing.
"""
from __future__ import annotations

from benchmarks import flops, flops_mla_moe

RULE_FLOPS = 7      # a key channel and value channel, forward (above)


def linear_projection_flops(*, hidden, heads, head_dim, taps) -> float:
    """A KDA layer's seven projections and three convolutions, a token."""
    width, rank = heads * head_dim, head_dim
    return 2.0 * (3 * hidden * width + 2 * (hidden * rank + rank * width)
                  + hidden * heads + width * hidden) \
        + 2.0 * taps * 3 * width


def rule_flops(*, heads, head_dim) -> float:
    """The recurrence's work a token, forward, all heads."""
    return float(RULE_FLOPS * head_dim * head_dim * heads)


def latent_projection_flops(*, hidden, heads, kv_rank, nope, rope,
                            v_dim) -> float:
    return 2.0 * (hidden * heads * (nope + rope) + hidden * (kv_rank + rope)
                  + kv_rank * heads * (nope + v_dim) + heads * v_dim * hidden)


def train_flops_per_token(*, hidden, linear_layers, linear_heads,
                          linear_head_dim, taps, full_layers, heads, kv_rank,
                          nope, rope, v_dim, dense_ffn, expert_ffn, shared,
                          dense_layers, sparse_layers, router_outputs, held,
                          k, vocab, seq) -> float:
    """3 x the forward pass (backward is twice forward)."""
    linear = linear_projection_flops(
        hidden=hidden, heads=linear_heads, head_dim=linear_head_dim,
        taps=taps) + rule_flops(heads=linear_heads, head_dim=linear_head_dim)
    full = latent_projection_flops(
        hidden=hidden, heads=heads, kv_rank=kv_rank, nope=nope, rope=rope,
        v_dim=v_dim) + seq * heads * (nope + rope + v_dim)
    dense = 6.0 * hidden * dense_ffn
    sparse = 6.0 * hidden * expert_ffn * shared \
        + 2.0 * hidden * router_outputs \
        + 6.0 * hidden * expert_ffn * flops_mla_moe.pairs_per_token(
            k=k, held=held, router_outputs=router_outputs)
    forward = linear_layers * linear + full_layers * full \
        + dense_layers * dense + sparse_layers * sparse \
        + 2.0 * hidden * vocab
    return 3.0 * forward


def rule_pass_cost(kind: str, *, tokens: int, heads: int, head_dim: int,
                   itemsize: int = 2):
    """(required FLOPs, required HBM bytes) of one layer's rule on `tokens`
    tokens, forward ("fwd") or backward ("bwd"). Bytes: q, k, v and o a
    head and token once in the activations' width, the log-decays and the
    write strength once in float32; the backward pass reads them and o's
    gradient and writes the five gradients, twice the forward's bytes, as
    its work is twice the forward's."""
    passes = {"fwd": 1, "bwd": 2}[kind]
    flop = passes * tokens * rule_flops(heads=heads, head_dim=head_dim)
    byte = passes * tokens * heads * (4 * head_dim * itemsize
                                      + head_dim * 4 + 4)
    return flop, float(byte)


least_seconds = flops.least_seconds
