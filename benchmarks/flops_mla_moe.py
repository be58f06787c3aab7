"""Operations and bytes the latent-attention sparse-expert family REQUIRES
of one chip's share, computed from shapes (`flops.py` has the conventions:
a product of [m,k] by [k,n] is 2mkn, backward is twice forward, remat and
the mask's wasted half do not count).

Per token and layer, forward, with h hidden, H held heads, n streams:

    attention projections   2 (h q_rank + q_rank H (nope + rope)
                               + h (kv_rank + rope) + kv_rank H (nope + v)
                               + H v h)
    attention products      QK^T 2 s H (nope + rope) and PV 2 s H v on the
                            full square; the causal mask needs half
    stream mixing, twice    projections 2 (n h)(2n + n^2); read-in 2 n h;
                            write-back 2 n^2 h + 2 n h. Sinkhorn's 20
                            normalisations of a 4 x 4 matrix are not counted
    dense MLP               2 * 3 h f_dense
    sparse FFN              shared 2 * 3 h f_expert * shared; router 2 h E;
                            routed 2 * 3 h f_expert * k * held / E pairs a
                            token: the share balanced routing sends to the
                            held experts (0.5 at k 4, 8 of 64)
    head                    2 h v_rows, at every position; the embedding
                            gather is no product
"""
from __future__ import annotations

from benchmarks import flops


def attention_projection_flops(*, hidden, heads, q_rank, kv_rank, nope, rope,
                               v_dim) -> float:
    return 2.0 * (hidden * q_rank + q_rank * heads * (nope + rope)
                  + hidden * (kv_rank + rope)
                  + kv_rank * heads * (nope + v_dim) + heads * v_dim * hidden)


def stream_mix_flops(*, hidden, streams) -> float:
    """One sub-layer's mixing, a token."""
    n = streams
    return 2.0 * (n * hidden) * (2 * n + n * n) + 2.0 * n * hidden \
        + 2.0 * n * n * hidden + 2.0 * n * hidden


def pairs_per_token(*, k, held, router_outputs) -> float:
    """(token, expert) pairs a token sends to the held experts under
    balanced routing."""
    return k * held / router_outputs


def train_flops_per_token(*, hidden, heads, q_rank, kv_rank, nope, rope,
                          v_dim, dense_ffn, expert_ffn, shared, dense_layers,
                          sparse_layers, router_outputs, held, k, streams,
                          vocab, seq) -> float:
    """3 x the forward pass (backward is twice forward)."""
    layers = dense_layers + sparse_layers
    every_layer = attention_projection_flops(
        hidden=hidden, heads=heads, q_rank=q_rank, kv_rank=kv_rank,
        nope=nope, rope=rope, v_dim=v_dim) \
        + seq * heads * (nope + rope + v_dim) \
        + 2 * stream_mix_flops(hidden=hidden, streams=streams)
    dense = 6.0 * hidden * dense_ffn
    sparse = 6.0 * hidden * expert_ffn * shared \
        + 2.0 * hidden * router_outputs \
        + 6.0 * hidden * expert_ffn * pairs_per_token(
            k=k, held=held, router_outputs=router_outputs)
    forward = layers * every_layer + dense_layers * dense \
        + sparse_layers * sparse + 2.0 * hidden * vocab
    return 3.0 * forward


# The routed experts of one layer on `pairs` (token, expert) rows: three
# grouped products forward (gate, up: [pairs, h] x [h, f]; down: [pairs, f]
# x [f, h]) and for each of them two in the backward (its input's gradient
# and its matrix's). Bytes: each held matrix read once a pass (and written
# once as a gradient), each row array read or written once.
_GROUPED = {        # pass: (products, matrix passes, [pairs,h] arrays, [pairs,f] arrays)
    "fwd": (3, 3, 2, 3),        # rows in, out; gate, up, act
    "bwd": (6, 9, 4, 8),        # matrices read twice and written once
}


def grouped_pass_cost(kind: str, *, pairs: float, held: int, hidden: int,
                      width: int, itemsize: int = 2):
    """(required FLOPs, required HBM bytes) of one layer's routed experts,
    forward ("fwd") or backward ("bwd")."""
    products, matrices, wide, narrow = _GROUPED[kind]
    flop = products * 2.0 * pairs * hidden * width
    byte = itemsize * (matrices * held * hidden * width
                       + wide * pairs * hidden + narrow * pairs * width)
    return flop, byte


# Flash attention with two widths (ops/pallas/flash_attention.py): q and k
# of [bh, s, d_qk], v and the output of [bh, s, d_v]. The products of
# `flops._FLASH`, each at the width it contracts or produces:
#   fwd   S = QK^T (d_qk), O = PV (d_v)
#   bwd   S again (d_qk), dP = dO V^T (d_v), dV = P^T dO (d_v),
#         dK = dS^T Q (d_qk), dQ = dS K (d_qk)
_MLA_FLASH = {      # pass: (products at d_qk, at d_v, arrays of d_qk, of d_v)
    "fwd": (1, 1, 2, 2),        # q k | v o
    "bwd": (3, 2, 4, 4),        # q k dq dk | v o do dv
}


def mla_flash_pass_cost(kind: str, *, bh: int, seq: int, d_qk: int, d_v: int,
                        causal: bool, itemsize: int = 2):
    """(required FLOPs, required HBM bytes) of one forward call or one
    whole backward pass; the float32 lse row rides along."""
    at_qk, at_v, wide, narrow = _MLA_FLASH[kind]
    flop = 2.0 * bh * seq * seq * (at_qk * d_qk + at_v * d_v)
    if causal:
        flop /= 2
    byte = itemsize * bh * seq * (wide * d_qk + narrow * d_v) + bh * seq * 4.0
    return flop, byte


least_seconds = flops.least_seconds
