"""Operations and bytes the algorithms REQUIRE, computed from shapes.

Model FLOP utilisation counts what the forward and backward passes need per
token, not what the program executes: recomputed matmuls (remat), a mask's
wasted half and a loss head run at unlabelled positions do not count. A
matrix multiplication of [m,k] by [k,n] is 2mkn; backward is twice forward,
so training is 6 per parameter per token.
"""
from __future__ import annotations


def gpt_train_flops_per_token(*, layers: int, hidden: int, ffn: int,
                              vocab: int, seq: int) -> float:
    """6*(L*(4h^2 + 2hf) + v*h) + 6*L*s*h.

    Per layer 4h^2 (qkv and output projections) + 2hf (the two MLP
    matrices), which is 12h^2 at f = 4h. The tied head v*h is counted once,
    as a matmul at every position; `wpe` and the embedding gather are not
    matmuls and are not counted. Attention: QK^T and PV are 2*2*s*h a token
    forward on the full square; the causal mask needs half, so 2*s*h
    forward and 6*s*h with backward."""
    params = layers * (4 * hidden * hidden + 2 * hidden * ffn) + vocab * hidden
    return 6.0 * params + 6.0 * layers * seq * hidden


def bert_mlm_train_flops_per_token(*, layers: int, hidden: int, ffn: int,
                                   vocab: int, seq: int,
                                   labelled_share: float) -> float:
    """6*(L*(4h^2 + 2hf) + h^2) + share*6*v*h + 12*L*s*h.

    The encoder as above; h^2 is the MLM head's transform. The decoder
    (tied embedding, v*h) is required only at the positions that carry a
    label (15 %). Attention is bidirectional, so the whole square counts:
    4*s*h forward, 12*s*h with backward."""
    params = layers * (4 * hidden * hidden + 2 * hidden * ffn) \
        + hidden * hidden
    return 6.0 * params + labelled_share * 6.0 * vocab * hidden \
        + 12.0 * layers * seq * hidden


# Flash attention on q, k, v of [bh, s, d] in `itemsize`-byte elements
# (ops/pallas/flash_attention.py), by what the mathematics needs and not by
# how the program splits it into kernels. Each s x s x d product is 2*s*s*d
# on the full square; a causal pass needs half of it.
#   fwd   S = QK^T, O = PV                                   2 products
#   bwd   S again, dP = dO V^T, dV = P^T dO, dK = dS^T Q, dQ = dS K
#                                                            5 products
#         (a dKV kernel and a dQ kernel that each recompute S and dP execute
#         4 + 3 = 7; the two repeated ones are not required)
# Bytes are each operand read once and each result written once; the
# float32 row statistics (lse: bh*s*4) ride along.
_FLASH = {          # pass: (products, arrays of [bh,s,d], float32 rows)
    "fwd": (2, 4, 1),       # q k v -> o, lse
    "bwd": (5, 8, 1),       # q k v o do lse -> dq dk dv
}


def flash_pass_cost(kind: str, *, bh: int, seq: int, head_dim: int,
                    causal: bool, itemsize: int = 2):
    """(required FLOPs, required HBM bytes) of one forward call ("fwd") or
    one whole backward pass ("bwd")."""
    products, arrays, rows = _FLASH[kind]
    flop = products * 2.0 * bh * seq * seq * head_dim
    if causal:
        flop /= 2
    byte = arrays * bh * seq * head_dim * itemsize + rows * bh * seq * 4.0
    return flop, byte


def least_seconds(flop: float, byte: float, peaks):
    """Roofline: the least time the chip could take, and which bound."""
    t_flop, t_byte = flop / peaks.flops, byte / peaks.hbm_bytes_s
    return (t_flop, "compute") if t_flop >= t_byte else (t_byte, "memory")
