"""Operations and bytes a decoder with grouped-query attention, window and
full layers mixed and a routed feed-forward REQUIRES of one chip's share,
computed from shapes (`flops.py` has the conventions: a product of [m,k] by
[k,n] is 2mkn, backward is twice forward; remat, a mask's wasted half and
the tiles a kernel skips or visits in vain do not count).

Per token, forward, with h hidden, H query and H_kv K/V heads of d, E the
router's outputs, `held` experts here of width f, k choices a token:

    every layer         projections 2 (h H d + 2 h H_kv d + H d h); router
                        2 h E; the held experts at the balanced share,
                        2 * 3 h f * k held / E
    attention products  QK^T and PV, 2 * 2 H d on each (query, key) pair a
                        query sees: pairs(s, window) / s keys a token, which
                        is (s + 1) / 2 in a full layer and, in a window
                        layer, w (w + 1) / 2 + (s - w) w over s
    head                2 h v_rows, at every position; the embedding gather
                        is no product

The balance term is counting and a 64-wide product of means a layer:
nothing counted.
"""
from __future__ import annotations

from benchmarks import flops

WINDOW = "sliding_attention"


def visible_pairs(seq: int, window=None) -> int:
    """(query, key) pairs of one causal sequence: key j <= query i, and
    i - j < window where there is one."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def pairs_per_token(*, k, held, router_outputs) -> float:
    """(token, expert) pairs a token sends to the held experts under
    balanced routing."""
    return k * held / router_outputs


def layer_product_flops(*, hidden, heads, kv_heads, head_dim,
                        router_outputs) -> float:
    """The projections and the router of one layer, a token."""
    return 2.0 * (hidden * heads * head_dim
                  + 2 * hidden * kv_heads * head_dim
                  + heads * head_dim * hidden + hidden * router_outputs)


def attention_flops_per_token(*, heads, head_dim, seq, window=None) -> float:
    """QK^T and PV of one layer, a token, on the pairs the mask keeps."""
    return 4.0 * heads * head_dim * visible_pairs(seq, window) / seq


def train_flops_per_token(*, hidden, heads, kv_heads, head_dim, expert_ffn,
                          router_outputs, held, k, layer_types, window,
                          vocab, seq) -> float:
    """3 x the forward pass (backward is twice forward)."""
    every_layer = layer_product_flops(
        hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        router_outputs=router_outputs) \
        + 6.0 * hidden * expert_ffn * pairs_per_token(
            k=k, held=held, router_outputs=router_outputs)
    attention = sum(attention_flops_per_token(
        heads=heads, head_dim=head_dim, seq=seq,
        window=window if kind == WINDOW else None) for kind in layer_types)
    forward = len(layer_types) * every_layer + attention \
        + 2.0 * hidden * vocab
    return 3.0 * forward


# Flash attention with fewer K/V heads than query heads and, in a window
# layer, a second diagonal (ops/pallas/flash_attention.py): the products of
# `flops._FLASH` on the pairs the mask keeps, each query head's own; q, the
# output and their gradients [b, s, H d] read or written once, k, v and
# their gradients [b, s, H_kv d] once PER K/V HEAD (a kernel that loads a
# K/V head once for each of its query heads shows the waste).
_GQA_FLASH = {      # pass: (products, arrays of H heads, arrays of H_kv)
    "fwd": (2, 2, 2),       # q o | k v
    "bwd": (5, 4, 4),       # q o do dq | k v dk dv
}


def gqa_flash_pass_cost(kind: str, *, batch: int, heads: int, kv_heads: int,
                        seq: int, head_dim: int, window=None,
                        itemsize: int = 2):
    """(required FLOPs, required HBM bytes) of one forward call ("fwd") or
    one whole backward pass ("bwd") of one layer; the float32 lse row rides
    along."""
    products, wide, narrow = _GQA_FLASH[kind]
    flop = products * 2.0 * batch * heads * visible_pairs(seq, window) \
        * head_dim
    byte = itemsize * batch * seq * head_dim * (wide * heads
                                                + narrow * kv_heads) \
        + batch * heads * seq * 4.0
    return flop, byte


least_seconds = flops.least_seconds
