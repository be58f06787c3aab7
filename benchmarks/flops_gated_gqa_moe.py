"""Operations a decoder with gated grouped-query attention of two head
counts, window and full layers mixed, a leading dense layer and routed
layers with a shared expert REQUIRES of one chip's share, computed from
shapes (`flops.py` has the conventions: a product of [m,k] by [k,n] is
2mkn, backward is twice forward; remat, a mask's wasted half and the tiles
a kernel skips or visits in vain do not count).

Per token, forward, with h hidden, layer n of H_n query heads on H_kv K/V
heads of d, E the router's outputs, `held` experts here of width f, k
choices a token, a shared expert of width f_s, the dense layers' width F:

    attention, a layer  projections 2 h (H_n d + 2 H_kv d) + 2 H_n d h, the
                        gate's product 2 h H_n d (`gate_flops_per_token`);
                        QK^T and PV, 2 * 2 H_n d on each (query, key) pair a
                        query sees (`flops_window_gqa_moe.visible_pairs`)
    dense layer         3 products of h by F
    sparse layer        router 2 h E; the held experts at the balanced
                        share, 2 * 3 h f * k held / E; the shared expert
                        2 * 3 h f_s
    head                2 h v_rows, at every position

The sigmoid, the gate's multiply, the norms and the rotary embedding are
elementwise: nothing counted. The kernels' work and bytes are
`flops_window_gqa_moe.gqa_flash_pass_cost` at each kind's own heads and
window.
"""
from __future__ import annotations

from benchmarks import flops
from benchmarks.flops_window_gqa_moe import pairs_per_token, visible_pairs

WINDOW = "sliding_attention"


def gate_flops_per_token(*, hidden, heads, head_dim) -> float:
    """The output gate's product y W_g of one layer, a token."""
    return 2.0 * hidden * heads * head_dim


def attention_flops_per_token(*, hidden, heads, kv_heads, head_dim, seq,
                              window=None, gated=True) -> float:
    """One attention layer, a token: its four projections, the gate's
    product where there is one, and QK^T and PV on the pairs its mask
    keeps."""
    products = 2.0 * (hidden * heads * head_dim
                      + 2 * hidden * kv_heads * head_dim
                      + heads * head_dim * hidden)
    core = 4.0 * heads * head_dim * visible_pairs(seq, window) / seq
    gate = gate_flops_per_token(hidden=hidden, heads=heads,
                                head_dim=head_dim) if gated else 0.0
    return products + gate + core


def train_flops_per_token(*, hidden, head_dim, kv_heads, layer_types,
                          layer_heads, mlp_layer_types, window, gated,
                          dense_ffn, expert_ffn, shared_ffn, router_outputs,
                          held, k, vocab, seq) -> float:
    """3 x the forward pass (backward is twice forward)."""
    forward = 2.0 * hidden * vocab
    for kind, heads, mlp in zip(layer_types, layer_heads, mlp_layer_types):
        forward += attention_flops_per_token(
            hidden=hidden, heads=heads, kv_heads=kv_heads,
            head_dim=head_dim, seq=seq,
            window=window if kind == WINDOW else None, gated=gated)
        if mlp == "dense":
            forward += 6.0 * hidden * dense_ffn
        else:
            forward += 2.0 * hidden * router_outputs \
                + 6.0 * hidden * expert_ffn * pairs_per_token(
                    k=k, held=held, router_outputs=router_outputs) \
                + 6.0 * hidden * shared_ffn
    return 3.0 * forward


least_seconds = flops.least_seconds
