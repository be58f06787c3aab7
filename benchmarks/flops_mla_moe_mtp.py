"""Operations the latent-attention sparse-expert family REQUIRES of one
chip's share when it has no residual streams and a multi-token-prediction
module (`flops.py` has the conventions: a product of [m,k] by [k,n] is
2mkn, backward is twice forward, remat and the mask's wasted half do not
count). `flops_mla_moe.train_flops_per_token` counts stream mixing and one
head pass, so this configuration has a count of its own; the per-layer
formulas are that file's.

Per token, forward, with h hidden, H heads, L_d dense and L_s sparse layers
and M prediction modules (each one more sparse layer):

    every layer, L_d + L_s + M   attention projections
                                 (`attention_projection_flops`) and the
                                 causal half of QK^T and PV:
                                 s H (nope + rope + v)
    dense MLP, L_d               2 * 3 h f_dense
    sparse FFN, L_s + M          shared 2 * 3 h f_expert * shared; router
                                 2 h E; routed 2 * 3 h f_expert * k held / E
    head, 1 + M passes           2 h v_rows each, at every position (the
                                 module's last position has no target and is
                                 counted all the same: 1 of s); the
                                 embedding gathers are no products
    module's projection, M       2 (2 h) h for W_eh on [hidden ; embedding]

The module's layer, its projection and its head pass are required work: the
loss the step minimises has them. The selection biases' move is counting
and a sign over 64 numbers a layer: no product, nothing counted.
"""
from __future__ import annotations

from benchmarks import flops_mla_moe


def train_flops_per_token(*, hidden, heads, q_rank, kv_rank, nope, rope,
                          v_dim, dense_ffn, expert_ffn, shared, dense_layers,
                          sparse_layers, mtp_layers, router_outputs, held, k,
                          vocab, seq) -> float:
    """3 x the forward pass (backward is twice forward)."""
    every_layer = flops_mla_moe.attention_projection_flops(
        hidden=hidden, heads=heads, q_rank=q_rank, kv_rank=kv_rank,
        nope=nope, rope=rope, v_dim=v_dim) \
        + seq * heads * (nope + rope + v_dim)
    dense = 6.0 * hidden * dense_ffn
    sparse = 6.0 * hidden * expert_ffn * shared \
        + 2.0 * hidden * router_outputs \
        + 6.0 * hidden * expert_ffn * flops_mla_moe.pairs_per_token(
            k=k, held=held, router_outputs=router_outputs)
    head = 2.0 * hidden * vocab
    forward = (dense_layers + sparse_layers + mtp_layers) * every_layer \
        + dense_layers * dense + (sparse_layers + mtp_layers) * sparse \
        + (1 + mtp_layers) * head + mtp_layers * 2.0 * (2 * hidden) * hidden
    return 3.0 * forward
