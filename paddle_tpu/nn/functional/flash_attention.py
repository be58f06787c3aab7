"""Flash attention surface (python/paddle/nn/functional/flash_attention.py
analog: flash_attn_qkvpacked:562, flash_attn_unpadded:756,
flashmask_attention).

The Pallas TPU kernels (paddle_tpu.ops.pallas) are the path whenever the
arguments qualify — the TPU-native replacement for the reference's
dynloaded flashattn CUDA library (paddle/phi/backends/dynload/flashattn.cc).
What the kernels do not implement (dropout, returned softmax, a window, a
sequence that does not tile by 128) goes to the fused XLA SDPA, decided
from the arguments before the call; a kernel that fails is an error, never
a silent change of path.
"""
from __future__ import annotations

import jax.numpy as jnp

from .attention import scaled_dot_product_attention


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """Inputs [batch, seq_len, num_heads, head_dim]; returns (out, softmax)
    tuple like the reference (softmax is None unless return_softmax)."""
    if return_softmax:
        raise NotImplementedError("return_softmax=True not supported")
    if dropout == 0.0 and query.shape[1] % 128 == 0 \
            and key.shape[1] % 128 == 0:
        from ...ops.pallas import flash_attention as pallas_fa
        return pallas_fa(query, key, value, causal=causal), None
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    return out, None


def flashmask_attention(query, key, value, startend_row_indices=None,
                        dropout=0.0, causal=True, window_size=None,
                        return_softmax_lse=False, return_seed_offset=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Sparse-mask flash attention (reference flashmask_attention:1299).

    Default path is the block-sparse Pallas kernel
    (ops/pallas/flash_varlen.py): key blocks whose columns ban the whole
    query block are skipped, no [S, S] mask is ever built. The dense
    additive-mask conversion below stays as the numerics reference
    (and the fallback for dropout / window_size)."""
    if startend_row_indices is not None and dropout == 0.0 \
            and window_size is None:
        from ...ops.pallas.flash_varlen import flashmask_attention_pallas
        return flashmask_attention_pallas(
            query, key, value, startend_row_indices, causal=causal)
    return flashmask_attention_dense(
        query, key, value, startend_row_indices, dropout, causal,
        training)


def flashmask_attention_dense(query, key, value, startend_row_indices=None,
                              dropout=0.0, causal=True, training=True,
                              *unused, **unused_kw):
    """Dense-mask reference path (O(S^2) memory — test oracle only)."""
    mask = None
    if startend_row_indices is not None:
        mask = _flashmask_to_dense(query, startend_row_indices, causal)
    out = scaled_dot_product_attention(query, key, value, mask, dropout,
                                       causal if mask is None else False,
                                       training)
    return out


def _flashmask_to_dense(query, startend_row_indices, causal):
    from ..._core.tensor import Tensor
    idx = startend_row_indices._value  # [B, H, S, 1 or 2]
    b, h, s, c = idx.shape
    rows = jnp.arange(s)[:, None, None]     # query index  [S,1,1] -> later
    q_idx = jnp.arange(s)[None, None, :, None]   # [1,1,S,1] query rows
    k_idx = jnp.arange(s)[None, None, None, :]   # [1,1,1,S] key cols
    start = idx[..., 0][:, :, None, :]  # [B,H,1,S] per-key-col start row
    masked = q_idx >= jnp.swapaxes(start, -1, -2) if False else None
    # LT (lower-triangle) mask semantics: key column j is masked for query
    # rows >= startend_row_indices[b,h,j,0] (and < [...,1] if provided)
    start_rows = idx[..., 0]  # [B,H,S]
    ban = q_idx >= start_rows[:, :, None, :]
    if c > 1:
        end_rows = idx[..., 1]
        ban = ban & (q_idx < end_rows[:, :, None, :])
    if causal:
        ban = ban | (k_idx > q_idx)
    allow = ~ban
    return Tensor(allow)


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False,
                         return_softmax=False, fixed_seed_offset=None,
                         rng_name="", training=True, name=None):
    """qkv: [batch, seq, 3, num_heads, head_dim]."""
    q = qkv[:, :, 0]
    k = qkv[:, :, 1]
    v = qkv[:, :, 2]
    return flash_attention(q, k, v, dropout, causal, return_softmax,
                           fixed_seed_offset, rng_name, training)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Varlen attention (reference flash_attn_unpadded:756): ragged
    batches packed as [total_tokens, H, D] with cu_seqlens. Default path
    is the block-sparse Pallas kernel (per-query-block key-block bounds
    from cu_seqlens — O(T·block) memory); the dense segment-mask below
    stays as the numerics reference / dropout fallback."""
    if dropout == 0.0 and not return_softmax:
        from ...ops.pallas.flash_varlen import flash_attn_varlen
        out = flash_attn_varlen(query, key, value, cu_seqlens_q,
                                cu_seqlens_k, scale=scale, causal=causal)
        return out, None
    return flash_attn_unpadded_dense(
        query, key, value, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
        max_seqlen_k, scale, dropout, causal, training)


def flash_attn_unpadded_dense(query, key, value, cu_seqlens_q,
                              cu_seqlens_k, max_seqlen_q, max_seqlen_k,
                              scale, dropout=0.0, causal=False,
                              training=True):
    """Dense segment-mask reference path (O(T^2) — test oracle only)."""
    from ..._core.tensor import Tensor
    cu_q = cu_seqlens_q._value
    tq = query.shape[0]
    seg_q = jnp.cumsum(
        jnp.zeros(tq, jnp.int32).at[cu_q[1:-1]].add(1)) \
        if cu_q.shape[0] > 2 else jnp.zeros(tq, jnp.int32)
    cu_k = cu_seqlens_k._value
    tk = key.shape[0]
    seg_k = jnp.cumsum(
        jnp.zeros(tk, jnp.int32).at[cu_k[1:-1]].add(1)) \
        if cu_k.shape[0] > 2 else jnp.zeros(tk, jnp.int32)
    mask = (seg_q[:, None] == seg_k[None, :])  # [tq, tk]
    if causal:
        pos_q = jnp.arange(tq) - jnp.take(cu_q, seg_q)
        pos_k = jnp.arange(tk) - jnp.take(cu_k, seg_k)
        mask = mask & (pos_k[None, :] <= pos_q[:, None])
    # stay on the Tensor graph so the oracle is differentiable too
    qb = query.unsqueeze(0)  # [1, tq, H, D]
    kb = key.unsqueeze(0)
    vb = value.unsqueeze(0)
    mb = Tensor(mask[None, None])
    out = scaled_dot_product_attention(qb, kb, vb, mb, dropout, False,
                                       training, scale=scale)
    return out.squeeze(0), None
