"""Block-sparse varlen + flashmask attention Pallas kernels.

The reference treats variable-length (cu_seqlens) and flashmask
(startend_row_indices) attention as first-class flash kernels
(python/paddle/nn/functional/flash_attention.py:756 flash_attn_unpadded,
:1299 flashmask_attention, dynloaded CUDA flashattn underneath). The
TPU-native equivalents here are Pallas kernels that never materialise a
[T, T] mask:

- varlen: ragged batches packed as [total_tokens, H, D]. Per-token
  segment ids + in-segment positions drive the mask; per-query-block
  key-block bounds (computed from cu_seqlens with O(T) work) make the
  kernel skip key blocks outside the query block's segments, so compute
  is O(sum_i T_i^2 / block) and memory O(T·block) — not O(T^2).
- flashmask: per-key-column [start, end) banned query-row intervals.
  Key blocks whose columns ban the whole query block are skipped with
  lax.cond; everything else gets a per-element mask in-register.
  Query rows whose keys are ALL banned produce zeros (the flash l == 0
  convention; a dense softmax would degenerate to uniform attention).

Both have full custom-VJP backward (dKV over key blocks, dQ over query
blocks) with identical block skipping. Off-TPU the kernels run in
interpret mode, so the CPU test mesh executes the same code the TPU
compiles (the numerics-parity tests compare against the dense-mask
reference path in nn/functional/flash_attention.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..._core.device import pallas_interpret
from .flash_attention import NEG_INF, _no_x64

_BQ = 128
_BK = 128


def _pad_to(x, n, axis):
    pad = n - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _cdiv(a, b):
    return -(-a // b)


# ===================================================== varlen (cu_seqlens)

def _varlen_meta(cu, t_pad, pad_seg):
    """Per-token segment id (+pad_seg for padding) and in-segment
    position, all int32, shaped [t_pad, 1] for TPU-friendly blocks."""
    cu = cu.astype(jnp.int32)
    nseg = cu.shape[0] - 1
    tok = jnp.arange(t_pad, dtype=jnp.int32)
    seg = jnp.searchsorted(cu, tok, side="right").astype(jnp.int32) - 1
    seg = jnp.clip(seg, 0, nseg - 1)
    pos = tok - jnp.take(cu, seg)
    seg = jnp.where(tok < cu[-1], seg, pad_seg)
    return seg[:, None], pos[:, None]


def _varlen_qblock_bounds(seg_q, pos_q, cu_k, bq, bk, tk_pad, causal):
    """[nqb] int32 lo/hi key-block bounds per query block."""
    cu_k = cu_k.astype(jnp.int32)
    nseg = cu_k.shape[0] - 1
    nqb = seg_q.shape[0] // bq
    s2 = seg_q[:, 0].reshape(nqb, bq)
    valid = s2 >= 0
    smin = jnp.min(jnp.where(valid, s2, nseg), axis=1)
    smax = jnp.max(jnp.where(valid, s2, -1), axis=1)
    any_valid = jnp.any(valid, axis=1)
    lo_tok = jnp.take(cu_k, jnp.clip(smin, 0, nseg))
    hi_tok = jnp.take(cu_k, jnp.clip(smax + 1, 0, nseg))
    if causal:
        p2 = pos_q[:, 0].reshape(nqb, bq)
        base = jnp.take(cu_k, jnp.clip(s2, 0, nseg - 1))
        kmax = jnp.where(valid, base + p2 + 1, 0)
        hi_tok = jnp.minimum(hi_tok, jnp.max(kmax, axis=1))
    lo = jnp.where(any_valid, lo_tok // bk, 0).astype(jnp.int32)
    hi = jnp.where(any_valid, jnp.minimum(_cdiv(hi_tok, bk), tk_pad // bk),
                   0).astype(jnp.int32)
    return lo, hi


def _varlen_kblock_bounds(seg_k, pos_k, cu_q, bk, bq, tq_pad, causal):
    """[nkb] int32 lo/hi QUERY-block bounds per key block (for dKV)."""
    cu_q = cu_q.astype(jnp.int32)
    nseg = cu_q.shape[0] - 1
    nkb = seg_k.shape[0] // bk
    s2 = seg_k[:, 0].reshape(nkb, bk)
    valid = s2 >= 0
    smin = jnp.min(jnp.where(valid, s2, nseg), axis=1)
    smax = jnp.max(jnp.where(valid, s2, -1), axis=1)
    any_valid = jnp.any(valid, axis=1)
    lo_tok = jnp.take(cu_q, jnp.clip(smin, 0, nseg))
    hi_tok = jnp.take(cu_q, jnp.clip(smax + 1, 0, nseg))
    if causal:
        # a key at (seg, pos) is visible only to queries at pos_q >= pos
        p2 = pos_k[:, 0].reshape(nkb, bk)
        base = jnp.take(cu_q, jnp.clip(s2, 0, nseg - 1))
        qmin = jnp.where(valid, base + p2, tq_pad)
        lo_tok = jnp.maximum(lo_tok, jnp.min(qmin, axis=1))
    lo = jnp.where(any_valid, lo_tok // bq, 0).astype(jnp.int32)
    hi = jnp.where(any_valid, jnp.minimum(_cdiv(hi_tok, bq), tq_pad // bq),
                   0).astype(jnp.int32)
    return lo, hi


def _v_fwd_kernel(q_ref, k_ref, v_ref, sq_ref, pq_ref, sk_ref, pk_ref,
                  lo_ref, hi_ref, o_ref, lse_ref, *, scale, causal,
                  block_k):
    q = q_ref[0]                                     # [bq, d]
    bq, d = q.shape
    seg_q = sq_ref[...]                              # [bq, 1]
    pos_q = pq_ref[...]
    qi = pl.program_id(1)
    lo = lo_ref[qi]
    hi = hi_ref[qi]

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        seg_k = jnp.swapaxes(sk_ref[pl.ds(j * block_k, block_k), :], 0, 1)
        pos_k = jnp.swapaxes(pk_ref[pl.ds(j * block_k, block_k), :], 0, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = seg_q == seg_k                        # [bq, bk]
        if causal:
            mask &= pos_k <= pos_q
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0] = jnp.where(l[:, None] == 0.0, 0.0,
                           m[:, None] + jnp.log(l_safe[:, None]))


def _v_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  sq_ref, pq_ref, sk_ref, pk_ref, lo_ref, hi_ref,
                  dk_ref, dv_ref, *, scale, causal, block_q):
    k = k_ref[0]                                     # [bk, d]
    v = v_ref[0]
    bk, d = k.shape
    seg_k = jnp.swapaxes(sk_ref[...], 0, 1)          # [1, bk]
    pos_k = jnp.swapaxes(pk_ref[...], 0, 1)
    kj = pl.program_id(1)
    lo = lo_ref[kj]
    hi = hi_ref[kj]

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), :]
        seg_q = sq_ref[pl.ds(i * block_q, block_q), :]   # [bq, 1]
        pos_q = pq_ref[pl.ds(i * block_q, block_q), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = seg_q == seg_k
        if causal:
            mask &= pos_k <= pos_q
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_new = dv + jax.lax.dot_general(
            p, do.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_new = dk + jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, hi, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _v_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 sq_ref, pq_ref, sk_ref, pk_ref, lo_ref, hi_ref,
                 dq_ref, *, scale, causal, block_k):
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]
    delta = delta_ref[0]
    bq, d = q.shape
    seg_q = sq_ref[...]
    pos_q = pq_ref[...]
    qi = pl.program_id(1)
    lo = lo_ref[qi]
    hi = hi_ref[qi]

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        seg_k = jnp.swapaxes(sk_ref[pl.ds(j * block_k, block_k), :], 0, 1)
        pos_k = jnp.swapaxes(pk_ref[pl.ds(j * block_k, block_k), :], 0, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = seg_q == seg_k
        if causal:
            mask &= pos_k <= pos_q
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(lo, hi, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _v_specs(h, t_pad, tk_pad, d, bq, bk):
    qspec = pl.BlockSpec((1, bq, d), lambda hh, i: (hh, i, 0))
    kfull = pl.BlockSpec((1, tk_pad, d), lambda hh, i: (hh, 0, 0))
    mq = pl.BlockSpec((bq, 1), lambda hh, i: (i, 0))
    mkfull = pl.BlockSpec((tk_pad, 1), lambda hh, i: (0, 0))
    bound = pl.BlockSpec(memory_space=pltpu.SMEM)
    return qspec, kfull, mq, mkfull, bound


def _varlen_fwd(q, k, v, segq, posq, segk, posk, lo, hi, scale, causal,
                bq, bk):
    h, tq_pad, d = q.shape
    tk_pad = k.shape[1]
    qspec, kfull, mq, mkfull, bound = _v_specs(h, tq_pad, tk_pad, d, bq, bk)
    with _no_x64():
        out, lse = pl.pallas_call(
            functools.partial(_v_fwd_kernel, scale=scale, causal=causal,
                              block_k=bk),
            grid=(h, tq_pad // bq),
            in_specs=[qspec, kfull, kfull, mq, mq, mkfull, mkfull,
                      bound, bound],
            out_specs=[qspec,
                       pl.BlockSpec((1, bq, 1), lambda hh, i: (hh, i, 0))],
            out_shape=[jax.ShapeDtypeStruct((h, tq_pad, d), q.dtype),
                       jax.ShapeDtypeStruct((h, tq_pad, 1), jnp.float32)],
            interpret=pallas_interpret(),
        )(q, k, v, segq, posq, segk, posk, lo, hi)
    return out, lse


def _varlen_bwd(q, k, v, out, lse, do, segq, posq, segk, posk,
                qlo, qhi, klo, khi, scale, causal, bq, bk):
    h, tq_pad, d = q.shape
    tk_pad = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    qfull = pl.BlockSpec((1, tq_pad, d), lambda hh, j: (hh, 0, 0))
    rowfull = pl.BlockSpec((1, tq_pad, 1), lambda hh, j: (hh, 0, 0))
    kspec = pl.BlockSpec((1, bk, d), lambda hh, j: (hh, j, 0))
    mqfull = pl.BlockSpec((tq_pad, 1), lambda hh, j: (0, 0))
    mk = pl.BlockSpec((bk, 1), lambda hh, j: (j, 0))
    kbound = pl.BlockSpec(memory_space=pltpu.SMEM)
    with _no_x64():
        dk, dv = pl.pallas_call(
            functools.partial(_v_dkv_kernel, scale=scale, causal=causal,
                              block_q=bq),
            grid=(h, tk_pad // bk),
            in_specs=[qfull, kspec, kspec, qfull, rowfull, rowfull,
                      mqfull, mqfull, mk, mk, kbound, kbound],
            out_specs=[kspec, kspec],
            out_shape=[jax.ShapeDtypeStruct((h, tk_pad, d), k.dtype),
                       jax.ShapeDtypeStruct((h, tk_pad, d), v.dtype)],
            interpret=pallas_interpret(),
        )(q, k, v, do, lse, delta, segq, posq, segk, posk, klo, khi)

    qspec = pl.BlockSpec((1, bq, d), lambda hh, i: (hh, i, 0))
    row = pl.BlockSpec((1, bq, 1), lambda hh, i: (hh, i, 0))
    kf = pl.BlockSpec((1, tk_pad, d), lambda hh, i: (hh, 0, 0))
    mq = pl.BlockSpec((bq, 1), lambda hh, i: (i, 0))
    mkf = pl.BlockSpec((tk_pad, 1), lambda hh, i: (0, 0))
    qbound = pl.BlockSpec(memory_space=pltpu.SMEM)
    with _no_x64():
        dq = pl.pallas_call(
            functools.partial(_v_dq_kernel, scale=scale, causal=causal,
                              block_k=bk),
            grid=(h, tq_pad // bq),
            in_specs=[qspec, kf, kf, qspec, row, row,
                      mq, mq, mkf, mkf, qbound, qbound],
            out_specs=qspec,
            out_shape=jax.ShapeDtypeStruct((h, tq_pad, d), q.dtype),
            interpret=pallas_interpret(),
        )(q, k, v, do, lse, delta, segq, posq, segk, posk, qlo, qhi)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(11, 12, 13, 14))
def _varlen(q, k, v, segq, posq, segk, posk, qlo, qhi, klo, khi,
            scale, causal, bq, bk):
    out, _ = _varlen_fwd(q, k, v, segq, posq, segk, posk, qlo, qhi,
                         scale, causal, bq, bk)
    return out


def _varlen_fwd_rule(q, k, v, segq, posq, segk, posk, qlo, qhi, klo, khi,
                     scale, causal, bq, bk):
    out, lse = _varlen_fwd(q, k, v, segq, posq, segk, posk, qlo, qhi,
                           scale, causal, bq, bk)
    return out, (q, k, v, out, lse, segq, posq, segk, posk,
                 qlo, qhi, klo, khi)


def _varlen_bwd_rule(scale, causal, bq, bk, res, do):
    (q, k, v, out, lse, segq, posq, segk, posk, qlo, qhi, klo, khi) = res
    dq, dk, dv = _varlen_bwd(q, k, v, out, lse, do, segq, posq, segk,
                             posk, qlo, qhi, klo, khi, scale, causal,
                             bq, bk)
    return (dq, dk, dv) + (None,) * 8


_varlen.defvjp(_varlen_fwd_rule, _varlen_bwd_rule)


def _varlen_body(q, k, v, cu_q, cu_k, scale, causal):
    """Registered kernel body: packed [T, H, D] inputs."""
    tq, h, d = q.shape
    tk = k.shape[0]
    bq = min(_BQ, _cdiv(tq, 1))
    bk = min(_BK, _cdiv(tk, 1))
    tq_pad = _cdiv(tq, bq) * bq
    tk_pad = _cdiv(tk, bk) * bk
    qt = _pad_to(jnp.moveaxis(q, 1, 0), tq_pad, 1)     # [H, Tq, D]
    kt = _pad_to(jnp.moveaxis(k, 1, 0), tk_pad, 1)
    vt = _pad_to(jnp.moveaxis(v, 1, 0), tk_pad, 1)
    segq, posq = _varlen_meta(cu_q, tq_pad, pad_seg=-1)
    segk, posk = _varlen_meta(cu_k, tk_pad, pad_seg=-2)
    qlo, qhi = _varlen_qblock_bounds(segq, posq, cu_k, bq, bk, tk_pad,
                                     causal)
    klo, khi = _varlen_kblock_bounds(segk, posk, cu_q, bk, bq, tq_pad,
                                     causal)
    out = _varlen(qt, kt, vt, segq, posq, segk, posk, qlo, qhi, klo, khi,
                  float(scale), bool(causal), bq, bk)
    return jnp.moveaxis(out[:, :tq, :], 0, 1)          # [Tq, H, D]


def flash_attn_varlen(query, key, value, cu_seqlens_q, cu_seqlens_k,
                      scale=None, causal=False):
    """Public block-sparse varlen entry on framework Tensors. Packed
    layout [total_tokens, num_heads, head_dim] with int32 cu_seqlens."""
    from ..._core.executor import apply
    from ..._core.op_registry import all_ops, register_op
    if "flash_attn_varlen" not in all_ops():
        register_op("flash_attn_varlen", _varlen_body)
    if scale is None:
        scale = 1.0 / (query.shape[-1] ** 0.5)
    return apply("flash_attn_varlen", query, key, value, cu_seqlens_q,
                 cu_seqlens_k, scale=float(scale), causal=bool(causal))


# ============================================ flashmask (startend indices)

def _fm_fwd_kernel(q_ref, k_ref, v_ref, st_ref, en_ref, o_ref, lse_ref, *,
                   scale, causal, block_k, kv_len):
    qi = pl.program_id(1)
    q = q_ref[0]
    bq, d = q.shape
    sk_pad = k_ref.shape[1]
    nkb = sk_pad // block_k
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
    q_lo = qi * bq
    q_hi = q_lo + bq

    def compute(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        st = jnp.swapaxes(st_ref[0, pl.ds(j * block_k, block_k), :], 0, 1)
        en = jnp.swapaxes(en_ref[0, pl.ds(j * block_k, block_k), :], 0, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        ban = (q_pos >= st) & (q_pos < en)
        mask = ~ban & (k_pos < kv_len)
        if causal:
            mask &= k_pos <= q_pos
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    def body(j, carry):
        # skip key blocks whose every column bans the whole query block
        # (int32 min-reduction: Mosaic only scalarises 32-bit types)
        st = st_ref[0, pl.ds(j * block_k, block_k), :]
        en = en_ref[0, pl.ds(j * block_k, block_k), :]
        ok = ((st <= q_lo) & (en >= q_hi)).astype(jnp.int32)
        full_ban = jnp.min(ok) == 1
        return jax.lax.cond(full_ban, lambda c: c,
                            lambda c: compute(j, c), carry)

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    if causal:
        nkb_eff = jnp.minimum(((qi * bq + bq - 1) // block_k) + 1, nkb)
    else:
        nkb_eff = nkb
    m, l, acc = jax.lax.fori_loop(0, nkb_eff, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0] = jnp.where(l[:, None] == 0.0, 0.0,
                           m[:, None] + jnp.log(l_safe[:, None]))


def _fm_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   st_ref, en_ref, dk_ref, dv_ref, *, scale, causal,
                   block_q, kv_len):
    kj = pl.program_id(1)
    k = k_ref[0]
    v = v_ref[0]
    bk, d = k.shape
    sq = q_ref.shape[1]
    nqb = sq // block_q
    st_col = jnp.swapaxes(st_ref[0], 0, 1)           # [1, bk]
    en_col = jnp.swapaxes(en_ref[0], 0, 1)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)

    def compute(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        ban = (q_pos >= st_col) & (q_pos < en_col)
        mask = ~ban & (k_pos < kv_len)
        if causal:
            mask &= k_pos <= q_pos
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv_new = dv + jax.lax.dot_general(
            p, do.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_new = dk + jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    def body(i, carry):
        q_lo = i * block_q
        q_hi = q_lo + block_q
        ok = ((st_col <= q_lo) & (en_col >= q_hi)).astype(jnp.int32)
        full_ban = jnp.min(ok) == 1
        return jax.lax.cond(full_ban, lambda c: c,
                            lambda c: compute(i, c), carry)

    if causal:
        first = jnp.maximum((kj * bk) // block_q, 0)
    else:
        first = 0
    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(first, nqb, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _fm_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  st_ref, en_ref, dq_ref, *, scale, causal, block_k,
                  kv_len):
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]
    delta = delta_ref[0]
    bq, d = q.shape
    sk = k_ref.shape[1]
    nkb = sk // block_k
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
    q_lo = qi * bq
    q_hi = q_lo + bq

    def compute(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        st = jnp.swapaxes(st_ref[0, pl.ds(j * block_k, block_k), :], 0, 1)
        en = jnp.swapaxes(en_ref[0, pl.ds(j * block_k, block_k), :], 0, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        ban = (q_pos >= st) & (q_pos < en)
        mask = ~ban & (k_pos < kv_len)
        if causal:
            mask &= k_pos <= q_pos
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def body(j, dq):
        st = st_ref[0, pl.ds(j * block_k, block_k), :]
        en = en_ref[0, pl.ds(j * block_k, block_k), :]
        ok = ((st <= q_lo) & (en >= q_hi)).astype(jnp.int32)
        full_ban = jnp.min(ok) == 1
        return jax.lax.cond(full_ban, lambda c: c,
                            lambda c: compute(j, c), dq)

    if causal:
        nkb_eff = jnp.minimum(((qi * bq + bq - 1) // block_k) + 1, nkb)
    else:
        nkb_eff = nkb
    dq = jax.lax.fori_loop(0, nkb_eff, body,
                           jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _fm_fwd(q, k, v, st, en, scale, causal, bq, bk, kv_len):
    bh, sq_pad, d = q.shape
    sk_pad = k.shape[1]
    qspec = pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0))
    kfull = pl.BlockSpec((1, sk_pad, d), lambda b, i: (b, 0, 0))
    colfull = pl.BlockSpec((1, sk_pad, 1), lambda b, i: (b, 0, 0))
    with _no_x64():
        out, lse = pl.pallas_call(
            functools.partial(_fm_fwd_kernel, scale=scale, causal=causal,
                              block_k=bk, kv_len=kv_len),
            grid=(bh, sq_pad // bq),
            in_specs=[qspec, kfull, kfull, colfull, colfull],
            out_specs=[qspec,
                       pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0))],
            out_shape=[jax.ShapeDtypeStruct((bh, sq_pad, d), q.dtype),
                       jax.ShapeDtypeStruct((bh, sq_pad, 1), jnp.float32)],
            interpret=pallas_interpret(),
        )(q, k, v, st, en)
    return out, lse


def _fm_bwd(q, k, v, out, lse, do, st, en, scale, causal, bq, bk, kv_len):
    bh, sq_pad, d = q.shape
    sk_pad = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    qfull = pl.BlockSpec((1, sq_pad, d), lambda b, j: (b, 0, 0))
    rowfull = pl.BlockSpec((1, sq_pad, 1), lambda b, j: (b, 0, 0))
    kspec = pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0))
    colspec = pl.BlockSpec((1, bk, 1), lambda b, j: (b, j, 0))
    with _no_x64():
        dk, dv = pl.pallas_call(
            functools.partial(_fm_dkv_kernel, scale=scale, causal=causal,
                              block_q=bq, kv_len=kv_len),
            grid=(bh, sk_pad // bk),
            in_specs=[qfull, kspec, kspec, qfull, rowfull, rowfull,
                      colspec, colspec],
            out_specs=[kspec, kspec],
            out_shape=[jax.ShapeDtypeStruct((bh, sk_pad, d), k.dtype),
                       jax.ShapeDtypeStruct((bh, sk_pad, d), v.dtype)],
            interpret=pallas_interpret(),
        )(q, k, v, do, lse, delta, st, en)

    qspec = pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0))
    row = pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0))
    kf = pl.BlockSpec((1, sk_pad, d), lambda b, i: (b, 0, 0))
    colf = pl.BlockSpec((1, sk_pad, 1), lambda b, i: (b, 0, 0))
    with _no_x64():
        dq = pl.pallas_call(
            functools.partial(_fm_dq_kernel, scale=scale, causal=causal,
                              block_k=bk, kv_len=kv_len),
            grid=(bh, sq_pad // bq),
            in_specs=[qspec, kf, kf, qspec, row, row, colf, colf],
            out_specs=qspec,
            out_shape=jax.ShapeDtypeStruct((bh, sq_pad, d), q.dtype),
            interpret=pallas_interpret(),
        )(q, k, v, do, lse, delta, st, en)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _fmask(q, k, v, st, en, scale, causal, bq, bk, kv_len):
    out, _ = _fm_fwd(q, k, v, st, en, scale, causal, bq, bk, kv_len)
    return out


def _fmask_fwd_rule(q, k, v, st, en, scale, causal, bq, bk, kv_len):
    out, lse = _fm_fwd(q, k, v, st, en, scale, causal, bq, bk, kv_len)
    return out, (q, k, v, out, lse, st, en)


def _fmask_bwd_rule(scale, causal, bq, bk, kv_len, res, do):
    q, k, v, out, lse, st, en = res
    dq, dk, dv = _fm_bwd(q, k, v, out, lse, do, st, en, scale, causal,
                         bq, bk, kv_len)
    return dq, dk, dv, None, None


_fmask.defvjp(_fmask_fwd_rule, _fmask_bwd_rule)


def _flashmask_body(q, k, v, startend, scale, causal):
    """Registered kernel body. q/k/v [B, S, H, D]; startend
    [B, H or 1, S, 1 or 2] int (LT semantics: key column j is banned for
    query rows in [start_j, end_j), matching the dense reference in
    nn/functional/flash_attention.py:_flashmask_to_dense)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq = min(_BQ, sq)
    bk = min(_BK, sk)
    sq_pad = _cdiv(sq, bq) * bq
    sk_pad = _cdiv(sk, bk) * bk
    qt = _pad_to(jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d), sq_pad, 1)
    kt = _pad_to(jnp.swapaxes(k, 1, 2).reshape(b * h, sk, d), sk_pad, 1)
    vt = _pad_to(jnp.swapaxes(v, 1, 2).reshape(b * h, sk, d), sk_pad, 1)
    idx = startend.astype(jnp.int32)
    if idx.shape[1] == 1 and h > 1:
        idx = jnp.broadcast_to(idx, (b, h, sk) + idx.shape[3:])
    st = idx[..., 0].reshape(b * h, sk)
    if idx.shape[-1] > 1:
        en = idx[..., 1].reshape(b * h, sk)
    else:
        # open-ended ban: use int32 max, not sk_pad + 1, so query rows
        # beyond the key length (sq > sk) are still inside the interval
        en = jnp.full_like(st, jnp.iinfo(jnp.int32).max)
    # padded key columns: banned everywhere via kv_len; padded query rows
    # produce zeros (l == 0) and are sliced off
    st = _pad_to(st, sk_pad, 1)[..., None]
    en = _pad_to(en, sk_pad, 1)[..., None]
    out = _fmask(qt, kt, vt, st, en, float(scale), bool(causal),
                 bq, bk, sk)
    return jnp.swapaxes(out[:, :sq, :].reshape(b, h, sq, d), 1, 2)


def flashmask_attention_pallas(query, key, value, startend_row_indices,
                               scale=None, causal=True):
    """Public block-sparse flashmask entry on framework Tensors."""
    from ..._core.executor import apply
    from ..._core.op_registry import all_ops, register_op
    if "flashmask_attention" not in all_ops():
        register_op("flashmask_attention", _flashmask_body)
    if scale is None:
        scale = 1.0 / (query.shape[-1] ** 0.5)
    return apply("flashmask_attention", query, key, value,
                 startend_row_indices, scale=float(scale),
                 causal=bool(causal))
