"""Flash attention as a Pallas TPU kernel with a custom VJP.

Online-softmax blocked attention (the same math the reference reaches via
the dynloaded flashattn CUDA lib, paddle/phi/backends/dynload/flashattn.cc;
surface at python/paddle/nn/functional/flash_attention.py). Forward streams
K/V blocks through VMEM against a resident Q block, carrying (m, l, acc)
accumulators; backward is the standard two-kernel split (dKV over key
blocks, dQ over query blocks) using the saved log-sum-exp rows.

Layout inside the kernels is [batch*heads, seq, head_dim]; the public entry
takes paddle's [batch, seq, heads, head_dim]. Logit math is fp32 on the MXU
(preferred_element_type), IO dtype is whatever the caller passes (bf16 on
TPU). On a TPU the kernels are Mosaic-compiled; anywhere else they run in
interpret mode (`_core.device.pallas_interpret`), so the CPU test mesh
exercises identical code.

K/V (forward, dQ) and Q/dO (dKV) stay whole-sequence resident in VMEM, so
the sequence length is capped by the 16 MiB scoped-VMEM limit:
`check_vmem` computes each kernel's footprint and raises
`FlashSequenceLimitError` instead of letting Mosaic fail with
RESOURCE_EXHAUSTED (README "Flash attention sequence limit").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.sharding import PartitionSpec as _P

from ..._core.device import pallas_interpret

NEG_INF = -1e30


def _no_x64():
    """Trace pallas kernels with x64 OFF: the framework enables
    jax_enable_x64 globally (paddle int64 parity), but Mosaic has no
    64-bit scalars (an index map returning an int64 zero fails to
    legalize). Kernel math is int32/fp32/bf16 regardless."""
    return jax.enable_x64(False)


# ---------------------------------------------------- scoped-VMEM sequence cap

# Mosaic's default scoped-VMEM limit on v5e ("Scoped allocation with size
# … and limit 16.00M" from the TPU compiler).
SCOPED_VMEM_BYTES = 16 << 20


class FlashSequenceLimitError(ValueError):
    """The sequence is longer than the whole-sequence-resident flash
    kernels can hold in scoped VMEM."""


def _vmem_block_bytes(rows: int, cols: int, dtype) -> int:
    """One block as VMEM tiles it: 128 lanes x 8 sublanes of 32 bits."""
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // itemsize
    return (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize


def vmem_footprint(sq: int, sk: int, d: int, dtype) -> dict:
    """Scoped-VMEM bytes each kernel needs with its operands in HBM:
    the pipeline double-buffers every in/out block of the BlockSpecs in
    `_fwd_call` / `_bwd`. Agrees with the compiler's own "scoped
    allocation" figure to its printed precision (bf16 and fp32, d 64-256,
    seq 1k-8k on the v5e ahead-of-time compiler); a [seq, 1] fp32 row
    pads to 128 lanes, which is why dKV is the largest."""
    bq, bk = _block_sizes(sq, sk, d)
    blk = functools.partial(_vmem_block_bytes, dtype=dtype)
    row = functools.partial(_vmem_block_bytes, cols=1, dtype=jnp.float32)
    return {
        "fwd": 2 * (2 * blk(bq, d) + 2 * blk(sk, d) + row(bq)),
        "dkv": 2 * (2 * blk(sq, d) + 2 * row(sq) + 4 * blk(bk, d)),
        "dq": 2 * (2 * blk(sk, d) + 3 * blk(bq, d) + 2 * row(bq)),
    }


def _fits(sq: int, sk: int, d: int, dtype, backward: bool):
    """Name of the first kernel that does not fit, or None."""
    need = vmem_footprint(sq, sk, d, dtype)
    for kernel in ("fwd", "dkv", "dq") if backward else ("fwd",):
        if need[kernel] >= SCOPED_VMEM_BYTES:
            return kernel, need[kernel]
    return None


def max_seq(d: int, dtype, backward: bool) -> int:
    """Longest self-attention sequence (a multiple of 512) whose kernels
    fit: forward only, or forward and backward."""
    s = 0
    while _fits(s + 512, s + 512, d, dtype, backward) is None:
        s += 512
    return s


def _check_vmem(q, k, backward: bool):
    """Raise the named limit before Mosaic raises RESOURCE_EXHAUSTED.
    The compiler sometimes fits more by keeping a small operand in VMEM
    itself (it depends on batch*heads); that is not a length to rely on."""
    if pallas_interpret():
        return
    sq, d = q.shape[-2:]
    sk = k.shape[-2]
    over = _fits(sq, sk, d, q.dtype, backward)
    if over:
        kernel, need = over
        raise FlashSequenceLimitError(
            f"flash attention {kernel} kernel at seq_q {sq}, seq_k {sk}, "
            f"head_dim {d}, {jnp.dtype(q.dtype).name} needs "
            f"{need / 2**20:.2f} MiB of scoped VMEM; the limit is "
            f"{SCOPED_VMEM_BYTES >> 20} MiB because K/V (and Q/dO in the "
            "backward) stay whole-sequence resident. Longest self-attention "
            f"sequence at this head_dim and dtype: "
            f"{max_seq(d, q.dtype, False)} forward only, "
            f"{max_seq(d, q.dtype, True)} with the backward")


def _block_sizes(sq: int, sk: int, d: int):
    from ..._core.flags import flag_value
    cap_q = int(flag_value("FLAGS_flash_block_q"))
    cap_k = int(flag_value("FLAGS_flash_block_k"))
    bq = min(cap_q, sq) if sq % cap_q == 0 else min(128, sq)
    bk = min(cap_k, sk) if sk % cap_k == 0 else min(128, sk)
    if sq % bq:
        bq = sq  # small/ragged: single block (wrapper pads first)
    if sk % bk:
        bk = sk
    return bq, bk


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, scale,
                block_k, kv_len, q_offset):
    qi = pl.program_id(1)
    q = q_ref[0]                                    # [bq, d]
    bq, d = q.shape
    sk_pad = k_ref.shape[1]
    nkb = sk_pad // block_k

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]          # [bk, d]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos + q_offset
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    if causal:
        # keys beyond the last valid diagonal block never contribute
        last = (qi * bq + bq - 1) + q_offset
        nkb_eff = jnp.minimum((last // block_k) + 1, nkb)
    else:
        nkb_eff = nkb
    m, l, acc = jax.lax.fori_loop(0, nkb_eff, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # [bq, 1]: the trailing singleton keeps the block's last dim equal to
    # the array's (TPU tiling rule) and broadcasts cleanly in the bwd
    lse_ref[0] = (m + jnp.log(l_safe))[:, None]


def _fwd(q, k, v, causal, scale, block_q, block_k, kv_len, q_offset):
    bh, sq, d = q.shape
    sk = k.shape[1]
    grid = (bh, sq // block_q)
    with _no_x64():
        out, lse = _fwd_call(q, k, v, causal, scale, block_k, kv_len,
                             q_offset, block_q, grid, bh, sq, sk, d)
    return out, lse


def _fwd_call(q, k, v, causal, scale, block_k, kv_len, q_offset, block_q,
              grid, bh, sq, sk, d):
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale,
                          block_k=block_k, kv_len=kv_len, q_offset=q_offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        interpret=pallas_interpret(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------- backward

def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, causal, scale, block_q, kv_len, q_offset):
    kj = pl.program_id(1)
    k = k_ref[0]                                    # [bk, d]
    v = v_ref[0]
    bk, d = k.shape
    sq = q_ref.shape[1]
    nqb = sq // block_q
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :]   # [bq, 1]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [bq, bk]
        q_pos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos + q_offset
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)            # [bq, bk]
        dv_new = dv + jax.lax.dot_general(
            p, do.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bq, bk]
        ds = p * (dp - delta) * scale
        dk_new = dk + jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [bk, d]
        return dk_new, dv_new

    if causal:
        # query rows before this key block's first diagonal see none of it
        first = jnp.maximum((kj * bk - q_offset) // block_q, 0)
    else:
        first = 0
    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(first, nqb, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               causal, scale, block_k, kv_len, q_offset):
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]       # [bq, 1]
    delta = delta_ref[0]   # [bq, 1]
    bq, d = q.shape
    sk = k_ref.shape[1]
    nkb = sk // block_k
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos + q_offset
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        last = (qi * bq + bq - 1) + q_offset
        nkb_eff = jnp.minimum((last // block_k) + 1, nkb)
    else:
        nkb_eff = nkb
    dq = jax.lax.fori_loop(0, nkb_eff, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd(q, k, v, out, lse, do, causal, scale, block_q, block_k, kv_len,
         q_offset):
    bh, sq, d = q.shape
    sk = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                    # [bh, sq, 1]
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    full_q = pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0))
    full_row = pl.BlockSpec((1, sq, 1), lambda b, j: (b, 0, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0))
    full_k = pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0))

    with _no_x64():
        dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, kv_len=kv_len, q_offset=q_offset),
        grid=(bh, sk // block_k),
        in_specs=[full_q, kspec, kspec, full_q, full_row, full_row],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype)],
            interpret=pallas_interpret(),
        )(q, k, v, do, lse, delta)

    rowspec = pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0))
    with _no_x64():
        dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale,
                          block_k=block_k, kv_len=kv_len, q_offset=q_offset),
        grid=(bh, sq // block_q),
        in_specs=[qspec, full_k, full_k, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            interpret=pallas_interpret(),
        )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------- public

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _mha(q, k, v, causal, scale):
    _check_vmem(q, k, backward=False)
    return _fwd_res(q, k, v, causal, scale)[0]


def _fwd_res(q, k, v, causal, scale):
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = _block_sizes(sq, sk, d)
    out, lse = _fwd(q, k, v, causal, scale, bq, bk, kv_len=sk,
                    q_offset=sk - sq)
    return out, (q, k, v, out, lse)


def _mha_fwd(q, k, v, causal, scale):
    _check_vmem(q, k, backward=True)
    return _fwd_res(q, k, v, causal, scale)


def _mha_bwd(causal, scale, res, do):
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = _block_sizes(sq, sk, d)
    dq, dk, dv = _bwd(q, k, v, out, lse, do, causal, scale, bq, bk,
                      kv_len=sk, q_offset=sk - sq)
    return dq, dk, dv


_mha.defvjp(_mha_fwd, _mha_bwd)


def mha_forward(q, k, v, causal=False, scale=None):
    """Differentiable blocked attention on [BH or B,H fused, S, D] arrays.

    Accepts [B, H, S, D] or [BH, S, D]; returns the same rank it was given.
    """
    squeeze = q.ndim == 4
    if squeeze:
        b, h, sq, d = q.shape
        q = q.reshape(b * h, sq, d)
        k = k.reshape(b * h, k.shape[2], d)
        v = v.reshape(b * h, v.shape[2], d)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    out = _mha(q, k, v, bool(causal), float(scale))
    if squeeze:
        out = out.reshape(b, h, sq, d)
    return out


def _fa_kernel_body(q, k, v, causal, scale):
    # paddle layout [B, S, H, D] -> [BH, S, D]
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qt = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * h, sk, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * h, sk, d)
    out = _mha(qt, kt, vt, causal, scale)
    return jnp.swapaxes(out.reshape(b, h, sq, d), 1, 2)


def flash_attention(query, key, value, causal=False, scale=None):
    """Public entry on framework Tensors (or raw arrays), paddle layout
    [batch, seq, heads, head_dim]. Seq lens must tile by 128 (the nn
    wrapper falls back to fused-XLA SDPA otherwise)."""
    from ..._core.executor import apply
    from ..._core.op_registry import all_ops, register_op
    if "flash_attention" not in all_ops():
        register_op("flash_attention", _fa_kernel_body)
    d = (query.shape[-1])
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    sq, sk = query.shape[1], key.shape[1]
    if sq % 128 or sk % 128:
        raise ValueError(f"flash_attention pallas kernel needs seq % 128 == 0"
                         f" (got q={sq}, k={sk})")
    return apply("flash_attention", query, key, value, causal=bool(causal),
                 scale=float(scale))


# ------------------------------------------------------- sharded dispatch

def mha_sharded(q, k, v, mesh, causal=False, scale=None):
    """Flash attention on mesh-sharded [B, H, S, D] arrays under jit.

    Mosaic kernels cannot be partitioned automatically, so the call is
    wrapped in a ``shard_map`` that is manual over EVERY mesh axis GSPMD
    still owns here (size 1 or not): batch splits over 'dp', heads over
    'mp', seq/head_dim are gathered at the boundary. Under plain jit that
    is the whole mesh; inside the compiled-pp body ('pp' already manual,
    pipeline_compiled.py) it is the remaining axes of the context mesh.
    The TPU analog of the reference wiring flash-attn into its SPMD rules
    (phi/infermeta/spmd_rules)."""
    ctx_mesh = jax.sharding.get_abstract_mesh()
    nested = bool(ctx_mesh.manual_axes)
    axes = set(mesh.axis_names) - set(ctx_mesh.manual_axes)
    for axis, dim, what in (("dp", q.shape[0], "batch"),
                            ("mp", q.shape[1], "heads")):
        if axis in axes and dim % mesh.shape[axis]:
            raise ValueError(
                f"flash attention: {what} {dim} not divisible by mesh "
                f"axis {axis!r} of size {mesh.shape[axis]}")
    spec = _P("dp" if "dp" in axes else None,
              "mp" if "mp" in axes else None, None, None)
    return jax.shard_map(
        functools.partial(mha_forward, causal=causal, scale=scale),
        mesh=ctx_mesh if nested else mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=axes, check_vma=False)(q, k, v)
