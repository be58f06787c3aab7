"""Flash attention as a Pallas TPU kernel with a custom VJP.

Online-softmax blocked attention (the same math the reference reaches via
the dynloaded flashattn CUDA lib, paddle/phi/backends/dynload/flashattn.cc;
surface at python/paddle/nn/functional/flash_attention.py). Forward is one
kernel over query blocks: it walks the key blocks a query block sees,
carrying (m, l, acc) in float32, and writes the output and one log-sum-exp
row per query block; backward is one kernel over key blocks that forms
every score tile once from those rows and returns dQ, dK and dV (five
products a tile; dQ is summed across key blocks in VMEM). Both form a
score tile key-major ([block_k, block_q]), so the softmax statistics are
lane-dense [1, block_q] rows, and both run two loops when causal: one over
the blocks seen whole, whose body has no iota, compare or select, and one
over the blocks the diagonal crosses; blocks beyond it are not visited
(`tile_counts`). The forward takes a tile into the running softmax
`SUB_KEYS` keys at a time and keeps the output transposed ([d_v, block_q])
until it is written.

Layout inside the kernels is [batch*heads, seq, head_dim]; the public entry
takes paddle's [batch, seq, heads, head_dim]. Two widths: q and k share
`d` (written `d_qk` where both appear), v, the output and its gradient have
`d_v`, read from v's shape; `d_v = d_qk` is ordinary multi-head attention,
latent attention has 192 and 128, or 256 and 256 (caps in bfloat16,
`max_seq(256, bfloat16, ., 256)`: 6,144 forward only, 2,560 with the
backward, whose key block is 256 at those widths, `_bwd_block_k`). Every
product accumulates in
fp32 on the MXU (preferred_element_type) from operands of the IO dtype,
which is whatever the caller passes (bf16 on TPU): p and ds are rounded to
it once, the softmax math between the products is fp32. On a TPU the
kernels are Mosaic-compiled; anywhere else they run in interpret mode
(`_core.device.pallas_interpret`), so the CPU test mesh exercises
identical code.

K/V (forward) and Q/dO/dQ (backward) stay whole-sequence resident in VMEM,
so the sequence length is capped by the 16 MiB scoped-VMEM limit:
`check_vmem` computes each kernel's footprint and raises
`FlashSequenceLimitError` instead of letting Mosaic fail with
RESOURCE_EXHAUSTED (README "Flash attention sequence limit").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def vmem_footprint(sq: int, sk: int, d: int, dtype, d_v: int = None) -> dict:
    """Scoped-VMEM bytes each kernel needs with its operands in HBM; `d` is
    the width of q and k, `d_v` that of v and the output (`d` if None). The
    pipeline double-buffers every in/out block of the BlockSpecs in `_fwd`
    / `_bwd`. To that the forward adds what its body keeps of one tile:
    the float32 scores, p rounded to the inputs' dtype and the transposed
    accumulator before and after a sub-block; the backward its fp32 dQ
    accumulator, three fp32 [bk, bq] tiles, one more for each of the two
    score-shaped products (k q^T over d, v dO^T over d_v) that contracts
    256 or more, and the dK, dV sums; its key block is `_bwd_block_k`'s.
    Both are upper estimates (the compiler's own choices move its figure by
    a MiB either way), under which the v5e ahead-of-time compiler accepted
    every length up to the cap in steps of 512 (bf16 and fp32, d 64-256).
    Without the deep products' tiles it read 14.25 MiB at 160 x 2048 x
    256 / 256 with 512-key blocks, where the compiler took 16.50."""
    dv = d if d_v is None else d_v
    bq, bk = _block_sizes(sq, sk, d)
    bkb = _bwd_block_k(bk, d, dv)
    blk = functools.partial(_vmem_block_bytes, dtype=dtype)
    f32 = functools.partial(_vmem_block_bytes, dtype=jnp.float32)
    return {
        # q, o; k, v; the lse row
        "fwd": (2 * (blk(bq, d) + blk(bq, dv) + blk(sk, d) + blk(sk, dv)
                     + f32(1, bq))
                + f32(bk, bq) + blk(bk, bq) + 2 * f32(dv, bq)),
        # q, dq, do; k, dk, v, dv; the lse and delta rows
        "bwd": (2 * (2 * blk(sq, d) + blk(sq, dv) + 2 * blk(bkb, d)
                     + 2 * blk(bkb, dv) + 2 * (sq // bq) * f32(1, bq))
                + f32(d, sq)
                + (3 + (d >= DEEP_PRODUCT) + (dv >= DEEP_PRODUCT))
                * f32(bkb, bq) + f32(bkb, d) + f32(bkb, dv)),
    }


def _fits(sq: int, sk: int, d: int, dtype, backward: bool, d_v: int = None):
    """Name of the first kernel that does not fit, or None."""
    need = vmem_footprint(sq, sk, d, dtype, d_v)
    for kernel in ("fwd", "bwd") if backward else ("fwd",):
        if need[kernel] >= SCOPED_VMEM_BYTES:
            return kernel, need[kernel]
    return None


def max_seq(d: int, dtype, backward: bool, d_v: int = None) -> int:
    """Longest self-attention sequence (a multiple of 512) whose kernels
    fit at q/k width `d` and v width `d_v` (`d` if None): forward only, or
    forward and backward."""
    s = 0
    while _fits(s + 512, s + 512, d, dtype, backward, d_v) is None:
        s += 512
    return s


def _check_vmem(q, k, v, backward: bool):
    """Raise the named limit before Mosaic raises RESOURCE_EXHAUSTED.
    The compiler sometimes fits more by keeping a small operand in VMEM
    itself (it depends on batch*heads); that is not a length to rely on."""
    if pallas_interpret():
        return
    sq, d = q.shape[-2:]
    sk, dv = k.shape[-2], v.shape[-1]
    over = _fits(sq, sk, d, q.dtype, backward, dv)
    if over:
        kernel, need = over
        raise FlashSequenceLimitError(
            f"flash attention {kernel} kernel at seq_q {sq}, seq_k {sk}, "
            f"head_dim {d} (q, k) and {dv} (v), {jnp.dtype(q.dtype).name} "
            f"needs {need / 2**20:.2f} MiB of scoped VMEM; the limit is "
            f"{SCOPED_VMEM_BYTES >> 20} MiB because K/V (Q, dO and dQ in the "
            "backward) stay whole-sequence resident. Longest self-attention "
            f"sequence at these widths and dtype: "
            f"{max_seq(d, q.dtype, False, dv)} forward only, "
            f"{max_seq(d, q.dtype, True, dv)} with the backward")


# Largest query and key block. PR 25 (the backward) and PR 29 (the forward as
# it is now) swept the block pairs on a v5e at gpt2-medium's and gpt3-1.3b's
# shapes: every pair other than 512 x 512 was slower (ROADMAP C3).
MAX_BLOCK = 512


def _block_sizes(sq: int, sk: int, d: int):
    bq = min(MAX_BLOCK, sq) if sq % MAX_BLOCK == 0 else min(128, sq)
    bk = min(MAX_BLOCK, sk) if sk % MAX_BLOCK == 0 else min(128, sk)
    if sq % bq:
        bq = sq  # small/ragged: single block (wrapper pads first)
    if sk % bk:
        bk = sk
    return bq, bk


# A product that contracts this much or more keeps its partial sums in a
# float32 tile of its own (what the v5e compiler's figures say at 256 / 256).
DEEP_PRODUCT = 256


def _bwd_block_k(bk: int, d: int, dv: int) -> int:
    """The backward's key block, from the forward's `bk`: half of a full
    `MAX_BLOCK` where q / k or v are `DEEP_PRODUCT` wide or wider. A key
    block carries k, dk [bk, d], v, dv [bk, d_v] and every [bk, bq] tile of
    the body; at 512 keys and 256 / 256 the kernel does not fit at any
    length worth having (16.50 MiB at 2,048), at 256 keys it does to 2,560.
    The query block is the forward's always: the lse rows are written in
    it."""
    return bk // 2 if bk == MAX_BLOCK and max(d, dv) >= DEEP_PRODUCT else bk


# ---------------------------------------------------------------- forward

_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b
_TN = (((0,), (0,)), ((), ()))      # a^T . b


def _visible_key_blocks(qi, block_q, block_k, nkb, causal, q_offset):
    """`(full, seen)` for query block `qi` (an index, traced or not, or an
    array of them): key blocks [0, full) are visible to every query of the
    block, the diagonal `k_pos <= q_pos + q_offset` crosses [full, seen),
    and [seen, nkb) lie above it. The forward kernel's two loops run over
    exactly these ranges."""
    if not causal:
        return nkb, nkb
    limit = qi * block_q + q_offset     # last key the block's first query sees
    full = jnp.clip((limit + 1) // block_k, 0, nkb)
    seen = jnp.clip((limit + block_q - 1) // block_k + 1, full, nkb)
    return full, seen


def tile_counts(sq: int, sk: int, block_q: int, block_k: int, causal: bool,
                q_offset: int):
    """`(masked, full, skipped)` score tiles of one batch*head in the
    forward: tiles that run the body with the diagonal's compare, tiles
    that run the one without, and tiles that are not visited."""
    nqb, nkb = sq // block_q, sk // block_k
    full, seen = _visible_key_blocks(jnp.arange(nqb), block_q, block_k, nkb,
                                     causal, q_offset)
    full = int(jnp.sum(jnp.broadcast_to(full, (nqb,))))
    seen = int(jnp.sum(jnp.broadcast_to(seen, (nqb,))))
    return seen - full, full, nqb * nkb - seen


# Keys of a score tile taken into the running softmax at a time. The whole
# tile's k.qT is issued first; walked in sub-blocks of this many keys, one
# sub-block's exponentials run while the MXU streams the rest of the tile and
# the previous sub-block's vT.pT (PR 29, v5e: 128 beat 64, 256 and the whole
# tile at every shape; it is one MXU pass of the contraction).
SUB_KEYS = 128


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, scale,
                block_k, q_offset):
    """One (batch*head, query block) grid step over the key blocks this
    query block sees. Every score tile is key-major ([bk, bq]), so the
    running maximum and sum are sublane reductions into lane-dense [1, bq]
    rows and the accumulator is the transposed output [d_v, bq], turned
    once at the end. Only the tiles the diagonal crosses pay for a mask."""
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    dv = v_ref.shape[2]
    nkb = k_ref.shape[1] // block_k
    # a ragged length is one key block of its own size, taken whole
    sub = SUB_KEYS if block_k % SUB_KEYS == 0 else block_k
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)

    def tile(masked, j, carry):
        m, l, acct = carry                  # [1, bq], [1, bq], [d_v, bq]
        first = pl.multiple_of(j * block_k, block_k)
        # q is read here, not above the loops: held across them it is
        # spilled to VMEM and read back in every tile
        st = dot(k_ref[0, pl.ds(first, block_k), :], q_ref[0], _NT) * scale
        if masked:
            k_minus_q = (jax.lax.broadcasted_iota(jnp.int32, (sub, bq), 0)
                         - jax.lax.broadcasted_iota(jnp.int32, (sub, bq), 1))
            # k_pos <= q_pos + q_offset, positions counted from the tile's corner
            diagonal = qi * bq + q_offset - j * block_k
        for r in range(0, block_k, sub):
            s = st[r:r + sub]                                   # [sub, bq]
            if masked:
                s = jnp.where(k_minus_q <= diagonal - r, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            pt = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(pt, axis=0, keepdims=True)
            v = v_ref[0, pl.ds(first + r, sub), :]              # [sub, d_v]
            acct = alpha * acct + dot(v, pt.astype(v.dtype), _TN)
            m = m_new
        return m, l, acct

    full, seen = _visible_key_blocks(qi, bq, block_k, nkb, causal, q_offset)
    carry = (jnp.full((1, bq), NEG_INF, jnp.float32),
             jnp.zeros((1, bq), jnp.float32),
             jnp.zeros((dv, bq), jnp.float32))
    carry = jax.lax.fori_loop(0, full, functools.partial(tile, False), carry)
    if causal:
        carry = jax.lax.fori_loop(full, seen, functools.partial(tile, True),
                                  carry)
    m, l, acct = carry
    l_safe = jnp.where(l == 0.0, 1.0, l)            # a block that saw no key
    o_ref[0] = (acct / l_safe).T.astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l_safe)


def _fwd(q, k, v, causal, scale, block_q, block_k, q_offset):
    """out [bh, sq, d_v] and lse as the backward reads it: one lane-dense
    float32 row per query block, [bh, nqb, 1, block_q]."""
    bh, sq, d = q.shape
    sk, dv = v.shape[1:]
    nqb = sq // block_q
    with _no_x64():
        return pl.pallas_call(
            functools.partial(_fwd_kernel, causal=causal, scale=scale,
                              block_k=block_k, q_offset=q_offset),
            grid=(bh, nqb),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, sk, dv), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, 1, 1, block_q), lambda b, i: (b, i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
                jax.ShapeDtypeStruct((bh, nqb, 1, block_q), jnp.float32),
            ],
            interpret=pallas_interpret(),
        )(q, k, v)


# ---------------------------------------------------------------- backward

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dqt_acc, *, causal, scale, block_q,
                q_offset):
    """One (batch*head, key block) grid step: each score tile of this key
    block is formed once, key-major ([bk, bq], so lse and delta are
    lane-dense [1, bq] rows and dV, dK plain products), and feeds all five
    products on operands of the inputs' dtype. dQ is summed over the key
    axis in VMEM, transposed ([d, sq]: the one transposed product then
    turns the narrow k, not the tile) and written at the last key block."""
    kj = pl.program_id(1)
    k = k_ref[0]                                    # [bk, d_qk]
    v = v_ref[0]                                    # [bk, d_v]
    bk, d = k.shape
    nqb = q_ref.shape[1] // block_q
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)

    @pl.when(kj == 0)
    def _():
        dqt_acc[...] = jnp.zeros_like(dqt_acc)

    k_minus_q = (jax.lax.broadcasted_iota(jnp.int32, (bk, block_q), 0)
                 - jax.lax.broadcasted_iota(jnp.int32, (bk, block_q), 1))

    def tile(masked, i, carry):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q = q_ref[0, rows, :]                       # [bq, d_qk]
        do = do_ref[0, rows, :]                     # [bq, d_v]
        # scaled on the float32 tile, as the forward does: lse is of that s
        st = dot(k, q, _NT) * scale                 # [bk, bq]
        pt = jnp.exp(st - lse_ref[0, i])
        if masked:
            # k_pos <= q_pos + q_offset, positions counted from the tile's corner
            pt = jnp.where(
                k_minus_q <= i * block_q + q_offset - kj * bk, pt, 0.0)
        dst = (pt * (dot(v, do, _NT) - delta_ref[0, i])).astype(q.dtype)
        dv = dv + dot(pt.astype(do.dtype), do, _NN)             # [bk, d_v]
        dk = dk + dot(dst, q, _NN)                              # [bk, d_qk]
        dqt_acc[:, rows] += dot(k, dst, _TN)                    # [d, bq]
        return dk, dv

    carry = (jnp.zeros((bk, d), jnp.float32),
             jnp.zeros((bk, v.shape[1]), jnp.float32))
    if causal:
        # query blocks before `first` see none of this key block; from
        # `full` on they see all of it and the compare is left out
        first = jnp.clip((kj * bk - q_offset) // block_q, 0, nqb)
        full = jnp.clip(
            ((kj + 1) * bk - 1 - q_offset + block_q - 1) // block_q,
            first, nqb)
        carry = jax.lax.fori_loop(first, full,
                                  functools.partial(tile, True), carry)
    else:
        full = 0
    dk, dv = jax.lax.fori_loop(full, nqb, functools.partial(tile, False),
                               carry)
    # ds's scale, applied once the tile is contracted away: on [., d]
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(kj == pl.num_programs(1) - 1)
    def _():
        def write(i, _):
            rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
            dq_ref[0, rows, :] = (dqt_acc[:, rows].T * scale).astype(
                dq_ref.dtype)
        jax.lax.fori_loop(0, nqb, write, None)


def _bwd(q, k, v, out, lse, do, causal, scale, block_q, block_k, q_offset):
    bh, sq, d = q.shape
    sk, dv = v.shape[1:]
    nqb = sq // block_q
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                    # [bh, sq]
    # Q and dO stay whole-sequence resident; lse and delta come as one
    # lane-dense row per query block (a [sq, 1] block pads to 128 lanes)
    full_q = pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0))
    full_do = pl.BlockSpec((1, sq, dv), lambda b, j: (b, 0, 0))
    full_row = pl.BlockSpec((1, nqb, 1, block_q), lambda b, j: (b, 0, 0, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0))
    vspec = pl.BlockSpec((1, block_k, dv), lambda b, j: (b, j, 0))
    with _no_x64():
        return pl.pallas_call(
            functools.partial(_bwd_kernel, causal=causal, scale=scale,
                              block_q=block_q, q_offset=q_offset),
            grid=(bh, sk // block_k),
            in_specs=[full_q, kspec, vspec, full_do, full_row, full_row],
            out_specs=[full_q, kspec, vspec],
            out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                       jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                       jax.ShapeDtypeStruct((bh, sk, dv), v.dtype)],
            scratch_shapes=[pltpu.VMEM((d, sq), jnp.float32)],
            # dQ's block is revisited along the key axis
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=pallas_interpret(),
        )(q, k, v, do, lse, delta.reshape(bh, nqb, 1, block_q))


# ---------------------------------------------------------------- public

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _mha(q, k, v, causal, scale):
    _check_vmem(q, k, v, backward=False)
    return _fwd_res(q, k, v, causal, scale)[0]


def _fwd_res(q, k, v, causal, scale):
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = _block_sizes(sq, sk, d)
    out, lse = _fwd(q, k, v, causal, scale, bq, bk, q_offset=sk - sq)
    return out, (q, k, v, out, lse)


def _mha_fwd(q, k, v, causal, scale):
    _check_vmem(q, k, v, backward=True)
    return _fwd_res(q, k, v, causal, scale)


def _mha_bwd(causal, scale, res, do):
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq, bk = _block_sizes(sq, sk, d)
    return _bwd(q, k, v, out, lse, do, causal, scale, bq,
                _bwd_block_k(bk, d, v.shape[-1]), q_offset=sk - sq)


_mha.defvjp(_mha_fwd, _mha_bwd)


def mha_forward(q, k, v, causal=False, scale=None):
    """Differentiable blocked attention on [BH or B,H fused, S, D] arrays.

    Accepts [B, H, S, D] or [BH, S, D]; returns the same rank it was given.
    v may have another last dimension than q and k (latent attention:
    192 and 128); the output has v's. The default scale is q's.
    """
    squeeze = q.ndim == 4
    if squeeze:
        b, h, sq, d = q.shape
        q = q.reshape(b * h, sq, d)
        k = k.reshape(b * h, k.shape[2], d)
        v = v.reshape(b * h, v.shape[2], v.shape[3])
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    out = _mha(q, k, v, bool(causal), float(scale))
    if squeeze:
        out = out.reshape(b, h, sq, out.shape[-1])
    return out


def _fa_kernel_body(q, k, v, causal, scale):
    # paddle layout [B, S, H, D] -> [BH, S, D]
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qt = jnp.swapaxes(q, 1, 2).reshape(b * h, sq, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * h, sk, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * h, sk, v.shape[-1])
    out = _mha(qt, kt, vt, causal, scale)
    return jnp.swapaxes(out.reshape(b, h, sq, v.shape[-1]), 1, 2)


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
