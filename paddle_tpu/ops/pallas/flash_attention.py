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
(`tile_counts`). A `window` (a query sees the `window` keys up to its own)
is a second, lower diagonal: a third loop runs the blocks it crosses, and
the blocks under it are not visited either, so a layer's time follows the
tiles it visits (21 of 64 at 4,096 tokens and a 1,024-key window, where
causal visits 36). The forward takes a tile into the running softmax
`SUB_KEYS` keys at a time and keeps the output transposed ([d_v, block_q])
until it is written.

Grouped-query attention: k and v may have fewer heads than q (`kv_heads`
dividing `heads`). Query head h reads K/V head h // (heads / kv_heads)
through the BlockSpecs' index maps, so no array of `heads` K/V heads exists
in HBM in either direction; the backward's grid walks a K/V head's query
heads one after another and sums their dK and dV in float32 VMEM
([seq_k, d], [seq_k, d_v]) before it writes them, [.., kv_heads d] wide.

Two entries, one kernel body. `mha_seq_major` takes q, k [batch, seq,
heads * d_qk] and v [batch, seq, heads * d_v] as the projections write them
and returns the output, dQ, dK and dV in that layout: a grid step handles
the `g` heads whose columns fill whole 128-lane blocks (`head_group`: 1 at
128 or 256 / 256, 2 at 64 and at 192 / 128), one head after another on lane
slices of the loaded blocks, and writes the group's results as one block,
so nothing is swapped or copied around the kernels. `mha_forward` takes
head-major [batch*heads, seq, head_dim], one head a grid step: the same
body as a group of one on blocks one head wide. `head_group` decides from
the shapes alone; where it gives None (a head count g does not divide, a
length past the grouped kernels' fit) `mha_seq_major` swaps the heads to
the front for `mha_forward` and back. paddle's [batch, seq, heads,
head_dim] (`flash_attention`) is the seq-major layout once flattened. Two
widths: q and k share `d` (written `d_qk` where both appear), v, the output
and its gradient have `d_v`, read from v's shape; `d_v = d_qk` is ordinary
multi-head attention, latent attention has 192 and 128, or 256 and 256.
Every product accumulates in fp32 on the MXU (preferred_element_type) from
operands of the IO dtype, which is whatever the caller passes (bf16 on
TPU): p and ds are rounded to it once, the softmax math between the
products is fp32. On a TPU the kernels are Mosaic-compiled; anywhere else
they run in interpret mode (`_core.device.pallas_interpret`), so the CPU
test mesh exercises identical code.

K/V (forward) and Q/dO/dQ (backward) stay whole-sequence resident in VMEM,
so the sequence length is capped by the 16 MiB scoped-VMEM limit:
`vmem_footprint` computes each kernel's need, `_check_vmem` raises
`FlashSequenceLimitError` instead of letting Mosaic fail with
RESOURCE_EXHAUSTED, and the backward's key block is halved where the whole
one does not fit (`_bwd_block_k`). The documented caps (`max_seq`; bfloat16
with the backward: 7,168 at head_dim 64, 6,144 at 128, 3,584 at 192 / 128,
2,560 at 256 / 256; README "Flash attention sequence limit") are the
head-major entry's; a group's blocks are g heads wide, so the seq-major
kernels end earlier (5,632 at 64, 2,048 at 192 / 128) and the head-major
ones take over: 32 heads of 192 / 128 at 2,048 tokens, the latent layer of
the hybrid linear-attention cell (PR 37), run the seq-major pairs exactly
at their cap. Several query heads on a K/V head add the float32 dK, dV
sums to the backward (4,096 at 128 with 256-key blocks, whatever their
number); a window changes no cap, since K/V stay resident all the same.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as _P

from ..._core.device import pallas_interpret
from ...observability.programs import mosaic_site

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


# A product that contracts this much or more keeps its partial sums in a
# float32 tile of its own (what the v5e compiler's figures say at 256 / 256).
DEEP_PRODUCT = 256


_f32_block_bytes = functools.partial(_vmem_block_bytes, dtype=jnp.float32)


def _fwd_bytes(bq, bk, sk, d, dv, dtype, group):
    blk, f32 = functools.partial(_vmem_block_bytes, dtype=dtype), \
        _f32_block_bytes
    # q, o; k, v; the lse rows
    need = (2 * (blk(bq, group * d) + blk(bq, group * dv)
                 + blk(sk, group * d) + blk(sk, group * dv)
                 + group * f32(1, bq))
            + f32(bk, bq) + blk(bk, bq) + 2 * f32(dv, bq))
    if group > 1:
        # a head's lanes of the loaded q, k and v; the heads' outputs side
        # by side before they are turned
        need += (blk(bq, d) + blk(bk, d) + blk(bk, dv)
                 + 2 * f32(group * dv, bq))
    return need


def _bwd_bytes(sq, bq, bkb, d, dv, dtype, group, sk=0, rep=1):
    blk, f32 = functools.partial(_vmem_block_bytes, dtype=dtype), \
        _f32_block_bytes
    # q, dq, do; k, dk, v, dv; the lse and delta rows
    need = (2 * (2 * blk(sq, group * d) + blk(sq, group * dv)
                 + 2 * blk(bkb, group * d) + 2 * blk(bkb, group * dv)
                 + 2 * group * (sq // bq) * f32(1, bq))
            + f32(group * d, sq)
            + (3 + (d >= DEEP_PRODUCT) + (dv >= DEEP_PRODUCT))
            * f32(bkb, bq) + f32(bkb, d) + f32(bkb, dv))
    if group > 1:
        # a head's lanes of the loaded q, dO, k and v; the heads' dK and dV
        # side by side before they are written
        need += (blk(bq, d) + blk(bq, dv) + blk(bkb, d) + blk(bkb, dv)
                 + f32(bkb, group * d) + f32(bkb, group * dv))
    if rep > 1:
        # dK and dV of one K/V head, summed over its `rep` query heads
        need += f32(sk, group * d) + f32(sk, group * dv)
    return need


def _bwd_block_k(sq: int, sk: int, d: int, dv: int, dtype,
                 group: int = 1, rep: int = 1) -> int:
    """The backward's key block: the forward's, or half of it where that
    does not fit and the half does (the smaller step first, ROADMAP A1 c).
    A key block carries k, dk [bk, g d], v, dv [bk, g d_v] and every
    [bk, bq] tile of the body: at 512 keys and 256 / 256 the kernel takes
    16.50 MiB at 2,048 and fits with 256 keys to 2,560; two heads of
    192 / 128 a grid step need 256 keys at 2,048 as well, and so do `rep`
    > 1 query heads on one K/V head at 4,096 x 128 (their dK, dV sums are
    4 MiB). The query block is the forward's always: the lse rows are
    written in it."""
    bq, bk = _block_sizes(sq, sk, d)
    half = bk // 2
    if (bk == MAX_BLOCK
            and _bwd_bytes(sq, bq, bk, d, dv, dtype, group, sk, rep)
            >= SCOPED_VMEM_BYTES
            > _bwd_bytes(sq, bq, half, d, dv, dtype, group, sk, rep)):
        return half
    return bk


def vmem_footprint(sq: int, sk: int, d: int, dtype, d_v: int = None,
                   group: int = 1, rep: int = 1) -> dict:
    """Scoped-VMEM bytes each kernel needs with its operands in HBM; `d` is
    the width of q and k, `d_v` that of v and the output (`d` if None),
    `group` the heads a grid step handles (1: the head-major entry, whose
    blocks are one head wide; g: the seq-major entry's, g d and g d_v
    wide, `head_group`). The pipeline double-buffers every in/out block of
    the BlockSpecs in `_fwd` / `_bwd`. To that the forward adds what its
    body keeps of one tile: the float32 scores, p rounded to the inputs'
    dtype and the transposed accumulator before and after a sub-block; the
    backward its fp32 dQ accumulator [g d, sq], three fp32 [bk, bq] tiles,
    one more for each of the two score-shaped products (k q^T over d,
    v dO^T over d_v) that contracts 256 or more, and the dK, dV sums; its
    key block is `_bwd_block_k`'s. A group of several heads adds one
    head's lanes of each loaded block and the group's results side by
    side; `rep` > 1 query heads on each K/V head (grouped-query attention)
    add the float32 dK and dV of one K/V head's whole sequence, which the
    backward sums over those heads before it writes them. Both are upper
    estimates (the compiler's own choices move its
    figure by a MiB either way), under which the v5e ahead-of-time
    compiler accepted every length up to the cap in steps of 512 (bf16
    and fp32, d 64-256). Without the deep products' tiles it read 14.25
    MiB at 160 x 2048 x 256 / 256 with 512-key blocks, where the compiler
    took 16.50."""
    dv = d if d_v is None else d_v
    bq, bk = _block_sizes(sq, sk, d)
    bkb = _bwd_block_k(sq, sk, d, dv, dtype, group, rep)
    return {"fwd": _fwd_bytes(bq, bk, sk, d, dv, dtype, group),
            "bwd": _bwd_bytes(sq, bq, bkb, d, dv, dtype, group, sk, rep)}


def _fits(sq: int, sk: int, d: int, dtype, backward: bool, d_v: int = None,
          group: int = 1, rep: int = 1):
    """Name of the first kernel that does not fit, or None."""
    need = vmem_footprint(sq, sk, d, dtype, d_v, group, rep)
    for kernel in ("fwd", "bwd") if backward else ("fwd",):
        if need[kernel] >= SCOPED_VMEM_BYTES:
            return kernel, need[kernel]
    return None


def max_seq(d: int, dtype, backward: bool, d_v: int = None,
            group: int = 1, rep: int = 1) -> int:
    """Longest self-attention sequence (a multiple of 512) whose kernels
    fit at q/k width `d` and v width `d_v` (`d` if None): forward only, or
    forward and backward. `group` 1 is the head-major entry, which every
    shape can take: its caps are the documented ones. `rep` is the query
    heads on each K/V head; a window changes no cap (K/V stay resident)."""
    s = 0
    while _fits(s + 512, s + 512, d, dtype, backward, d_v, group,
                rep) is None:
        s += 512
    return s


def head_group(heads: int, d_qk: int, d_v: int, sq: int, sk: int, dtype,
               kv_heads: int = None):
    """Heads a grid step of the seq-major entry handles on q
    [B, S, heads d_qk], k [B, S, kv_heads d_qk] and v [B, S, kv_heads d_v],
    or None where the shapes take the head-major entry: the smallest g for
    which g d_qk and g d_v are multiples of 128 lanes (1 at 128 or
    256 / 256, 2 at 64 and at 192 / 128), if it divides `heads` and the
    grouped kernels, forward and backward, fit in scoped VMEM. Their blocks
    are g heads wide, so they end earlier than the head-major ones (5,632
    against 7,168 at head_dim 64 in bfloat16). With fewer K/V heads than
    query heads only a group of one is taken: a K/V head's columns are then
    a block of whole lanes that the index maps can name."""
    group = next((g for g in (1, 2, 4, 8)
                  if g * d_qk % 128 == 0 and g * d_v % 128 == 0), None)
    if group is None or heads % group:
        return None
    rep = heads // (kv_heads or heads)
    if rep > 1 and group > 1:
        return None
    if _fits(sq, sk, d_qk, dtype, True, d_v, group, rep) is not None:
        return None
    return group


def _check_vmem(q, k, v, backward: bool, heads: int = 1, group: int = 1,
                rep: int = 1):
    """Raise the named limit before Mosaic raises RESOURCE_EXHAUSTED, on
    the operands as `_mha` takes them: head-major [BH, S, D], or
    [B, S, heads D] in groups of `group` (which `head_group` only gives
    where they fit). The compiler sometimes fits more by keeping a small
    operand in VMEM itself (it depends on batch*heads); that is not a
    length to rely on."""
    if pallas_interpret():
        return
    sq, sk = q.shape[1], k.shape[1]
    d, dv = q.shape[2] // heads, v.shape[2] // _kv_heads(heads, rep)
    over = _fits(sq, sk, d, q.dtype, backward, dv, group, rep)
    if over:
        kernel, need = over
        raise FlashSequenceLimitError(
            f"flash attention {kernel} kernel at seq_q {sq}, seq_k {sk}, "
            f"head_dim {d} (q, k) and {dv} (v), {jnp.dtype(q.dtype).name}, "
            f"{group} head{'s' * (group > 1)} a grid step, {rep} query "
            f"head{'s' * (rep > 1)} a K/V head, "
            f"needs {need / 2**20:.2f} MiB of scoped VMEM; the limit is "
            f"{SCOPED_VMEM_BYTES >> 20} MiB because K/V (Q, dO and dQ in the "
            "backward) stay whole-sequence resident. Longest self-attention "
            f"sequence at these widths and dtype, one head a grid step: "
            f"{max_seq(d, q.dtype, False, dv, rep=rep)} forward only, "
            f"{max_seq(d, q.dtype, True, dv, rep=rep)} with the backward")


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


# ---------------------------------------------------------------- forward

_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b
_TN = (((0,), (0,)), ((), ()))      # a^T . b


def _visible_key_blocks(qi, block_q, block_k, nkb, causal, q_offset,
                        window=None):
    """`(first, lower, full, seen)` for query block `qi` (an index, traced
    or not, or an array of them). Key blocks [lower, full) are visible to
    every query of the block; the diagonal `k_pos <= q_pos + q_offset`
    crosses [full, seen), and [seen, nkb) lie above it. With a `window`
    (a query sees only the keys with `q_pos + q_offset - k_pos < window`)
    a second, lower diagonal crosses [first, lower) and [0, first) lie
    below it; without one both are 0. The forward kernel's loops run over
    exactly these ranges. A window shorter than `block_q + block_k - 2`
    can put both diagonals through one block: such a block is in
    [first, lower), and `_crossed_twice` says so from the sizes alone."""
    if not causal:
        return 0, 0, nkb, nkb
    limit = qi * block_q + q_offset     # last key the block's first query sees
    full = jnp.clip((limit + 1) // block_k, 0, nkb)
    seen = jnp.clip((limit + block_q - 1) // block_k + 1, full, nkb)
    if window is None:
        return 0, 0, full, seen
    edge = limit - window + 1       # first key the block's first query sees
    first = jnp.clip(edge // block_k, 0, seen)
    # the first block all of whose keys the block's LAST query still sees
    lower = jnp.clip((edge + block_q - 1 + block_k - 1) // block_k, first,
                     seen)
    return first, lower, jnp.maximum(full, lower), seen


def _crossed_twice(block_q: int, block_k: int, window) -> bool:
    """Whether a score tile can hold both the causal diagonal and the
    window's: then the tiles on the window's edge take both compares."""
    return window is not None and window < block_q + block_k - 2


def tile_counts(sq: int, sk: int, block_q: int, block_k: int, causal: bool,
                q_offset: int, window: int = None):
    """`(masked, full, skipped)` score tiles of one batch*head in the
    forward: tiles that run the body with a diagonal's compare (the causal
    one, the window's, or both), tiles that run the one without, and tiles
    that are not visited."""
    nqb, nkb = sq // block_q, sk // block_k
    first, lower, full, seen = (
        int(jnp.sum(jnp.broadcast_to(n, (nqb,))))
        for n in _visible_key_blocks(jnp.arange(nqb), block_q, block_k, nkb,
                                     causal, q_offset, window))
    return (lower - first) + (seen - full), full - lower, \
        nqb * nkb - (seen - first)


# Keys of a score tile taken into the running softmax at a time. The whole
# tile's k.qT is issued first; walked in sub-blocks of this many keys, one
# sub-block's exponentials run while the MXU streams the rest of the tile and
# the previous sub-block's vT.pT (PR 29, v5e: 128 beat 64, 256 and the whole
# tile at every shape; it is one MXU pass of the contraction).
SUB_KEYS = 128


def _head(x, h: int, width: int, group: int):
    """Head `h`'s lanes of a loaded [rows, group * width] value. The value
    is sliced, not the ref: Mosaic takes no ref view at a lane offset that
    is not a multiple of 128. A group of one is the block itself."""
    return x if group == 1 else x[:, h * width:(h + 1) * width]


def _visible(k_minus_q, diagonal, window, mask: str):
    """Which (key, query) pairs of a tile are visible, from their
    positions' difference counted from the tile's corner: at or under the
    causal diagonal (`k_pos <= q_pos + q_offset`), above the window's
    (`q_pos + q_offset - k_pos < window`), or both."""
    if mask == "causal":
        return k_minus_q <= diagonal
    if mask == "edge":
        return k_minus_q > diagonal - window
    return (k_minus_q <= diagonal) & (k_minus_q > diagonal - window)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, group, causal, scale,
                block_k, q_offset, window):
    """One (batch, head group, query block) grid step over the key blocks
    this query block sees, one head of the group after another. Every
    score tile is key-major ([bk, bq]), so the running maximum and sum are
    sublane reductions into lane-dense [1, bq] rows and the accumulator is
    the transposed output [d_v, bq]; the group's accumulators are turned
    once, side by side, into one [bq, g d_v] store. Only the tiles a
    diagonal crosses pay for a mask. (On the window's edge a query may see
    none of a sub-block's keys while its running maximum is still NEG_INF:
    the ones that then stand in its sums are multiplied by exp(NEG_INF - m)
    = 0 exactly when its first visible key comes, which its own position
    always is.)"""
    qi = pl.program_id(2)
    bq = q_ref.shape[1]
    d = q_ref.shape[2] // group
    dv = v_ref.shape[2] // group
    nkb = k_ref.shape[1] // block_k
    # a ragged length is one key block of its own size, taken whole
    sub = SUB_KEYS if block_k % SUB_KEYS == 0 else block_k
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)
    first, lower, full, seen = _visible_key_blocks(
        qi, bq, block_k, nkb, causal, q_offset, window)

    def tile(h, mask, j, carry):
        m, l, acct = carry                  # [1, bq], [1, bq], [d_v, bq]
        first = pl.multiple_of(j * block_k, block_k)
        # q is read here, not above the loops: held across them it is
        # spilled to VMEM and read back in every tile
        st = dot(_head(k_ref[0, pl.ds(first, block_k), :], h, d, group),
                 _head(q_ref[0], h, d, group), _NT) * scale
        if mask:
            k_minus_q = (jax.lax.broadcasted_iota(jnp.int32, (sub, bq), 0)
                         - jax.lax.broadcasted_iota(jnp.int32, (sub, bq), 1))
            # k_pos <= q_pos + q_offset, positions counted from the tile's corner
            diagonal = qi * bq + q_offset - j * block_k
        for r in range(0, block_k, sub):
            s = st[r:r + sub]                                   # [sub, bq]
            if mask:
                s = jnp.where(_visible(k_minus_q, diagonal - r, window, mask),
                              s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            pt = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(pt, axis=0, keepdims=True)
            v = _head(v_ref[0, pl.ds(first + r, sub), :], h, dv, group)
            acct = alpha * acct + dot(v, pt.astype(v.dtype), _TN)
            m = m_new
        return m, l, acct

    outs = []
    for h in range(group):
        carry = (jnp.full((1, bq), NEG_INF, jnp.float32),
                 jnp.zeros((1, bq), jnp.float32),
                 jnp.zeros((dv, bq), jnp.float32))
        if window is not None:
            carry = jax.lax.fori_loop(
                first, lower, functools.partial(
                    tile, h, "both" if _crossed_twice(bq, block_k, window)
                    else "edge"), carry)
        carry = jax.lax.fori_loop(lower, full,
                                  functools.partial(tile, h, None), carry)
        if causal:
            carry = jax.lax.fori_loop(
                full, seen, functools.partial(tile, h, "causal"), carry)
        m, l, acct = carry
        l_safe = jnp.where(l == 0.0, 1.0, l)        # a block that saw no key
        outs.append(acct / l_safe)
        lse_ref[0, h, 0] = m + jnp.log(l_safe)
    out = outs[0] if group == 1 else jnp.concatenate(outs, axis=0)
    o_ref[0] = out.T.astype(o_ref.dtype)


def _kv_heads(heads: int, rep: int) -> int:
    """K/V heads side by side in a row of k and v: heads / rep, and one
    where the heads are on the leading axis (`heads` 1, head-major)."""
    return max(heads // rep, 1)


def _kv_index(heads: int, rep: int):
    """(batch index, head-group index) of a grid step -> the same of the
    K/V block it reads: its own where every query head has a K/V head; with
    `rep` query heads on each, head `g // rep` of q [b, s, heads d]'s
    batch, or, head-major (`heads` 1: q [b heads, s, d] and k, v
    [b kv_heads, s, d]), row `b // rep`."""
    if rep == 1:
        return lambda b, g: (b, g)
    if heads == 1:
        return lambda b, g: (b // rep, g)
    return lambda b, g: (b, g // rep)


def _fwd(q, k, v, heads, group, causal, scale, block_q, block_k, q_offset,
         rep=1, window=None):
    """out [b, sq, heads d_v] and lse as the backward reads it: one
    lane-dense float32 row per head and query block,
    [b, heads, nqb, 1, block_q]. A grid step takes the `group` heads whose
    columns make one block of q [b, s, heads d], and the K/V head they
    attend through from k [b, s, kv_heads d] and v [b, s, kv_heads d_v]
    (`_kv_index`: `rep` query heads a K/V head; consecutive grid steps on
    one K/V head load it once); the head-major entry's [bh, s, d] is
    `heads` 1 in a group of one."""
    b, sq, hd = q.shape
    sk = v.shape[1]
    kv_heads = _kv_heads(heads, rep)
    gd, gdv = hd // heads * group, v.shape[2] // kv_heads * group
    nqb = sq // block_q
    kv = _kv_index(heads, rep)
    with mosaic_site(_fwd_kernel, q, k, v), _no_x64():
        return pl.pallas_call(
            functools.partial(_fwd_kernel, group=group, causal=causal,
                              scale=scale, block_k=block_k,
                              q_offset=q_offset, window=window),
            grid=(b, heads // group, nqb),
            in_specs=[
                pl.BlockSpec((1, block_q, gd), lambda b, g, i: (b, i, g)),
                pl.BlockSpec((1, sk, gd),
                             lambda b, g, i: (kv(b, g)[0], 0, kv(b, g)[1])),
                pl.BlockSpec((1, sk, gdv),
                             lambda b, g, i: (kv(b, g)[0], 0, kv(b, g)[1])),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, gdv), lambda b, g, i: (b, i, g)),
                pl.BlockSpec((1, group, 1, 1, block_q),
                             lambda b, g, i: (b, g, i, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, sq, heads * gdv // group), q.dtype),
                jax.ShapeDtypeStruct((b, heads, nqb, 1, block_q),
                                     jnp.float32),
            ],
            interpret=pallas_interpret(),
        )(q, k, v)


# ---------------------------------------------------------------- backward

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dqt_acc, *kv_acc, group, causal,
                scale, block_q, q_offset, window, rep, rep_axis):
    """One (batch, head group, key block) grid step, one head of the group
    after another: each score tile of this key block is formed once,
    key-major ([bk, bq], so lse and delta are lane-dense [1, bq] rows and
    dV, dK plain products), and feeds all five products on operands of the
    inputs' dtype. dQ is summed over the key axis in VMEM, transposed
    ([g d, sq]: the one transposed product then turns the narrow k, not the
    tile) and written at the last key block; dK and dV leave as the
    group's [bk, g d] and [bk, g d_v] blocks. With `rep` query heads on
    each K/V head the grid's `rep_axis` walks them one after another and
    `kv_acc` (float32 [sk, d], [sk, d_v]) holds the K/V head's dK and dV
    summed over the query heads so far: every step writes the sum as it
    stands to the key block's dK, dV, and the last query head's write is
    the one that stays."""
    kj = pl.program_id(2)
    bk = k_ref.shape[1]
    d = k_ref.shape[2] // group
    dv_width = v_ref.shape[2] // group
    nqb = q_ref.shape[1] // block_q
    dot = functools.partial(jax.lax.dot_general,
                            preferred_element_type=jnp.float32)

    @pl.when(kj == 0)
    def _():
        dqt_acc[...] = jnp.zeros_like(dqt_acc)

    k_minus_q = (jax.lax.broadcasted_iota(jnp.int32, (bk, block_q), 0)
                 - jax.lax.broadcasted_iota(jnp.int32, (bk, block_q), 1))

    def tile(h, k, v, mask, i, carry):
        dk, dv = carry
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q = _head(q_ref[0, rows, :], h, d, group)           # [bq, d_qk]
        do = _head(do_ref[0, rows, :], h, dv_width, group)  # [bq, d_v]
        # scaled on the float32 tile, as the forward does: lse is of that s
        st = dot(k, q, _NT) * scale                 # [bk, bq]
        pt = jnp.exp(st - lse_ref[0, h, i])
        if mask:
            # k_pos <= q_pos + q_offset, positions counted from the tile's corner
            pt = jnp.where(
                _visible(k_minus_q, i * block_q + q_offset - kj * bk, window,
                         mask), pt, 0.0)
        dst = (pt * (dot(v, do, _NT) - delta_ref[0, h, i])).astype(q.dtype)
        dv = dv + dot(pt.astype(do.dtype), do, _NN)             # [bk, d_v]
        dk = dk + dot(dst, q, _NN)                              # [bk, d_qk]
        dqt_acc[h * d:(h + 1) * d, rows] += dot(k, dst, _TN)    # [d, bq]
        return dk, dv

    if causal:
        # query blocks before `first` see none of this key block; from
        # `full` on they see all of it and the compare is left out
        first = jnp.clip((kj * bk - q_offset) // block_q, 0, nqb)
        full = jnp.clip(
            ((kj + 1) * bk - 1 - q_offset + block_q - 1) // block_q,
            first, nqb)
    else:
        full = 0
    end = nqb
    if window is not None:
        # the window's diagonal: query blocks from `end` on are past this
        # key block's last key's window, from `inside` on it crosses them
        reach = kj * bk + window - q_offset
        end = jnp.clip((reach + bk - 2) // block_q + 1, first, nqb)
        full = jnp.clip(full, first, end)
        inside = jnp.clip((reach - block_q) // block_q + 1, full, end)
    dks, dvs = [], []
    for h in range(group):
        k = _head(k_ref[0], h, d, group)                # [bk, d_qk]
        v = _head(v_ref[0], h, dv_width, group)         # [bk, d_v]
        carry = (jnp.zeros((bk, d), jnp.float32),
                 jnp.zeros((bk, dv_width), jnp.float32))
        if causal:
            carry = jax.lax.fori_loop(
                first, full, functools.partial(
                    tile, h, k, v,
                    "both" if _crossed_twice(block_q, bk, window)
                    else "causal"), carry)
        if window is None:
            dk, dv = jax.lax.fori_loop(
                full, nqb, functools.partial(tile, h, k, v, None), carry)
        else:
            carry = jax.lax.fori_loop(
                full, inside, functools.partial(tile, h, k, v, None), carry)
            dk, dv = jax.lax.fori_loop(
                inside, end, functools.partial(tile, h, k, v, "edge"), carry)
        # ds's scale, applied once the tile is contracted away: on [., d]
        dk = dk * scale
        if rep > 1:         # a group of one: the K/V head's sums so far
            dk_acc, dv_acc = kv_acc
            keys = pl.ds(pl.multiple_of(kj * bk, bk), bk)
            earlier = pl.program_id(rep_axis) % rep > 0
            dk = dk + jnp.where(earlier, dk_acc[keys, :], 0.0)
            dv = dv + jnp.where(earlier, dv_acc[keys, :], 0.0)
            dk_acc[keys, :] = dk
            dv_acc[keys, :] = dv
        dks.append(dk.astype(dk_ref.dtype))
        dvs.append(dv.astype(dv_ref.dtype))
    dk_ref[0] = dks[0] if group == 1 else jnp.concatenate(dks, axis=1)
    dv_ref[0] = dvs[0] if group == 1 else jnp.concatenate(dvs, axis=1)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _():
        def write(i, _):
            rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
            dq_ref[0, rows, :] = (dqt_acc[:, rows].T * scale).astype(
                dq_ref.dtype)
        jax.lax.fori_loop(0, nqb, write, None)


def _head_sums(x, heads: int):
    """x [b, s, heads w] float32 -> each head's sum over its w columns,
    [b, heads, s]. Several heads share a row's lanes, and a reduction over
    part of them would have XLA lay the whole array out again, one head a
    row (w 64 padded to 128 lanes); a product with the heads' 0 / 1
    membership columns, at full float32 precision, reads it where it is."""
    if heads == 1:
        return jnp.sum(x, axis=-1)[:, None]
    member = jnp.repeat(jnp.eye(heads, dtype=x.dtype), x.shape[2] // heads,
                        axis=0)                                 # [h w, h]
    return jnp.einsum("bsk,kh->bhs", x, member,
                      precision=jax.lax.Precision.HIGHEST)


def _bwd(q, k, v, out, lse, do, heads, group, causal, scale, block_q,
         block_k, q_offset, rep=1, window=None):
    b, sq, hd = q.shape
    sk = v.shape[1]
    kv_heads = _kv_heads(heads, rep)
    d, dv = hd // heads, v.shape[2] // kv_heads
    nqb = sq // block_q
    delta = _head_sums(do.astype(jnp.float32) * out.astype(jnp.float32),
                       heads).reshape(b, heads, nqb, 1, block_q)
    kv = _kv_index(heads, rep)
    # Q and dO stay whole-sequence resident; lse and delta come as one
    # lane-dense row per head and query block (a [sq, 1] block pads to 128
    # lanes)
    full_q = pl.BlockSpec((1, sq, group * d), lambda b, g, j: (b, 0, g))
    full_do = pl.BlockSpec((1, sq, group * dv), lambda b, g, j: (b, 0, g))
    full_row = pl.BlockSpec((1, group, nqb, 1, block_q),
                            lambda b, g, j: (b, g, 0, 0, 0))
    kspec = pl.BlockSpec((1, block_k, group * d),
                         lambda b, g, j: (kv(b, g)[0], j, kv(b, g)[1]))
    vspec = pl.BlockSpec((1, block_k, group * dv),
                         lambda b, g, j: (kv(b, g)[0], j, kv(b, g)[1]))
    scratch = [pltpu.VMEM((group * d, sq), jnp.float32)]
    # dQ's block is revisited along the key axis
    semantics = ("parallel", "parallel", "arbitrary")
    rep_axis = 0 if heads == 1 else 1
    if rep > 1:
        # and dK's, dV's along the axis that walks a K/V head's query heads
        scratch += [pltpu.VMEM((sk, group * d), jnp.float32),
                    pltpu.VMEM((sk, group * dv), jnp.float32)]
        semantics = tuple("arbitrary" if axis >= rep_axis else "parallel"
                          for axis in range(3))
    with mosaic_site(_bwd_kernel, q, k, v, do), _no_x64():
        return pl.pallas_call(
            functools.partial(_bwd_kernel, group=group, causal=causal,
                              scale=scale, block_q=block_q,
                              q_offset=q_offset, window=window, rep=rep,
                              rep_axis=rep_axis),
            grid=(b, heads // group, sk // block_k),
            in_specs=[full_q, kspec, vspec, full_do, full_row, full_row],
            out_specs=[full_q, kspec, vspec],
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       jax.ShapeDtypeStruct(k.shape, k.dtype),
                       jax.ShapeDtypeStruct(v.shape, v.dtype)],
            scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics),
            interpret=pallas_interpret(),
        )(q, k, v, do, lse, delta)


# ---------------------------------------------------------------- public

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _mha(q, k, v, heads, group, causal, scale, rep, window):
    """q [b, s, heads d], k [b, s, kv_heads d], v [b, s, kv_heads d_v],
    `group` heads a grid step, `rep` = heads / kv_heads query heads a K/V
    head, `window` keys a query sees at most (None: all before it); the
    head-major q [b heads, s, d], k, v [b kv_heads, s, .] is `heads` 1,
    `group` 1."""
    _check_vmem(q, k, v, False, heads, group, rep)
    return _fwd_res(q, k, v, heads, group, causal, scale, rep, window)[0]


def _fwd_res(q, k, v, heads, group, causal, scale, rep=1, window=None):
    sq, sk = q.shape[1], k.shape[1]
    bq, bk = _block_sizes(sq, sk, q.shape[2] // heads)
    out, lse = _fwd(q, k, v, heads, group, causal, scale, bq, bk,
                    q_offset=sk - sq, rep=rep, window=window)
    return out, (q, k, v, out, lse)


def _mha_fwd(q, k, v, heads, group, causal, scale, rep, window):
    _check_vmem(q, k, v, True, heads, group, rep)
    return _fwd_res(q, k, v, heads, group, causal, scale, rep, window)


def _mha_bwd(heads, group, causal, scale, rep, window, res, do):
    q, k, v, out, lse = res
    sq, sk = q.shape[1], k.shape[1]
    d, dv = q.shape[2] // heads, v.shape[2] // _kv_heads(heads, rep)
    bq, _ = _block_sizes(sq, sk, d)
    return _bwd(q, k, v, out, lse, do, heads, group, causal, scale, bq,
                _bwd_block_k(sq, sk, d, dv, q.dtype, group, rep),
                q_offset=sk - sq, rep=rep, window=window)


_mha.defvjp(_mha_fwd, _mha_bwd)


def _window(window, causal: bool):
    """A window is the causal mask's second diagonal: it needs the first."""
    if window is not None and not causal:
        raise ValueError("flash attention: a window needs causal=True")
    return None if window is None else int(window)


def mha_forward(q, k, v, causal=False, scale=None, window=None):
    """Differentiable blocked attention on head-major arrays, one head a
    grid step: the entry every shape under the caps can take.

    Accepts [B, H, S, D] or [BH, S, D]; returns the same rank it was given.
    v may have another last dimension than q and k (latent attention:
    192 and 128); the output has v's. The default scale is q's. k and v
    may have fewer heads than q, [B, H_kv, S, .] with H_kv dividing H
    (grouped-query attention): query head h reads K/V head h // (H / H_kv)
    through the index maps, and dK, dV come back with H_kv heads. `window`
    (with `causal`): a query sees the `window` keys up to its own.
    """
    squeeze = q.ndim == 4
    if squeeze:
        b, h, sq, d = q.shape
        q = q.reshape(b * h, sq, d)
        k = k.reshape(-1, k.shape[2], d)
        v = v.reshape(-1, v.shape[2], v.shape[3])
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    rep = q.shape[0] // k.shape[0]
    if k.shape[0] * rep != q.shape[0]:
        raise ValueError(f"flash attention: {k.shape[0]} K/V heads (times "
                         f"batch) do not divide {q.shape[0]} query heads")
    out = _mha(q, k, v, 1, 1, bool(causal), float(scale), rep,
               _window(window, causal))
    if squeeze:
        out = out.reshape(b, h, sq, out.shape[-1])
    return out


def mha_seq_major(q, k, v, heads, causal=False, scale=None, kv_heads=None,
                  window=None):
    """Differentiable blocked attention on q [B, S, heads d], k
    [B, S, kv_heads d] and v [B, S, kv_heads d_v] as the projections write
    them -> [B, S, heads d_v], and dQ, dK, dV in the same layouts.
    `kv_heads` (None: `heads`) may be fewer: query head h attends through
    K/V head h // (heads / kv_heads), which the kernels' index maps name,
    so no array of `heads` K/V heads exists in either direction, and dK,
    dV are summed over a K/V head's query heads inside the backward
    kernel. `window` (with `causal`): a query sees the `window` keys up to
    its own, and the tiles outside both diagonals are not visited. Where
    `head_group` gives a group the kernels index those arrays directly, a
    group's columns a grid step, and nothing is copied around them;
    elsewhere (an odd head count at head_dim 64, fewer K/V heads at a head
    width under 128, a length past the grouped kernels' fit) the heads are
    swapped to the front for `mha_forward` and the output swapped back."""
    b, sq, hd = q.shape
    kv_heads = kv_heads or heads
    if heads % kv_heads:
        raise ValueError(f"flash attention: {kv_heads} K/V heads do not "
                         f"divide {heads} query heads")
    sk, d, dv = k.shape[1], hd // heads, v.shape[2] // kv_heads
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    window = _window(window, causal)
    group = head_group(heads, d, dv, sq, sk, q.dtype, kv_heads)
    if group is not None:
        return _mha(q, k, v, heads, group, bool(causal), float(scale),
                    heads // kv_heads, window)
    q = jnp.swapaxes(q.reshape(b, sq, heads, d), 1, 2)      # [B, H, S, D]
    k, v = (jnp.swapaxes(a.reshape(b, sk, kv_heads, -1), 1, 2)
            for a in (k, v))
    out = mha_forward(q, k, v, causal=causal, scale=scale, window=window)
    return jnp.swapaxes(out, 1, 2).reshape(b, sq, heads * dv)


def _fa_kernel_body(q, k, v, causal, scale):
    # paddle layout [B, S, H, D]: the seq-major entry's once flattened
    b, sq, h, _ = q.shape
    out = mha_seq_major(*(a.reshape(a.shape[0], a.shape[1], -1)
                          for a in (q, k, v)), h, causal, scale,
                        kv_heads=k.shape[2])
    return out.reshape(b, sq, h, v.shape[-1])


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

def mha_sharded(q, k, v, mesh, causal=False, scale=None, heads=None,
                kv_heads=None, window=None):
    """Flash attention on mesh-sharded arrays under jit: [B, S, heads D]
    as the projections write them (`mha_seq_major` on every shard; k and v
    may have `kv_heads` fewer heads, which `mp` must divide too), or,
    with `heads` None, head-major [B, H, S, D] (`mha_forward`).

    Mosaic kernels cannot be partitioned automatically, so the call is
    wrapped in a ``shard_map`` that is manual over EVERY mesh axis GSPMD
    still owns here (size 1 or not): batch splits over 'dp', heads over
    'mp' (contiguous shares of the heads D columns are heads), seq and
    head_dim are gathered at the boundary. Under plain jit that is the
    whole mesh; inside the compiled-pp body ('pp' already manual,
    pipeline_compiled.py) it is the remaining axes of the context mesh.
    The TPU analog of the reference wiring flash-attn into its SPMD rules
    (phi/infermeta/spmd_rules)."""
    ctx_mesh = jax.sharding.get_abstract_mesh()
    nested = bool(ctx_mesh.manual_axes)
    axes = set(mesh.axis_names) - set(ctx_mesh.manual_axes)
    n_heads = q.shape[1] if heads is None else heads
    n_kv = k.shape[1] if heads is None else (kv_heads or heads)
    for axis, dim, what in (("dp", q.shape[0], "batch"),
                            ("mp", n_heads, "heads"),
                            ("mp", n_kv, "K/V heads")):
        if axis in axes and dim % mesh.shape[axis]:
            raise ValueError(
                f"flash attention: {what} {dim} not divisible by mesh "
                f"axis {axis!r} of size {mesh.shape[axis]}")
    dp, mp = ("dp" if "dp" in axes else None), ("mp" if "mp" in axes else None)
    if heads is None:
        spec = _P(dp, mp, None, None)
        body = functools.partial(mha_forward, causal=causal, scale=scale,
                                 window=window)
    else:
        spec = _P(dp, None, mp)
        shards = mesh.shape[mp] if mp else 1
        body = functools.partial(
            mha_seq_major, heads=heads // shards, causal=causal, scale=scale,
            kv_heads=n_kv // shards, window=window)
    return jax.shard_map(
        body, mesh=ctx_mesh if nested else mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=axes, check_vma=False)(q, k, v)
