"""Mixing of several residual streams (manifold-constrained
hyper-connections, arXiv:2512.24880) as four Pallas TPU kernels under two
`jax.custom_vjp`s: every half of the mixing, in each direction, reads the
streams once, in their own dtype, and no float32 copy of them reaches HBM.

A sub-layer on the streams x [n, tokens, h] is

    u = RMSNorm(vec x) * norm_g;  proj = u @ phi           (2n + n*n columns)
    H_pre = sigmoid(a0 proj + b_pre);  H_post = 2 sigmoid(a1 proj + b_post)
    H_res = Sinkhorn(exp(clip(a2 proj + b_res)))
    h_in = sum_i H_pre[i] x[i];  y = F(h_in)                      (`read_in`)
    x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y               (`write_back`)

One grid step holds a tile of tokens, all n streams of it, in VMEM and goes
over it in chunks of lanes. What is per token and small (the 2n + n*n
projections, the coefficients, Sinkhorn's 20 iterations and, in the backward,
`jax.vjp` of the same function) is computed with the tokens on the lanes,
[k, tile]; what multiplies a stream wants them on the sublanes, [tile, 1],
and a transpose through 128 padded columns turns one into the other.

Precision. The streams are exact in float32 whatever their dtype; every sum
over h and every coefficient is float32; only h_in, x' and the cotangents of
the streams and of y are rounded to the streams' dtype. The norm's scale
`rinv` is a scalar a token and comes out of the projection, so that
`rinv * (x @ (norm_g * phi))` needs no float32 copy of x. With bfloat16
streams the three products with the [n h, 2n + n*n] matrix are one pass of
the MXU each: x is one exact bfloat16 term, the float32 matrix is split
into three, and the three terms ride side by side in one operand (3 * 32
rows of the 128 the MXU has), summed in float32, smallest first; the
stream's cotangent, which is rounded to bfloat16 anyway, takes the three
largest of the nine term products. Streams of another dtype take float32
products at HIGHEST.

`read_in` also returns x itself: `write_back` takes that copy, so x has one
consumer and its two cotangents never meet in an XLA add; the write-back's
part arrives as the cotangent of the copy and the read-in's backward adds
its own to it in place. On a TPU the kernels are Mosaic-compiled; anywhere
else they run in the interpreter (`_core.device.pallas_interpret`). A width
the kernels cannot tile raises `StreamWidthError`; there is no other path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..._core.device import pallas_interpret
from ...observability.programs import mosaic_site
from .flash_attention import _no_x64

_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
LANES = 128
# one tile of the streams; the pipeline holds two of each block, and the
# read-in's backward has three such blocks (x, the partial, the result)
TILE_BYTES = 4 << 20
VMEM_LIMIT_BYTES = 64 << 20

_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b
_TN = (((0,), (0,)), ((), ()))      # a^T . b


class StreamWidthError(ValueError):
    """The streams' width cannot be tiled by the mixing kernels."""


def sinkhorn(m, iters: int, eps: float):
    """m [n, n, ...] positive -> doubly stochastic over its first two axes:
    `iters` times, divide each row (axis 1 summed) by its sum + eps, then
    each column. The token axes stay minor, so a row sum is a few
    elementwise adds and no cross-lane reduction."""
    for _ in range(iters):
        m = m / (m.sum(1, keepdims=True) + eps)
        m = m / (m.sum(0, keepdims=True) + eps)
    return m


def coefficients(z, n: int, iters: int, eps: float, clamp):
    """z [2n + n*n or more, tokens], the scaled and biased projections ->
    (H_pre [n, tokens], H_post [n, tokens], H_res [n, n, tokens])."""
    res = jnp.stack([z[(2 + i) * n:(3 + i) * n] for i in range(n)])
    return (jax.nn.sigmoid(z[:n]), 2.0 * jax.nn.sigmoid(z[n:2 * n]),
            sinkhorn(jnp.exp(jnp.clip(res, *clamp)), iters, eps))


# ------------------------------------------------------------------ tiling

def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b


def tiling(n: int, tokens: int, h: int, dtype):
    """(tokens a tile, lanes a chunk) from the shapes: the largest tile of
    128, 256 or 512 tokens whose n streams fit `TILE_BYTES` (and no more
    than the tokens there are), gone over 512 lanes at a time or the
    largest multiple of 128 under that which divides h."""
    if h % LANES and not pallas_interpret():
        raise StreamWidthError(
            f"stream mixing needs a hidden size that is a multiple of "
            f"{LANES} lanes; got {h}")
    chunk = next((c for c in (512, 384, 256, 128) if h % c == 0), h)
    row = n * h * jnp.dtype(dtype).itemsize
    if LANES * row > 2 * TILE_BYTES:
        raise StreamWidthError(
            f"{n} streams of width {h} ({jnp.dtype(dtype).name}) take "
            f"{LANES * row / 2**20:.1f} MiB for the smallest tile of "
            f"{LANES} tokens; the kernels keep a tile of all the streams "
            f"in VMEM and allow {2 * TILE_BYTES >> 20} MiB for it")
    tile = next((t for t in (512, 256) if t * row <= TILE_BYTES), LANES)
    return min(tile, _round_up(tokens, LANES)), chunk


def _fold(a):
    """[tile, chunk] -> [tile, 128]: the lane groups added up (no
    cross-lane work); a chunk that is no multiple of 128 stays whole."""
    if a.shape[1] % LANES:
        return a
    return sum(a[:, k:k + LANES] for k in range(0, a.shape[1], LANES))


def _zero_sums(count: int, tile: int, chunk: int):
    """`count` accumulators of what `_fold` returns."""
    return [jnp.zeros((tile, min(chunk, LANES)), _F32)] * count


def _columns(rows):
    """[k, tile] with the tokens on the lanes -> k columns [tile, 1] with
    them on the sublanes, through a transpose padded to 128 rows."""
    k, tile = rows.shape
    cols = jnp.concatenate(
        [rows, jnp.zeros((LANES - k, tile), rows.dtype)], 0).T
    return [cols[:, j:j + 1] for j in range(k)]


def _sums_to_rows(sums, k: int):
    """A list of [tile, lanes] partial sums over h -> [k, tile]: row j is
    the lane sum of sums[j] (k >= len(sums); further rows are zero)."""
    tile = sums[0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile, LANES), 1)
    cols = jnp.zeros((tile, LANES), _F32)
    for j, s in enumerate(sums):
        cols = jnp.where(lane == j, s.sum(1, keepdims=True), cols)
    return cols.T[:k]


def _split3(a):
    """float32 -> three bfloat16 terms, largest first, whose float32 sum
    is `a` to rounding. The first two are cut, not rounded: masking the
    low half of the bits leaves a float32 that is a bfloat16 already, so a
    compiler that may keep excess precision has no rounding to drop."""
    def cut(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.int32) & jnp.int32(-65536)
        return jax.lax.bitcast_convert_type(bits, _F32)

    hi = cut(a)
    mid = cut(a - hi)
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            (a - hi - mid).astype(jnp.bfloat16))


def _sum_terms(a, kp: int):
    """[terms * kp, m] -> [kp, m]: the term blocks added, smallest first."""
    out = a[a.shape[0] - kp:]
    for r in range(a.shape[0] - 2 * kp, -1, -kp):
        out = out + a[r:r + kp]
    return out


def _dot(a, b, dims):
    """A float32-accumulated product: one MXU pass on bfloat16 terms,
    HIGHEST on anything else."""
    exact = a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32,
                               precision=None if exact else _HIGHEST)


def _chunks(h: int, chunk: int, body, carry=None):
    """`body(lanes, carry)` over h in chunks of lanes."""
    def step(c, carry):
        return body(pl.ds(pl.multiple_of(c * chunk, chunk), chunk), carry)
    return jax.lax.fori_loop(0, h // chunk, step, carry)


# ------------------------------------------------------------- the kernels

def _read_in_kernel(x_ref, g_ref, sb_ref, hin_ref, mix_ref, praw_ref,
                    rinv_ref, *, chunk, coef, eps):
    """One tile of tokens: the sum of squares and the projections in one
    pass over the tile, the coefficients, h_in in a second pass."""
    n, tile, h = x_ref.shape
    kp = praw_ref.shape[0]

    def reduce(lanes, carry):
        acc, sq = carry
        for i in range(n):
            xc = x_ref[i, :, lanes]
            acc = acc + _dot(g_ref[i, :, lanes], xc.astype(g_ref.dtype), _NT)
            xf = xc.astype(_F32)
            sq = sq + _fold(xf * xf)
        return acc, sq

    acc, sq = _chunks(h, chunk, reduce, (
        jnp.zeros((g_ref.shape[1], tile), _F32),
        _zero_sums(1, tile, chunk)[0]))
    rinv = jax.lax.rsqrt(sq.T.sum(0, keepdims=True) / (n * h) + eps)
    praw = _sum_terms(acc, kp)                              # [kp, tile]
    pre, post, res = coef(praw * rinv * sb_ref[:, 0:1] + sb_ref[:, 1:2])
    praw_ref[...] = praw
    rinv_ref[...] = rinv
    mix_ref[0:n] = post
    for i in range(n):
        mix_ref[(1 + i) * n:(2 + i) * n] = res[i]
    pre = _columns(pre)

    def mix_in(lanes, _):
        hin_ref[:, lanes] = sum(
            pre[i] * x_ref[i, :, lanes].astype(_F32)
            for i in range(n)).astype(hin_ref.dtype)

    _chunks(h, chunk, mix_in)


def _mix_columns(mix_ref, n: int):
    """H_post [n] and H_res [n][n] as [tile, 1] columns."""
    cols = _columns(mix_ref[...])
    return cols[:n], [cols[(1 + i) * n:(2 + i) * n] for i in range(n)]


def _write_back_kernel(x_ref, y_ref, mix_ref, out_ref, *, chunk):
    n, _, h = x_ref.shape
    post, res = _mix_columns(mix_ref, n)

    def body(lanes, _):
        xs = [x_ref[j, :, lanes].astype(_F32) for j in range(n)]
        yf = y_ref[:, lanes].astype(_F32)
        for i in range(n):
            out_ref[i, :, lanes] = (
                sum(res[i][j] * xs[j] for j in range(n))
                + post[i] * yf).astype(out_ref.dtype)

    _chunks(h, chunk, body)


def _write_back_bwd_kernel(dout_ref, x_ref, y_ref, mix_ref, dx_ref, dy_ref,
                           dmix_ref, *, chunk):
    """The write-back's part of dx, dy, and the n + n*n sums over h that
    are dH_post and dH_res, on the tile that is in VMEM anyway."""
    n, tile, h = x_ref.shape
    post, res = _mix_columns(mix_ref, n)

    def body(lanes, sums):
        ds = [dout_ref[i, :, lanes].astype(_F32) for i in range(n)]
        xs = [x_ref[j, :, lanes].astype(_F32) for j in range(n)]
        yf = y_ref[:, lanes].astype(_F32)
        for j in range(n):
            dx_ref[j, :, lanes] = sum(
                res[i][j] * ds[i] for i in range(n)).astype(dx_ref.dtype)
        dy_ref[:, lanes] = sum(
            post[i] * ds[i] for i in range(n)).astype(dy_ref.dtype)
        new = [ds[i] * yf for i in range(n)] + [
            ds[i] * xs[j] for i in range(n) for j in range(n)]
        return [s + _fold(a) for s, a in zip(sums, new)]

    sums = _chunks(h, chunk, body, _zero_sums(n + n * n, tile, chunk))
    dmix_ref[...] = _sums_to_rows(sums, dmix_ref.shape[0])


def _read_in_bwd_kernel(dhin_ref, x_ref, part_ref, g_ref, praw_ref, rinv_ref,
                        dmix_ref, sb_ref, dx_ref, dg_ref, dsb_ref, *, chunk,
                        coef):
    """dH_pre from a first pass over the tile, `jax.vjp` of the
    coefficients, then dx added to the write-back's part in place and
    d(norm_g * phi), d scale and d bias summed over the token grid."""
    n, tile, h = x_ref.shape
    kp = praw_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        dsb_ref[...] = jnp.zeros_like(dsb_ref)

    def reduce(lanes, sums):
        dh = dhin_ref[:, lanes].astype(_F32)
        return [s + _fold(dh * x_ref[i, :, lanes].astype(_F32))
                for i, s in enumerate(sums)]

    sums = _chunks(h, chunk, reduce, _zero_sums(n, tile, chunk))
    dpre = _sums_to_rows(sums, n)
    praw, rinv, scale = praw_ref[...], rinv_ref[...], sb_ref[:, 0:1]
    proj = praw * rinv
    (pre, _, _), pull = jax.vjp(coef, proj * scale + sb_ref[:, 1:2])
    dres = jnp.stack([dmix_ref[(1 + i) * n:(2 + i) * n] for i in range(n)])
    (dz,) = pull((dpre, dmix_ref[0:n], dres))
    dsb_ref[:, 0:1] += (dz * proj).sum(1, keepdims=True)
    dsb_ref[:, 1:2] += dz.sum(1, keepdims=True)
    dproj = dz * scale
    dpraw = dproj * rinv
    # rinv = (ss / (n h) + eps) ** -0.5 and d(ss) / dx = 2 x
    dss2 = -(dproj * praw).sum(0, keepdims=True) * rinv ** 3 / (n * h)
    *pre, dss2 = _columns(jnp.concatenate([pre, dss2], 0))
    if g_ref.dtype == jnp.bfloat16:
        hi, mid, lo = _split3(dpraw)
        for_dx = jnp.concatenate([hi, hi, mid], 0)      # against hi mid hi
        for_dg = jnp.concatenate([hi, mid, lo], 0)
    else:
        for_dx = for_dg = dpraw

    def second(lanes, _):
        dh = dhin_ref[:, lanes].astype(_F32)
        for i in range(n):
            xc = x_ref[i, :, lanes]
            xf = xc.astype(_F32)
            dx_ref[i, :, lanes] = (
                part_ref[i, :, lanes].astype(_F32) + pre[i] * dh
                + dss2 * xf + _dot(for_dx, g_ref[i, :, lanes], _TN)
            ).astype(dx_ref.dtype)
            dg_ref[i, :, lanes] += _sum_terms(
                _dot(for_dg, xc.astype(for_dg.dtype), _NN), kp)

    _chunks(h, chunk, second)


# --------------------------------------------------------------- the calls

def _call(kernel, args, in_specs, out_specs, out_shape, grid, aliases=None):
    with mosaic_site(kernel, *args), _no_x64():
        return pl.pallas_call(
            kernel, grid=(grid,), in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, input_output_aliases=aliases or {},
            # the last kernel sums over the token grid
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT_BYTES),
            interpret=pallas_interpret())(*args)


def _streams(n, tile, h):
    return pl.BlockSpec((n, tile, h), lambda t: (0, t, 0))


def _tokens(tile, h):
    return pl.BlockSpec((tile, h), lambda t: (t, 0))


def _rows(k, tile):
    return pl.BlockSpec((k, tile), lambda t: (0, t))


def _whole(shape):
    return pl.BlockSpec(shape, lambda t: (0,) * len(shape))


def read_in_forward(x, g, sb, coef, eps):
    """x [n, T, h], g [n, terms * kp, h] (`pack`), sb [kp, 2] -> (h_in
    [T, h], mix [n + n*n, T] = H_post over H_res, praw [kp, T], rinv
    [1, T]); T a multiple of the tile."""
    n, t, h = x.shape
    kp = sb.shape[0]
    tile, chunk = tiling(n, t, h, x.dtype)
    return _call(
        functools.partial(_read_in_kernel, chunk=chunk, coef=coef, eps=eps),
        (x, g, sb),
        [_streams(n, tile, h), _whole(g.shape), _whole(sb.shape)],
        [_tokens(tile, h), _rows(n + n * n, tile), _rows(kp, tile),
         _rows(1, tile)],
        [jax.ShapeDtypeStruct((t, h), x.dtype),
         jax.ShapeDtypeStruct((n + n * n, t), _F32),
         jax.ShapeDtypeStruct((kp, t), _F32),
         jax.ShapeDtypeStruct((1, t), _F32)], t // tile)


def write_back_forward(x, y, mix):
    """x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y, [n, T, h]."""
    n, t, h = x.shape
    tile, chunk = tiling(n, t, h, x.dtype)
    return _call(
        functools.partial(_write_back_kernel, chunk=chunk), (x, y, mix),
        [_streams(n, tile, h), _tokens(tile, h), _rows(n + n * n, tile)],
        _streams(n, tile, h), jax.ShapeDtypeStruct(x.shape, x.dtype),
        t // tile)


def write_back_backward(dout, x, y, mix):
    """-> (the write-back's part of dx [n, T, h], written over `dout`; dy
    [T, h]; dmix [n + n*n, T] float32)."""
    n, t, h = x.shape
    tile, chunk = tiling(n, t, h, x.dtype)
    return _call(
        functools.partial(_write_back_bwd_kernel, chunk=chunk),
        (dout, x, y, mix),
        [_streams(n, tile, h), _streams(n, tile, h), _tokens(tile, h),
         _rows(n + n * n, tile)],
        [_streams(n, tile, h), _tokens(tile, h), _rows(n + n * n, tile)],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct(y.shape, y.dtype),
         jax.ShapeDtypeStruct(mix.shape, _F32)], t // tile, aliases={0: 0})


def read_in_backward(dhin, x, part, g, praw, rinv, dmix, sb, coef):
    """-> (dx [n, T, h] written over `part`, d(norm_g * phi) [n, kp, h],
    d(scale, bias) [kp, 2]); g is `pack`'s operand for dx."""
    n, t, h = x.shape
    kp = sb.shape[0]
    tile, chunk = tiling(n, t, h, x.dtype)
    return _call(
        functools.partial(_read_in_bwd_kernel, chunk=chunk, coef=coef),
        (dhin, x, part, g, praw, rinv, dmix, sb),
        [_tokens(tile, h), _streams(n, tile, h), _streams(n, tile, h),
         _whole(g.shape), _rows(kp, tile), _rows(1, tile),
         _rows(n + n * n, tile), _whole(sb.shape)],
        [_streams(n, tile, h), _whole((n, kp, h)), _whole(sb.shape)],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         jax.ShapeDtypeStruct((n, kp, h), _F32),
         jax.ShapeDtypeStruct(sb.shape, _F32)], t // tile, aliases={2: 0})


# ------------------------------------------------------ parameters, packed

def pack(hc, n: int, h: int):
    """The sub-layer's parameters as the kernels take them: (norm_g * phi
    transposed to [n, kp, h] float32, kp = 2n + n*n rounded up to 16 with
    zero rows; [kp, 2] = each projection's alpha beside its bias)."""
    k = 2 * n + n * n
    pad = _round_up(k, 16) - k
    g = (hc["norm_g"][:, None] * hc["phi"]).reshape(n, h, k)
    g = jnp.pad(jnp.swapaxes(g, 1, 2), ((0, 0), (0, pad), (0, 0))).astype(_F32)
    scale = jnp.repeat(hc["alpha"], np.array([n, n, n * n]))
    bias = jnp.concatenate([hc["b_pre"], hc["b_post"],
                            hc["b_res"].reshape(-1)])
    return g, jnp.pad(jnp.stack([scale, bias], 1).astype(_F32),
                      ((0, pad), (0, 0)))


def _terms(g, dtype, order):
    """The matrix as the product with streams of `dtype` takes it: its
    bfloat16 terms in `order` side by side, or itself."""
    if dtype != jnp.bfloat16:
        return g
    split = _split3(g)
    return jnp.concatenate([split[i] for i in order], 1)


# --------------------------------------------------------------- public

def _flatten(a, lead: int, trail: int, padded: int):
    """The token axes (between `lead` leading and `trail` trailing ones)
    made one and padded with zeros to `padded` tokens: a padded token
    mixes to zero and adds nothing to any gradient."""
    a = a.reshape(a.shape[:lead] + (-1,) + a.shape[a.ndim - trail:])
    if a.shape[lead] == padded:
        return a
    widths = [(0, 0)] * a.ndim
    widths[lead] = (0, padded - a.shape[lead])
    return jnp.pad(a, widths)


def _unflatten(a, lead: int, tokens):
    """The reverse: the padding dropped, the token axes restored."""
    a = jax.lax.slice_in_dim(a, 0, math.prod(tokens), axis=lead)
    return a.reshape(a.shape[:lead] + tuple(tokens) + a.shape[lead + 1:])


def _padded(x) -> int:
    """The streams' tokens rounded up to whole tiles."""
    count = math.prod(x.shape[1:-1])
    tile, _ = tiling(x.shape[0], count, x.shape[-1], x.dtype)
    return _round_up(count, tile)


def _coef(n, iters, eps, clamp):
    return functools.partial(coefficients, n=n, iters=iters, eps=eps,
                             clamp=clamp)


def _narrow(a):
    """The mixing is float32: wider streams are narrowed to it."""
    return a.astype(_F32) if a.dtype.itemsize > 4 else a


def read_in(x, hc, iters: int, eps: float, clamp):
    """The streams x [n, *tokens, h] and a sub-layer's mixing parameters
    hc (norm_g [n h], phi [n h, 2n + n*n], alpha [3], b_pre [n], b_post
    [n], b_res [n, n], float32) -> (h_in [*tokens, h], mix [n + n*n,
    *tokens] float32 = H_post over H_res row by row, x itself for
    `write_back`)."""
    return _read_in(_narrow(x), hc, iters, eps, clamp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _read_in(x, hc, iters, eps, clamp):
    return _read_in_fwd(x, hc, iters, eps, clamp)[0]


def _read_in_fwd(x, hc, iters, eps, clamp):
    n, h, tokens = x.shape[0], x.shape[-1], x.shape[1:-1]
    g, sb = pack(hc, n, h)
    hin, mix, praw, rinv = read_in_forward(
        _flatten(x, 1, 1, _padded(x)), _terms(g, x.dtype, (0, 1, 2)), sb,
        _coef(n, iters, eps, clamp), eps)
    return ((_unflatten(hin, 0, tokens), _unflatten(mix, 1, tokens), x),
            (x, hc, praw, rinv))


def _read_in_bwd(iters, eps, clamp, saved, cotangents):
    x, hc, praw, rinv = saved
    dhin, dmix, part = cotangents
    n, h, padded = x.shape[0], x.shape[-1], praw.shape[1]
    (g, sb), pull = jax.vjp(functools.partial(pack, n=n, h=h), hc)
    # against for_dx's terms (hi, hi, mid): hi.hi + hi.mid + mid.hi
    dx, dg, dsb = read_in_backward(
        _flatten(dhin, 0, 1, padded), _flatten(x, 1, 1, padded),
        _flatten(part, 1, 1, padded), _terms(g, x.dtype, (0, 1, 0)), praw,
        rinv, _flatten(dmix, 1, 0, padded), sb, _coef(n, iters, eps, clamp))
    return _unflatten(dx, 1, x.shape[1:-1]), pull((dg, dsb))[0]


_read_in.defvjp(_read_in_fwd, _read_in_bwd)


def write_back(x, y, mix):
    """x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y on x [n, *tokens, h],
    y [*tokens, h] and `read_in`'s mix."""
    x = _narrow(x)
    return _write_back(x, y.astype(x.dtype), mix)


@jax.custom_vjp
def _write_back(x, y, mix):
    return _write_back_fwd(x, y, mix)[0]


def _write_back_fwd(x, y, mix):
    padded = _padded(x)
    out = write_back_forward(_flatten(x, 1, 1, padded),
                             _flatten(y, 0, 1, padded),
                             _flatten(mix, 1, 0, padded))
    return _unflatten(out, 1, x.shape[1:-1]), (x, y, mix)


def _write_back_bwd(saved, dout):
    x, y, mix = saved
    padded, tokens = _padded(x), x.shape[1:-1]
    dx, dy, dmix = write_back_backward(
        _flatten(dout, 1, 1, padded), _flatten(x, 1, 1, padded),
        _flatten(y, 0, 1, padded), _flatten(mix, 1, 0, padded))
    return (_unflatten(dx, 1, tokens), _unflatten(dy, 0, tokens),
            _unflatten(dmix, 1, tokens))


_write_back.defvjp(_write_back_fwd, _write_back_bwd)
