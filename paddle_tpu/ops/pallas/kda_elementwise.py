"""The elementwise passes of a Kimi-Delta-Attention layer around the chunked
rule (`models/delta_layer.py`: the short convolutions with their head norms,
the log-decays, the gated head norm) as Pallas TPU kernels under three
`jax.custom_vjp`s. Each reads its operands once where the projections wrote
them, `[B, S, H d]`, keeps everything it forms in VMEM in float32, and writes
its result once:

    conv_heads(x, taps, heads, norm, eps,   silu(sum_i taps[i] x[t-K+1+i])
               dtype)                       with zero before a row's start;
                                            with `norm`, each head over its L2
                                            norm (eps under the root), times
                                            `norm`; in `dtype` (x's if None)
    log_decay(a, decay_log, dt_bias)        -exp(A_h) softplus(a + b_dt),
                                            float32
    gated_norm(o, gain, gate, eps)          RMSNorm over each head's d (gain
                                            shared by the heads) times
                                            sigmoid(gate); in o's dtype

A grid step takes a block of `(1, tokens, lanes)` of every `[B, S, H d]`
operand, whole heads along the lanes (`tiling`), on the grid (row, token
block, lane block). The convolution's step also reads the `HALO` tokens
before its block, a second BlockSpec on the same operand (zero at a row's
start), and moves the block later by 1 to K - 1 tokens with a rotation of
its sublanes (`_later`), the first rows from that halo.

**Backward.** Each `custom_vjp` keeps its inputs and nothing else: the
backward kernel forms the forward again in VMEM from them, writes the
gradient of each `[B, S, H d]` operand once, and the parameters' gradients as
float32 sums over its block's tokens, one set a grid step (`[B, S / tokens,
rows, lanes]`), which XLA adds up. The convolution's backward sums the taps
over the tokens after a token, so it also reads the `HALO` tokens after its
block, of x and of the cotangent (zero past a row's end).

**Precision:** float32 inside every kernel; results rounded once, to the
input's dtype (the convolution's to the `dtype` it is given: the model hands
it float32 products and takes the rule's bfloat16), and the log-decays kept
in float32.

`taken(head_dim, seq, *operands)` says from the operands alone whether the
kernels serve a call: head widths that are multiples of 128 lanes, a
sequence of whole `HALO`s and no 64-bit operand. Anything else keeps the
XLA functions of `models/delta_layer.py`, which the kernels are tested against.
The entries are module-level `jax.jit` functions with static tiles and
mode, so that the sites of one shape share one trace of a kernel body and
one Mosaic body a lowered program (as `grouped_matmul.py`'s and
`delta_rule.py`'s); each `pallas_call` stands in a `mosaic_site` span. On
a TPU the kernels are Mosaic-compiled; anywhere else they run in the
interpreter (`_core.device.pallas_interpret`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..._core.device import pallas_interpret
from ...observability.programs import mosaic_site
from .flash_attention import _no_x64

LANES = 128
HALO = 16                   # tokens of a neighbouring block (>= taps - 1;
#                             whole tiles of a 16-bit operand)
TILE_TOKENS = 512           # the most tokens a block takes,
TILE_ELEMENTS = 512 * 512   # and the most elements

_F32 = jnp.float32


def taken(head_dim: int, seq: int, *operands) -> bool:
    """Whether the kernels serve a layer of this head width and sequence
    on these operands (Mosaic has no 64-bit types)."""
    return (head_dim % LANES == 0 and seq % HALO == 0
            and all(jnp.dtype(x.dtype).itemsize <= 4 for x in operands))


def tiling(seq: int, width: int, head_dim: int):
    """(tokens, lanes) of a block: the most tokens up to `TILE_TOKENS` that
    divide the sequence, then the most whole heads that divide the width
    and keep the block within `TILE_ELEMENTS`."""
    tokens = next(t for t in (TILE_TOKENS, 256, 128, 64, 32, HALO)
                  if seq % t == 0)
    heads = width // head_dim
    fit = max((n for n in range(1, heads + 1) if heads % n == 0
               and tokens * n * head_dim <= TILE_ELEMENTS), default=1)
    return tokens, fit * head_dim


# ------------------------------------------------- what the kernels form

def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _later(x, before, j: int):
    """x [T, d] moved j tokens later: row t is x[t - j], its first j rows
    from `before` [HALO, d], the tokens just before x."""
    if j == 0:
        return x
    moved = pltpu.roll(x, j, 0)
    head = jnp.where(_iota(before.shape, 0) < j, pltpu.roll(before, j, 0),
                     moved[:HALO])
    return head if x.shape[0] == HALO else jnp.concatenate(
        [head, moved[HALO:]], 0)


def _earlier(x, after, j: int):
    """x [T, d] moved j tokens earlier: row t is x[t + j], its last j rows
    from `after` [HALO, d], the tokens just after x."""
    if j == 0:
        return x
    tokens = x.shape[0]
    moved = pltpu.roll(x, tokens - j, 0)
    tail = jnp.where(_iota(after.shape, 0) >= HALO - j,
                     pltpu.roll(after, HALO - j, 0), moved[tokens - HALO:])
    return tail if tokens == HALO else jnp.concatenate(
        [moved[:tokens - HALO], tail], 0)


def _convolve(x, before, taps):
    """sum_i taps[i] x[t - K + 1 + i] for x [T, d] after `before`, taps
    [K, d] -> (the sum, the K moved copies of x it multiplies)."""
    size = taps.shape[0]
    moved = [_later(x, before, size - 1 - i) for i in range(size)]
    return sum(taps[i:i + 1] * moved[i] for i in range(size)), moved


def _activated(pre, norm, eps):
    """silu(pre) [T, d], over its L2 norm times `norm` where given."""
    y = pre * jax.nn.sigmoid(pre)
    if norm is None:
        return y
    return y * (norm * jax.lax.rsqrt((y * y).sum(-1, keepdims=True) + eps))


def _activated_grad(pre, dz, norm, eps):
    """The gradient to `pre` of `_activated` under its cotangent dz."""
    s = jax.nn.sigmoid(pre)
    if norm is not None:
        y = pre * s
        r = jax.lax.rsqrt((y * y).sum(-1, keepdims=True) + eps)
        dz = (norm * r) * (dz - y * (r * r) * (dz * y).sum(-1, keepdims=True))
    return dz * s * (1.0 + pre * (1.0 - s))


def _softplus(z):
    return jnp.maximum(z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z)))


def _heads(ref, head_dim: int):
    """The lane slices of the heads in a block of `ref`."""
    return [slice(h * head_dim, (h + 1) * head_dim)
            for h in range(ref.shape[-1] // head_dim)]


# ------------------------------------------------------------ the kernels

def _conv_fwd_kernel(x_ref, before_ref, taps_ref, out_ref, *, head_dim, norm,
                     eps):
    first = pl.program_id(1) == 0
    for lanes in _heads(x_ref, head_dim):
        before = jnp.where(first, 0.0, before_ref[0, :, lanes].astype(_F32))
        pre, _ = _convolve(x_ref[0, :, lanes].astype(_F32), before,
                           taps_ref[:, lanes].astype(_F32))
        out_ref[0, :, lanes] = _activated(pre, norm, eps).astype(
            out_ref.dtype)


def _conv_bwd_kernel(x_ref, before_ref, dout_ref, taps_ref, dx_ref, dtaps_ref,
                     x_after, d_after, *, head_dim, norm, eps):
    """dx[t] = sum_i taps[i] dpre[t + K - 1 - i], dpre the gradient to the
    convolution's sum, which the HALO tokens after the block also need
    (their sums read the block's last tokens); dtaps[i] = sum_t dpre[t]
    x[t - K + 1 + i] over the block. The grid walks a row's blocks from
    its end, so the tokens after a block, of x and of the cotangent, are
    the first ones of the block walked before it: VMEM scratch, zero at
    the row's end."""
    walked = pl.program_id(2)
    start = walked == pl.num_programs(2) - 1

    @pl.when(walked == 0)
    def _():
        x_after[...] = jnp.zeros_like(x_after)
        d_after[...] = jnp.zeros_like(d_after)

    for lanes in _heads(x_ref, head_dim):
        taps = taps_ref[:, lanes].astype(_F32)
        size = taps.shape[0]
        x = x_ref[0, :, lanes].astype(_F32)
        dout = dout_ref[0, :, lanes].astype(_F32)
        before = jnp.where(start, 0.0, before_ref[0, :, lanes].astype(_F32))
        after = x_after[:, lanes]
        pre, moved = _convolve(x, before, taps)
        pre_after, _ = _convolve(after, x[x.shape[0] - HALO:], taps)
        d_pre = _activated_grad(pre, dout, norm, eps)
        d_pre_after = _activated_grad(pre_after, d_after[:, lanes], norm, eps)
        x_after[:, lanes] = x[:HALO]
        d_after[:, lanes] = dout[:HALO]
        dx_ref[0, :, lanes] = sum(
            taps[i:i + 1] * _earlier(d_pre, d_pre_after, size - 1 - i)
            for i in range(size)).astype(dx_ref.dtype)
        for i in range(size):
            dtaps_ref[0, 0, i:i + 1, lanes] = (d_pre * moved[i]).sum(
                0, keepdims=True)


def _decay_fwd_kernel(a_ref, coef_ref, bias_ref, g_ref):
    g_ref[0] = coef_ref[...] * _softplus(
        a_ref[0].astype(_F32) + bias_ref[...])


def _decay_bwd_kernel(a_ref, coef_ref, bias_ref, dg_ref, da_ref, sums_ref):
    """da, and the sums over the block's tokens of the gradients to the
    coefficient (row 0) and to the bias (row 1), a channel each."""
    z = a_ref[0].astype(_F32) + bias_ref[...]
    dg = dg_ref[0]
    dz = dg * coef_ref[...] * jax.nn.sigmoid(z)
    da_ref[0] = dz.astype(da_ref.dtype)
    sums_ref[0, 0, 0:1] = (dg * _softplus(z)).sum(0, keepdims=True)
    sums_ref[0, 0, 1:2] = dz.sum(0, keepdims=True)


def _norm_fwd_kernel(o_ref, gate_ref, gain_ref, y_ref, *, head_dim, eps):
    gain = gain_ref[...].astype(_F32)
    for lanes in _heads(o_ref, head_dim):
        o = o_ref[0, :, lanes].astype(_F32)
        r = jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)
        y_ref[0, :, lanes] = (o * r * gain * jax.nn.sigmoid(
            gate_ref[0, :, lanes].astype(_F32))).astype(y_ref.dtype)


def _norm_bwd_kernel(o_ref, gate_ref, gain_ref, dy_ref, do_ref, dgate_ref,
                     dgain_ref, *, head_dim, eps):
    """do, dgate, and the sum over the block's tokens and heads of the
    gradient to the gain."""
    gain = gain_ref[...].astype(_F32)
    d_gain = jnp.zeros(gain.shape, _F32)
    for lanes in _heads(o_ref, head_dim):
        o = o_ref[0, :, lanes].astype(_F32)
        dy = dy_ref[0, :, lanes].astype(_F32)
        s = jax.nn.sigmoid(gate_ref[0, :, lanes].astype(_F32))
        r = jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps)
        normed = o * r
        dgate_ref[0, :, lanes] = (dy * normed * gain * s * (1.0 - s)).astype(
            dgate_ref.dtype)
        u = dy * gain * s
        do_ref[0, :, lanes] = (r * (u - normed * (u * normed).mean(
            -1, keepdims=True))).astype(do_ref.dtype)
        d_gain = d_gain + (dy * normed * s).sum(0, keepdims=True)
    dgain_ref[0, 0] = d_gain


# -------------------------------------------------------------- the calls

def _call(kernel, operands, grid, in_specs, out_specs, out_shape,
          interpret, scratch=(), aliases=None, sequential=False):
    """One `pallas_call` in its `mosaic_site`; `sequential`: the grid's
    last axis carries scratch from step to step."""
    semantics = ("parallel",) * (len(grid) - 1) \
        + ("arbitrary" if sequential else "parallel",)
    with mosaic_site(kernel, *operands):
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=list(scratch),
            input_output_aliases=aliases or {},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics),
            interpret=interpret)(*operands)


def _grid(x, head_dim: int):
    """(tokens, lanes, grid) for the `[B, S, W]` operand x."""
    b, s, w = x.shape
    tokens, lanes = tiling(s, w, head_dim)
    return tokens, lanes, (b, s // tokens, w // lanes)


def _block(tokens, lanes):
    return pl.BlockSpec((1, tokens, lanes), lambda b, n, c: (b, n, c))


def _before(tokens, lanes):
    """The HALO tokens before a block, clamped into the row: the kernels
    zero them at its start."""
    per = tokens // HALO
    return pl.BlockSpec((1, HALO, lanes),
                        lambda b, n, c: (b, jnp.maximum(n * per - 1, 0), c))


def _columns(rows, lanes):
    """A `[rows, W]` operand's block of lanes, the same for every token."""
    return pl.BlockSpec((rows, lanes), lambda b, n, c: (0, c))


def _sums(rows, lanes):
    """A grid step's own `[rows, lanes]` of a `[B, S / tokens, rows, W]`."""
    return pl.BlockSpec((1, 1, rows, lanes), lambda b, n, c: (b, n, 0, c))


def _sums_shape(x, tokens, rows, width):
    return jax.ShapeDtypeStruct(
        (x.shape[0], x.shape[1] // tokens, rows, width), _F32)


@functools.partial(jax.jit, static_argnames=("head_dim", "norm", "eps",
                                             "dtype", "interpret"))
def _conv_forward(x, taps, *, head_dim, norm, eps, dtype, interpret):
    tokens, lanes, grid = _grid(x, head_dim)
    return _call(
        functools.partial(_conv_fwd_kernel, head_dim=head_dim, norm=norm,
                          eps=eps),
        (x, x, taps), grid,
        [_block(tokens, lanes), _before(tokens, lanes),
         _columns(taps.shape[0], lanes)],
        _block(tokens, lanes), jax.ShapeDtypeStruct(x.shape, dtype),
        interpret)


@functools.partial(jax.jit, static_argnames=("head_dim", "norm", "eps",
                                             "interpret"))
def _conv_backward(x, taps, dout, *, head_dim, norm, eps, interpret):
    """(dx, dtaps [K, W] float32); the grid is (row, lane block, token
    block from the row's end)."""
    tokens, lanes, (b, blocks, columns) = _grid(x, head_dim)
    size, per = taps.shape[0], tokens // HALO

    def block(b, c, m):
        return b, blocks - 1 - m, c

    def before(b, c, m):
        return b, jnp.maximum((blocks - 1 - m) * per - 1, 0), c

    dx, dtaps = _call(
        functools.partial(_conv_bwd_kernel, head_dim=head_dim, norm=norm,
                          eps=eps),
        (x, x, dout, taps), (b, columns, blocks),
        [pl.BlockSpec((1, tokens, lanes), block),
         pl.BlockSpec((1, HALO, lanes), before),
         pl.BlockSpec((1, tokens, lanes), block),
         pl.BlockSpec((size, lanes), lambda b, c, m: (0, c))],
        [pl.BlockSpec((1, tokens, lanes), block),
         pl.BlockSpec((1, 1, size, lanes),
                      lambda b, c, m: (b, blocks - 1 - m, 0, c))],
        [jax.ShapeDtypeStruct(x.shape, x.dtype),
         _sums_shape(x, tokens, size, x.shape[2])], interpret,
        scratch=[pltpu.VMEM((HALO, lanes), _F32)] * 2, sequential=True)
    return dx, dtaps.sum((0, 1))


@functools.partial(jax.jit, static_argnames=("head_dim", "interpret"))
def _decay_forward(a, coef, bias, *, head_dim, interpret):
    tokens, lanes, grid = _grid(a, head_dim)
    return _call(
        _decay_fwd_kernel, (a, coef, bias), grid,
        [_block(tokens, lanes), _columns(1, lanes), _columns(1, lanes)],
        _block(tokens, lanes), jax.ShapeDtypeStruct(a.shape, _F32),
        interpret)


@functools.partial(jax.jit, static_argnames=("head_dim", "interpret"))
def _decay_backward(a, coef, bias, dg, *, head_dim, interpret):
    """(da, dcoef [1, W], dbias [1, W])."""
    tokens, lanes, grid = _grid(a, head_dim)
    da, sums = _call(
        _decay_bwd_kernel, (a, coef, bias, dg.astype(_F32)), grid,
        [_block(tokens, lanes), _columns(1, lanes), _columns(1, lanes),
         _block(tokens, lanes)],
        [_block(tokens, lanes), _sums(2, lanes)],
        [jax.ShapeDtypeStruct(a.shape, a.dtype),
         _sums_shape(a, tokens, 2, a.shape[2])], interpret)
    sums = sums.sum((0, 1))
    return da, sums[0:1], sums[1:2]


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _norm_forward(o, gate, gain, *, eps, interpret):
    head_dim = gain.shape[1]
    tokens, lanes, grid = _grid(o, head_dim)
    return _call(
        functools.partial(_norm_fwd_kernel, head_dim=head_dim, eps=eps),
        (o, gate, gain), grid,
        [_block(tokens, lanes), _block(tokens, lanes),
         pl.BlockSpec((1, head_dim), lambda b, n, c: (0, 0))],
        _block(tokens, lanes), jax.ShapeDtypeStruct(o.shape, o.dtype),
        interpret)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _norm_backward(o, gate, gain, dy, *, eps, interpret):
    """(do, dgate, dgain [1, d] float32)."""
    head_dim = gain.shape[1]
    tokens, lanes, grid = _grid(o, head_dim)
    do, dgate, dgain = _call(
        functools.partial(_norm_bwd_kernel, head_dim=head_dim, eps=eps),
        (o, gate, gain, dy.astype(o.dtype)), grid,
        [_block(tokens, lanes), _block(tokens, lanes),
         pl.BlockSpec((1, head_dim), lambda b, n, c: (0, 0)),
         _block(tokens, lanes)],
        [_block(tokens, lanes), _block(tokens, lanes),
         pl.BlockSpec((1, 1, 1, head_dim), lambda b, n, c: (b, n, 0, c))],
        [jax.ShapeDtypeStruct(o.shape, o.dtype),
         jax.ShapeDtypeStruct(gate.shape, gate.dtype),
         _sums_shape(o, tokens, 1, grid[2] * head_dim)], interpret,
        aliases={3: 0, 1: 1})
    return do, dgate, dgain.reshape(-1, head_dim).sum(0, keepdims=True)


# ------------------------------------------------------------ the public

def conv_heads(x, taps, heads: int, norm, eps: float, dtype=None):
    """x [B, S, H d], taps [K, H d] -> [B, S, H, d] in `dtype` (x's if
    None): `models/delta_layer._conv_heads`'s result, rounded once. Gradients
    reach x and taps, in their own dtypes."""
    b, s, w = x.shape
    return _conv(x, taps, w // heads, norm, eps,
                 jnp.dtype(dtype or x.dtype)).reshape(b, s, heads, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _conv(x, taps, head_dim, norm, eps, dtype):
    with _no_x64():
        return _conv_forward(x, taps, head_dim=head_dim, norm=norm, eps=eps,
                             dtype=dtype, interpret=pallas_interpret())


def _conv_fwd(x, taps, head_dim, norm, eps, dtype):
    return _conv(x, taps, head_dim, norm, eps, dtype), (x, taps)


def _conv_bwd(head_dim, norm, eps, dtype, saved, dout):
    x, taps = saved
    with _no_x64():
        dx, dtaps = _conv_backward(x, taps, dout, head_dim=head_dim,
                                   norm=norm, eps=eps,
                                   interpret=pallas_interpret())
    return dx, dtaps.astype(taps.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


def log_decay(a, decay_log, dt_bias):
    """a [B, S, H d], decay_log [H], dt_bias [H d] -> the log-decays
    -exp(A_h) softplus(a + b_dt), float32 [B, S, H, d]:
    `models/delta_layer._log_decay`'s result. Gradients reach all three."""
    b, s, w = a.shape
    heads = decay_log.shape[0]
    coef = jnp.repeat(-jnp.exp(decay_log.astype(_F32)), w // heads)
    return _decay(a, coef[None], dt_bias.astype(_F32)[None]).reshape(
        b, s, heads, -1)


@jax.custom_vjp
def _decay(a, coef, bias):
    with _no_x64():
        return _decay_forward(a, coef, bias, head_dim=LANES,
                              interpret=pallas_interpret())


def _decay_fwd(a, coef, bias):
    return _decay(a, coef, bias), (a, coef, bias)


def _decay_bwd(saved, dg):
    with _no_x64():
        return _decay_backward(*saved, dg, head_dim=LANES,
                               interpret=pallas_interpret())


_decay.defvjp(_decay_fwd, _decay_bwd)


def gated_norm(o, gain, gate, eps: float):
    """o [B, S, H, d], gain [d], gate [B, S, H d] -> RMSNorm_d(o; gain) *
    sigmoid(gate), [B, S, H d] in o's dtype: `models/delta_layer._gated_norm`'s
    result. Gradients reach all three."""
    b, s, heads, d = o.shape
    return _norm(o.reshape(b, s, heads * d), gate, gain.reshape(1, d), eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _norm(o, gate, gain, eps):
    with _no_x64():
        return _norm_forward(o, gate, gain, eps=eps,
                             interpret=pallas_interpret())


def _norm_fwd(o, gate, gain, eps):
    return _norm(o, gate, gain, eps), (o, gate, gain)


def _norm_bwd(eps, saved, dy):
    with _no_x64():
        do, dgate, dgain = _norm_backward(*saved, dy, eps=eps,
                                          interpret=pallas_interpret())
    return do, dgate, dgain.astype(saved[2].dtype)


_norm.defvjp(_norm_fwd, _norm_bwd)
