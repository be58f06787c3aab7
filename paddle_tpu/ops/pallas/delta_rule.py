"""The chunk terms of the gated delta rule (`ops/linear_attention.py`, whose
docstring has the algebra) as two Pallas TPU kernels under one
`jax.custom_vjp`: `chunk_terms(q, k, v, g, beta, heads)` computes, for every
chunk of `CHUNK` tokens of every head, what the scan over chunks reads and
what does not know the state,

    W = T (beta K exp G)    U~ = T (beta V)    Q exp G    K exp(G_C - G)
    keep = exp G_C          B[i, j] = sum_c q_i k_j exp(G_i - G_j)  (j <= i)

with T = (I + A)^-1, A[i, j] = beta_i sum_c k_i k_j exp(G_i - G_j) (j < i)
and G the log-decays summed from the chunk's first token.

**Forward.** A grid step takes one chunk of `heads_step` heads
(`HEADS_STEP`, halved until it divides the heads: their chains are
independent, for the scheduler to overlap): q, k, g `[CHUNK, d_k]`, v
`[CHUNK, d_v]` and beta are read where the projections' successors wrote
them, `[B, S, H d]` in `(1, CHUNK, heads_step d)` blocks indexed by (row,
chunk, head group), and the six results are written once, the chunk axis
leading and the heads apart (`[N, B, H, CHUNK, d]`), as
`lax.scan`'s `xs` want them: no transposed copy stands on either side.
Nothing else reaches HBM. Inside, per head:

* G is a product with a lower triangle of ones (float32, `HIGHEST`).
* The `CHUNK / SUB` sub-blocks on the diagonal are pairwise, all at once on
  `[CHUNK / SUB, SUB, d]` arrays, one step for each row i of a sub-block:
  exp(G_i - G_j) for the j <= i of the sub-block, times k_j, summed over the
  channels against beta_i k_i and against q_i. The first sum is row i of A
  as a column, which is what forward substitution wants next: row i of the
  sub-block's inverse is e_i - sum_j A[i, j] row_j, in the same step. The
  second is row i of B, placed as a column of B^T, which the MXU transposes
  at the end (exactly: by the identity, after B's rounding to q's dtype).
* Below the diagonal the chunk is halved as in the XLA form: a level's
  later halves meet its earlier halves through the summed log at the later
  half's first row, every exponent <= 0, as two products of operands
  rounded to q's dtype.
* T from all the levels at once (`_inverse`): their blocks are L, A below
  the diagonal sub-blocks, and with D^-1 the sub-blocks' inverses N =
  D^-1 L is strictly lower by sub-blocks, N^4 = 0, so T = (I - N + N^2 -
  N^3) D^-1 = (I + N^2) P with P = D^-1 - N D^-1: float32 products
  (`HIGHEST`) three deep, N, then N N and N D^-1 apart, then N^2 P.

**Backward.** Keeps the five inputs and nothing else. It takes the six
cotangents, forms G, the pairwise exponentials, T, W and U again in VMEM and
writes dq, dk, dv, dg, dbeta once. With dKg = T^T dW and dVb = T^T dU the
inverse transposes as dA = -(dKg W^T + dVb U^T) strictly below the diagonal
(= -T^T dT T^T, never formed from dT); a sum S[i, j] = sum_c x_i k_j
exp(G_i - G_j) with cotangent M sends dx_i = sum_j M_ij k_j e_ij to its row
operand (x = beta k for A, q for B), dk_j = sum_i M_ij x_i e_ij to its
column operand, and x dx - k dk to G: pairwise on the diagonal sub-blocks,
through the same halving and the same roundings below it. dg is the
reversed sum of dG within the chunk (a product with an upper triangle of
ones).

**Precision** is the XLA form's: log-decays, their sums, every `exp`, the
pairwise sums and T in float32; the products' operands rounded to q's dtype
(the identity where q is float32, and then at `HIGHEST`), accumulated in
float32.

`taken(d_k, d_v, chunk, sub, dtype)` says from the operands alone whether the
kernels serve a call: head widths that are multiples of 128 lanes, the chunk
of `CHUNK` = 4 sub-blocks of 16, no 64-bit operands. `_forward` and
`_backward` are module-level `jax.jit` functions with static tiles and mode,
so that the call sites of one shape share one trace of a kernel body and one
Mosaic body a lowered program (`grouped_matmul.py`, PERF.md section 6, PRs
33 and 34); the `pallas_call` stands in a `mosaic_site` span, one for each
trace of a body (`_terms_fwd`, `_terms_bwd`). On a TPU the kernels are
Mosaic-compiled; anywhere else they run in the interpreter
(`_core.device.pallas_interpret`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..._core.device import pallas_interpret
from ...observability.programs import mosaic_site
from .flash_attention import _NT, _TN, NEG_INF, _no_x64

CHUNK = 64
SUB = 16
LANES = 128
# heads a grid step takes: independent chains for the scheduler to
# interleave and fewer steps, against code size and compile time
HEADS_STEP = 8

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def taken(d_k: int, d_v: int, chunk: int, sub: int, dtype) -> bool:
    """Whether the kernels serve these head widths, chunk, sub-block and
    q's dtype (Mosaic has no 64-bit types)."""
    return (d_k % LANES == 0 and d_v % LANES == 0 and chunk == CHUNK
            and sub == SUB and jnp.dtype(dtype).itemsize <= 4)


# ------------------------------------------------- what both kernels form

def _dot(a, b, dims=None):
    """a . b accumulated in float32; float32 operands at `HIGHEST`."""
    precision = _HIGHEST if a.dtype == _F32 else None
    if dims is None:
        return jnp.dot(a, b, precision=precision,
                       preferred_element_type=_F32)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _eye(dtype):
    """The [CHUNK, CHUNK] identity."""
    shape = (CHUNK, CHUNK)
    return (_iota(shape, 0) == _iota(shape, 1)).astype(dtype)


def _triangle(upper: bool = False):
    """[CHUNK, CHUNK] float32 ones on and below (above) the diagonal."""
    shape = (CHUNK, CHUNK)
    rows, cols = _iota(shape, 0), _iota(shape, 1)
    return (cols >= rows if upper else rows >= cols).astype(_F32)


def _blocks(x):
    """[CHUNK, d] -> [CHUNK / SUB, SUB, d]."""
    return x.reshape(CHUNK // SUB, SUB, x.shape[-1])


def _own_lane(shape, i: int):
    """On a [CHUNK / SUB, SUB, CHUNK] array: the lane that row i of each
    sub-block has on the whole chunk's [CHUNK, CHUNK]."""
    return _iota(shape, 2) == _iota(shape, 0) * SUB + i


def _pairwise(G4, i: int):
    """exp(G_i - G_j) [CHUNK / SUB, SUB, d] for the j <= i of each
    sub-block, zero for the others."""
    at = _iota(G4.shape, 1)
    return jnp.exp(jnp.where(at <= i, G4[:, i:i + 1] - G4, NEG_INF))


def _levels():
    """The halvings below the diagonal, as the sizes of a half."""
    size = SUB
    while size < CHUNK:
        yield size
        size *= 2


def _through(G, size: int):
    """A level's two factors of exp(G_i - G_j) [CHUNK, d]: (exp(G_i - r) on
    the later halves' rows, exp(r - G_j) on the earlier halves', zero on
    the others), with r the summed log at the later half's first row of
    the same run of 2 `size` tokens: both exponents are <= 0."""
    run = 2 * size
    r = jnp.concatenate([
        jnp.broadcast_to(G[at + size:at + size + 1], (run, G.shape[1]))
        for at in range(0, CHUNK, run)])
    later = (_iota(G.shape, 0) & size) != 0
    return (jnp.exp(jnp.where(later, G - r, NEG_INF)),
            jnp.exp(jnp.where(later, NEG_INF, r - G)))


def _same_run(size: int):
    """[CHUNK, CHUNK]: row and column in one run of 2 `size` tokens."""
    shape = (CHUNK, CHUNK)
    return (_iota(shape, 0) ^ _iota(shape, 1)) < 2 * size


def _inverse_and_b(q, k, kb, G, dtype, want_b: bool):
    """T = (I + A)^-1 [CHUNK, CHUNK] float32, B in `dtype` where `want_b`
    (else None), and each level's factors for the backward to use again:
    the module docstring's forward up to the six results."""
    q4, k4, kb4, G4 = _blocks(q), _blocks(k), _blocks(kb), _blocks(G)
    shape = (CHUNK // SUB, SUB, CHUNK)
    at = _iota(shape, 1)
    T4 = jnp.zeros(shape, _F32)
    Bt4 = jnp.zeros(shape, _F32)
    for i in range(SUB):
        ke = k4 * _pairwise(G4, i)
        a = (ke * kb4[:, i:i + 1]).sum(-1, keepdims=True)
        own = _own_lane(shape, i)
        # rows i and later of T4 are still zero, and a is zero past i
        row = own.astype(_F32) - (a * T4).sum(1, keepdims=True)
        T4 = jnp.where(at == i, row, T4)
        if want_b:
            b = (ke * q4[:, i:i + 1]).sum(-1, keepdims=True)
            Bt4 = jnp.where(own, b, Bt4)
    T = T4.reshape(CHUNK, CHUNK)
    B = None
    if want_b:
        # the diagonal sub-blocks and the levels below have no element in
        # common: each is rounded where it is made
        Bt = Bt4.reshape(CHUNK, CHUNK).astype(dtype)
        B = _dot(_eye(dtype), Bt, _NT)
    factors, lower = [], 0.0
    for size in _levels():
        rows, cols = _through(G, size)
        factors.append((size, rows, cols))
        lhs = jnp.concatenate([q * rows, kb * rows] if want_b
                              else [kb * rows]).astype(dtype)
        below = _dot(lhs, (k * cols).astype(dtype), _NT)
        if 2 * size < CHUNK:
            below = jnp.where(jnp.concatenate(
                [_same_run(size)] * (lhs.shape[0] // CHUNK)), below, 0.0)
        if want_b:
            B = B + below[:CHUNK]
        # the levels' blocks have no element in common
        lower = lower + below[-CHUNK:]
    return (_inverse(T, lower), (None if B is None else B.astype(dtype)),
            factors)


def _inverse(diagonal, lower):
    """T = (I + A)^-1 from D^-1, the diagonal sub-blocks' inverses, and L,
    A below them: N = D^-1 L is strictly lower by sub-blocks, so N^4 = 0
    and T = (I - N + N^2 - N^3) D^-1 = (I + N^2) P with P = D^-1 - N D^-1.
    Three dependent float32 products (`HIGHEST`), the middle two apart."""
    n = _dot(diagonal, lower)
    p = diagonal - _dot(n, diagonal)
    return p + _dot(_dot(n, n), p)


def _head_operands(refs, h: int, head, widths):
    """Head h of a step's blocks, `head` of the layer: q, k, v (float32
    copies), beta [CHUNK, 1] and G, the log-decays summed from the chunk's
    first token (a product with a triangle of ones)."""
    q_ref, k_ref, v_ref, g_ref, beta_ref = refs
    dk, dv = widths
    q = q_ref[0, :, h * dk:(h + 1) * dk].astype(_F32)
    k = k_ref[0, :, h * dk:(h + 1) * dk].astype(_F32)
    v = v_ref[0, :, h * dv:(h + 1) * dv].astype(_F32)
    betas = beta_ref[0]
    beta = jnp.where(_iota(betas.shape, 1) == head, betas, 0.0).sum(
        -1, keepdims=True)
    return q, k, v, beta, _dot(_triangle(), g_ref[0, :, h * dk:(h + 1) * dk])


# ------------------------------------------------------------ the kernels

def _terms_fwd(q_ref, k_ref, v_ref, g_ref, beta_ref, w_ref, u_ref, qin_ref,
                kout_ref, keep_ref, b_ref, *, heads_step, widths):
    """One (row, chunk, head group) grid step of the forward: the six
    results of each of the step's heads, one head after another."""
    dk, dv = widths
    dtype = q_ref.dtype
    for h in range(heads_step):
        head = pl.program_id(2) * heads_step + h
        q, k, v, beta, G = _head_operands(
            (q_ref, k_ref, v_ref, g_ref, beta_ref), h, head, widths)
        decayed, last = jnp.exp(G), G[CHUNK - 1:]
        kb = k * beta
        T, B, _ = _inverse_and_b(q, k, kb, G, dtype, True)
        wu = _dot(T.astype(dtype), jnp.concatenate(
            [kb * decayed, v * beta], 1).astype(dtype))
        w_ref[0, 0, h] = wu[:, :dk].astype(dtype)
        u_ref[0, 0, h] = wu[:, dk:].astype(dtype)
        qin_ref[0, 0, h] = (q * decayed).astype(dtype)
        kout_ref[0, 0, h] = (k * jnp.exp(last - G)).astype(dtype)
        keep_ref[0, 0, :, h * dk:(h + 1) * dk] = jnp.exp(last)
        b_ref[0, 0, h] = B


def _terms_bwd(q_ref, k_ref, v_ref, g_ref, beta_ref, dw_ref, du_ref,
                dqin_ref, dkout_ref, dkeep_ref, db_ref, dq_ref, dk_ref,
                dv_ref, dg_ref, dbeta_ref, *, heads_step, widths):
    """One grid step of the backward: the five gradients of each of the
    step's heads from its inputs and the six cotangents. dbeta's block
    holds all the heads of the chunk and stays in VMEM over the head
    groups, the grid's last and sequential axis: zeroed at the first, a
    column added by each head."""
    dk_, dv_ = widths
    dtype = q_ref.dtype
    shape = (CHUNK // SUB, SUB, CHUNK)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dbeta_ref[...] = jnp.zeros_like(dbeta_ref)

    for h in range(heads_step):
        head = pl.program_id(2) * heads_step + h
        q, k, v, beta, G = _head_operands(
            (q_ref, k_ref, v_ref, g_ref, beta_ref), h, head, widths)
        decayed, last = jnp.exp(G), G[CHUNK - 1:]
        fade = jnp.exp(last - G)
        kb = k * beta
        T, _, factors = _inverse_and_b(q, k, kb, G, dtype, False)
        Tl = T.astype(dtype)
        kg = kb * decayed
        wu = _dot(Tl, jnp.concatenate([kg, v * beta], 1).astype(dtype))
        # [dKg, dVb] = T^T [dW, dU]
        d_in = _dot(Tl, jnp.concatenate(
            [dw_ref[0, 0, h], du_ref[0, 0, h]], 1).astype(dtype), _TN)
        d_kg, d_vb = d_in[:, :dk_], d_in[:, dk_:]
        # dA = -(dKg W^T + dVb U^T), and its transpose for the pairwise part
        d_in_l, wu_l = d_in.astype(dtype), wu.astype(dtype)
        d_a = -_dot(d_in_l, wu_l, _NT)
        d_at = jnp.where(
            _iota((CHUNK, CHUNK), 0) < _iota((CHUNK, CHUNK), 1),
            -_dot(wu_l, d_in_l, _NT), 0.0)
        d_b = db_ref[0, 0, h]
        d_bt = _dot(_eye(dtype), d_b, _NT)
        d_at4, d_bt4 = _blocks(d_at), _blocks(d_bt)

        # the diagonal sub-blocks, pairwise
        q4, k4, kb4, G4 = _blocks(q), _blocks(k), _blocks(kb), _blocks(G)
        wide = (CHUNK // SUB, SUB, dk_)
        at_wide = _iota(wide, 1)
        d_q4 = jnp.zeros(wide, _F32)       # to q, from B
        d_kb4 = jnp.zeros(wide, _F32)      # to beta k, from A's rows
        d_col4 = jnp.zeros(wide, _F32)     # to k, from both's columns
        for i in range(SUB):
            e = _pairwise(G4, i)
            own = _own_lane(shape, i)
            # row i of dA and of dB as columns; e is zero past j = i
            m_a = jnp.where(own, d_at4, 0.0).sum(-1, keepdims=True)
            m_b = jnp.where(own, d_bt4, 0.0).sum(-1, keepdims=True)
            z_a, z_b = m_a * e, m_b * e
            d_col4 = d_col4 + z_a * kb4[:, i:i + 1] + z_b * q4[:, i:i + 1]
            d_kb4 = jnp.where(at_wide == i, (z_a * k4).sum(1, keepdims=True),
                              d_kb4)
            d_q4 = jnp.where(at_wide == i, (z_b * k4).sum(1, keepdims=True),
                             d_q4)
        d_q, d_kb = d_q4.reshape(CHUNK, dk_), d_kb4.reshape(CHUNK, dk_)
        d_col = d_col4.reshape(CHUNK, dk_)

        # the levels below, through the forward's factors and roundings
        at_row, at_col = _iota((CHUNK, CHUNK), 0), _iota((CHUNK, CHUNK), 1)
        for size, rows, cols in factors:
            # a later half's rows on the earlier half's columns of a run
            region = _same_run(size) & ((at_row & size) != 0) \
                & ((at_col & size) == 0)
            m = jnp.concatenate([
                jnp.where(region, d_b.astype(_F32), 0.0),
                jnp.where(region, d_a, 0.0)]).astype(dtype)
            to_rows = _dot(m, (k * cols).astype(dtype))
            d_q = d_q + rows * to_rows[:CHUNK]
            d_kb = d_kb + rows * to_rows[CHUNK:]
            d_col = d_col + cols * _dot(
                m, jnp.concatenate([q * rows, kb * rows]).astype(dtype), _TN)

        qin, kout = q * decayed, k * fade
        d_qin = dqin_ref[0, 0, h].astype(_F32)
        d_kout = dkout_ref[0, 0, h].astype(_F32)
        d_G = (q * d_q + kb * d_kb - k * d_col + d_kg * kg + d_qin * qin
               - d_kout * kout)
        d_last = (d_kout * kout).sum(0, keepdims=True) \
            + dkeep_ref[0, 0, :, h * dk_:(h + 1) * dk_] * jnp.exp(last)
        d_G = d_G + jnp.where(_iota(d_G.shape, 0) == CHUNK - 1, d_last, 0.0)
        dg_ref[0, :, h * dk_:(h + 1) * dk_] = _dot(_triangle(True), d_G)
        dq_ref[0, :, h * dk_:(h + 1) * dk_] = (
            d_q + d_qin * decayed).astype(dtype)
        dk_ref[0, :, h * dk_:(h + 1) * dk_] = (
            beta * (d_kb + d_kg * decayed) + d_col + d_kout * fade
        ).astype(dtype)
        dv_ref[0, :, h * dv_:(h + 1) * dv_] = (beta * d_vb).astype(dtype)
        d_beta = ((d_kb + d_kg * decayed) * k).sum(-1, keepdims=True) \
            + (d_vb * v).sum(-1, keepdims=True)
        betas = dbeta_ref[0]
        dbeta_ref[0] = betas + jnp.where(
            _iota(betas.shape, 1) == head, d_beta, 0.0)


# -------------------------------------------------------------- the calls

def _specs(heads: int, heads_step: int, widths):
    """(the five inputs' blocks, the six results' blocks) on the grid
    (row, chunk, head group)."""
    dk, dv = widths

    def by_token(width):
        return pl.BlockSpec((1, CHUNK, width), lambda b, n, h: (b, n, h))

    def by_chunk(*block):
        return pl.BlockSpec((1, 1, heads_step) + block,
                            lambda b, n, h: (n, b, h) + (0,) * len(block))

    inputs = [by_token(heads_step * dk), by_token(heads_step * dk),
              by_token(heads_step * dv), by_token(heads_step * dk),
              pl.BlockSpec((1, CHUNK, heads), lambda b, n, h: (b, n, 0))]
    keep = pl.BlockSpec((1, 1, 1, heads_step * dk),
                        lambda b, n, h: (n, b, 0, h))
    results = [by_chunk(CHUNK, dk), by_chunk(CHUNK, dv), by_chunk(CHUNK, dk),
               by_chunk(CHUNK, dk), keep, by_chunk(CHUNK, CHUNK)]
    return inputs, results


def _result_shapes(b: int, n: int, heads: int, widths, dtype):
    dk, dv = widths
    lead = (n, b, heads, CHUNK)
    return [jax.ShapeDtypeStruct(lead + (dk,), dtype),
            jax.ShapeDtypeStruct(lead + (dv,), dtype),
            jax.ShapeDtypeStruct(lead + (dk,), dtype),
            jax.ShapeDtypeStruct(lead + (dk,), dtype),
            jax.ShapeDtypeStruct((n, b, 1, heads * dk), _F32),
            jax.ShapeDtypeStruct(lead + (CHUNK,), dtype)]


def _call(kernel, operands, heads, heads_step, in_specs, out_specs,
          out_shape, interpret):
    b, s = operands[0].shape[:2]
    with mosaic_site(kernel, *operands):
        return pl.pallas_call(
            kernel, grid=(b, s // CHUNK, heads // heads_step),
            in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret)(*operands)


@functools.partial(jax.jit,
                   static_argnames=("heads", "heads_step", "interpret"))
def _forward(q, k, v, g, beta, *, heads, heads_step, interpret):
    """The forward kernel's call; jitted, so that the sites of one shape
    share one trace of the kernel body and one Mosaic body a program."""
    b, s = q.shape[:2]
    widths = (q.shape[2] // heads, v.shape[2] // heads)
    inputs, results = _specs(heads, heads_step, widths)
    W, U, q_in, k_out, keep, B = _call(
        functools.partial(_terms_fwd, heads_step=heads_step, widths=widths),
        (q, k, v, g, beta), heads, heads_step, inputs, results,
        _result_shapes(b, s // CHUNK, heads, widths, q.dtype), interpret)
    return W, U, q_in, k_out, keep.reshape(keep.shape[:2] + (heads, -1)), B


@functools.partial(jax.jit,
                   static_argnames=("heads", "heads_step", "interpret"))
def _backward(q, k, v, g, beta, d_terms, *, heads, heads_step, interpret):
    """The backward kernel's call, jitted as `_forward` is."""
    widths = (q.shape[2] // heads, v.shape[2] // heads)
    inputs, results = _specs(heads, heads_step, widths)
    dW, dU, dq_in, dk_out, dkeep, dB = d_terms
    dkeep = dkeep.reshape(dkeep.shape[:2] + (1, -1)).astype(_F32)
    cast = [x.astype(q.dtype) for x in (dW, dU, dq_in, dk_out)]
    return _call(
        functools.partial(_terms_bwd, heads_step=heads_step, widths=widths),
        (q, k, v, g, beta, *cast, dkeep, dB.astype(q.dtype)), heads,
        heads_step, inputs + results, inputs,
        [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v, g, beta)],
        interpret)


def _heads_step(heads: int) -> int:
    """`HEADS_STEP`, halved until it divides `heads`."""
    step = HEADS_STEP
    while heads % step:
        step //= 2
    return step


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def chunk_terms(q, k, v, g, beta, heads: int):
    """q, k [B, S, H d_k], v [B, S, H d_v], g [B, S, H d_k] (float32), beta
    [B, S, H] (float32), S a multiple of `CHUNK` -> (W, U~, Q exp G,
    K exp(G_C - G) [N, B, H, CHUNK, d] in q's dtype, keep = exp G_C
    [N, B, H, d_k] float32, B [N, B, H, CHUNK, CHUNK] in q's dtype) with
    N = S / CHUNK: `linear_attention._chunk_terms`' results, the chunk axis
    leading. Gradients reach all five operands."""
    with _no_x64():
        return _forward(q, k, v, g, beta, heads=heads,
                        heads_step=_heads_step(heads),
                        interpret=pallas_interpret())


def _chunk_terms_fwd(q, k, v, g, beta, heads):
    return chunk_terms(q, k, v, g, beta, heads), (q, k, v, g, beta)


def _chunk_terms_bwd(heads, operands, d_terms):
    with _no_x64():
        return tuple(_backward(*operands, tuple(d_terms), heads=heads,
                               heads_step=_heads_step(heads),
                               interpret=pallas_interpret()))


chunk_terms.defvjp(_chunk_terms_fwd, _chunk_terms_bwd)
