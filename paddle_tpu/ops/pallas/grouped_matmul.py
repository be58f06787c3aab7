"""Grouped matrix products of the dropless experts path as Pallas TPU
kernels under one `jax.custom_vjp`: `grouped_dot(lhs [C, K], rhs [n, K, N],
sizes [n]) -> [C, N]`, row r of group g (the groups lie one after another
from row 0, `sizes[g]` rows each) times `rhs[g]`. What `jax.lax.ragged_dot`
computes, bfloat16 operands, float32 accumulation, the result in the
operands' dtype, with two differences that the caller has to know:

* **Only the row tiles that hold a group's rows are visited.** The grid has
  one step for every (group, row tile) pair in which the group has a row
  (`_steps`, a handful of integer operations on `sizes`; the grid's bound is
  that count, read on the device), so a chunk whose groups fill half of it
  costs half. A tile that a group's edge crosses is visited once for each
  group in it, and the rows of the other are masked.
* **Rows past the last group are never written** (in the result and in the
  gradient to `lhs`): they hold whatever the buffer held, NaN included. A
  caller selects them away (`jnp.where`), never multiplies them away. Inside
  the kernels nothing of such a row reaches a live result: the forward and
  the gradient to `lhs` are row by row, and the gradient to `rhs`, which
  contracts the rows, selects both operands' rows by group before the
  product.

Three kinds of call on two kernel bodies. Forward (`gmm`): a step's row tile
times its group's matrix, which stays whole in VMEM (its block index does
not change between the consecutive tiles of one group, so it is fetched once
a group, and the next group's arrives while this one's last tile is
multiplied); the contraction is not tiled, so there is no accumulator. The
gradient to `lhs` is the same kernel on `rhs.swapaxes(1, 2)` (XLA makes that
copy once a layer, outside the loop over the chunks; contracting the stack's
minor axis in the kernel instead kept eight more stacks alive in the Mellum
step, 15.06 GB against 14.53, PERF.md section 6, PR 33) with an optional
operand that is added row by row and gives its buffer to the result: the
products of one `lhs` (`rhs` a tuple: gate and up) sum their gradients to it
inside the second call. The gradient to `rhs` (`tgmm`) contracts a group's
rows, float32 sums in VMEM, written once a group, zeros for an empty one.
Where a group's whole matrix does not fit `VMEM_BUDGET_BYTES` its columns
are tiled (`_column_tile`) and the steps are walked once a column tile.

**Each distinct call is traced and lowered once a program.** `_gmm` and
`_tgmm`, which build the step tables and the `pallas_call`, are module-level
`jax.jit` functions whose static arguments are the tiles and the mode: the
sites of one shape (gate and up; every layer of a scan's period; the
backward's recompute) share one trace of the kernel body and one Mosaic
body in the lowered module, which every site calls and XLA inlines. A site
costs about 0.15 s of Python otherwise (body to jaxpr, jaxpr to Mosaic MLIR,
serialised), in every program of a set-up that holds the experts (PERF.md
section 6, PRs 33 and 34).

The row tile comes from the shapes alone (`row_tile`): 256 rows at the
Mellum cell's 65,536 rows over 16 groups, 128 at the GLM and Xing cells'.
`tile_counts` gives the steps of a grid and the tiles of a dense walk from
concrete group sizes, for PERF.md and the tests. On a TPU the kernels are
Mosaic-compiled; anywhere else they run in the interpreter
(`_core.device.pallas_interpret`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..._core.device import pallas_interpret
from ...observability.programs import mosaic_site
from .flash_attention import SCOPED_VMEM_BYTES, _no_x64

LANES = 128
ROW_TILES = (512, 256, 128)
# a grid step's fixed cost in rows of MXU work: the chip's sweep read 93, 90
# and 84 % of the MXU's peak on the rows visited at 512, 256 and 128 rows a
# step (PERF.md section 6, PR 33)
STEP_ROWS = 32
# what one call may take of a v5e core's 128 MiB of VMEM. A call asks for
# what it needs (`_gmm_bytes`, `_tgmm_bytes`, and `VMEM_MARGIN_BYTES` for
# what Mosaic adds: it took 1 to 2 MiB more at the three cells' shapes) and
# no more: XLA keeps arrays of 50 to 70 MB resident in VMEM between its own
# fusions, and a flat 64 MiB for every call took that room away (the GLM
# cell's sort, gathers and combine ran 13 ms a step slower, PERF.md section
# 6, PR 33)
VMEM_BUDGET_BYTES = 56 << 20
VMEM_MARGIN_BYTES = 4 << 20

_TN = (((0,), (0,)), ((), ()))      # a^T . b


# ------------------------------------------------------------- the tiling

def _gmm_bytes(tm: int, k: int, tn: int, itemsize: int, added: bool) -> int:
    """VMEM of the forward kernel at lhs [tm, k], rhs [k, tn]: the
    pipeline's two buffers of each block (the [tm, tn] result twice where a
    gradient is added to) and the float32 product before it is rounded."""
    return (2 * itemsize * (tm * k + k * tn + (2 if added else 1) * tm * tn)
            + 4 * tm * tn)


def _tgmm_bytes(tm: int, k: int, tn: int, itemsize: int) -> int:
    """VMEM of the kernel that contracts the rows: two buffers of each
    block, the float32 [k, tn] sums, and float32 copies of both row tiles
    where a group's edge is masked."""
    return (2 * itemsize * (tm * (k + tn) + k * tn) + 4 * k * tn
            + 4 * tm * (k + tn))


def row_tile(rows: int, groups: int) -> int:
    """Rows a grid step takes, from the shapes alone. A group of r rows
    that starts and ends anywhere costs r + tm rows of products (its two
    edge tiles are multiplied whole) and r / tm steps, each with a fixed
    cost of about `STEP_ROWS` rows' worth of MXU time: the sum is least at
    tm^2 = STEP_ROWS * r. A group's rows are data; what the shapes say is
    the rows the groups of a full chunk hold, rows / groups, and the
    caller's chunks are half full under balance (`ops/moe.chunk_count`), so
    the tile is the largest of 512, 256, 128 that divides `rows` with
    2 tm^2 <= STEP_ROWS * rows / groups, the smallest that divides them
    where none is as small: 256 at 65,536 rows over 16 groups, 128 at
    16,384 and at 4,096 over 8. Rows that none divides are one tile."""
    fit = [tm for tm in ROW_TILES if rows % tm == 0]
    if not fit:
        return rows
    return next((tm for tm in fit
                 if 2 * tm * tm * groups <= STEP_ROWS * rows), fit[-1])


def _column_tile(need, n: int) -> int:
    """Columns of a group's matrix a step holds: all `n` where `need(tn)`
    bytes fit `VMEM_BUDGET_BYTES` (the three cells: 17 to 36 MiB), else the
    widest whole number of 128 lanes that divides `n` and fits."""
    tiles = [n] + [tn for tn in range(n - LANES, 0, -LANES)
                   if n % LANES == 0 and n % tn == 0]
    for tn in tiles:
        if need(tn) + VMEM_MARGIN_BYTES <= VMEM_BUDGET_BYTES:
            return tn
    raise ValueError(
        f"grouped product: no column tile of {n} columns fits "
        f"{VMEM_BUDGET_BYTES >> 20} MiB of VMEM ({need(tiles[-1]) >> 20} "
        f"MiB at {tiles[-1]}): the contraction would have to be tiled")


@functools.partial(jax.jit, static_argnames=("rows", "tm", "visit_empty"))
def _steps(sizes, *, rows: int, tm: int, visit_empty: bool):
    """The grid's step tables from the group sizes: (group of step, row
    tile of step, each group's first row, each group's end, steps), the
    first two of `rows // tm + n - 1` entries, the most there can be. A
    group takes one step for every tile from the one its first row is in
    to the one its last row is in; an empty group none, or one (on a tile
    none of whose rows it owns) where `visit_empty`: the gradient to `rhs`
    has its zeros to write. Entries past `steps` repeat the last step."""
    n = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    last_tile = rows // tm - 1
    first = jnp.minimum(starts // tm, last_tile)
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                      1 if visit_empty else 0).astype(jnp.int32)
    step_end = jnp.cumsum(count, dtype=jnp.int32)
    steps = step_end[-1]
    at = jnp.minimum(jnp.arange(rows // tm + n - 1, dtype=jnp.int32),
                     jnp.maximum(steps - 1, 0))
    group = jnp.minimum((at[:, None] >= step_end[None, :]).sum(
        1, dtype=jnp.int32), n - 1)
    tile = jnp.clip(first[group] + at - (step_end - count)[group], 0,
                    last_tile)
    return group, tile, starts, ends, steps


def tile_counts(sizes, tm: int, rows: int):
    """(visited, dense) from concrete group sizes: the steps of the forward
    kernel's grid, one for every (group, row tile) pair in which the group
    has a row (a tile two groups share counts twice), and the `rows // tm`
    tiles a dense walk of the chunk visits. Under balance the Mellum cell's
    running chunk reads (128, 256) at 256 rows a tile; uneven groups of the
    same sum add at most a step for each group but the last."""
    sizes = np.asarray(sizes, np.int64)
    ends = np.cumsum(sizes)
    starts = ends - sizes
    visited = np.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)
    return int(visited.sum()), rows // tm


# ------------------------------------------------------------ the kernels

def _rows_of_group(group_ref, tile_ref, starts_ref, ends_ref, tm):
    """(whole, mask [tm, 1]): whether this step's tile lies inside its
    group, and which of its rows are the group's."""
    s = pl.program_id(1)
    g = group_ref[s]
    lo = tile_ref[s] * tm
    start, end = starts_ref[g], ends_ref[g]
    row = lo + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return ((start <= lo) & (lo + tm <= end),
            (row >= start) & (row < end))


def _gmm_kernel(group_ref, tile_ref, starts_ref, ends_ref, lhs_ref, rhs_ref,
                *rest, tm):
    """One step of the forward product (or of the gradient to `lhs`):
    this tile's rows times this group's matrix, added to the rows of
    `add_ref` where one is given, written where the rows are the group's."""
    add_ref, out_ref = rest if len(rest) == 2 else (None,) + rest
    whole, mask = _rows_of_group(group_ref, tile_ref, starts_ref, ends_ref,
                                 tm)
    out = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32)
    if add_ref is not None:
        out = out + add_ref[...].astype(jnp.float32)

    @pl.when(whole)
    def _():
        out_ref[...] = out.astype(out_ref.dtype)

    @pl.when(jnp.logical_not(whole))
    def _():
        out_ref[...] = jnp.where(mask, out, out_ref[...].astype(
            jnp.float32)).astype(out_ref.dtype)


def _tgmm_kernel(group_ref, tile_ref, starts_ref, ends_ref, lhs_ref, rhs_ref,
                 out_ref, acc_ref, *, tm):
    """One step of the gradient to `rhs`: this tile's rows of `lhs`,
    transposed, times its rows of `rhs`, summed in float32 over the steps
    of one group and written at its last step."""
    s = pl.program_id(1)
    g = group_ref[s]
    whole, mask = _rows_of_group(group_ref, tile_ref, starts_ref, ends_ref,
                                 tm)

    @pl.when((s == 0) | (group_ref[jnp.maximum(s - 1, 0)] != g))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(whole)
    def _():
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], _TN,
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_not(whole) & (ends_ref[g] > starts_ref[g]))
    def _():
        # a dead row may hold anything, and 0 * NaN is NaN: both operands
        lhs = jnp.where(mask, lhs_ref[...].astype(jnp.float32), 0)
        rhs = jnp.where(mask, rhs_ref[...].astype(jnp.float32), 0)
        acc_ref[...] += jax.lax.dot_general(
            lhs.astype(lhs_ref.dtype), rhs.astype(rhs_ref.dtype), _TN,
            preferred_element_type=jnp.float32)

    last = pl.num_programs(1) - 1

    @pl.when((s == last) | (group_ref[jnp.minimum(s + 1, last)] != g))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _by_tile(tm: int, width: int, columns: bool = False):
    """The [tm, width] block of a step's row tile: all of a row, or the
    grid's column tile of it."""
    return pl.BlockSpec((tm, width), lambda j, s, group, tile, *_: (
        tile[s], j if columns else 0))


def _by_group(k: int, tn: int):
    """The [k, tn] column tile of a step's group's matrix: the same block
    from one tile of a group to the next, so it is fetched once a group."""
    return pl.BlockSpec((None, k, tn),
                        lambda j, s, group, *_: (group[s], 0, j))


def _call(kernel, vmem, columns, tables, in_specs, out_spec, out_shape,
          interpret, *args, scratch=(), aliases=None):
    """One `pallas_call` over the column tiles and, inside each, the steps
    of `tables` (`_steps`), which are prefetched as scalars and bound the
    grid, with `vmem` bytes (and the margin) of scoped VMEM, never under
    Mosaic's default. `aliases` counts operands from the first table."""
    with mosaic_site(kernel, *args):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(columns, tables[4]),
                in_specs=in_specs, out_specs=out_spec,
                scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=max(vmem + VMEM_MARGIN_BYTES,
                                     SCOPED_VMEM_BYTES)),
            input_output_aliases=aliases or {},
            interpret=interpret)(*tables[:4], *args)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _gmm(lhs, rhs, sizes, add_to, *, tm, tn, interpret):
    """`gmm`'s tables and call; jitted, so that the sites of one shape share
    one trace of the kernel body and one Mosaic body a lowered program."""
    rows, n = lhs.shape[0], rhs.shape[2]
    more = () if add_to is None else (add_to,)
    return _call(
        functools.partial(_gmm_kernel, tm=tm),
        _gmm_bytes(tm, rhs.shape[1], tn, lhs.dtype.itemsize, bool(more)),
        n // tn, _steps(sizes, rows=rows, tm=tm, visit_empty=False),
        [_by_tile(tm, lhs.shape[1]), _by_group(rhs.shape[1], tn)]
        + [_by_tile(tm, tn, True)] * len(more), _by_tile(tm, tn, True),
        jax.ShapeDtypeStruct((rows, n), lhs.dtype), interpret, lhs, rhs,
        *more,
        # operand 6, after the four tables, lhs and rhs
        aliases={6: 0} if more else None)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _tgmm(lhs, rhs, sizes, *, tm, tn, interpret):
    """`tgmm`'s tables and call, jitted as `_gmm` is."""
    k, n = lhs.shape[1], rhs.shape[1]
    return _call(
        functools.partial(_tgmm_kernel, tm=tm),
        _tgmm_bytes(tm, k, tn, lhs.dtype.itemsize), n // tn,
        _steps(sizes, rows=lhs.shape[0], tm=tm, visit_empty=True),
        [_by_tile(tm, k), _by_tile(tm, tn, True)], _by_group(k, tn),
        jax.ShapeDtypeStruct((sizes.shape[0], k, n), lhs.dtype), interpret,
        lhs, rhs, scratch=[pltpu.VMEM((k, tn), jnp.float32)])


def gmm(lhs, rhs, sizes, tm: int, add_to=None):
    """lhs [C, K] x rhs [n, K, N] -> [C, N] by group. Rows of no group are
    not written. `add_to`, an array like the result, is added row by row
    and gives its buffer to the result (two gradients to one `lhs` are
    then one array and no pass of XLA's to add them)."""
    itemsize = lhs.dtype.itemsize
    tn = _column_tile(lambda tn: _gmm_bytes(tm, rhs.shape[1], tn, itemsize,
                                            add_to is not None),
                      rhs.shape[2])
    with _no_x64():
        return _gmm(lhs, rhs, sizes, add_to, tm=tm, tn=tn,
                    interpret=pallas_interpret())


def tgmm(lhs, rhs, sizes, tm: int):
    """lhs [C, K], rhs [C, N] -> [n, K, N]: for each group the product of
    its rows of `lhs`, transposed, with its rows of `rhs`; zeros for an
    empty group. float32 sums in VMEM, written in `lhs`'s dtype."""
    itemsize = lhs.dtype.itemsize
    tn = _column_tile(lambda tn: _tgmm_bytes(tm, lhs.shape[1], tn, itemsize),
                      rhs.shape[1])
    with _no_x64():
        return _tgmm(lhs, rhs, sizes, tm=tm, tn=tn,
                     interpret=pallas_interpret())


# ----------------------------------------------------------------- the op

@jax.custom_vjp
def _grouped(lhs, stacks, sizes):
    """lhs times each stack of `stacks` (a tuple of [n, K, N_i]), a tuple:
    products of one `lhs` share the gradient to it."""
    tm = row_tile(lhs.shape[0], sizes.shape[0])
    return tuple(gmm(lhs, rhs, sizes, tm) for rhs in stacks)


def _grouped_fwd(lhs, stacks, sizes):
    return _grouped(lhs, stacks, sizes), (lhs, stacks, sizes)


def _grouped_bwd(res, d_outs):
    lhs, stacks, sizes = res
    tm = row_tile(lhs.shape[0], sizes.shape[0])
    d_lhs = None
    for rhs, d_out in zip(stacks, d_outs):
        d_lhs = gmm(d_out.astype(lhs.dtype), rhs.swapaxes(1, 2), sizes, tm,
                    add_to=d_lhs)
    return d_lhs, tuple(tgmm(lhs, d_out.astype(lhs.dtype), sizes, tm)
                        for d_out in d_outs), None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_dot(lhs, rhs, sizes):
    """lhs [C, K], rhs [n, K, N], sizes [n] int32 (sum <= C) -> [C, N]:
    rows sizes[:g].sum() .. sizes[:g + 1].sum() - 1 times rhs[g]. `rhs` may
    be a tuple of stacks of one K: the result is then the tuple of the
    products, and their gradients to `lhs` are summed inside the kernels.
    Rows past the last group are left unwritten, in the result and in the
    gradient to `lhs`: select them away. Gradients reach lhs and rhs. The
    stacks are taken in `lhs`'s dtype; Mosaic has no 64-bit types, so
    float64 operands (x64 on a CPU) are multiplied in float32."""
    stacks = rhs if isinstance(rhs, tuple) else (rhs,)
    dtype = jnp.float32 if lhs.dtype == jnp.float64 else lhs.dtype
    out = _grouped(lhs.astype(dtype), tuple(r.astype(dtype) for r in stacks),
                   sizes.astype(jnp.int32))
    out = tuple(o.astype(lhs.dtype) for o in out)
    return out if isinstance(rhs, tuple) else out[0]
