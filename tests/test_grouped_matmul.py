"""The grouped products of the dropless experts path
(`ops/pallas/grouped_matmul.py`) in the Pallas interpreter: the three kinds
of call against a plain per-group einsum in float32, `jax.grad` of
`held_experts_ffn` through them against a dense reference, the step tables
and the counter against hand counts, the shape rules at the three sparse
cells' shapes, and that the sites of one shape share one trace."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import moe as moe_ops
from paddle_tpu.ops.pallas import grouped_matmul as gm

ROWS, K, N, TILE = 512, 256, 128, 128
# group sizes over 512 rows in tiles of 128 (or 256)
SIZES = {
    "on_a_tile_edge": [128, 128, 128, 128],
    "uneven": [37, 201, 150, 124],
    "one_row_past_an_edge": [129, 127, 128, 128],
    "an_empty_group_first": [0, 130, 126, 200],
    "an_empty_group_in_the_middle": [128, 0, 129, 255],
    "an_empty_group_last": [100, 60, 90, 0],
    "nothing_held": [0, 0, 0, 0],
    "every_row_held": [0, 512, 0, 0],
}


def _operands(dtype=jnp.bfloat16):
    keys = jax.random.split(jax.random.PRNGKey(34), 4)
    lhs = jax.random.normal(keys[0], (ROWS, K), jnp.float32).astype(dtype)
    rhs = (jax.random.normal(keys[1], (4, K, N), jnp.float32) * 0.1).astype(
        dtype)
    d_out = jax.random.normal(keys[2], (ROWS, N), jnp.float32).astype(dtype)
    first = jax.random.normal(keys[3], (ROWS, K), jnp.float32).astype(dtype)
    return lhs, rhs, d_out, first


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


def _group_of_row(sizes):
    """[ROWS] the group each row is in, len(sizes) for a row of none."""
    return np.searchsorted(np.cumsum(sizes), np.arange(ROWS), side="right")


def _plain(kind, sizes, lhs, rhs, d_out, first):
    """The product as one einsum a group in float32: (what the kernel
    gives, the rows of it that are written)."""
    group = _group_of_row(sizes)
    live = group < len(sizes)
    own = np.minimum(group, len(sizes) - 1)
    lhs, rhs, d_out, first = map(_f32, (lhs, rhs, d_out, first))
    if kind == "forward":
        return np.einsum("rk,rkn->rn", lhs, rhs[own]), live
    if kind == "dgrad":
        return np.einsum("rn,rkn->rk", d_out, rhs[own]), live
    if kind == "dgrad_accumulate":
        return first + np.einsum("rn,rkn->rk", d_out, rhs[own]), live
    one_hot = (group[:, None] == np.arange(len(sizes))).astype(np.float32)
    return np.einsum("rg,rk,rn->gkn", one_hot, lhs, d_out), slice(None)


def _kernel(kind, sizes, tile, lhs, rhs, d_out, first):
    sizes = jnp.asarray(sizes, jnp.int32)
    if kind == "forward":
        return gm.gmm(lhs, rhs, sizes, tile)
    if kind == "wgrad":
        return gm.tgmm(lhs, d_out, sizes, tile)
    return gm.gmm(d_out, rhs.swapaxes(1, 2), sizes, tile,
                  add_to=first if kind == "dgrad_accumulate" else None)


@pytest.mark.parametrize("tile", [128, 256])
@pytest.mark.parametrize("kind", ["forward", "dgrad", "dgrad_accumulate",
                                  "wgrad"])
@pytest.mark.parametrize("case", list(SIZES))
def test_each_kind_is_a_plain_product_a_group(case, kind, tile):
    operands = _operands()
    got = _kernel(kind, SIZES[case], tile, *operands)
    want, written = _plain(kind, SIZES[case], *operands)
    assert got.dtype == jnp.bfloat16 and got.shape == want.shape
    # bfloat16 operands, a float32 sum rounded once to bfloat16
    np.testing.assert_allclose(_f32(got)[written], want[written], rtol=1e-2,
                               atol=2e-2)
    if kind == "wgrad":
        empty = np.array(SIZES[case]) == 0
        assert not _f32(got)[empty].any()


@pytest.mark.parametrize("poisoned", ["lhs", "cotangent", "both"])
@pytest.mark.parametrize("case", ["an_empty_group_last",
                                  "one_row_past_an_edge"])
def test_a_poisoned_dead_tail_reaches_nothing(case, poisoned):
    """NaN in the rows past the last group, in `lhs` and in the incoming
    cotangent, leaves every live row of the result and of the gradient to
    `lhs`, and all of the gradient to `rhs`, as they were."""
    sizes = np.array(SIZES[case])
    sizes[-2] -= 40                       # a tail inside the last live tile
    live = (np.arange(ROWS) < sizes.sum())[:, None]
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs, rhs, d_out, _ = _operands()

    def everything(lhs, d_out):
        out, vjp = jax.vjp(lambda l, w: gm.grouped_dot(l, w, sizes), lhs, rhs)
        return (out,) + vjp(d_out)

    clean = everything(lhs, jnp.where(live, d_out, 0))
    nan = jnp.asarray(jnp.nan, lhs.dtype)
    got = everything(
        jnp.where(live, lhs, nan) if poisoned != "cotangent" else lhs,
        jnp.where(live, d_out, nan if poisoned != "lhs" else 0))
    for g, c, rows in zip(got, clean, (live[:, 0], live[:, 0], slice(None))):
        assert np.isfinite(_f32(g)[rows]).all()
        np.testing.assert_array_equal(_f32(g)[rows], _f32(c)[rows])


@pytest.mark.parametrize("case", ["one_row_past_an_edge", "uneven"])
def test_products_of_one_lhs_sum_their_gradients_to_it(case):
    """`rhs` a tuple (gate and up): the results are the single products',
    and the gradient to `lhs` is their gradients' sum, taken inside the
    second call in the first one's buffer."""
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    lhs, rhs, d_out, _ = _operands()
    pair = (rhs, rhs[::-1] * 0.5)
    out, vjp = jax.vjp(lambda l, ws: gm.grouped_dot(l, ws, sizes), lhs, pair)
    d_lhs, d_pair = vjp((d_out, -d_out))
    for stack, o, d, d_stack in zip(pair, out, (d_out, -d_out), d_pair):
        args = (lhs, stack, d, lhs)
        for got, kind in ((o, "forward"), (d_stack, "wgrad")):
            want, _ = _plain(kind, SIZES[case], *args)
            np.testing.assert_allclose(_f32(got), want, rtol=1e-2, atol=2e-2)
    want = sum(_plain("dgrad", SIZES[case], lhs, stack, d, lhs)[0]
               for stack, d in zip(pair, (d_out, -d_out)))
    np.testing.assert_allclose(_f32(d_lhs), want, rtol=2e-2, atol=4e-2)


def test_float32_operands_go_through_the_kernels_too():
    operands = _operands(jnp.float32)
    for kind in ("forward", "dgrad_accumulate", "wgrad"):
        got = _kernel(kind, SIZES["uneven"], TILE, *operands)
        want, _ = _plain(kind, SIZES["uneven"], *operands)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kind", ["forward", "dgrad_accumulate", "wgrad"])
def test_a_matrix_past_the_vmem_budget_is_walked_in_column_tiles(
        monkeypatch, kind):
    """With room for half a group's matrix the grid has two column tiles,
    each walking the steps, and the product is the same; with room for
    none of its 128-lane tiles the call says so."""
    operands = _operands()
    lhs, rhs = operands[:2]
    rhs = jnp.concatenate([rhs, rhs * 0.5], axis=2)          # N = 256
    d_out = jnp.concatenate([operands[2]] * 2, axis=1)
    if kind == "dgrad_accumulate":      # the columns tiled are lhs's K
        need = gm._gmm_bytes(TILE, 2 * N, K // 2, 2, True)
    elif kind == "forward":
        need = gm._gmm_bytes(TILE, K, N, 2, False)
    else:
        need = gm._tgmm_bytes(TILE, K, N, 2)
    monkeypatch.setattr(gm, "VMEM_BUDGET_BYTES", need + gm.VMEM_MARGIN_BYTES)
    args = (lhs, rhs, d_out, operands[3])
    got = _kernel(kind, SIZES["uneven"], TILE, *args)
    want, written = _plain(kind, SIZES["uneven"], *args)
    np.testing.assert_allclose(_f32(got)[written], want[written], rtol=1e-2,
                               atol=2e-2)
    monkeypatch.setattr(gm, "VMEM_BUDGET_BYTES", gm.VMEM_MARGIN_BYTES + 1)
    with pytest.raises(ValueError, match="no column tile"):
        _kernel(kind, SIZES["uneven"], TILE, *args)


@pytest.mark.parametrize("held, experts", [((2, 2), 8), ((0, 4), 4)],
                         ids=["a_quarter_held_two_chunks", "all_held"])
def test_held_experts_ffn_gradients_are_a_dense_references(held, experts):
    """`jax.grad` of `held_experts_ffn` through the kernels (chunks of 128
    rows, one of them skipped where a quarter is held) against `jax.grad`
    of every held expert's SwiGLU run densely on every token in float32
    and masked by the routing."""
    t, hidden, width, k = 128, 128, 256, 2
    keys = jax.random.split(jax.random.PRNGKey(35), 7)
    bf16 = jnp.bfloat16
    x = jax.random.normal(keys[0], (t, hidden), jnp.float32).astype(bf16)
    ids = jax.random.randint(keys[1], (t, k), 0, experts).astype(jnp.int32)
    weights = jax.random.uniform(keys[2], (t, k), jnp.float32)
    stack = {name: (jax.random.normal(key, shape, jnp.float32) * 0.1).astype(
        bf16) for name, key, shape in (
            ("gate_w", keys[3], (held[1], hidden, width)),
            ("up_w", keys[4], (held[1], hidden, width)),
            ("down_w", keys[5], (held[1], width, hidden)))}
    d_out = jax.random.normal(keys[6], (t, hidden), jnp.float32)

    def through_kernels(x, weights, stack):
        out = moe_ops.held_experts_ffn(x, ids, weights, stack, held, experts)
        return (out.astype(jnp.float32) * d_out).sum()

    def dense(x, weights, stack):
        x = x.astype(jnp.float32)
        s = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), stack)
        gate = jnp.einsum("tm,emf->etf", x, s["gate_w"])
        up = jnp.einsum("tm,emf->etf", x, s["up_w"])
        each = jnp.einsum("etf,efm->etm", jax.nn.silu(gate) * up,
                          s["down_w"])
        chose = ids[None] == (held[0] + jnp.arange(held[1]))[:, None, None]
        share = (chose * weights[None]).sum(-1)               # [held, T]
        return ((share[..., None] * each).sum(0) * d_out).sum()

    got = jax.grad(through_kernels, argnums=(0, 1, 2))(x, weights, stack)
    want = jax.grad(dense, argnums=(0, 1, 2))(x, weights, stack)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all()
        # bfloat16 activations between the three products
        np.testing.assert_allclose(g, w, rtol=5e-2,
                                   atol=3e-2 * np.abs(w).max())


@pytest.mark.parametrize("sizes, tile, rows, want", [
    # group 0 owns tile 0 and a row of tile 1, group 1 the rest of tile 1,
    # group 3 tiles 2 and 3 and 44 rows of tile 4; tiles 5 to 7 are dead
    ([129, 127, 0, 300], 128, 1024, (6, 8)),
    ([129, 127, 0, 300], 256, 1024, (4, 4)),
    ([0, 512, 0, 0], 128, 512, (4, 4)),
    ([0, 0, 0, 5], 256, 512, (1, 2)),
    ([0, 0, 0, 0], 128, 512, (0, 4)),
    # the three cells' running chunk under balance: half of its tiles
    ([2048] * 16, 256, 65536, (128, 256)),
    ([1024] * 8, 128, 16384, (64, 128)),
    ([256] * 8, 128, 4096, (16, 32)),
    # one row more in the first group moves every later edge off the tiles
    ([2049] + [2048] * 15, 256, 65536, (128 + 16, 256)),
])
def test_tile_counts_against_a_hand_count(sizes, tile, rows, want):
    assert gm.tile_counts(sizes, tile, rows) == want


@pytest.mark.parametrize("cell, rows, groups, tile", [
    ("mellum", 65536, 16, 256), ("glm", 16384, 8, 128),
    ("xing", 4096, 8, 128)])
@pytest.mark.parametrize("seed", range(3))
def test_uneven_loads_of_a_cells_balance_add_at_most_a_step_a_group(
        cell, rows, groups, tile, seed):
    """The three cells' running chunk, half full: groups of the balanced
    sum and any sizes visit the balanced count of tiles and at most one
    more for each group but the last."""
    assert gm.row_tile(rows, groups) == tile
    balanced = rows // 2 // tile
    rng = np.random.default_rng(seed)
    sizes = rng.multinomial(rows // 2, rng.dirichlet(np.full(groups, 20.0)))
    visited, dense = gm.tile_counts(sizes, tile, rows)
    assert dense == 2 * balanced
    assert balanced <= visited <= balanced + groups - 1


@pytest.mark.parametrize("visit_empty", [False, True])
@pytest.mark.parametrize("tile", [128, 256])
@pytest.mark.parametrize("case", list(SIZES))
def test_the_kernels_grid_is_what_tile_counts_says(case, tile, visit_empty):
    """The step tables the kernels are built with: as many steps as
    `tile_counts` visits (and one more for each empty group where the
    gradient to `rhs` visits them), each on a tile in which its group has
    a row, groups and tiles never going back."""
    sizes = np.array(SIZES[case])
    group, tile_of, starts, ends, steps = map(np.asarray, gm._steps(
        jnp.asarray(sizes, jnp.int32), rows=ROWS, tm=tile,
        visit_empty=visit_empty))
    visited, dense = gm.tile_counts(sizes, tile, ROWS)
    empty = int((sizes == 0).sum()) if visit_empty else 0
    assert steps == visited + empty
    assert len(group) == len(tile_of) == dense + len(sizes) - 1
    np.testing.assert_array_equal(ends, np.cumsum(sizes))
    np.testing.assert_array_equal(starts, np.cumsum(sizes) - sizes)
    group, tile_of = group[:steps], tile_of[:steps]
    assert (np.diff(group) >= 0).all() and (np.diff(tile_of) >= 0).all()
    assert len(set(zip(group, tile_of))) == steps
    for g, t in zip(group, tile_of):
        if sizes[g]:
            assert starts[g] < (t + 1) * tile and t * tile < ends[g]
    if visit_empty:
        assert set(group) == set(range(len(sizes)))


# a chunk's rows, hidden, expert width, experts held: the three sparse cells
CELLS = {"mellum": (65536, 2304, 896, 16), "glm": (16384, 2048, 1536, 8),
         "xing": (4096, 3584, 1024, 8)}


@pytest.mark.parametrize("down", [False, True], ids=["gate_up", "down"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_the_shape_rules_tile_the_three_cells(cell, down):
    """The row tile PR 33's sweep on the chip chose, and every kernel of a
    product with a group's whole matrix resident inside the budget."""
    rows, k, n, held = CELLS[cell]
    if down:
        k, n = n, k
    tile = gm.row_tile(rows, held)
    assert tile == {"mellum": 256, "glm": 128, "xing": 128}[cell]
    for need, width in (
            (lambda tn: gm._gmm_bytes(tile, k, tn, 2, False), n),
            (lambda tn: gm._gmm_bytes(tile, n, tn, 2, True), k),
            (lambda tn: gm._tgmm_bytes(tile, k, tn, 2), n)):
        assert gm._column_tile(need, width) == width


def test_the_row_tile_grows_with_the_rows_a_group_holds():
    tiles = [gm.row_tile(65536, groups) for groups in (256, 64, 32, 16, 8, 4,
                                                       1)]
    assert tiles == [128, 128, 128, 256, 256, 512, 512]
    # rows that no tile divides are one tile; the smallest that does
    assert gm.row_tile(200, 4) == 200
    assert gm.row_tile(384, 1) == 128


def test_the_sites_of_one_shape_share_one_trace_of_the_kernel():
    """Two layers' worth of calls on one shape under one outer trace: the
    jitted entry is traced once (one `pallas_call` in its cached jaxpr),
    and every site is a call of it."""
    lhs, rhs, _, _ = _operands()
    sizes = jnp.asarray(SIZES["uneven"], jnp.int32)

    def two_layers(lhs, rhs):
        gate, up = gm.grouped_dot(lhs, (rhs, rhs * 0.5), sizes)
        again = gm.grouped_dot(lhs + 1, rhs * 2.0, sizes)
        return gate + up + again

    before = gm._gmm._cache_size()
    jaxpr = jax.make_jaxpr(two_layers)(lhs, rhs)
    assert gm._gmm._cache_size() <= before + 1
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "custom_vjp_call"]
    assert len(calls) == 2
    text = str(jaxpr)
    assert text.count("name=_gmm") == 3 and "ragged_dot" not in text
