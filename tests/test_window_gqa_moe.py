"""models/llama.py with its three optional mechanisms on (an explicit head
width with grouped queries, window and full layers in a period, a routed
feed-forward with a balance term) against the plain float32 reference
(benchmarks/reference/window_gqa_moe.py) on seeded weights, small and on
the CPU; and the chip's share tied to the model: the four shares of a
layer add up to the uncut layer."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import generator
from benchmarks.cells import load_cell
from benchmarks.reference import window_gqa_moe as ref
from paddle_tpu.models import blocks, llama

CELL = "mellum2-ep4share-pretrain-s4096"


@pytest.fixture(scope="module")
def tiny():
    """(the cell at its tiny sizes, the program's config, float32 masters,
    two check sequences and the same tiled to the batch)."""
    cell = load_cell(CELL, tiny=True)
    c = cell.runner.program_config(cell.config)
    master = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), llama.init_llama_params(c, 7))
    two, tiled = generator.make_check_batch(cell.traffic,
                                            cell.config["vocab_size"], 7)
    return cell, c, master, two, tiled


def test_the_tiny_sizes_keep_the_mechanisms(tiny):
    cell, c, *_ = tiny
    assert c.sliding_window < cell.traffic["seq"]
    assert c.kv_heads < c.num_heads
    assert c.num_heads * c.head_dim != 0 and c.head_dim == 16
    assert c.period == ("sliding_attention",) * 3 + ("full_attention",)
    assert c.held == (0, 4) and c.num_experts == 16


def test_loss_its_parts_and_every_gradient_against_the_reference(tiny):
    cell, c, master, two, tiled = tiny
    grad_fn = ref._grad_fn(cell.config, jnp.dtype("float32"))
    with jax.default_matmul_precision("highest"):
        want, want_lm, want_balance, want_pairs, want_grads = ref.batch_loss(
            grad_fn, master, two, cell.config)
        (got, aux), grads = jax.value_and_grad(functools.partial(
            llama.loss_parts, config=c, remat=True), has_aux=True)(
                master, *map(jnp.asarray, tiled))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(aux["lm"], want_lm, rtol=2e-6)
    np.testing.assert_allclose(aux["balance"], want_balance, rtol=2e-6)
    # the tiled batch holds each check sequence twice
    np.testing.assert_array_equal(aux["pairs"], 2 * np.asarray(want_pairs))
    assert abs(float(aux["balance"]) - 8.0) < 0.1     # near balance: k
    flat_want = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        w = flat_want[path]
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-6 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))
        assert float(jnp.abs(w).max()) > 0, jax.tree_util.keystr(path)


def test_the_first_update_against_the_reference(tiny):
    cell, c, master, two, tiled = tiny
    # a rate at which a float32 loss resolves the fall (the cell's 1e-6
    # moves it by a ten-thousandth at this size)
    opt = dict(cell.config["optimizer"], lr=1e-3)
    want = ref.check_step(jax.tree_util.tree_map(jnp.copy, master), two,
                          dict(cell.config, optimizer=opt))
    init_fn, step = llama.build_train_step(
        c, lr=opt["lr"], wd=opt["wd"], b1=opt["b1"], b2=opt["b2"])
    state = init_fn(7)
    with jax.default_matmul_precision("highest"):
        state, loss0 = step(state, *tiled)
        _, loss1 = step(state, *tiled)
    np.testing.assert_allclose(loss0, want["loss0"], rtol=2e-6)
    np.testing.assert_allclose(float(loss0) - float(loss1),
                               want["loss0"] - want["loss1"], rtol=2e-3)
    assert want["loss0"] > want["loss1"]


@pytest.mark.parametrize("how, moved", [
    (dict(window=None), "lm0"), (dict(group_of="modulo"), "lm0")],
    ids=["without_the_window", "kv_head_h_mod_4"])
def test_a_reference_of_another_model_reads_another_loss(tiny, how, moved):
    """The two wrong models the chip's limits must refuse: they are not
    this one at the tiny size either."""
    cell, _, master, two, _ = tiny
    grad_fn = ref._grad_fn(cell.config, jnp.dtype("float32"))
    wrong_fn = ref._grad_fn(cell.config, jnp.dtype("float32"), **how)
    right = ref.batch_loss(grad_fn, master, two, cell.config)
    wrong = ref.batch_loss(wrong_fn, master, two, cell.config)
    assert abs(right[1] - wrong[1]) > 1e-5 * right[1]


def test_the_four_shares_add_up_to_the_uncut_layer(tiny):
    """Each chip of the four routes over all 16 experts and computes its 4;
    the four partial sums are the uncut reference's routed layer."""
    cell, c, *_ = tiny
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    h, f, e = c.hidden_size, c.moe_intermediate_size, c.num_experts
    y = jax.random.normal(keys[0], (2, 64, h), jnp.float32)
    f32 = jnp.float32
    whole = {"router_w": jax.random.normal(keys[1], (h, e), f32) * 0.3,
             "experts": {
                 "gate_w": jax.random.normal(keys[2], (e, h, f), f32) * .1,
                 "up_w": jax.random.normal(keys[3], (e, h, f), f32) * .1,
                 "down_w": jax.random.normal(keys[4], (e, f, h), f32) * .1}}
    uncut = dict(cell.config, num_experts=e,
                 deployment=dict(cell.config["deployment"], experts_first=0))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.routed_ffn(row, whole, uncut)[0] for row in y])
        total, records = 0, []
        for first in range(0, e, 4):
            share = dataclasses.replace(c, experts_held=(first, 4))
            blk = dict(whole, experts=jax.tree_util.tree_map(
                lambda a: a[first:first + 4], whole["experts"]))
            part, record = llama._routed_ffn(y, blk, share)
            total, records = total + part, records + [record]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    # every share sees the same router: the same counts and balance term
    for record in records[1:]:
        np.testing.assert_array_equal(record["pairs"], records[0]["pairs"])
    assert int(records[0]["pairs"].sum()) == 2 * 64 * c.num_experts_per_tok


def test_scan_periods_is_the_loop_over_the_layers():
    """Two periods of (a, a, b): the scan over periods gives what the
    plain loop over the six layers gives, outputs stacked in layer order,
    with and without remat, and a period of one is `scan_layers`."""
    def kind(scale):
        return lambda x, layer: (x * layer["w"] + scale, (x * scale).sum())

    fns = [kind(1.0), kind(1.0), kind(-2.0)]
    stacked = {"w": jnp.arange(1.0, 7.0)[:, None] * jnp.ones((6, 3))}
    x0 = jnp.asarray([1.0, 2.0, 3.0])
    x, want = x0, []
    for n in range(6):
        x, y = fns[n % 3](x, {"w": stacked["w"][n]})
        want.append(y)
    for remat in (False, True):
        got_x, got_y = blocks.scan_periods(fns, x0, stacked, remat)
        np.testing.assert_allclose(got_x, x)
        np.testing.assert_allclose(got_y, jnp.stack(want))
    one_x, _ = blocks.scan_periods(fns[:1], x0, stacked, True)
    np.testing.assert_allclose(
        one_x, blocks.scan_layers(fns[0], x0, stacked, True)[0])


def _scan_periods_before(block_fns, x, stacked, remat):
    """`blocks.scan_periods` as it stood before a period's positions could
    carry parameter trees of their own (PR 37), word for word."""
    size = len(block_fns)
    if size == 1:
        return blocks.scan_layers(block_fns[0], x, stacked, remat)
    if remat:
        block_fns = [jax.checkpoint(fn) for fn in block_fns]

    def period(x, layers):
        ys = []
        for i, fn in enumerate(block_fns):
            x, y = fn(x, jax.tree_util.tree_map(lambda a: a[i], layers))
            ys.append(y)
        return x, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)

    x, ys = jax.lax.scan(period, x, jax.tree_util.tree_map(
        lambda a: a.reshape((-1, size) + a.shape[1:]), stacked))
    return x, jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), ys)


@pytest.mark.parametrize("remat", [False, True])
def test_a_period_of_like_layers_gives_what_it_gave(tiny, remat):
    """The family that shares `scan_periods` with the layers of two kinds:
    on its tiny configuration (three window layers to a full one, one
    stacked tree) the values, the routers' records and every gradient are
    the former function's, bit for bit, and so is the lowered text."""
    _, c, master, _, tiled = tiny
    x0 = master["wte"][jnp.asarray(tiled[0])]
    fns = [functools.partial(llama._block, config=c, kind=kind)
           for kind in c.period]

    def run(scan, blocks_, x):
        x, records = scan(fns, x, blocks_, remat)
        return (x * x).mean() + records["balance"].sum(), (x, records)

    got, want = (jax.value_and_grad(functools.partial(run, scan),
                                    argnums=(0, 1), has_aux=True)
                 for scan in (blocks.scan_periods, _scan_periods_before))
    assert jax.jit(got).lower(master["blocks"], x0).as_text() \
        == jax.jit(want).lower(master["blocks"], x0).as_text().replace(
            "_scan_periods_before", "scan_periods")
    (g_loss, g_aux), g_grads = got(master["blocks"], x0)
    (w_loss, w_aux), w_grads = want(master["blocks"], x0)
    for a, b in zip(jax.tree_util.tree_leaves((g_loss, g_aux, g_grads)),
                    jax.tree_util.tree_leaves((w_loss, w_aux, w_grads))):
        np.testing.assert_array_equal(a, b)


def test_a_period_whose_positions_have_trees_of_their_own():
    """Two periods of (a, b) where a and b take DIFFERENT parameters: a
    list with one tree a position, each stacked over the periods, gives
    what the plain loop over the four layers gives, values and gradients,
    with each position's outputs apart; and a list that is not one tree a
    position is refused."""
    def a(x, layer):
        return x * layer["w"], x.sum()

    def b(x, layer):
        return x + layer["u"] @ layer["v"], {"norm": (x * x).sum()}

    trees = [{"w": jnp.asarray([[1.5, 2.0, 0.5], [0.25, 1.0, 3.0]])},
             {"u": jnp.arange(12.0).reshape(2, 3, 2) / 7,
              "v": jnp.asarray([[1.0, -1.0], [0.5, 2.0]])}]
    x0 = jnp.asarray([1.0, 2.0, 3.0])

    def loop(trees, x):
        ys = ([], [])
        for n in range(2):
            for i, fn in enumerate((a, b)):
                x, y = fn(x, jax.tree_util.tree_map(lambda t: t[n],
                                                    trees[i]))
                ys[i].append(y)
        return (x * x).sum(), (x, ys)

    (_, (want_x, want_ys)), want_g = jax.value_and_grad(
        loop, has_aux=True)(trees, x0)
    for remat in (False, True):
        def scanned(trees, x):
            x, ys = blocks.scan_periods([a, b], x, trees, remat)
            return (x * x).sum(), (x, ys)
        (_, (x, ys)), grads = jax.value_and_grad(scanned, has_aux=True)(
            trees, x0)
        np.testing.assert_allclose(x, want_x, rtol=1e-6)
        np.testing.assert_allclose(ys[0], jnp.stack(want_ys[0]), rtol=1e-6)
        np.testing.assert_allclose(
            ys[1]["norm"], jnp.stack([y["norm"] for y in want_ys[1]]),
            rtol=1e-6)
        for g, w in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want_g)):
            np.testing.assert_allclose(g, w, rtol=1e-6)
    with pytest.raises(ValueError, match="2 layers in a period and 1"):
        blocks.scan_periods([a, b], x0, trees[:1], False)
    # one position with a tree of its own is still a list
    x, (ys,) = blocks.scan_periods([a], x0, trees[:1], True)
    np.testing.assert_allclose(x, x0 * trees[0]["w"][0] * trees[0]["w"][1])
    assert ys.shape == (2,)


def test_the_period_is_the_shortest_run_the_stack_repeats():
    full, window = llama.FULL, llama.WINDOW
    def cfg(kinds):
        return llama.LlamaConfig(num_layers=len(kinds), layer_types=kinds,
                                 sliding_window=8)
    assert cfg((window, window, window, full) * 7).period == (
        window, window, window, full)
    assert cfg((window, full, full)).period == (window, full, full)
    assert llama.LlamaConfig(num_layers=5).period == (full,)
    with pytest.raises(ValueError, match="layer_types"):
        llama.LlamaConfig(num_layers=3, layer_types=(full, full))
    with pytest.raises(ValueError, match="sliding_window"):
        llama.LlamaConfig(num_layers=1, layer_types=(window,))


def test_each_kind_rotates_by_its_own_table(tiny):
    _, c, *_ = tiny
    theta, inv_freq, factor = llama._rotary(c, llama.WINDOW)
    assert (theta, inv_freq, factor) == (500000, None, None)
    theta, inv_freq, factor = llama._rotary(c, llama.FULL)
    assert factor == 1.2772588722239782
    np.testing.assert_allclose(factor, 0.1 * np.log(16) + 1)
    want, want_factor = ref.inv_freq(c.head_dim,
                                     c.rope_parameters[llama.FULL])
    np.testing.assert_allclose(inv_freq, want, rtol=1e-6)
    assert want_factor == factor
    # cos and sin carry the factor: the rotated vector's norm is scaled
    x = jnp.ones((1, 8, 2, c.head_dim), jnp.float32)
    plain = blocks.rope(x, theta, inv_freq)
    np.testing.assert_allclose(blocks.rope(x, theta, inv_freq, factor),
                               plain * factor, rtol=1e-6)


def test_moves_of_the_routers_alone_lower_the_balance_term(tiny):
    cell, c, master, _, tiled = tiny
    init_fn, _ = llama.build_train_step(c)
    state = init_fn(7)
    move = jax.jit(functools.partial(llama.move_routers_only, config=c,
                                     rate=0.003))
    facts = jax.jit(functools.partial(llama.step_facts, config=c))
    before = float(facts(state["params"], *tiled)["balance"])
    moved = state
    for _ in range(5):
        moved = move(moved, *tiled)
    after = float(facts(moved["params"], *tiled)["balance"])
    assert after < before
    changed = jax.tree_util.tree_map(
        lambda a, b: bool((a != b).any()), state, moved)
    for name in ("params", "master"):
        assert changed[name]["blocks"].pop("router_w")
        assert not any(jax.tree_util.tree_leaves(changed[name]))
    assert not any(jax.tree_util.tree_leaves(
        [changed["m"], changed["v"], changed["step"]]))


def test_the_dense_presets_compile_none_of_it():
    """No router, no window and one rotary table in a dense preset's step:
    its jaxpr names neither stage of the routed feed-forward."""
    c = dataclasses.replace(llama.LLAMA_CONFIGS["llama-tiny"], num_layers=2)
    params = jax.eval_shape(lambda: llama.init_llama_params(c, 0))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    text = str(jax.make_jaxpr(functools.partial(
        llama.llama_loss, config=c))(params, tokens, tokens))
    assert "top_k" not in text and "ragged_dot" not in text
    assert set(params["blocks"]) == {"ln1_g", "q_w", "k_w", "v_w", "o_w",
                                     "ln2_g", "gate_w", "up_w", "down_w"}
    assert llama.count_params(c)["total"] == sum(
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(params))


def test_every_instruction_of_the_routed_step_says_its_stage():
    """The stages as they are: the router's product, softmax, top-k,
    weights and balance term under `moe_router`, the held experts under
    `moe_experts`, the feed-forward's norm and residual add under `mlp`,
    in every direction the checkpointed layers have; what stands under no
    stage is the period scan's plumbing (a layer's slice of the stacked
    parameters, its gradient's pad and sum, the calls)."""
    import re

    from benchmarks.layer_metrics import _stages
    from benchmarks.runners import _trainer
    from paddle_tpu.models import stages
    cell = load_cell(CELL, tiny=True)
    lowered, _, _ = _trainer.lower_step(cell, jax.devices()[:1])
    text = lowered.compile().as_text()
    placed = {path: _stages.place(path, stages)
              for op_name in _stages.op_names(text).values()
              for path in op_name.split(";")
              if path.startswith("jit(step_fn)/")}
    block = stages.BLOCK + (stages.ROUTER, stages.EXPERTS)
    want = {(s, d) for s in block for d in ("forward", "remat", "backward")}
    want |= {(s, d) for s in (stages.EMBED, stages.LOSS_HEAD)
             for d in ("forward", "backward")} | {(stages.OPTIMIZER, "update")}
    assert {found for found in placed.values() if found[0]} == want
    unscoped = {re.sub(r"^jit\(step_fn\)/(?:transpose\(jvp\(\)\)|jvp\(\))/",
                       "", path)
                for path, (stage, _) in placed.items() if stage is None}
    plumbing = re.compile(r"^while/body/closed_call(?:/(?:remat2|checkpoint"
                          r"|slice|squeeze|pad|add_any|reduce_sum"
                          r"|broadcast_in_dim|concatenate|reshape))?$"
                          r"|^(?:broadcast_in_dim|reshape|while(?:/cond/lt"
                          r"|/body/(?:add|sub|lt|select_n|dynamic_slice"
                          r"|squeeze|dynamic_update_slice"
                          r"|broadcast_in_dim))?)$")
    assert [p for p in sorted(unscoped) if not plumbing.match(p)] == []
    # the balance term's mean and its weight are the router's too
    balance = [p for p in placed if p.endswith("/mul")
               and f"({stages.ROUTER})" in p]
    assert balance


# ------------------------------------- the options ISSUE 39 added, left out

def test_a_configuration_without_the_new_keys_builds_the_parents_step():
    """`heads_by_kind`, `gating`, `first_k_dense`, a shared expert and the
    sigmoid router left out: the parameter tree is the one stacked tree,
    and the jaxpr of the Mellum cell's tiny step and of the dense preset's
    loss are the parent's (06f4fa5) to the character, by their sha256."""
    import hashlib
    cell = load_cell(CELL, tiny=True)
    c = cell.runner.program_config(cell.config)
    assert c.segments is None and not c.gating and c.router == "softmax"
    init_fn, step, _ = cell.runner.build(cell.config, None, {})
    state = jax.eval_shape(lambda: init_fn(0))
    assert set(state["params"]) == {"wte", "blocks", "lnf_g", "lm_head"}
    assert set(state["params"]["blocks"]) == {
        "ln1_g", "q_w", "k_w", "v_w", "o_w", "ln2_g", "router_w", "experts"}
    tokens = jax.ShapeDtypeStruct(
        (cell.traffic["batch"], cell.traffic["seq"]), jnp.int32)
    text = str(jax.make_jaxpr(step)(state, tokens, tokens))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "8b73aeb3ccf23ee7"
    dense = dataclasses.replace(llama.LLAMA_CONFIGS["llama-tiny"],
                                num_layers=2)
    params = jax.eval_shape(lambda: llama.init_llama_params(dense, 0))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    text = str(jax.make_jaxpr(functools.partial(
        llama.llama_loss, config=dense))(params, tokens, tokens))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "ca35969d877eadde"


def test_partial_rotary_turns_the_first_components_and_keeps_the_rest():
    """`blocks.rope` with a rotary width under the head's: the first
    `rotary_dim` components rotated, halves paired within them, as a head
    of that width would be; the rest passed through."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 2, 32))
    inv = blocks.yarn_inv_freq(16, 500000.0, {
        "factor": 64, "original_max_position_embeddings": 4096,
        "beta_fast": 64, "beta_slow": 1})
    got = blocks.rope(x, 500000.0, inv, 1.4, rotary_dim=16)
    np.testing.assert_array_equal(got[..., 16:], x[..., 16:])
    np.testing.assert_allclose(
        got[..., :16], blocks.rope(x[..., :16], 500000.0, inv, 1.4),
        rtol=1e-6)
    np.testing.assert_array_equal(blocks.rope(x, 10.0, rotary_dim=32),
                                  blocks.rope(x, 10.0))


def test_leading_dense_layers_and_heads_by_kind_make_segments():
    full, window = llama.FULL, llama.WINDOW
    kinds = (full,) + (window, window, window, full) * 2 + (window,)
    c = llama.LlamaConfig(
        num_layers=10, layer_types=kinds, sliding_window=8, num_heads=8,
        num_kv_heads=2, heads_by_kind={window: 12}, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=16, first_k_dense=1,
        router="sigmoid")
    assert (c.heads(full), c.heads(window)) == (8, 12)
    assert c.segments == [("dense", (full,), False, 1),
                          ("sparse", (window,) * 3 + (full,), True, 2),
                          ("tail", (window,), True, 1)]
    params = jax.eval_shape(lambda: llama.init_llama_params(c, 0))
    assert [len(params[g]) for g in ("dense", "sparse", "tail")] == [1, 4, 1]
    d = c.head_dim
    assert params["sparse"][0]["q_w"].shape[1:] == (c.hidden_size, 12 * d)
    assert params["sparse"][3]["q_w"].shape == (2, c.hidden_size, 8 * d)
    assert "router_b" in params["tail"][0] and "gate_w" in params["dense"][0]
    mask = llama.wd_mask(c)
    assert mask["sparse"][0]["router_w"] and not mask["sparse"][0]["router_b"]
    with pytest.raises(ValueError, match="router"):
        llama.LlamaConfig(router="softplus")
    with pytest.raises(ValueError, match="leading dense"):
        llama.LlamaConfig(first_k_dense=1)
    with pytest.raises(ValueError, match="K/V heads"):
        llama.LlamaConfig(num_heads=8, num_kv_heads=2,
                          heads_by_kind={full: 7})
