"""What ISSUE 30 adds to the latent-attention sparse-expert family
(models/mla_moe.py: the plain residual path, the multi-token-prediction
module, the load-driven selection bias through the trainer's
`state_update`) against the plain float32 reference
(benchmarks/reference/mla_moe_mtp.py), at small sizes on the CPU with
seeded weights."""
import dataclasses
import functools
import importlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.cells import load_cell
from benchmarks.layer_metrics import _stages
from benchmarks.reference import mla_moe_mtp as ref
from benchmarks.runners import mla_moe as streams_runner
from benchmarks.runners import mla_moe_mtp as runner
from paddle_tpu.models import mla_moe as m
from paddle_tpu.models import stages, trainer

CELL = "glm47f-ep8share-pretrain-s2048"
BATCH, SEQ = 2, 64


@pytest.fixture(scope="module")
def tiny():
    """(configuration dict, program config): the cell's `tiny` cut."""
    config = load_cell(CELL, tiny=True).config
    return config, runner.program_config(config)


@pytest.fixture(scope="module")
def batch(tiny):
    ids = np.random.default_rng(0).integers(
        0, tiny[0]["vocab_size"], (BATCH, SEQ + 1), dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def _away_from_the_start(params, seed=11):
    """Every gain off 1 and every bias off 0, so that none hides."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        a + 0.05 * jax.random.normal(k, a.shape, a.dtype)
        for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def both(tiny, batch):
    """((L_main, L_mtp), gradients of the step's scalar) of the program,
    and the same of the reference."""
    config, c = tiny
    params = _away_from_the_start(m.init_mla_moe_params(c, 3))

    def program(p):
        main, mtp, _ = m.loss_parts(p, *batch, c, remat=True)
        return main + c.mtp_loss_weight * mtp, (main, mtp)

    (_, parts_p), grads_p = jax.jit(jax.value_and_grad(
        program, has_aux=True))(params)
    _, grads_r, main_r, mtp_r, _ = ref.train_step(
        jax.tree_util.tree_map(jnp.copy, params), batch, config)
    return (parts_p, grads_p), ((main_r, mtp_r), grads_r)


def _leaf_paths():
    config = load_cell(CELL, tiny=True).config
    shapes = jax.eval_shape(
        lambda: m.init_mla_moe_params(runner.program_config(config), 0))
    return [jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(shapes)]


@pytest.mark.parametrize("part", [0, 1], ids=["L_main", "L_mtp"])
def test_each_loss_agrees_with_the_reference(both, part):
    (parts_p, _), (parts_r, _) = both
    assert float(parts_p[part]) == pytest.approx(parts_r[part], rel=1e-5)
    assert float(parts_p[part]) > 1.0       # it is there at all


def test_the_step_scalar_is_the_weighted_sum(tiny, batch):
    _, c = tiny
    params = m.init_mla_moe_params(c, 3)
    main, mtp, ids = m.loss_parts(params, *batch, c)
    assert ids is None
    assert float(m.mla_moe_loss(params, *batch, c)) == pytest.approx(
        float(main + 0.3 * mtp), rel=1e-6)
    loss, ids = m.mla_moe_loss(params, *batch, c, want_ids=True)
    # every router's choices, the module's last
    assert ids.shape == (c.sparse_layers + 1, BATCH * SEQ, 4)


@pytest.mark.parametrize("leaf", _leaf_paths())
def test_gradient_of_every_parameter_agrees_with_the_reference(both, leaf):
    """The head's and the embedding's are the sum of two passes."""
    (_, grads_p), (_, grads_r) = both
    got, want = ({jax.tree_util.keystr(p): a for p, a in
                  jax.tree_util.tree_leaves_with_path(g)}[leaf]
                 for g in (grads_p, grads_r))
    if leaf.endswith("['router_b']"):
        # it selects and does not weigh: no gradient reaches it
        assert not np.asarray(got).any() and not np.asarray(want).any()
        return
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-4 * scale)


def test_the_head_gradient_is_the_sum_of_two_passes(tiny, batch):
    _, c = tiny
    params = m.init_mla_moe_params(c, 3)
    grad = jax.jit(jax.grad(lambda p, w: m.mla_moe_loss(
        p, *batch, dataclasses.replace(c, mtp_loss_weight=w))))
    whole, main_only = grad(params, 0.3), grad(params, 0.0)
    module = jax.tree_util.tree_map(jnp.subtract, whole, main_only)
    assert float(jnp.abs(module["lm_head"]).max()) > 1e-6
    assert float(jnp.abs(module["mtp"]["eh_w"]).max()) > 1e-6
    assert not np.asarray(main_only["mtp"]["eh_w"]).any()


def test_weight_decay_masks_agree(tiny):
    params = jax.eval_shape(lambda: m.init_mla_moe_params(tiny[1], 0))
    assert m.wd_mask(params) == ref.decayed(params)
    assert m.wd_mask(params)["mtp"]["eh_w"] is True
    assert m.wd_mask(params)["mtp"]["hnorm_g"] is False
    assert m.wd_mask(params)["mtp"]["layer"]["router_b"] is False
    assert "hc_attn" not in params["sparse"]        # no streams, no mixing


# ------------------------------------------------- one step of the trainer

@pytest.fixture(scope="module")
def stepped(tiny, batch):
    """(the program's state after one step, the reference's parameters
    after one step, the initial biases)."""
    config, c = tiny
    init_fn, step = m.build_train_step(
        c, **{k: config["optimizer"][k] for k in ("lr", "wd", "b1", "b2")})
    state = init_fn(5)
    reference, *_ = ref.train_step(jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), m.init_mla_moe_params(c, 5)),
        batch, config)
    state, loss = step(state, *batch)
    assert math.isfinite(float(loss))
    return state, reference


def test_parameters_after_one_step_agree_with_the_reference(stepped):
    state, reference = stepped
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(state["master"]),
            jax.tree_util.tree_leaves(reference)):
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))
    # master and parameter move alike
    for got, want in zip(jax.tree_util.tree_leaves(state["params"]),
                         jax.tree_util.tree_leaves(state["master"])):
        np.testing.assert_array_equal(got, want.astype(got.dtype))


def test_biases_after_one_step_agree_with_the_reference(tiny, stepped):
    config, c = tiny
    state, reference = stepped
    got = np.asarray(m._router_biases(state["params"]))
    want = np.asarray(ref.router_biases(reference))
    assert got.shape == (c.sparse_layers + 1, c.n_routed_experts)
    np.testing.assert_array_equal(got, want)
    gamma = np.float32(config["router_bias_update_rate"])
    assert set(np.unique(got)) <= {-gamma, np.float32(0), gamma}
    assert (got != 0).mean() > 0.5
    # no AdamW moment has a say: the gradient was zero and stays unseen
    for tree in (state["m"], state["v"]):
        assert not np.asarray(tree["sparse"]["router_b"]).any()
        assert not np.asarray(tree["mtp"]["layer"]["router_b"]).any()


def test_the_bias_moves_against_the_load(tiny, batch):
    _, c = tiny
    params = m.init_mla_moe_params(c, 5)
    facts = jax.jit(functools.partial(m.step_facts, config=c))(
        params, *batch)
    pairs = np.asarray(facts["pairs"])
    assert (pairs.sum(1) == BATCH * SEQ * c.num_experts_per_tok).all()
    _, ids = m.mla_moe_loss(params, *batch, c, want_ids=True)
    moved = m._move_router_biases(params, ids, c)
    delta = np.asarray(m._router_biases(moved))
    mean = pairs.mean(1, keepdims=True)
    np.testing.assert_array_equal(
        delta, np.float32(c.router_bias_update_rate) * np.sign(mean - pairs))
    # only the biases were replaced
    assert moved["wte"] is params["wte"]
    assert moved["sparse"]["router_w"] is params["sparse"]["router_w"]


def test_bias_only_moves_balance_the_load(tiny, batch):
    """The rule alone, with no weight update, evens out the pairs the
    experts draw on a batch; nothing but the biases moves."""
    _, c = tiny
    fast = dataclasses.replace(c, router_bias_update_rate=0.004)
    init_fn, _ = m.build_train_step(fast)
    state = init_fn(2)
    drawn = jax.jit(functools.partial(m.step_facts, config=fast))
    balance = jax.jit(functools.partial(m.move_biases_only, config=fast))

    def unevenness(state):
        pairs = np.asarray(drawn(state["params"], *batch)["pairs"])
        return (pairs.max(1) / pairs.mean(1)).max()

    before, start = unevenness(state), state
    for _ in range(40):
        state = balance(state, *batch)
    assert unevenness(state) < before - 0.1
    assert int(state["step"]) == 0
    for name in ("params", "master"):
        np.testing.assert_array_equal(state[name]["sparse"]["router_w"],
                                      start[name]["sparse"]["router_w"])
        np.testing.assert_array_equal(
            m._router_biases(state[name]), m._router_biases(state["master"]))
    assert float(jnp.abs(m._router_biases(state["params"])).max()) > 0.01


def test_rate_zero_leaves_the_biases_bit_equal(tiny, batch):
    _, c = tiny
    held = dataclasses.replace(c, router_bias_update_rate=0.0)
    init_fn, step = m.build_train_step(held, lr=1e-2)
    state = init_fn(1)
    start = np.asarray(m._router_biases(state["params"])) + 0.25
    for tree in (state["params"], state["master"]):
        tree["sparse"]["router_b"] = jnp.asarray(start[:-1])
        tree["mtp"]["layer"]["router_b"] = jnp.asarray(start[-1:])
    for _ in range(3):
        state, loss = step(state, *batch)
    for tree in (state["params"], state["master"]):
        np.testing.assert_array_equal(m._router_biases(tree), start)


def test_more_than_one_module_is_refused(tiny):
    with pytest.raises(NotImplementedError, match="depth 1"):
        dataclasses.replace(tiny[1], mtp_layers=2)


# ------------------------------------------------------- the residual path

def _one_layer(tree):
    return jax.tree_util.tree_map(lambda a: a[0], tree)


@pytest.mark.parametrize("group", ["dense", "sparse"])
def test_the_plain_residual_block_is_the_references_layer(tiny, group):
    config, c = tiny
    params = _away_from_the_start(m.init_mla_moe_params(c, 7))
    blk = _one_layer(params[group])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, SEQ, c.hidden_size))
    got, ids = m._block(x, blk, c, sparse=group == "sparse", want_ids=True)
    want, drawn = ref.layer(group == "sparse", config)(x[0], blk)
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    assert got.shape == x.shape and float(jnp.abs(got - x).max()) > 1e-2
    if group == "sparse":
        np.testing.assert_array_equal(
            np.bincount(np.asarray(ids).ravel(),
                        minlength=c.n_routed_experts), drawn)


SHARES = 8


@pytest.mark.parametrize("where", ["sparse", "mtp"])
def test_the_shares_add_up_to_the_uncut_layer(tiny, where):
    """Experts 2 at a time over 8 shares, each routing over all 16: the
    shares' layers summed, with what every chip computes alike (the
    residual, attention, the shared expert) counted once, are the uncut
    reference's layer; in the trunk and in the prediction module."""
    config, c = tiny
    uncut = dataclasses.replace(c, experts_held=None)
    params = _away_from_the_start(m.init_mla_moe_params(uncut, 9))
    blk = _one_layer(params["sparse"] if where == "sparse"
                     else params["mtp"]["layer"])
    blk = dict(blk, experts=jax.tree_util.tree_map(lambda a: a * 8.0,
                                                   blk["experts"]))
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 48, c.hidden_size))
    held = c.n_routed_experts // SHARES
    total = 0
    for i in range(SHARES):
        share = dataclasses.replace(c, experts_held=(i * held, held))
        part = dict(blk, experts={k: a[i * held:(i + 1) * held]
                                  for k, a in blk["experts"].items()})
        total = total + m._block(x, part, share, sparse=True,
                                 want_ids=False)[0]
    whole = dict(config, n_routed_experts=c.n_routed_experts)
    none = dict(config, n_routed_experts=0)
    want, _ = ref.layer(True, whole)(x[0], blk)
    alike, _ = ref.layer(True, none)(x[0], blk)
    np.testing.assert_allclose(total[0] - (SHARES - 1) * alike, want,
                               rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(want - alike).max()) > 1e-3    # the routed part


# ------------------------------------------- the flash kernels at 256 / 256

@pytest.mark.parametrize("seq, d_v, causal", [(1024, 256, True),
                                              (1536, 256, True),
                                              (1024, 128, False)])
def test_flash_backward_with_a_narrower_key_block(seq, d_v, causal,
                                                  monkeypatch):
    """Where 512 keys do not fit the backward runs 256-key blocks against
    the forward's 512-query blocks (`_bwd_block_k`; at 256 / 256 from
    2,048 on, which the GLM cell runs): the only shapes at which the two
    differ, here in the interpreter, at lengths it can afford and a limit
    cut to match, against the einsum's gradients."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    bq, bk = fa._block_sizes(seq, seq, 256)
    assert fa._bwd_block_k(2048, 2048, 256, 256, jnp.bfloat16) == 256
    whole, half = (fa._bwd_bytes(seq, bq, keys, 256, d_v, jnp.float32, 1)
                   for keys in (512, 256))
    monkeypatch.setattr(fa, "SCOPED_VMEM_BYTES", (whole + half) // 2)
    assert (bq, bk, fa._bwd_block_k(seq, seq, 256, d_v, jnp.float32)) \
        == (512, 512, 256)
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seq), 4)
    q, k = (jax.random.normal(key, (1, seq, 256), jnp.float32)
            for key in (k0, k1))
    v, w = (jax.random.normal(key, (1, seq, d_v), jnp.float32)
            for key in (k2, k3))

    def einsum(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q, k) / 16.0
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)

    got, want = (jax.grad(lambda q, k, v: (f(q, k, v) * w).sum(), (0, 1, 2))(
        q, k, v) for f in (functools.partial(fa.mha_forward, causal=causal),
                           einsum))
    for a, b in zip(got, want):
        assert float(jnp.abs(a - b).max()) < 1e-5 * float(jnp.abs(b).max())


# ------------------------------------------------- scopes in the real step

def _lowered(c, remat=True):
    init_fn, step = m.build_train_step(c, remat=remat)
    state = jax.eval_shape(lambda: init_fn(0))
    tokens = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    return step.trace(state, tokens, tokens).lower()


def _paths(hlo_text):
    return {path for op_name in _stages.op_names(hlo_text).values()
            for path in op_name.split(";")
            if path.startswith("jit(step_fn)/")}


def test_the_module_stands_whole_under_its_stage(tiny):
    """One scope around the module, outside the stages its layer opens:
    `place` files by the first stage of a path, so the block's stages
    appear under `mtp` in every direction and the trunk's keep their own."""
    text = _lowered(tiny[1]).compile().as_text()
    placed = {path: _stages.place(path, stages) for path in _paths(text)}
    found = {p for p in placed.values() if p[0]}
    assert {(stages.MTP, d) for d in ("forward", "remat", "backward")} \
        <= found
    assert (stages.OPTIMIZER, "update") in found
    for stage in (stages.ATTN_CORE, stages.ROUTER, stages.EXPERTS):
        inside = [p for p in placed if f"({stages.MTP})" in p
                  and f"/{stage}/" in p]
        assert inside and all(placed[p][0] == stages.MTP for p in inside)
        assert any(s == stage for s, _ in found)        # the trunk's
    # its pass through the head opens no stage of its own; the trunk's does
    assert {(stages.LOSS_HEAD, d) for d in ("forward", "backward")} <= found
    assert any(f"({stages.MTP})" in p and "log_softmax" in p for p in placed)
    assert not any(s == stages.RESIDUAL_MIX for s, _ in found)
    # the bias update stands under the optimizer
    assert any(placed[p] == (stages.OPTIMIZER, "update") and "sign" in p
               for p in placed)


# -------------------------------------- a family that passes no `state_update`

def _step_as_it_was(loss_fn, init_params_fn, wd_mask, lr=3e-4, wd=0.1,
                    b1=0.9, b2=0.95, eps=1e-8):
    """`trainer.build_adamw_train_step`'s step on one chip as it stood
    before `state_update` (PR 29's), kept here to compare with."""
    def step_fn(state, tokens, labels):
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], tokens,
                                                  labels)
        with jax.named_scope(stages.OPTIMIZER):
            step = state["step"] + 1
            t = step.astype(jnp.float32)

            def upd(p_master, g, m, v, use_wd):
                g = g.astype(jnp.float32)
                m2 = b1 * m + (1 - b1) * g
                v2 = b2 * v + (1 - b2) * g * g
                mhat = m2 / (1 - b1 ** t)
                vhat = v2 / (1 - b2 ** t)
                decay = wd * p_master if use_wd else 0.0
                new_master = p_master - lr * (
                    mhat / (jnp.sqrt(vhat) + eps) + decay)
                return new_master, m2, v2

            flat_master, tree = jax.tree_util.tree_flatten(state["master"])
            outs = [upd(pm, g, m, v, w) for pm, g, m, v, w in zip(
                flat_master, jax.tree_util.tree_leaves(grads),
                jax.tree_util.tree_leaves(state["m"]),
                jax.tree_util.tree_leaves(state["v"]),
                jax.tree_util.tree_leaves(wd_mask))]
            new_master = jax.tree_util.tree_unflatten(
                tree, [o[0] for o in outs])
            new_m = jax.tree_util.tree_unflatten(tree, [o[1] for o in outs])
            new_v = jax.tree_util.tree_unflatten(tree, [o[2] for o in outs])
            new_params = jax.tree_util.tree_map(
                lambda pm, p: pm.astype(p.dtype), new_master, state["params"])
            return {"params": new_params, "master": new_master, "m": new_m,
                    "v": new_v, "step": step}, loss

    return jax.jit(step_fn, donate_argnums=(0,))


def _computation(text):
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return re.sub(r"\n\nFileNames\n.*?\n\n\n", "\n\n", text, count=1,
                  flags=re.DOTALL)


def test_a_family_without_a_state_update_compiles_as_before():
    """The streams configuration passes neither an aux nor an update: its
    step is the instructions it was before the trainer could take one."""
    config = load_cell("xing4-ep8share-pretrain-s2048", tiny=True).config
    c = streams_runner.program_config(config)
    assert not c.router_bias_update_rate and not c.mtp_layers
    init_fn, step = m.build_train_step(c)
    state = jax.eval_shape(lambda: init_fn(0))
    tokens = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    shapes = jax.eval_shape(lambda: m.init_mla_moe_params(c, 0))
    before = _step_as_it_was(
        functools.partial(m.mla_moe_loss, config=c, remat=True),
        functools.partial(m.init_mla_moe_params, c), m.wd_mask(shapes))
    now, then = (s.trace(state, tokens, tokens).lower().compile().as_text()
                 for s in (step, before))
    assert _computation(now) == _computation(then)
    # and one that does pass them is another program
    _, moving = m.build_train_step(dataclasses.replace(
        c, router_bias_update_rate=1e-3))
    moved = moving.trace(state, tokens, tokens).lower().compile().as_text()
    assert _computation(moved) != _computation(then)


def test_the_trainer_hands_the_update_the_master_and_the_aux():
    """No family's name in the trainer: any loss with an aux and any
    function of (master, aux) will do."""
    def loss_fn(params, x, y):
        return jnp.mean((x @ params["w"] + params["b"] - y) ** 2), x.sum()

    def update(master, aux):
        return dict(master, b=master["b"] + aux)

    init_fn, step = trainer.build_adamw_train_step(
        loss_fn, lambda seed: {"w": jnp.ones((3, 1)), "b": jnp.zeros((1,))},
        None, {"w": True, "b": False}, lr=0.0, state_update=update)
    state, loss = step(init_fn(0), jnp.ones((4, 3)), jnp.zeros((4, 1)))
    assert float(loss) == 9.0
    np.testing.assert_array_equal(state["master"]["b"], [12.0])
    np.testing.assert_array_equal(state["params"]["b"], [12.0])
    np.testing.assert_array_equal(state["master"]["w"], jnp.ones((3, 1)))


def test_count_params_of_the_published_share():
    """The benchmark's cut at the published widths: ISSUE 30's figures
    (21.76 M of attention a layer, 706.5 M in all)."""
    config = load_cell(CELL).config
    counts = m.count_params(runner.program_config(config))
    attention = 2048 * 768 + 768 + 768 * 20 * 256 + 2048 * 576 + 512 \
        + 512 * 20 * 448 + 20 * 256 * 2048
    sparse = attention + 2 * 2048 + 2048 * 64 + 64 + 3 * 2048 * 1536 \
        + 8 * 3 * 2048 * 1536
    assert counts["embedding_and_head"] == 2 * 19360 * 2048
    assert counts["dense_layers"] == attention + 2 * 2048 + 3 * 2048 * 10240
    assert counts["sparse_layers"] == 4 * sparse
    assert counts["mtp_module"] == sparse + 3 * 2048 + 4096 * 2048
    assert counts["total"] == sum(counts[k] for k in (
        "embedding_and_head", "dense_layers", "sparse_layers",
        "mtp_module")) + 2048 == 706_518_848
