"""MoE: gating math, dispatch/combine einsums, MoELayer eager training,
fused_moe, expert-parallel sharding under pjit (SURVEY §2e EP row)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.ops import moe as moe_ops


def test_top2_gating_combine_properties():
    rng = np.random.RandomState(0)
    s, e = 64, 4
    logits = jnp.asarray(rng.randn(s, e), jnp.float32)
    combine, dispatch, aux = moe_ops.top2_gating(logits, capacity=s)
    c = combine.shape[-1]
    assert combine.shape == (s, e, c) and dispatch.shape == (s, e, c)
    # with capacity == s nothing is dropped: weights sum to 1 per token
    np.testing.assert_allclose(np.asarray(jnp.sum(combine, axis=(1, 2))),
                               np.ones(s), rtol=1e-5)
    # each token occupies <= 2 slots; no slot is used twice per expert
    slot_usage = jnp.sum(dispatch.astype(jnp.int32), axis=0)  # [E, C]
    assert int(jnp.max(slot_usage)) <= 1
    assert float(aux) > 0.0


def test_top1_gating_capacity_drops():
    rng = np.random.RandomState(1)
    s, e = 32, 4
    logits = jnp.asarray(rng.randn(s, e), jnp.float32)
    combine, dispatch, aux = moe_ops.top1_gating(logits, capacity=2)
    # at most capacity tokens per expert survive
    per_expert = jnp.sum(jnp.any(dispatch, axis=-1).astype(jnp.int32),
                         axis=0)
    assert int(jnp.max(per_expert)) <= 2


def test_dispatch_combine_roundtrip():
    rng = np.random.RandomState(2)
    s, e, m = 16, 4, 8
    logits = jnp.asarray(rng.randn(s, e), jnp.float32)
    x = jnp.asarray(rng.randn(s, m), jnp.float32)
    combine, dispatch, _ = moe_ops.top2_gating(logits, capacity=s)
    xe = moe_ops.moe_dispatch(x, dispatch)
    assert xe.shape[0] == e and xe.shape[2] == m
    # identity experts -> output == sum_k gate_k * x == x (gates normed)
    y = moe_ops.moe_combine(xe, combine)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-4,
                               atol=1e-5)


def test_moe_layer_eager_forward_backward():
    paddle.seed(0)
    d_model, n_exp = 16, 4
    experts = [nn.Sequential(nn.Linear(d_model, 32), nn.GELU(),
                             nn.Linear(32, d_model)) for _ in range(n_exp)]
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    layer = MoELayer(d_model, experts=experts, gate={"type": "gshard"})
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(2, 8, d_model).astype(np.float32),
        stop_gradient=False)
    out = layer(x)
    assert tuple(out.shape) == (2, 8, d_model)
    assert layer.l_aux is not None
    loss = paddle.mean(out * out) + layer.l_aux * 0.01
    loss.backward()
    g = layer.experts[0][0].weight.grad
    assert g is not None
    assert layer.gate.weight.grad is not None


def test_moe_layer_switch_and_naive_gates():
    paddle.seed(0)
    from paddle_tpu.incubate.distributed.models.moe import MoELayer
    for gate in ("switch", "naive"):
        experts = [nn.Linear(8, 8) for _ in range(2)]
        layer = MoELayer(8, experts=experts, gate=gate)
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        out = layer(x)
        assert tuple(out.shape) == (4, 8)


def test_fused_moe_functional():
    rng = np.random.RandomState(3)
    s, m, e, h = 16, 8, 4, 32
    x = paddle.to_tensor(rng.randn(2, s, m).astype(np.float32))
    gate_w = paddle.to_tensor(rng.randn(m, e).astype(np.float32))
    w0 = paddle.to_tensor(rng.randn(e, m, h).astype(np.float32) * 0.1)
    w1 = paddle.to_tensor(rng.randn(e, h, m).astype(np.float32) * 0.1)
    from paddle_tpu.incubate.nn.functional import fused_moe
    out = fused_moe(x, gate_w, w0, w1)
    assert tuple(out.shape) == (2, s, m)


def test_moe_ffn_expert_parallel_pjit():
    """Expert weights sharded over an 'ep' mesh axis; the jitted program
    must compile and match the unsharded result."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    rng = np.random.RandomState(4)
    s, m, e, h = 32, 8, 4, 16
    x = jnp.asarray(rng.randn(s, m), jnp.float32)
    gate_w = jnp.asarray(rng.randn(m, e), jnp.float32)
    w0 = jnp.asarray(rng.randn(e, m, h) * 0.1, jnp.float32)
    b0 = jnp.zeros((e, h), jnp.float32)
    w1 = jnp.asarray(rng.randn(e, h, m) * 0.1, jnp.float32)
    b1 = jnp.zeros((e, m), jnp.float32)

    ref, aux_ref = moe_ops.moe_ffn(x, gate_w, w0, b0, w1, b1)

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("ep",))
    ep = NamedSharding(mesh, P("ep"))
    w0s = jax.device_put(w0, ep)
    b0s = jax.device_put(b0, ep)
    w1s = jax.device_put(w1, ep)
    b1s = jax.device_put(b1, ep)

    @jax.jit
    def f(x, gate_w, w0, b0, w1, b1):
        return moe_ops.moe_ffn(x, gate_w, w0, b0, w1, b1)

    out, aux = f(x, gate_w, w0s, b0s, w1s, b1s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)


# ------------------------------------------- the dropless path's softmax router

def test_softmax_topk_route_is_softmax_then_top_k_renormalised():
    x = jax.random.normal(jax.random.PRNGKey(0), (37, 24), jnp.float32)
    w_r = jax.random.normal(jax.random.PRNGKey(1), (24, 16), jnp.float32)
    ids, weights, probs = moe_ops.softmax_topk_route(x, w_r, 8)
    want = jax.nn.softmax(jnp.dot(x, w_r, precision="highest"), -1)
    top, top_ids = jax.lax.top_k(want, 8)
    np.testing.assert_allclose(probs, want, rtol=1e-6)
    np.testing.assert_array_equal(ids, top_ids)
    np.testing.assert_allclose(weights, top / top.sum(-1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    assert ids.dtype == jnp.int32 and weights.dtype == jnp.float32
    # bfloat16 activations meet a float32 router in float32
    low, _, _ = moe_ops.softmax_topk_route(x.astype(jnp.bfloat16), w_r, 8)
    assert low.shape == ids.shape


def test_balance_term_is_k_at_perfect_balance_and_grows_with_skew():
    e, k, t = 16, 4, 64
    even = jnp.stack([(jnp.arange(k) + i * k) % e for i in range(t)])
    uniform = jnp.full((t, e), 1.0 / e)
    value, drawn = moe_ops.balance_term(even, uniform)
    np.testing.assert_allclose(value, k, rtol=1e-6)
    np.testing.assert_array_equal(drawn, np.full(e, t * k // e))
    np.testing.assert_array_equal(moe_ops.pairs_drawn(even, e), drawn)
    # every token on the same k experts, and probabilities that follow
    skewed = jnp.tile(jnp.arange(k), (t, 1))
    peaked = jnp.zeros((t, e)).at[:, :k].set(1.0 / k)
    assert float(moe_ops.balance_term(skewed, peaked)[0]) == pytest.approx(e)
    # the counts carry no gradient, the probabilities do
    grad = jax.grad(lambda p: moe_ops.balance_term(skewed, p)[0])(uniform)
    np.testing.assert_allclose(grad[:, :k], e / t, rtol=1e-6)
    np.testing.assert_allclose(grad[:, k:], 0.0)


@pytest.mark.parametrize("held, experts, pairs, want", [
    (8, 64, 4 * 16384, 4),      # an eighth held: the two older sparse cells
    (16, 64, 8 * 16384, 2),     # a quarter: the balanced load ends mid-chunk
    (32, 64, 4096, 1), (64, 64, 4096, 1), (4, 16, 4096, 2),
    (8, 64, 100, 1),            # rows that do not cut into whole sublanes
])
def test_chunk_count_follows_the_held_share(held, experts, pairs, want):
    assert moe_ops.chunk_count(held, experts, pairs) == want


@pytest.mark.parametrize("experts", [None, 16, 32, 64],
                         ids=["all_held", "quarter", "eighth", "sixteenth"])
def test_held_experts_ffn_is_the_same_in_any_number_of_chunks(experts):
    """The chunk count is bookkeeping: whatever the router's width says of
    the held share, the held experts' part is the loop's."""
    t, hidden, width, k, held = 64, 32, 16, 2, (0, 4)
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(keys[0], (t, hidden), jnp.float32)
    ids = jax.random.randint(keys[1], (t, k), 0, 6).astype(jnp.int32)
    weights = jax.random.uniform(keys[2], (t, k), jnp.float32)
    stack = {"gate_w": jax.random.normal(keys[3], (4, hidden, width),
                                         jnp.float32) * 0.2,
             "up_w": jax.random.normal(keys[4], (4, hidden, width),
                                       jnp.float32) * 0.2,
             "down_w": jax.random.normal(keys[3], (4, width, hidden),
                                         jnp.float32) * 0.2}
    got = moe_ops.held_experts_ffn(x, ids, weights, stack, held, experts)
    want = 0
    for i in range(4):
        mine = (weights * (ids == i)).sum(-1, keepdims=True)
        gate = x @ stack["gate_w"][i]
        want = want + mine * ((jax.nn.silu(gate) * (x @ stack["up_w"][i]))
                              @ stack["down_w"][i])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
