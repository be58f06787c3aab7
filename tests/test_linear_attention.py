"""The chunked gated delta rule (ops/linear_attention.py) against the
recurrence it stands for, token by token, written out here: outputs and all
five gradients at three chunk sizes, log-decays strong enough to overflow a
form that multiplies exp(G) by exp(-G), and the shapes it refuses. At head
widths of 128 and the chunk of 64 the chunk terms are the Mosaic kernels of
ops/pallas/delta_rule.py (here in the interpreter): the same comparisons,
and against the XLA form on the same operands."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import linear_attention
from paddle_tpu.ops.linear_attention import SUB, chunk_gated_delta_rule

NAMES = ("q", "k", "v", "g", "beta")


def recurrence(q, k, v, g, beta):
    """S' = Diag(exp g_t) S; S = S' + beta_t k_t (v_t - S'^T k_t)^T;
    o_t = S^T q_t, from S = 0, one token at a time."""
    b, s, h, dk = q.shape

    def token(S, x):
        q, k, v, g, beta = x
        S = jnp.exp(g)[..., None] * S
        write = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", S, k))
        S = S + k[..., None] * write[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q)

    _, o = jax.lax.scan(token, jnp.zeros((b, h, dk, v.shape[-1]), q.dtype),
                        tuple(jnp.moveaxis(x, 1, 0)
                              for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def inputs(decay: float, seq=128, batch=2, heads=2, dk=16, dv=8, seed=0,
           dtype=jnp.float32):
    """q and k as the layer hands them (unit norm, q scaled), log-decays
    about -`decay` a token and channel."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (batch, seq, heads, dk))
    k = jax.random.normal(ks[1], (batch, seq, heads, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (batch, seq, heads, dv))
    g = -decay * (0.5 + jax.nn.sigmoid(
        jax.random.normal(ks[3], (batch, seq, heads, dk))))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads)))
    return tuple(x.astype(dtype) for x in (q, k, v, g, beta))


@pytest.fixture(scope="module")
def mild():
    x = inputs(0.1)
    weight = jax.random.normal(jax.random.PRNGKey(9), x[2].shape)
    with jax.default_matmul_precision("highest"):
        want = recurrence(*x)
        grads = jax.grad(lambda *a: (recurrence(*a) * weight).sum(),
                         argnums=range(5))(*x)
    return x, weight, want, grads


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_outputs_are_the_recurrences(mild, chunk):
    x, _, want, _ = mild
    with jax.default_matmul_precision("highest"):
        got = chunk_gated_delta_rule(*x, chunk=chunk)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_gradients_are_the_recurrences(mild, chunk, name):
    x, weight, _, grads = mild
    i = NAMES.index(name)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: (chunk_gated_delta_rule(
            *a, chunk=chunk) * weight).sum(), argnums=i)(*x)
    scale = float(jnp.abs(grads[i]).max())
    np.testing.assert_allclose(got, grads[i], rtol=1e-4, atol=1e-5 * scale)


def naive_chunk(q, k, v, g, beta, chunk):
    """The chunked form with every ratio of decays as exp(G_i) * exp(-G_j):
    the same algebra, and an overflow once a chunk's summed log-decay
    passes -88."""
    b, s, h, dk = q.shape
    out, S = [], jnp.zeros((b, h, dk, v.shape[-1]), q.dtype)
    for n in range(s // chunk):
        qc, kc, vc, gc, bc = (jnp.moveaxis(
            x[:, n * chunk:(n + 1) * chunk], 1, 2) for x in (q, k, v, g, beta))
        G = jnp.cumsum(gc, -2)
        up, down = jnp.exp(G), jnp.exp(-G)
        lower = jnp.tril(jnp.ones((chunk, chunk), q.dtype))
        A = jnp.einsum("bhic,bhjc->bhij", kc * up, kc * down) \
            * bc[..., None] * jnp.tril(lower, -1)
        B = jnp.einsum("bhic,bhjc->bhij", qc * up, kc * down) * lower
        T = jnp.linalg.inv(jnp.eye(chunk, dtype=q.dtype) + A)
        u = T @ (bc[..., None] * (vc - (kc * up) @ S))
        out.append(jnp.moveaxis((qc * up) @ S + B @ u, 2, 1))
        S = up[..., -1, :, None] * S + jnp.einsum(
            "bhic,bhiv->bhcv", kc * up[..., -1:, :] * down, u)
    return jnp.concatenate(out, 1)


def test_the_naive_form_agrees_where_it_does_not_overflow(mild):
    x, _, want, _ = mild
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(naive_chunk(*x, chunk=64), want,
                                   rtol=1e-3, atol=1e-4)


def test_strong_decay_overflows_the_naive_form_and_not_the_program():
    """Log-decays near -5 a token: a chunk of 64 sums to about -320, and
    exp(320) is not a float32."""
    x = inputs(5.0)
    assert float(jnp.cumsum(x[3][:, :64], 1).min()) < -300
    with jax.default_matmul_precision("highest"):
        want = recurrence(*x)
        assert not bool(jnp.isfinite(naive_chunk(*x, chunk=64)).all())
        for chunk in (16, 64):
            got = chunk_gated_delta_rule(*x, chunk=chunk)
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
        weight = jax.random.normal(jax.random.PRNGKey(9), want.shape)
        want_g = jax.grad(lambda *a: (recurrence(*a) * weight).sum(),
                          argnums=range(5))(*x)
        got_g = jax.grad(lambda *a: (chunk_gated_delta_rule(
            *a, chunk=64) * weight).sum(), argnums=range(5))(*x)
    for got, want in zip(got_g, want_g):
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=1e-5 * float(jnp.abs(want).max()))


def test_bfloat16_operands_keep_a_float32_state():
    """As the trainer calls it: bfloat16 q, k, v, float32 log-decays. The
    output is bfloat16 and within bfloat16's rounding of the float32
    answer over 128 tokens of carried state."""
    q, k, v, g, beta = inputs(0.1)
    with jax.default_matmul_precision("highest"):
        want = recurrence(q, k, v, g, beta)
    got = chunk_gated_delta_rule(*(x.astype(jnp.bfloat16) for x in (q, k, v)),
                                 g, beta, chunk=64)
    assert got.dtype == jnp.bfloat16
    err = jnp.abs(got.astype(jnp.float32) - want).max()
    assert float(err) < 0.03 * float(jnp.abs(want).max())


def test_a_length_that_is_no_multiple_of_the_chunk_raises_by_name():
    x = inputs(0.1, seq=96)
    with pytest.raises(ValueError, match="chunk_gated_delta_rule.*96.*64"):
        chunk_gated_delta_rule(*x, chunk=64)
    assert chunk_gated_delta_rule(*x, chunk=32).shape == x[2].shape


def test_a_chunk_is_the_sub_block_times_a_power_of_two():
    x = inputs(0.1, seq=96)
    with pytest.raises(ValueError, match="power of two"):
        chunk_gated_delta_rule(*x, chunk=3 * SUB)
    # below the sub-block the whole chunk is pairwise
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            chunk_gated_delta_rule(*x, chunk=8), recurrence(*x),
            rtol=2e-5, atol=2e-6)


# ------------------------------------------- shapes the Mosaic kernels take

KERNEL_CASES = [(128, 1, "float32"), (256, 2, "float32"),
                (128, 2, "bfloat16"), (256, 1, "bfloat16")]


def off(got, want) -> float:
    """|got - want|_2 / |want|_2 in float32."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum()))


@functools.lru_cache(maxsize=None)
def kernel_case(seq: int, batch: int, dtype: str, decay: float = 0.1):
    """(the recurrence's, the kernels', the XLA form's) output and five
    gradients at 2 heads of 128: q, k, v in `dtype`, the recurrence on
    their float32 values."""
    x = inputs(decay, seq=seq, batch=batch, dk=128, dv=128)
    given = tuple(a.astype(dtype) for a in x[:3]) + x[3:]
    x = tuple(a.astype(jnp.float32) for a in given)
    weight = jax.random.normal(jax.random.PRNGKey(9), x[2].shape)

    def both(rule, operands):
        # jitted: eagerly the XLA form alone takes 14 s here
        return jax.jit(lambda *a: (rule(*a), jax.grad(
            lambda *a: (rule(*a).astype(jnp.float32) * weight).sum(),
            argnums=range(5))(*a)))(*operands)

    with jax.default_matmul_precision("highest"):
        return (both(recurrence, x), both(chunk_gated_delta_rule, given),
                both(lambda *a: linear_attention._rule(*a, 64, SUB, False),
                     given))


@pytest.mark.parametrize("what", ("o",) + NAMES)
@pytest.mark.parametrize("seq, batch, dtype", KERNEL_CASES)
def test_the_kernels_are_the_recurrence_and_the_xla_form(seq, batch, dtype,
                                                         what):
    (want, want_g), (got, got_g), (xla, xla_g) = kernel_case(seq, batch,
                                                             dtype)
    if what != "o":
        i = NAMES.index(what)
        want, got, xla = want_g[i], got_g[i], xla_g[i]
    assert got.shape == xla.shape and got.dtype == xla.dtype
    if dtype == "float32":
        scale = float(jnp.abs(want).max())
        for other in (want, xla):
            np.testing.assert_allclose(got, other, rtol=1e-4,
                                       atol=1e-5 * scale)
    else:
        # bfloat16 operands: the kernels round where the XLA form rounds
        assert off(xla, want) < 8e-3
        assert off(got, want) < 8e-3 and off(got, xla) < 6e-3


def test_strong_decay_through_the_kernels_is_finite_and_the_recurrences():
    """A chunk of 64 sums to about -320 a channel: the kernels' exponents
    are differences of summed logs that are <= 0, forward and backward."""
    (want, want_g), (got, got_g), _ = kernel_case(128, 1, "float32", 5.0)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    for g, w in zip(got_g, want_g):
        assert bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("dk, dv, chunk, kernels", [
    (128, 128, 64, True), (16, 8, 64, False), (128, 128, 32, False),
    (128, 64, 64, False)])
def test_the_shapes_alone_choose_the_kernels(dk, dv, chunk, kernels):
    """Head widths that are multiples of 128 at the chunk of 64 take the
    Mosaic kernels, forward and backward; any other shape lowers the XLA
    form with no `pallas_call`."""
    x = inputs(0.1, seq=64, batch=1, dk=dk, dv=dv)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: chunk_gated_delta_rule(*a, chunk=chunk).sum(),
        argnums=range(5)))(*x))
    assert ("pallas_call" in text) is kernels
