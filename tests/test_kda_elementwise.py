"""The KDA layer's elementwise passes around the chunked rule as Mosaic
kernels (ops/pallas/kda_elementwise.py), here in the interpreter, against
the XLA functions of models/delta_layer.py that they replace: the short
convolutions with SiLU and the head norms, the log-decays and the gated head
norm, forward and every gradient, on sequences of several token blocks (the
halo between blocks, the zero history at a row's start, the zero future at
its end), and the shapes that choose them."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.cells import load_cell
from paddle_tpu.models import delta_layer, mla_moe
from paddle_tpu.ops.pallas import kda_elementwise as ke

HEADS, D, TAPS = 2, 128, 4


def _normal(key, shape, scale=1.0, dtype=jnp.float32):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _close(got, want, tol):
    """Relative L2 distance of every leaf under `tol`."""
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert np.linalg.norm(g - w) <= tol * np.linalg.norm(w), \
            np.linalg.norm(g - w) / np.linalg.norm(w)


def _both(fn, ref, args, seed):
    """(fn's output and gradients to every argument, ref's) under one
    seeded cotangent."""
    out = []
    for f in (fn, ref):
        y, pull = jax.vjp(f, *args)
        cot = _normal(jax.random.PRNGKey(seed), y.shape, dtype=y.dtype)
        out.append((y, pull(cot)))
    return out


# -------------------------------------------------------- the convolutions

@pytest.mark.parametrize("seq", [48, 1024])
@pytest.mark.parametrize("norm", [D ** -0.5, 1.0, None])
def test_conv_heads_is_the_xla_form_forward_and_backward(seq, norm):
    """Three token blocks of 16 at 48 (each block's halo is the whole block
    before it) and two of 512 at 1,024; float32, so the two forms differ by
    their order of float32 sums."""
    kx, kt = jax.random.split(jax.random.PRNGKey(seq))
    x = _normal(kx, (2, seq, HEADS * D))
    taps = _normal(kt, (TAPS, HEADS * D), 0.5)
    assert ke.taken(D, seq, x, taps)
    assert ke.tiling(seq, HEADS * D, D)[0] == (16 if seq == 48 else 512)
    got, want = _both(
        lambda x, t: ke.conv_heads(x, t, HEADS, norm, delta_layer.QK_NORM_EPS),
        lambda x, t: delta_layer._conv_heads(x, t, HEADS, norm), (x, taps), 1)
    _close(got, want, 2e-6)


def test_conv_heads_sees_no_history_before_a_row_and_none_after_it():
    """Each row is its own sequence: a row's first token takes no tap from
    the row before, and its last token's gradient reaches no token of the
    next row, whatever the next row holds."""
    x = _normal(jax.random.PRNGKey(3), (2, 32, HEADS * D))
    taps = _normal(jax.random.PRNGKey(4), (TAPS, HEADS * D))
    swapped = x.at[0].set(x[1] * 7.0)

    def first_row(x):
        return ke.conv_heads(x, taps, HEADS, None, 1e-6)[1]

    np.testing.assert_array_equal(first_row(x), first_row(swapped))
    grad = jax.grad(lambda x: first_row(x).sum())(x)
    assert float(jnp.abs(grad[0]).max()) == 0.0
    assert float(jnp.abs(grad[1]).max()) > 0.0


def test_a_float32_product_in_and_the_rules_dtype_out():
    """The model hands the convolutions float32 products and the rule takes
    bfloat16: the kernel rounds once, to the dtype it is given, where the
    XLA form on the same float32 operand rounds at its end; the gradient to
    x comes back float32 and to the taps in theirs."""
    bf = jnp.bfloat16
    x = _normal(jax.random.PRNGKey(11), (2, 64, HEADS * D))
    taps = _normal(jax.random.PRNGKey(12), (TAPS, HEADS * D), 0.5, bf)
    got, want = _both(
        lambda x, t: ke.conv_heads(x, t, HEADS, D ** -0.5,
                                   delta_layer.QK_NORM_EPS, dtype=bf),
        lambda x, t: delta_layer._conv_heads(
            x, t, HEADS, D ** -0.5).astype(bf),
        (x, taps), 13)
    assert got[0].dtype == bf and got[1][0].dtype == jnp.float32
    _close(got[0], want[0], 2e-3)
    _close(got[1], want[1], 1e-5)


def test_the_wide_product_is_float32_forward_and_bfloat16_backward():
    """`delta_layer._wide_product`: the q, k, v products unrounded, as XLA's
    fusions hand them to the XLA form's convolutions, and a backward pass
    that is the bfloat16 product's on the cotangent rounded to bfloat16."""
    bf = jnp.bfloat16
    y = _normal(jax.random.PRNGKey(14), (2, 16, 64), dtype=bf)
    w = _normal(jax.random.PRNGKey(15), (64, 256), 0.1, bf)
    out, pull = jax.vjp(delta_layer._wide_product, y, w)
    assert out.dtype == jnp.float32
    _close(out, jnp.einsum("bsh,hk->bsk", y.astype(jnp.float32),
                           w.astype(jnp.float32)), 1e-6)
    cot = _normal(jax.random.PRNGKey(16), out.shape)
    _, narrow = jax.vjp(lambda y, w: jnp.einsum("bsh,hk->bsk", y, w), y, w)
    for g, n in zip(pull(cot), narrow(cot.astype(bf))):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(n, np.float32))


# ----------------------------------------------------------- the log-decays

def test_log_decay_is_the_xla_form_forward_and_backward():
    ka, kd, kb = jax.random.split(jax.random.PRNGKey(5), 3)
    a = _normal(ka, (2, 48, HEADS * D), 3.0)
    decay_log = jnp.log(jax.random.uniform(kd, (HEADS,), jnp.float32, 1, 16))
    dt_bias = _normal(kb, (HEADS * D,), 2.0)
    assert ke.taken(D, 48, a, decay_log, dt_bias)
    got, want = _both(ke.log_decay, delta_layer._log_decay,
                      (a, decay_log, dt_bias), 6)
    assert got[0].dtype == jnp.float32 and got[0].shape == (2, 48, HEADS, D)
    _close(got, want, 2e-6)


# --------------------------------------------------------- the gated norm

def test_gated_norm_is_the_xla_form_forward_and_backward():
    ko, kg, kw = jax.random.split(jax.random.PRNGKey(7), 3)
    o = _normal(ko, (2, 48, HEADS, D))
    gate = _normal(kg, (2, 48, HEADS * D), 2.0)
    gain = 1.0 + _normal(kw, (D,), 0.3)
    assert ke.taken(D, 48, o, gate, gain)
    got, want = _both(lambda o, w, g: ke.gated_norm(o, w, g, 1e-5),
                      lambda o, w, g: delta_layer._gated_norm(
                          o, w, g, 1e-5, jax.nn.sigmoid),
                      (o, gain, gate), 8)
    _close(got, want, 2e-6)


# -------------------------------------------------------- bfloat16 operands

def test_bfloat16_operands_round_once_where_the_xla_form_rounds_more():
    """At the trainer's dtype every kernel computes in float32 and rounds
    its result once: against the float32 functions on the same bfloat16
    operands it is within one bfloat16 rounding, no further off than the
    XLA form at the same dtype, and the log-decays stay float32."""
    keys = jax.random.split(jax.random.PRNGKey(9), 6)
    bf, seq = jnp.bfloat16, 64
    x = _normal(keys[0], (2, seq, HEADS * D), dtype=bf)
    taps = _normal(keys[1], (TAPS, HEADS * D), 0.5, bf)
    a = _normal(keys[2], (2, seq, HEADS * D), 3.0, bf)
    o = _normal(keys[3], (2, seq, HEADS, D), dtype=bf)
    gate = _normal(keys[4], (2, seq, HEADS * D), 2.0, bf)
    gain = (1.0 + _normal(keys[5], (D,), 0.3)).astype(bf)
    decay_log = jnp.log(jnp.linspace(1.0, 16.0, HEADS, dtype=jnp.float32))
    dt_bias = jnp.linspace(-7.0, -2.0, HEADS * D, dtype=jnp.float32)

    def wide(*args):
        return [a.astype(jnp.float32) if a.dtype == bf else a for a in args]

    cases = [
        (lambda x, t: ke.conv_heads(x, t, HEADS, D ** -0.5, 1e-6),
         lambda x, t: delta_layer._conv_heads(x, t, HEADS, D ** -0.5),
         (x, taps)),
        (ke.log_decay, delta_layer._log_decay, (a, decay_log, dt_bias)),
        (lambda o, w, g: ke.gated_norm(o, w, g, 1e-5),
         lambda o, w, g: delta_layer._gated_norm(o, w, g, 1e-5,
                                                 jax.nn.sigmoid),
         (o, gain, gate))]
    for kernel, xla, args in cases:
        got, same = kernel(*args), xla(*args)
        exact = xla(*wide(*args))
        assert got.dtype == same.dtype
        off = float(jnp.linalg.norm((got.astype(jnp.float32) - exact).ravel())
                    / jnp.linalg.norm(exact.ravel()))
        xla_off = float(jnp.linalg.norm(
            (same.astype(jnp.float32) - exact).ravel())
            / jnp.linalg.norm(exact.ravel()))
        assert off <= max(xla_off, 1e-6) and off < 4e-3
    assert ke.log_decay(a, decay_log, dt_bias).dtype == jnp.float32


# ---------------------------------------------------------------- the rule

@pytest.mark.parametrize("head_dim, seq, dtype, kernels", [
    (128, 2048, jnp.bfloat16, True), (256, 64, jnp.float32, True),
    (16, 128, jnp.float32, False), (64, 2048, jnp.bfloat16, False),
    (128, 40, jnp.bfloat16, False), (128, 2048, jnp.float64, False)])
def test_the_shapes_alone_choose_the_kernels(head_dim, seq, dtype, kernels):
    x = jax.ShapeDtypeStruct((1, seq, 2 * head_dim), dtype)
    assert ke.taken(head_dim, seq, x) is kernels


def test_the_tiny_configuration_keeps_the_xla_form_and_its_step():
    """Heads of 16 take none of the kernels: the jaxpr of the Kimi cell's
    tiny step calls none of them and is, by its sha256, the step the XLA
    functions gave before the kernels existed, to the character."""
    cell = load_cell("kimil-ep32share-pretrain-s2048", tiny=True)
    assert cell.config["linear_attn_config"]["head_dim"] == 16
    init_fn, step, _ = cell.runner.build(cell.config, None, {})
    state = jax.eval_shape(lambda: init_fn(0))
    tokens = jax.ShapeDtypeStruct(
        (cell.traffic["batch"], cell.traffic["seq"]), jnp.int32)
    text = str(jax.make_jaxpr(step)(state, tokens, tokens))
    assert not any(kernel in text for kernel in (
        "_conv_fwd_kernel", "_decay_fwd_kernel", "_norm_fwd_kernel"))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "a9f8670c11e2dc9f"


def test_a_wide_layer_takes_the_kernels_through_the_model():
    """A linear-attention layer of heads of 128: `_linear_attention` calls
    the kernels five times forward (three convolutions, the log-decays, the
    gated norm) and its output and gradient to the layer's input are the
    XLA functions' in float32."""
    cell = load_cell("kimil-ep32share-pretrain-s2048", tiny=True)
    c = dataclasses.replace(cell.runner.program_config(cell.config),
                            linear_heads=HEADS, linear_head_dim=D)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), mla_moe.init_mla_moe_params(c, 1))
    blk = jax.tree_util.tree_map(lambda a: a[0], params["sparse"][0])
    y = _normal(jax.random.PRNGKey(2), (1, 64, c.hidden_size))

    def layer(y):
        return mla_moe._linear_attention(y, blk, c)[0]

    def calls():        # a new function each time: no trace is reused
        return str(jax.make_jaxpr(lambda y: layer(y))(y)).count(
            "pallas_call")

    kernels = calls()
    got = jax.vjp(layer, y)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ke, "taken", lambda *a: False)
        assert kernels - calls() == 5
        want = jax.vjp(layer, y)
    cot = _normal(jax.random.PRNGKey(3), got[0].shape)
    with jax.default_matmul_precision("highest"):
        _close((got[0], got[1](cot)), (want[0], want[1](cot)), 1e-5)
