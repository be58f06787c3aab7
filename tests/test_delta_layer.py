"""The gated-delta-rule layer both families call (models/delta_layer.py) and
what the decoder family gained around it (models/llama.py: linear-attention
layers, the post-norm block, the q/k norm, no rotary; models/trainer.py:
`opt_share`). The Kimi form is the program it was in models/mla_moe.py
(its jaxpr, forward and backward, by sha256, at the tiny widths and behind
the kernels); a step that asks for none of the new options is the program
it was, cell by cell; the Gated DeltaNet form and the full layer are the
plain reference's; and the shares of a ZeRO-1 optimizer state together
make the whole update, each leaving the rest of the parameters where they
were."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.cells import load_cell
from benchmarks.reference import gdn_hybrid as ref
from paddle_tpu.models import delta_layer, llama, mla_moe
from paddle_tpu.models.trainer import zero1_dim

KIMI = "kimil-ep32share-pretrain-s2048"
OLMO = "olmohy-zero8share-pretrain-s4096"


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# -------------------------------------------- the Kimi layer, where it was

@pytest.mark.parametrize("tiny, want", [(True, "00539c9d24b150d0"),
                                        (False, "57547b8ba3376c5b")],
                         ids=["xla-form", "kernels"])
def test_the_kimi_layer_is_the_program_it_was_in_mla_moe(tiny, want):
    """`mla_moe._linear_attention`, forward and its transpose, on shapes:
    the jaxpr the layer gave when it was written in models/mla_moe.py, to
    the character, at the tiny configuration's heads of 16 (the XLA
    functions) and at the cell's heads of 128 (the kda_elementwise and
    delta_rule kernels)."""
    cell = load_cell(KIMI, tiny=tiny)
    c = cell.runner.program_config(cell.config)
    params = jax.eval_shape(lambda: mla_moe.init_mla_moe_params(c, 5))
    blk = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
        params["sparse"][0])
    y = jax.ShapeDtypeStruct((2, 256, c.hidden_size), jnp.dtype(c.dtype))

    def layer(y, blk):
        out, pull = jax.vjp(
            lambda y, blk: mla_moe._linear_attention(y, blk, c)[0], y, blk)
        return out, pull(out)

    assert _sha(str(jax.make_jaxpr(layer)(y, blk))) == want


@pytest.mark.parametrize("cell, want", [
    ("gpt2m-pretrain-s1024", "b559abbbdbce09ab"),
    ("bertl-mlm-s512", "f14a89115147c9a5"),
    ("xing4-ep8share-pretrain-s2048", "72fa793d0ffbbe74"),
    ("glm47f-ep8share-pretrain-s2048", "84fb7ac9412b816e"),
    ("mellum2-ep4share-pretrain-s4096", "8b73aeb3ccf23ee7"),
    ("laguna-ep8share-pretrain-s4096", "0b7eb7aca4bbde4a")])
def test_a_step_without_the_new_options_is_the_program_it_was(cell, want):
    """Each cell's tiny step, traced: the jaxpr it gave before the linear
    layers, the post-norm block, the q/k norm, the optional rotary and
    `opt_share` existed, to the character (the Kimi cell's is
    tests/test_kda_elementwise.py's). An option left out compiles none of
    it."""
    cell = load_cell(cell, tiny=True)
    init_fn, step, _ = cell.runner.build(cell.config, None, {})
    state = jax.eval_shape(lambda: init_fn(0))
    tokens = jax.ShapeDtypeStruct(
        (cell.traffic["batch"], cell.traffic["seq"]), jnp.int32)
    assert _sha(str(jax.make_jaxpr(step)(state, tokens, tokens))) == want


# --------------------------------------------- Gated DeltaNet, the full layer

@pytest.fixture(scope="module")
def olmo():
    config = load_cell(OLMO, tiny=True).config
    c = load_cell(OLMO, tiny=True).runner.program_config(config)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), llama.init_llama_params(c, 2))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64, c.hidden_size),
                          jnp.float32)
    return config, c, params, x


def test_the_configuration_asks_for_the_gdn_form(olmo):
    _, c, _, _ = olmo
    form = llama.linear_form(c)
    assert (form.decay, form.gate, form.beta_scale) \
        == (delta_layer.HEAD, delta_layer.SILU, 2.0)
    assert (form.heads, form.key_dim, form.value_dim, form.taps) \
        == (2, 24, 40, 4)
    kimi = load_cell(KIMI, tiny=True)
    form = mla_moe.linear_form(kimi.runner.program_config(kimi.config))
    assert (form.decay, form.gate, form.beta_scale) \
        == (delta_layer.CHANNEL, delta_layer.SIGMOID, 1.0)


@pytest.mark.parametrize("position", [0, 3], ids=["linear", "full"])
def test_each_kind_of_mixer_is_the_references(olmo, position):
    """The mixer alone, one row, float32: the post-norm model's linear
    layer (no pre-norm, the residual stream in) and its full layer (q/k
    norm, no rotary) against the reference's."""
    config, c, params, x = olmo
    blk = jax.tree_util.tree_map(lambda a: a[0], params["sparse"][position])
    with jax.default_matmul_precision("highest"):
        if position == 0:
            got = jax.jit(lambda x, blk: delta_layer.linear_attention(
                x, blk, llama.linear_form(c), c.dtype, pre_norm=False))(
                    x, blk)
            want = jax.jit(lambda x, blk: ref.linear_attention(
                x, blk, config))(x[0], blk)
        else:
            got = jnp.einsum("bsk,kh->bsh", llama._attention(
                x, blk, c, llama.FULL), blk["o_w"])
            want = ref.full_attention(x[0], blk, config)
    assert float(jnp.abs(want).max()) > 1e-3
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-6)


def test_the_full_layer_has_a_qk_norm_and_no_rotary(olmo):
    """Its parameters hold the two gains, and the linear layers' none;
    without a theta the attention's program holds no cos or sin, with one
    it does."""
    _, c, params, _ = olmo
    full = params["sparse"][3]
    assert full["ln_q_g"].shape == full["ln_k_g"].shape == (1, 64)
    assert "ln_q_g" not in params["sparse"][0]
    jaxpr = str(jax.make_jaxpr(lambda x: llama._attention(
        x, jax.tree_util.tree_map(lambda a: a[0], full), c, llama.FULL))(
            jnp.zeros((1, 64, c.hidden_size))))
    assert " cos" not in jaxpr and " sin" not in jaxpr
    rotated = dataclasses.replace(c, rope_theta=10000.0)
    jaxpr = str(jax.make_jaxpr(lambda x: llama._attention(
        x, jax.tree_util.tree_map(lambda a: a[0], full), rotated,
        llama.FULL))(jnp.zeros((1, 64, c.hidden_size))))
    assert " cos" in jaxpr and " sin" in jaxpr


def test_a_post_norm_block_reads_the_residual_stream(olmo):
    """Olmo's block: x + RMSNorm(Mix(x)), then x + RMSNorm(SwiGLU(x)), and
    the reference's layer agrees on it."""
    config, c, params, x = olmo
    blk = jax.tree_util.tree_map(lambda a: a[0], params["sparse"][3])
    with jax.default_matmul_precision("highest"):
        got, _ = llama._block(x, blk, c, kind=llama.FULL)
        want = ref.layer(llama.FULL, config, jnp.float32)(x[0], blk)
        mixed = ref.full_attention(x[0], blk, config)
        by_hand = x[0] + ref.rms_norm(mixed, blk["ln1_g"], c.rms_norm_eps)
        by_hand = by_hand + ref.rms_norm(
            ref.swiglu(by_hand, blk["gate_w"], blk["up_w"], blk["down_w"]),
            blk["ln2_g"], c.rms_norm_eps)
    np.testing.assert_allclose(want, by_hand, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)


def test_a_linear_layer_without_its_widths_is_refused(olmo):
    _, c, _, _ = olmo
    with pytest.raises(ValueError, match="key and value widths"):
        dataclasses.replace(c, linear_num_heads=0)
    with pytest.raises(NotImplementedError, match="dense feed-forward"):
        dataclasses.replace(c, num_experts=4, num_experts_per_tok=2,
                            moe_intermediate_size=8)


# ------------------------------------------------ the held optimizer share

SHARE_CONFIG = llama.LlamaConfig(
    vocab_size=64, hidden_size=12, intermediate_size=16, num_layers=2,
    num_heads=2, rope_theta=None, post_norm=True, qk_norm=True,
    dtype="float32")


def test_the_eight_shares_make_the_whole_update_and_touch_nothing_else():
    """`opt_share=(i, 8)` for each i on the same state and batch: each
    share's step moves its own slice of every parameter exactly as the
    unsharded step moves it (ZeRO-1's dimension, `zero1_dim`: a leaf that
    8 divides on no dimension, the gains of 12, whole in every share),
    holds those slices of the masters and moments, and leaves the rest of
    every parameter as it was; the eight together are the whole update."""
    c = SHARE_CONFIG
    ids = np.random.default_rng(0).integers(0, 64, (2, 17)).astype(np.int32)
    batch = (ids[:, :-1], ids[:, 1:])
    init_fn, step = llama.build_train_step(c, lr=1e-2)
    whole, _ = step(init_fn(0), *batch)
    start = jax.tree_util.tree_leaves(init_fn(0)["params"])
    want = jax.tree_util.tree_leaves(whole["params"])
    dims = [zero1_dim(a.shape, 8) for a in start]
    assert None in dims and 0 in dims and 1 in dims
    together = [np.array(a) for a in start]
    for i in range(8):
        init_fn, step = llama.build_train_step(c, lr=1e-2, opt_share=(i, 8))
        state, _ = step(init_fn(0), *batch)
        got = jax.tree_util.tree_leaves(state["params"])
        masters = jax.tree_util.tree_leaves(state["master"])
        moments = jax.tree_util.tree_leaves(state["m"])
        for n, (a, b, w, d) in enumerate(zip(start, got, want, dims)):
            a, b, w = (np.asarray(x) for x in (a, b, w))
            if d is None:
                np.testing.assert_allclose(b, w, rtol=1e-6, atol=1e-7)
                together[n] = b
                continue
            size = a.shape[d] // 8
            mine = [slice(None)] * a.ndim
            mine[d] = slice(i * size, (i + 1) * size)
            mine = tuple(mine)
            np.testing.assert_allclose(b[mine], w[mine], rtol=1e-6,
                                       atol=1e-7)
            np.testing.assert_allclose(masters[n], w[mine], rtol=1e-6,
                                       atol=1e-7)
            assert moments[n].shape == w[mine].shape
            rest = np.ones(a.shape, bool)
            rest[mine] = False
            np.testing.assert_array_equal(b[rest], a[rest])
            together[n][mine] = b[mine]
    for g, w in zip(together, want):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-7)


def test_a_share_is_one_chips_with_adamw_alone():
    with pytest.raises(NotImplementedError, match="one chip's share"):
        llama.build_train_step(dataclasses.replace(
            SHARE_CONFIG, num_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=8, router="sigmoid", post_norm=False,
            router_bias_update_rate=1e-3), opt_share=(0, 8))
