"""models/blocks.py: what the four compiled families are built from, each
against its plain formula, and the seam kept: the kernel decision, the
vocabulary-parallel head and the layer loop live in blocks.py alone, and a
seed gives the weights it gave before the families shared it."""
import ast
import hashlib
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import with_flag
from jax.sharding import Mesh

from paddle_tpu._core import device
from paddle_tpu.models import bert, blocks, gpt, llama, mla_moe

MODELS = pathlib.Path(blocks.__file__).parent


# --------------------------------------------------------------- attention

def _former(q, k, v, scale_scores, causal, mask=None):
    """The einsum-softmax path as each family wrote it before blocks.py:
    head-major operands, the family's way of scaling, tril or an additive
    mask, float32 softmax, merge."""
    b, s, h, _ = q.shape
    q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
    logits = scale_scores(jnp.einsum("bhqd,bhkd->bhqk", q, k))
    if causal:
        logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -1e30)
    if mask is not None:
        logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return jnp.swapaxes(out, 1, 2).reshape(b, s, h * v.shape[-1])


def _qkv(seq, heads, d_qk, d_v, kv_heads=None, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (2, seq, heads, d_qk), jnp.float32),
            jax.random.normal(keys[1], (2, seq, kv_heads or heads, d_qk),
                              jnp.float32),
            jax.random.normal(keys[2], (2, seq, kv_heads or heads, d_v),
                              jnp.float32))


@pytest.mark.parametrize("family", ["gpt", "bert", "bert_masked",
                                    "llama_gqa", "mla_moe"])
def test_attention_is_each_familys_former_formula(family):
    d, d_v = (24, 16) if family == "mla_moe" else (32, 32)
    q, k, v = _qkv(48, 4, d, d_v, kv_heads=2 if family == "llama_gqa"
                   else None)
    few = (k, v)
    if family == "llama_gqa":       # the former formula repeated K and V
        k, v = (jnp.repeat(a, 2, axis=2) for a in (k, v))
    mask = None
    if family == "bert_masked":
        keep = jnp.arange(48)[None, :] < jnp.array([[48], [31]])
        mask = (1.0 - keep[:, None, None, :].astype(jnp.float32)) * -1e30
    causal = not family.startswith("bert")
    if family in ("gpt", "mla_moe"):
        scale = 0.7 / math.sqrt(d)
        want = _former(q, k, v, lambda s: s * scale, causal)
    else:       # bert and llama divided by the root
        scale = 1.0 / math.sqrt(d)
        want = _former(q, k, v, lambda s: s / math.sqrt(d), causal, mask)
    got = blocks.attention(q, *few, causal=causal, scale=scale, flash=False,
                           mask=mask)
    assert got.shape == (2, 48, 4 * d_v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kv_heads", [4, 2, 1])
@pytest.mark.parametrize("window", [1, 7, 48, 100])
def test_attention_einsum_branch_with_a_window_and_fewer_kv_heads(kv_heads,
                                                                  window):
    """The second diagonal, and K/V heads shared without a repeat, against
    the former formula on repeated heads with the mask written from i, j."""
    q, k, v = _qkv(48, 4, 32, 32, kv_heads=kv_heads, seed=3)
    scale = 1.0 / math.sqrt(32)
    i, j = jnp.arange(48)[:, None], jnp.arange(48)[None, :]
    hidden = jnp.where((j <= i) & (i - j < window), 0.0, -1e30)
    want = _former(q, *(jnp.repeat(a, 4 // kv_heads, axis=2) for a in (k, v)),
                   lambda s: s * scale, False, hidden[None, None])
    got = blocks.attention(q, k, v, causal=True, scale=scale, flash=False,
                           window=window)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    if window >= 48:        # a window longer than the sequence is no window
        np.testing.assert_allclose(got, blocks.attention(
            q, k, v, causal=True, scale=scale, flash=False), rtol=1e-6)


@pytest.mark.parametrize("seq", [128, 256])
def test_attention_kernel_in_the_interpreter_equals_einsum(seq):
    q, k, v = _qkv(seq, 2, 64, 64, seed=1)
    scale = 1.0 / 8
    plain = blocks.attention(q, k, v, causal=True, scale=scale, flash=False)
    with with_flag("FLAGS_flash_interpret", True):
        assert blocks.use_flash_kernel(True, seq)
        kernel = blocks.attention(q, k, v, causal=True, scale=scale,
                                  flash=True)
    np.testing.assert_allclose(kernel, plain, rtol=2e-4, atol=2e-5)


# heads, d_qk, d_v of the families that take the kernel branch: gpt's pairs
# of 64, the latent widths in pairs (192 / 128) and alone (256 / 256), and
# a head count that cannot be paired (the head-major kernels, with swaps)
@pytest.mark.parametrize("heads, d, d_v", [(4, 64, 64), (2, 192, 128),
                                           (2, 256, 256), (3, 64, 64)],
                         ids=["gpt", "mla_192_128", "mla_256_256",
                              "three_heads"])
def test_attention_kernel_branch_is_each_familys_former_formula(heads, d,
                                                                d_v):
    """Value and the three gradients of the kernel branch, in the
    interpreter, against the formula the families wrote."""
    q, k, v = _qkv(128, heads, d, d_v, seed=2)
    w = jax.random.normal(jax.random.PRNGKey(9), (2, 128, heads * d_v),
                          jnp.float32)
    scale = 0.7 / math.sqrt(d)

    def kernel(q, k, v):
        return blocks.attention(q, k, v, causal=True, scale=scale,
                                flash=True)

    def former(q, k, v):
        return _former(q, k, v, lambda s: s * scale, True)

    with with_flag("FLAGS_flash_interpret", True):
        got, pull = jax.vjp(kernel, q, k, v)
        grads = pull(w)
    want, pull = jax.vjp(former, q, k, v)
    assert got.shape == (2, 128, heads * d_v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for a, b in zip(grads, pull(w)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("causal, masked", [(True, False), (False, False),
                                            (False, True)],
                         ids=["causal", "full", "masked"])
def test_attention_einsum_branch_traces_as_it_did(causal, masked):
    """bert, llama and every masked call take the einsum branch, whose
    jaxpr is the one `attention` gave before the kernels read the
    projections' layout: swaps, two einsums, float32 softmax, merge."""
    q, k, v = (jax.ShapeDtypeStruct((2, 128, 4, 32), jnp.bfloat16),) * 3
    mask = jax.ShapeDtypeStruct((2, 1, 1, 128), jnp.float32)

    def before(q, k, v, mask=None):
        b, s, heads, _ = q.shape
        q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.25
        if causal:
            logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits,
                               jnp.array(-1e30, logits.dtype))
        if mask is not None:
            logits = logits + mask
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(
            q.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        return jnp.swapaxes(out, 1, 2).reshape(b, s, heads * v.shape[-1])

    def now(q, k, v, mask=None):
        # `flash=True`: a mask, or the CPU without the interpreter flag,
        # is the einsum branch whatever the caller asks
        return blocks.attention(q, k, v, causal=causal, scale=0.25,
                                flash=masked, mask=mask)

    args = (q, k, v, mask) if masked else (q, k, v)
    assert str(jax.make_jaxpr(now)(*args)) == str(
        jax.make_jaxpr(before)(*args))
    grad = jax.grad(lambda f, *a: f(*a).astype(jnp.float32).sum(),
                    argnums=(1, 2, 3))
    assert str(jax.make_jaxpr(lambda *a: grad(now, *a))(*args)) == str(
        jax.make_jaxpr(lambda *a: grad(before, *a))(*args))


def test_gpt_block_projects_q_k_v_apart_from_the_one_qkv_matrix():
    """Off an `mp` mesh `gpt._block` makes q, k and v as three products on
    column blocks of `qkv_w` (what the kernels read without a copy); on
    one, as one product split by heads. The parameter tree is the same and
    so is the block's value, and its jaxpr holds three / one projection."""
    config = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                           num_heads=4, max_position_embeddings=16,
                           dtype="float32", use_flash_attention=False)
    blk = jax.tree_util.tree_map(lambda a: a[0],
                                 gpt.init_gpt_params(config, 3)["blocks"])
    blk["qkv_b"] = jnp.linspace(-1.0, 1.0, 96)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32), jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("mp",))

    def projections(mesh):
        jaxpr = jax.make_jaxpr(lambda x: gpt._block(x, blk, config, mesh))(x)
        return [e.outvars[0].aval.shape for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "dot_general"][:3]

    assert projections(None) == [(2, 16, 32)] * 3
    assert projections(mesh)[0] == (2, 16, 96)
    apart, _ = gpt._block(x, blk, config, None)
    whole, _ = gpt._block(x, blk, config, mesh)
    np.testing.assert_allclose(apart, whole, rtol=1e-5, atol=1e-6)


def _tiny_bert(seq):
    """Two layers, two heads of 64 (a pair a grid step), float32."""
    config = bert.BertConfig(vocab_size=96, hidden_size=128, num_layers=2,
                             num_heads=2, intermediate_size=256,
                             max_position_embeddings=seq, dtype="float32")
    params = bert.init_bert_params(config, 5)
    params["blocks"]["qkv_b"] = jnp.tile(
        jnp.linspace(-0.5, 0.5, 384, dtype=jnp.float32), (2, 1))
    rng = np.random.RandomState(seq)
    tokens = jnp.asarray(rng.randint(0, 96, (2, seq)), jnp.int32)
    labels = jnp.where(jnp.asarray(rng.rand(2, seq)) < 0.15, tokens, -100)
    return config, params, tokens, labels


def test_bert_loss_and_gradients_through_the_kernels_equal_the_einsum_path():
    """256 tokens and no padding mask: with the interpreter's flag bert's
    layers take the kernel branch (three qkv products, no diagonal), and
    the masked-LM loss and every parameter's gradient are the einsum
    path's."""
    config, params, tokens, labels = _tiny_bert(256)
    grad = jax.value_and_grad(lambda p: bert.bert_mlm_loss(
        p, tokens, labels, config))
    want, want_grads = grad(params)
    with with_flag("FLAGS_flash_interpret", True):
        assert "pallas_call" in str(jax.make_jaxpr(grad)(params))
        got, got_grads = grad(params)
    np.testing.assert_allclose(got, want, rtol=2e-4)
    flat, _ = jax.tree_util.tree_flatten_with_path(want_grads)
    for (path, b), a in zip(flat, jax.tree_util.tree_leaves(got_grads)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("seq, masked, interpret, kernels", [
    (256, False, True, True), (256, True, True, False),
    (256, False, False, False), (200, False, True, False)],
    ids=["kernels", "padding_mask", "cpu", "ragged"])
def test_bert_block_takes_the_kernels_only_where_they_serve_it(
        seq, masked, interpret, kernels):
    """`bert._block` always asks for the kernels and `blocks.attention`
    decides: no mask, a sequence they tile, a TPU or the interpreter's
    flag. With a padding mask, on a CPU or at a ragged length the einsum
    branch stays. Whichever branch runs, the block's value is that of the
    one [h, 3h] product on the stored `qkv_w`, split by thirds, through
    softmax attention written out here."""
    config, params, _, _ = _tiny_bert(256)
    blk = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, 128), jnp.float32)
    mask = None
    if masked:
        keep = jnp.arange(seq)[None, :] < jnp.array([[seq], [seq - 57]])
        mask = (1.0 - keep[:, None, None, :].astype(jnp.float32)) * -1e30

    def block(x):
        return bert._block(x, blk, config, mask)[0]

    q, k, v = ((x @ blk["qkv_w"] + blk["qkv_b"]).reshape(2, seq, 3, 2, 64)
               .transpose(2, 0, 3, 1, 4))                   # [B, H, S, D]
    logits = q @ k.swapaxes(-1, -2) / 8.0 + (0.0 if mask is None else mask)
    attn = (jax.nn.softmax(logits, -1) @ v).swapaxes(1, 2).reshape(
        2, seq, 128)
    y = blocks.layer_norm(x + attn @ blk["proj_w"] + blk["proj_b"],
                          blk["ln1_g"], blk["ln1_b"], config.layer_norm_eps)
    want = blocks.layer_norm(
        y + blocks.gelu_mlp(y, blk["fc_w"], blk["fc_b"], blk["fo_w"],
                            blk["fo_b"]),
        blk["ln2_g"], blk["ln2_b"], config.layer_norm_eps)
    with with_flag("FLAGS_flash_interpret", interpret):
        assert ("pallas_call" in str(jax.make_jaxpr(block)(x))) is kernels
        got = block(x)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_bert_block_on_an_mp_mesh_keeps_the_one_product_and_the_value():
    """`qkv_w`'s columns are sharded over `mp` as one matrix, so on an
    `mp` mesh the block makes the one product and splits it by heads (as
    `gpt._block`), and the kernels run inside `mha_sharded`'s shard_map;
    on a `dp` mesh the three products stay. The value is the meshless
    block's either way."""
    config, params, _, _ = _tiny_bert(256)
    blk = jax.tree_util.tree_map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 256, 128), jnp.float32)
    want = bert._block(x, blk, config)[0]
    devs = np.asarray(jax.devices()[:4]).reshape(2, 2)
    for mesh, first in ((Mesh(devs, ("dp", "mp")), (2, 256, 384)),
                        (Mesh(devs[:, 0], ("dp",)), (2, 256, 128))):
        def block(x):
            return bert._block(x, blk, config, mesh=mesh)[0]
        with with_flag("FLAGS_flash_interpret", True):
            jaxpr = jax.make_jaxpr(block)(x)
            got = jax.jit(block)(x)
        assert [e.outvars[0].aval.shape for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "dot_general"][0] == first
        assert "shard_map" in str(jaxpr) and "pallas_call" in str(jaxpr)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("flash, seq, tpu, interpret, want", [
    (True, 1024, True, False, True), (False, 1024, True, False, False),
    (True, 128, True, False, False), (True, 1000, True, False, False),
    (True, 1024, False, False, False), (True, 128, False, True, True)])
def test_kernel_or_einsum_is_decided_once(flash, seq, tpu, interpret, want,
                                          monkeypatch):
    monkeypatch.setattr(device, "is_tpu", lambda: tpu)
    with with_flag("FLAGS_flash_interpret", interpret):
        assert blocks.use_flash_kernel(flash, seq) is want


# --------------------------------------------------------------- loss head

def _np_nll(logits, labels):
    x = np.asarray(logits, np.float64)
    x = x - x.max(-1, keepdims=True)
    logp = x - np.log(np.exp(x).sum(-1, keepdims=True))
    return -np.take_along_axis(logp, np.maximum(labels, 0)[..., None],
                               -1)[..., 0]


def _logits_labels():
    rng = np.random.RandomState(3)
    return (jnp.asarray(rng.randn(2, 12, 50) * 3, jnp.bfloat16),
            rng.randint(0, 50, (2, 12)).astype(np.int32))


def test_cross_entropy_against_numpy():
    logits, labels = _logits_labels()
    want = _np_nll(logits.astype(jnp.float32), labels)
    got = blocks.cross_entropy(logits, jnp.asarray(labels))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want.mean(), rtol=1e-5)
    # masked: the mean is over the labelled positions alone
    masked = np.where(np.arange(12)[None, :] % 3 == 0, labels, -100)
    got = blocks.cross_entropy(logits, jnp.asarray(masked),
                               ignore_negative=True)
    np.testing.assert_allclose(got, want[masked >= 0].mean(), rtol=1e-5)


def test_masked_cross_entropy_with_every_or_no_position_labelled():
    logits, labels = _logits_labels()
    assert float(blocks.cross_entropy(logits, jnp.asarray(labels), True)) \
        == pytest.approx(float(blocks.cross_entropy(
            logits, jnp.asarray(labels))), rel=1e-6)
    none = jnp.full(labels.shape, -100, jnp.int32)
    assert float(blocks.cross_entropy(logits, none, True)) == 0.0


def test_lm_head_loss_on_an_mp_mesh_equals_the_unsharded_one():
    rng = np.random.RandomState(4)
    hidden = jnp.asarray(rng.randn(2, 16, 32), jnp.float32)
    head = jnp.asarray(rng.randn(64, 32) * 0.2, jnp.float32)
    labels = jnp.asarray(rng.randint(0, 64, (2, 16)), jnp.int32)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("mp",))
    f = jax.value_and_grad(blocks.lm_head_loss, argnums=(0, 1))
    want, want_g = f(hidden, head, labels)
    got, got_g = jax.jit(lambda *a: f(*a, mesh))(hidden, head, labels)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    # the sharded head never forms [B, S, V]
    text = jax.jit(lambda *a: blocks.lm_head_loss(*a, mesh)).lower(
        hidden, head, labels).as_text()
    assert "x16x64x" not in text and "x16x32x" in text


# -------------------------------------------------------------- layer loop

@pytest.mark.parametrize("remat", [False, True])
def test_scan_layers_value_and_gradient(remat):
    rng = np.random.RandomState(5)
    stacked = {"w": jnp.asarray(rng.randn(3, 8, 8) * 0.3, jnp.float32),
               "b": jnp.asarray(rng.randn(3, 8), jnp.float32)}
    x = jnp.asarray(rng.randn(4, 8), jnp.float32)

    def block(x, layer):
        y = jnp.tanh(x @ layer["w"] + layer["b"])
        return y, y.sum()

    def loop(x, stacked):
        for i in range(3):
            x, _ = block(x, jax.tree_util.tree_map(lambda a: a[i], stacked))
        return (x ** 2).sum()

    def scanned(x, stacked):
        out, ys = blocks.scan_layers(block, x, stacked, remat)
        assert ys.shape == (3,)
        return (out ** 2).sum()

    want, want_g = jax.value_and_grad(loop, argnums=(0, 1))(x, stacked)
    got, got_g = jax.value_and_grad(scanned, argnums=(0, 1))(x, stacked)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        got_g, want_g)
    text = jax.make_jaxpr(jax.grad(scanned))(x, stacked).pretty_print()
    assert ("checkpoint" in text or "remat" in text) is remat


def test_layer_trunk_is_the_pipeline_on_a_pp_axis_alone():
    def block(x, layer):
        return x, None

    devs = np.asarray(jax.devices()[:4])
    assert blocks.layer_trunk(block, None, 4, True) is None
    assert blocks.layer_trunk(
        block, Mesh(devs.reshape(2, 1, 2), ("dp", "pp", "mp")), 3,
        True) is None
    pp = Mesh(devs.reshape(1, 2, 2), ("dp", "pp", "mp"))
    assert callable(blocks.layer_trunk(block, pp, 4, True, 2))
    with pytest.raises(ValueError, match="not divisible by pp 2"):
        blocks.layer_trunk(block, pp, 3, True)


# ---------------------------------------------------------------- the seam

def _imports(path):
    """Every module a file imports, absolute from the package root."""
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom):
            base = ["paddle_tpu", "models"][:3 - node.level] \
                if node.level else []
            module = ".".join(base + ([node.module] if node.module else []))
            found.add(module)
            found.update(f"{module}.{a.name}" for a in node.names)
        elif isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
    return found


def test_kernels_and_collectives_enter_models_through_blocks_alone():
    seam = ("paddle_tpu.ops.pallas.flash_attention",
            "paddle_tpu.distributed.fleet.mp_ops",
            "paddle_tpu.distributed.pipeline_compiled")
    for path in MODELS.glob("*.py"):
        reached = [m for m in _imports(path) if m.startswith(seam)]
        if path.name == "blocks.py":
            assert all(any(m.startswith(s) for m in reached) for s in seam)
        else:
            assert not reached, (path.name, reached)
    theirs = _imports(MODELS / "mla_moe.py")
    assert not [m for m in theirs
                if m.startswith(("paddle_tpu.models.gpt",
                                 "paddle_tpu.models.llama"))]
    for family in (gpt, bert, llama, mla_moe):
        for name in ("_ln", "_rms", "_rope", "_swiglu", "_use_flash_kernel"):
            assert not hasattr(family, name), (family.__name__, name)


# --------------------------------------------------------------- the seeds

def _digest(params):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(leaf.dtype).encode())
        h.update(str(leaf.shape).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


# sha256 over every leaf's path, dtype, shape and bytes of the parameters
# seed 0 gave at commit 48462e9 (PR 27), before the families shared
# `blocks.normal`: the benchmark's reference reads the same tree, and
# `--seed` must keep meaning the same weights
SEEDED = {
    "gpt": (lambda: gpt.init_gpt_params(gpt.GPTConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        max_position_embeddings=64), 0),
        "40fe6a83f15b3e7e22b6005223520cbc6be579fb8aee8dbf082b34a04f17d0e5"),
    "bert": (lambda: bert.init_bert_params(
        bert.BERT_CONFIGS["bert-tiny"], 0),
        "9acc54cd169e8c28c0719f85fc68cc1d37234e0c25d1c8e54b0ded1e3f661f2b"),
    "llama": (lambda: llama.init_llama_params(
        llama.LLAMA_CONFIGS["llama-tiny"], 0),
        "2d14101f3b9f3f272d778756564018d17d178396fc185a4473f080de0053e0bd"),
    "mla_moe": (lambda: mla_moe.init_mla_moe_params(mla_moe.MlaMoeConfig(
        vocab_size=64, hidden_size=64, num_layers=2, first_k_dense=1,
        num_heads=4, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=64,
        moe_intermediate_size=32, n_routed_experts=8,
        experts_held=(2, 4)), 0),
        "7a3916542772c6182ae99590e6544cb194f6068dbc8f74d7517e9d5c955d1fb3"),
}


@pytest.mark.parametrize("family", sorted(SEEDED))
def test_a_seed_gives_the_weights_it_gave_before(family):
    init, want = SEEDED[family]
    assert _digest(init()) == want
