"""The latent-attention sparse-expert family (models/mla_moe.py, the
dropless path of ops/moe.py, the flash kernels at two widths) against the
plain float32 reference (benchmarks/reference/mla_moe.py), at small sizes
on the CPU with seeded weights."""
import contextlib
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
from benchmarks.reference import mla_moe as ref
from benchmarks.runners import mla_moe as runner
from paddle_tpu.models import blocks, llama
from paddle_tpu.models import mla_moe as m
from paddle_tpu.models import stages
from paddle_tpu.ops import moe
from paddle_tpu.ops.pallas import stream_mix

# the package exports a function of the module's name
fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

BATCH, SEQ = 2, 64


@pytest.fixture(scope="module")
def tiny():
    """(configuration dict, program config): the cell's `tiny` cut."""
    config = load_cell("xing4-ep8share-pretrain-s2048", tiny=True).config
    return config, runner.program_config(config)


@pytest.fixture(scope="module")
def batch(tiny):
    ids = np.random.default_rng(0).integers(
        0, tiny[0]["vocab_size"], (BATCH, SEQ + 1), dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def _reference_loss(params, tokens, labels, config):
    sums = [ref.nll(params, t, l, config) for t, l in zip(tokens, labels)]
    return sum(n for n, _ in sums) / sum(c for _, c in sums)


@pytest.fixture(scope="module")
def both(tiny, batch):
    """((loss, grads) of the program, (loss, grads) of the reference)."""
    config, c = tiny
    params = m.init_mla_moe_params(c, 3)
    program = jax.jit(jax.value_and_grad(
        lambda p: m.mla_moe_loss(p, *batch, c, remat=True)))(params)
    reference = jax.jit(jax.value_and_grad(
        lambda p: _reference_loss(p, *batch, config)))(params)
    return program, reference


def _leaf_paths():
    config = load_cell("xing4-ep8share-pretrain-s2048", tiny=True).config
    shapes = jax.eval_shape(
        lambda: m.init_mla_moe_params(runner.program_config(config), 0))
    return [jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(shapes)]


def test_loss_agrees_with_the_reference(both):
    (loss_p, _), (loss_r, _) = both
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-5)


@pytest.mark.parametrize("leaf", _leaf_paths())
def test_gradient_of_every_parameter_agrees_with_the_reference(both, leaf):
    (_, grads_p), (_, grads_r) = both
    got = {jax.tree_util.keystr(p): a for p, a in
           jax.tree_util.tree_leaves_with_path(grads_p)}[leaf]
    want = {jax.tree_util.keystr(p): a for p, a in
            jax.tree_util.tree_leaves_with_path(grads_r)}[leaf]
    if leaf.endswith("['router_b']"):
        # the selection bias steers the choice only: no gradient reaches it
        assert not np.asarray(got).any() and not np.asarray(want).any()
        return
    # relative to the largest entry; the first layer's streams are copies
    # of one another, so its mixture biases' gradients are rounding alone
    scale = max(float(jnp.abs(want).max()), 1e-6)
    assert float(jnp.abs(got - want).max()) <= 2e-4 * scale


def test_weight_decay_masks_agree(tiny):
    params = jax.eval_shape(lambda: m.init_mla_moe_params(tiny[1], 0))
    assert m.wd_mask(params) == ref.decayed(params)
    assert m.wd_mask(params)["sparse"]["router_b"] is False
    assert m.wd_mask(params)["sparse"]["experts"]["down_w"] is True


# ------------------------------------------------------------- the shares

SHARES = 8
SHARE_CONFIG = dict(
    hidden_size=64, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
    n_shared_experts=1, num_experts_per_tok=4, routed_scaling_factor=2,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"})
ALL_HEADS, ALL_EXPERTS = 32, 64


def _uncut():
    return m.MlaMoeConfig(
        vocab_size=64, hidden_size=64, num_layers=2, first_k_dense=1,
        num_heads=ALL_HEADS, q_lora_rank=32, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=64, moe_intermediate_size=32,
        n_routed_experts=ALL_EXPERTS, rope_scaling=SHARE_CONFIG[
            "rope_scaling"], use_flash_attention=False, dtype="float32")


@pytest.fixture(scope="module")
def uncut_layer():
    """One sparse layer's parameters with every head and every expert, and
    a normed input [1, s, h]."""
    c = _uncut()
    params = m.init_mla_moe_params(c, 5)
    blk = jax.tree_util.tree_map(lambda a: a[0] * 8.0 if a.ndim > 2 else a[0],
                                 params["sparse"])
    y = jax.random.normal(jax.random.PRNGKey(9), (1, 48, 64), jnp.float32)
    return c, blk, y


def test_the_attention_shares_add_up_to_the_uncut_layer(uncut_layer):
    """Heads 4 at a time over 8 shares: each share's partial output
    projection, summed, is the reference's whole attention."""
    c, blk, y = uncut_layer
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    held = ALL_HEADS // SHARES
    share = dataclasses.replace(c, heads_held=held)
    total = 0
    for i in range(SHARES):
        cols = slice(i * held, (i + 1) * held)
        part = dict(blk)
        part["q_b_w"] = blk["q_b_w"].reshape(-1, ALL_HEADS, dn + dr)[
            :, cols].reshape(c.q_lora_rank, -1)
        part["kv_b_w"] = blk["kv_b_w"].reshape(-1, ALL_HEADS, dn + dv)[
            :, cols].reshape(c.kv_lora_rank, -1)
        part["o_w"] = blk["o_w"].reshape(ALL_HEADS, dv, -1)[cols].reshape(
            held * dv, -1)
        total = total + m._attention(y, part, share)[0]
    want = ref.attention(ref.rms_norm(y[0], blk["ln1_g"], 1e-6), blk,
                         SHARE_CONFIG)
    np.testing.assert_allclose(total[0], want, rtol=2e-4, atol=2e-5)


def test_the_expert_shares_add_up_to_the_uncut_layer(uncut_layer):
    """Experts 8 at a time over 8 shares, each routing over all 64: the
    partial results summed, with the shared expert (which every chip
    computes alike) counted once, are the reference's whole layer."""
    c, blk, y = uncut_layer
    held = ALL_EXPERTS // SHARES
    total, chosen = 0, None
    for i in range(SHARES):
        share = dataclasses.replace(c, experts_held=(i * held, held))
        part = dict(blk, experts={k: a[i * held:(i + 1) * held]
                                  for k, a in blk["experts"].items()})
        out, ids = m._sparse_ffn(y, part, share)
        total = total + out
        chosen = ids
    x = ref.rms_norm(y[0], blk["ln2_g"], 1e-6)
    shared = ref.swiglu(x, blk["shared_gate_w"], blk["shared_up_w"],
                        blk["shared_down_w"])
    config = dict(SHARE_CONFIG, n_routed_experts=ALL_EXPERTS,
                  deployment={"experts_first": 0})
    want = ref.sparse_ffn(x, blk, config)
    np.testing.assert_allclose(total[0] - (SHARES - 1) * shared, want,
                               rtol=2e-4, atol=2e-5)
    # every share saw the same choices, over all the experts
    assert chosen.shape == (48, 4) and int(chosen.max()) >= held
    assert float(jnp.abs(want - shared).max()) > 1e-3    # the routed part


# ---------------------------------------------------------- dropless path

def _experts(key, n, width, hidden):
    k = jax.random.split(key, 3)
    return {"gate_w": jax.random.normal(k[0], (n, hidden, width)) * 0.2,
            "up_w": jax.random.normal(k[1], (n, hidden, width)) * 0.2,
            "down_w": jax.random.normal(k[2], (n, width, hidden)) * 0.2}


def _loop_experts(x, ids, weights, experts, held):
    first, count = held
    out = 0
    for i in range(count):
        mine = (weights * (ids == first + i)).sum(-1, keepdims=True)
        out = out + mine * ref.swiglu(x, experts["gate_w"][i],
                                      experts["up_w"][i],
                                      experts["down_w"][i])
    return out


@pytest.mark.parametrize("favoured", [(2,), (0, 1, 2, 3), ()],
                         ids=["one-held-expert", "every-pair-held",
                              "unbiased"])
def test_dropless_under_skew(favoured):
    """A router biased so that every token chooses the favoured held
    experts: no pair is dropped, whatever share of the T*k pairs lands
    here, and the gradients are the loop's."""
    t, hidden, width, e, k, held = 96, 32, 16, 16, 4, (0, 4)
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(keys[0], (t, hidden))
    w_r = jax.random.normal(keys[1], (hidden, e)) * 0.3
    bias = jnp.zeros(e).at[jnp.asarray(favoured, jnp.int32)].set(10.0)
    experts = _experts(keys[2], held[1], width, hidden)

    def run(fn, x, w_r, experts):
        ids, weights = moe.sigmoid_topk_route(x, w_r, bias, k, 2.0)
        return fn(x, ids, weights, experts, held), ids

    (got, ids), (want, _) = (run(fn, x, w_r, experts) for fn in
                             (moe.held_experts_ffn, _loop_experts))
    for expert in favoured:
        assert bool((ids == expert).any(-1).all())
    if len(favoured) == k:
        assert bool((ids < held[1]).all())      # the whole buffer is held
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    grads = [jax.grad(lambda *a: (run(fn, *a)[0] ** 2).sum(),
                      argnums=(0, 1, 2))(x, w_r, experts)
             for fn in (moe.held_experts_ffn, _loop_experts)]
    for a, b in zip(*(jax.tree_util.tree_leaves(g) for g in grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))


def test_the_router_follows_its_equations():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 8))
    w_r = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    bias = jnp.asarray([0.0, 0.0, 5.0, 0.0, 0.0, 0.0])
    ids, weights = moe.sigmoid_topk_route(x, w_r, bias, 2, 2.0)
    score = 1 / (1 + np.exp(-np.asarray(x, np.float64)
                            @ np.asarray(w_r, np.float64)))
    for row in range(5):
        want = np.argsort(-(score[row] + np.asarray(bias)))[:2]
        assert sorted(np.asarray(ids[row])) == sorted(want)
        assert 2 in ids[row]                    # the bias chose it ...
        picked = score[row][np.asarray(ids[row])]   # ... its weight is s's
        np.testing.assert_allclose(weights[row], 2 * picked / picked.sum(),
                                   rtol=1e-5)


# --------------------------------------------------------------- Sinkhorn

def test_sinkhorn_is_doubly_stochastic_and_differentiable():
    """After 20 iterations the columns sum to 1 to rounding (the last step
    normalises them) and the rows carry what is left of the alternation:
    within 2e-4 at the logits the configuration starts from (2 I + N(0,
    0.5^2)), within 5e-2 at logits four times as wide; gradients flow."""
    noise = jax.random.normal(jax.random.PRNGKey(2), (4, 4, 3, 50))
    wide = m.sinkhorn(jnp.exp(2.0 * noise), 20, 1e-6)
    assert float(jnp.abs(wide.sum(0) - 1).max()) < 1e-5
    assert float(jnp.abs(wide.sum(1) - 1).max()) < 5e-2
    raw = 2.0 * jnp.eye(4)[:, :, None, None] + 0.5 * noise

    def doubly(raw):
        return m.sinkhorn(jnp.exp(raw), 20, 1e-6)

    got = doubly(raw)
    assert float(jnp.abs(got.sum(0) - 1).max()) < 1e-5
    assert float(jnp.abs(got.sum(1) - 1).max()) < 2e-4
    assert float(got.min()) > 0
    want = ref.sinkhorn(jnp.exp(jnp.moveaxis(raw, (0, 1), (-2, -1))), 20,
                        1e-6)
    np.testing.assert_allclose(jnp.moveaxis(got, (0, 1), (-2, -1)), want,
                               rtol=1e-5)
    grad = jax.grad(lambda r: (doubly(r) ** 2).sum())(raw)
    assert bool(jnp.isfinite(grad).all()) and float(jnp.abs(grad).max()) > 1e-3


def test_mixing_at_the_identity_is_a_plain_residual(tiny):
    """H_res = I, H_pre picking stream 0 and H_post = 1 give x + f(x) on
    stream 0: the residual path the other families have."""
    _, c = tiny
    n, h = c.hc_mult, c.hidden_size
    hc = {"norm_g": jnp.ones(n * h), "phi": jnp.zeros((n * h, 2 * n + n * n)),
          "alpha": jnp.zeros(3),
          "b_pre": jnp.asarray([40.0] + [-40.0] * (n - 1)),
          "b_post": jnp.zeros(n), "b_res": 40.0 * jnp.eye(n) - 20.0}
    x = jax.random.normal(jax.random.PRNGKey(3), (n, 2, 8, h))
    out, _ = m._sublayer(x, hc, lambda y: (jnp.tanh(y), None), c)
    np.testing.assert_allclose(out[0], x[0] + jnp.tanh(x[0]), atol=1e-5)
    np.testing.assert_allclose(out[1], x[1] + jnp.tanh(x[0]), atol=1e-5)


# ------------------------------------------ the mixing kernels' oracle

_HIGHEST = jax.lax.Precision.HIGHEST
MIX = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0))


def mix_coefficients(x, hc, n, iters, eps, clamp):
    """The formulation models/mla_moe.py had before the kernels, as `jnp`:
    the streams x [n, B, S, h] -> (H_pre [n, B, S], H_post [n, B, S], H_res
    [n, n, B, S]) in float32: per token, u = RMSNorm(vec(x)); three
    projections of u with their scales and biases; a sigmoid, twice a
    sigmoid, and Sinkhorn of the clipped exponential."""
    xf = x.astype(jnp.float32)
    rinv = jax.lax.rsqrt((xf * xf).mean((0, 3), keepdims=True) + eps)
    u = xf * rinv * hc["norm_g"].reshape(n, 1, 1, -1)
    proj = jnp.einsum("nbsh,nhk->kbs", u,
                      hc["phi"].reshape(n, x.shape[-1], -1),
                      precision=_HIGHEST)                    # [2n+n*n, B, S]
    a = hc["alpha"]
    pre = a[0] * proj[:n] + hc["b_pre"][:, None, None]
    post = a[1] * proj[n:2 * n] + hc["b_post"][:, None, None]
    res = a[2] * proj[2 * n:].reshape((n, n) + proj.shape[1:]) \
        + hc["b_res"][:, :, None, None]
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            m.sinkhorn(jnp.exp(jnp.clip(res, *clamp)), iters, eps))


def oracle_read_in(x, hc, iters, eps, clamp):
    """-> (h_in = sum_i H_pre[i] x[i], H_post over H_res row by row, x),
    elementwise in float32: what `stream_mix.read_in` returns."""
    n = x.shape[0]
    h_pre, h_post, h_res = mix_coefficients(x, hc, n, iters, eps, clamp)
    h_in = sum(h_pre[j][..., None] * x[j].astype(jnp.float32)
               for j in range(n)).astype(x.dtype)
    return h_in, jnp.concatenate(
        [h_post, h_res.reshape((n * n,) + h_post.shape[1:])]), x


def oracle_write_back(x, y, mix):
    """x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y."""
    n = x.shape[0]
    h_post, h_res = mix[:n], mix[n:].reshape((n, n) + mix.shape[1:])
    streams = [x[j].astype(jnp.float32) for j in range(n)]
    yf = y.astype(jnp.float32)
    return jnp.stack([
        sum(h_res[i, j][..., None] * streams[j] for j in range(n))
        + h_post[i][..., None] * yf for i in range(n)]).astype(x.dtype)


def _mixing_parameters(n, h):
    k = jax.random.split(jax.random.PRNGKey(n), 5)
    f32 = jnp.float32
    return {"norm_g": 1 + 0.1 * jax.random.normal(k[0], (n * h,), f32),
            "phi": jax.random.normal(k[1], (n * h, 2 * n + n * n), f32) * 0.3,
            "alpha": jnp.asarray([0.3, 0.2, 0.4], f32),
            "b_pre": jax.random.normal(k[2], (n,), f32),
            "b_post": jax.random.normal(k[3], (n,), f32) * 0.5,
            "b_res": 2.0 * jnp.eye(n, dtype=f32)
            + jax.random.normal(k[4], (n, n), f32) * 0.5}


@functools.lru_cache(maxsize=None)
def _mixing_both(n, tokens, dtype):
    """{name: (the kernels', the oracle's)} for every output and every
    cotangent of the two halves, each half on its own with seeded
    cotangents: read-in forward and backward, write-back forward and
    backward."""
    h, dt = 128, jnp.dtype(dtype)
    k = jax.random.split(jax.random.PRNGKey(7), 6)

    def normal(key, shape, dtype=dt):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    hc = _mixing_parameters(n, h)
    x = normal(k[0], (n,) + tokens + (h,))
    y = normal(k[1], tokens + (h,))
    found = {}

    def both(fn_new, fn_old, args, cotangent, outputs, gradients):
        for i, fn in enumerate((fn_new, fn_old)):
            out, pull = jax.vjp(fn, *args)
            grads = pull(cotangent(out))
            for name, value in list(zip(outputs, jax.tree_util.tree_leaves(
                    out))) + list(zip(gradients, jax.tree_util.tree_leaves(
                        grads))):
                found.setdefault(name, [None, None])[i] = value

    mix_shape = (n + n * n,) + tokens
    both(lambda x, hc: stream_mix.read_in(x, hc, **MIX),
         lambda x, hc: oracle_read_in(x, hc, **MIX), (x, hc),
         lambda out: (normal(k[2], out[0].shape),
                      normal(k[3], mix_shape, jnp.float32),
                      normal(k[4], x.shape)),
         ("h_in", "mix"),
         ("read_in dx", "d alpha", "d b_post", "d b_pre", "d b_res",
          "d norm_g", "d phi"))
    mix = found["mix"][1]
    both(stream_mix.write_back, oracle_write_back, (x, y, mix),
         lambda out: normal(k[5], out.shape), ("x_out",),
         ("write_back dx", "dy", "d mix"))
    return found


MIXING_CASES = [(n, tokens, dtype) for n in (2, 4)
                for tokens in ((2, 64), (3, 50))        # 128 and 150 tokens
                for dtype in ("float32", "bfloat16")]
MIXING_OUTPUTS = ("h_in", "mix", "x_out", "read_in dx", "write_back dx", "dy",
                  "d mix", "d alpha", "d b_pre", "d b_post", "d b_res",
                  "d norm_g", "d phi")


@pytest.mark.parametrize("what", MIXING_OUTPUTS)
@pytest.mark.parametrize("n, tokens, dtype", MIXING_CASES)
def test_mixing_kernels_against_the_oracle(n, tokens, dtype, what):
    """The four kernels (interpreted) against `jax.vjp` of the `jnp`
    formulation: a tile of 128 tokens, so 128 tokens are one tile and 150
    are padded. What is float32 agrees to float32 rounding; what is
    rounded to bfloat16 streams may differ by one rounding (the oracle's
    stream cotangent is rounded three times, the kernels' once)."""
    got, want = _mixing_both(n, tokens, dtype)[what]
    assert got.shape == want.shape and got.dtype == want.dtype
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    rounded = got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2.0 ** -6 if rounded else 2e-5,
        atol=(2.0 ** -8 if rounded else 2e-5) * scale)


def test_read_in_hands_the_streams_on():
    """`read_in` returns x itself for `write_back`, so that x has one
    consumer: the two halves' cotangents meet inside the read-in's
    backward kernel and in no XLA add."""
    n, h = 2, 128
    hc = _mixing_parameters(n, h)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, 1, 128, h), jnp.float32)

    def sublayer(x):
        h_in, mix, x = stream_mix.read_in(x, hc, **MIX)
        return stream_mix.write_back(x, jnp.tanh(h_in), mix).sum()

    np.testing.assert_array_equal(stream_mix.read_in(x, hc, **MIX)[2], x)
    jaxpr = str(jax.make_jaxpr(jax.grad(sublayer))(x))
    # (Sinkhorn's `jax.vjp` inside the kernel adds [n, n, tile] ones)
    assert "f32[2,2,128] = add_any" in jaxpr
    assert not re.search(r"f32\[2,(?:1,128|128),128\] = add_any", jaxpr)


def test_a_width_that_cannot_be_tiled_is_a_named_error(monkeypatch):
    monkeypatch.setattr(stream_mix, "pallas_interpret", lambda: False)
    with pytest.raises(stream_mix.StreamWidthError, match="multiple of 128"):
        stream_mix.tiling(4, 4096, 3000, jnp.bfloat16)
    with pytest.raises(stream_mix.StreamWidthError, match="MiB"):
        stream_mix.tiling(4, 4096, 32768, jnp.bfloat16)
    assert stream_mix.tiling(4, 4096, 3584, jnp.bfloat16) == (128, 512)
    assert stream_mix.tiling(2, 100, 1024, jnp.float32) == (128, 512)


# ------------------------------------------------------------------- yarn

def test_yarn_against_the_closed_forms():
    scaling = SHARE_CONFIG["rope_scaling"]
    dim, base = 64, 10000.0
    got = blocks.yarn_inv_freq(dim, base, scaling)
    plain = [base ** (-2 * i / dim) for i in range(dim // 2)]

    def corr(r):
        return dim * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(base))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    for i in range(dim // 2):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = plain[i] / 64 * ramp + plain[i] * (1 - ramp)
        assert got[i] == pytest.approx(want, rel=1e-6)
    assert got[low] == pytest.approx(plain[low], rel=1e-6)        # kept
    assert got[high] == pytest.approx(plain[high] / 64, rel=1e-6)  # scaled
    np.testing.assert_allclose(
        got, ref.yarn_inv_freq({"qk_rope_head_dim": dim, "rope_theta": base,
                                "rope_scaling": scaling}), rtol=1e-6)
    np.testing.assert_allclose(blocks.yarn_inv_freq(dim, base, None),
                               plain,
                               rtol=1e-6)
    c = m.MlaMoeConfig(rope_scaling=scaling)
    want = 192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2
    assert m.attention_scale(c) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(1.4159 ** 2 / 13.856, rel=1e-3)
    assert m.attention_scale(m.MlaMoeConfig()) == pytest.approx(192 ** -0.5)
    with pytest.raises(NotImplementedError):
        m.attention_scale(m.MlaMoeConfig(
            rope_scaling=dict(scaling, mscale_all_dim=0.5)))


# ------------------------------------------------ the kernels, two widths

def _einsum_attention(q, k, v, causal, scale):
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones(s.shape[-2:], bool),
                        k.shape[1] - q.shape[1])
        s = jnp.where(mask, s, -jnp.inf)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("d_qk, d_v", [(192, 128), (64, 64), (48, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernels_at_two_widths_against_einsum(d_qk, d_v, causal):
    """Forward and backward in the interpreter; `d_v = d_qk` is what the
    gpt cells run."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    bh, s = 3, 256
    q = jax.random.normal(keys[0], (bh, s, d_qk), jnp.float32)
    k = jax.random.normal(keys[1], (bh, s, d_qk), jnp.float32)
    v = jax.random.normal(keys[2], (bh, s, d_v), jnp.float32)
    w = jax.random.normal(keys[3], (bh, s, d_v), jnp.float32)
    scale = 0.7 / math.sqrt(d_qk)

    def flash(q, k, v):
        return fa.mha_forward(q, k, v, causal=causal, scale=scale)

    def plain(q, k, v):
        return _einsum_attention(q, k, v, causal, scale)

    out = flash(q, k, v)
    assert out.shape == (bh, s, d_v)
    np.testing.assert_allclose(out, plain(q, k, v), rtol=2e-4, atol=2e-5)
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: (plain(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
    # [B, H, S, D] in, the same rank out, v's width
    four = flash(q[None], k[None], v[None])
    assert four.shape == (1, bh, s, d_v)
    np.testing.assert_array_equal(four[0], out)


def test_one_width_is_what_it_was():
    """`d_v = d_qk` (or no `d_v`) gives the footprint formula the kernels
    had with one width, the cap that formula gave, and the second width
    moves it."""
    blk = functools.partial(fa._vmem_block_bytes, dtype=jnp.bfloat16)
    f32 = functools.partial(fa._vmem_block_bytes, dtype=jnp.float32)
    for s, d in ((1024, 64), (2048, 128), (2560, 192)):
        bq, bk = fa._block_sizes(s, s, d)
        old = {"fwd": (2 * (2 * blk(bq, d) + 2 * blk(s, d) + f32(1, bq))
                       + f32(bk, bq) + blk(bk, bq) + 2 * f32(d, bq)),
               "bwd": (2 * (3 * blk(s, d) + 4 * blk(bk, d)
                            + 2 * (s // bq) * f32(1, bq))
                       + f32(d, s) + 3 * f32(bk, bq) + 2 * f32(bk, d))}
        assert fa.vmem_footprint(s, s, d, jnp.bfloat16) == old
        assert fa.vmem_footprint(s, s, d, jnp.bfloat16, d) == old
    # the caps that formula gave with 512-key blocks (6,144 / 2,560 /
    # 3,072), one step of 512 further since the backward halves its key
    # block where the whole one does not fit (`_bwd_block_k`)
    assert fa.max_seq(64, jnp.bfloat16, True) == 7168
    assert fa.max_seq(192, jnp.bfloat16, True) == 3072
    assert fa.max_seq(192, jnp.bfloat16, True, 128) == 3584
    for d, dv, before in ((64, 64, 6144), (192, 192, 2560),
                          (192, 128, 3072)):
        assert fa._bwd_block_k(before, before, d, dv, jnp.bfloat16) == 512
        assert fa._bwd_block_k(before + 512, before + 512, d, dv,
                               jnp.bfloat16) == 256


def test_the_limit_message_names_both_widths(monkeypatch):
    monkeypatch.setattr(fa, "pallas_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((2, 4096, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 4096, 128), jnp.bfloat16)
    with pytest.raises(fa.FlashSequenceLimitError) as err:
        fa._check_vmem(q, q, v, backward=True)
    assert "head_dim 192 (q, k) and 128 (v)" in str(err.value)
    assert "3584 with the backward" in str(err.value)
    assert "1 head a grid step" in str(err.value)


# ------------------------------------------------- scopes in the real step

THROUGH_BLOCK = stages.BLOCK + (stages.RESIDUAL_MIX,)
SPARSE_ONLY = (stages.ROUTER, stages.EXPERTS)
# what stands under no scope: the scans and the checkpoints, as in
# tests/test_model_stages.py, here two scans, and the sum of the streams'
# two cotangents (each sub-layer reads them twice, in and back)
SCAN_PLUMBING = re.compile(
    r"^(?:broadcast_in_dim|while(?:/cond/lt|/body/(?:add|sub|lt|select_n"
    r"|dynamic_slice|squeeze|dynamic_update_slice|broadcast_in_dim"
    r"|closed_call(?:/(?:remat2|checkpoint(?:/add_any)?|add_any))?))?)$")


def _lowered(c, remat):
    init_fn, step = m.build_train_step(c, remat=remat)
    state = jax.eval_shape(lambda: init_fn(0))
    tokens = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    return step.trace(state, tokens, tokens).lower()


@functools.lru_cache(maxsize=None)
def _step_text(remat):
    config = load_cell("xing4-ep8share-pretrain-s2048", tiny=True).config
    return _lowered(runner.program_config(config), remat).compile().as_text()


def _paths(hlo_text):
    return {path for op_name in _stages.op_names(hlo_text).values()
            for path in op_name.split(";")
            if path.startswith("jit(step_fn)/")}


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "plain"])
def test_every_stage_in_every_direction(remat):
    placed = {path: _stages.place(path, stages)
              for path in _paths(_step_text(remat))}
    through = ("forward", "remat", "backward") if remat \
        else ("forward", "backward")
    want = {(s, d) for s in THROUGH_BLOCK + SPARSE_ONLY for d in through} | {
        (s, d) for s in (stages.EMBED, stages.LOSS_HEAD)
        for d in ("forward", "backward")} | {(stages.OPTIMIZER, "update")}
    assert {found for found in placed.values() if found[0]} == want
    unscoped = {re.sub(r"^jit\(step_fn\)/(?:transpose\(jvp\(\)\)|jvp\(\))/",
                       "", path)
                for path, (stage, _) in placed.items() if stage is None}
    assert [p for p in sorted(unscoped) if not SCAN_PLUMBING.match(p)] == []


def test_the_scopes_change_no_computation(monkeypatch):
    def computation(text):
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        return re.sub(r"\n\nFileNames\n.*?\n\n\n", "\n\n", text, count=1,
                      flags=re.DOTALL)

    scoped = _step_text(True)
    assert f"/{stages.EXPERTS}/" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    config = load_cell("xing4-ep8share-pretrain-s2048", tiny=True).config
    bare = _lowered(runner.program_config(config), True).compile().as_text()
    assert f"/{stages.EXPERTS}/" not in bare
    assert computation(bare) == computation(scoped)


def test_the_new_stages_are_appended_and_the_dense_block_keeps_its_four():
    assert stages.ALL[:7] == ("embed", "attn_qkv", "attn_core", "attn_out",
                              "mlp", "loss_head", "optimizer")
    assert stages.ALL[7:11] == (stages.ROUTER, stages.EXPERTS,
                                stages.RESIDUAL_MIX, stages.MTP)
    assert stages.ALL[11:] == (stages.LINEAR_ATTN, stages.ATTN_GATE)
    assert len(stages.BLOCK) == 4 and not set(stages.BLOCK) & set(
        stages.ALL[7:])


# ---------------------------------------------------------------- the step

def test_the_step_trains_and_counts_its_routing(tiny, batch):
    """One XLA program a step through `trainer.build_adamw_train_step`: the
    loss falls on a repeated batch, the router's bias stays at zero, and
    `routing_stats` accounts for every pair."""
    _, c = tiny
    init_fn, step = m.build_train_step(c, lr=1e-2)
    state = init_fn(1)
    losses = []
    for _ in range(4):
        state, loss = step(state, *batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.05 and all(map(math.isfinite, losses))
    assert not np.asarray(state["params"]["sparse"]["router_b"]).any()
    counts = np.asarray(jax.jit(functools.partial(
        m.routing_stats, config=c))(state["params"], batch[0]))
    assert counts.shape == (c.sparse_layers, c.n_routed_experts)
    assert (counts.sum(1) == BATCH * SEQ * c.num_experts_per_tok).all()


@pytest.mark.parametrize("family, cell", [
    (m, "xing4-ep8share-pretrain-s2048"),
    (llama, "mellum2-ep4share-pretrain-s4096")], ids=["latent", "decoder"])
def test_a_mesh_of_several_chips_is_refused(family, cell):
    """Both sparse families run a routed share on one chip, and refuse a
    mesh of several in one place (`routed.build_train_step`)."""
    from jax.sharding import Mesh
    cell = load_cell(cell, tiny=True)
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2, 1), ("dp", "mp"))
    with pytest.raises(NotImplementedError, match="'ep' mesh axis"):
        family.build_train_step(cell.runner.program_config(cell.config),
                                mesh)


def test_count_params_of_the_published_share():
    """The benchmark's cut at the published widths: the figures of ISSUE 26
    (7.77 M of attention, 0.72 M of mixing a layer, 656 M in all)."""
    config = load_cell("xing4-ep8share-pretrain-s2048").config
    counts = m.count_params(runner.program_config(config))
    assert counts["embedding_and_head"] == 2 * 16384 * 3584
    assert counts["routed_experts"] == 4 * 8 * 3 * 3584 * 1024
    attention = 3584 * 768 + 768 + 768 * 4 * 192 + 3584 * 576 + 512 \
        + 512 * 4 * 256 + 4 * 128 * 3584
    mixing = 2 * (4 * 3584 * 24 + 4 * 3584 + 3 + 4 + 4 + 16)
    assert counts["dense_layers"] == attention + mixing + 2 * 3584 \
        + 3 * 3584 * 9216
    assert counts["total"] == 656_270_606
