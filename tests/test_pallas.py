"""Pallas kernel numerics vs reference jnp implementations (interpret mode
on the CPU test mesh — same kernel code that runs compiled on TPU)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import (flash_attention, mha_forward, rms_norm,
                                   swiglu, fused_rotary_position_embedding)


def _ref_scores(q, k, causal, scale, precision=None):
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   precision=precision).astype(jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -1e30)
    return s


def _ref_attn(q, k, v, causal, scale, precision=None):
    # [BH, S, D] fp32 reference
    p = jax.nn.softmax(_ref_scores(q, k, causal, scale, precision), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(q.dtype), v,
                      precision=precision)


@pytest.mark.parametrize("causal", [False, True])
def test_mha_forward_matches_reference(causal):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 256, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, 256, 64), jnp.float32)
    v = jnp.asarray(rng.randn(2, 256, 64), jnp.float32)
    scale = 1.0 / 8.0
    out = mha_forward(q, k, v, causal=causal, scale=scale)
    ref = _ref_attn(q, k, v, causal, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_mha_grads_match_reference(causal):
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 128, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 32), jnp.float32)
    scale = 0.17

    def loss_pallas(q, k, v):
        return jnp.sum(mha_forward(q, k, v, causal=causal, scale=scale) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_ref_attn(q, k, v, causal, scale) ** 2)

    g1 = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# normalized max error of a bfloat16 gradient against the float32 reference:
# the outputs are rounded to bfloat16 (2^-9 a value) and so are p and ds on
# their way into the MXU; chip_smoke.py's BF16_TOL is the same figure
BF16_GRAD_TOL = 2e-2


def _weighted_grads(attn, q, k, v, w):
    return jax.grad(lambda q, k, v: jnp.sum(
        attn(q, k, v).astype(jnp.float32) * w), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("sq,sk", [(256, 256), (256, 512)],
                         ids=["self", "cross_with_offset"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_mha_backward_over_many_tiles(causal, sq, sk, dtype, monkeypatch):
    """128-wide blocks, so one key block meets query blocks that see none
    of it, that the diagonal crosses and that see all of it."""
    monkeypatch.setattr(
        importlib.import_module("paddle_tpu.ops.pallas.flash_attention"),
        "MAX_BLOCK", 128)
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(2, sq, 64), dtype)
    k = jnp.asarray(rng.randn(2, sk, 64), dtype)
    v = jnp.asarray(rng.randn(2, sk, 64), dtype)
    w = jnp.asarray(rng.randn(2, sq, 64), jnp.float32)
    scale = 0.125 if dtype == jnp.bfloat16 else 0.17
    got = _weighted_grads(lambda q, k, v: mha_forward(
        q, k, v, causal=causal, scale=scale), q, k, v, w)
    want = _weighted_grads(
        lambda q, k, v: _ref_attn(q, k, v, causal, scale,
                                  jax.lax.Precision.HIGHEST),
        *(a.astype(jnp.float32) for a in (q, k, v)), w)
    for g, r in zip(got, want):
        assert g.dtype == dtype
        g, r = np.asarray(g, np.float32), np.asarray(r)
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)
        else:
            assert np.abs(g - r).max() / np.abs(r).max() <= BF16_GRAD_TOL


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_mha_backward_is_one_kernel_on_narrow_operands(causal):
    """dQ, dK and dV come out of ONE pallas_call, and with bfloat16 inputs
    none of its products takes a float32 operand (p and ds are rounded to
    the inputs' dtype; the accumulation stays float32)."""
    a = jax.ShapeDtypeStruct((2, 256, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(mha_forward(
        q, k, v, causal=causal).astype(jnp.float32)), argnums=(0, 1, 2)))(
            a, a, a)
    calls = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    backward = [e for e in calls
                if sum(o.aval.shape == a.shape for o in e.outvars) == 3]
    assert len(calls) == 2 and len(backward) == 1       # forward, backward
    dots = [e for e in _eqns(backward[0].params["jaxpr"])
            if e.primitive.name == "dot_general"]
    # five products a tile; the causal kernel holds the loop twice, once
    # with the diagonal's compare and once without
    assert len(dots) == (10 if causal else 5)
    for dot in dots:
        assert [v.aval.dtype for v in dot.invars] == [jnp.bfloat16] * 2
        assert dot.outvars[0].aval.dtype == jnp.float32


# (max block, sq, sk): 128-wide blocks so that one query block meets key
# blocks it sees whole, key blocks the diagonal crosses and key blocks it
# skips; then the real 512-wide blocks, each walked in four sub-blocks
@pytest.mark.parametrize("d,dv", [(64, 64), (192, 128), (48, 32)],
                         ids=["d64", "mla_192_128", "d48_32"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("max_block,sq,sk", [
    (128, 256, 256), (128, 256, 512), (512, 1024, 1024), (512, 512, 1024)],
    ids=["self", "cross_with_offset", "self_512", "cross_with_offset_512"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_mha_forward_over_many_tiles(causal, max_block, sq, sk, dtype, d, dv,
                                     monkeypatch):
    """Output and the saved log-sum-exp rows against a float32 HIGHEST
    reference, over masked, unmasked and skipped tiles."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "MAX_BLOCK", max_block)
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(2, sq, d), dtype)
    k = jnp.asarray(rng.randn(2, sk, d), dtype)
    v = jnp.asarray(rng.randn(2, sk, dv), dtype)
    scale = 0.125 if dtype == jnp.bfloat16 else 0.17
    out, (_, _, _, _, lse) = fa._fwd_res(q, k, v, 1, 1, causal, scale)
    bq, bk = fa._block_sizes(sq, sk, d)
    assert (bq, bk) == (max_block, max_block)
    assert out.dtype == dtype and out.shape == (2, sq, dv)
    assert lse.dtype == jnp.float32 \
        and lse.shape == (2, 1, sq // bq, 1, bq)
    q32, k32, v32 = (a.astype(jnp.float32) for a in (q, k, v))
    want = np.asarray(_ref_attn(q32, k32, v32, causal, scale,
                                jax.lax.Precision.HIGHEST))
    want_lse = np.asarray(jax.scipy.special.logsumexp(_ref_scores(
        q32, k32, causal, scale, jax.lax.Precision.HIGHEST), axis=-1))
    got = np.asarray(out, np.float32)
    got_lse = np.asarray(lse).reshape(2, sq)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got_lse, want_lse, rtol=1e-4, atol=1e-4)
    else:
        assert np.abs(got - want).max() / np.abs(want).max() <= BF16_GRAD_TOL
        # the scores are float32 sums of exact bfloat16 products
        np.testing.assert_allclose(got_lse, want_lse, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("sq,sk", [(192, 192), (128, 192), (320, 320)],
                         ids=["s192", "q128_k192", "s320"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_mha_takes_a_ragged_length_as_one_block(causal, sq, sk):
    """A length that no block divides is one block of its own size, which
    the forward does not walk in sub-blocks."""
    rng = np.random.RandomState(12)
    q = jnp.asarray(rng.randn(2, sq, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, sk, 64), jnp.float32)
    v = jnp.asarray(rng.randn(2, sk, 64), jnp.float32)
    w = jnp.asarray(rng.randn(2, sq, 64), jnp.float32)
    out = mha_forward(q, k, v, causal=causal, scale=0.125)
    ref = _ref_attn(q, k, v, causal, 0.125, jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    got = _weighted_grads(lambda q, k, v: mha_forward(
        q, k, v, causal=causal, scale=0.125), q, k, v, w)
    want = _weighted_grads(lambda q, k, v: _ref_attn(
        q, k, v, causal, 0.125, jax.lax.Precision.HIGHEST), q, k, v, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("sq,sk,bq,bk,causal,want", [
    (1024, 1024, 512, 512, True, (2, 1, 1)),        # gpt2-medium's cell
    (2048, 2048, 512, 512, True, (4, 6, 6)),        # gpt3-1.3b's
    (1024, 1024, 512, 512, False, (0, 4, 0)),
    (256, 512, 128, 128, True, (2, 5, 1)),          # sq < sk: offset 256
    (512, 1024, 512, 512, True, (1, 1, 0)),
    (512, 256, 128, 128, True, (2, 1, 5)),          # sq > sk: rows that see no key
    (1024, 1024, 512, 128, True, (8, 4, 4)),
], ids=["gpt2m", "gpt3xl", "full", "offset", "offset_512", "negative_offset",
        "narrow_keys"])
def test_forward_tile_counts_against_hand_counts(sq, sk, bq, bk, causal, want):
    """(masked, full, skipped) per batch*head, from the function that gives
    the kernel its loop bounds."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    got = fa.tile_counts(sq, sk, bq, bk, causal, sk - sq)
    assert got == want and sum(got) == (sq // bq) * (sk // bk)


def _loop_bodies(jaxpr):
    """Bodies of a kernel's loops: traced bounds make a `while`, static
    ones a `scan`."""
    return [e.params["body_jaxpr" if e.primitive.name == "while"
                     else "jaxpr"].jaxpr
            for e in jaxpr.eqns if e.primitive.name in ("while", "scan")]


_COMPARES = {"lt", "le", "gt", "ge", "eq", "ne"}


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_mha_forward_is_one_kernel_that_masks_only_the_diagonals_tiles(causal):
    """One pallas_call; a loop whose body holds no compare and no select
    and, when causal, a second whose body holds one of each a sub-block;
    bfloat16 operands on every product; lse leaves as the lane-dense rows
    the backward reads, and the gradient reshapes or copies no lse."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    bh, s, d = 2, 1024, 64
    a = jax.ShapeDtypeStruct((bh, s, d), jnp.bfloat16)
    bq, bk = fa._block_sizes(s, s, d)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: fa._fwd_res(q, k, v, 1, 1, causal, 0.125))(a, a, a)
    calls = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    lse_shape = (bh, 1, s // bq, 1, bq)
    assert [o.aval.shape for o in calls[0].outvars] == [a.shape, lse_shape]
    assert calls[0].outvars[1].aval.dtype == jnp.float32
    kernel = calls[0].params["jaxpr"]
    per_loop = []
    for body in _loop_bodies(kernel):
        names = [e.primitive.name for e in _eqns(body)]
        per_loop.append((sum(n in _COMPARES for n in names),
                         names.count("select_n")))
    sub_blocks = bk // fa.SUB_KEYS
    assert per_loop == ([(0, 0), (sub_blocks, sub_blocks)] if causal
                        else [(0, 0)])
    dots = [e for e in _eqns(kernel) if e.primitive.name == "dot_general"]
    assert len(dots) == (1 + sub_blocks) * len(per_loop)
    for dot in dots:
        assert [v.aval.dtype for v in dot.invars] == [jnp.bfloat16] * 2
        assert dot.outvars[0].aval.dtype == jnp.float32

    grad = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(fa.mha_forward(
        q, k, v, causal=causal).astype(jnp.float32)), argnums=(0, 1, 2)))(
            a, a, a)
    forward, backward = [e for e in _eqns(grad.jaxpr)
                         if e.primitive.name == "pallas_call"]
    # the very rows the forward wrote are an operand of the backward
    assert any(v is forward.outvars[1] for v in backward.invars)
    for e in _eqns(grad.jaxpr):
        assert not any(v is forward.outvars[1] for v in e.invars) \
            or e is backward, e


# ----------------------------------------- the seq-major entry (`head_group`)

def _by_head(a, heads):
    """[b, s, heads w] -> head-major [b heads, s, w]."""
    b, s, _ = a.shape
    return jnp.swapaxes(a.reshape(b, s, heads, -1), 1, 2).reshape(
        b * heads, s, -1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("sq,sk", [(256, 256), (128, 256)],
                         ids=["self", "cross_with_offset"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("d,dv,heads,group", [
    (64, 64, 4, 2), (192, 128, 2, 2), (128, 128, 2, 1), (256, 256, 2, 1)],
    ids=["d64_g2", "mla_192_128_g2", "d128_g1", "d256_g1"])
def test_seq_major_entry_equals_the_head_major_one(d, dv, heads, group,
                                                   causal, sq, sk, dtype):
    """q, k, v read as the projections write them, `group` heads a grid
    step: out and lse are the head-major entry's to the bit (per head the
    tile arithmetic is the same), dQ, dK, dV within the file's limits
    (`delta` is summed by another route)."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    assert fa.head_group(heads, d, dv, sq, sk, dtype) == group
    rng = np.random.RandomState(5)
    b = 2
    q = jnp.asarray(rng.randn(b, sq, heads * d), dtype)
    k = jnp.asarray(rng.randn(b, sk, heads * d), dtype)
    v = jnp.asarray(rng.randn(b, sk, heads * dv), dtype)
    w = jnp.asarray(rng.randn(b, sq, heads * dv), dtype)
    scale = 0.125 if dtype == jnp.bfloat16 else 0.17

    out, (_, _, _, _, lse) = fa._fwd_res(q, k, v, heads, group, causal,
                                         scale)
    qh, kh, vh = (_by_head(a, heads) for a in (q, k, v))
    want, (_, _, _, _, want_lse) = fa._fwd_res(qh, kh, vh, 1, 1, causal,
                                               scale)
    assert out.shape == (b, sq, heads * dv) and out.dtype == dtype
    np.testing.assert_array_equal(np.asarray(_by_head(out, heads), np.float32),
                                  np.asarray(want, np.float32))
    assert lse.shape[:2] == (b, heads)
    np.testing.assert_array_equal(
        np.asarray(lse).reshape(want_lse.shape), np.asarray(want_lse))

    _, pull = jax.vjp(lambda q, k, v: fa.mha_seq_major(
        q, k, v, heads, causal=causal, scale=scale), q, k, v)
    _, pull_h = jax.vjp(lambda q, k, v: mha_forward(
        q, k, v, causal=causal, scale=scale), qh, kh, vh)
    for g, r in zip(pull(w), pull_h(_by_head(w, heads))):
        assert g.dtype == dtype
        g = np.asarray(_by_head(g, heads), np.float32)
        r = np.asarray(r, np.float32)
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-4)
        else:
            assert np.abs(g - r).max() / np.abs(r).max() <= BF16_GRAD_TOL


# heads, d_qk, d_v, sq, sk, dtype -> heads a grid step, counted by hand:
# the smallest g with g d_qk and g d_v multiples of 128 lanes, if it
# divides the heads and the grouped forward and backward fit in 16 MiB
@pytest.mark.parametrize("heads,d,dv,sq,sk,dtype,want", [
    (16, 64, 64, 1024, 1024, "bfloat16", 2),      # gpt2-medium
    (16, 64, 64, 2048, 2048, "bfloat16", 2),      # gpt3-1.3b's share
    (20, 256, 256, 2048, 2048, "bfloat16", 1),    # GLM-4.7-Flash
    (4, 192, 128, 2048, 2048, "bfloat16", 2),     # Xing's held heads
    (16, 128, 128, 2048, 2048, "bfloat16", 1),
    (15, 64, 64, 1024, 1024, "bfloat16", None),   # 15 heads in pairs
    (3, 192, 128, 1024, 1024, "bfloat16", None),
    (16, 48, 32, 1024, 1024, "bfloat16", 8),      # 384 and 256 lanes
    (4, 48, 32, 1024, 1024, "bfloat16", None),    # 4 heads, groups of 8
    (16, 80, 80, 1024, 1024, "bfloat16", None),   # 8 x 80: 640 lanes,
                                                  # too wide to fit
    (16, 72, 72, 1024, 1024, "bfloat16", None),   # 16 x 72 is the first
    (16, 64, 64, 5632, 5632, "bfloat16", 2),      # the grouped cap at 64
    (16, 64, 64, 6144, 6144, "bfloat16", None),   # past it: head-major
    (4, 192, 128, 2560, 2560, "bfloat16", None),
    (20, 256, 256, 2560, 2560, "bfloat16", 1),    # g 1: the same cap
    (20, 256, 256, 3072, 3072, "bfloat16", None),
    (16, 64, 64, 3072, 3072, "float32", 2),
    (16, 64, 64, 3584, 3584, "float32", None)])
def test_head_group_decision_table(heads, d, dv, sq, sk, dtype, want):
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    assert fa.head_group(heads, d, dv, sq, sk, jnp.dtype(dtype)) == want
    if want is not None:
        assert heads % want == 0 and want * d % 128 == 0 \
            and want * dv % 128 == 0
        assert fa._fits(sq, sk, d, jnp.dtype(dtype), True, dv, want) is None


def test_the_grouped_kernels_end_before_the_head_major_ones():
    """A group's blocks are g heads wide (and dQ's scratch g d rows), so
    the seq-major entry ends earlier; no head-major cap is lower than it
    was (6,144 / 3,072 / 2,560 before the key block was chosen by fit)."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    bf16 = jnp.bfloat16
    assert fa.max_seq(64, bf16, True, group=2) == 5632
    assert fa.max_seq(64, bf16, True) == 7168
    assert fa.max_seq(192, bf16, True, 128, group=2) == 2048
    assert fa.max_seq(192, bf16, True, 128) == 3584
    assert fa.max_seq(256, bf16, True, 256) == 2560
    assert fa.max_seq(128, bf16, True) == 6144
    # the key block is halved where the whole one does not fit and the half
    # does, at any width
    assert fa._bwd_block_k(4608, 4608, 64, 64, bf16, 2) == 512
    assert fa._bwd_block_k(5120, 5120, 64, 64, bf16, 2) == 256
    assert fa._bwd_block_k(6144, 6144, 64, 64, bf16) == 512
    assert fa._bwd_block_k(7168, 7168, 64, 64, bf16) == 256
    assert fa._bwd_block_k(2048, 2048, 192, 128, bf16, 2) == 256
    assert fa._bwd_block_k(2048, 2048, 192, 128, bf16) == 512
    assert fa._bwd_block_k(2048, 2048, 256, 256, bf16) == 256
    # only a whole `MAX_BLOCK` is halved
    assert fa._bwd_block_k(256, 256, 256, 256, bf16) == 128
    assert fa._bwd_block_k(384, 384, 64, 64, bf16, 2) == 128


def test_seq_major_entry_falls_back_to_head_major_with_its_swaps():
    """Three heads of 64 cannot be paired: the same call swaps the heads
    to the front, runs the head-major kernels and swaps back."""
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(2, 128, 3 * 64), jnp.float32)
               for _ in range(3))
    assert fa.head_group(3, 64, 64, 128, 128, q.dtype) is None
    got = fa.mha_seq_major(q, k, v, 3, causal=True)
    want = mha_forward(*(_by_head(a, 3) for a in (q, k, v)), causal=True)
    np.testing.assert_array_equal(_by_head(got, 3), want)
    jaxpr = jax.make_jaxpr(lambda q, k, v: fa.mha_seq_major(
        q, k, v, 3, causal=True))(q, k, v)
    swaps = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "transpose"
             and e.outvars[0].aval.ndim == 4]
    assert len(swaps) == 4                  # q, k, v there; the output back


@pytest.mark.parametrize("heads,d,dv", [(4, 64, 64), (2, 192, 128)],
                         ids=["d64", "mla_192_128"])
def test_attention_kernel_branch_transposes_nothing_of_qs_size(heads, d, dv):
    """`blocks.attention` hands the kernels the projections' layout: the
    gradient's jaxpr holds no transpose of an array as large as q (the
    small `delta` rows do not count), two pallas_calls, and the backward
    takes q, k, v and dO and gives dQ, dK, dV in that layout."""
    from conftest import with_flag
    from paddle_tpu.models import blocks
    b, s = 2, 256
    q, k = (jax.ShapeDtypeStruct((b, s, heads, d), jnp.bfloat16),) * 2
    v = jax.ShapeDtypeStruct((b, s, heads, dv), jnp.bfloat16)
    with with_flag("FLAGS_flash_interpret", True):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: blocks.attention(
                q, k, v, causal=True, scale=0.1, flash=True).astype(
                    jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
    size = min(b * s * heads * d, b * s * heads * dv)
    eqns = list(_eqns(jaxpr.jaxpr))
    for e in eqns:
        if e.primitive.name == "transpose":
            assert all(np.prod(o.aval.shape) < size for o in e.outvars), e
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 2
    flat = [(b, s, heads * d), (b, s, heads * d), (b, s, heads * dv)]
    assert [o.aval.shape for o in calls[0].invars] == flat
    assert [o.aval.shape for o in calls[1].invars[:4]] == flat + [flat[2]]
    assert [o.aval.shape for o in calls[1].outvars] == flat


def test_mha_cross_attention_shapes():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(2, 128, 32), jnp.float32)
    k = jnp.asarray(rng.randn(2, 256, 32), jnp.float32)
    v = jnp.asarray(rng.randn(2, 256, 32), jnp.float32)
    out = mha_forward(q, k, v, causal=True, scale=0.2)
    ref = _ref_attn(q, k, v, True, 0.2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_tensor_entry_and_autograd():
    import paddle_tpu as pt
    rng = np.random.RandomState(3)
    q = pt.to_tensor(rng.randn(2, 128, 4, 32).astype("float32"),
                     stop_gradient=False)
    k = pt.to_tensor(rng.randn(2, 128, 4, 32).astype("float32"),
                     stop_gradient=False)
    v = pt.to_tensor(rng.randn(2, 128, 4, 32).astype("float32"),
                     stop_gradient=False)
    out = flash_attention(q, k, v, causal=True)
    loss = (out * out).sum()
    loss.backward()
    assert q.grad is not None and np.isfinite(q.grad.numpy()).all()
    # parity with the SDPA path
    from paddle_tpu.nn.functional.attention import \
        scaled_dot_product_attention
    ref = scaled_dot_product_attention(q, k, v, None, 0.0, True, False)
    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=2e-5,
                               atol=2e-5)


def test_nn_functional_flash_attention_uses_pallas():
    import paddle_tpu as pt
    from paddle_tpu.nn.functional.flash_attention import flash_attention \
        as fa
    rng = np.random.RandomState(4)
    q = pt.to_tensor(rng.randn(1, 256, 2, 64).astype("float32"))
    out, sm = fa(q, q, q, causal=True)
    assert sm is None
    assert out.shape == [1, 256, 2, 64]


def test_rms_norm_matches_reference_and_grads():
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(64, 128), jnp.float32)
    w = jnp.asarray(rng.rand(128) + 0.5, jnp.float32)

    def ref(x, w):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + 1e-6) * w

    y = rms_norm(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, w)),
                               rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda x, w: jnp.sum(rms_norm(x, w) ** 2),
                  argnums=(0, 1))(x, w)
    g2 = jax.grad(lambda x, w: jnp.sum(ref(x, w) ** 2), argnums=(0, 1))(x, w)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_rms_norm_tensor_path():
    import paddle_tpu as pt
    x = pt.to_tensor(np.random.RandomState(6).randn(4, 16, 128).astype(
        "float32"), stop_gradient=False)
    w = pt.to_tensor(np.ones(128, "float32"), stop_gradient=False)
    y = rms_norm(x, w)
    y.sum().backward()
    assert x.grad is not None and w.grad is not None


def test_swiglu_matches_reference():
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(32, 256), jnp.float32)
    g = jnp.asarray(rng.randn(32, 256), jnp.float32)
    y = swiglu(x, g)
    ref = jax.nn.silu(x) * g
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)
    # split form
    xy = jnp.concatenate([x, g], axis=-1)
    y2 = swiglu(xy)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(ref), rtol=1e-5,
                               atol=1e-5)
    gr1 = jax.grad(lambda x, g: jnp.sum(swiglu(x, g) ** 2),
                   argnums=(0, 1))(x, g)
    gr2 = jax.grad(lambda x, g: jnp.sum((jax.nn.silu(x) * g) ** 2),
                   argnums=(0, 1))(x, g)
    for a, b in zip(gr1, gr2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_rope_rotates_and_preserves_norm():
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(2, 16, 4, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, 16, 4, 64), jnp.float32)
    qo, ko, v = fused_rotary_position_embedding(q, k)
    assert v is None
    assert qo.shape == q.shape and ko.shape == k.shape
    # rotation preserves pairwise norms
    np.testing.assert_allclose(
        np.asarray(jnp.sum(qo ** 2, -1)), np.asarray(jnp.sum(q ** 2, -1)),
        rtol=1e-4, atol=1e-4)
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(qo[:, 0]), np.asarray(q[:, 0]),
                               rtol=1e-5, atol=1e-5)
