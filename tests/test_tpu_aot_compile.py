"""Ahead-of-time compile of every Pallas kernel for a real v5e, in tier-1.

The CPU suite runs every kernel in the Pallas interpreter, which accepts
programs Mosaic refuses (an int64 index map, a block that overflows the
16 MiB scoped VMEM). libtpu is part of the installation and can describe a
v5e host without one being attached, so these cases run the REAL TPU
compiler on compile-only devices with the package's gates open
(`device.is_tpu` answers True, so `pallas_interpret()` is False). This is
what keeps "compiles only in interpret mode" from coming back between chip
runs. No skip for a missing libtpu.
"""
import importlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, SingleDeviceSharding

from paddle_tpu._core import device
from paddle_tpu.models.gpt import GPTConfig, build_train_step, init_gpt_params

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
fv = importlib.import_module("paddle_tpu.ops.pallas.flash_varlen")
fused = importlib.import_module("paddle_tpu.ops.pallas.fused")
sm = importlib.import_module("paddle_tpu.ops.pallas.stream_mix")


@pytest.fixture(scope="module")
def v5e():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


@pytest.fixture(autouse=True)
def tpu_gates_open(monkeypatch):
    monkeypatch.setattr(device, "is_tpu", lambda: True)
    assert not device.pallas_interpret()


def _compile(devs, fn, *shapes, kernels=True):
    """Trace on v5e compile-only device 0, lower for TPU, run Mosaic + XLA.
    `kernels`: whether the lowered program holds a Mosaic call."""
    sh = SingleDeviceSharding(devs[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in shapes]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    assert ("tpu_custom_call" in lowered.as_text()) is kernels
    return lowered.compile()


def _sum32(x):
    return x.astype(jnp.float32).sum()


# gpt2-medium at batch 8, bert-large's widths, gpt3-1.3b's share of one chip
@pytest.mark.parametrize("shape", [(128, 1024, 64), (192, 512, 64),
                                   (128, 2048, 64)])
@pytest.mark.parametrize("backward", [False, True])
def test_flash_compiles(v5e, shape, backward):
    def fwd(q, k, v):
        return fa.mha_forward(q, k, v, causal=True)

    fn = jax.grad(lambda q, k, v: _sum32(fwd(q, k, v)), argnums=(0, 1, 2)) \
        if backward else fwd
    _compile(v5e, fn, *[(shape, jnp.bfloat16)] * 3)


# latent attention: q and k of width 192 (128 + 64 rotary), v of 128; the
# benchmark's 4 rows x 4 held heads at 2048
@pytest.mark.parametrize("backward", [False, True])
def test_flash_compiles_at_two_widths(v5e, backward):
    def fwd(q, k, v):
        return fa.mha_forward(q, k, v, causal=True)

    fn = jax.grad(lambda q, k, v: _sum32(fwd(q, k, v)), argnums=(0, 1, 2)) \
        if backward else fwd
    compiled = _compile(v5e, fn, ((16, 2048, 192), jnp.bfloat16),
                        ((16, 2048, 192), jnp.bfloat16),
                        ((16, 2048, 128), jnp.bfloat16))
    assert "bf16[16,2048,128]" in compiled.as_text()


def test_flash_gradient_keeps_no_padded_lse(v5e):
    """gpt2-medium's attention at batch 16: the forward hands lse to the
    backward as lane-dense rows, so the compiled gradient holds no float32
    [bh, sq, 1] array (134 MB a layer once padded to 128 lanes) and no
    copy that relays it."""
    shape = (256, 1024, 64)
    text = _compile(v5e, jax.grad(lambda q, k, v: _sum32(fa.mha_forward(
        q, k, v, causal=True)), argnums=(0, 1, 2)),
        *[(shape, jnp.bfloat16)] * 3).as_text()
    assert "f32[256,1024,1]" not in text
    assert "f32[256,1,2,1,512]" in text
    assert text.count("tpu_custom_call") == 2


def test_flash_varlen_compiles(v5e):
    t, h, d, nseq = 4096, 8, 64, 5

    def loss(q, k, v, cu):
        return _sum32(fv._varlen_body(q, k, v, cu, cu, 0.125, True))

    _compile(v5e, jax.grad(loss, argnums=(0, 1, 2)),
             *[((t, h, d), jnp.bfloat16)] * 3, ((nseq + 1,), jnp.int32))


def test_flashmask_compiles(v5e):
    b, s, h, d = 4, 2048, 8, 64

    def loss(q, k, v, startend):
        return _sum32(fv._flashmask_body(q, k, v, startend, 0.125, True))

    _compile(v5e, jax.grad(loss, argnums=(0, 1, 2)),
             *[((b, s, h, d), jnp.bfloat16)] * 3, ((b, 1, s, 2), jnp.int32))


def test_fused_rms_compiles_at_llama_hidden(v5e):
    _compile(v5e, lambda x, w: fused._rms(x, w, 1e-6),
             ((8192, 4096), jnp.bfloat16), ((4096,), jnp.bfloat16))


def test_fused_swiglu_compiles_at_llama_mlp_width(v5e):
    _compile(v5e, fused._swiglu, *[((8192, 11008), jnp.bfloat16)] * 2)


# the sparse cell's streams: 4 x (2 x 2048 tokens) x 3584, bfloat16
STREAMS = (4, 2 * 2048, 3584)


def _mixing_shapes():
    n, t, h = STREAMS
    rows, kp, bf16, f32 = n + n * n, 32, jnp.bfloat16, jnp.float32
    x, y = (STREAMS, bf16), ((t, h), bf16)
    g, sb = ((n, 3 * kp, h), bf16), ((kp, 2), f32)
    mix, praw, rinv = ((rows, t), f32), ((kp, t), f32), ((1, t), f32)
    coef = sm._coef(n, 20, 1e-6, (-30.0, 30.0))
    return {
        "read_in_forward": (lambda x, g, sb: sm.read_in_forward(
            x, g, sb, coef, 1e-6), (x, g, sb)),
        "write_back_forward": (sm.write_back_forward, (x, y, mix)),
        "write_back_backward": (sm.write_back_backward, (x, x, y, mix)),
        "read_in_backward": (lambda *a: sm.read_in_backward(*a, coef), (
            y, x, x, g, praw, rinv, mix, sb))}


@pytest.mark.parametrize("kernel", ["read_in_forward", "write_back_forward",
                                    "write_back_backward",
                                    "read_in_backward"])
def test_stream_mixing_compiles_at_the_sparse_cells_shapes(v5e, kernel):
    fn, shapes = _mixing_shapes()[kernel]
    text = _compile(v5e, fn, *shapes).as_text()
    # no float32 copy of a stream, or of all of them, reaches HBM
    assert "f32[4096,3584]" not in text and "f32[4,4096,3584]" not in text


def test_stream_mixing_whole_sublayer_compiles_with_its_gradient(v5e):
    """`read_in` and `write_back` under `jax.grad`, parameters packed and
    unpacked by XLA around the four Mosaic calls."""
    n, t, h = STREAMS
    k = 2 * n + n * n
    f32 = jnp.float32
    hc = {"norm_g": ((n * h,), f32), "phi": ((n * h, k), f32),
          "alpha": ((3,), f32), "b_pre": ((n,), f32), "b_post": ((n,), f32),
          "b_res": ((n, n), f32)}

    def loss(x, *leaves):
        params = dict(zip(hc, leaves))
        h_in, mix, x = sm.read_in(x, params, 20, 1e-6, (-30.0, 30.0))
        return _sum32(sm.write_back(x, h_in, mix))

    compiled = _compile(v5e, jax.grad(loss, argnums=tuple(range(7))),
                        ((n, 2, 2048, h), jnp.bfloat16), *hc.values())
    # read-in, and both backward kernels; the gradient of a sum does not
    # need the write-back's forward result
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 3


def test_stream_width_that_cannot_be_tiled_is_a_named_error(v5e):
    """Mosaic tiles the lanes by 128: another width raises before it."""
    with pytest.raises(sm.StreamWidthError, match="multiple of 128"):
        _compile(v5e, sm.write_back_forward, ((4, 4096, 3000), jnp.bfloat16),
                 ((4096, 3000), jnp.bfloat16), ((20, 4096), jnp.float32))


def test_flash_sequence_limit_is_a_named_error(v5e):
    """Past the computed cap the named error comes first, not Mosaic's
    RESOURCE_EXHAUSTED; at the cap the real compiler still accepts. The
    cap is the head-major entry's, 6,144 while the backward's key block was
    512 at every length and 7,168 since it is chosen by fit."""
    cap = fa.max_seq(64, jnp.bfloat16, backward=True)
    assert cap == 7168 and fa.max_seq(64, jnp.bfloat16, backward=False) > cap

    def grad(q, k, v):
        return jax.grad(lambda q: _sum32(fa.mha_forward(
            q, k, v, causal=True)))(q)

    # bh 64: too large for XLA to park an operand in VMEM and mask the limit
    for seq in (6144, cap):
        _compile(v5e, grad, *[((64, seq, 64), jnp.bfloat16)] * 3)
    with pytest.raises(fa.FlashSequenceLimitError,
                       match=f"bwd kernel .* {cap} with the"):
        _compile(v5e, grad, *[((64, cap + 512, 64), jnp.bfloat16)] * 3)
    # forward only fits longer sequences than the backward does: compiled
    # at its own computed cap (the estimate counts the body's tiles too),
    # at latent attention's widths as well, refused by name past it
    def forward(q, k, v):
        return fa.mha_forward(q, k, v, causal=True)

    for d, dv in ((64, 64), (192, 128)):
        longest = fa.max_seq(d, jnp.bfloat16, backward=False, d_v=dv)
        assert longest > fa.max_seq(d, jnp.bfloat16, backward=True, d_v=dv)
        _compile(v5e, forward, ((64, longest, d), jnp.bfloat16),
                 ((64, longest, d), jnp.bfloat16),
                 ((64, longest, dv), jnp.bfloat16))
    with pytest.raises(fa.FlashSequenceLimitError, match="fwd kernel"):
        _compile(v5e, forward, *[((64, longest + 4096, 192), jnp.bfloat16)] * 2,
                 ((64, longest + 4096, 128), jnp.bfloat16))


def test_flash_at_256_wide_compiles_at_the_new_cells_shape(v5e):
    """Latent attention with a 256-wide value and all 20 heads (ISSUE 30:
    8 x 20 x 2048 at 256 / 256): with 512-key blocks the compiler took
    16.50 MiB for the backward and refused; `_bwd_block_k` gives it 256
    keys (by fit, as at every width since), and the repaired estimate's cap
    is one the compiler accepts."""
    bf16 = jnp.bfloat16
    assert fa._bwd_block_k(2048, 2048, 256, 256, bf16) == 256
    assert fa._bwd_block_k(2048, 2048, 192, 128, bf16) \
        == fa._bwd_block_k(2048, 2048, 64, 64, bf16) == 512 \
        and fa._bwd_block_k(384, 384, 256, 256, bf16) == 128
    cap = fa.max_seq(256, jnp.bfloat16, backward=True, d_v=256)
    assert cap == 2560
    assert fa.max_seq(256, jnp.bfloat16, backward=False, d_v=256) == 6144

    def grad(q, k, v):
        return jax.grad(lambda q, k, v: _sum32(fa.mha_forward(
            q, k, v, causal=True)), (0, 1, 2))(q, k, v)

    for seq in (2048, cap):
        _compile(v5e, grad, *[((160, seq, 256), jnp.bfloat16)] * 3)
    with pytest.raises(fa.FlashSequenceLimitError,
                       match=f"bwd kernel .* {cap} with the"):
        _compile(v5e, grad, *[((160, cap + 512, 256), jnp.bfloat16)] * 3)


# ------------------------------- the seq-major entry (heads side by side)

def _seq_major(heads):
    def fwd(q, k, v):
        return fa.mha_seq_major(q, k, v, heads, causal=True)
    return fwd, jax.grad(lambda q, k, v: _sum32(fwd(q, k, v)),
                         argnums=(0, 1, 2))


def _of_size(text, size, *kinds):
    """Lines of compiled HLO whose instruction is one of `kinds` and whose
    result has `size` elements."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?\S+ = \w+\[([\d,]*)\]\S* (\S+?)\(",
                     line)
        if m and m.group(2) in kinds and m.group(1) \
                and math.prod(map(int, m.group(1).split(","))) == size:
            found.append(line.strip())
    return found


def _mosaic_calls(text, size):
    """(operands, results) of `size` elements for each Mosaic call."""
    shapes = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = (.*?) [\w-]+\(", line)
        if m:
            shapes[m.group(1)] = [
                math.prod(map(int, dims.split(","))) if dims else 1
                for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(2))]
    calls = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.match(r"\s*(?:ROOT )?%?(\S+) = .*? custom-call\((.*?)\), ",
                     line)
        operands = [shapes[name.strip().lstrip("%")][0]
                    for name in re.sub(r"/\*.*?\*/", "",
                                       m.group(2)).split(",")]
        calls.append((operands.count(size), shapes[m.group(1)].count(size)))
    return calls


# batch, seq, heads, d_qk, d_v, heads a grid step: the four kernel cells,
# and the longest the grouped kernels take at head_dim 64
@pytest.mark.parametrize("b, s, heads, d, dv, group", [
    (16, 1024, 16, 64, 64, 2), (8, 2048, 16, 64, 64, 2),
    (8, 2048, 20, 256, 256, 1), (2, 2048, 4, 192, 128, 2),
    (4, 5632, 16, 64, 64, 2)],
    ids=["gpt2m", "gpt3xl_share", "glm47f", "xing4", "grouped_cap_at_64"])
def test_seq_major_kernels_compile_with_no_copy_around_them(v5e, b, s, heads,
                                                            d, dv, group):
    assert fa.head_group(heads, d, dv, s, s, jnp.bfloat16) == group
    shapes = [((b, s, heads * d), jnp.bfloat16)] * 2 \
        + [((b, s, heads * dv), jnp.bfloat16)]
    forward, gradient = _seq_major(heads)
    for fn, calls in ((forward, [(3, 1)]), (gradient, [(3, 1), (4, 3)])):
        text = _compile(v5e, fn, *shapes).as_text()
        for size in {b * s * heads * d, b * s * heads * dv}:
            assert not _of_size(text, size, "copy", "transpose")
        if d == dv:
            assert _mosaic_calls(text, b * s * heads * d) == calls


def test_past_the_grouped_fit_the_head_major_kernels_take_over(v5e):
    """At head_dim 64 the pairs of heads end at 5,632; 6,144 and the
    head-major cap (7,168) compile through the same call, with the swaps."""
    cap = fa.max_seq(64, jnp.bfloat16, backward=True)
    _, gradient = _seq_major(16)
    for seq in (6144, cap):
        assert fa.head_group(16, 64, 64, seq, seq, jnp.bfloat16) is None
        text = _compile(v5e, gradient,
                        *[((4, seq, 1024), jnp.bfloat16)] * 3).as_text()
        assert f"bf16[64,{seq},64]" in text
    with pytest.raises(fa.FlashSequenceLimitError, match="1 head a grid"):
        _compile(v5e, gradient, *[((4, cap + 512, 1024), jnp.bfloat16)] * 3)


@pytest.mark.parametrize("window", [1024, None], ids=["window", "full"])
def test_grouped_query_kernels_compile_at_the_mellum_cells_shape(v5e,
                                                                 window):
    """32 query heads on 4 K/V heads of 128 at 4 x 4,096, a window layer and
    a full one: q [4, 4096, 4096] and k, v [4, 4096, 512] go to the two
    Mosaic calls as the projections wrote them, dK and dV come back with 4
    heads, and nothing of 32 K/V heads' size other than q, dO, the output
    and dQ exists."""
    b, s, heads, kv, d = 4, 4096, 32, 4, 128
    assert fa.head_group(heads, d, d, s, s, jnp.bfloat16, kv) == 1

    def gradient(q, k, v):
        return jax.grad(lambda q, k, v: _sum32(fa.mha_seq_major(
            q, k, v, heads, causal=True, kv_heads=kv, window=window)),
            argnums=(0, 1, 2))(q, k, v)

    shapes = [((b, s, heads * d), jnp.bfloat16)] \
        + [((b, s, kv * d), jnp.bfloat16)] * 2
    compiled = _compile(v5e, gradient, *shapes)
    text = compiled.as_text()
    wide, narrow = b * s * heads * d, b * s * kv * d
    assert sorted(_mosaic_calls(text, narrow)) == [(2, 0), (2, 2)]
    assert sorted(_mosaic_calls(text, wide)) == [(1, 1), (2, 1)]
    assert not _of_size(text, wide, "copy", "transpose")
    dq, dk, dv = jax.eval_shape(gradient, *[
        jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes])
    assert (dq.shape, dk.shape, dv.shape) == (
        (b, s, heads * d), (b, s, kv * d), (b, s, kv * d))


def test_gpt2_medium_block_reaches_the_kernels_without_a_copy(v5e):
    """One checkpointed gpt2-medium layer and its gradient at the cell's
    batch: the three Mosaic calls (forward, its remat, backward) take q, k
    and v as the three qkv products wrote them, and no `copy` or
    `transpose` of q's element count stands under `attn_core`."""
    from paddle_tpu.models import blocks, gpt, stages
    config = GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=1,
                       num_heads=16, max_position_embeddings=1024,
                       dtype="bfloat16", use_flash_attention=True)
    params = jax.eval_shape(lambda: init_gpt_params(config, 0))["blocks"]
    x = ((16, 1024, 1024), jnp.bfloat16)

    def loss(x, *leaves):
        stacked = dict(zip(params, leaves))
        out, _ = blocks.scan_layers(
            lambda x, blk: gpt._block(x, blk, config, None), x, stacked, True)
        return _sum32(out)

    text = _compile(v5e, jax.grad(loss, argnums=(0, 1, 2, 3, 4)), x,
                    *[(a.shape, a.dtype) for a in params.values()]).as_text()
    size = 16 * 1024 * 1024
    moved = [line for line in _of_size(text, size, "copy", "transpose")
             if stages.ATTN_CORE in line]
    assert not moved, moved
    assert sorted(_mosaic_calls(text, size)) == [(3, 1), (3, 1), (4, 3)]


def _arrays_of(text, size):
    """Shapes in compiled HLO that hold `size` elements."""
    return {dims for dims in re.findall(r"\w+\[([\d,]+)\]", text)
            if math.prod(map(int, dims.split(","))) == size}


def _bert_large_layer_gradient(v5e, batch, seq, kernels):
    """Compiled HLO of one checkpointed bert-large layer and its gradient
    at [batch, seq, 1024] bfloat16, with no padding mask."""
    from paddle_tpu.models import bert, blocks
    config = bert.BertConfig(hidden_size=1024, num_layers=1, num_heads=16,
                             intermediate_size=4096)
    params = jax.eval_shape(
        lambda: bert.init_bert_params(config, 0))["blocks"]

    def loss(x, *leaves):
        stacked = dict(zip(params, leaves))
        out, _ = blocks.scan_layers(
            lambda x, blk: bert._block(x, blk, config), x, stacked, True)
        return _sum32(out)

    return _compile(v5e, jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                    ((batch, seq, 1024), jnp.bfloat16),
                    *[(a.shape, a.dtype) for a in params.values()],
                    kernels=kernels).as_text()


def test_bert_large_block_reaches_the_kernels_without_a_copy(v5e):
    """The bert twin of the gpt2-medium case, at `bertl-mlm-s512`'s batch:
    the three Mosaic calls (forward, its remat, backward) take q, k and v
    as the three qkv products wrote them, in pairs of heads and not
    head-major; no `copy` or `transpose` of q's element count stands under
    `attn_core`, and no array of the scores' size exists."""
    from paddle_tpu.models import stages
    assert fa.head_group(16, 64, 64, 512, 512, jnp.bfloat16) == 2
    text = _bert_large_layer_gradient(v5e, 32, 512, kernels=True)
    size = 32 * 512 * 1024
    moved = [line for line in _of_size(text, size, "copy", "transpose")
             if stages.ATTN_CORE in line]
    assert not moved, moved
    assert sorted(_mosaic_calls(text, size)) == [(3, 1), (3, 1), (4, 3)]
    assert not _arrays_of(text, 32 * 16 * 512 * 512)


def test_bert_large_block_at_128_tokens_holds_no_kernel(v5e):
    """`bertl-mlm-s128`'s layer: 128 keys are under the kernels' 256, so
    the einsum path and the one qkv product stay, and no Mosaic call."""
    text = _bert_large_layer_gradient(v5e, 128, 128, kernels=False)
    assert _arrays_of(text, 128 * 16 * 128 * 128)


def test_train_step_with_flash_lowers_on_pp2_mp2_mesh(v5e):
    """Lowering only: Mosaic kernels cannot be auto-partitioned, so flash in
    a mesh program must sit in a shard_map over every axis GSPMD still owns,
    size-1 'dp' included, here nested inside the compiled-pp body."""
    config = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=4,
                       num_heads=4, max_position_embeddings=256)
    mesh = Mesh(np.asarray(v5e).reshape(1, 2, 2), ("dp", "pp", "mp"))
    _, step = build_train_step(config, mesh, remat=True, pp_microbatches=2)
    params = jax.eval_shape(lambda: init_gpt_params(config, 0))
    f32 = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), params)
    state = {"params": params, "master": f32, "m": f32, "v": f32,
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    batch = jax.ShapeDtypeStruct((4, 256), jnp.int32)
    text = step.trace(state, batch, batch).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("axes", [{"dp": 2, "mp": 2}, {"dp": 4}],
                         ids=["dp2mp2", "dp4"])
def test_bert_train_step_reaches_the_kernels_on_a_mesh(v5e, axes):
    """`bert.build_train_step(config, mesh)` at 512 tokens with no padding
    mask: the mesh reaches `bert._block`, so the kernels sit in
    `mha_sharded`'s shard_map (batch over dp, heads over mp) and the step
    lowers and compiles for four v5e chips. Without the mesh there the
    Mosaic call meets GSPMD and the lowering raises."""
    from paddle_tpu.models import bert
    config = bert.BertConfig(vocab_size=1024, hidden_size=256, num_layers=2,
                             num_heads=4, intermediate_size=512)
    mesh = Mesh(np.asarray(v5e).reshape(tuple(axes.values())), tuple(axes))
    init_fn, step = bert.build_train_step(config, mesh)
    state = jax.eval_shape(lambda: init_fn(0))
    batch = jax.ShapeDtypeStruct((8, 512), jnp.int32)
    lowered = step.trace(state, batch, batch).lower(
        lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
    text = lowered.compile().as_text()
    # forward, its remat and the backward in the scanned layer, a chip's
    # share of the scores nowhere
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 3
    assert not _arrays_of(text, 8 * 4 * 512 * 512 // 4)


# ------------------------------------------------- grouped products (PR 34)

@pytest.mark.parametrize("heads, window", [(48, None), (64, 512)],
                         ids=["full_48_on_8", "window_64_on_8"])
def test_grouped_query_kernels_compile_at_the_laguna_cells_shapes(
        v5e, heads, window):
    """The Laguna cell's two kinds at 2 x 4,096, forward and backward: 48
    query heads on 8 K/V heads of 128 in a full layer (6 a K/V head), 64 on
    8 with a 512-key window in a window layer (8 a K/V head, a window one
    tile wide); q [2, 4096, H 128] and k, v [2, 4096, 1024] go to the two
    Mosaic calls as the projections wrote them, dK and dV come back with
    8 heads."""
    b, s, kv, d = 2, 4096, 8, 128

    def gradient(q, k, v):
        return jax.grad(lambda q, k, v: _sum32(fa.mha_seq_major(
            q, k, v, heads, causal=True, kv_heads=kv, window=window)),
            argnums=(0, 1, 2))(q, k, v)

    shapes = [((b, s, heads * d), jnp.bfloat16)] \
        + [((b, s, kv * d), jnp.bfloat16)] * 2
    text = _compile(v5e, gradient, *shapes).as_text()
    wide, narrow = b * s * heads * d, b * s * kv * d
    assert sorted(_mosaic_calls(text, narrow)) == [(2, 0), (2, 2)]
    assert sorted(_mosaic_calls(text, wide)) == [(1, 1), (2, 1)]
    assert not _of_size(text, wide, "copy", "transpose")


gm = importlib.import_module("paddle_tpu.ops.pallas.grouped_matmul")
# a chunk's rows, hidden, expert width, experts held: the Mellum, GLM,
# Xing and Laguna cells
GROUPED = {"mellum": (65536, 2304, 896, 16), "glm": (16384, 2048, 1536, 8),
           "xing": (4096, 3584, 1024, 8), "laguna": (16384, 2048, 512, 32)}


@pytest.mark.parametrize("kernel", ["forward", "dgrad", "wgrad"])
@pytest.mark.parametrize("cell", list(GROUPED))
def test_grouped_products_compile_at_the_sparse_cells_shapes(v5e, cell,
                                                             kernel):
    """A group's whole matrix resident (over the 16 MiB default scoped
    VMEM: the calls raise the limit), a grid whose bound is read on the
    device, the contraction of the ragged rows, a gradient added into the
    buffer of an earlier one; at the tile the shape rule gives."""
    rows, k, n, held = GROUPED[cell]
    bf16, sizes = jnp.bfloat16, ((held,), jnp.int32)
    tile = gm.row_tile(rows, held)
    fn, shapes = {
        "forward": (lambda l, w, s: gm.gmm(l, w, s, tile),
                    (((rows, k), bf16), ((held, k, n), bf16), sizes)),
        "dgrad": (lambda d, w, s, first: gm.gmm(d, w.swapaxes(1, 2), s, tile,
                                                add_to=first),
                  (((rows, n), bf16), ((held, k, n), bf16), sizes,
                   ((rows, k), bf16))),
        "wgrad": (lambda l, d, s: gm.tgmm(l, d, s, tile),
                  (((rows, k), bf16), ((rows, n), bf16), sizes))}[kernel]
    text = _compile(v5e, fn, *shapes).as_text()
    # no float32 copy of the rows reaches HBM
    assert f"f32[{rows}," not in text


def _routed_layers_gradient(layers):
    """`jax.grad` of `layers` routed feed-forwards side by side, each with
    matrices of its own, at the Xing cell's shapes."""
    from paddle_tpu.ops import moe
    t, hidden, width, k, experts, held = 4096, 3584, 1024, 4, 64, 8
    bf16 = jnp.bfloat16

    def loss(x, weights, ids, *stacks):
        return sum(_sum32(moe.held_experts_ffn(
            x, ids, weights, {"gate_w": gate_w, "up_w": up_w,
                              "down_w": down_w}, (0, held), experts))
            for gate_w, up_w, down_w in zip(*[iter(stacks)] * 3))

    shapes = [((t, hidden), bf16), ((t, k), jnp.float32), ((t, k), jnp.int32)]
    shapes += [((held, hidden, width), bf16), ((held, hidden, width), bf16),
               ((held, width, hidden), bf16)] * layers
    return (jax.grad(loss, argnums=(0, 1) + tuple(range(3, 3 + 3 * layers))),
            shapes)


def test_held_experts_ffn_gradient_compiles_with_the_kernels_in_its_loop(
        v5e):
    """The kernels inside the `lax.cond` inside the `lax.scan` of
    `ops/moe._chunks`, under `jax.grad`: the backward's three recomputed
    products and six gradients are nine Mosaic calls and no grouped matmul
    of XLA's is left."""
    fn, shapes = _routed_layers_gradient(1)
    text = _compile(v5e, fn, *shapes).as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 9
    assert "ragged-dot" not in text


def test_a_second_routed_layer_lowers_no_further_mosaic_body(v5e):
    """The guard on the set-up cost (about 0.15 s of Python a lowered body
    a program, PERF.md section 6, PR 34): the jitted entries make the sites
    of one shape call one `func.func`, so a second layer of the same
    shapes adds calls and no Mosaic body to the lowered module."""
    def bodies(layers):
        fn, shapes = _routed_layers_gradient(layers)
        sh = SingleDeviceSharding(v5e[0])
        text = jax.jit(fn).trace(*[jax.ShapeDtypeStruct(
            s, d, sharding=sh) for s, d in shapes]).lower(
                lowering_platforms=("tpu",)).as_text()
        assert "ragged_dot" not in text
        return text.count("stablehlo.custom_call @tpu_custom_call")

    one = bodies(1)
    assert 5 <= one <= 9          # five are distinct; twelve sites a layer
    assert bodies(2) == one


def _delta_rule_layers(layers, backward=True):
    """`layers` chunked rules side by side, each on operands of its own, at
    the hybrid cell's widths (32 heads of 128, 2,048 tokens, bfloat16 q,
    k, v, float32 log-decays and write strengths), two rows: the sum of
    their outputs or its gradient, and the operands' shapes."""
    la = importlib.import_module("paddle_tpu.ops.linear_attention")
    wide, heads = (2, 2048, 32, 128), (2, 2048, 32)

    def loss(*operands):
        return sum(_sum32(la.chunk_gated_delta_rule(*x))
                   for x in zip(*[iter(operands)] * 5))

    shapes = ([(wide, jnp.bfloat16)] * 3 + [(wide, jnp.float32),
                                            (heads, jnp.float32)]) * layers
    return (jax.grad(loss, argnums=tuple(range(5 * layers))) if backward
            else loss), shapes


@pytest.mark.parametrize("backward", [False, True])
def test_chunked_delta_rule_compiles_at_the_kimi_cells_shape(v5e, backward):
    """`ops/linear_attention.chunk_gated_delta_rule` and its gradient at the
    hybrid cell's widths, two rows: the chunk terms are the Mosaic kernels
    of `ops/pallas/delta_rule.py` (the forward; under `jax.grad` the
    forward and the backward), the scan over chunks is still a
    `while` loop that carries a float32 [rows, 32, 128, 128] state, rows
    taken one at a time (`GROUP_TOKENS`); outside the kernels nothing
    writes the pairwise [.., 16, 16, 128] tensor of a sub-block's decays
    or a float32 [.., 64, 64] array a chunk and head (a level of the
    halving, T, B before its rounding), and no integer division reaches
    the device."""
    fn, shapes = _delta_rule_layers(1, backward)
    compiled = _compile(v5e, fn, *shapes, kernels=True)
    text = compiled.as_text()
    calls = len(re.findall(r'custom_call_target="tpu_custom_call"', text))
    # the gradient alone: the forward's own pass is dead code, what is left
    # is the backward's repeat of the forward kernel and the backward kernel
    assert calls == (2 if backward else 1)
    state = [rows for line in text.splitlines() if " while(" in line
             for rows in re.findall(r"f32\[(\d+),32,128,128\]",
                                    line.split(" while(")[0])]
    assert state and set(state) == {"1"}
    # what a fusion keeps in registers is no array: look outside the fused
    # computations, at what instructions write
    written = "\n".join(
        block for block in text.split("\n\n")
        if not block.lstrip().startswith("%fused_computation"))
    assert _arrays_of(written, 2048 * 32 * 128)
    chunk_heads = 32 * 32           # a row's: 2,048 / 64 chunks of 32 heads
    for rows in (1, 2):
        assert not _arrays_of(written, rows * chunk_heads * 4 * 16 * 16 * 128)
    # (a trip of the scan's transpose writes its own [32, 64, 64])
    assert all(math.prod(map(int, dims.split(","))) < chunk_heads * 64 * 64
               for dims in re.findall(r"f32\[([\d,]*\b64,64)\]", written))
    assert not re.findall(r" (?:divide|remainder)\([^)]*\), .*s32\[", text)
    assert "/rem\"" not in text and "floor_divide" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_a_second_delta_rule_layer_lowers_no_further_mosaic_body(v5e):
    """The guard on the set-up cost, as for the grouped products: the
    kernels' entries are jitted, so a second layer of the same shapes (and
    the backward's repeat of the forward) adds calls and no Mosaic body to
    the lowered module: one forward body and one backward body, whatever
    the heads a grid step takes."""
    def bodies(layers):
        fn, shapes = _delta_rule_layers(layers)
        sh = SingleDeviceSharding(v5e[0])
        text = jax.jit(fn).trace(*[jax.ShapeDtypeStruct(
            s, d, sharding=sh) for s, d in shapes]).lower(
                lowering_platforms=("tpu",)).as_text()
        calls = [line for line in text.splitlines()
                 if "stablehlo.custom_call @tpu_custom_call" in line]
        return sorted(name for line in calls
                      for name in re.findall(r'kernel_name = "(\w+)"', line))

    assert bodies(1) == ["_terms_bwd", "_terms_fwd"]
    assert bodies(2) == ["_terms_bwd", "_terms_fwd"]


def _kda_elementwise(kernel, backward):
    """One of `ops/pallas/kda_elementwise.py`'s passes at the hybrid cell's
    widths (32 heads of 128, 2,048 tokens, bfloat16 but for q's float32
    product; two rows): the sum of its output or its gradient to every
    operand, and the operands' shapes."""
    ke = importlib.import_module("paddle_tpu.ops.pallas.kda_elementwise")
    heads, d, wide = 32, 128, (2, 2048, 32 * 128)
    bf, f32 = jnp.bfloat16, jnp.float32
    fn, shapes = {
        # as the model hands them: a float32 product in, bfloat16 out
        "conv_qk": (lambda x, t: ke.conv_heads(x, t, heads, d ** -0.5, 1e-6,
                                               dtype=bf),
                    [(wide, f32), ((4, heads * d), bf)]),
        "conv_v": (lambda x, t: ke.conv_heads(x, t, heads, None, 1e-6),
                   [(wide, bf), ((4, heads * d), bf)]),
        "decay": (ke.log_decay,
                  [(wide, bf), ((heads,), f32), ((heads * d,), f32)]),
        "norm": (lambda o, w, g: ke.gated_norm(o, w, g, 1e-5),
                 [((2, 2048, heads, d), bf), ((d,), bf), (wide, bf)]),
    }[kernel]

    def loss(*operands):
        return _sum32(fn(*operands))

    return (jax.grad(loss, argnums=tuple(range(len(shapes)))) if backward
            else loss), shapes


@pytest.mark.parametrize("backward", [False, True])
@pytest.mark.parametrize("kernel", ["conv_qk", "conv_v", "decay", "norm"])
def test_kda_elementwise_kernels_compile_at_the_kimi_cells_shape(
        v5e, kernel, backward):
    """The KDA layer's short convolution (normed and not), log-decays and
    gated norm at the hybrid cell's widths: one Mosaic call each, the
    forward kernel, or under `jax.grad` the backward kernel alone (each
    `custom_vjp` keeps its inputs, so the forward's own pass is dead
    code), and outside it no array the size of an operand."""
    fn, shapes = _kda_elementwise(kernel, backward)
    compiled = _compile(v5e, fn, *shapes, kernels=True)
    text = compiled.as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          text)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2048 * 4096


def test_a_second_convolution_site_lowers_no_further_mosaic_body(v5e):
    """The guard on the set-up cost: the kernels' entries are jitted, so a
    second convolution of the same shapes and mode (a layer's q and k take
    one mode each, a later layer repeats them) adds calls and no Mosaic
    body to the lowered module: one forward body and one backward."""
    ke = importlib.import_module("paddle_tpu.ops.pallas.kda_elementwise")
    wide, taps = (2, 2048, 4096), (4, 4096)

    def bodies(sites):
        def loss(*operands):
            return sum(_sum32(ke.conv_heads(x, t, 32, 1.0, 1e-6))
                       for x, t in zip(operands[::2], operands[1::2]))
        sh = SingleDeviceSharding(v5e[0])
        shapes = [wide, taps] * sites
        return jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(2 * sites)))).trace(
            *[jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sh)
              for s in shapes]).lower(lowering_platforms=("tpu",)).as_text(
                  ).count("stablehlo.custom_call @tpu_custom_call")

    assert bodies(1) == 2
    assert bodies(2) == 2
