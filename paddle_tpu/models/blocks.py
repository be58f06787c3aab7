"""What the compiled model families are built from, each written once.

gpt.py, bert.py, llama.py and mla_moe.py keep their configuration, their
parameter tree and the composition of their block; the norms, the rotary
embedding, the MLPs, the attention core, the loss head and the layer loop
are here, pure `jax.numpy`. The decisions a kernel or memory PR touches are
made in one function each: Mosaic kernel or einsum in `attention`,
vocabulary-parallel or dense logits in `lm_head_loss`, checkpoint-then-scan
in `scan_layers`, the pipeline over a `pp` axis in `layer_trunk`.

No function here opens a `stages` scope: the caller stands in the stage
its call belongs to (models/stages.py), so the scopes stay flat. The routed
feed-forward of the sparse families, which opens its own, is routed.py's.
"""
from __future__ import annotations

import math
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .._core import device
from .._core.flags import flag_value
from ..distributed.fleet.mp_ops import vocab_parallel_softmax_cross_entropy
from ..distributed.pipeline_compiled import pipelined_trunk
from ..ops.pallas.flash_attention import mha_seq_major, mha_sharded


def normal(key, shape, std, dtype):
    """Seeded normal weights: drawn in float32, scaled, then cast."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


# ------------------------------------------------------ norms, rope, MLPs

def layer_norm(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g + b


def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * g


def yarn_inv_freq(dim: int, base: float, scaling: Optional[dict]):
    """Rotary inverse frequencies [dim/2] under yarn (Peng et al. 2023, as
    DeepSeek-V2 computes them): below `low` a component keeps its frequency,
    above `high` it is divided by `factor`, between them a linear ramp.
    One yarn, two callers: the latent family scales its scores by mscale^2
    (`mla_moe.attention_scale`), the decoder family its cos and sin by
    `attention_factor` (`rope`'s `factor`)."""
    i = np.arange(dim // 2, dtype=np.float64)
    extra = base ** (-2.0 * i / dim)
    if not scaling:
        return extra.astype(np.float32)
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def corr(rotations):
        return dim * math.log(original / (2 * math.pi * rotations)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(scaling["beta_fast"])), 0)
    high = min(math.ceil(corr(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope(x, theta: float, inv_freq=None, factor: Optional[float] = None,
         rotary_dim: Optional[int] = None):
    """x [B, S, H, D] -> rotated. Half-split convention. `inv_freq`
    [rotary_dim/2] replaces theta's plain frequencies (a scaled RoPE such as
    yarn); `factor` multiplies cos and sin (yarn's `attention_factor` where
    a model applies it there: q and k both carry it, the scores its
    square). `rotary_dim` (None: D) rotates the first components of a head
    alone, halves paired within them; the rest pass through (partial
    rotary)."""
    b, s, h, d = x.shape
    rot = d if rotary_dim is None else rotary_dim
    half = rot // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    if factor is not None:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., :half], x[..., half:rot]
    xf1 = x1.astype(jnp.float32)
    xf2 = x2.astype(jnp.float32)
    out = jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
        axis=-1).astype(x.dtype)
    return out if rot == d else jnp.concatenate([out, x[..., rot:]], -1)


def swiglu(y, gate_w, up_w, down_w):
    """down(silu(gate y) * up y) on y [..., h]."""
    gate = jnp.einsum("...h,hf->...f", y, gate_w)
    up = jnp.einsum("...h,hf->...f", y, up_w)
    return jnp.einsum("...f,fh->...h", jax.nn.silu(gate) * up, down_w)


def gelu_mlp(y, fc_w, fc_b, fo_w, fo_b):
    """fo(gelu(fc y)) on y [B, S, h], gelu in its tanh form."""
    y = jnp.einsum("bsh,hf->bsf", y, fc_w) + fc_b
    y = jax.nn.gelu(y, approximate=True)
    return jnp.einsum("bsf,fh->bsh", y, fo_w) + fo_b


# -------------------------------------------------------------- attention

def use_flash_kernel(flash: bool, seq: int) -> bool:
    """Pallas flash attention or the einsum path, decided from what the
    caller asks and the shape. The kernel tiles the sequence by 128. On a
    TPU it is used from seq 256 up. Anywhere else the kernel could only run
    in the Pallas interpreter, which is a test mode: FLAGS_flash_interpret
    opts in (CPU mesh tests / multichip dryrun), otherwise the CPU runs the
    einsum path."""
    if not flash or seq % 128:
        return False
    if device.is_tpu():
        return seq >= 256
    return bool(flag_value("FLAGS_flash_interpret"))


def attention(q, k, v, *, causal: bool, scale: float, flash: bool,
              mesh: Optional[Mesh] = None, mask=None,
              window: Optional[int] = None):
    """softmax(scale q k^T) v on q [B, S, H, D], k [B, S, H_kv, D] and v
    [B, S, H_kv, D_v] as the projections leave them -> [B, S, H * D_v].
    H_kv may be a divisor of H (grouped-query attention): query head h
    attends through K/V head h // (H / H_kv), and nothing is repeated in
    either branch. `window` (with `causal`) is a second diagonal: query i
    sees key j iff j <= i and i - j < window. `mask`, additive and
    broadcastable to [B, H, S, S], is bert's padding mask; the kernels take
    none, so a masked call is the einsum path whatever `flash` says. The
    kernels read the projections' own layout, the heads side by side in a
    row (`mha_seq_major`, which names a head's K/V head in its index maps
    and skips the tiles outside the window): nothing is swapped or copied
    around them. On a `mesh` they run manual over every axis
    (`mha_sharded`). The einsum path swaps the heads to the front and
    back, and computes the same."""
    b, s, heads, _ = q.shape
    kv_heads = k.shape[2]
    if mask is None and use_flash_kernel(flash, s):
        q, k, v = (a.reshape(b, s, -1) for a in (q, k, v))  # [B, S, H D]
        if mesh is not None:
            return mha_sharded(q, k, v, mesh, causal=causal, scale=scale,
                               heads=heads, kv_heads=kv_heads, window=window)
        return mha_seq_major(q, k, v, heads, causal=causal, scale=scale,
                             kv_heads=kv_heads, window=window)
    q, k, v = (jnp.swapaxes(a, 1, 2) for a in (q, k, v))    # [B, H, S, D]
    grouped = kv_heads != heads
    if grouped:     # a K/V head's query heads side by side: [B, H_kv, r, ..]
        q = q.reshape(b, kv_heads, heads // kv_heads, s, -1)
        logits = jnp.einsum("bgrqd,bgkd->bgrqk", q, k) * scale
    else:
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        seen = jnp.tril(jnp.ones((s, s), bool))
        if window is not None:
            seen = seen & ~jnp.tril(jnp.ones((s, s), bool), -window)
        logits = jnp.where(seen, logits, jnp.array(-1e30, logits.dtype))
    if mask is not None:
        logits = logits + (mask[:, :, None] if grouped else mask)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(q.dtype)
    if grouped:
        out = jnp.einsum("bgrqk,bgkd->bgrqd", probs, v).reshape(
            b, heads, s, -1)
    else:
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return jnp.swapaxes(out, 1, 2).reshape(b, s, heads * v.shape[-1])


# -------------------------------------------------------------- loss head

def cross_entropy(logits, labels, ignore_negative: bool = False):
    """Mean float32 cross-entropy of logits [..., V] against labels [...].
    With `ignore_negative` the mean is over the positions whose label is
    >= 0 (masked-LM's -100 elsewhere), and 0 where there is none."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    if not ignore_negative:
        picked = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        return -picked.mean()
    safe = jnp.maximum(labels, 0)
    picked = jnp.take_along_axis(logp, safe[..., None], -1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return -(picked * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def lm_head_loss(hidden, head_w, labels, mesh: Optional[Mesh] = None,
                 ignore_negative: bool = False):
    """Mean loss of hidden [B, S, h] under the classifier head_w [V, h].
    On a mesh whose `mp` axis divides the vocabulary the head goes through
    vocabulary-parallel softmax-cross-entropy (mp_ops.py:77-385 analog):
    head_w is vocabulary-sharded over mp, so the full [B, S, V] logits are
    never materialized; each shard computes [B, S, V/mp] and three small
    collectives finish the loss. Its caller (gpt) labels every position;
    a masked mean takes the dense path."""
    if mesh is not None and not ignore_negative \
            and "mp" in mesh.axis_names and mesh.shape["mp"] > 1 \
            and head_w.shape[0] % mesh.shape["mp"] == 0:
        return vocab_parallel_softmax_cross_entropy(
            hidden, head_w, labels, mesh, axis="mp").mean()
    return cross_entropy(jnp.einsum("bsh,vh->bsv", hidden, head_w), labels,
                         ignore_negative)


# ------------------------------------------------------------- layer loop

def scan_layers(block_fn: Callable, x, stacked, remat: bool):
    """Scan `block_fn(x, layer) -> (x, y)` over parameters stacked on a
    leading layer axis -> (x, ys); compile time is O(1) in depth. `remat`
    checkpoints the whole block (the reference's recompute pass)."""
    if remat:
        block_fn = jax.checkpoint(block_fn)
    return jax.lax.scan(block_fn, x, stacked)


def scan_periods(block_fns: Sequence[Callable], x, stacked, remat: bool):
    """`scan_layers` for a model whose layers repeat in a PERIOD of several
    kinds (three window layers to each full one): `block_fns[i](x, layer)
    -> (x, y)` is the i-th layer of a period. The scan is over periods and
    its body is one period, its kinds each compiled once, so compile time
    is O(1) in depth. `remat` checkpoints each layer of the body (a
    period's backward then holds one layer's internals and a period's layer
    inputs, not four layers' internals).

    `stacked` is the parameters in one of two forms. Where the kinds share
    one parameter tree (a window layer and a full one): that tree, all the
    layers on a leading axis, in order, so a period's are consecutive ->
    (x, ys), ys stacked over all the layers. Where each position of the
    period has a tree of its own (a linear-attention layer beside a softmax
    one): a list of them, one a position, each stacked over the PERIODS ->
    (x, a tuple of each position's ys, stacked over the periods)."""
    size = len(block_fns)
    own_trees = isinstance(stacked, (list, tuple))
    if size == 1 and not own_trees:
        return scan_layers(block_fns[0], x, stacked, remat)
    if remat:
        block_fns = [jax.checkpoint(fn) for fn in block_fns]
    if own_trees:
        if len(stacked) != size:
            raise ValueError(f"scan_periods: {size} layers in a period and "
                             f"{len(stacked)} parameter trees")

        def period(x, layers):
            ys = []
            for fn, layer in zip(block_fns, layers):
                x, y = fn(x, layer)
                ys.append(y)
            return x, tuple(ys)

        return jax.lax.scan(period, x, tuple(stacked))

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


def segments(kinds: Sequence[str], dense: int):
    """The trunk of a model whose first `dense` layers are dense and the
    rest routed, its layers of `kinds` (one name a layer), in order, as
    (group of the parameters, kinds of one period, sparse, periods): the
    leading dense layers once ("dense"), the shortest run the sparse
    layers repeat at least twice (or all of them once) as often as it is
    whole ("sparse"), and what is left, once ("tail"); a group without a
    layer is left out. Each position of a group's period has a parameter
    tree of its own, stacked over the group's periods (`scan_periods`)."""
    sparse = len(kinds) - dense
    rest = kinds[dense:]
    size = next((p for p in range(1, sparse // 2 + 1) if all(
        rest[i] == rest[i % p] for i in range(sparse // p * p))), sparse)
    whole = sparse // size * size
    found = [("dense", kinds[:dense], False, 1),
             ("sparse", rest[:size], True, sparse // size),
             ("tail", rest[whole:], True, 1)]
    return [segment for segment in found if segment[1]]


def scan_segments(block_of: Callable, trunk, x, params, remat: bool):
    """`scan_periods` over the segments of a trunk in order (`segments`'
    tuples; `params[group]` each group's trees), `block_of(kind, sparse)`
    the block function of a layer -> (x, what the sparse layers gave, in
    layer order across the groups (`in_layer_order`), or None where there
    is no sparse layer)."""
    found = []
    for group, kinds, sparse, _ in trunk:
        x, ys = scan_periods([block_of(kind, sparse) for kind in kinds], x,
                             params[group], remat)
        if sparse:
            found.append(in_layer_order(ys))
    if not found:
        return x, None
    return x, jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *found)


def in_layer_order(found):
    """What a group's layers gave or hold, [layers, ...] in layer order:
    a stack of like layers as it is; of a list with one entry a position
    of the period, each [periods, ...] (or a tree of such), the periods'
    positions interleaved."""
    if not isinstance(found, (list, tuple)):
        return found
    return jax.tree_util.tree_map(
        lambda *a: jnp.stack(a, 1).reshape((-1,) + a[0].shape[1:]), *found)


def layer_trunk(block_fn: Callable, mesh: Optional[Mesh], num_layers: int,
                remat: bool, pp_microbatches: Optional[int] = None):
    """What a forward calls in place of the scan on a mesh whose `pp` axis
    is larger than 1: `trunk(stacked, x) -> x`, the compiled
    collective-permute pipeline over the stacked layer axis
    (distributed/pipeline_compiled.py). None on any other mesh."""
    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    if pp == 1:
        return None
    if num_layers % pp:
        raise ValueError(f"num_layers {num_layers} not divisible by pp {pp}")
    return pipelined_trunk(lambda x, blk: block_fn(x, blk)[0], mesh,
                           pp_microbatches or 2 * pp, axis_name="pp",
                           remat=remat)
