"""Context parallelism: ring attention and Ulysses (all-to-all) attention.

The reference's long-context axis is `sep` (segment parallel — sequence
split across ranks; fleet/base/topology.py:199-260,
meta_parallel/segment_parallel.py:26), with ring-attention/Ulysses variants
living downstream in PaddleNLP. Here they are first-class (SURVEY.md §7):

- ``ring_attention``: blockwise attention with online softmax; K/V chunks
  rotate around the `sep` ring via ``ppermute`` (rides ICI), queries stay
  resident. Memory per chip is O(S/n), comm is overlapped by XLA with the
  per-chunk matmuls. Differentiable (scan + ppermute have transpose rules),
  so the backward pass is the standard ring-attention backward generated by
  AD.
- ``ulysses_attention``: all-to-all swaps the sharded axis seq<->heads so
  each chip runs *full-sequence* attention for H/n heads (DeepSpeed-Ulysses
  scheme); needs num_heads % sep == 0.

Both are written to be called INSIDE ``shard_map``/pjit where q, k, v are
the local shards [B, S_local, H, D]; ``ring_attention_global`` /
``ulysses_attention_global`` wrap them over a mesh for global arrays.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs, unchecked=False):
    kw = {"check_vma": False} if unchecked else {}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)

NEG_INF = -1e30


# ------------------------------------------------------------------ ring

def _chunk_attn(q, k, v, scale, m, l, acc, q_off, k_off, causal, kv_len):
    """One online-softmax accumulation step of local Q against one K/V
    chunk. Shapes: q [B,H,Sq,D], k/v [B,H,Sk,D]; carries m,l [B,H,Sq],
    acc [B,H,Sq,D] (fp32)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    sk = k.shape[2]
    k_pos = k_off + jnp.arange(sk)[None, None, None, :]
    mask = k_pos < kv_len
    if causal:
        q_pos = q_off + jnp.arange(q.shape[2])[None, None, :, None]
        mask = mask & (k_pos <= q_pos)
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard: fully-masked rows keep p == 0 (s - m_new would be 0 - 0)
    p = jnp.where(s <= NEG_INF / 2, 0.0,
                  jnp.exp(s - m_new[..., None]))
    alpha = jnp.exp(jnp.where(m <= NEG_INF / 2, 0.0, m - m_new))
    l_new = alpha * l + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, axis_name: str = "sep", causal: bool = False,
                   scale: Optional[float] = None):
    """Ring attention over the ``axis_name`` mesh axis.

    Args are LOCAL shards [B, S_local, H, D] (sequence sharded). Returns
    the local output shard [B, S_local, H, D]. Call inside shard_map.
    """
    n = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    if scale is None:
        scale = d ** -0.5

    qt = jnp.swapaxes(q, 1, 2)           # [B,H,Sq,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    q_off = rank * s_local

    m0 = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    acc0 = jnp.zeros((b, h, s_local, d), jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, step):
        m, l, acc, kc, vc = carry
        # at ring step t this device holds the chunk that started on rank
        # (rank - t) mod n
        k_off = ((rank - step) % n) * s_local
        m, l, acc = _chunk_attn(qt, kc, vc, scale, m, l, acc, q_off, k_off,
                                causal, kv_len=n * s_local)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (m, l, acc, kc, vc), ()

    # scan the n-1 rotating steps; final chunk needs no trailing ppermute
    (m, l, acc, kc, vc), _ = jax.lax.scan(
        body, (m0, l0, acc0, kt, vt), jnp.arange(n - 1))
    k_off = ((rank - (n - 1)) % n) * s_local
    m, l, acc = _chunk_attn(qt, kc, vc, scale, m, l, acc, q_off, k_off,
                            causal, kv_len=n * s_local)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.swapaxes(out.astype(q.dtype), 1, 2)


# --------------------------------------------------------------- ulysses

def ulysses_attention(q, k, v, axis_name: str = "sep",
                      causal: bool = False, scale: Optional[float] = None,
                      attn_fn=None):
    """DeepSpeed-Ulysses attention over ``axis_name``.

    all_to_all turns seq-sharded [B, S/n, H, D] into head-sharded
    [B, S, H/n, D]; full-sequence attention runs locally (flash kernel
    eligible), then the inverse all_to_all restores the seq sharding.
    """
    n = jax.lax.psum(1, axis_name)
    h = q.shape[2]
    if h % n:
        raise ValueError(f"ulysses needs num_heads % sep == 0, got {h} % {n}")

    def fwd_a2a(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def bwd_a2a(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = fwd_a2a(q), fwd_a2a(k), fwd_a2a(v)   # [B, S, H/n, D]
    if attn_fn is None:
        attn_fn = functools.partial(_full_attention, causal=causal,
                                    scale=scale)
    out = attn_fn(qg, kg, vg)
    return bwd_a2a(out)


def _full_attention(q, k, v, causal, scale):
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ------------------------------------------------------------ global wrap

def _seq_sharded_wrap(fn, mesh: Mesh, seq_axis: str,
                      batch_axis: Optional[str] = None):
    """batch_axis: mesh axis carrying the batch dim (e.g. 'dp' on a hybrid
    dp x sep mesh) so data parallelism survives the wrap; None = batch
    replicated over the other axes (pure sep mesh)."""
    spec = P(batch_axis, seq_axis, None, None)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec, unchecked=True)
    def inner(q, k, v):
        return fn(q, k, v)

    return inner


def ring_attention_global(q, k, v, mesh: Mesh, seq_axis: str = "sep",
                          causal: bool = False,
                          scale: Optional[float] = None,
                          batch_axis: Optional[str] = None):
    """Ring attention on GLOBAL arrays [B, S, H, D]; shards the sequence
    over ``seq_axis`` of ``mesh`` (and batch over ``batch_axis`` if given)
    and runs the ring."""
    fn = functools.partial(ring_attention, axis_name=seq_axis,
                           causal=causal, scale=scale)
    return _seq_sharded_wrap(fn, mesh, seq_axis, batch_axis)(q, k, v)


def ulysses_attention_global(q, k, v, mesh: Mesh, seq_axis: str = "sep",
                             causal: bool = False,
                             scale: Optional[float] = None,
                             batch_axis: Optional[str] = None):
    fn = functools.partial(ulysses_attention, axis_name=seq_axis,
                           causal=causal, scale=scale)
    return _seq_sharded_wrap(fn, mesh, seq_axis, batch_axis)(q, k, v)
