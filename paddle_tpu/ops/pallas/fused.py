"""Fused elementwise/norm Pallas kernels.

TPU-native equivalents of the reference's hand-fused CUDA kernels surfaced
via python/paddle/incubate/nn/functional (fused_rms_norm, swiglu,
fused_rotary_position_embedding; CUDA impls under
paddle/phi/kernels/fusion/gpu). Forward runs as a Pallas kernel (VPU,
rows resident in VMEM); backward uses the closed-form jnp VJP — XLA fuses
the backward fine, the win the kernel buys is the single-pass fp32
row-statistics forward on bf16 activations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..._core.device import pallas_interpret
from .flash_attention import SCOPED_VMEM_BYTES, _no_x64


def _row_block(n: int, row_bytes: int) -> int:
    """Rows per grid step: the largest divisor of n up to 256 whose
    double-buffered blocks (`row_bytes` per row over every in/out array)
    take at most half the scoped VMEM. 256 rows whatever the width put a
    LLaMA-class MLP row (11008 wide) at 2.5x the limit."""
    budget = SCOPED_VMEM_BYTES // 2
    for cand in (256, 128, 64, 32, 16, 8):
        if n % cand == 0 and 2 * cand * row_bytes <= budget:
            return cand
    if n % 8 and 2 * n * row_bytes <= budget:
        return n    # small ragged row count: one block
    raise ValueError(
        f"fused kernel: no row block for {n} rows of {row_bytes} bytes "
        f"fits {budget >> 20} MiB of VMEM; pad the row count to a "
        "multiple of 8")


# ----------------------------------------------------------------- rms_norm

def _rms_kernel(x_ref, w_ref, y_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    y_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(y_ref.dtype)


def _rms_fwd_pallas(x2, w, eps):
    n, h = x2.shape
    bn = _row_block(n, 2 * h * x2.dtype.itemsize)
    with _no_x64():
        return pl.pallas_call(
            functools.partial(_rms_kernel, eps=eps),
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((1, h), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((bn, h), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, h), x2.dtype),
            interpret=pallas_interpret(),
        )(x2, w.reshape(1, h))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms(x2, w, eps):
    return _rms_fwd_pallas(x2, w, eps)


def _rms_fwd(x2, w, eps):
    return _rms_fwd_pallas(x2, w, eps), (x2, w)


def _rms_bwd(eps, res, g):
    x2, w = res
    x = x2.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    xhat = x * r
    dw = jnp.sum(gf * xhat, axis=0).astype(w.dtype)
    gx = gf * wf
    h = x.shape[-1]
    dx = r * (gx - xhat * jnp.sum(gx * xhat, axis=-1, keepdims=True) / h)
    return dx.astype(x2.dtype), dw


_rms.defvjp(_rms_fwd, _rms_bwd)


def _is_tensor(x):
    from ..._core.tensor import Tensor
    return isinstance(x, Tensor)


def rms_norm(x, weight, epsilon=1e-6):
    """fused_rms_norm analog on raw arrays or Tensors; normalizes the last
    axis. Returns same-shape output."""
    unwrap = _is_tensor(x)
    xv = x._value if unwrap else x
    wv = weight._value if _is_tensor(weight) else weight
    shape = xv.shape
    y = _rms(xv.reshape(-1, shape[-1]), wv, float(epsilon)).reshape(shape)
    if unwrap:
        from ..._core.executor import apply
        from ..._core.op_registry import all_ops, register_op
        if "fused_rms_norm" not in all_ops():
            register_op(
                "fused_rms_norm",
                lambda xa, wa, eps: _rms(
                    xa.reshape(-1, xa.shape[-1]), wa, eps).reshape(xa.shape))
        return apply("fused_rms_norm", x, weight, eps=float(epsilon))
    return y


# ------------------------------------------------------------------ swiglu

def _swiglu_kernel(x_ref, g_ref, y_ref):
    x = x_ref[...].astype(jnp.float32)
    y_ref[...] = (jax.nn.silu(x) * g_ref[...].astype(jnp.float32)).astype(
        y_ref.dtype)


def _swiglu_fwd_pallas(x2, g2):
    n, h = x2.shape
    bn = _row_block(n, 3 * h * x2.dtype.itemsize)
    spec = pl.BlockSpec((bn, h), lambda i: (i, 0))
    with _no_x64():
        return pl.pallas_call(
            _swiglu_kernel, grid=(n // bn,),
            in_specs=[spec, spec], out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((n, h), x2.dtype),
            interpret=pallas_interpret(),
        )(x2, g2)


@jax.custom_vjp
def _swiglu(x2, g2):
    return _swiglu_fwd_pallas(x2, g2)


def _swiglu_fwd(x2, g2):
    return _swiglu_fwd_pallas(x2, g2), (x2, g2)


def _swiglu_bwd(res, dout):
    x2, g2 = res
    x = x2.astype(jnp.float32)
    g = g2.astype(jnp.float32)
    d = dout.astype(jnp.float32)
    sig = jax.nn.sigmoid(x)
    silu = x * sig
    dsilu = sig * (1 + x * (1 - sig))
    return ((d * g * dsilu).astype(x2.dtype),
            (d * silu).astype(g2.dtype))


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


def _swiglu_body(xa, ga):
    if ga is None:
        xa, ga = jnp.split(xa, 2, axis=-1)
    shape = xa.shape
    return _swiglu(xa.reshape(-1, shape[-1]),
                   ga.reshape(-1, shape[-1])).reshape(shape)


def swiglu(x, gate=None):
    """silu(x) * gate; with gate=None splits x in half on the last axis
    (reference incubate/nn/functional/swiglu semantics)."""
    if _is_tensor(x):
        from ..._core.executor import apply
        from ..._core.op_registry import all_ops, register_op
        if "fused_swiglu" not in all_ops():
            register_op("fused_swiglu", _swiglu_body)
        return apply("fused_swiglu", x, gate)
    return _swiglu_body(x, gate)


# -------------------------------------------------------------------- rope

def _rope_half(x, cos, sin):
    # rotate-half convention on the last axis, fp32 trig applied per
    # position; cos/sin: [S, D] broadcast over batch/heads.
    x1, x2 = jnp.split(x, 2, axis=-1)
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * cos + rot.astype(jnp.float32) * sin
            ).astype(x.dtype)


def _rope_body(q, k, cos, sin):
    # q/k: [B, S, H, D]; cos/sin: [S, D] or [1, S, 1, D]
    if cos.ndim == 2:
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    qo = _rope_half(q, cos, sin)
    ko = _rope_half(k, cos, sin) if k is not None else None
    return (qo, ko) if ko is not None else qo


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True):
    """incubate/nn/functional/fused_rotary_position_embedding analog.

    Returns (q, k, v) tuple like the reference; v passes through
    unrotated when given.
    """
    from ..._core.tensor import Tensor
    qv = q._value if isinstance(q, Tensor) else q
    kv = k._value if isinstance(k, Tensor) else k
    s, d = qv.shape[1], qv.shape[-1]
    if cos is None:
        inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        t = jnp.arange(s, dtype=jnp.float32)
        freqs = jnp.outer(t, inv)
        emb = jnp.concatenate([freqs, freqs], axis=-1)
        cosv, sinv = jnp.cos(emb), jnp.sin(emb)
    else:
        cosv = cos._value if _is_tensor(cos) else cos
        sinv = sin._value if _is_tensor(sin) else sin
        cosv = cosv.reshape(cosv.shape[-2], cosv.shape[-1])
        sinv = sinv.reshape(sinv.shape[-2], sinv.shape[-1])
    if position_ids is not None:
        pid = position_ids._value if _is_tensor(position_ids) \
            else position_ids
        cosv = jnp.take(cosv, pid, axis=0)[0]
        sinv = jnp.take(sinv, pid, axis=0)[0]
    if isinstance(q, Tensor) and k is not None:
        from ..._core.executor import apply
        from ..._core.op_registry import all_ops, register_op
        if "fused_rope" not in all_ops():
            register_op("fused_rope", _rope_body, multi_output=True)
        qo, ko = apply("fused_rope", q, k, Tensor(cosv), Tensor(sinv))
        return qo, ko, v
    out = _rope_body(qv, kv, cosv, sinv)
    if kv is None:
        return out, None, v
    return out[0], out[1], v
