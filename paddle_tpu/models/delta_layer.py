"""The gated-delta-rule linear-attention layer, written once for the two
families that have one: the latent family's Kimi Delta Attention
(`models/mla_moe.py`, arXiv:2510.26692) and the decoder family's Gated
DeltaNet (`models/llama.py`, Yang, Kautz & Hatamizadeh, arXiv:2412.06464,
in flash-linear-attention's `GatedDeltaNet` form).

On y [B, S, h] (normed here where the caller asks, `pre_norm`):

    q, k, v    silu(conv_K(y W_q)), ... : a causal depthwise convolution of
               K taps on each, zero before a row's start, then SiLU; q and k
               normalised a head by their L2 norm (`QK_NORM_EPS` under the
               root), q times d_k^-1/2
    g          the log-decay, <= 0: -exp(A_h) softplus(a + b_dt), a a head
               AND key channel through a rank of d_k, (y W_fa) W_fb (`decay`
               "channel", KDA), or one a head, y W_a (`decay` "head", GDN)
    beta       the write strength a head, `beta_scale` * sigmoid(y W_beta);
               a scale of 2 lets I - beta k k^T take a negative eigenvalue
               (Grazzi et al. 2024, arXiv:2411.12537)
    o          the chunked gated delta rule (`ops/linear_attention.py`) with a
               state [d_k, d_v] a head from zero
    out        RMSNorm_{d_v}(o; one gain shared by the heads) times
               sigmoid((y W_ga) W_gb) through a rank of d_v (`gate`
               "sigmoid", KDA) or silu(y W_og) (`gate` "silu", GDN); then
               W_o

The two forms are `DeltaForm`s; a family builds its own from its
configuration and this module asks nothing else of it. The parameters of a
layer are `init_params`' (the output projection and the block's norms are
the family's), the log-decay's `decay_log` (A) and `dt_bias` float32 like a
router's.

Stages: the projections under ATTN_QKV, the activations, the rule and the
gated norm under LINEAR_ATTN, the output projection under ATTN_OUT. The
activations and the gated norm are the checkpointed XLA functions below,
or, for a KDA layer whose heads are whole lane tiles, the one-pass Mosaic
kernels of `ops/pallas/kda_elementwise.py` (`_kernels`), which are
KDA-shaped: a GDN layer keeps the XLA functions.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from ..ops.linear_attention import chunk_gated_delta_rule
from ..ops.pallas import kda_elementwise
from . import stages
from .blocks import normal, rms_norm

QK_NORM_EPS = 1e-6      # under the root of a linear layer's q and k norms
CHANNEL, HEAD = "channel", "head"       # a log-decay a key channel, a head
SIGMOID, SILU = "sigmoid", "silu"       # the output gate's activation
_GATES = {SIGMOID: jax.nn.sigmoid, SILU: jax.nn.silu}


@dataclasses.dataclass(frozen=True)
class DeltaForm:
    heads: int
    key_dim: int
    value_dim: int
    taps: int                       # the short convolutions'
    norm_eps: float                 # the gated norm's (and a pre-norm's)
    decay: str = CHANNEL            # CHANNEL (KDA) | HEAD (GDN)
    gate: str = SIGMOID             # SIGMOID, low rank (KDA) | SILU (GDN)
    beta_scale: float = 1.0         # 2: a write strength up to 2

    def __post_init__(self):
        if self.decay not in (CHANNEL, HEAD) or self.gate not in _GATES:
            raise ValueError(f"decay {self.decay!r} ({CHANNEL!r} or "
                             f"{HEAD!r}), gate {self.gate!r} ({SIGMOID!r} "
                             f"or {SILU!r})")


def init_params(key, layers: int, form: DeltaForm, hidden: int, std: float,
                dtype):
    """A linear-attention layer's own parameters, stacked over `layers`,
    from keys of their own. The projections at `std`; the convolutions'
    taps uniform in +-1/sqrt(taps) (a depthwise Conv1d's default);
    `decay_log` the log of uniform(1, 16) a head and `dt_bias` the inverse
    softplus of a step log-uniform in [1e-3, 1e-1] a decay channel (a key
    channel, or a head), as the delta-rule family seeds its gate, both
    float32 like the router. The draws come in one order for both forms,
    the order the KDA layer has always made them in."""
    h, dt = hidden, jnp.dtype(dtype)
    heads, dk, dv, taps = form.heads, form.key_dim, form.value_dim, form.taps
    ks = iter(jax.random.split(key, 16))

    def norm(shape):
        return normal(next(ks), (layers,) + shape, std, dt)

    def taps_w(width):
        return jax.random.uniform(
            next(ks), (layers, taps, width), jnp.float32,
            -taps ** -0.5, taps ** -0.5).astype(dt)

    channels = heads * dk if form.decay == CHANNEL else heads
    step = jnp.exp(jax.random.uniform(
        next(ks), (layers, channels), jnp.float32, math.log(1e-3),
        math.log(1e-1)))
    p = {"q_w": norm((h, heads * dk)), "k_w": norm((h, heads * dk)),
         "v_w": norm((h, heads * dv)),
         "q_conv_w": taps_w(heads * dk), "k_conv_w": taps_w(heads * dk),
         "v_conv_w": taps_w(heads * dv)}
    if form.decay == CHANNEL:
        p.update(decay_a_w=norm((h, dk)), decay_b_w=norm((dk, heads * dk)))
    else:
        p["decay_w"] = norm((h, heads))
    p.update(decay_log=jnp.log(jax.random.uniform(
        next(ks), (layers, heads), jnp.float32, 1.0, 16.0)),
        dt_bias=step + jnp.log(-jnp.expm1(-step)),
        beta_w=norm((h, heads)))
    if form.gate == SIGMOID:
        p.update(gate_a_w=norm((h, dv)), gate_b_w=norm((dv, heads * dv)))
    else:
        p["o_gate_w"] = norm((h, heads * dv))
    p["o_ln"] = jnp.ones((layers, dv), dt)
    return p


# ------------------------------------------- the activations, XLA's form

@functools.partial(jax.checkpoint, static_argnums=(2, 3))
def _conv_heads(x, taps, heads: int, norm):
    """x [B, S, H d] through the causal depthwise convolution with taps
    [K, H d] and SiLU, silu(sum_i taps[i] * x[t - (K - 1) + i]) with zero
    before the row's start, then split into heads [B, S, H, d]; with
    `norm`, each head over its L2 norm, times `norm`. Float32 inside, and
    checkpointed: the backward pass keeps x alone."""
    size, (b, seq, _) = taps.shape[0], x.shape
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (size - 1, 0), (0, 0)))
    taps = taps.astype(jnp.float32)
    out = jax.nn.silu(sum(taps[i] * padded[:, i:i + seq]
                          for i in range(size))).reshape(b, seq, heads, -1)
    if norm:
        out = out * (norm * jax.lax.rsqrt(
            (out * out).sum(-1, keepdims=True) + QK_NORM_EPS))
    return out.astype(x.dtype)


@jax.checkpoint
def _log_decay(a, decay_log, dt_bias):
    """a [B, S, H d] -> the log-decay a head and key channel, float32
    [B, S, H, d]: -exp(A_h) * softplus(a + b_dt) < 0."""
    heads = decay_log.shape[0]
    rate = jax.nn.softplus(a.astype(jnp.float32) + dt_bias)
    return -jnp.exp(decay_log)[:, None] * rate.reshape(
        a.shape[:2] + (heads, -1))


@jax.checkpoint
def _head_log_decay(a, decay_log, dt_bias):
    """a [B, S, H] -> the log-decay a head, float32 [B, S, H]: -exp(A_h)
    * softplus(a + b_dt) < 0 (the rule broadcasts it over the channels)."""
    return -jnp.exp(decay_log) * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _gated_norm(out, gain, gate, eps: float, act):
    """out [B, S, H, d], gate [B, S, H d] -> RMSNorm_d(out; gain) *
    act(gate), [B, S, H d], `act` the gate's activation."""
    b, s, heads, d = out.shape
    gate = act(gate.astype(jnp.float32)).reshape(out.shape)
    return (rms_norm(out, gain, eps) * gate.astype(out.dtype)).reshape(
        b, s, heads * d)


# a linear layer's parameters that the activations before the rule read
_ACTIVATED = ("q_conv_w", "k_conv_w", "v_conv_w", "decay_log", "dt_bias")


@jax.custom_vjp
def _wide_product(y, w):
    """y [B, S, h] @ w [h, k] -> float32 [B, S, k]. XLA fuses the product
    into the XLA form's convolutions, which read its float32 accumulator;
    the kernels read this, so that the rule's operands are as precise
    (rounded to bfloat16 first, the first layer's rule read 6.0e-3 off its
    float32 recurrence on a v5e, where the XLA form reads 5.4e-3). The
    backward pass takes the cotangent in y's dtype, as the XLA form's
    does."""
    return jnp.einsum("bsh,hk->bsk", y, w, preferred_element_type=jnp.float32)


def _wide_product_fwd(y, w):
    return _wide_product(y, w), (y, w)


def _wide_product_bwd(saved, cotangent):
    y, w = saved
    _, pull = jax.vjp(lambda y, w: jnp.einsum("bsh,hk->bsk", y, w), y, w)
    return pull(cotangent.astype(y.dtype))


_wide_product.defvjp(_wide_product_fwd, _wide_product_bwd)


# ------------------------------------------------------------- the layer

def _kernels(y, blk, form: DeltaForm) -> bool:
    """Whether the one-pass Mosaic kernels of `ops/pallas/kda_elementwise.py`
    serve this layer's activations and its gated norm: a KDA layer
    (`kda_elementwise.taken`: its head width, sequence and operands
    alone)."""
    return (form.decay == CHANNEL and form.gate == SIGMOID
            and form.key_dim == form.value_dim
            and kda_elementwise.taken(form.key_dim, y.shape[1], y,
                                      *jax.tree_util.tree_leaves(blk)))


def _projections(y, blk, form: DeltaForm, kernels: bool, pre_norm: bool):
    """The layer's norm where `pre_norm` and its projections of y [B, S,
    h], under ATTN_QKV: ((q, k, v, the decay's, the write strength's), the
    output gate's [B, S, H d_v]). Where `kernels`, q's, k's and v's
    products are float32 (`_wide_product`)."""
    with jax.named_scope(stages.ATTN_QKV):
        if pre_norm:
            y = rms_norm(y, blk["ln1_g"], form.norm_eps)
        q, k, v = (_wide_product(y, blk[name]) if kernels
                   else jnp.einsum("bsh,hk->bsk", y, blk[name])
                   for name in ("q_w", "k_w", "v_w"))
        if form.decay == CHANNEL:
            decay = jnp.einsum("bsr,rk->bsk", jnp.einsum(
                "bsh,hr->bsr", y, blk["decay_a_w"]), blk["decay_b_w"])
        else:
            decay = jnp.einsum("bsh,hn->bsn", y, blk["decay_w"])
        if form.gate == SIGMOID:
            gate = jnp.einsum("bsr,rk->bsk", jnp.einsum(
                "bsh,hr->bsr", y, blk["gate_a_w"]), blk["gate_b_w"])
        else:
            gate = jnp.einsum("bsh,hk->bsk", y, blk["o_gate_w"])
        beta = jnp.einsum("bsh,hn->bsn", y, blk["beta_w"])
    return (q, k, v, decay, beta), gate


def _activated(projected, params, form: DeltaForm, kernels: bool, dtype):
    """The projections as the rule takes them: (q, k, v) through the short
    convolutions with SiLU, q's and k's heads normed, in `dtype`, the
    log-decays and the write strengths. The kernels where `kernels`, else
    the checkpointed XLA functions above."""
    heads = form.heads
    q, k, v, decay, beta = projected
    if kernels:
        conv = functools.partial(kda_elementwise.conv_heads, eps=QK_NORM_EPS,
                                 dtype=jnp.dtype(dtype))
        decays = kda_elementwise.log_decay
    else:
        conv = _conv_heads
        decays = _log_decay if form.decay == CHANNEL else _head_log_decay
    activated = (conv(q, params["q_conv_w"], heads, form.key_dim ** -0.5),
                 conv(k, params["k_conv_w"], heads, 1.0),
                 conv(v, params["v_conv_w"], heads, None),
                 decays(decay, params["decay_log"], params["dt_bias"]),
                 jax.nn.sigmoid(beta.astype(jnp.float32)))
    if form.beta_scale == 1:
        return activated
    return activated[:4] + (form.beta_scale * activated[4],)


def rule_operands(y, blk, form: DeltaForm, dtype, pre_norm: bool = True):
    """The layer on y [B, S, h] up to the rule: ((q, k, v, the log-decays,
    the write strengths) as the rule takes them, the output gate's
    projection [B, S, H d_v]): `_projections` under ATTN_QKV, `_activated`
    under LINEAR_ATTN."""
    fast = _kernels(y, blk, form)
    projected, gate = _projections(y, blk, form, fast, pre_norm)
    with jax.named_scope(stages.LINEAR_ATTN):
        return _activated(projected, blk, form, fast, dtype), gate


def linear_attention(y, blk, form: DeltaForm, dtype, pre_norm: bool = True):
    """The layer on y [B, S, h] -> its output projection, [B, S, h]: the
    projections under ATTN_QKV; the activations, the chunked rule and the
    gated head norm under LINEAR_ATTN; the output's projection under
    ATTN_OUT. Where the kernels serve the layer, the activations and the
    rule are checkpointed together: the layer's backward pass keeps the
    projections, which the kernels' backward reads anyway, and forms the
    rule's operands from them once more (one more run of the forward
    kernels), so that neither those operands nor the float32 log-decays
    are kept across the rest of the layer's backward pass. Kept, they
    compiled the Kimi cell's step for a v5e to about 15.4 GB, against
    13.85 checkpointed together and 13.96 for the XLA form."""
    fast = _kernels(y, blk, form)
    projected, gate = _projections(y, blk, form, fast, pre_norm)
    with jax.named_scope(stages.LINEAR_ATTN):
        if fast:
            out = jax.checkpoint(lambda projected, params: (
                chunk_gated_delta_rule(*_activated(projected, params, form,
                                                   True, dtype))))(
                projected, {name: blk[name] for name in _ACTIVATED})
            out = kda_elementwise.gated_norm(out, blk["o_ln"], gate,
                                             form.norm_eps)
        else:
            out = chunk_gated_delta_rule(*_activated(projected, blk, form,
                                                     False, dtype))
            out = _gated_norm(out, blk["o_ln"], gate, form.norm_eps,
                              _GATES[form.gate])
    with jax.named_scope(stages.ATTN_OUT):
        return jnp.einsum("bsk,kh->bsh", out, blk["o_w"])
