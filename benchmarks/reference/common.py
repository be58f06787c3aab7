"""What the family references share: layer norm, GELU, attention, the
cross-entropy sum and one AdamW update, in plain float32 jax.numpy."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_norm(x, gain, bias, eps):
    """Ba et al. 2016: normalise the last axis, then gain and bias."""
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def gelu_tanh(x):
    """Hendrycks & Gimpel 2016, the tanh form (GPT-2's gelu_new, and what
    Google's BERT code computes)."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def attention(x, qkv_w, qkv_b, proj_w, proj_b, heads: int, causal: bool):
    """Vaswani et al. 2017, multi-head scaled dot-product attention on one
    sequence x [s, h]. The fused qkv matrix's 3h columns are laid out as
    (q|k|v, head, head_dim): a convention of the parameter, which the
    reference has to share with the program whose weights it is given."""
    s, h = x.shape
    d = h // heads
    qkv = (x @ qkv_w + qkv_b).reshape(s, 3, heads, d)
    q, k, v = (qkv[:, i].transpose(1, 0, 2) for i in range(3))   # [H, s, d]
    scores = q @ k.transpose(0, 2, 1) / math.sqrt(d)             # [H, s, s]
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    scores = scores - scores.max(-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / probs.sum(-1, keepdims=True)
    out = (probs @ v).transpose(1, 0, 2).reshape(s, h)
    return out @ proj_w + proj_b


def nll_sum(logits, labels):
    """Summed negative log-likelihood over the positions whose label is not
    negative, and their count. logits [s, v], labels [s]."""
    logits = logits - logits.max(-1, keepdims=True)
    logp = logits - jnp.log(jnp.exp(logits).sum(-1, keepdims=True))
    keep = labels >= 0
    picked = jnp.take_along_axis(
        logp, jnp.where(keep, labels, 0)[:, None], axis=-1)[:, 0]
    return -jnp.where(keep, picked, 0.0).sum(), keep.sum()


def adamw_first_update(params, grads, decayed, opt: dict, param_dtype):
    """One AdamW update (Loshchilov & Hutter 2019, algorithm 2) from zero
    moments, then the rounding the configuration states: the weights the
    forward pass sees are the float32 master weights rounded to
    `param_dtype`. At step 1 the update is lr * g / (|g| + eps), about one
    bfloat16 unit in the last place of a typical weight, so the rounding is
    as large as the update and belongs to the configuration, not to noise.
    `decayed` is a tree of booleans: which leaves take weight decay."""
    lr, wd, b1, b2, eps = (opt[k] for k in ("lr", "wd", "b1", "b2", "eps"))

    def one(p, g, use_wd):
        m = (1.0 - b1) * g
        v = (1.0 - b2) * g * g
        m_hat = m / (1.0 - b1)
        v_hat = v / (1.0 - b2)
        step = m_hat / (jnp.sqrt(v_hat) + eps) + (wd * p if use_wd else 0.0)
        return (p - lr * step).astype(param_dtype).astype(jnp.float32)

    return jax.tree_util.tree_map(one, params, grads, decayed)
