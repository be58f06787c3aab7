"""A dense decoder of Gated DeltaNet linear-attention layers and
position-free, q/k-normed full-attention layers in Olmo's post-norm block,
written from the equations (Yang, Kautz & Hatamizadeh 2024,
arXiv:2412.06464, in flash-linear-attention's `GatedDeltaNet` form; Grazzi
et al. 2024, arXiv:2411.12537, for write strengths up to 2; OLMo 2 and 3
for the block and the q/k norm). No bias on any projection; eps
`rms_norm_eps` in every norm.

    layer n  kind t = layer_types[n]; x <- x + RMSNorm(Mix_t(x); g_1),
             x <- x + RMSNorm(SwiGLU(x); g_2): the mixer and the MLP read
             the residual stream itself, no norm before either
    linear   H heads, d_k keys and d_v values a head. q~ = x W_q, k~ = x W_k
             [s, H d_k], v~ = x W_v [s, H d_v]; each through a causal
             depthwise convolution of K taps, zero before the row's start,
             then SiLU: q_t = silu(sum_{i<K} w_i * q~_{t-K+1+i})
             a head: q <- q / sqrt(|q|^2 + 1e-6) * d_k^-1/2,
             k <- k / sqrt(|k|^2 + 1e-6)
             decay ONE a head: g_t = -exp(A_h) softplus(x W_a + b_dt),
             alpha_t = exp(g_t), the same in every key channel
             write strength a head: beta_t = 2 sigmoid(x W_b) where
             `linear_allow_neg_eigval`, else sigmoid(x W_b)
             state S [d_k, d_v] a head, float32, S_0 = 0, TOKEN BY TOKEN:
               S' = alpha_t S_{t-1}
               S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t
             a head u_t = RMSNorm_{d_v}(o_t; gamma_o) * silu((x W_og)_h);
             Mix = concat_h(u) W_o
    full     H heads of d = h / H: q = RMSNorm(x W_q; g_q) and k =
             RMSNorm(x W_k; g_k), each over the whole projection; v = x W_v;
             no rotary; causal softmax(q k^T / sqrt(d)) v, then W_o
    loss     final RMSNorm, untied head, mean cross-entropy, float32

The recurrence is a `lax.scan` over the tokens (`kda_mla_moe.delta_rule`,
whose state is [d_k, d_v] and which takes the per-head decay broadcast
over the channels): no chunk, no triangular system, no kernel, no padding
of the heads' widths. Queries of the full layer go in blocks of
`QUERY_BLOCK`, and each layer runs under `jax.checkpoint`: both repeat
arithmetic and change none.

The update is one chip's share of a ZeRO-1 optimizer state over `count`
data-parallel ranks (the configuration's `deployment.opt_share`, [index,
count]): the first AdamW step updates the `index`-th of `count` slices of
each weight, on the first dimension that `count` divides and is no larger
than (a weight that none divides whole), from the same slice of its
gradient; the rest of every weight stays as it was. It is handed the
program's parameters and so shares their layout, which it reads from the
tree itself: "sparse" is the group blocks.segments names the layers after
the leading dense ones, a list with one tree a position of the period,
each stacked over the periods.

`dtype` and `state_dtype` are for the readings a tolerance is set between
(`check_step`, `check_rule`): the whole of it in bfloat16, or only the state
S and the log-decays in bfloat16. The reference is float32 in both.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import common, kda_mla_moe
from benchmarks.reference.kda_mla_moe import short_conv, sigmoid
from benchmarks.reference.mla_moe import rms_norm, swiglu

QK_NORM_EPS = 1e-6
QUERY_BLOCK = 512
LINEAR = "linear_attention"
# what the program keeps float32 and the reference with it; the masters'
# rounding is the Kimi cell's reference's, on the same names
as_the_forward_sees = kda_mla_moe.as_the_forward_sees
decayed = kda_mla_moe.decayed


def rule_operands(x, p, config: dict):
    """The linear layer's (q, k, v, log-decays [s, H, d_k], write strengths)
    on one sequence x [s, h], the residual stream: what the recurrence
    takes."""
    heads, dk, dv = (config["linear_num_key_heads"],
                     config["linear_key_head_dim"],
                     config["linear_value_head_dim"])
    assert config["linear_num_value_heads"] == heads
    assert p["q_conv_w"].shape[0] == config["linear_conv_kernel_dim"]
    s = x.shape[0]
    q, k = (short_conv(x @ p[f"{n}_w"], p[f"{n}_conv_w"]).reshape(
        s, heads, dk) for n in "qk")
    v = short_conv(x @ p["v_w"], p["v_conv_w"]).reshape(s, heads, dv)
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + QK_NORM_EPS) \
        * dk ** -0.5
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + QK_NORM_EPS)
    a = (x @ p["decay_w"]).astype(jnp.float32) + p["dt_bias"]
    softplus = jnp.maximum(a, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(a)))
    g = -jnp.exp(p["decay_log"]) * softplus                     # [s, H]
    scale = 2.0 if config["linear_allow_neg_eigval"] else 1.0
    beta = scale * sigmoid((x @ p["beta_w"]).astype(jnp.float32))
    return (q, k, v, jnp.broadcast_to(g[..., None], (s, heads, dk)),
            beta.astype(q.dtype))


def linear_attention(x, p, config: dict, state_dtype=jnp.float32):
    """The Gated DeltaNet mixer on one sequence x [s, h]."""
    heads, dv = (config["linear_num_key_heads"],
                 config["linear_value_head_dim"])
    s = x.shape[0]
    o = kda_mla_moe.delta_rule(*rule_operands(x, p, config), state_dtype)
    z = (x @ p["o_gate_w"]).reshape(s, heads, dv)
    u = rms_norm(o, p["o_ln"], config["rms_norm_eps"]) * z * sigmoid(z)
    return u.reshape(s, heads * dv) @ p["o_w"]


def full_attention(x, p, config: dict):
    """Causal multi-head attention with the q/k norm and no rotary, on one
    sequence x [s, h] -> [s, h] after W_o."""
    s, h = x.shape
    heads = config["num_attention_heads"]
    assert config["num_key_value_heads"] == heads
    d = h // heads
    eps = config["rms_norm_eps"]
    q = rms_norm(x @ p["q_w"], p["ln_q_g"], eps).reshape(s, heads, d)
    k = rms_norm(x @ p["k_w"], p["ln_k_g"], eps).reshape(s, heads, d)
    v = (x @ p["v_w"]).reshape(s, heads, d)
    size = min(s, QUERY_BLOCK)
    assert s % size == 0, s
    j = jnp.arange(s)[None, :]

    @jax.checkpoint
    def block(first):
        """`size` queries from `first` against every key (checkpointed: the
        backward holds one block's scores, not the sequence's)."""
        i = first + jnp.arange(size)[:, None]
        rows = jax.lax.dynamic_slice_in_dim(q, first, size)
        scores = jnp.einsum("qhd,khd->hqk", rows, k) / math.sqrt(d)
        scores = jnp.where(j <= i, scores, -jnp.inf)
        scores = scores - scores.max(-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / probs.sum(-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(block, jnp.arange(0, s, size)).reshape(s, h)
    return out @ p["o_w"]


def layers_in_order(params, config: dict) -> list:
    """(kind, parameters) of each layer, in layer order, from the tree's own
    layout."""
    found = []
    for group in ("dense", "sparse", "tail"):
        positions = params.get(group, [])
        periods = positions[0]["ln1_g"].shape[0] if positions else 0
        found += [jax.tree_util.tree_map(lambda a: a[period], tree)
                  for period in range(periods) for tree in positions]
    kinds = config["layer_types"]
    assert len(found) == config["num_hidden_layers"] == len(kinds)
    return list(zip(kinds, found))


def layer(kind: str, config: dict, state_dtype):
    """One post-norm layer, (x [s, h], its parameters) -> x,
    checkpointed."""
    eps = config["rms_norm_eps"]

    @jax.checkpoint
    def run(x, p):
        mixed = linear_attention(x, p, config, state_dtype) \
            if kind == LINEAR else full_attention(x, p, config)
        x = x + rms_norm(mixed, p["ln1_g"], eps)
        return x + rms_norm(swiglu(x, p["gate_w"], p["up_w"], p["down_w"]),
                            p["ln2_g"], eps)

    return run


def nll(params, tokens, labels, config: dict, state_dtype=jnp.float32):
    """(summed NLL, count) of one sequence, tokens and labels [s]."""
    x = params["wte"][tokens]
    for kind, p in layers_in_order(params, config):
        x = layer(kind, config, state_dtype)(x, p)
    x = rms_norm(x, params["lnf_g"], config["rms_norm_eps"])
    return common.nll_sum(x @ params["lm_head"].T, labels)


# ------------------------------------------------------ the held share

def held_dim(shape, count: int):
    """The dimension a weight of `shape` is divided on over `count` ranks:
    the first that `count` divides and is no larger than; None where none
    is (the weight is held whole)."""
    return next((i for i, n in enumerate(shape)
                 if n % count == 0 and n >= count), None)


def held(tree, config: dict):
    """The held slice of every leaf (the leaf whole where it is held
    whole)."""
    index, count = config["deployment"]["opt_share"]

    def one(a):
        dim = held_dim(a.shape, count)
        if dim is None:
            return a
        size = a.shape[dim] // count
        return jax.lax.slice_in_dim(a, index * size, (index + 1) * size,
                                    axis=dim)

    return jax.tree_util.tree_map(one, tree)


def put_held(tree, part, config: dict):
    """`tree` with the held slice of each leaf replaced by `part`'s."""
    index, count = config["deployment"]["opt_share"]

    def one(a, b):
        dim, b = held_dim(a.shape, count), b.astype(a.dtype)
        if dim is None:
            return b
        return jax.lax.dynamic_update_slice_in_dim(
            a, b, index * b.shape[dim], dim)

    return jax.tree_util.tree_map(one, tree, part)


def _total(params, tokens, labels, config: dict, state_dtype):
    total, count = nll(params, tokens, labels, config, state_dtype)
    return total, (total, count)


def _grad_fn(config: dict, state_dtype=jnp.float32):
    """(value, (summed NLL, count)) and the HELD slices of the gradient:
    the rest is of no use to the update, and kept whole the summed
    gradients of a 929 M-parameter model would not fit beside it."""
    value_and_grad = jax.value_and_grad(functools.partial(
        _total, config=config, state_dtype=state_dtype), has_aux=True)

    @jax.jit
    def run(params, tokens, labels):
        found, grads = value_and_grad(params, tokens, labels)
        return found, held(grads, config)

    return run


def _mean(found) -> float:
    return sum(float(f[0]) for f in found) / sum(int(f[1]) for f in found)


def check_step(master, seqs, config: dict, precision="highest",
               dtype=jnp.float32, state_dtype=jnp.float32):
    """One training step from the float32 `master` weights the seed gives
    on the check sequences (tokens [n, s], labels [n, s]), one at a time, as
    models/trainer.py makes it: the gradient of the mean cross-entropy at
    the weights the forward sees, the first AdamW update of the HELD slices
    of the masters, the rest untouched; then the loss on them again at the
    new masters' cast: `loss0`, `loss1`. "highest" products, float32
    everywhere and a float32 state are the reference; `dtype` bfloat16
    (nothing float32, the update of the bfloat16 weights themselves) and
    `state_dtype` are the readings a tolerance is set between. `master`
    is donated."""
    keep = dtype == jnp.float32
    with jax.default_matmul_precision(precision):
        grad_fn = _grad_fn(config, state_dtype)
        # the masters wait on the host while the gradients take their room
        host = jax.device_get(master) if keep else None
        params = jax.jit(functools.partial(
            as_the_forward_sees, config=config, dtype=dtype),
            donate_argnums=(0,))(master)
        found, grads = [], None
        for t, l in zip(*seqs):
            (_, aux), g = grad_fn(params, t, l)
            found.append(aux)
            grads = g if grads is None else _add(grads, g)
        if keep:
            del params
            master = jax.device_put(host)
        else:       # the bfloat16 weights themselves, in float32 to update
            master = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), params)
            del params
        count = sum(int(f[1]) for f in found)

        def update(master, grads):
            mine = held(master, config)
            return put_held(master, common.adamw_first_update(
                mine, jax.tree_util.tree_map(lambda x: x / count, grads),
                decayed(mine), config["optimizer"], dtype), config)

        master = jax.jit(update, donate_argnums=(0,))(master, grads)
        del grads
        params = jax.jit(functools.partial(
            as_the_forward_sees, config=config, dtype=dtype),
            donate_argnums=(0,))(master)
        loss1 = _mean([grad_fn(params, t, l)[0][1] for t, l in zip(*seqs)])
    return {"loss0": _mean(found), "loss1": loss1}


def check_rule(master, tokens, cotangent, config: dict,
               state_dtype=jnp.float32):
    """The FIRST layer's rule alone, which must be a linear layer's, from
    the float32 masters at the forward's cast, on the sequences tokens [n,
    s], one at a time: (o [n, s, H, d_v], the gradient of sum(o *
    cotangent) to the layer's input x = wte[tokens], [n, s, h]). Operands
    by this file's convolutions, norms and decay, o by the token
    recurrence, the gradient by its transpose: for the program's chunked
    form, forward and backward, at the timed widths. "highest" products
    and a float32 state are the reference; `state_dtype` is for the
    reading a limit lies under."""
    assert config["layer_types"][0] == LINEAR

    def rule(x, p):
        return kda_mla_moe.delta_rule(*rule_operands(x, p, config),
                                      state_dtype)

    @jax.jit
    def both(wte, first, tokens, cotangent):
        seen = as_the_forward_sees(
            {"wte": wte, "first": jax.tree_util.tree_map(
                lambda a: a[0], first)}, config)
        o, back = jax.vjp(functools.partial(rule, p=seen["first"]),
                          seen["wte"][tokens])
        return o, back(cotangent.astype(jnp.float32))[0]

    with jax.default_matmul_precision("highest"):
        found = [both(master["wte"], master["sparse"][0], t, c)
                 for t, c in zip(tokens, cotangent)]
    return tuple(jnp.stack(x) for x in zip(*found))


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)
