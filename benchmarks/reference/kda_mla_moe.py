"""A decoder whose layers are of two kinds, a gated-delta-rule linear
attention with a decay per key channel (KDA, arXiv:2510.26692) and a
position-free latent softmax attention (MLA with `mla_use_nope`), on one
residual stream with sparse experts and a router bias moved by load, written
from the equations as ISSUE 37 states them. No bias anywhere; y = RMSNorm(x).

    layer    x <- x + Attn(y); x <- x + FFN(RMSNorm(x)); Attn is KDA in the
             layers `linear_attn_config.kda_layers` names (counted from 1)
             and MLA in `full_attn_layers`
    KDA      H heads of d. q~, k~, v~ = y W_q, y W_k, y W_v [s, H d]
             short convolution, causal, depthwise, K taps, zero before the
             row's start, then SiLU: q_t = silu(sum_{i<K} w_i * q~_{t-K+1+i})
             and the same for k and v on taps of their own
             a head: q <- q / sqrt(|q|^2 + 1e-6) * d^-1/2, k <- k / sqrt(|k|^2
             + 1e-6)
             decay a head AND key channel: a_t = (y W_fa) W_fb,
             g_t = -exp(A_h) softplus(a_t + b_dt), alpha_t = exp(g_t)
             write strength a head: beta_t = sigmoid(y W_beta)
             state S [d, d] a head, float32, S_0 = 0, TOKEN BY TOKEN:
               S' = Diag(alpha_t) S_{t-1}
               S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t
             z_t = (y W_ga) W_gb; a head u_t = RMSNorm_d(o_t; gamma_o)
             * sigmoid(z_t); Attn = concat_h(u) W_o
    MLA      q = y W_q a head (nope | 64 more), no latent
             [c | k_r] = y W_kva; [k_nope | v] = RMSNorm(c) W_kvb a head;
             k = (k_nope | k_r), k_r one vector a token that every head
             shares, NOT rotated: the model has no rotary table
             causal softmax(q k^T / sqrt(nope + 64)) v, then W_o
    FFN      layer 1 (`first_k_dense_replace`): SwiGLU. The others:
             s = sigmoid(y W_r) over all the experts; the k largest of
             s + b; w = scaling * s[chosen] / sum s[chosen]; Shared(y) + sum
             over the chosen AND HELD experts of w_e E_e(y), each a SwiGLU
    bias     after a step, c_e = pairs of the step's batch that chose e,
             over all the experts: b_e <- b_e + gamma sign(mean(c) - c_e)
    loss     final RMSNorm, untied head, mean cross-entropy, float32

The recurrence is a `lax.scan` over the tokens: no chunk, no triangular
system, no kernel. Its backward pass would keep a state a token (32 heads x
128 x 128 float32 = 2 MB, 4 GB a layer at 2,048 tokens), so the scan runs in
blocks of 64 tokens under `jax.checkpoint`, and each layer whole under
another: that repeats arithmetic in the backward pass and changes none.

Given a chip's share it computes that share (`reference/mla_moe.py`): of the
routed experts only those held (`deployment.experts_first`, `num_experts`),
chosen by a router over all `published.num_experts`, each held expert a
dense product under a mask; logits and loss over the held rows of the
vocabulary. It is handed the program's parameters and so shares their
layout, which it reads from the tree itself: "dense", "sparse" and "tail"
are lists with one tree a position of a period, each stacked over the
periods (the layers in order are the periods' positions interleaved).

`dtype` and `state_dtype` are for the readings a tolerance is set between
(`check_step`, `check_rule`): the whole of it in bfloat16, or only the state
S and the log-decays in bfloat16. The reference is float32 in both.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import common
from benchmarks.reference.mla_moe import rms_norm, swiglu
from benchmarks.reference.mla_moe_mtp import round_through

QK_NORM_EPS = 1e-6
FLOAT32 = ("router_w", "router_b", "decay_log", "dt_bias")  # the program's


def sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def short_conv(x, taps):
    """x [s, c], taps [K, c] -> silu(sum_i taps[i] * x[t - K + 1 + i])."""
    s, size = x.shape[0], taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((size - 1, x.shape[1]), x.dtype), x])
    out = sum(taps[i] * padded[i:i + s] for i in range(size))
    return out * sigmoid(out)


def delta_rule(q, k, v, g, beta, state_dtype=jnp.float32):
    """q, k, g [s, H, d], v [s, H, d_v], beta [s, H] -> o [s, H, d_v]: the
    recurrence, one token at a time, in blocks of tokens that the backward
    pass recomputes."""
    s, heads, d = q.shape
    block = math.gcd(s, 64)

    def read(S, key):           # S^T key a head, written out: no product
        return (S * key[..., None]).sum(-2)     # unit to round or to pass

    def token(S, x):
        q, k, v, g, beta = x
        S = (jnp.exp(g)[..., None] * S).astype(state_dtype)
        write = beta[:, None] * (v - read(S, k))
        S = (S + k[..., None] * write[:, None, :]).astype(state_dtype)
        return S, read(S, q)

    @jax.checkpoint
    def tokens(S, xs):
        return jax.lax.scan(token, S, xs)

    xs = tuple(x.reshape((s // block, block) + x.shape[1:])
               for x in (q, k, v, g.astype(state_dtype), beta))
    _, o = jax.lax.scan(tokens, jnp.zeros((heads, d, v.shape[-1]),
                                          state_dtype), xs)
    return o.reshape(s, heads, -1).astype(q.dtype)


def rule_operands(y, p, config: dict):
    """KDA's (q, k, v, log-decays, write strengths) a head on one sequence
    y [s, h], already normed: what the recurrence takes."""
    spec = config["linear_attn_config"]
    heads, d = spec["num_heads"], spec["head_dim"]
    assert p["q_conv_w"].shape[0] == spec["short_conv_kernel_size"]
    s = y.shape[0]
    q, k, v = (short_conv(y @ p[f"{n}_w"], p[f"{n}_conv_w"]).reshape(
        s, heads, d) for n in "qkv")
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + QK_NORM_EPS) * d ** -0.5
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + QK_NORM_EPS)
    a = ((y @ p["decay_a_w"]) @ p["decay_b_w"]).astype(jnp.float32) \
        + p["dt_bias"]
    softplus = jnp.maximum(a, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(a)))
    g = -jnp.exp(p["decay_log"])[:, None] * softplus.reshape(s, heads, d)
    beta = sigmoid((y @ p["beta_w"]).astype(jnp.float32))
    return q, k, v, g, beta.astype(q.dtype)


def linear_attention(y, p, config: dict, state_dtype=jnp.float32):
    """KDA on one sequence y [s, h], already normed."""
    spec = config["linear_attn_config"]
    heads, d = spec["num_heads"], spec["head_dim"]
    s = y.shape[0]
    o = delta_rule(*rule_operands(y, p, config), state_dtype)
    z = ((y @ p["gate_a_w"]) @ p["gate_b_w"]).reshape(s, heads, d)
    u = rms_norm(o, p["o_ln"], config["rms_norm_eps"]) * sigmoid(z)
    return u.reshape(s, heads * d) @ p["o_w"]


def latent_attention(y, p, config: dict):
    """MLA without a query latent and without rotation, on y [s, h]."""
    s = y.shape[0]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    rank, heads = config["kv_lora_rank"], config["num_attention_heads"]
    assert config["q_lora_rank"] is None and config["mla_use_nope"]
    q = (y @ p["q_w"]).reshape(s, heads, dn + dr)
    kv_a = y @ p["kv_a_w"]
    c_kv = rms_norm(kv_a[:, :rank], p["kv_a_ln"], config["rms_norm_eps"])
    kv = (c_kv @ p["kv_b_w"]).reshape(s, heads, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        kv_a[:, None, rank:], (s, heads, dr))], -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dn + dr)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    scores = scores - scores.max(-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / probs.sum(-1, keepdims=True)
    out = jnp.einsum("hqk,khd->qhd", probs, kv[..., dn:])
    return out.reshape(s, heads * dv) @ p["o_w"]


def route(y, router_w, router_b, config: dict):
    """(chosen [s, k], weights [s, k]) over all the routed experts, in
    float32 whatever y is."""
    assert config["moe_router_activation_func"] == "sigmoid" \
        and config["moe_renormalize"] and config["num_expert_group"] == 1
    score = sigmoid(y.astype(router_w.dtype) @ router_w)
    _, chosen = jax.lax.top_k(score + router_b,
                              config["num_experts_per_token"])
    w = jnp.take_along_axis(score, chosen, -1)
    return chosen, w / w.sum(-1, keepdims=True) \
        * config["routed_scaling_factor"]


def sparse_ffn(y, p, config: dict):
    """(shared expert + the part the held routed experts give, the pairs
    each of ALL the experts drew [E])."""
    chosen, w = route(y, p["router_w"], p["router_b"], config)
    out = swiglu(y, p["shared_gate_w"], p["shared_up_w"], p["shared_down_w"])
    first, held = (config["deployment"]["experts_first"],
                   config["num_experts"])

    def add(out, expert):                                   # a held one
        i, e = expert
        mine = (w * (chosen == first + i)).sum(-1, keepdims=True)   # [s, 1]
        return out + mine.astype(y.dtype) * swiglu(
            y, e["gate_w"], e["up_w"], e["down_w"]), None

    out, _ = jax.lax.scan(add, out, (jnp.arange(held), jax.tree_util.tree_map(
        lambda a: a[:held], p["experts"])))
    drawn = (chosen[..., None] == jnp.arange(p["router_w"].shape[-1])).sum(
        (0, 1))
    return out, drawn


def layers_in_order(params, config: dict):
    """[(layer number from 1, its parameters, linear?, sparse?)] from the
    tree's own layout: a group is a list of positions, each stacked over
    the periods."""
    found = []
    for group in ("dense", "sparse", "tail"):
        positions = params.get(group, [])
        periods = positions[0]["ln1_g"].shape[0] if positions else 0
        for period in range(periods):
            for tree in positions:
                found.append(jax.tree_util.tree_map(
                    lambda a: a[period], tree))
    spec = config["linear_attn_config"]
    assert len(found) == config["num_hidden_layers"] \
        and sorted(spec["kda_layers"] + spec["full_attn_layers"]) \
        == list(range(1, len(found) + 1))
    return [(n, p, n in spec["kda_layers"],
             n > config["first_k_dense_replace"])
            for n, p in enumerate(found, 1)]


def layer(linear: bool, sparse: bool, config: dict, state_dtype):
    """One pre-norm layer, (x [s, h], its parameters) -> (x, pairs drawn
    [E] or None), checkpointed."""
    eps = config["rms_norm_eps"]

    @jax.checkpoint
    def run(x, p):
        y = rms_norm(x, p["ln1_g"], eps)
        x = x + (linear_attention(y, p, config, state_dtype) if linear
                 else latent_attention(y, p, config))
        y = rms_norm(x, p["ln2_g"], eps)
        if not sparse:
            return x + swiglu(y, p["gate_w"], p["up_w"], p["down_w"]), None
        y, drawn = sparse_ffn(y, p, config)
        return x + y, drawn

    return run


def parts(params, tokens, labels, config: dict, state_dtype=jnp.float32):
    """tokens, labels [s] of one sequence -> ((summed NLL, count), pairs
    drawn [L_sparse, E] in layer order)."""
    x = params["wte"][tokens]
    drawn = []
    for _, p, linear, sparse in layers_in_order(params, config):
        x, d = layer(linear, sparse, config, state_dtype)(x, p)
        drawn += [d] if sparse else []
    x = rms_norm(x, params["lnf_g"], config["rms_norm_eps"])
    return common.nll_sum(x @ params["lm_head"].T, labels), jnp.stack(drawn)


def nll(params, tokens, labels, config: dict):
    """(summed NLL, count): the form `aot_check.py` takes."""
    return parts(params, tokens, labels, config)[0]


def decayed(params):
    """Weight decay on the matrices (names ending in `_w`) and the
    embeddings; none on norm gains, the router's bias, `decay_log` and
    `dt_bias` (models/mla_moe.py agrees)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key in ("wte", "lm_head")
        or path[-1].key.endswith("_w"), params)


def as_the_forward_sees(master, config: dict, dtype=jnp.float32):
    """The float32 master weights rounded to what the forward pass is given
    (`reference/mla_moe_mtp.as_the_forward_sees`): the configuration's
    dtype on their bits, and float32 as they are for the router's matrix
    and bias and the decay's two vectors; then cast to `dtype`, the
    float32 ones too when it is not float32."""
    stated = jnp.dtype(config["dtype"])
    return jax.tree_util.tree_map_with_path(
        lambda path, a: (a if path[-1].key in FLOAT32
                         else round_through(a, stated)).astype(dtype), master)


def router_biases(params):
    """[L_sparse, E] in layer order."""
    found = []
    for group in ("sparse", "tail"):
        positions = params.get(group, [])
        if positions:
            both = jnp.stack([p["router_b"] for p in positions], 1)
            found.append(both.reshape((-1,) + both.shape[2:]))
    return jnp.concatenate(found)


def move_router_biases(params, drawn, config: dict):
    """b_e + gamma sign(mean(c) - c_e) for every router, from the pairs
    `drawn` [L_sparse, E] of the step's whole batch."""
    drawn = drawn.astype(jnp.float32)
    moved = router_biases(params) + config["router_bias_update_rate"] \
        * jnp.sign(drawn.mean(-1, keepdims=True) - drawn)
    params, at = dict(params), 0
    for group in ("sparse", "tail"):
        positions = params.get(group, [])
        if positions:
            periods, size = positions[0]["router_b"].shape[0], len(positions)
            mine = moved[at:at + periods * size].reshape(periods, size, -1)
            params[group] = [dict(p, router_b=mine[:, i])
                             for i, p in enumerate(positions)]
            at += periods * size
    return params


def _total(params, tokens, labels, config: dict, state_dtype):
    (total, count), drawn = parts(params, tokens, labels, config,
                                  state_dtype)
    return total, (total, count, drawn)


def _grad_fn(config: dict, state_dtype=jnp.float32):
    return jax.jit(jax.value_and_grad(functools.partial(
        _total, config=config, state_dtype=state_dtype), has_aux=True))


def _mean(found) -> float:
    return sum(float(f[0]) for f in found) / sum(int(f[1]) for f in found)


def train_step(master, seqs, config: dict, grad_fn=None,
               dtype=jnp.float32):
    """One training step from the float32 `master` weights on the sequences
    (tokens [n, s], labels [n, s]), one at a time, as models/trainer.py
    makes it: the gradient of the mean cross-entropy at the weights the
    forward sees, the first AdamW update of the masters themselves and the
    biases' move. With `dtype` bfloat16 nothing is float32: the update is
    of the bfloat16 weights. Returns (the masters after it, the loss, the
    pairs every expert of every router drew [L_sparse, E]); `master` is
    donated."""
    grad_fn = grad_fn or _grad_fn(config)
    keep = dtype == jnp.float32
    # the masters wait on the host while the gradients take their room
    host = jax.device_get(master) if keep else None
    params = jax.jit(functools.partial(as_the_forward_sees, config=config,
                                       dtype=dtype),
                     donate_argnums=(0,))(master)
    found, grads = [], None
    for t, l in zip(*seqs):
        (_, aux), g = grad_fn(params, t, l)
        found.append(aux)
        grads = g if grads is None else _add(grads, g)
    if keep:
        del params
        master = jax.device_put(host)
    else:
        master = params
    count = sum(int(f[1]) for f in found)
    update = jax.jit(lambda p, g, drawn: move_router_biases(
        common.adamw_first_update(
            p, jax.tree_util.tree_map(lambda x: x / count, g), decayed(p),
            config["optimizer"], dtype), drawn, config),
        donate_argnums=(0,))
    drawn = sum(f[2] for f in found)
    return update(master, grads, drawn), _mean(found), drawn


def check_step(master, seqs, config: dict, precision="highest",
               dtype=jnp.float32, state_dtype=jnp.float32):
    """`train_step` on the check sequences from the float32 masters the
    seed gives, then the loss on them again at the new masters' cast:
    `loss0`, `loss1`, `pairs0` (the pairs each expert of each router drew
    at step 0) and `biases1`, both [L_sparse, E], and the share of the
    biases that moved. "highest" products, float32 everywhere and a
    float32 state are the reference; lower ones are for the readings a
    tolerance is set between."""
    with jax.default_matmul_precision(precision):
        grad_fn = _grad_fn(config, state_dtype)
        before = router_biases(master)
        master, loss0, drawn = train_step(master, seqs, config, grad_fn,
                                          dtype)
        after = router_biases(master)
        params = jax.jit(functools.partial(
            as_the_forward_sees, config=config, dtype=dtype),
            donate_argnums=(0,))(master)
        loss1 = _mean([grad_fn(params, t, l)[0][1] for t, l in zip(*seqs)])
    return {"loss0": loss0, "loss1": loss1,
            "biases1": jax.device_get(after),
            "pairs0": jax.device_get(drawn),
            "bias_moved_share": float((after != before).mean())}


def check_rule(master, tokens, cotangent, config: dict,
               state_dtype=jnp.float32):
    """The FIRST layer's rule alone, which must be a KDA layer's, from the
    float32 masters at the forward's cast, on the sequences tokens [n, s],
    one at a time: (o [n, s, H, d], the gradient of sum(o * cotangent) to
    the layer's input x = wte[tokens], [n, s, h]). Operands by this file's
    convolution, norms and decay, o by the token recurrence, the gradient
    by its transpose: for the program's chunked form, forward and
    backward, at the timed widths. "highest" products and a float32 state
    are the reference; `state_dtype` is for the reading a limit lies
    under."""
    assert 1 in config["linear_attn_config"]["kda_layers"]
    group = next(g for g in ("dense", "sparse", "tail") if master.get(g))

    def rule(x, p):
        y = rms_norm(x, p["ln1_g"], config["rms_norm_eps"])
        return delta_rule(*rule_operands(y, p, config), state_dtype)

    @jax.jit
    def both(wte, first, tokens, cotangent):
        seen = as_the_forward_sees(
            {"wte": wte, "first": jax.tree_util.tree_map(
                lambda a: a[0], first)}, config)
        o, back = jax.vjp(functools.partial(rule, p=seen["first"]),
                          seen["wte"][tokens])
        return o, back(cotangent.astype(jnp.float32))[0]

    with jax.default_matmul_precision("highest"):
        found = [both(master["wte"], master[group][0], t, c)
                 for t, c in zip(tokens, cotangent)]
    return tuple(jnp.stack(x) for x in zip(*found))


@jax.jit
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)
