"""A pre-norm decoder whose layers come in two kinds, with grouped-query
attention and a routed feed-forward, written from the equations as ISSUE 32
states them (Su et al. 2021 for the rotary embedding, Peng et al. 2023 for
yarn, Ainslie et al. 2023 for grouped queries, Fedus et al. 2021 for the
router's balance term):

    layer    x <- x + Attn_t(RMSNorm(x)) W_o; x <- x + FFN(RMSNorm(x))
    Attn_t   q = x W_q (H heads of d), k = x W_k, v = x W_v (H_kv heads);
             q, k rotated by position, the components paired as halves:
             kind "sliding_attention" by theta^(-2i/d), kind
             "full_attention" by yarn's frequencies with cos and sin times
             `attention_factor`; query head h reads K/V head h // (H / H_kv);
             a_ij = q_i . k_j / sqrt(d); key j visible to query i iff j <= i
             and (kind full or i - j < sliding_window); float32 softmax
    FFN      p = softmax(x W_r) over ALL E experts; the k largest chosen;
             w_e = p_e / sum of the chosen p; FFN = sum over the chosen AND
             HELD experts of w_e down_e(silu(gate_e x) * up_e x); no shared
             expert, no bias, no scaling
    loss     L = L_lm + alpha * mean over layers of E * sum_e F_e P_e, F_e
             the share of the BATCH's tokens that chose e (a count: no
             gradient), P_e the mean over the batch's tokens of p_e

Given a chip's share it computes that share: of the routed experts only
those held (`deployment.experts_first`, `num_experts`), chosen by a router
over all `published.num_experts`; what the absent ones would add is left
out. One loop over the held experts with a mask (a `lax.scan`): no sort, no
grouped product, no kernel. The masks are built from the positions i and j;
queries go in blocks of `QUERY_BLOCK`, so that one 4,096-token sequence's
float32 scores fit, and each layer runs under `jax.checkpoint`: both repeat
arithmetic and change none.

A batch's balance term is not the mean of its sequences' (F and P are both
means over the batch), so a step takes two passes over the sequences: the
first counts F, the second differentiates L with F given.

`dtype`, `window` and `group_of` are what the tolerances' readings vary:
the reference proper is float32, with the configuration's window and query
head h on K/V head h // (H / H_kv).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import common
from benchmarks.reference.mla_moe_mtp import round_through

QUERY_BLOCK = 512
WINDOW = "sliding_attention"


def rms_norm(x, gain, eps):
    """Zhang & Sennrich 2019: x / sqrt(mean(x^2) + eps) * gain."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gain


def inv_freq(dim: int, p: dict):
    """[dim/2] inverse frequencies of one kind's rotary embedding, and the
    factor on its cos and sin: (theta^(-2i/dim), 1) for `default`; for
    `yarn` the frequencies divided by `factor` above `high`, kept below
    `low`, a linear ramp between, and `attention_factor`."""
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = p["rope_theta"] ** (-2.0 * i / dim)
    if p["rope_type"] == "default":
        return plain, 1.0
    assert p["rope_type"] == "yarn", p

    def corr(rotations):
        return dim * math.log(p["original_max_position_embeddings"]
                              / (2 * math.pi * rotations)) \
            / (2 * math.log(p["rope_theta"]))

    low = max(math.floor(corr(p["beta_fast"])), 0)
    high = min(math.ceil(corr(p["beta_slow"])), dim - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return plain / p["factor"] * ramp + plain * (1.0 - ramp), \
        p["attention_factor"]


def rope(x, freqs, factor):
    """x [s, heads, dim] rotated by position, halves paired (i with
    i + dim/2), cos and sin times `factor`."""
    s, half = x.shape[0], x.shape[-1] // 2
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = (jnp.cos(angle) * factor)[:, None, :].astype(x.dtype)
    sin = (jnp.sin(angle) * factor)[:, None, :].astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(x, p, kind: str, config: dict, window="config",
              group_of="floor"):
    """One sequence x [s, h], already normed -> [s, H d] before W_o."""
    s = x.shape[0]
    heads, kv_heads, d = (config["num_attention_heads"],
                          config["num_key_value_heads"], config["head_dim"])
    rep = heads // kv_heads
    freqs, factor = inv_freq(d, config["rope_parameters"][kind])
    q = rope((x @ p["q_w"]).reshape(s, heads, d), freqs, factor)
    k = rope((x @ p["k_w"]).reshape(s, kv_heads, d), freqs, factor)
    v = (x @ p["v_w"]).reshape(s, kv_heads, d)
    # the K/V head of each query head
    of = jnp.arange(heads) // rep if group_of == "floor" \
        else jnp.arange(heads) % kv_heads
    k, v = k[:, of], v[:, of]                                 # [s, H, d]
    if window == "config":
        window = config["sliding_window"] if kind == WINDOW else None
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
        seen = j <= i
        if window is not None:
            seen = seen & (i - j < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        scores = scores - scores.max(-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / probs.sum(-1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    return jax.lax.map(block, jnp.arange(0, s, size)).reshape(s, heads * d)


def swiglu(x, gate_w, up_w, down_w):
    """Shazeer 2020: down(silu(gate x) * up x)."""
    g = x @ gate_w
    return (g / (1.0 + jnp.exp(-g)) * (x @ up_w)) @ down_w


def route(x, router_w, config: dict):
    """(chosen [s, k], weights [s, k], p [s, E]) over all the experts, in
    x's dtype (float32 in the reference proper)."""
    z = x @ router_w
    z = z - z.max(-1, keepdims=True)
    p = jnp.exp(z)
    p = p / p.sum(-1, keepdims=True)
    w, chosen = jax.lax.top_k(p, config["num_experts_per_tok"])
    return chosen, w / w.sum(-1, keepdims=True), p


def routed_ffn(x, p, config: dict):
    """(the part the held experts give [s, h], pairs each of ALL the
    experts drew [E], sum over this sequence's tokens of p [E])."""
    chosen, w, probs = route(x, p["router_w"], config)
    first, held = (config["deployment"]["experts_first"],
                   config["num_experts"])

    def add(out, expert):                                   # a held one
        i, e = expert
        mine = (w * (chosen == first + i)).sum(-1, keepdims=True)   # [s, 1]
        return out + mine * swiglu(x, e["gate_w"], e["up_w"],
                                   e["down_w"]), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(x), (
        jnp.arange(held), jax.tree_util.tree_map(lambda a: a[:held],
                                                 p["experts"])))
    drawn = (chosen[..., None] == jnp.arange(probs.shape[-1])).sum((0, 1))
    return out, drawn, probs.astype(jnp.float32).sum(0)


def layer(kind: str, config: dict, **how):
    """One pre-norm layer of `kind`, checkpointed: (x [s, h], its
    parameters) -> (x, (pairs drawn [E], summed p [E]))."""
    eps = config["rms_norm_eps"]

    @jax.checkpoint
    def run(x, p):
        x = x + attention(rms_norm(x, p["ln1_g"], eps), p, kind, config,
                          **how) @ p["o_w"]
        y, drawn, p_sum = routed_ffn(rms_norm(x, p["ln2_g"], eps), p, config)
        return x + y, (drawn, p_sum)

    return run


def parts(params, tokens, labels, config: dict, **how):
    """tokens, labels [s] of one sequence -> ((summed NLL, count), pairs
    drawn [L, E], summed p [L, E])."""
    x = params["wte"][tokens]
    drawn, p_sums = [], []
    for n, kind in enumerate(config["layer_types"]):
        x, (d, p) = layer(kind, config, **how)(
            x, jax.tree_util.tree_map(lambda a: a[n], params["blocks"]))
        drawn.append(d)
        p_sums.append(p)
    x = rms_norm(x, params["lnf_g"], config["rms_norm_eps"])
    return common.nll_sum(x @ params["lm_head"].T, labels), \
        jnp.stack(drawn), jnp.stack(p_sums)


def balance_of(share, p_mean):
    """mean over layers of E * sum_e F_e P_e: share, p_mean [L, E]."""
    return (share.shape[-1] * (share * p_mean).sum(-1)).mean()


def nll(params, tokens, labels, config: dict):
    """(a sum, a count) whose quotient is ONE sequence's L_lm + alpha *
    its own balance term: the form `aot_check.py` compiles. (A batch's
    balance term is `check_step`'s: it is not the mean of these.)"""
    (total, count), drawn, p_sum = parts(params, tokens, labels, config)
    s = tokens.shape[0]
    balance = balance_of(jax.lax.stop_gradient(drawn / s), p_sum / s)
    return total + config["router_aux_loss_coef"] * balance * count, count


def decayed(params):
    """Weight decay on the matrices and the embeddings; none on norm gains
    (models/llama.py agrees)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: not path[-1].key.startswith("ln"), params)


def as_the_forward_sees(master, config: dict):
    """The float32 master weights rounded, on their bits, to what the
    forward pass is given (models/trainer.py hands the model their cast):
    the configuration's dtype, and float32 as it is for the router's
    matrix."""
    dtype = jnp.dtype(config["dtype"])
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key == "router_w"
        else round_through(a, dtype), master)


def _seq_loss(params, tokens, labels, share, config: dict, tokens_in_batch,
              **how):
    """One sequence's part of the batch's L, so that the parts of all the
    sequences add up to it, with `share` [L, E] = the batch's F given:
    summed NLL / the batch's positions + alpha * mean over layers of
    E * sum_e F_e (this sequence's summed p_e / the batch's tokens)."""
    (total, count), drawn, p_sum = parts(params, tokens, labels, config,
                                         **how)
    balance = balance_of(share, p_sum / tokens_in_batch)
    lm = total / tokens_in_batch
    return lm + config["router_aux_loss_coef"] * balance, (lm, balance,
                                                            drawn)


def batch_loss(grad_fn, params, seqs, config: dict):
    """(L, L_lm, balance, pairs drawn [L, E], gradients of L) on the
    sequences (tokens [n, s], labels [n, s]): a first pass counts F over
    the batch, a second differentiates with it."""
    tokens, labels = seqs
    in_batch = tokens.size
    layers, experts = (len(config["layer_types"]),
                       config["published"]["num_experts"])
    zero = jnp.zeros((layers, experts), jnp.float32)
    drawn = sum(grad_fn(params, t, l, zero, in_batch)[0][1][2]
                for t, l in zip(tokens, labels))
    share = drawn.astype(jnp.float32) / in_batch
    lm = balance = 0.0
    grads = None
    for t, l in zip(tokens, labels):
        (_, (lm_s, balance_s, _)), g = grad_fn(params, t, l, share, in_batch)
        lm, balance = lm + float(lm_s), balance + float(balance_s)
        grads = g if grads is None else _add(grads, g)
    return (lm + config["router_aux_loss_coef"] * balance, lm, balance,
            drawn, grads)


def _grad_fn(config: dict, dtype, **how):
    def loss(params, tokens, labels, share, in_batch):
        params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params) \
            if dtype != jnp.float32 else params
        return _seq_loss(params, tokens, labels, share, config, in_batch,
                         **how)

    return jax.jit(jax.value_and_grad(loss, has_aux=True),
                   static_argnums=(4,))


def check_step(master, seqs, config: dict, precision="highest",
               dtype=jnp.float32, **how):
    """One training step from the float32 `master` weights on the check
    sequences, as models/trainer.py makes it: every gradient of L at the
    weights the forward sees (`as_the_forward_sees`), the first AdamW
    update of the masters themselves, then L again at the new masters'
    cast. `loss0`, `lm0`, `balance0` and the same three after the step,
    `pairs0` [L, E]. `precision` is the products' ("highest" is the
    reference), `dtype` what everything is computed in (float32 is the
    reference; bfloat16 throughout, the router and the softmaxes too, is
    the reading a tolerance must refuse) and `how` (`window=None`,
    `group_of="modulo"`) the two wrong models a tolerance must refuse."""
    with jax.default_matmul_precision(precision):
        grad_fn = _grad_fn(config, jnp.dtype(dtype), **how)
        see = jax.jit(functools.partial(as_the_forward_sees, config=config),
                      donate_argnums=(0,))
        # the masters wait on the host while the gradients take their room
        host = jax.device_get(master)
        loss0, lm0, balance0, drawn, grads = batch_loss(
            grad_fn, see(master), seqs, config)
        master = jax.jit(lambda p, g: common.adamw_first_update(
            p, g, decayed(p), config["optimizer"], jnp.float32),
            donate_argnums=(0,))(jax.device_put(host), grads)
        del grads, host
        loss1, lm1, balance1, _, _ = batch_loss(grad_fn, see(master), seqs,
                                                config)
    return {"loss0": loss0, "lm0": lm0, "balance0": balance0,
            "loss1": loss1, "lm1": lm1, "balance1": balance1,
            "pairs0": jax.device_get(drawn)}


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)
