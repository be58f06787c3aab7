"""A pre-norm decoder of gated grouped-query attention in window and full
layers, a leading dense layer and then routed layers with a shared expert,
written from the equations as ISSUE 39 states them (Qiu et al. 2025 for the
output gate, Su et al. 2021 and Peng et al. 2023 for the rotary embedding
and yarn, Ainslie et al. 2023 for grouped queries, DeepSeek-V3 for the
router and its bias). No bias on any projection; y = RMSNorm(x), eps 1e-6.

    layer n  kind t = layer_types[n], H_t = num_attention_heads_per_layer[n]
             query heads on H_kv K/V heads of d
             x <- x + (Attn_t(y) * sigmoid(y W_g)) W_o;  x <- x + FFN_n(y')
    Attn_t   q = y W_q [H_t d], k = y W_k, v = y W_v [H_kv d]; q and k
             rotated by position on their first r = d * partial_rotary_factor
             components, halves paired within them (i with i + r/2), by the
             kind's table over r: window layers theta 10000 plain, full
             layers yarn's frequencies with cos and sin times
             `attention_factor`; components r.. pass through. Query head h
             reads K/V head h // (H_t / H_kv); a_ij = q_i . k_j / sqrt(d);
             key j visible to query i iff j <= i and (full, or i - j <
             sliding_window); float32 softmax. The gate is elementwise over
             the H_t d components
    FFN_n    dense (`mlp_layer_types` "dense"): SwiGLU of `intermediate_size`.
             Sparse: s = sigmoid(y' W_r) over ALL E experts; the k largest
             of s + b chosen; w = s_chosen / sum s_chosen *
             moe_routed_scaling_factor; FFN = Shared(y') + sum over the
             chosen AND HELD experts of w_e E_e(y'), each a SwiGLU
    bias     after a step, c_e = pairs of the step's batch that chose e,
             over all the experts: b_e <- b_e + gamma sign(mean(c) - c_e)
    loss     final RMSNorm, untied head, mean cross-entropy, float32

Given a chip's share it computes that share (`reference/mla_moe.py`): of the
routed experts only those held, chosen by a router over all
`published.num_experts`, each a dense product under a mask (a `lax.scan`:
no sort, no grouped product, no kernel); logits and loss over the held rows
of the vocabulary. Queries go in blocks of `QUERY_BLOCK` so that a
4,096-token sequence's float32 scores fit, and each layer runs under
`jax.checkpoint`: both repeat arithmetic and change none. It is handed the
program's parameters and so shares their layout, which it reads from the
tree itself (`layers_in_order`): "dense", "sparse" and "tail" are lists
with one tree a position of a period, each stacked over the periods.

The keywords of `attention` are what the tolerances' readings vary: the
reference proper has the gate, rotates r components and computes both in
float32; `gate=False`, `whole_rotary=True` are the two wrong models a limit
must refuse, `gate_dtype` and `rotary_dtype` bfloat16 the gate and the
rotary computed in a lower precision than the configuration states, and
`check_step`'s `dtype` bfloat16 the whole of it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import common, kda_mla_moe
from benchmarks.reference.window_gqa_moe import inv_freq, rms_norm, swiglu

QUERY_BLOCK = 512
WINDOW = "sliding_attention"
# what the program keeps float32 and the reference with it; the masters'
# rounding, the weights' decay and the biases' move are the Kimi cell's
# reference's, on the same tree layout
as_the_forward_sees = kda_mla_moe.as_the_forward_sees
decayed = kda_mla_moe.decayed
router_biases = kda_mla_moe.router_biases
move_router_biases = kda_mla_moe.move_router_biases


def rotary(x, p: dict, whole: bool = False, dtype=jnp.float32):
    """x [s, n, d] rotated by position on its first d * p's
    `partial_rotary_factor` components (all of them where `whole`), the
    angles, cos, sin and products in `dtype`; the rest as it is."""
    s, d = x.shape[0], x.shape[-1]
    rot = d if whole else round(d * p.get("partial_rotary_factor", 1))
    freqs, factor = inv_freq(rot, p)
    angle = jnp.arange(s, dtype=dtype)[:, None] * freqs.astype(dtype)[None]
    cos = (jnp.cos(angle) * factor)[:, None, :]
    sin = (jnp.sin(angle) * factor)[:, None, :]
    a, b = x[..., :rot // 2].astype(dtype), x[..., rot // 2:rot].astype(dtype)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:].astype(dtype)], -1).astype(x.dtype)


def attention(y, p, n: int, config: dict, gate=True, whole_rotary=False,
              gate_dtype=jnp.float32, rotary_dtype=jnp.float32):
    """Layer n's attention on one sequence y [s, h], already normed, gated
    -> [s, H_t d] before W_o."""
    s = y.shape[0]
    kind = config["layer_types"][n]
    heads, kv_heads, d = (config["num_attention_heads_per_layer"][n],
                          config["num_key_value_heads"], config["head_dim"])
    table = config["rope_parameters"][kind]

    def rotated(x, count):
        return rotary(x.reshape(s, count, d), table, whole_rotary,
                      rotary_dtype)

    q = rotated(y @ p["q_w"], heads)
    k = rotated(y @ p["k_w"], kv_heads)
    v = (y @ p["v_w"]).reshape(s, kv_heads, d)
    of = jnp.arange(heads) // (heads // kv_heads)   # each head's K/V head
    k, v = k[:, of], v[:, of]                                 # [s, H, d]
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

    out = jax.lax.map(block, jnp.arange(0, s, size)).reshape(s, heads * d)
    if not gate:
        return out
    g = (y.astype(gate_dtype) @ p["attn_gate_w"].astype(gate_dtype))
    return (out.astype(gate_dtype) / (1.0 + jnp.exp(-g))).astype(out.dtype)


def route(y, router_w, router_b, config: dict):
    """(chosen [s, k], weights [s, k]) over all the routed experts, in
    router_w's dtype (float32 in the reference proper)."""
    score = 1.0 / (1.0 + jnp.exp(-(y.astype(router_w.dtype) @ router_w)))
    _, chosen = jax.lax.top_k(score + router_b,
                              config["num_experts_per_tok"])
    w = jnp.take_along_axis(score, chosen, -1)
    return chosen, w / w.sum(-1, keepdims=True) \
        * config["moe_routed_scaling_factor"]


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


def _places(params, config: dict) -> list:
    """(group, position, period) of each layer, in layer order, from the
    tree's own layout: a group is a list of positions, each stacked over
    the periods."""
    found = []
    for group in ("dense", "sparse", "tail"):
        positions = params.get(group, [])
        periods = positions[0]["ln1_g"].shape[0] if positions else 0
        found += [(group, position, period) for period in range(periods)
                  for position in range(len(positions))]
    assert len(found) == config["num_hidden_layers"]
    return found


def layer_params(params, n: int, config: dict):
    """Layer n's parameters."""
    group, position, period = _places(params, config)[n]
    return jax.tree_util.tree_map(lambda a: a[period],
                                  params[group][position])


def layers_in_order(params, config: dict) -> list:
    """Each layer's parameters, in layer order."""
    return [layer_params(params, n, config)
            for n in range(config["num_hidden_layers"])]


def layer(n: int, config: dict, **how):
    """Layer n, checkpointed: (x [s, h], its parameters) -> (x, pairs drawn
    [E] or None)."""
    eps = config["rms_norm_eps"]
    sparse = config["mlp_layer_types"][n] == "sparse"

    @jax.checkpoint
    def run(x, p):
        y = rms_norm(x, p["ln1_g"], eps)
        x = x + attention(y, p, n, config, **how) @ p["o_w"]
        y = rms_norm(x, p["ln2_g"], eps)
        if not sparse:
            return x + swiglu(y, p["gate_w"], p["up_w"], p["down_w"]), None
        y, drawn = sparse_ffn(y, p, config)
        return x + y, drawn

    return run


def parts(params, tokens, labels, config: dict, **how):
    """tokens, labels [s] of one sequence -> ((summed NLL, count), pairs
    drawn [L_sparse, E] in layer order)."""
    x = params["wte"][tokens]
    drawn = []
    for n, p in enumerate(layers_in_order(params, config)):
        x, d = layer(n, config, **how)(x, p)
        drawn += [] if d is None else [d]
    x = rms_norm(x, params["lnf_g"], config["rms_norm_eps"])
    return common.nll_sum(x @ params["lm_head"].T, labels), \
        jnp.stack(drawn)


def nll(params, tokens, labels, config: dict):
    """(summed NLL, count): the form `aot_check.py` takes."""
    return parts(params, tokens, labels, config)[0]


def _total(params, tokens, labels, config: dict, how: dict):
    (total, count), drawn = parts(params, tokens, labels, config, **how)
    return total, (total, count, drawn)


def _grad_fn(config: dict, **how):
    return jax.jit(jax.value_and_grad(functools.partial(
        _total, config=config, how=how), has_aux=True))


def check_step(master, seqs, config: dict, precision="highest",
               dtype=jnp.float32, **how):
    """One training step from the float32 `master` weights the seed gives
    on the check sequences (tokens [n, s], labels [n, s]), as
    models/trainer.py makes it (`kda_mla_moe.train_step`: the gradient of
    the mean cross-entropy at the weights the forward sees, the first AdamW
    update of the masters and the biases' move), then the loss on them again
    at the new masters' cast: `loss0`, `loss1`, `pairs0` (the pairs each
    expert of each router drew at step 0) and `biases1`, both [L_sparse,
    E], and the share of the biases that moved. "highest" products and
    float32 everywhere are the reference; `dtype` bfloat16 (nothing
    float32, the update of the bfloat16 weights themselves) and `how`
    (`attention`'s keywords) are the readings a tolerance is set between.
    `master` is donated."""
    with jax.default_matmul_precision(precision):
        grad_fn = _grad_fn(config, **how)
        before = router_biases(master)
        master, loss0, drawn = kda_mla_moe.train_step(master, seqs, config,
                                                      grad_fn, dtype)
        after = router_biases(master)
        params = jax.jit(functools.partial(
            as_the_forward_sees, config=config, dtype=dtype),
            donate_argnums=(0,))(master)
        found = [grad_fn(params, t, l)[0][1] for t, l in zip(*seqs)]
    return {"loss0": loss0,
            "loss1": sum(float(f[0]) for f in found)
            / sum(int(f[1]) for f in found),
            "biases1": jax.device_get(after),
            "pairs0": jax.device_get(drawn),
            "bias_moved_share": float((after != before).mean())}


def check_attention(master, tokens, cotangents: dict, config: dict,
                    dtype=jnp.float32, **how) -> dict:
    """The attention sub-layers of the layers `cotangents` names alone
    ({n: [rows, s, H_n d]}), from the float32 masters at the forward's
    cast, each handed the embedding's rows x = wte[tokens] of the
    sequences tokens [rows, s], one at a time: {n: (o [rows, s, H_n d],
    the gated output before W_o; the gradient of sum(o * cotangent) to x,
    [rows, s, h])}. For `llama.layer_attention`, forward and backward, at
    the timed widths. "highest" products and float32 are the reference;
    `dtype` bfloat16 (with `how`'s dtypes bfloat16 too: the whole of it)
    and `how` (`attention`'s keywords) the readings a limit lies
    between."""
    eps = config["rms_norm_eps"]
    found = {}
    for n, cotangent in cotangents.items():
        def sublayer(x, p, n=n):
            return attention(rms_norm(x, p["ln1_g"], eps), p, n, config,
                             **how)

        @jax.jit
        def both(wte, p, tokens, cotangent, sublayer=sublayer):
            seen = as_the_forward_sees({"wte": wte, "layer": p}, config,
                                       dtype)
            o, back = jax.vjp(functools.partial(sublayer, p=seen["layer"]),
                              seen["wte"][tokens])
            return o, back(cotangent.astype(o.dtype))[0]

        with jax.default_matmul_precision("highest"):
            p = layer_params(master, n, config)
            outs = [both(master["wte"], p, t, c)
                    for t, c in zip(tokens, cotangent)]
        found[n] = tuple(jnp.stack(x) for x in zip(*outs))
    return found
