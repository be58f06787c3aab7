"""A decoder with multi-head latent attention and sparse experts on ONE
residual stream, a multi-token-prediction module and a router bias moved by
load, written from the equations as ISSUE 30 states them (DeepSeek-V2
section 2.1 and DeepSeek-V3 sections 2.1 and 2.2; Wang et al. 2024,
arXiv:2408.15664, for the bias):

    layer    x <- x + Attn(RMSNorm(x)); x <- x + FFN(RMSNorm(x))
    MLA      c_q = RMSNorm(x W_qa); q = c_q W_qb, per head (nope | rope)
             [c_kv | k_r] = x W_kva; c_kv = RMSNorm(c_kv)
             [k_nope | v] = c_kv W_kvb per head; RoPE (theta, no scaling) on
             q_rope and on k_r, which every head shares; k = (k_nope | k_r)
             causal softmax(q k^T / sqrt(nope + rope)) v, then W_o
    sparse   s = sigmoid(x W_r); the k largest of s + b; g = scaling *
             s[chosen] / sum s[chosen]; FFN = Shared(x) + sum over the
             chosen AND HELD experts of g_e E_e(x), each a SwiGLU
    bias     after a step, c_e = pairs of the step's batch that chose e,
             over all the experts: b_e <- b_e + gamma sign(mean(c) - c_e)
    module   h' = [RMSNorm_h(h) ; RMSNorm_e(Emb(t_{i+1}))] W_eh with h the
             last layer's output before the final norm; h'' = one more
             sparse layer (its own attention, router, bias and experts);
             logits = Head(RMSNorm_m(h'')), the same embedding and head;
             target t_{i+2}. L = L_main + lambda L_mtp, mean cross-entropies

Given a chip's share it computes that share, as `reference/mla_moe.py`
does and with its pieces (RMSNorm, RoPE, SwiGLU, the router): of the routed
experts only those held, chosen by a router over all of them. One loop over
the held experts with a mask (a `lax.scan`, so that the compiler sees one
expert's body and not eight): no sort, no grouped product, no kernel. Each
layer runs under `jax.checkpoint` so that a 2,048-token sequence in float32
fits a chip beside the parameters and their gradients; that repeats
arithmetic in the backward pass and changes none.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import common
from benchmarks.reference.mla_moe import rms_norm, rope, route, swiglu


def attention(x, p, config: dict):
    """MLA on one sequence x [s, h], already normed."""
    s = x.shape[0]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    rank, eps = config["kv_lora_rank"], config["rms_norm_eps"]
    assert config["rope_scaling"] is None
    inv_freq = config["rope_theta"] ** (
        -2.0 * jnp.arange(dr // 2, dtype=jnp.float32) / dr)
    c_q = rms_norm(x @ p["q_a_w"], p["q_a_ln"], eps)
    q = (c_q @ p["q_b_w"]).reshape(s, -1, dn + dr)           # [s, H, 256]
    heads = q.shape[1]
    kv_a = x @ p["kv_a_w"]
    c_kv = rms_norm(kv_a[:, :rank], p["kv_a_ln"], eps)
    k_r = rope(kv_a[:, rank:], inv_freq)                      # [s, 64]
    kv = (c_kv @ p["kv_b_w"]).reshape(s, heads, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], inv_freq)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_r[:, None, :], (s, heads, dr))], -1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(dn + dr, x.dtype))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    scores = scores - scores.max(-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / probs.sum(-1, keepdims=True)
    out = jnp.einsum("hqk,khd->qhd", probs, kv[..., dn:])
    return out.reshape(s, heads * dv) @ p["o_w"]


def sparse_ffn(x, p, config: dict):
    """(shared expert + the part the held routed experts give, the pairs
    each of ALL the experts drew [E])."""
    chosen, w = route(x, p["router_w"], p["router_b"], config)
    out = swiglu(x, p["shared_gate_w"], p["shared_up_w"], p["shared_down_w"])
    first, held = (config["deployment"]["experts_first"],
                   config["n_routed_experts"])

    def add(out, expert):                                   # a held one
        i, e = expert
        mine = (w * (chosen == first + i)).sum(-1, keepdims=True)   # [s, 1]
        return out + mine * swiglu(x, e["gate_w"], e["up_w"],
                                   e["down_w"]), None

    out, _ = jax.lax.scan(add, out, (jnp.arange(held), jax.tree_util.tree_map(
        lambda a: a[:held], p["experts"])))
    drawn = (chosen[..., None] == jnp.arange(p["router_w"].shape[-1])).sum(
        (0, 1))
    return out, drawn


def layer(sparse: bool, config: dict):
    """One pre-norm layer, (x [s, h], its parameters) -> (x, pairs drawn
    [E] or None), checkpointed."""
    eps = config["rms_norm_eps"]

    @jax.checkpoint
    def run(x, p):
        x = x + attention(rms_norm(x, p["ln1_g"], eps), p, config)
        y = rms_norm(x, p["ln2_g"], eps)
        if not sparse:
            return x + swiglu(y, p["gate_w"], p["up_w"], p["down_w"]), None
        y, drawn = sparse_ffn(y, p, config)
        return x + y, drawn

    return run


def parts(params, tokens, labels, config: dict):
    """tokens, labels [s] of one sequence -> ((summed NLL, count) of the
    main head, the same of the module's, pairs drawn [L_sparse + 1, E])."""
    eps = config["rms_norm_eps"]
    x = params["wte"][tokens]
    # the loops over the layers, whose parameters are stacked on axis 0
    x, _ = jax.lax.scan(layer(False, config), x, params["dense"])
    x, drawn = jax.lax.scan(layer(True, config), x, params["sparse"])
    head = params["lm_head"].T
    main = common.nll_sum(rms_norm(x, params["lnf_g"], eps) @ head, labels)

    mtp = params["mtp"]
    both = jnp.concatenate(
        [rms_norm(x, mtp["hnorm_g"], eps),
         rms_norm(params["wte"][labels], mtp["enorm_g"], eps)], -1)
    y, mtp_drawn = jax.lax.scan(layer(True, config), both @ mtp["eh_w"],
                                mtp["layer"])
    after_next = jnp.concatenate([labels[1:], jnp.full((1,), -1,
                                                       labels.dtype)])
    module = common.nll_sum(rms_norm(y, mtp["lnf_g"], eps) @ head,
                            after_next)
    return main, module, jnp.concatenate([drawn, mtp_drawn])


def nll(params, tokens, labels, config: dict):
    """(a sum, a count) whose quotient over any number of sequences OF ONE
    LENGTH is L_main + lambda L_mtp, the form `check.reference_losses` and
    `aot_check.py` take: the module's sum is scaled by lambda and by the
    ratio of the two counts (s over s - 1, the same in every sequence)."""
    (main, count), (module, fewer), _ = parts(params, tokens, labels, config)
    return main + config["mtp_loss_weight"] * module * count / fewer, count


def decayed(params):
    """Weight decay on the matrices (names ending in `_w`) and the
    embeddings; none on norm gains and the router's bias (models/mla_moe.py
    agrees)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key in ("wte", "lm_head")
        or path[-1].key.endswith("_w"), params)


def round_through(x, dtype):
    """float32 `x` rounded to `dtype` (to nearest, ties to even) and back.
    For bfloat16 it is done on the bits: a float32 -> bfloat16 -> float32
    round trip inside one program is a pair of converts that XLA's TPU
    pipeline removes (`xla_allow_excess_precision`), which would leave the
    reference's weights unrounded on the chip and rounded on the CPU; and
    the first AdamW update is about two bfloat16 units in the last place
    of a typical weight, so the rounding is a large part of the update."""
    if dtype == jnp.float32:
        return x
    assert dtype == jnp.bfloat16, dtype
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def as_the_forward_sees(master, config: dict):
    """The float32 master weights rounded to what the forward pass is given
    (models/trainer.py keeps float32 masters and hands the model their
    cast): the configuration's dtype, and float32 as they are for the
    router's matrix and bias. On the chip the masters a seed gives are NOT
    bfloat16 values to begin with (the initialiser's own float32 ->
    bfloat16 -> float32 round trip is dropped there too), so this rounding
    is no identity at step 0 either."""
    dtype = jnp.dtype(config["dtype"])
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key.startswith("router_")
        else round_through(a, dtype), master)


def router_biases(params):
    """[L_sparse + 1, E]: the trunk's layers, then the module's."""
    return jnp.concatenate([params["sparse"]["router_b"],
                            params["mtp"]["layer"]["router_b"]])


def move_router_biases(params, drawn, config: dict):
    """b_e + gamma sign(mean(c) - c_e) for every router, from the pairs
    `drawn` [L_sparse + 1, E] of the step's whole batch."""
    drawn = drawn.astype(jnp.float32)
    moved = router_biases(params) + config["router_bias_update_rate"] \
        * jnp.sign(drawn.mean(-1, keepdims=True) - drawn)
    cut = params["sparse"]["router_b"].shape[0]
    sparse = dict(params["sparse"], router_b=moved[:cut])
    module = dict(params["mtp"], layer=dict(params["mtp"]["layer"],
                                            router_b=moved[cut:]))
    return dict(params, sparse=sparse, mtp=module)


def _total(params, tokens, labels, config: dict):
    """One sequence's `nll` sum with what it is made of: (main, count,
    module's, its count, pairs drawn)."""
    (main, count), (module, fewer), drawn = parts(params, tokens, labels,
                                                  config)
    return main + config["mtp_loss_weight"] * module * count / fewer, (
        main, count, module, fewer, drawn)


def _means(found):
    """(L_main, L_mtp) over the sequences' `_total` records."""
    main, count, module, fewer = (
        sum(float(f[i]) for f in found) for i in range(4))
    return main / count, module / fewer


def _grad_fn(config: dict):
    return jax.jit(jax.value_and_grad(
        functools.partial(_total, config=config), has_aux=True))


def train_step(master, seqs, config: dict, grad_fn=None):
    """One training step from the float32 `master` weights on the sequences
    (tokens [n, s], labels [n, s]), one at a time, as models/trainer.py
    makes it: every gradient of L_main + lambda L_mtp at the weights the
    forward sees (`as_the_forward_sees`), the first AdamW update of the
    masters themselves and the biases' move. Returns (the masters after
    it, the gradients of the mean, L_main, L_mtp, the pairs every expert
    of every router drew [L_sparse + 1, E]); `master` is donated."""
    grad_fn = grad_fn or _grad_fn(config)
    # the masters wait on the host while the gradients take their room: at
    # the published widths masters, cast, two gradients and a sequence's
    # activations are 14 GB of a chip's 16
    host = jax.device_get(master)
    params = jax.jit(functools.partial(as_the_forward_sees, config=config),
                     donate_argnums=(0,))(master)
    found, grads = [], None
    for t, l in zip(*seqs):
        (_, aux), g = grad_fn(params, t, l)
        found.append(aux)
        grads = g if grads is None else _add(grads, g)
    del params
    master = jax.device_put(host)
    count = sum(int(f[1]) for f in found)
    grads = jax.jit(lambda g: jax.tree_util.tree_map(
        lambda x: x / count, g))(grads)
    update = jax.jit(lambda p, g, drawn: move_router_biases(
        common.adamw_first_update(p, g, decayed(p), config["optimizer"],
                                  jnp.float32), drawn, config),
        donate_argnums=(0,))
    drawn = sum(f[4] for f in found)
    return update(master, grads, drawn), grads, *_means(found), drawn


def check_step(master, seqs, config: dict, precision="highest"):
    """`train_step` on the check sequences from the float32 masters the
    seed gives, then the loss on them again at the new masters' cast:
    `loss0` = L_main + lambda L_mtp with `main0` and `mtp0` apart, the same
    three after the step, `pairs0` (the pairs each expert of each router drew
    at step 0) and `biases1`, both [L_sparse + 1, E], and the share of the
    biases that moved. `precision` is the products': "highest" is the reference,
    lower ones are for the readings a tolerance is set between."""
    lam = config["mtp_loss_weight"]
    with jax.default_matmul_precision(precision):
        # one compiled function for both losses: at the published widths
        # compiling a forward of its own costs more than three runs of it
        grad_fn = _grad_fn(config)
        before = router_biases(master)
        master, _, main0, mtp0, drawn = train_step(master, seqs, config,
                                                   grad_fn)
        after = router_biases(master)
        params = jax.jit(functools.partial(
            as_the_forward_sees, config=config), donate_argnums=(0,))(master)
        main1, mtp1 = _means([grad_fn(params, t, l)[0][1]
                              for t, l in zip(*seqs)])
    return {"loss0": main0 + lam * mtp0, "main0": main0, "mtp0": mtp0,
            "loss1": main1 + lam * mtp1, "main1": main1, "mtp1": mtp1,
            "biases1": jax.device_get(after),
            "pairs0": jax.device_get(drawn),
            "bias_moved_share": float((after != before).mean())}


@jax.jit
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)
