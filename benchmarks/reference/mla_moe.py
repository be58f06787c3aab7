"""A decoder with multi-head latent attention, sparse experts and several
residual streams, written from the equations (DeepSeek-V2 section 2.1 and
DeepSeek-V3 section 2.1 for the attention and the router; Peng et al. 2023
for yarn; arXiv:2512.24880 for the streams), as ISSUE 26 states them:

    MLA      c_q = RMSNorm(x W_qa); q = c_q W_qb, per head (nope | rope)
             [c_kv | k_r] = x W_kva; c_kv = RMSNorm(c_kv)
             [k_nope | v] = c_kv W_kvb per head; RoPE on q_rope and on k_r,
             which every head shares; k = (k_nope | k_r)
             causal softmax(q k^T scale) v, then W_o
    router   s = sigmoid(x W_r); the k largest of s + b; w = s[chosen],
             w / (sum w + 1e-20) * routed_scaling_factor
    experts  W_down(silu(W_gate x) * W_up x); y = sum_k w_k E_k(x) + shared
    streams  X [s, n, h]; u = RMSNorm(vec X); H_pre = sigmoid(a_pre u Phi_pre
             + b_pre), H_post = 2 sigmoid(a_post u Phi_post + b_post),
             H_res = Sinkhorn(exp(clip(a_res mat(u Phi_res) + B_res)));
             h_in = sum_i H_pre[i] X[i]; y = F(RMSNorm(h_in));
             X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

Given a chip's share it computes that share: the heads whose columns the
parameters hold, and of the routed experts only those the configuration
says are held (`deployment.experts_first`, `n_routed_experts`), chosen by
a router over all `published.n_routed_experts`; what the absent ones would
add is left out. One loop over the held experts with a mask: no sort, no
grouped product, no kernel. Departures and assumptions are the
configuration file's (`changed`, `assumed`). One departure from the other
references: each layer runs under `jax.checkpoint`, so that the float32
activations of a 2,048-token sequence (four streams of them, and eight
experts' worth) fit a chip beside the float32 parameters and gradients. It
repeats arithmetic in the backward pass and changes none.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmarks.reference import common


def rms_norm(x, gain, eps):
    """Zhang & Sennrich 2019: x / sqrt(mean(x^2) + eps) * gain."""
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * gain


def yarn_inv_freq(config: dict):
    """[dim/2] inverse frequencies of the rotary part under yarn."""
    dim, base = config["qk_rope_head_dim"], config["rope_theta"]
    y = config["rope_scaling"]
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    f_extra = base ** (-2.0 * i / dim)
    f_inter = f_extra / y["factor"]

    def corr(rotations):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (2 * math.pi * rotations)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return f_inter * ramp + f_extra * (1.0 - ramp)


def softmax_scale(config: dict) -> float:
    """(nope + rope)^-1/2 * m(factor, mscale_all_dim)^2 with
    m(s, a) = 0.1 a ln s + 1. cos and sin are scaled by
    m(factor, mscale) / m(factor, mscale_all_dim), which is 1 here."""
    y = config["rope_scaling"]
    assert y["mscale"] == y["mscale_all_dim"]
    m = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
    d = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return d ** -0.5 * m * m


def rope(x, inv_freq):
    """x [s, ..., dim] rotated by position; the components are paired as
    halves (i with i + dim/2): a relabelling of random weights, the
    program's (models/llama.py)."""
    s, half = x.shape[0], x.shape[-1] // 2
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = angle.reshape((s,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(x, p, config: dict):
    """MLA on one sequence x [s, h], already normed: the held heads' part
    of the output."""
    s = x.shape[0]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    rank, eps = config["kv_lora_rank"], config["rms_norm_eps"]
    inv_freq = yarn_inv_freq(config)
    c_q = rms_norm(x @ p["q_a_w"], p["q_a_ln"], eps)
    q = (c_q @ p["q_b_w"]).reshape(s, -1, dn + dr)           # [s, H, 192]
    kv_a = x @ p["kv_a_w"]
    c_kv = rms_norm(kv_a[:, :rank], p["kv_a_ln"], eps)
    k_r = rope(kv_a[:, rank:], inv_freq)                      # [s, 64]
    kv = (c_kv @ p["kv_b_w"]).reshape(s, -1, dn + dv)
    heads = q.shape[1]
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], inv_freq)], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_r[:, None, :], (s, heads, dr))], -1)
    v = kv[..., dn:]
    scores = jnp.einsum("qhd,khd->hqk", q, k) * softmax_scale(config)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    scores = scores - scores.max(-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / probs.sum(-1, keepdims=True)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, heads * dv)
    return out @ p["o_w"]


def swiglu(x, gate_w, up_w, down_w):
    """Shazeer 2020: down(silu(gate x) * up x)."""
    g = x @ gate_w
    return (g / (1.0 + jnp.exp(-g)) * (x @ up_w)) @ down_w


def route(x, router_w, router_b, config: dict):
    """(chosen [s, k], weights [s, k]) over all the routed experts."""
    score = 1.0 / (1.0 + jnp.exp(-(x @ router_w)))
    _, chosen = jax.lax.top_k(score + router_b,
                              config["num_experts_per_tok"])
    w = jnp.take_along_axis(score, chosen, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, w * config["routed_scaling_factor"]


def sparse_ffn(x, p, config: dict):
    """Shared expert + the part the held routed experts give."""
    chosen, w = route(x, p["router_w"], p["router_b"], config)
    out = swiglu(x, p["shared_gate_w"], p["shared_up_w"], p["shared_down_w"])
    first = config["deployment"]["experts_first"]
    e = p["experts"]
    for i in range(config["n_routed_experts"]):             # the held ones
        mine = (w * (chosen == first + i)).sum(-1, keepdims=True)   # [s, 1]
        out = out + mine * swiglu(x, e["gate_w"][i], e["up_w"][i],
                                  e["down_w"][i])
    return out


def sinkhorn(m, iters: int, eps: float):
    """m [s, n, n] positive: `iters` times, each row divided by its sum +
    eps, then each column."""
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def sublayer(X, hc, fn, config: dict):
    """One sub-layer on the streams X [s, n, h]."""
    s, n, h = X.shape
    eps = config["hc_eps"]
    u = rms_norm(X.reshape(s, n * h), hc["norm_g"], eps)
    proj = u @ hc["phi"]                                  # [s, n + n + n*n]
    a_pre, a_post, a_res = hc["alpha"]
    h_pre = jax.nn.sigmoid(a_pre * proj[:, :n] + hc["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(a_post * proj[:, n:2 * n] + hc["b_post"])
    r = a_res * proj[:, 2 * n:].reshape(s, n, n) + hc["b_res"]
    r = jnp.clip(r, config["mhc_h_res_clamp_min"],
                 config["mhc_h_res_clamp_max"])
    h_res = sinkhorn(jnp.exp(r), config["hc_sinkhorn_iters"], eps)
    h_in = (h_pre[:, :, None] * X).sum(1)                 # [s, h]
    y = fn(h_in)
    return jnp.einsum("sij,sjh->sih", h_res, X) + h_post[:, :, None] \
        * y[:, None, :]


def nll(params, tokens, labels, config: dict):
    """tokens, labels [s] -> (summed NLL over labelled positions, count)."""
    eps, n = config["rms_norm_eps"], config["hc_mult"]
    x = params["wte"][tokens]
    X = jnp.broadcast_to(x[:, None, :], (x.shape[0], n, x.shape[1]))

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def block(X, p, ffn):
        X = sublayer(X, p["hc_attn"], lambda y: attention(
            rms_norm(y, p["ln1_g"], eps), p, config), config)
        X = sublayer(X, p["hc_ffn"], lambda y: ffn(
            rms_norm(y, p["ln2_g"], eps), p), config)
        return X, None

    # the loops over the layers, whose parameters are stacked on axis 0
    X, _ = jax.lax.scan(
        lambda X, p: block(X, p, lambda y, p: swiglu(
            y, p["gate_w"], p["up_w"], p["down_w"])), X, params["dense"])
    X, _ = jax.lax.scan(
        lambda X, p: block(X, p, lambda y, p: sparse_ffn(y, p, config)),
        X, params["sparse"])
    x = rms_norm(X.sum(1), params["lnf_g"], eps)
    return common.nll_sum(x @ params["lm_head"].T, labels)


def decayed(params):
    """Weight decay on the matrices (names ending in `_w`, and `phi`) and
    the embeddings; none on norm gains, the router's bias, and the stream
    mixing's scalars and biases (models/mla_moe.py agrees)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key in ("wte", "lm_head", "phi")
        or path[-1].key.endswith("_w"), params)
