"""Latent-attention sparse-expert family — functional TPU-compiled path.

Named for its mechanisms, not for a model (DeepSeek-V2/V3-style blocks with
manifold-constrained hyper-connections, arXiv:2512.24880):

- multi-head latent attention (MLA): queries and keys/values go through
  low-rank latents with their own RMSNorm; a head's query/key is a no-position
  part beside a rotary part whose key is one vector a token shared by every
  head; the value is narrower than the query (the flash kernels' two widths).
  No projection is absorbed into another: that is serving's trick.
- yarn-scaled RoPE (`blocks.yarn_inv_freq`, `attention_scale`).
- leading dense SwiGLU layers, then sparse layers: a sigmoid top-k router
  with a selection bias over ALL routed experts, the routed experts this
  chip HOLDS (`experts_held`, dropless, ops/moe.py) and a shared expert.
- `hc_mult` residual streams: every sub-layer reads a learned mixture of the
  streams and writes back through a per-token matrix that Sinkhorn
  iterations make doubly stochastic (ops/pallas/stream_mix.py: four
  kernels, each one pass over the streams).

- layers of two kinds in one trunk (`layer_types`): beside the latent
  softmax layer (FULL) a linear-attention layer (LINEAR, Kimi Delta
  Attention, arXiv:2510.26692): q, k and v through a short causal depthwise
  convolution and SiLU, q and k normalised a head, a log-decay per head AND
  key channel from a low-rank projection, a write strength a head, the gated
  delta rule in its chunked form (ops/linear_attention.py) and a gated
  RMSNorm a head before the output projection; the layer is
  `models/delta_layer.py`'s, which the decoder family (llama.py) shares, in
  its KDA form (`linear_form`). The feed-forward half of a
  layer is the same under both. The trunk is then the leading dense layers,
  `blocks.scan_periods` over the whole periods of the sparse layers'
  pattern and a tail of what is left, each position of a period on a
  parameter tree of its own.

`q_lora_rank` None is a query projection without a latent; `mla_use_nope`
takes the rotary table away (the key columns every head shares stay,
unrotated), as a model does whose linear layers carry the positions.

Three mechanisms are optional, and a configuration that leaves one out
compiles none of it:

- the streams: `hc_mult` None is the plain pre-norm residual, x + F(norm x)
  (the absence of streams, not one stream through a 1 x 1 Sinkhorn);
- the load-driven selection bias (`router_bias_update_rate`, Wang et al.
  2024, arXiv:2408.15664; DeepSeek-V3 section 2.1.2): after each step an
  expert that drew more than the mean of the step's pairs has its bias
  lowered by the rate, one that drew fewer has it raised. No gradient, no
  AdamW: `trainer.build_adamw_train_step`'s `state_update`
  (`routed.move_router_biases`). 0 holds the bias where it is;
- a multi-token-prediction module of depth 1 (`mtp_layers` 1; DeepSeek-V3
  section 2.2): one more sparse layer behind the last, on parameters of its
  own, fed the projection of [norm(hidden) ; norm(embedding of the next
  token)], through the SAME embedding and head, with the token after next
  as its target; the step's loss is L_main + `mtp_loss_weight` * L_mtp.

`heads_held` and `experts_held` are a chip's share of a layer that several
chips divide (tensor-parallel heads, expert-parallel experts): the
attention output is then a partial sum over the held heads and the routed
output a partial sum over the held experts, and nothing here stands in for
the absent chips. None means all.

Same compiled-trainer machinery as gpt.py / llama.py: parameters of like
layers stacked on a leading axis and scanned under whole-block
`jax.checkpoint`, bf16 compute with fp32 master weights
(`trainer.build_adamw_train_step`); RoPE, RMSNorm, SwiGLU, the attention
core, the loss head and the layer scan are blocks.py's; the routed
feed-forward, the router's facts, the biases' move and the one-chip step
are routed.py's, which the decoder family (llama.py) shares.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.linear_attention import chunk_gated_delta_rule
from ..ops.pallas import stream_mix
from . import delta_layer, routed, stages
from .blocks import (attention, lm_head_loss, normal, rms_norm, rope,
                     scan_layers, scan_segments, segments, swiglu,
                     yarn_inv_freq)


FULL, LINEAR = "full", "linear"        # the kinds of attention layer


@dataclasses.dataclass
class MlaMoeConfig:
    vocab_size: int = 131072
    hidden_size: int = 3584
    num_layers: int = 40
    first_k_dense: int = 2                    # leading dense layers
    num_heads: int = 32                       # published
    heads_held: Optional[int] = None          # on this chip; None = all
    q_lora_rank: Optional[int] = 768          # None: no query latent
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 9216             # dense layers' MLP
    moe_intermediate_size: int = 1024         # one expert
    n_routed_experts: int = 64                # the router's outputs
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 2.0
    hc_mult: Optional[int] = 4                # residual streams; None =
    #                                           the plain residual
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None       # yarn's keys, as published
    mla_use_nope: bool = False                # no rotary table at all
    layer_types: Optional[Tuple[str, ...]] = None   # FULL | LINEAR a layer;
    #                                           None = every layer FULL
    linear_heads: int = 32                    # a LINEAR layer's heads,
    linear_head_dim: int = 128                # their width (keys, values),
    linear_conv_size: int = 4                 # the short convolution's taps
    initializer_range: float = 0.02
    router_bias_update_rate: float = 0.0      # gamma; 0 = the bias is held
    mtp_layers: int = 0                       # prediction modules: 0 or 1
    mtp_loss_weight: float = 0.3              # lambda
    use_flash_attention: bool = True
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.mtp_layers not in (0, 1):
            raise NotImplementedError(
                "multi-token prediction of depth 1 only: each further "
                "module feeds on the one before it")
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            if len(self.layer_types) != self.num_layers or set(
                    self.layer_types) - {FULL, LINEAR}:
                raise ValueError(
                    f"layer_types names {FULL!r} or {LINEAR!r} for each of "
                    f"the {self.num_layers} layers, got {self.layer_types}")

    @property
    def heads(self) -> int:
        return self.heads_held or self.num_heads

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def sparse_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def segments(self):
        """The trunk in order, as (group of the parameters, kinds of one
        period, sparse, periods). Without `layer_types`: the dense layers
        and the sparse ones, each a stack of like layers. With them: the
        leading dense layers once, the shortest run the sparse layers
        repeat at least twice (or all of them once) as often as it is
        whole, and under "tail" what is left, once (`blocks.segments`)."""
        if self.layer_types is None:
            return [("dense", (FULL,), False, self.first_k_dense),
                    ("sparse", (FULL,), True, self.sparse_layers)]
        return segments(self.layer_types, self.first_k_dense)


# ------------------------------------------------------------------- yarn

def _mscale(factor: float, a: float) -> float:
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def attention_scale(config: MlaMoeConfig) -> float:
    """qk_head_dim^-1/2 times yarn's mscale(factor, mscale_all_dim)^2. (The
    other half of yarn's magnitude correction scales cos and sin by
    mscale(factor, mscale) / mscale(factor, mscale_all_dim), which is 1
    where the two are equal, as in every published config of the family;
    another ratio is refused rather than dropped.)"""
    scale = config.qk_head_dim ** -0.5
    s = config.rope_scaling
    if s:
        if s.get("mscale", 1) != s.get("mscale_all_dim", 1):
            raise NotImplementedError(
                "yarn with mscale != mscale_all_dim scales cos and sin")
        scale *= _mscale(s["factor"], s.get("mscale_all_dim", 1)) ** 2
    return scale


# ----------------------------------------------------------------- params

def _init_hc(key, layers: int, c: MlaMoeConfig):
    """One sub-layer's stream-mixing parameters, float32. phi's columns are
    (pre: n | post: n | res: n*n). Seeded around the hyper-connections
    start: alpha 0.01; H_res near the identity (b_res = 2 I + noise), H_post
    near 1 (b_post = noise), H_pre a seeded mixture (b_pre = noise); the
    noise keeps Sinkhorn and the mixtures doing work from step 0."""
    n, h = c.hc_mult, c.hidden_size
    k = jax.random.split(key, 4)
    f32 = jnp.float32
    return {
        "norm_g": jnp.ones((layers, n * h), f32),
        "phi": jax.random.normal(k[0], (layers, n * h, 2 * n + n * n), f32)
        * c.initializer_range,
        "alpha": jnp.full((layers, 3), 0.01, f32),
        "b_pre": jax.random.normal(k[1], (layers, n), f32),
        "b_post": jax.random.normal(k[2], (layers, n), f32) * 0.5,
        "b_res": 2.0 * jnp.eye(n, dtype=f32)
        + jax.random.normal(k[3], (layers, n, n), f32) * 0.5,
    }


def _init_layers(key, layers: int, c: MlaMoeConfig, sparse: bool,
                 kind: str = FULL):
    h, dt, std = c.hidden_size, jnp.dtype(c.dtype), c.initializer_range
    out_std = std / math.sqrt(2 * c.num_layers)
    heads, dn, dr, dv = (c.heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                         c.v_head_dim)
    ks = iter(jax.random.split(key, 16))

    def norm(shape, scale=std, dtype=dt):
        return normal(next(ks), (layers,) + shape, scale, dtype)

    p = {"ln1_g": jnp.ones((layers, h), dt)}
    if kind == LINEAR:
        p.update(delta_layer.init_params(jax.random.fold_in(key, 1), layers,
                                         linear_form(c), h, std, dt))
        width = c.linear_heads * c.linear_head_dim
    else:
        if c.q_lora_rank is None:
            p["q_w"] = norm((h, heads * (dn + dr)))
        else:
            p.update(q_a_w=norm((h, c.q_lora_rank)),
                     q_a_ln=jnp.ones((layers, c.q_lora_rank), dt),
                     q_b_w=norm((c.q_lora_rank, heads * (dn + dr))))
        p.update(kv_a_w=norm((h, c.kv_lora_rank + dr)),
                 kv_a_ln=jnp.ones((layers, c.kv_lora_rank), dt),
                 kv_b_w=norm((c.kv_lora_rank, heads * (dn + dv))))
        width = heads * dv
    p.update(o_w=norm((width, h), out_std),
             ln2_g=jnp.ones((layers, h), dt))
    if c.hc_mult is not None:
        p.update(hc_attn=_init_hc(next(ks), layers, c),
                 hc_ffn=_init_hc(next(ks), layers, c))
    if not sparse:
        f = c.intermediate_size
        p.update(gate_w=norm((h, f)), up_w=norm((h, f)),
                 down_w=norm((f, h), out_std))
        return p
    f, n = c.moe_intermediate_size, c.held[1]
    fs = f * c.n_shared_experts
    p.update(
        # the router is float32, as the family's checkpoints keep it; its
        # selection bias starts at zero and no gradient reaches it (what
        # moves it, if anything does, is `routed.move_router_biases`)
        router_w=norm((h, c.n_routed_experts), dtype=jnp.float32),
        router_b=jnp.zeros((layers, c.n_routed_experts), jnp.float32),
        shared_gate_w=norm((h, fs)), shared_up_w=norm((h, fs)),
        shared_down_w=norm((fs, h), out_std),
        experts={"gate_w": norm((n, h, f)), "up_w": norm((n, h, f)),
                 "down_w": norm((n, f, h), out_std)})
    return p


def linear_form(c: MlaMoeConfig) -> delta_layer.DeltaForm:
    """A LINEAR layer's form (`models/delta_layer.py`): Kimi Delta
    Attention, a log-decay a head and key channel, a low-rank sigmoid gate
    and a write strength up to 1, keys and values of one width."""
    return delta_layer.DeltaForm(
        heads=c.linear_heads, key_dim=c.linear_head_dim,
        value_dim=c.linear_head_dim, taps=c.linear_conv_size,
        norm_eps=c.rms_norm_eps)


def init_mla_moe_params(config: MlaMoeConfig, seed: int = 0) -> Dict:
    """Parameters as a pytree: the leading dense layers stacked under
    "dense", the sparse layers under "sparse" (the scan layouts), an
    untied embedding and head; with a prediction module, under "mtp" its
    two norms, the projection of [hidden ; embedding], its one sparse
    layer (stacked, a stack of one) and its final norm. With `layer_types`
    a group ("dense", "sparse", "tail": `MlaMoeConfig.segments`) is a LIST
    of trees, one for each position of its period, each stacked over the
    periods."""
    c = config
    h, dt, std = c.hidden_size, jnp.dtype(c.dtype), c.initializer_range
    root = jax.random.PRNGKey(seed)
    k = jax.random.split(root, 4)

    params = {
        "wte": normal(k[0], (c.vocab_size, h), std, dt),
        "lnf_g": jnp.ones((h,), dt),
        "lm_head": normal(k[3], (c.vocab_size, h), std, dt),
    }
    keys = {"dense": k[1], "sparse": k[2], "tail": jax.random.fold_in(root, 5)}
    for group, kinds, sparse, periods in c.segments:
        if c.layer_types is None:
            params[group] = _init_layers(keys[group], periods, c, sparse)
        else:
            params[group] = [
                _init_layers(jax.random.fold_in(keys[group], i), periods, c,
                             sparse, kind) for i, kind in enumerate(kinds)]
    if c.mtp_layers:
        # keys of its own, so that the trunk's do not move with the module
        k = jax.random.split(jax.random.fold_in(root, 4), 2)
        params["mtp"] = {
            "hnorm_g": jnp.ones((h,), dt), "enorm_g": jnp.ones((h,), dt),
            "eh_w": normal(k[0], (2 * h, h), std, dt),
            "layer": _init_layers(k[1], 1, c, sparse=True),
            "lnf_g": jnp.ones((h,), dt)}
    return params


def wd_mask(params) -> Dict:
    """Weight decay on the matrices (`*_w`, `phi`) and the embeddings; none
    on norm gains, the router's bias, the mixing's scalars and biases, or
    a linear layer's `decay_log` and `dt_bias`."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key in ("wte", "lm_head", "phi")
        or path[-1].key.endswith("_w"), params)


def count_params(config: MlaMoeConfig) -> Dict[str, int]:
    """Parameters held here, by group."""
    shapes = jax.eval_shape(lambda: init_mla_moe_params(config, 0))

    def size(tree):
        return sum(math.prod(a.shape)
                   for a in jax.tree_util.tree_leaves(tree))

    def experts(tree):
        return sum(math.prod(a.shape) for path, a in
                   jax.tree_util.tree_leaves_with_path(tree)
                   if any(getattr(k, "key", None) == "experts"
                          for k in path))

    sparse = [shapes[g] for g in ("sparse", "tail") if g in shapes]
    out = {"embedding_and_head": size(shapes["wte"])
           + size(shapes["lm_head"]),
           "dense_layers": size(shapes.get("dense", ())),
           "sparse_layers": size(sparse),
           "routed_experts": experts(sparse)}
    if "mtp" in shapes:
        out["mtp_module"] = size(shapes["mtp"])
    out["total"] = size(shapes)
    return out


# ------------------------------------------------------- residual streams

sinkhorn = stream_mix.sinkhorn


def _sublayer(x, hc, fn, c: MlaMoeConfig):
    """x [n, B, S, h] -> x': y = fn(h_in) between the read-in, h_in =
    sum_i H_pre[i] x[i], and the write-back, x'[i] = sum_j H_res[i, j] x[j]
    + H_post[i] y, with the coefficients a token from the norm of its
    flattened streams, three projections and Sinkhorn, all in float32
    (ops/pallas/stream_mix.py: one pass over the bfloat16 streams for each
    half in each direction, the backward written by hand, so nothing is
    checkpointed here). `fn` opens its own stages and returns (y, aux)."""
    with jax.named_scope(stages.RESIDUAL_MIX):
        h_in, mix, x = stream_mix.read_in(
            x, hc, c.hc_sinkhorn_iters, c.hc_eps, tuple(c.hc_res_clamp))
    y, aux = fn(h_in)
    with jax.named_scope(stages.RESIDUAL_MIX):
        return stream_mix.write_back(x, y, mix), aux


# ---------------------------------------------------------------- a block

def _attention(y, blk, c: MlaMoeConfig):
    """MLA on y [B, S, h] (already mixed in; its norm is here) -> the held
    heads' part of the output projection, [B, S, h]."""
    b, s, _ = y.shape
    heads, dn, dr, dv = (c.heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                         c.v_head_dim)
    eps = c.rms_norm_eps
    with jax.named_scope(stages.ATTN_QKV):
        y = rms_norm(y, blk["ln1_g"], eps)
        if c.q_lora_rank is None:
            q = jnp.einsum("bsh,hk->bsk", y, blk["q_w"])
        else:
            c_q = rms_norm(jnp.einsum("bsh,hr->bsr", y, blk["q_a_w"]),
                           blk["q_a_ln"], eps)
            q = jnp.einsum("bsr,rk->bsk", c_q, blk["q_b_w"])
        kv_a = jnp.einsum("bsh,hr->bsr", y, blk["kv_a_w"])
        c_kv = rms_norm(kv_a[..., :c.kv_lora_rank], blk["kv_a_ln"], eps)
        k_rope = kv_a[..., c.kv_lora_rank:]
        # each head's columns of kv_b_w are its keys' then its values': v as
        # a product of its own is written [B, S, H d_v], as the kernels
        # read it, where a slice of the one product is a copy
        kv_w = blk["kv_b_w"].reshape(-1, heads, dn + dv)
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, kv_w[..., :dn])
        v = jnp.einsum("bsr,rk->bsk", c_kv,
                       kv_w[..., dn:].reshape(-1, heads * dv))
    with jax.named_scope(stages.ATTN_CORE):
        q = q.reshape(b, s, heads, dn + dr)
        if not c.mla_use_nope:
            inv_freq = yarn_inv_freq(dr, c.rope_theta, c.rope_scaling)
            q = jnp.concatenate(
                [q[..., :dn], rope(q[..., dn:], c.rope_theta, inv_freq)], -1)
            k_rope = rope(k_rope[:, :, None, :], c.rope_theta, inv_freq)
        else:
            k_rope = k_rope[:, :, None, :]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, heads, dr))], -1)
        attn = attention(q, k, v.reshape(b, s, heads, dv), causal=True,
                         scale=attention_scale(c),
                         flash=c.use_flash_attention)
    with jax.named_scope(stages.ATTN_OUT):
        return jnp.einsum("bsk,kh->bsh", attn, blk["o_w"]), None


def _linear_attention(y, blk, c: MlaMoeConfig):
    """The linear-attention layer on y [B, S, h] (its norm is there) -> its
    output projection, [B, S, h] (`delta_layer.linear_attention`)."""
    return delta_layer.linear_attention(y, blk, linear_form(c), c.dtype), \
        None


def first_rule(params, x, config: MlaMoeConfig):
    """The chunked rule as the model's FIRST layer runs it on that layer's
    input x [B, S, h] (the embedding's rows), which must be a
    linear-attention layer: the rule's output [B, S, H, d]. For a check of
    the rule alone, forward and backward, against the recurrence it stands
    for. Jit it."""
    c = config
    if c.layer_types is None or c.layer_types[0] != LINEAR:
        raise ValueError("the model's first layer is no linear-attention "
                         "layer")
    group = next(g for g in ("dense", "sparse", "tail") if params.get(g))
    blk = jax.tree_util.tree_map(lambda a: a[0], params[group][0])
    operands, _ = delta_layer.rule_operands(
        x.astype(jnp.dtype(c.dtype)), blk, linear_form(c), c.dtype)
    return chunk_gated_delta_rule(*operands)


def _dense_ffn(y, blk, c: MlaMoeConfig):
    with jax.named_scope(stages.MLP):
        y = rms_norm(y, blk["ln2_g"], c.rms_norm_eps)
        return swiglu(y, blk["gate_w"], blk["up_w"], blk["down_w"]), None


def _sparse_ffn(y, blk, c: MlaMoeConfig):
    """Shared expert + the held routed experts' part
    (`routed.feed_forward`); aux = the router's choices [B*S, k], over all
    the experts."""
    return routed.feed_forward(
        y, blk, c.rms_norm_eps, k=c.num_experts_per_tok,
        scale=c.routed_scaling_factor, held=c.held,
        experts=c.n_routed_experts)


def _block(x, blk, c: MlaMoeConfig, sparse: bool, want_ids: bool,
           kind: str = FULL):
    """One layer -> (x', router choices where `want_ids` and the layer is
    sparse, else None): an attention sub-layer of `kind` and a feed-forward
    one. On the streams x [n, B, S, h] each has its own stream mixing;
    without streams x is [B, S, h] and each is the plain pre-norm
    residual."""
    ffn = functools.partial(_sparse_ffn if sparse else _dense_ffn, blk=blk,
                            c=c)
    attend = functools.partial(
        _linear_attention if kind == LINEAR else _attention, blk=blk, c=c)
    if c.hc_mult is None:
        y, _ = attend(x)
        with jax.named_scope(stages.ATTN_OUT):
            x = x + y
        y, ids = ffn(x)
        with jax.named_scope(stages.MLP):
            x = x + y
    else:
        x, _ = _sublayer(x, blk["hc_attn"], attend, c)
        x, ids = _sublayer(x, blk["hc_ffn"], ffn, c)
    return x, ids if want_ids else None


def _spread(x, c: MlaMoeConfig):
    """[B, S, h] -> what the blocks carry: a copy for each stream."""
    return x if c.hc_mult is None else jnp.broadcast_to(
        x, (c.hc_mult,) + x.shape)


def _gather(x, c: MlaMoeConfig):
    """What the blocks carry -> [B, S, h]: the streams summed."""
    return x if c.hc_mult is None else x.astype(jnp.float32).sum(0).astype(
        x.dtype)


def _trunk(params, tokens, c: MlaMoeConfig, remat: bool, want_ids: bool):
    """tokens [B, S] -> (the last layer's output [B, S, h], before the final
    norm; choices [L_sparse, T, k] or None): the embedding (copied to the
    streams where there are any), the trunk's segments in order (the dense
    layers, the sparse layers' periods, their tail; the streams summed)."""
    with jax.named_scope(stages.EMBED):
        x = _spread(params["wte"][tokens].astype(jnp.dtype(c.dtype)), c)
    x, ids = scan_segments(
        lambda kind, sparse: functools.partial(
            _block, c=c, sparse=sparse, want_ids=want_ids and sparse,
            kind=kind), c.segments, x, params, remat)
    with jax.named_scope(stages.LOSS_HEAD):
        return _gather(x, c), ids


def _mtp_loss(params, hidden, labels, c: MlaMoeConfig, remat: bool,
              want_ids: bool):
    """The prediction module on the trunk's `hidden` [B, S, h] -> (mean
    cross-entropy of the token after next, its router's choices [1, T, k]
    or None). `labels` [B, S] are the next tokens: their embedding is the
    module's second input, and shifted once more they are its targets, the
    last position having none. One scope around all of it, outside the
    stages its layer opens (models/stages.py)."""
    mtp, eps = params["mtp"], c.rms_norm_eps
    with jax.named_scope(stages.MTP):
        nxt = params["wte"][labels].astype(hidden.dtype)
        x = jnp.concatenate([rms_norm(hidden, mtp["hnorm_g"], eps),
                             rms_norm(nxt, mtp["enorm_g"], eps)], -1)
        x = _spread(jnp.einsum("bsk,kh->bsh", x, mtp["eh_w"]), c)
        x, ids = scan_layers(
            functools.partial(_block, c=c, sparse=True, want_ids=want_ids),
            x, mtp["layer"], remat)
        x = rms_norm(_gather(x, c), mtp["lnf_g"], eps)
        after_next = jnp.concatenate(
            [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], 1)
        return lm_head_loss(x, params["lm_head"], after_next,
                            ignore_negative=True), ids


def loss_parts(params, tokens, labels, config: MlaMoeConfig, remat=True,
               want_ids=False):
    """(L_main, L_mtp or None, choices or None): the two mean
    cross-entropies in float32, apart, and with `want_ids` every router's
    choices [L_sparse (+ 1 for the module's, last), T, k]."""
    c = config
    hidden, ids = _trunk(params, tokens, c, remat, want_ids)
    with jax.named_scope(stages.LOSS_HEAD):
        main = lm_head_loss(rms_norm(hidden, params["lnf_g"], c.rms_norm_eps),
                            params["lm_head"], labels)
    if not c.mtp_layers:
        return main, None, ids
    mtp, mtp_ids = _mtp_loss(params, hidden, labels, c, remat, want_ids)
    return main, mtp, jnp.concatenate([ids, mtp_ids]) if want_ids else None


def mla_moe_loss(params, tokens, labels, config: MlaMoeConfig, remat=True,
                 want_ids=False):
    """The step's scalar: mean next-token cross-entropy in float32, plus
    `mtp_loss_weight` times the prediction module's where there is one.
    With `want_ids`, (that, every router's choices) for `state_update`."""
    main, mtp, ids = loss_parts(params, tokens, labels, config, remat,
                                want_ids)
    loss = main if mtp is None else main + config.mtp_loss_weight * mtp
    return (loss, ids) if want_ids else loss


def routing_stats(params, tokens, config: MlaMoeConfig):
    """Per sparse layer of the trunk, the (token, expert) pairs each of ALL
    the routed experts drew on this batch: int32 [L_sparse,
    n_routed_experts]. Jit it; it runs the forward pass."""
    _, ids = _trunk(params, tokens, config, remat=False, want_ids=True)
    return routed.facts(params, ids, config.n_routed_experts)["pairs"]


def step_facts(params, tokens, labels, config: MlaMoeConfig):
    """What a step computes besides its scalar, from one forward pass:
    `loss_main` and `loss_mtp` apart (the second absent without a module),
    `pairs` as `routing_stats` counts them and `biases`, both [L_sparse
    (+ 1 for the module's, last), n_routed_experts]. Jit it."""
    main, mtp, ids = loss_parts(params, tokens, labels, config, remat=False,
                                want_ids=True)
    facts = dict(loss_main=main,
                 **routed.facts(params, ids, config.n_routed_experts))
    if mtp is not None:
        facts["loss_mtp"] = mtp
    return facts


# [L_sparse (+ 1), n_routed_experts], in the order of the choices
_router_biases = routed.router_biases


def move_biases_only(state, tokens, labels, config: MlaMoeConfig):
    """`routed.move_biases_only` after a forward pass on the batch. Jit it
    with the state donated."""
    _, ids = mla_moe_loss(state["params"], tokens, labels, config,
                          remat=False, want_ids=True)
    return routed.move_biases_only(state, ids, config.n_routed_experts,
                                   config.router_bias_update_rate)


def build_train_step(config: MlaMoeConfig, mesh: Optional[Mesh] = None, *,
                     remat: bool = True, **adamw):
    """(init_fn, step): step(state, tokens, labels) -> (state, loss) is ONE
    compiled XLA program (forward, backward through the rematted scans,
    AdamW, and where the configuration has a rate the selection biases'
    move), through `routed.build_train_step`: one chip's share, without
    its exchange."""
    init_params = functools.partial(init_mla_moe_params, config)
    shapes = jax.eval_shape(lambda: init_params(0))
    return routed.build_train_step(
        functools.partial(mla_moe_loss, config=config, remat=remat),
        init_params, jax.tree_util.tree_map(lambda _: P(), shapes),
        wd_mask(shapes), mesh, share=True, experts=config.n_routed_experts,
        rate=config.router_bias_update_rate, **adamw)
