"""Decoder family with RMSNorm, rotary embeddings and SwiGLU (the LLaMA
shape) — functional TPU-compiled path.

Mirrors the reference test models' LLaMA coverage
(test/auto_parallel/hybrid_strategy/semi_auto_llama.py; PaddleNLP arch):
RMSNorm pre-norm, rotary position embeddings, SwiGLU MLP, grouped-query
attention through the flash kernels (the K/V head of a query head is named
in the kernels' index maps; nothing is repeated). Same compiled-trainer
machinery as gpt.py: layer-stacked params scanned (or pipelined over a 'pp'
mesh axis), Megatron TP specs on the mp axis, ZeRO-1 over dp, bf16 compute
+ fp32 master.

These mechanisms are optional, and a configuration that leaves one out
compiles none of it (the dense presets below leave out all of them):

- an explicit head width (`head_dim`; None = hidden / heads), so that
  heads * head_dim need not be the hidden size;
- layers of two kinds in a period (`layer_types`, as published: one name a
  layer): `"sliding_attention"` layers see `sliding_window` keys (a second
  diagonal in the kernels, whose tiles outside it are not visited) and
  rotate by `rope_parameters["sliding_attention"]`; `"full_attention"`
  layers see every earlier key and rotate by
  `rope_parameters["full_attention"]`, which may be yarn with its
  `attention_factor` on cos and sin. The scan is over PERIODS
  (`blocks.scan_periods`): one body holds a period's layers, so depth
  costs no compile time;
- a routed feed-forward in place of the dense SwiGLU (`num_experts` > 0):
  a softmax top-k router over ALL the experts with renormalised weights
  and no shared expert (ops/moe.py), the routed experts this chip HOLDS
  (`experts_held`, dropless), and a balance term in the loss,
  `router_aux_loss_coef` * mean over layers of E * sum_e F_e P_e;
- in place of that router (`router` "sigmoid"): sigmoid scores with a
  selection bias that only steers the choice, the chosen scores
  renormalised and times `moe_routed_scaling_factor`
  (`moe.sigmoid_topk_route`), no balance term, and the bias moved after
  each step by the load (`router_bias_update_rate`,
  `routed.move_router_biases`);
- a shared expert beside the routed ones (`shared_expert_intermediate_size`),
  added ungated;
- leading dense layers in a routed model (`first_k_dense`) and query heads
  by layer kind (`heads_by_kind`): the kinds' parameter trees then differ,
  and the trunk is segments (`blocks.segments`: the dense layers, whole
  periods of the rest, a tail), each position of a period on a tree of its
  own;
- an output gate on attention (`gating`): sigmoid(y W_g) times the core's
  output, elementwise over the heads' H * head_dim, y the block's normed
  input, under the stage `attn_gate`;
- rotary on part of a head: a kind's `partial_rotary_factor` in
  `rope_parameters` rotates that share of each head's components (yarn's
  frequencies over it) and passes the rest through;
- no rotary at all (`rope_theta` None), as a hybrid whose linear layers
  carry the positions;
- linear-attention layers (`"linear_attention"` in `layer_types`): the
  gated delta rule of `models/delta_layer.py` in its Gated DeltaNet form
  (`linear_form`: a log-decay a head, a SiLU output gate, a write strength
  up to 2 where `linear_allow_neg_eigval`), `linear_num_heads` heads of
  `linear_key_head_dim` keys and `linear_value_head_dim` values, in the
  trunk's segments beside the softmax layers, each position of a period on
  a tree of its own;
- Olmo's post-norm block (`post_norm`): x + RMSNorm(mixer(x)), then x +
  RMSNorm(SwiGLU(x)), the mixer and the MLP reading the residual stream
  itself; `ln1_g` and `ln2_g` are then the two norms after them;
- a q/k norm (`qk_norm`): RMSNorm over the whole q projection and the whole
  k projection (Olmo's), with gains `ln_q_g`, `ln_k_g`.

`experts_held` is a chip's share of a layer that several chips divide by
expert parallelism: the routed output is then a partial sum over the held
experts, and nothing here stands in for the absent chips. None means all.
The routed feed-forward, the selection biases' move and the one-chip step
are routed.py's, which the latent family (mla_moe.py) shares.
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
from . import delta_layer, routed, stages
from .blocks import (attention, layer_trunk, lm_head_loss, normal, rms_norm,
                     rope, scan_periods, scan_segments, segments, swiglu,
                     yarn_inv_freq)

FULL, WINDOW, LINEAR = ("full_attention", "sliding_attention",
                         "linear_attention")


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5504             # the dense SwiGLU
    num_layers: int = 24
    num_heads: int = 16
    num_kv_heads: Optional[int] = None        # None = MHA; < heads = GQA
    head_dim: Optional[int] = None            # None = hidden / heads
    max_position_embeddings: int = 2048
    rms_norm_eps: float = 1e-6
    rope_theta: Optional[float] = 10000.0     # None = no rotary at all
    layer_types: Optional[Tuple[str, ...]] = None   # a name a layer; None
    #                                           = every layer full
    sliding_window: Optional[int] = None      # keys a window layer sees
    rope_parameters: Optional[dict] = None    # per kind, as published;
    #                                           None = rope_theta, plain
    num_experts: int = 0                      # the router's outputs; 0 =
    #                                           the dense SwiGLU
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0            # one expert
    experts_held: Optional[Tuple[int, int]] = None   # (first, count)
    router_aux_loss_coef: float = 0.001       # alpha of the balance term
    initializer_range: float = 0.02
    dtype: str = "bfloat16"
    tie_embeddings: bool = False
    heads_by_kind: Optional[Dict[str, int]] = None  # query heads of a layer
    #                                           kind; None = num_heads in all
    gating: bool = False                      # a sigmoid gate on attention
    first_k_dense: int = 0                    # leading dense layers (routed)
    shared_expert_intermediate_size: int = 0  # 0 = no shared expert
    router: str = "softmax"                   # | "sigmoid", with a bias
    moe_routed_scaling_factor: float = 1.0    # the sigmoid router's weights
    router_bias_update_rate: float = 0.0      # gamma; 0 = the bias is held
    linear_num_heads: int = 0                 # a linear_attention layer's
    linear_key_head_dim: int = 0              # heads, their keys' and
    linear_value_head_dim: int = 0            # values' widths,
    linear_conv_kernel_dim: int = 4           # the short convolutions' taps
    linear_allow_neg_eigval: bool = False     # beta up to 2
    post_norm: bool = False                   # Olmo's block
    qk_norm: bool = False                     # RMSNorm over q's and k's
    #                                           whole projections

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            if len(self.layer_types) != self.num_layers \
                    or set(self.layer_types) - {FULL, WINDOW, LINEAR}:
                raise ValueError(
                    f"layer_types must name each of the {self.num_layers} "
                    f"layers {FULL!r}, {WINDOW!r} or {LINEAR!r}: "
                    f"{self.layer_types}")
            if LINEAR in self.layer_types and not (
                    self.linear_num_heads and self.linear_key_head_dim
                    and self.linear_value_head_dim):
                raise ValueError("a linear_attention layer needs its heads "
                                 "and their key and value widths")
            if WINDOW in self.layer_types and not self.sliding_window:
                raise ValueError("a sliding_attention layer needs a "
                                 "sliding_window")
        if self.router not in ("softmax", "sigmoid"):
            raise ValueError(f"router {self.router!r}: softmax or sigmoid")
        if self.first_k_dense and not self.sparse:
            raise ValueError("leading dense layers need a routed "
                             "feed-forward after them")
        if self.post_norm and self.sparse:
            raise NotImplementedError("the post-norm block has a dense "
                                      "feed-forward")
        if any(n % self.kv_heads for n in (self.heads_by_kind or {}).values()):
            raise ValueError(f"{self.kv_heads} K/V heads do not divide "
                             f"every kind's query heads: {self.heads_by_kind}")

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    def heads(self, kind: str = FULL) -> int:
        """Query heads of a layer of `kind`."""
        return (self.heads_by_kind or {}).get(kind, self.num_heads)

    @property
    def period(self) -> Tuple[str, ...]:
        """The kinds of one period: the shortest run of layers the whole
        stack repeats."""
        kinds = self.layer_types or (FULL,) * self.num_layers
        return next(kinds[:n] for n in range(1, len(kinds) + 1)
                    if len(kinds) % n == 0
                    and kinds == kinds[:n] * (len(kinds) // n))

    @property
    def sparse(self) -> bool:
        return self.num_experts > 0

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def segments(self):
        """None where every layer shares one parameter tree, stacked over
        all the layers under "blocks" (`period` is then the scan's); with
        leading dense layers, heads by kind or linear-attention layers the
        trunk in order, as `blocks.segments` gives it (group, kinds of one
        period, sparse, periods), each group a list of trees, one a
        position."""
        if not self.first_k_dense and self.heads_by_kind is None \
                and LINEAR not in (self.layer_types or ()):
            return None
        return [(group, kinds, sparse and self.sparse, periods)
                for group, kinds, sparse, periods in segments(
                    self.layer_types or (FULL,) * self.num_layers,
                    self.first_k_dense)]


LLAMA_CONFIGS = {
    "llama-tiny": LlamaConfig(vocab_size=1024, hidden_size=128,
                              intermediate_size=352, num_layers=2,
                              num_heads=4, num_kv_heads=2,
                              max_position_embeddings=256),
    "llama-7b": LlamaConfig(),
    "llama2-7b": LlamaConfig(hidden_size=4096, intermediate_size=11008,
                             num_layers=32, num_heads=32),
}


def _layers(ks, key, L: int, c: LlamaConfig, heads: int, sparse: bool,
            kind: str = FULL):
    """`L` like layers of `heads` query heads, stacked on a leading axis:
    the matrices from the split keys `ks` and the router from `key` folded
    with 9, as the stacked layout has always drawn them, and what the
    options add from `key` folded with 10 and up. A linear-attention
    layer's own parameters are `delta_layer.init_params`', from `key`
    folded with 12, beside an output projection from its H d_v."""
    h, f = c.hidden_size, c.intermediate_size
    qh, kvh = heads * c.head_dim, c.kv_heads * c.head_dim
    dt = jnp.dtype(c.dtype)
    std = c.initializer_range
    out_std = std / math.sqrt(2 * c.num_layers)
    if kind == LINEAR:
        form = linear_form(c)
        blocks = {"ln1_g": jnp.ones((L, h), dt),
                  **delta_layer.init_params(jax.random.fold_in(key, 12), L,
                                            form, h, std, dt),
                  "o_w": normal(ks[4], (L, form.heads * form.value_dim, h),
                                out_std, dt),
                  "ln2_g": jnp.ones((L, h), dt)}
    else:
        blocks = {
            "ln1_g": jnp.ones((L, h), dt),
            "q_w": normal(ks[1], (L, h, qh), std, dt),
            "k_w": normal(ks[2], (L, h, kvh), std, dt),
            "v_w": normal(ks[3], (L, h, kvh), std, dt),
            "o_w": normal(ks[4], (L, qh, h), out_std, dt),
            "ln2_g": jnp.ones((L, h), dt),
        }
        if c.qk_norm:
            blocks.update(ln_q_g=jnp.ones((L, qh), dt),
                          ln_k_g=jnp.ones((L, kvh), dt))
    if c.gating and kind != LINEAR:
        blocks["attn_gate_w"] = normal(jax.random.fold_in(key, 10),
                                       (L, h, qh), std, dt)
    if sparse:
        f, n = c.moe_intermediate_size, c.held[1]
        blocks.update(
            # the router is float32, as sparse checkpoints keep the gate
            router_w=normal(jax.random.fold_in(key, 9),
                            (L, h, c.num_experts), std, jnp.float32),
            experts={"gate_w": normal(ks[5], (L, n, h, f), std, dt),
                     "up_w": normal(ks[6], (L, n, h, f), std, dt),
                     "down_w": normal(ks[7], (L, n, f, h), out_std, dt)})
        if c.router == "sigmoid":
            # no gradient reaches it: `routed.move_router_biases` moves it
            blocks["router_b"] = jnp.zeros((L, c.num_experts), jnp.float32)
        fs = c.shared_expert_intermediate_size
        if fs:
            k = jax.random.split(jax.random.fold_in(key, 11), 3)
            blocks.update(shared_gate_w=normal(k[0], (L, h, fs), std, dt),
                          shared_up_w=normal(k[1], (L, h, fs), std, dt),
                          shared_down_w=normal(k[2], (L, fs, h), out_std,
                                               dt))
    else:
        blocks.update(gate_w=normal(ks[5], (L, h, f), std, dt),
                      up_w=normal(ks[6], (L, h, f), std, dt),
                      down_w=normal(ks[7], (L, f, h), out_std, dt))
    return blocks


def init_llama_params(config: LlamaConfig, seed: int = 0) -> Dict:
    """The embedding, the head and the layers: stacked under "blocks", or
    with `segments` a list of trees under each group ("dense", "sparse",
    "tail"), one a position of its period, each stacked over the periods
    and drawn from keys of its own."""
    key = jax.random.PRNGKey(seed)
    c = config
    h, dt, std = c.hidden_size, jnp.dtype(c.dtype), c.initializer_range
    ks = jax.random.split(key, 9)
    params = {"wte": normal(ks[0], (c.vocab_size, h), std, dt)}
    if c.segments is None:
        params["blocks"] = _layers(ks, key, c.num_layers, c, c.num_heads,
                                   c.sparse)
    for n, (group, kinds, sparse, periods) in enumerate(c.segments or ()):
        params[group] = []
        for i, kind in enumerate(kinds):
            mine = jax.random.fold_in(jax.random.fold_in(key, 20 + n), i)
            params[group].append(_layers(
                jax.random.split(mine, 9), mine, periods, c, c.heads(kind),
                sparse, kind))
    params["lnf_g"] = jnp.ones((h,), dt)
    if not c.tie_embeddings:
        params["lm_head"] = normal(ks[8], (c.vocab_size, h), std, dt)
    return params


def param_specs(config: LlamaConfig, pp: Optional[str] = None) -> Dict:
    """Megatron TP layout: q/k/v/gate/up column-split, o/down row-split.
    A routed feed-forward or a trunk in `segments` runs one chip's share
    (`routed.build_train_step`): every leaf replicated."""
    if config.sparse or config.segments is not None:
        return jax.tree_util.tree_map(lambda _: P(), jax.eval_shape(
            lambda: init_llama_params(config, 0)))
    blocks = {
        "ln1_g": P(pp, None),
        "q_w": P(pp, None, "mp"), "k_w": P(pp, None, "mp"),
        "v_w": P(pp, None, "mp"), "o_w": P(pp, "mp", None),
        "ln2_g": P(pp, None),
        "gate_w": P(pp, None, "mp"), "up_w": P(pp, None, "mp"),
        "down_w": P(pp, "mp", None),
    }
    if config.gating:
        blocks["attn_gate_w"] = P(pp, None, "mp")
    if config.qk_norm:
        blocks.update(ln_q_g=P(pp, None), ln_k_g=P(pp, None))
    specs = {"wte": P("mp", None), "blocks": blocks, "lnf_g": P(None)}
    if not config.tie_embeddings:
        specs["lm_head"] = P("mp", None)
    return specs


def wd_mask(config: LlamaConfig) -> Dict:
    """Weight decay on the matrices (`*_w`) and the embeddings; none on the
    norm gains, a router's selection bias or a linear layer's `decay_log`
    and `dt_bias`."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key in ("wte", "lm_head")
        or path[-1].key.endswith("_w"),
        param_specs(config), is_leaf=lambda x: isinstance(x, P))


def count_params(config: LlamaConfig) -> Dict[str, int]:
    """Parameters held here, by group."""
    shapes = jax.eval_shape(lambda: init_llama_params(config, 0))

    def size(tree):
        return sum(math.prod(a.shape)
                   for a in jax.tree_util.tree_leaves(tree))

    layers = [shapes[g] for g in ("blocks", "dense", "sparse", "tail")
              if g in shapes]
    out = {"embedding_and_head": size(shapes["wte"])
           + size(shapes.get("lm_head", ())),
           "layers": size(layers), "total": size(shapes)}
    if config.sparse:
        out["routed_experts"] = sum(
            math.prod(a.shape) for path, a in
            jax.tree_util.tree_leaves_with_path(layers)
            if any(getattr(k, "key", None) == "experts" for k in path))
    return out


def _rotary(config: LlamaConfig, kind: str):
    """(theta, inv_freq or None, factor or None) of `blocks.rope` for a
    layer of `kind`: the plain frequencies of its theta, or yarn's with
    `attention_factor` (0.1 ln(factor) + 1 where the file gives none) on
    cos and sin, at every length, as the published code applies it."""
    if config.rope_parameters is None:
        return config.rope_theta, None, None
    p = config.rope_parameters[kind]
    if p["rope_type"] == "default":
        return p["rope_theta"], None, None
    if p["rope_type"] != "yarn":
        raise NotImplementedError(f"rope_type {p['rope_type']!r}")
    factor = p.get("attention_factor") or 0.1 * math.log(p["factor"]) + 1.0
    return p["rope_theta"], yarn_inv_freq(
        _rotary_dim(config, kind) or config.head_dim, p["rope_theta"],
        p), factor


def _rotary_dim(config: LlamaConfig, kind: str) -> Optional[int]:
    """Components of a head that a layer of `kind` rotates, its
    `partial_rotary_factor` of `head_dim`; None where it is all of them."""
    if config.rope_parameters is None:
        return None
    share = config.rope_parameters[kind].get("partial_rotary_factor", 1)
    rot = round(config.head_dim * share)
    return None if rot == config.head_dim else rot


def linear_form(c: LlamaConfig) -> delta_layer.DeltaForm:
    """A linear_attention layer's form (`models/delta_layer.py`): Gated
    DeltaNet, a log-decay a head, a SiLU output gate from one product,
    write strengths up to 2 where `linear_allow_neg_eigval`."""
    return delta_layer.DeltaForm(
        heads=c.linear_num_heads, key_dim=c.linear_key_head_dim,
        value_dim=c.linear_value_head_dim, taps=c.linear_conv_kernel_dim,
        norm_eps=c.rms_norm_eps, decay=delta_layer.HEAD,
        gate=delta_layer.SILU,
        beta_scale=2.0 if c.linear_allow_neg_eigval else 1.0)


def _routing(c: LlamaConfig) -> dict:
    """The arguments of `routed.routed_part` from the configuration."""
    return dict(k=c.num_experts_per_tok, scale=c.moe_routed_scaling_factor,
                held=c.held, experts=c.num_experts)


def _routed_ffn(y, blk, c: LlamaConfig):
    """The held routed experts' part on y [B, S, h], already normed ->
    (that, the router's record; `routed.routed_part`)."""
    b, s, h = y.shape
    out, record = routed.routed_part(y.reshape(b * s, h), blk, **_routing(c))
    return out.reshape(b, s, h), record


def _attention(x, blk, c: LlamaConfig, kind: str):
    """The attention sub-layer of `kind` on x [B, S, H], the block's input
    (normed here, but in the post-norm block): its output before W_o,
    gated where the configuration gates, [B, S, heads * head_dim]."""
    b, s, _ = x.shape
    nh, nkv, d = c.heads(kind), c.kv_heads, c.head_dim
    theta, inv_freq, factor = _rotary(c, kind)
    rot = _rotary_dim(c, kind)

    with jax.named_scope(stages.ATTN_QKV):
        y = x if c.post_norm else rms_norm(x, blk["ln1_g"], c.rms_norm_eps)
        q = jnp.einsum("bsh,hk->bsk", y, blk["q_w"])
        k = jnp.einsum("bsh,hk->bsk", y, blk["k_w"])
        v = jnp.einsum("bsh,hk->bsk", y, blk["v_w"])
        if c.qk_norm:
            q = rms_norm(q, blk["ln_q_g"], c.rms_norm_eps)
            k = rms_norm(k, blk["ln_k_g"], c.rms_norm_eps)
    with jax.named_scope(stages.ATTN_CORE):
        def rotated(x, heads):
            x = x.reshape(b, s, heads, d)
            return x if theta is None else rope(x, theta, inv_freq, factor,
                                                rot)

        q, k = rotated(q, nh), rotated(k, nkv)
        attn = attention(q, k, v.reshape(b, s, nkv, d), causal=True,
                         scale=1.0 / math.sqrt(d),
                         flash=True,
                         window=c.sliding_window if kind == WINDOW else None)
    if c.gating:
        with jax.named_scope(stages.ATTN_GATE):
            gate = jnp.einsum("bsh,hk->bsk", y, blk["attn_gate_w"])
            attn = attn * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(attn.dtype)
    return attn


def _block(x, blk, config: LlamaConfig, kind: str = FULL,
           sparse: Optional[bool] = None):
    """Decoder block of `kind`: x [B, S, H] -> (x, None, or the router's
    record where the feed-forward is routed: `sparse`, None = the
    configuration's). Pre-norm, or Olmo's post-norm (`post_norm`)."""
    c = config
    sparse = c.sparse if sparse is None else sparse
    eps = c.rms_norm_eps
    if kind == LINEAR:
        out = delta_layer.linear_attention(x, blk, linear_form(c), c.dtype,
                                           pre_norm=not c.post_norm)
    else:
        attn = _attention(x, blk, c, kind)
    with jax.named_scope(stages.ATTN_OUT):
        if kind != LINEAR:
            out = jnp.einsum("bsh,hk->bsk", attn, blk["o_w"])
        x = x + (rms_norm(out, blk["ln1_g"], eps) if c.post_norm else out)
    if sparse:
        y, record = routed.feed_forward(x, blk, eps, **_routing(c))
        with jax.named_scope(stages.MLP):
            return x + y, record
    with jax.named_scope(stages.MLP):
        if c.post_norm:
            return x + rms_norm(swiglu(x, blk["gate_w"], blk["up_w"],
                                       blk["down_w"]), blk["ln2_g"], eps), \
                None
        y = rms_norm(x, blk["ln2_g"], eps)
        return x + swiglu(y, blk["gate_w"], blk["up_w"], blk["down_w"]), None


def _layer(params, n: int, config: LlamaConfig):
    """(layer n's parameters, its kind) in a trunk of segments."""
    at = 0
    for group, kinds, _, periods in config.segments:
        if n < at + len(kinds) * periods:
            period, position = divmod(n - at, len(kinds))
            return jax.tree_util.tree_map(
                lambda a: a[period], params[group][position]), \
                kinds[position]
        at += len(kinds) * periods
    raise ValueError(f"the trunk has {at} layers, no layer {n}")


def layer_attention(params, x, n: int, config: LlamaConfig):
    """Layer n's attention sub-layer as the trunk runs it, on x [B, S, H]
    handed in as that layer's input: its output before W_o, gated (the
    kernels, the partial rotary and the gate of the timed step), for a
    check of the layer against a reference forward and backward. The
    trunk must be segments (`config.segments`). Jit it."""
    c = config
    blk, kind = _layer(params, n, c)
    return _attention(x.astype(jnp.dtype(c.dtype)), blk, c, kind)


def layer_rule(params, x, n: int, config: LlamaConfig):
    """The chunked rule as layer n, a linear-attention layer, runs it on x
    [B, S, H] handed in as that layer's input: the rule's output [B, S, H,
    d_v], from the layer's own projections, convolutions, norms and
    decays, for a check of the rule alone, forward and backward, against
    the recurrence it stands for. Jit it."""
    c = config
    blk, kind = _layer(params, n, c)
    if kind != LINEAR:
        raise ValueError(f"layer {n} is a {kind} layer")
    operands, _ = delta_layer.rule_operands(
        x.astype(jnp.dtype(c.dtype)), blk, linear_form(c), c.dtype,
        pre_norm=not c.post_norm)
    return chunk_gated_delta_rule(*operands)


def _hidden(params, tokens, config: LlamaConfig, remat, pp_trunk):
    """tokens [B, S] -> (the final norm's output [B, S, H], the head, the
    routers' records stacked over the layers or None)."""
    with jax.named_scope(stages.EMBED):
        x = params["wte"][tokens].astype(jnp.dtype(config.dtype))
    records = None
    if pp_trunk is not None:
        x = pp_trunk(params["blocks"], x)
    elif config.segments is not None:
        x, records = scan_segments(
            lambda kind, sparse: functools.partial(
                _block, config=config, kind=kind, sparse=sparse),
            config.segments, x, params, remat)
    else:
        x, records = scan_periods(
            [functools.partial(_block, config=config, kind=kind)
             for kind in config.period], x, params["blocks"], remat)
    with jax.named_scope(stages.LOSS_HEAD):
        x = rms_norm(x, params["lnf_g"], config.rms_norm_eps)
    head = params["wte"] if config.tie_embeddings else params["lm_head"]
    return x, head, records


def llama_forward(params, tokens, config: LlamaConfig, remat=True,
                  pp_trunk=None):
    x, head, _ = _hidden(params, tokens, config, remat, pp_trunk)
    with jax.named_scope(stages.LOSS_HEAD):
        return jnp.einsum("bsh,vh->bsv", x, head)


def loss_parts(params, tokens, labels, config: LlamaConfig, remat=True,
               pp_trunk=None):
    """(L, aux): the step's scalar, and what it is made of. Dense: L is
    the mean float32 cross-entropy and aux None. Routed by softmax: L =
    L_lm + alpha * balance with aux {"lm": L_lm, "balance": the mean over
    the layers of E * sum_e F_e P_e (k at perfect balance), "pairs": the
    pairs each of ALL the experts drew in each layer [L, E]}, over this
    chip's tokens. Routed by sigmoid: L = L_lm with aux {"lm": L_lm,
    "ids": every routed layer's choices [L, T, k]}."""
    x, head, records = _hidden(params, tokens, config, remat, pp_trunk)
    with jax.named_scope(stages.LOSS_HEAD):
        lm = lm_head_loss(x, head, labels)
    if records is None:
        return lm, None
    if config.router == "sigmoid":
        return lm, {"lm": lm, "ids": records}
    with jax.named_scope(stages.ROUTER):
        balance = records["balance"].mean()
        loss = lm + config.router_aux_loss_coef * balance
    return loss, {"lm": lm, "balance": balance, "pairs": records["pairs"]}


def llama_loss(params, tokens, labels, config: LlamaConfig, remat=True,
               pp_trunk=None, want_ids=False):
    """The step's scalar; with `want_ids` (a sigmoid router), (that, every
    routed layer's choices) for `state_update`."""
    loss, aux = loss_parts(params, tokens, labels, config, remat, pp_trunk)
    return (loss, aux["ids"]) if want_ids else loss


def step_facts(params, tokens, labels, config: LlamaConfig):
    """What a routed step computes besides its scalar, from one forward
    pass: `loss` and `lm`, `pairs` [L, num_experts] and, by softmax,
    `balance` apart, by sigmoid the selection `biases` [L, num_experts].
    Jit it."""
    loss, aux = loss_parts(params, tokens, labels, config, remat=False)
    if config.router == "sigmoid":
        ids = aux.pop("ids")
        return dict(aux, loss=loss,
                    **routed.facts(params, ids, config.num_experts))
    return dict(aux, loss=loss)


def move_biases_only(state, tokens, labels, config: LlamaConfig):
    """`routed.move_biases_only` after a forward pass on the batch. Jit it
    with the state donated."""
    _, aux = loss_parts(state["params"], tokens, labels, config, remat=False)
    return routed.move_biases_only(state, aux["ids"], config.num_experts,
                                   config.router_bias_update_rate)


def move_routers_only(state, tokens, labels, config: LlamaConfig,
                      rate: float):
    """The train state after one move of the routers' matrices ALONE, by
    the program's own balance term (the mean over the layers of E * sum_e
    F_e P_e on this batch; no LM loss, no AdamW moment or step count
    touched): router_w -= rate * sign(d balance / d router_w), on master
    and parameters alike. The sign, as AdamW's first step moves a weight
    (by the rate, whichever way its gradient points): the raw gradient is
    a thousand times stiffer along the direction the tokens of a batch
    share than across it, and a step it survives moves nothing else. What
    the balance term does to the load over a job's first thousands of
    steps (the routers' matrices drift until the experts draw alike, the
    other weights hardly moving) a benchmark can reach in tens of these
    before it times real steps. Jit it with the state donated."""
    def balance(router_w):
        blocks = dict(state["params"]["blocks"], router_w=router_w)
        return loss_parts(dict(state["params"], blocks=blocks), tokens,
                          labels, config)[1]["balance"]

    moved = state["master"]["blocks"]["router_w"] - rate * jnp.sign(
        jax.grad(balance)(state["params"]["blocks"]["router_w"]))

    def put(tree):
        return dict(tree, blocks=dict(
            tree["blocks"],
            router_w=moved.astype(tree["blocks"]["router_w"].dtype)))

    return dict(state, params=put(state["params"]),
                master=put(state["master"]))


def build_train_step(config: LlamaConfig, mesh: Optional[Mesh] = None, *,
                     remat: bool = True,
                     pp_microbatches: Optional[int] = None, **adamw):
    """(init_fn, step): ONE compiled XLA program a step through
    `routed.build_train_step`, which runs a routed feed-forward or layers
    of two kinds as one chip's share and refuses a mesh of several chips
    for them; a dense trunk of one kind runs on any mesh, pipelined over a
    `pp` axis."""
    pp_trunk = layer_trunk(functools.partial(_block, config=config), mesh,
                           config.num_layers, remat, pp_microbatches)
    return routed.build_train_step(
        functools.partial(llama_loss, config=config, remat=remat,
                          pp_trunk=pp_trunk),
        functools.partial(init_llama_params, config),
        param_specs(config, pp=None if pp_trunk is None else "pp"),
        wd_mask(config), mesh,
        share=config.sparse or len(config.period) > 1
        or config.segments is not None,
        experts=config.num_experts, rate=config.router_bias_update_rate,
        **adamw)
