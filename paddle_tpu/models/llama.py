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

Three mechanisms are optional, and a configuration that leaves one out
compiles none of it (the dense presets below leave out all three):

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
  `router_aux_loss_coef` * mean over layers of E * sum_e F_e P_e.

`experts_held` is a chip's share of a layer that several chips divide by
expert parallelism: the routed output is then a partial sum over the held
experts, and nothing here stands in for the absent chips. None means all.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import moe
from . import stages
from .blocks import (attention, layer_trunk, lm_head_loss, normal, rms_norm,
                     rope, scan_periods, swiglu, yarn_inv_freq)
from .trainer import build_adamw_train_step

FULL, WINDOW = "full_attention", "sliding_attention"


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
    rope_theta: float = 10000.0
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

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            if len(self.layer_types) != self.num_layers \
                    or set(self.layer_types) - {FULL, WINDOW}:
                raise ValueError(
                    f"layer_types must name each of the {self.num_layers} "
                    f"layers {FULL!r} or {WINDOW!r}: {self.layer_types}")
            if WINDOW in self.layer_types and not self.sliding_window:
                raise ValueError("a sliding_attention layer needs a "
                                 "sliding_window")

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

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


LLAMA_CONFIGS = {
    "llama-tiny": LlamaConfig(vocab_size=1024, hidden_size=128,
                              intermediate_size=352, num_layers=2,
                              num_heads=4, num_kv_heads=2,
                              max_position_embeddings=256),
    "llama-7b": LlamaConfig(),
    "llama2-7b": LlamaConfig(hidden_size=4096, intermediate_size=11008,
                             num_layers=32, num_heads=32),
}


def init_llama_params(config: LlamaConfig, seed: int = 0) -> Dict:
    key = jax.random.PRNGKey(seed)
    c = config
    h, f, L = c.hidden_size, c.intermediate_size, c.num_layers
    qh, kvh = c.num_heads * c.head_dim, c.kv_heads * c.head_dim
    dt = jnp.dtype(c.dtype)
    std = c.initializer_range
    out_std = std / math.sqrt(2 * L)
    ks = jax.random.split(key, 9)
    blocks = {
        "ln1_g": jnp.ones((L, h), dt),
        "q_w": normal(ks[1], (L, h, qh), std, dt),
        "k_w": normal(ks[2], (L, h, kvh), std, dt),
        "v_w": normal(ks[3], (L, h, kvh), std, dt),
        "o_w": normal(ks[4], (L, qh, h), out_std, dt),
        "ln2_g": jnp.ones((L, h), dt),
    }
    if c.sparse:
        f, n = c.moe_intermediate_size, c.held[1]
        blocks.update(
            # the router is float32, as sparse checkpoints keep the gate
            router_w=normal(jax.random.fold_in(key, 9),
                            (L, h, c.num_experts), std, jnp.float32),
            experts={"gate_w": normal(ks[5], (L, n, h, f), std, dt),
                     "up_w": normal(ks[6], (L, n, h, f), std, dt),
                     "down_w": normal(ks[7], (L, n, f, h), out_std, dt)})
    else:
        blocks.update(gate_w=normal(ks[5], (L, h, f), std, dt),
                      up_w=normal(ks[6], (L, h, f), std, dt),
                      down_w=normal(ks[7], (L, f, h), out_std, dt))
    params = {
        "wte": normal(ks[0], (c.vocab_size, h), std, dt),
        "blocks": blocks,
        "lnf_g": jnp.ones((h,), dt),
    }
    if not c.tie_embeddings:
        params["lm_head"] = normal(ks[8], (c.vocab_size, h), std, dt)
    return params


def param_specs(config: LlamaConfig, pp: Optional[str] = None) -> Dict:
    """Megatron TP layout: q/k/v/gate/up column-split, o/down row-split.
    The routed feed-forward runs one chip's share (`build_train_step`):
    its leaves are replicated."""
    blocks = {
        "ln1_g": P(pp, None),
        "q_w": P(pp, None, "mp"), "k_w": P(pp, None, "mp"),
        "v_w": P(pp, None, "mp"), "o_w": P(pp, "mp", None),
        "ln2_g": P(pp, None),
    }
    if config.sparse:
        blocks.update(router_w=P(), experts={
            "gate_w": P(), "up_w": P(), "down_w": P()})
    else:
        blocks.update(gate_w=P(pp, None, "mp"), up_w=P(pp, None, "mp"),
                      down_w=P(pp, "mp", None))
    specs = {"wte": P("mp", None), "blocks": blocks, "lnf_g": P(None)}
    if not config.tie_embeddings:
        specs["lm_head"] = P("mp", None)
    return specs


def wd_mask(config: LlamaConfig) -> Dict:
    """Weight decay on the matrices and the embeddings, none on the norm
    gains."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: not path[-1].key.startswith("ln"),
        param_specs(config), is_leaf=lambda x: isinstance(x, P))


def count_params(config: LlamaConfig) -> Dict[str, int]:
    """Parameters held here, by group."""
    shapes = jax.eval_shape(lambda: init_llama_params(config, 0))

    def size(tree):
        return sum(math.prod(a.shape)
                   for a in jax.tree_util.tree_leaves(tree))

    out = {"embedding_and_head": size(shapes["wte"])
           + size(shapes.get("lm_head", ())),
           "layers": size(shapes["blocks"]), "total": size(shapes)}
    if config.sparse:
        out["routed_experts"] = size(shapes["blocks"]["experts"])
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
    return p["rope_theta"], yarn_inv_freq(config.head_dim, p["rope_theta"],
                                          p), factor


def _routed_ffn(y, blk, c: LlamaConfig):
    """The held routed experts' part on y [B, S, h], already normed ->
    (that, {"balance": the layer's E * sum_e F_e P_e, "pairs": the pairs
    each of ALL the experts drew [E]})."""
    b, s, h = y.shape
    flat = y.reshape(b * s, h)
    with jax.named_scope(stages.ROUTER):
        ids, weights, probs = moe.softmax_topk_route(
            flat, blk["router_w"], c.num_experts_per_tok)
        balance, drawn = moe.balance_term(ids, probs)
    with jax.named_scope(stages.EXPERTS):
        routed = moe.held_experts_ffn(flat, ids, weights, blk["experts"],
                                      c.held, c.num_experts)
    return routed.reshape(b, s, h), {"balance": balance, "pairs": drawn}


def _block(x, blk, config: LlamaConfig, kind: str = FULL):
    """Pre-norm decoder block of `kind`: x [B, S, H] -> (x, None, or the
    router's record where the feed-forward is routed)."""
    c = config
    b, s, _ = x.shape
    nh, nkv, d = c.num_heads, c.kv_heads, c.head_dim
    theta, inv_freq, factor = _rotary(c, kind)

    with jax.named_scope(stages.ATTN_QKV):
        y = rms_norm(x, blk["ln1_g"], c.rms_norm_eps)
        q = jnp.einsum("bsh,hk->bsk", y, blk["q_w"])
        k = jnp.einsum("bsh,hk->bsk", y, blk["k_w"])
        v = jnp.einsum("bsh,hk->bsk", y, blk["v_w"])
    with jax.named_scope(stages.ATTN_CORE):
        q = rope(q.reshape(b, s, nh, d), theta, inv_freq, factor)
        k = rope(k.reshape(b, s, nkv, d), theta, inv_freq, factor)
        attn = attention(q, k, v.reshape(b, s, nkv, d), causal=True,
                         scale=1.0 / math.sqrt(d),
                         flash=True,
                         window=c.sliding_window if kind == WINDOW else None)
    with jax.named_scope(stages.ATTN_OUT):
        x = x + jnp.einsum("bsh,hk->bsk", attn, blk["o_w"])
    with jax.named_scope(stages.MLP):
        y = rms_norm(x, blk["ln2_g"], c.rms_norm_eps)
        if not c.sparse:
            return x + swiglu(y, blk["gate_w"], blk["up_w"],
                              blk["down_w"]), None
    y, record = _routed_ffn(y, blk, c)
    with jax.named_scope(stages.MLP):
        return x + y, record


def _hidden(params, tokens, config: LlamaConfig, remat, pp_trunk):
    """tokens [B, S] -> (the final norm's output [B, S, H], the head, the
    routers' records stacked over the layers or None)."""
    with jax.named_scope(stages.EMBED):
        x = params["wte"][tokens].astype(jnp.dtype(config.dtype))
    records = None
    if pp_trunk is not None:
        x = pp_trunk(params["blocks"], x)
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
    the mean float32 cross-entropy and aux None. Routed: L = L_lm + alpha *
    balance with aux {"lm": L_lm, "balance": the mean over the layers of
    E * sum_e F_e P_e (k at perfect balance), "pairs": the pairs each of
    ALL the experts drew in each layer [L, E]}, over this chip's tokens."""
    x, head, records = _hidden(params, tokens, config, remat, pp_trunk)
    with jax.named_scope(stages.LOSS_HEAD):
        lm = lm_head_loss(x, head, labels)
    if records is None:
        return lm, None
    with jax.named_scope(stages.ROUTER):
        balance = records["balance"].mean()
        loss = lm + config.router_aux_loss_coef * balance
    return loss, {"lm": lm, "balance": balance, "pairs": records["pairs"]}


def llama_loss(params, tokens, labels, config: LlamaConfig, remat=True,
               pp_trunk=None):
    return loss_parts(params, tokens, labels, config, remat, pp_trunk)[0]


def step_facts(params, tokens, labels, config: LlamaConfig):
    """What a routed step computes besides its scalar, from one forward
    pass: `loss`, `lm` and `balance` apart and `pairs` [L, num_experts].
    Jit it."""
    loss, aux = loss_parts(params, tokens, labels, config, remat=False)
    return dict(aux, loss=loss)


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
    `trainer.build_adamw_train_step`. A routed feed-forward or layers of
    two kinds run one chip's share without its exchange; on a mesh of
    several chips they need an `ep` axis (and a pipeline whose stage holds
    a period), which the trainer does not have yet."""
    several = mesh is not None and mesh.size > 1
    if several and (config.sparse or len(config.period) > 1):
        raise NotImplementedError(
            "llama.build_train_step runs a routed feed-forward or mixed "
            "layer kinds as one chip's share; experts over an 'ep' mesh "
            "axis are not implemented")
    pp_trunk = layer_trunk(functools.partial(_block, config=config), mesh,
                           config.num_layers, remat, pp_microbatches)
    return build_adamw_train_step(
        functools.partial(llama_loss, config=config, remat=remat,
                          pp_trunk=pp_trunk),
        functools.partial(init_llama_params, config),
        param_specs(config, pp=None if pp_trunk is None else "pp"),
        wd_mask(config), mesh=mesh, **adamw)
