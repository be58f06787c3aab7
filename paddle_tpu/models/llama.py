"""LLaMA model family — functional TPU-compiled path.

Mirrors the reference test models' LLaMA coverage
(test/auto_parallel/hybrid_strategy/semi_auto_llama.py; PaddleNLP arch):
RMSNorm pre-norm, rotary position embeddings, SwiGLU MLP, grouped-query
attention. Same compiled-trainer machinery as gpt.py: layer-stacked params
scanned (or pipelined over a 'pp' mesh axis), Megatron TP specs on the
mp axis, ZeRO-1 over dp, bf16 compute + fp32 master."""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import stages
from .blocks import (attention, layer_trunk, lm_head_loss, normal, rms_norm,
                     rope, scan_layers, swiglu)
from .trainer import build_adamw_train_step


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5504
    num_layers: int = 24
    num_heads: int = 16
    num_kv_heads: Optional[int] = None        # None = MHA; < heads = GQA
    max_position_embeddings: int = 2048
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    dtype: str = "bfloat16"
    tie_embeddings: bool = False

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads


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
    kvh = c.kv_heads * c.head_dim
    dt = jnp.dtype(c.dtype)
    std = c.initializer_range
    out_std = std / math.sqrt(2 * L)
    ks = jax.random.split(key, 9)
    params = {
        "wte": normal(ks[0], (c.vocab_size, h), std, dt),
        "blocks": {
            "ln1_g": jnp.ones((L, h), dt),
            "q_w": normal(ks[1], (L, h, h), std, dt),
            "k_w": normal(ks[2], (L, h, kvh), std, dt),
            "v_w": normal(ks[3], (L, h, kvh), std, dt),
            "o_w": normal(ks[4], (L, h, h), out_std, dt),
            "ln2_g": jnp.ones((L, h), dt),
            "gate_w": normal(ks[5], (L, h, f), std, dt),
            "up_w": normal(ks[6], (L, h, f), std, dt),
            "down_w": normal(ks[7], (L, f, h), out_std, dt),
        },
        "lnf_g": jnp.ones((h,), dt),
    }
    if not c.tie_embeddings:
        params["lm_head"] = normal(ks[8], (c.vocab_size, h), std, dt)
    return params


def param_specs(config: LlamaConfig, pp: Optional[str] = None) -> Dict:
    """Megatron TP layout: q/k/v/gate/up column-split, o/down row-split."""
    blocks = {
        "ln1_g": P(pp, None),
        "q_w": P(pp, None, "mp"), "k_w": P(pp, None, "mp"),
        "v_w": P(pp, None, "mp"), "o_w": P(pp, "mp", None),
        "ln2_g": P(pp, None),
        "gate_w": P(pp, None, "mp"), "up_w": P(pp, None, "mp"),
        "down_w": P(pp, "mp", None),
    }
    specs = {"wte": P("mp", None), "blocks": blocks, "lnf_g": P(None)}
    if not config.tie_embeddings:
        specs["lm_head"] = P("mp", None)
    return specs


def wd_mask(config: LlamaConfig) -> Dict:
    mask = {
        "wte": True,
        "blocks": {k: not k.startswith("ln")
                   for k in ["ln1_g", "q_w", "k_w", "v_w", "o_w", "ln2_g",
                             "gate_w", "up_w", "down_w"]},
        "lnf_g": False,
    }
    if not config.tie_embeddings:
        mask["lm_head"] = True
    return mask


def _block(x, blk, config: LlamaConfig):
    """Pre-norm decoder block: x [B, S, H] -> (x, None)."""
    c = config
    b, s, _ = x.shape
    nh, nkv, d = c.num_heads, c.kv_heads, c.head_dim

    with jax.named_scope(stages.ATTN_QKV):
        y = rms_norm(x, blk["ln1_g"], c.rms_norm_eps)
        q = jnp.einsum("bsh,hk->bsk", y, blk["q_w"])
        k = jnp.einsum("bsh,hk->bsk", y, blk["k_w"])
        v = jnp.einsum("bsh,hk->bsk", y, blk["v_w"])
    with jax.named_scope(stages.ATTN_CORE):
        q = rope(q.reshape(b, s, nh, d), c.rope_theta)
        k = rope(k.reshape(b, s, nkv, d), c.rope_theta)
        v = v.reshape(b, s, nkv, d)
        if nkv != nh:  # GQA: repeat kv heads
            rep = nh // nkv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        # flash=False: no kernel serves this family yet. ROADMAP A2 turns it
        # on here; B3 moves the repeat above into the kernel.
        attn = attention(q, k, v, causal=True, scale=1.0 / math.sqrt(d),
                         flash=False)
    with jax.named_scope(stages.ATTN_OUT):
        x = x + jnp.einsum("bsh,hk->bsk", attn, blk["o_w"])
    with jax.named_scope(stages.MLP):
        y = rms_norm(x, blk["ln2_g"], c.rms_norm_eps)
        return x + swiglu(y, blk["gate_w"], blk["up_w"],
                          blk["down_w"]), None


def _hidden(params, tokens, config: LlamaConfig, remat, pp_trunk):
    """tokens [B, S] -> (the final norm's output [B, S, H], the head)."""
    with jax.named_scope(stages.EMBED):
        x = params["wte"][tokens].astype(jnp.dtype(config.dtype))
    if pp_trunk is not None:
        x = pp_trunk(params["blocks"], x)
    else:
        x, _ = scan_layers(functools.partial(_block, config=config), x,
                           params["blocks"], remat)
    with jax.named_scope(stages.LOSS_HEAD):
        x = rms_norm(x, params["lnf_g"], config.rms_norm_eps)
    return x, params["wte"] if config.tie_embeddings else params["lm_head"]


def llama_forward(params, tokens, config: LlamaConfig, remat=True,
                  pp_trunk=None):
    x, head = _hidden(params, tokens, config, remat, pp_trunk)
    with jax.named_scope(stages.LOSS_HEAD):
        return jnp.einsum("bsh,vh->bsv", x, head)


def llama_loss(params, tokens, labels, config: LlamaConfig, remat=True,
               pp_trunk=None):
    x, head = _hidden(params, tokens, config, remat, pp_trunk)
    with jax.named_scope(stages.LOSS_HEAD):
        return lm_head_loss(x, head, labels)


def build_train_step(config: LlamaConfig, mesh: Optional[Mesh] = None, *,
                     remat: bool = True,
                     pp_microbatches: Optional[int] = None, **adamw):
    pp_trunk = layer_trunk(functools.partial(_block, config=config), mesh,
                           config.num_layers, remat, pp_microbatches)
    return build_adamw_train_step(
        functools.partial(llama_loss, config=config, remat=remat,
                          pp_trunk=pp_trunk),
        functools.partial(init_llama_params, config),
        param_specs(config, pp=None if pp_trunk is None else "pp"),
        wd_mask(config), mesh=mesh, **adamw)
