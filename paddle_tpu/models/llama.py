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
from .trainer import build_adamw_train_step, filter_specs_for_mesh


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
    ks = jax.random.split(key, 9)

    def norm(k, shape, scale=std):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    params = {
        "wte": norm(ks[0], (c.vocab_size, h)),
        "blocks": {
            "ln1_g": jnp.ones((L, h), dt),
            "q_w": norm(ks[1], (L, h, h)),
            "k_w": norm(ks[2], (L, h, kvh)),
            "v_w": norm(ks[3], (L, h, kvh)),
            "o_w": norm(ks[4], (L, h, h), scale=std / math.sqrt(2 * L)),
            "ln2_g": jnp.ones((L, h), dt),
            "gate_w": norm(ks[5], (L, h, f)),
            "up_w": norm(ks[6], (L, h, f)),
            "down_w": norm(ks[7], (L, f, h),
                           scale=std / math.sqrt(2 * L)),
        },
        "lnf_g": jnp.ones((h,), dt),
    }
    if not c.tie_embeddings:
        params["lm_head"] = norm(ks[8], (c.vocab_size, h))
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


# ------------------------------------------------------------------ rope

def _rope(x, theta: float, inv_freq=None):
    """x [B, S, H, D] -> rotated. Half-split convention. `inv_freq` [D/2]
    replaces theta's plain frequencies (a scaled RoPE such as yarn)."""
    b, s, h, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1 = x1.astype(jnp.float32)
    xf2 = x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin],
        axis=-1).astype(x.dtype)


def _rms(x, g, eps):
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype) * g


def _swiglu(y, gate_w, up_w, down_w):
    """down(silu(gate y) * up y) on y [..., h]."""
    gate = jnp.einsum("...h,hf->...f", y, gate_w)
    up = jnp.einsum("...h,hf->...f", y, up_w)
    return jnp.einsum("...f,fh->...h", jax.nn.silu(gate) * up, down_w)


def _block(x, blk, config: LlamaConfig):
    c = config
    b, s, h = x.shape
    nh, nkv, d = c.num_heads, c.kv_heads, c.head_dim

    with jax.named_scope(stages.ATTN_QKV):
        y = _rms(x, blk["ln1_g"], c.rms_norm_eps)
        q = jnp.einsum("bsh,hk->bsk", y, blk["q_w"])
        k = jnp.einsum("bsh,hk->bsk", y, blk["k_w"])
        v = jnp.einsum("bsh,hk->bsk", y, blk["v_w"])
    with jax.named_scope(stages.ATTN_CORE):
        q = _rope(q.reshape(b, s, nh, d), c.rope_theta)
        k = _rope(k.reshape(b, s, nkv, d), c.rope_theta)
        v = v.reshape(b, s, nkv, d)
        if nkv != nh:  # GQA: repeat kv heads
            rep = nh // nkv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        qt = jnp.swapaxes(q, 1, 2)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / math.sqrt(d)
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, jnp.array(-1e30, logits.dtype))
        probs = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(
            x.dtype)
        attn = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
        attn = jnp.swapaxes(attn, 1, 2).reshape(b, s, h)
    with jax.named_scope(stages.ATTN_OUT):
        x = x + jnp.einsum("bsh,hk->bsk", attn, blk["o_w"])
    with jax.named_scope(stages.MLP):
        y = _rms(x, blk["ln2_g"], c.rms_norm_eps)
        return x + _swiglu(y, blk["gate_w"], blk["up_w"], blk["down_w"])


def llama_forward(params, tokens, config: LlamaConfig, remat=True,
                  pp_trunk=None):
    with jax.named_scope(stages.EMBED):
        x = params["wte"][tokens].astype(jnp.dtype(config.dtype))
    if pp_trunk is not None:
        x = pp_trunk(params["blocks"], x)
    else:
        fn = functools.partial(_block, config=config)
        if remat:
            fn = jax.checkpoint(fn)
        x, _ = jax.lax.scan(lambda c, blk: (fn(c, blk), None), x,
                            params["blocks"])
    with jax.named_scope(stages.LOSS_HEAD):
        x = _rms(x, params["lnf_g"], config.rms_norm_eps)
        head = params["wte"] if config.tie_embeddings \
            else params["lm_head"]
        return jnp.einsum("bsh,vh->bsv", x, head)


def llama_loss(params, tokens, labels, config: LlamaConfig, remat=True,
               pp_trunk=None):
    logits = llama_forward(params, tokens, config, remat, pp_trunk)
    with jax.named_scope(stages.LOSS_HEAD):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        picked = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        return -picked.mean()


def build_train_step(config: LlamaConfig, mesh: Optional[Mesh] = None,
                     lr: float = 3e-4, remat: bool = True,
                     pp_microbatches: Optional[int] = None, **adamw):
    pp_size = mesh.shape.get("pp", 1) if mesh is not None else 1
    use_pp = pp_size > 1
    if use_pp and config.num_layers % pp_size:
        raise ValueError("num_layers not divisible by pp degree")
    pp_trunk = None
    if use_pp:
        from ..distributed.pipeline_compiled import pipelined_trunk
        pp_trunk = pipelined_trunk(
            functools.partial(_block, config=config), mesh,
            pp_microbatches or 2 * pp_size, axis_name="pp", remat=remat)

    loss = functools.partial(llama_loss, config=config, remat=remat,
                             pp_trunk=pp_trunk)
    return build_adamw_train_step(
        lambda p, t, l: loss(p, t, l),
        functools.partial(init_llama_params, config),
        param_specs(config, pp="pp" if use_pp else None),
        wd_mask(config), mesh=mesh, lr=lr, **adamw)
