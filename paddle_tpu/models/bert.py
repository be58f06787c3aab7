"""BERT / ERNIE encoder family — functional TPU-compiled path.

ERNIE-3.0-base is architecturally a BERT encoder (12L/768H/12A) with
task-specific pretraining; the driver baseline tracks ERNIE tokens/sec/chip
(BASELINE.md config 5). Same compiled-trainer machinery as gpt/llama:
stacked-layer scan + remat, TP specs on mp, ZeRO-1 over dp; pretraining
objective here is masked-LM (the throughput-relevant part)."""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import stages
from .blocks import (attention, gelu_mlp, layer_norm, lm_head_loss, normal,
                     scan_layers)
from .trainer import build_adamw_train_step


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


BERT_CONFIGS = {
    "bert-tiny": BertConfig(vocab_size=1024, hidden_size=128,
                            num_layers=2, num_heads=2,
                            intermediate_size=512,
                            max_position_embeddings=128),
    "bert-base": BertConfig(),
    "ernie-3.0-base": BertConfig(vocab_size=40000),
    "bert-large": BertConfig(hidden_size=1024, num_layers=24,
                             num_heads=16, intermediate_size=4096),
}


def init_bert_params(config: BertConfig, seed: int = 0) -> Dict:
    key = jax.random.PRNGKey(seed)
    c = config
    h, f, L = c.hidden_size, c.intermediate_size, c.num_layers
    dt = jnp.dtype(c.dtype)
    std = c.initializer_range
    out_std = std / math.sqrt(2 * L)
    ks = jax.random.split(key, 8)
    return {
        "wte": normal(ks[0], (c.vocab_size, h), std, dt),
        "wpe": normal(ks[1], (c.max_position_embeddings, h), std, dt),
        "wtype": normal(ks[2], (c.type_vocab_size, h), std, dt),
        "emb_ln_g": jnp.ones((h,), dt), "emb_ln_b": jnp.zeros((h,), dt),
        "blocks": {
            "qkv_w": normal(ks[3], (L, h, 3 * h), std, dt),
            "qkv_b": jnp.zeros((L, 3 * h), dt),
            "proj_w": normal(ks[4], (L, h, h), out_std, dt),
            "proj_b": jnp.zeros((L, h), dt),
            "ln1_g": jnp.ones((L, h), dt), "ln1_b": jnp.zeros((L, h), dt),
            "fc_w": normal(ks[5], (L, h, f), std, dt),
            "fc_b": jnp.zeros((L, f), dt),
            "fo_w": normal(ks[6], (L, f, h), out_std, dt),
            "fo_b": jnp.zeros((L, h), dt),
            "ln2_g": jnp.ones((L, h), dt), "ln2_b": jnp.zeros((L, h), dt),
        },
        "mlm_w": normal(ks[7], (h, h), std, dt), "mlm_b": jnp.zeros((h,), dt),
        "mlm_ln_g": jnp.ones((h,), dt), "mlm_ln_b": jnp.zeros((h,), dt),
    }


def param_specs(config: BertConfig) -> Dict:
    blocks = {
        "qkv_w": P(None, None, "mp"), "qkv_b": P(None, "mp"),
        "proj_w": P(None, "mp", None), "proj_b": P(None, None),
        "ln1_g": P(None, None), "ln1_b": P(None, None),
        "fc_w": P(None, None, "mp"), "fc_b": P(None, "mp"),
        "fo_w": P(None, "mp", None), "fo_b": P(None, None),
        "ln2_g": P(None, None), "ln2_b": P(None, None),
    }
    return {
        "wte": P("mp", None), "wpe": P(None, None), "wtype": P(None, None),
        "emb_ln_g": P(None), "emb_ln_b": P(None),
        "blocks": blocks,
        "mlm_w": P(None, None), "mlm_b": P(None),
        "mlm_ln_g": P(None), "mlm_ln_b": P(None),
    }


def wd_mask(config: BertConfig) -> Dict:
    dec = {"qkv_w", "proj_w", "fc_w", "fo_w"}
    return {
        "wte": True, "wpe": True, "wtype": True,
        "emb_ln_g": False, "emb_ln_b": False,
        "blocks": {k: (k in dec) for k in
                   ["qkv_w", "qkv_b", "proj_w", "proj_b", "ln1_g",
                    "ln1_b", "fc_w", "fc_b", "fo_w", "fo_b", "ln2_g",
                    "ln2_b"]},
        "mlm_w": True, "mlm_b": False,
        "mlm_ln_g": False, "mlm_ln_b": False,
    }


def _block(x, blk, config: BertConfig, attn_mask=None,
           mesh: Optional[Mesh] = None):
    """Post-norm encoder block (BERT convention): x [B, S, H] -> (x, None);
    attn_mask [B, 1, 1, S] additive or None. `blocks.attention` picks the
    branch: without a mask, on a TPU, at a sequence of 256 tokens or more
    that 128 divides, the flash kernels (no diagonal: every tile whole); at
    128 tokens, with a padding mask (the kernels take none) or on a CPU the
    einsum path. Off an `mp` mesh q, k and v are three products on column
    blocks of `qkv_w`: three [B, S, h] arrays the seq-major kernels index
    as they are, where slices of one [B, S, 3h] product are three copies in
    front of every call (forward, remat, backward), and cost the einsum
    path its copies too. On a `mesh` the kernels run manual over its axes
    (`mha_sharded`: Mosaic calls are not partitioned automatically), the
    batch over `dp` and the heads over `mp`, which must divide them. The
    stored parameters are the same everywhere."""
    c = config
    b, s, h = x.shape
    with jax.named_scope(stages.ATTN_QKV):
        if mesh is None or mesh.shape.get("mp", 1) == 1:
            q, k, v = (jnp.einsum("bsh,hk->bsk", x,
                                  blk["qkv_w"][:, i * h:(i + 1) * h])
                       + blk["qkv_b"][i * h:(i + 1) * h] for i in range(3))
        else:
            # qkv_w's columns are sharded over mp as one [h, 3h] matrix: a
            # column block of it lives on other chips than its heads, so
            # the one product is split, by heads (as `gpt._block` does)
            qkv = jnp.einsum("bsh,hk->bsk", x, blk["qkv_w"]) + blk["qkv_b"]
            qkv = qkv.reshape(b, s, 3, h)
            q, k, v = (qkv[:, :, i] for i in range(3))
    with jax.named_scope(stages.ATTN_CORE):
        q, k, v = (a.reshape(b, s, c.num_heads, c.head_dim)
                   for a in (q, k, v))
        attn = attention(q, k, v, causal=False,
                         scale=1.0 / math.sqrt(c.head_dim), flash=True,
                         mesh=mesh, mask=attn_mask)
    with jax.named_scope(stages.ATTN_OUT):
        attn = jnp.einsum("bsh,hk->bsk", attn, blk["proj_w"]) \
            + blk["proj_b"]
        x = layer_norm(x + attn, blk["ln1_g"], blk["ln1_b"],
                       c.layer_norm_eps)
    with jax.named_scope(stages.MLP):
        y = gelu_mlp(x, blk["fc_w"], blk["fc_b"], blk["fo_w"], blk["fo_b"])
        return layer_norm(x + y, blk["ln2_g"], blk["ln2_b"],
                          c.layer_norm_eps), None


def bert_encode(params, tokens, token_type_ids=None, attention_mask=None,
                config: BertConfig = None, remat=True,
                mesh: Optional[Mesh] = None):
    """tokens [B, S] int32 -> hidden [B, S, H]. Under jit on more than one
    device pass the `mesh` (see `_block`)."""
    b, s = tokens.shape
    c = config
    with jax.named_scope(stages.EMBED):
        x = params["wte"][tokens] + params["wpe"][:s]
        if token_type_ids is not None:
            x = x + params["wtype"][token_type_ids]
        else:
            x = x + params["wtype"][0]
        x = layer_norm(x.astype(jnp.dtype(c.dtype)), params["emb_ln_g"],
                       params["emb_ln_b"], c.layer_norm_eps)
    add_mask = None
    if attention_mask is not None:
        with jax.named_scope(stages.ATTN_CORE):
            add_mask = (1.0 - attention_mask[:, None, None, :].astype(
                jnp.float32)) * -1e30
    x, _ = scan_layers(
        functools.partial(_block, config=c, attn_mask=add_mask, mesh=mesh),
        x, params["blocks"], remat)
    return x


def _mlm_transform(params, x, config: BertConfig):
    """The MLM head before the tied classifier: dense, gelu, LayerNorm."""
    x = jnp.einsum("bsh,hk->bsk", x, params["mlm_w"]) + params["mlm_b"]
    x = jax.nn.gelu(x, approximate=True)
    return layer_norm(x, params["mlm_ln_g"], params["mlm_ln_b"],
                      config.layer_norm_eps)


def bert_mlm_logits(params, tokens, config: BertConfig, remat=True,
                    attention_mask=None, mesh: Optional[Mesh] = None):
    x = bert_encode(params, tokens, None, attention_mask, config, remat,
                    mesh)
    with jax.named_scope(stages.LOSS_HEAD):
        return jnp.einsum("bsh,vh->bsv", _mlm_transform(params, x, config),
                          params["wte"])


def bert_mlm_loss(params, tokens, labels, config: BertConfig, remat=True,
                  mesh: Optional[Mesh] = None):
    """labels: -100 for unmasked positions (ignored), else target id."""
    x = bert_encode(params, tokens, config=config, remat=remat, mesh=mesh)
    with jax.named_scope(stages.LOSS_HEAD):
        return lm_head_loss(_mlm_transform(params, x, config),
                            params["wte"], labels, ignore_negative=True)


def build_train_step(config: BertConfig, mesh: Optional[Mesh] = None, *,
                     remat: bool = True, lr: float = 1e-4, **adamw):
    return build_adamw_train_step(
        functools.partial(bert_mlm_loss, config=config, remat=remat,
                          mesh=mesh),
        functools.partial(init_bert_params, config),
        param_specs(config), wd_mask(config), mesh=mesh, lr=lr, **adamw)
