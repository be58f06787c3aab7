"""GPT model family — the flagship (BASELINE.md configs 4/5 shape).

Two execution paths, mirroring the reference's dygraph/static split:

1. Eager Layer path (`GPTModel`, `GPTForPretraining`): built from fleet TP
   layers (VocabParallelEmbedding / Column/RowParallelLinear — the
   mp_layers.py analogs) so weights carry mp sharding annotations.

2. Compiled functional trainer (`build_train_step`): the TPU-native
   "static graph with parallel passes" (SURVEY §3.5) — ONE jitted XLA
   program per training step:
     - per-block params stacked [L, ...] and scanned (lax.scan) — compile
       time O(1) in depth;
     - jax.checkpoint per block = the reference's recompute pass;
     - GSPMD shardings: dp over batch, mp over hidden (Megatron layout:
       qkv/mlp-in column-sharded, proj/mlp-out row-sharded, embeddings
       vocab-sharded), sp (sequence parallel) shards the activation seq
       dim between blocks, ZeRO-style optimizer-state sharding over dp;
     - fused AdamW update in the same program (no separate optimizer
       dispatch) with bf16 params + fp32 master weights.

Reference parity: python/paddle/distributed/fleet/layers/mpu/mp_layers.py,
semi_auto_llama.py test topology (test/auto_parallel/hybrid_strategy/),
GPT-3 config table from the reference's megatron-style examples.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import nn
from ..nn import functional as F
from .._core.tensor import Tensor
from ..distributed.fleet.mp_layers import (ColumnParallelLinear,
                                           RowParallelLinear,
                                           VocabParallelEmbedding)
from . import stages
from .blocks import (attention, gelu_mlp, layer_norm, layer_trunk,
                     lm_head_loss, normal, scan_layers)
from .trainer import build_adamw_train_step


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: Optional[int] = None
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.0
    attention_dropout_prob: float = 0.0
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    use_flash_attention: bool = True
    use_recompute: bool = False
    dtype: str = "bfloat16"

    @property
    def ffn(self):
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


# configs matching the reference's model table
GPT_CONFIGS = {
    "gpt2-small": GPTConfig(hidden_size=768, num_layers=12, num_heads=12),
    "gpt2-medium": GPTConfig(hidden_size=1024, num_layers=24, num_heads=16),
    "gpt3-1.3b": GPTConfig(hidden_size=2048, num_layers=24, num_heads=32,
                           max_position_embeddings=2048),
    "gpt3-6.7b": GPTConfig(hidden_size=4096, num_layers=32, num_heads=32,
                           max_position_embeddings=2048),
}


# =====================================================================
# Eager Layer path
# =====================================================================

class GPTEmbeddings(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.word_embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size)
        self.position_embeddings = nn.Embedding(
            config.max_position_embeddings, config.hidden_size)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, position_ids=None):
        from ..ops.creation import arange
        if position_ids is None:
            position_ids = arange(input_ids.shape[1], dtype="int64")
        h = self.word_embeddings(input_ids) \
            + self.position_embeddings(position_ids)
        return self.dropout(h)


class GPTDecoderLayer(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        h = config.hidden_size
        self.ln_1 = nn.LayerNorm(h, config.layer_norm_eps)
        self.qkv_proj = ColumnParallelLinear(h, 3 * h, gather_output=False)
        self.out_proj = RowParallelLinear(h, h)
        self.ln_2 = nn.LayerNorm(h, config.layer_norm_eps)
        self.mlp_in = ColumnParallelLinear(h, config.ffn,
                                           gather_output=False)
        self.mlp_out = RowParallelLinear(config.ffn, h)
        self.config = config
        self.attn_dropout = nn.Dropout(config.attention_dropout_prob)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, x, attn_mask=None):
        c = self.config
        residual = x
        y = self.ln_1(x)
        qkv = self.qkv_proj(y)
        b, s = qkv.shape[0], qkv.shape[1]
        qkv = qkv.reshape([b, s, 3, c.num_heads, c.head_dim])
        q, k, v = qkv.unbind(axis=2)
        attn, _ = F.flash_attention(q, k, v,
                                    dropout=c.attention_dropout_prob,
                                    causal=True, training=self.training)
        attn = attn.reshape([b, s, c.hidden_size])
        x = residual + self.dropout(self.out_proj(attn))
        residual = x
        y = self.ln_2(x)
        y = self.mlp_out(F.gelu(self.mlp_in(y), approximate=True))
        return residual + self.dropout(y)


class GPTModel(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = nn.LayerList(
            [GPTDecoderLayer(config) for _ in range(config.num_layers)])
        self.ln_f = nn.LayerNorm(config.hidden_size, config.layer_norm_eps)

    def forward(self, input_ids, position_ids=None, attention_mask=None):
        x = self.embeddings(input_ids, position_ids)
        for i, layer in enumerate(self.layers):
            if self.config.use_recompute and self.training:
                from ..distributed.fleet.recompute import recompute
                x = recompute(layer, x)
            else:
                x = layer(x)
        return self.ln_f(x)


class GPTForPretraining(nn.Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)

    def forward(self, input_ids, position_ids=None):
        h = self.gpt(input_ids, position_ids)
        # tied lm head: logits = h @ W_emb^T
        from ..ops.linalg import matmul
        w = self.gpt.embeddings.word_embeddings.weight
        return matmul(h, w, transpose_y=True)


class GPTPretrainingCriterion(nn.Layer):
    def forward(self, logits, labels, loss_mask=None):
        loss = F.cross_entropy(logits, labels, reduction="none")
        if loss_mask is not None:
            from ..ops.reduction import sum as psum
            flat = loss_mask.reshape(loss.shape)
            return psum(loss * flat) / psum(flat)
        from ..ops.reduction import mean
        return mean(loss)


# =====================================================================
# Compiled functional trainer (the perf path)
# =====================================================================

def init_gpt_params(config: GPTConfig, seed: int = 0) -> Dict[str, Any]:
    """Initialize params as a pytree with per-block arrays stacked on a
    leading layer axis [L, ...] (the scan layout)."""
    key = jax.random.PRNGKey(seed)
    h, f_, L = config.hidden_size, config.ffn, config.num_layers
    v, s_max = config.vocab_size, config.max_position_embeddings
    std = config.initializer_range
    out_std = std / math.sqrt(2 * L)
    dt = jnp.dtype(config.dtype)
    ks = jax.random.split(key, 8)
    return {
        "wte": normal(ks[0], (v, h), std, dt),
        "wpe": normal(ks[1], (s_max, h), std, dt),
        "blocks": {
            "ln1_g": jnp.ones((L, h), dt), "ln1_b": jnp.zeros((L, h), dt),
            "qkv_w": normal(ks[2], (L, h, 3 * h), std, dt),
            "qkv_b": jnp.zeros((L, 3 * h), dt),
            "proj_w": normal(ks[3], (L, h, h), out_std, dt),
            "proj_b": jnp.zeros((L, h), dt),
            "ln2_g": jnp.ones((L, h), dt), "ln2_b": jnp.zeros((L, h), dt),
            "fc_w": normal(ks[4], (L, h, f_), std, dt),
            "fc_b": jnp.zeros((L, f_), dt),
            "fo_w": normal(ks[5], (L, f_, h), out_std, dt),
            "fo_b": jnp.zeros((L, h), dt),
        },
        "lnf_g": jnp.ones((h,), dt),
        "lnf_b": jnp.zeros((h,), dt),
    }


def param_specs(config: GPTConfig, dp: str = "dp", mp: str = "mp",
                zero_axis: Optional[str] = None,
                pp: Optional[str] = None) -> Dict[str, Any]:
    """GSPMD PartitionSpecs per param (Megatron TP layout). zero_axis, when
    set, additionally shards the 'long' dim of otherwise-replicated params
    for ZeRO-3 style param sharding. pp, when set, shards the stacked layer
    dim of blocks over the pipeline axis (compiled PP)."""
    def spec(*entries):
        return P(*entries)

    blocks = {
        "ln1_g": spec(pp, None), "ln1_b": spec(pp, None),
        "qkv_w": spec(pp, None, mp), "qkv_b": spec(pp, mp),
        "proj_w": spec(pp, mp, None), "proj_b": spec(pp, None),
        "ln2_g": spec(pp, None), "ln2_b": spec(pp, None),
        "fc_w": spec(pp, None, mp), "fc_b": spec(pp, mp),
        "fo_w": spec(pp, mp, None), "fo_b": spec(pp, None),
    }
    return {
        "wte": spec(mp, None),
        "wpe": spec(None, None),
        "blocks": blocks,
        "lnf_g": spec(None), "lnf_b": spec(None),
    }


def _block(x, blk, config: GPTConfig, mesh_axes, sp_sharding=None):
    """One decoder block, pure jnp: x [B, S, H] -> (x, None). With
    sp_sharding the residual-stream activations are sharded along the
    sequence dim over the mp axis (Megatron-SP, sequence_parallel_utils.py
    analog): GSPMD turns the boundary into the all-gather/reduce-scatter
    pair."""
    c = config
    b, s, _ = x.shape
    with jax.named_scope(stages.ATTN_QKV):
        if sp_sharding is not None:
            x = jax.lax.with_sharding_constraint(x, sp_sharding)
        y = layer_norm(x, blk["ln1_g"], blk["ln1_b"], c.layer_norm_eps)
        if mesh_axes is None or mesh_axes.shape.get("mp", 1) == 1:
            # q, k and v each as a product of its own: three [B, S, h]
            # arrays the kernels index as they are, where slices of one
            # [B, S, 3h] product would be three copies
            h = c.hidden_size
            q, k, v = (jnp.einsum("bsh,hk->bsk", y,
                                  blk["qkv_w"][:, i * h:(i + 1) * h])
                       + blk["qkv_b"][i * h:(i + 1) * h] for i in range(3))
        else:
            # qkv_w's columns are sharded over mp as one [h, 3h] matrix: a
            # column block of it lives on other chips than its heads, so
            # the one product is split, by heads
            qkv = jnp.einsum("bsh,hk->bsk", y, blk["qkv_w"]) + blk["qkv_b"]
            qkv = qkv.reshape(b, s, 3, c.hidden_size)
            q, k, v = (qkv[:, :, i] for i in range(3))
    with jax.named_scope(stages.ATTN_CORE):
        q, k, v = (a.reshape(b, s, c.num_heads, c.head_dim)
                   for a in (q, k, v))
        attn = attention(q, k, v,
                         causal=True, scale=1.0 / math.sqrt(c.head_dim),
                         flash=c.use_flash_attention, mesh=mesh_axes)
    with jax.named_scope(stages.ATTN_OUT):
        proj = jnp.einsum("bsh,hk->bsk", attn, blk["proj_w"]) \
            + blk["proj_b"]
        x = x + proj
    with jax.named_scope(stages.MLP):
        y = layer_norm(x, blk["ln2_g"], blk["ln2_b"], c.layer_norm_eps)
        out = x + gelu_mlp(y, blk["fc_w"], blk["fc_b"], blk["fo_w"],
                           blk["fo_b"])
        if sp_sharding is not None:
            out = jax.lax.with_sharding_constraint(out, sp_sharding)
    return out, None


def _hidden(params, tokens, config: GPTConfig, mesh_axes, remat,
            sp_sharding, pp_trunk):
    """tokens [B, S] int32 -> the final norm's output [B, S, H]."""
    s = tokens.shape[1]
    with jax.named_scope(stages.EMBED):
        x = params["wte"][tokens] + params["wpe"][:s]
        x = x.astype(jnp.dtype(config.dtype))
    if pp_trunk is not None:
        x = pp_trunk(params["blocks"], x)
    else:
        x, _ = scan_layers(
            functools.partial(_block, config=config, mesh_axes=mesh_axes,
                              sp_sharding=sp_sharding),
            x, params["blocks"], remat)
    with jax.named_scope(stages.LOSS_HEAD):
        return layer_norm(x, params["lnf_g"], params["lnf_b"],
                          config.layer_norm_eps)


def gpt_forward(params, tokens, config: GPTConfig, mesh_axes=None,
                remat=True, sp_sharding=None, pp_trunk=None):
    """Pure forward: tokens [B, S] int32 -> logits [B, S, V] under the tied
    head. pp_trunk, when given (`blocks.layer_trunk`), replaces the layer
    scan with the compiled pp-axis pipeline."""
    x = _hidden(params, tokens, config, mesh_axes, remat, sp_sharding,
                pp_trunk)
    with jax.named_scope(stages.LOSS_HEAD):
        return jnp.einsum("bsh,vh->bsv", x, params["wte"])


def gpt_loss(params, tokens, labels, config: GPTConfig, mesh_axes=None,
             remat=True, sp_sharding=None, pp_trunk=None):
    """Mean LM loss; vocabulary-parallel on a mesh with mp > 1
    (`blocks.lm_head_loss`)."""
    x = _hidden(params, tokens, config, mesh_axes, remat, sp_sharding,
                pp_trunk)
    with jax.named_scope(stages.LOSS_HEAD):
        return lm_head_loss(x, params["wte"], labels, mesh_axes)


def wd_mask(config: GPTConfig) -> Dict[str, Any]:
    """Decay only matrix weights + embeddings; LayerNorm gains/biases and
    bias vectors are excluded (Megatron/reference convention)."""
    decay = {"qkv_w", "proj_w", "fc_w", "fo_w"}
    return {
        "wte": True, "wpe": True,
        "blocks": {k: (k in decay)
                   for k in ["ln1_g", "ln1_b", "qkv_w", "qkv_b",
                             "proj_w", "proj_b", "ln2_g", "ln2_b",
                             "fc_w", "fc_b", "fo_w", "fo_b"]},
        "lnf_g": False, "lnf_b": False,
    }


def build_train_step(config: GPTConfig, mesh: Optional[Mesh] = None, *,
                     remat: bool = True, seq_shard: bool = False,
                     pp_microbatches: Optional[int] = None, **adamw):
    """Build (init_fn, step_fn): step is ONE compiled XLA program, fwd +
    bwd (remat'd scan) + AdamW, with dp/mp/sp/ZeRO-1 shardings when `mesh`
    has those axes. A 'pp' mesh axis (size > 1) engages the compiled
    collective-permute pipeline over the stacked layer dim, in which the
    sequence is not sharded. `adamw` (lr, wd, b1, b2, eps) goes to
    models.trainer.build_adamw_train_step, which owns the optimizer and
    the shardings."""
    pp_trunk = layer_trunk(
        functools.partial(_block, config=config, mesh_axes=mesh),
        mesh, config.num_layers, remat, pp_microbatches)

    sp_sharding = None
    if seq_shard and mesh is not None and "mp" in mesh.axis_names \
            and "dp" in mesh.axis_names:
        sp_sharding = NamedSharding(mesh, P("dp", "mp", None))

    def loss_fn(params, tokens, labels):
        return gpt_loss(params, tokens, labels, config, mesh_axes=mesh,
                        remat=remat, sp_sharding=sp_sharding,
                        pp_trunk=pp_trunk)

    return build_adamw_train_step(
        loss_fn, functools.partial(init_gpt_params, config),
        param_specs(config, pp=None if pp_trunk is None else "pp"),
        wd_mask(config), mesh=mesh, **adamw)
