"""Runner of the GPT family: the system under test is
`paddle_tpu.models.gpt.build_train_step` (forward, backward and AdamW in one
XLA program, `models/trainer.py`). Configuration files use the key names of
the public GPT-2 `config.json`."""
from __future__ import annotations

import functools

from benchmarks import flops
from benchmarks.runners import _trainer


def program_config(config: dict):
    from paddle_tpu.models.gpt import GPTConfig
    return GPTConfig(
        vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        intermediate_size=config["n_inner"],
        max_position_embeddings=config["n_positions"],
        layer_norm_eps=config["layer_norm_epsilon"],
        initializer_range=config["initializer_range"],
        dtype=config["dtype"])


def build(config: dict, mesh, layout: dict):
    """(init_fn, step, init_params): `init_fn(seed)` builds the whole train
    state, `step(state, tokens, labels)` is the jitted program, and
    `init_params(seed)` gives the parameters alone, for the reference.
    `layout` is the cell's: what the mesh cannot say (sequence sharding,
    pipeline micro-batches)."""
    from paddle_tpu.models.gpt import build_train_step, init_gpt_params
    c = program_config(config)
    opt = config["optimizer"]
    init_fn, step = build_train_step(
        c, mesh, lr=opt["lr"], wd=opt["wd"], b1=opt["b1"], b2=opt["b2"],
        seq_shard=bool(layout.get("seq_shard")), remat=config["remat"],
        pp_microbatches=layout.get("pp_microbatches"))
    return init_fn, step, functools.partial(init_gpt_params, c)


def attention(cell) -> dict:
    config = cell.config
    return _trainer.attention_of(
        cell, config["n_head"], config["n_embd"] // config["n_head"],
        causal=True)


def flops_per_token(cell) -> float:
    config = cell.config
    return flops.gpt_train_flops_per_token(
        layers=config["n_layer"], hidden=config["n_embd"],
        ffn=config["n_inner"], vocab=config["vocab_size"],
        seq=cell.traffic["seq"])


set_up = _trainer.set_up
