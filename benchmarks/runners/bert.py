"""Runner of the BERT family: the system under test is
`paddle_tpu.models.bert.build_train_step` (masked-LM loss, backward and AdamW
in one XLA program, `models/trainer.py`). Configuration files use the key
names of the public BERT `config.json`."""
from __future__ import annotations

import functools

from benchmarks import flops
from benchmarks.generator import labelled_share
from benchmarks.runners import _trainer


def program_config(config: dict):
    from paddle_tpu.models.bert import BertConfig
    return BertConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        layer_norm_eps=config["layer_norm_eps"],
        initializer_range=config["initializer_range"],
        dtype=config["dtype"])


def build(config: dict, mesh, layout: dict):
    """(init_fn, step, init_params), as `runners/_trainer.py` asks."""
    from paddle_tpu.models.bert import build_train_step, init_bert_params
    c = program_config(config)
    opt = config["optimizer"]
    init_fn, step = build_train_step(
        c, mesh, lr=opt["lr"], remat=config["remat"], wd=opt["wd"],
        b1=opt["b1"], b2=opt["b2"], eps=opt["eps"])
    return init_fn, step, functools.partial(init_bert_params, c)


def attention(cell) -> dict:
    config = cell.config
    heads = config["num_attention_heads"]
    return _trainer.attention_of(cell, heads, config["hidden_size"] // heads,
                                 causal=False)


def flops_per_token(cell) -> float:
    config = cell.config
    return flops.bert_mlm_train_flops_per_token(
        layers=config["num_hidden_layers"], hidden=config["hidden_size"],
        ffn=config["intermediate_size"], vocab=config["vocab_size"],
        seq=cell.traffic["seq"], labelled_share=labelled_share(cell.traffic))


set_up = _trainer.set_up
