"""Runner of the latent-attention sparse-expert family: the system under
test is `paddle_tpu.models.mla_moe.build_train_step` (forward, backward and
AdamW in one XLA program, `models/trainer.py`). Configuration files use the
key names of the family's public `config.json`; where a key counts what a
chip holds of a layer that several chips divide (heads, routed experts,
vocabulary rows), `published` has the model's count and `deployment` says
which share this is."""
from __future__ import annotations

import functools

from benchmarks import flops_mla_moe, generator
from benchmarks.runners import _trainer


def program_config(config: dict):
    from paddle_tpu.models.mla_moe import MlaMoeConfig
    published, deployment = config["published"], config["deployment"]
    return MlaMoeConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        first_k_dense=config["first_k_dense_replace"],
        num_heads=published["num_attention_heads"],
        heads_held=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_routed_experts=published["n_routed_experts"],
        experts_held=(deployment["experts_first"],
                      config["n_routed_experts"]),
        n_shared_experts=config["n_shared_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        routed_scaling_factor=config["routed_scaling_factor"],
        hc_mult=config["hc_mult"],
        hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_res_clamp=(config["mhc_h_res_clamp_min"],
                      config["mhc_h_res_clamp_max"]),
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        rope_scaling=config["rope_scaling"],
        initializer_range=config["initializer_range"],
        dtype=config["dtype"])


def build(config: dict, mesh, layout: dict):
    """(init_fn, step, init_params), as `runners/_trainer.py` asks."""
    from paddle_tpu.models.mla_moe import (build_train_step,
                                           init_mla_moe_params)
    c = program_config(config)
    opt = config["optimizer"]
    init_fn, step = build_train_step(
        c, mesh, lr=opt["lr"], wd=opt["wd"], b1=opt["b1"], b2=opt["b2"],
        remat=config["remat"])
    return init_fn, step, functools.partial(init_mla_moe_params, c)


def attention(cell) -> dict:
    """One chip's attention problem: the held heads, q and k of `head_dim`
    (nope + rope) and v of `v_head_dim`."""
    config = cell.config
    found = _trainer.attention_of(
        cell, config["num_attention_heads"],
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"], causal=True)
    found["v_head_dim"] = config["v_head_dim"]
    return found


def shapes(cell) -> dict:
    """What `flops_mla_moe` counts from."""
    config = cell.config
    return dict(
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], dense_ffn=config["intermediate_size"],
        expert_ffn=config["moe_intermediate_size"],
        shared=config["n_shared_experts"],
        dense_layers=config["first_k_dense_replace"],
        sparse_layers=config["num_hidden_layers"]
        - config["first_k_dense_replace"],
        router_outputs=config["published"]["n_routed_experts"],
        held=config["n_routed_experts"], k=config["num_experts_per_tok"],
        streams=config["hc_mult"], vocab=config["vocab_size"],
        seq=cell.traffic["seq"])


def flops_per_token(cell) -> float:
    return flops_mla_moe.train_flops_per_token(**shapes(cell))


def set_up(cell, seed: int, devices, phases):
    """`_trainer.set_up`, then the routing counters: on the check batch,
    with the parameters as the two check steps left them, the pairs each of
    all the routed experts drew in each sparse layer."""
    import jax
    import numpy as np

    from paddle_tpu.models.mla_moe import routing_stats
    # the driver's seeds pass 2**31 and `_trainer.set_up` makes the seed an
    # int32 for the device: fold it first, the same way every run
    seed %= 1 << 31
    program = _trainer.set_up(cell, seed, devices, phases)
    config, traffic = cell.config, cell.traffic
    _, tiled = generator.make_check_batch(traffic, config["vocab_size"],
                                          seed)
    tokens = program.put(tiled)[0]
    counts = np.asarray(jax.jit(functools.partial(
        routing_stats, config=program_config(config)))(
            program.state["params"], tokens))        # [layers, all experts]
    first = config["deployment"]["experts_first"]
    held = counts[:, first:first + config["n_routed_experts"]]
    pairs = tokens.size * config["num_experts_per_tok"]
    balanced = pairs / counts.shape[1]
    program.facts["moe"] = {
        "tokens": int(tokens.size), "pairs": int(pairs),
        "layers": int(counts.shape[0]),
        "held_share": float(held.sum(1).mean() / pairs),
        "fullest_over_balanced": float(held.max() / balanced),
        "emptiest_over_balanced": float(held.min() / balanced),
        "dropped_pairs": int(pairs * counts.shape[0] - counts.sum()),
        "shapes": shapes(cell)}
    phases.end("routing_stats")
    return program
