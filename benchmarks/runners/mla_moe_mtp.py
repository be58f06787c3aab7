"""Runner of the latent-attention sparse-expert family WITHOUT residual
streams, WITH a multi-token-prediction module and a load-driven selection
bias: the system under test is still `paddle_tpu.models.mla_moe
.build_train_step` (forward with both losses, backward, AdamW and the
biases' move in one XLA program, `models/trainer.py`), as in
`runners/mla_moe.py`, whose conventions for a chip's share (`published`,
`deployment`) are this file's too.

What is this runner's own: the step does something besides AdamW, so the
comparison with the plain reference (`reference/mla_moe_mtp.check_step`)
also moves the biases before the second loss, reports `L_main` and `L_mtp`
apart (a module that contributes nothing cannot hide in the sum), and reads
the biases the program's first step left; and the load the window sees is
balanced first, by the program's own bias rule (`balance_steps`)."""
from __future__ import annotations

import functools

from benchmarks import flops_mla_moe_mtp, generator
from benchmarks.runners import Program, _trainer, memory_of
from benchmarks.runners import mla_moe as family


def program_config(config: dict):
    from paddle_tpu.models.mla_moe import MlaMoeConfig
    published, deployment = config["published"], config["deployment"]
    return MlaMoeConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        first_k_dense=config["first_k_dense_replace"],
        num_heads=config["num_attention_heads"],
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
        hc_mult=None,
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        rope_scaling=config["rope_scaling"],
        initializer_range=config["initializer_range"],
        router_bias_update_rate=config["router_bias_update_rate"],
        mtp_layers=config["num_nextn_predict_layers"],
        mtp_loss_weight=config["mtp_loss_weight"],
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


attention = family.attention


def shapes(cell) -> dict:
    """What `flops_mla_moe_mtp` counts from (`runners/mla_moe.shapes`
    without the streams, with the modules)."""
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
        mtp_layers=config["num_nextn_predict_layers"],
        router_outputs=config["published"]["n_routed_experts"],
        held=config["n_routed_experts"], k=config["num_experts_per_tok"],
        vocab=config["vocab_size"], seq=cell.traffic["seq"])


def flops_per_token(cell) -> float:
    return flops_mla_moe_mtp.train_flops_per_token(**shapes(cell))


def pairs_off(drawn, reference) -> float:
    """How far the routers' choices are from the reference's: the share of
    a router's pairs that went to another expert than in the reference
    (half the summed distance between the two distributions of its pairs
    over the experts), the mean over the routers. `drawn` and `reference`
    are [routers, experts] counts, of any two batches in which the same
    sequences recur equally often."""
    import numpy as np
    p, r = (np.asarray(a, np.float64) for a in (drawn, reference))
    p, r = p / p.sum(1, keepdims=True), r / r.sum(1, keepdims=True)
    return float(0.5 * np.abs(p - r).sum(1).mean())


def compare(program: dict, reference: dict, tolerance: dict) -> list:
    """Problems found, empty when the program agrees with the reference:
    the step's scalar and its fall after the first update by
    `check.compare_losses`, each of the two losses it is made of by the
    step-0 limit, and a bias update that moved nothing."""
    from benchmarks import check
    problems = check.compare_losses(
        (program["loss0"], program["loss1"]),
        (reference["loss0"], reference["loss1"]), tolerance)
    for part in ("main0", "mtp0"):
        p, r = program[part], reference[part]
        if not abs(p - r) <= tolerance["loss"] * abs(r):
            problems.append(f"step-0 {part} {p} vs reference {r}: off by "
                            f"more than {tolerance['loss']} relative")
    if reference["bias_moved_share"] and not program["bias_moved_share"]:
        problems.append("no selection bias moved in the first step; the "
                        f"reference moved {reference['bias_moved_share']} "
                        "of them")
    return problems


def set_up(cell, seed: int, devices, phases) -> Program:
    """`_trainer.set_up`'s order (compile on shapes, the reference's answer
    before the state takes its room, the state in one compiled call, two
    steps on the check batch) with this family's comparison, then the
    configuration's `balance_steps` moves of the biases alone, so that the
    window starts from the load a deployment's rule has evened out."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.mla_moe import move_biases_only, step_facts
    config, traffic = cell.config, cell.traffic
    vocab = config["vocab_size"]
    # the driver's seeds pass 2**31 and the device takes an int32
    seed %= 1 << 31

    lowered, init_fn, init_params = _trainer.lower_step(cell, devices)
    phases.end("trace_and_lower")
    on_chip = devices[0].platform == "tpu"
    problems = _trainer.flash_problems(cell, lowered) if on_chip else []
    step = lowered.compile()
    phases.end("compile_or_load_step")
    (state_sharding, *batch_shardings), _ = step.input_shardings

    ring = generator.make_ring(traffic, vocab, seed)
    two, tiled = generator.make_check_batch(traffic, vocab, seed)
    seed = np.int32(seed)
    to_f32 = jax.jit(lambda s: jax.tree_util.tree_map(
        lambda a: a.astype("float32"), init_params(s)))
    fingerprint = jax.jit(lambda tree: jnp.stack(
        [jnp.abs(a).sum() for a in jax.tree_util.tree_leaves(tree)]))
    start = to_f32(seed)
    started_from = np.asarray(fingerprint(start))
    reference = cell.reference.check_step(start, two, config)
    del start
    phases.end("reference")

    state = jax.jit(init_fn, out_shardings=state_sharding)(seed)
    jax.block_until_ready(state)
    # the reference steps from float32 masters and rounds them itself: they
    # have to be the ones the program holds (on the chip neither is the
    # bfloat16 parameters' exact copy: `reference/mla_moe_mtp.py`)
    if not (np.asarray(fingerprint(state["master"])) == started_from).all():
        problems.append("the reference did not start from the program's "
                        "float32 master weights")
    phases.end("init_state")

    def put(batch):
        return [jax.device_put(a, s) for a, s in zip(batch, batch_shardings)]

    c = program_config(config)
    facts = jax.jit(functools.partial(step_facts, config=c))

    def facts_of(state, batch=tiled):
        found = jax.device_get(facts(state["params"], *put(batch)))
        return {k: np.asarray(v) for k, v in found.items()}

    before = facts_of(state)
    state, loss0 = step(state, *put(tiled))
    after = facts_of(state)         # before the next step takes the state
    state, loss1 = step(state, *put(tiled))
    answer = {"loss0": float(loss0), "loss1": float(loss1),
              "main0": float(before["loss_main"]),
              "mtp0": float(before["loss_mtp"]),
              "bias_moved_share": float(
                  (after["biases"] != before["biases"]).mean()),
              "bias_as_reference_share": float(
                  (after["biases"] == reference["biases1"]).mean()),
              "pairs_off": pairs_off(before["pairs"], reference["pairs0"])}
    reference = {k: v for k, v in reference.items()
                 if k not in ("biases1", "pairs0")}
    problems += compare(answer, reference, config["tolerance"])
    phases.end("two_check_steps")

    # the load the timed steps see is the one the biases have balanced (the
    # configuration's `balance_steps` says why): the program's own rule on
    # the ring's batches, forward passes only, counted as set-up
    balance = jax.jit(functools.partial(move_biases_only, config=c),
                      donate_argnums=(0,))
    for i in range(config["balance_steps"]):
        state = balance(state, *put(ring[i % len(ring)]))
    warm = facts_of(state, ring[0])
    phases.end("balance_steps")

    # the routing counters, as `runners/mla_moe.set_up` logs them: the
    # pairs each of all the routed experts drew on the ring's first batch,
    # by the parameters and biases the window starts from
    pairs = warm["pairs"]                         # [layers + 1, all experts]
    first = config["deployment"]["experts_first"]
    held = pairs[:, first:first + config["n_routed_experts"]]
    tokens = int(ring[0][0].size)
    sent = tokens * config["num_experts_per_tok"]
    balanced = sent / pairs.shape[1]
    return Program(
        step=step, state=state, ring=ring, put=put, unit="tokens",
        units_per_step=traffic["batch"] * traffic["seq"],
        flops_per_unit=flops_per_token(cell), problems=problems,
        memory=memory_of(step), hlo_text=step.as_text,
        facts={"reference": reference, "program": answer,
               "attention": attention(cell),
               "moe": {"tokens": tokens, "pairs": sent,
                       "layers": int(pairs.shape[0]),
                       "held_share": float(held.sum(1).mean() / sent),
                       "fullest_over_balanced": float(held.max() / balanced),
                       "emptiest_over_balanced": float(held.min()
                                                       / balanced),
                       "dropped_pairs": int(sent * pairs.shape[0]
                                            - pairs.sum()),
                       "bias_moved_share": answer["bias_moved_share"],
                       "bias_max_abs": float(np.abs(warm["biases"]).max()),
                       "held_share_by_router": [
                           float(x) for x in held.sum(1) / sent],
                       "shapes": shapes(cell)}})
