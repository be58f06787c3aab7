"""Runner of the decoder family with grouped-query attention, window and
full layers mixed and a routed feed-forward: the system under test is
`paddle_tpu.models.llama.build_train_step` (forward with the balance term,
backward, AdamW in one XLA program, `models/trainer.py`). Configuration
files use the key names of the model's public `config.json`; where a key
counts what a chip holds of a layer that several chips divide (routed
experts, vocabulary rows), `published` has the model's count and
`deployment` says which share this is, as in `runners/mla_moe.py`.

What is this runner's own: the loss is two terms, so the comparison with the
plain reference (`reference/window_gqa_moe.check_step`) holds L, L_lm and
the balance term apart, each to its own limit; and the load the window sees
is evened first by the program's own balance term (`balance_steps`)."""
from __future__ import annotations

import functools
import re

from benchmarks import flops_window_gqa_moe, generator
from benchmarks.runners import Program, _trainer, memory_of
from benchmarks.runners.mla_moe_mtp import pairs_off

WINDOW = "sliding_attention"


def program_config(config: dict):
    from paddle_tpu.models.llama import LlamaConfig
    assert config["norm_topk_prob"] and not config["attention_bias"] \
        and not config["tie_word_embeddings"] \
        and set(config["mlp_layer_types"]) == {"sparse"}, \
        "the family renormalises the chosen weights, has no bias and an " \
        "untied head, and every layer here is routed"
    return LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"],
        layer_types=tuple(config["layer_types"]),
        sliding_window=config["sliding_window"],
        rope_parameters=config["rope_parameters"],
        num_experts=config["published"]["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        experts_held=(config["deployment"]["experts_first"],
                      config["num_experts"]),
        router_aux_loss_coef=config["router_aux_loss_coef"],
        initializer_range=config["initializer_range"],
        dtype=config["dtype"])


def build(config: dict, mesh, layout: dict):
    """(init_fn, step, init_params), as `runners/_trainer.py` asks."""
    from paddle_tpu.models.llama import build_train_step, init_llama_params
    c = program_config(config)
    opt = config["optimizer"]
    init_fn, step = build_train_step(
        c, mesh, lr=opt["lr"], wd=opt["wd"], b1=opt["b1"], b2=opt["b2"],
        remat=config["remat"])
    return init_fn, step, functools.partial(init_llama_params, c)


def attention(cell) -> dict:
    """One chip's attention problem: `heads` query heads on `kv_heads` K/V
    heads, the layers of each kind and the window of the window ones, and
    the score tiles `tile_counts` of the kernel module says each kind's
    forward runs masked, whole and not at all, on the block sizes the
    kernels take at these shapes."""
    config = cell.config
    found = _trainer.attention_of(cell, config["num_attention_heads"],
                                  config["head_dim"], causal=True)
    found["kv_heads"] = config["num_key_value_heads"]
    found["window"] = config["sliding_window"]
    found["layers"] = {kind: config["layer_types"].count(kind)
                       for kind in sorted(set(config["layer_types"]))}
    return found


def tiles(cell) -> dict:
    """{kind: [masked, whole, skipped]} of one head's forward, and what a
    causal layer's would be, from the kernel module (a JAX import)."""
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    seq, config = cell.traffic["seq"], cell.config
    bq, bk = fa._block_sizes(seq, seq, config["head_dim"])
    found = {kind: list(fa.tile_counts(
        seq, seq, bq, bk, True, 0,
        config["sliding_window"] if kind == WINDOW else None))
        for kind in sorted(set(config["layer_types"]))}
    found["causal"] = list(fa.tile_counts(seq, seq, bq, bk, True, 0))
    found["blocks"] = [bq, bk]
    return found


def shapes(cell) -> dict:
    """What `flops_window_gqa_moe` counts from (and `hidden`, `held`,
    `expert_ffn` as `layer_metrics/_moe.py` finds the grouped products)."""
    config = cell.config
    return dict(
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        expert_ffn=config["moe_intermediate_size"],
        router_outputs=config["published"]["num_experts"],
        held=config["num_experts"], k=config["num_experts_per_tok"],
        layer_types=tuple(config["layer_types"]),
        window=config["sliding_window"], vocab=config["vocab_size"],
        seq=cell.traffic["seq"])


def flops_per_token(cell) -> float:
    return flops_window_gqa_moe.train_flops_per_token(**shapes(cell))


def lowered_problems(cell, lowered) -> list:
    """`_trainer.flash_problems`, and what grouped queries add to it: no
    [.., S, S] tensor under any leading shape (the einsum branch's is
    [B, H_kv, r, S, S]) and no K or V broadcast to the query heads."""
    problems = _trainer.flash_problems(cell, lowered)
    a, text = attention(cell), lowered.as_text()
    # rank 4 or more: q itself is [B, S, H d], and H d may equal S
    if re.search(rf"\dx\d+x{a['seq']}x{a['seq']}x", text):
        problems.append(f"a [.., {a['seq']}, {a['seq']}] tensor is lowered")
    rep = a["heads"] // a["kv_heads"]
    repeated = (f"{a['batch']}x{a['seq']}x{a['kv_heads']}x{rep}x"
                f"{a['head_dim']}x")
    if repeated in text:
        problems.append(f"K or V is repeated to the query heads: "
                        f"tensor<{repeated}..>")
    return problems


def compare(program: dict, reference: dict, tolerance: dict) -> list:
    """Problems found, empty when the program agrees with the reference:
    the step's scalar and its fall after the first update by
    `check.compare_losses`, and each of the two terms it is made of by its
    own step-0 limit."""
    from benchmarks import check
    problems = check.compare_losses(
        (program["loss0"], program["loss1"]),
        (reference["loss0"], reference["loss1"]), tolerance)
    for part, limit in (("lm0", tolerance["loss"]),
                        ("balance0", tolerance["balance"])):
        p, r = program[part], reference[part]
        if not abs(p - r) <= limit * abs(r):
            problems.append(f"step-0 {part} {p} vs reference {r}: off by "
                            f"more than {limit} relative")
    return problems


def routing(pairs, config: dict, tokens: int) -> dict:
    """What the routers' counters [layers, all experts] say of the load."""
    first = config["deployment"]["experts_first"]
    held = pairs[:, first:first + config["num_experts"]]
    sent = tokens * config["num_experts_per_tok"]
    balanced = sent / pairs.shape[1]
    return {"held_share": float(held.sum(1).mean() / sent),
            "fullest_over_balanced": float(held.max() / balanced),
            "emptiest_over_balanced": float(held.min() / balanced),
            "dropped_pairs": int(sent * pairs.shape[0] - pairs.sum()),
            "held_share_by_router": [float(x) for x in held.sum(1) / sent]}


def set_up(cell, seed: int, devices, phases) -> Program:
    """`_trainer.set_up`'s order (compile on shapes, the reference's answer
    before the state takes its room, the state in one compiled call, two
    steps on the check batch) with this family's comparison, then the
    configuration's `balance_steps` moves of the routers' matrices alone,
    so that the window starts from the load a job's balance term has
    evened out."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.llama import move_routers_only, step_facts
    config, traffic = cell.config, cell.traffic
    vocab = config["vocab_size"]
    # the driver's seeds pass 2**31 and the device takes an int32
    seed %= 1 << 31

    lowered, init_fn, init_params = _trainer.lower_step(cell, devices)
    phases.end("trace_and_lower")
    on_chip = devices[0].platform == "tpu"
    problems = lowered_problems(cell, lowered) if on_chip else []
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
    # have to be the ones the program holds
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
    state, loss1 = step(state, *put(tiled))
    answer = {"loss0": float(loss0), "loss1": float(loss1),
              "lm0": float(before["lm"]),
              "balance0": float(before["balance"]),
              "pairs_off": pairs_off(before["pairs"], reference["pairs0"])}
    reference = {k: v for k, v in reference.items() if k != "pairs0"}
    problems += compare(answer, reference, config["tolerance"])
    phases.end("two_check_steps")

    # the load the timed steps see is the one the balance term has evened
    # (the configuration's `balance_steps` says why): moves of the routers'
    # matrices alone by its gradient's sign on the ring's batches, counted
    # as set-up. The counters are read on the ring's first batch, before
    # and after: the evening is of the sequences the window sends
    tokens = int(ring[0][0].size)
    cold = routing(facts_of(state, ring[0])["pairs"], config, tokens)
    balance = jax.jit(functools.partial(move_routers_only, config=c),
                      donate_argnums=(0,))
    moves, rate = config["balance_steps"], config["balance_rate"]
    for i in range(moves):      # the rate falls linearly to nothing
        state = balance(state, *put(ring[i % len(ring)]),
                        rate=np.float32(rate * (1 - i / moves)))
    warm = facts_of(state, ring[0])
    phases.end("balance_steps")

    return Program(
        step=step, state=state, ring=ring, put=put, unit="tokens",
        units_per_step=traffic["batch"] * traffic["seq"],
        flops_per_unit=flops_per_token(cell), problems=problems,
        memory=memory_of(step), hlo_text=step.as_text,
        facts={"reference": reference, "program": answer,
               "attention": dict(attention(cell), tiles=tiles(cell)),
               "moe": dict(routing(warm["pairs"], config, tokens),
                           tokens=tokens,
                           pairs=tokens * config["num_experts_per_tok"],
                           layers=int(warm["pairs"].shape[0]),
                           balance_term=float(warm["balance"]),
                           before_balance_steps=cold, shapes=shapes(cell))})
