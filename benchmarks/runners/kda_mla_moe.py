"""Runner of the latent-attention sparse-expert family with layers of two
kinds: the system under test is still `paddle_tpu.models.mla_moe
.build_train_step` (forward, backward, AdamW and the selection biases' move
in one XLA program, `models/trainer.py`), as in `runners/mla_moe_mtp.py`,
whose order of set-up, comparison of the biases and balancing of the load
are this file's too; `runners/mla_moe.py` has the conventions for a chip's
share (`published`, `deployment`).

What is this runner's own: the configuration names its layers' kinds by two
lists of layer numbers (`linear_attn_config.kda_layers`, `.full_attn_layers`),
it has no query latent, no rotary table and no prediction module, and the
lowered step must hold, beside the Mosaic attention calls of the latent
layer, the chunked rule's scan over chunks and no loop over tokens
(`linear_attention_of` reads both from the compiled step)."""
from __future__ import annotations

import functools
import re

from benchmarks import flops_kda_mla_moe, generator
from benchmarks.runners import Program, _trainer, memory_of
from benchmarks.runners.mla_moe_mtp import pairs_off

def layer_types(config: dict) -> tuple:
    """"linear" or "full" for each layer, from the two published lists of
    layer numbers (counted from 1), which together name every layer once."""
    spec = config["linear_attn_config"]
    layers = config["num_hidden_layers"]
    linear, full = set(spec["kda_layers"]), set(spec["full_attn_layers"])
    if linear & full or linear | full != set(range(1, layers + 1)):
        raise ValueError(
            f"kda_layers and full_attn_layers do not name each of the "
            f"{layers} layers once: {spec}")
    return tuple("linear" if n in linear else "full"
                 for n in range(1, layers + 1))


def program_config(config: dict):
    from paddle_tpu.models.mla_moe import MlaMoeConfig
    published, deployment = config["published"], config["deployment"]
    spec = config["linear_attn_config"]
    assert config["moe_router_activation_func"] == "sigmoid" \
        and config["moe_renormalize"] and config["num_expert_group"] == 1 \
        and config["moe_layer_freq"] == 1 \
        and not config["tie_word_embeddings"], \
        "the family's router is a sigmoid over one group, renormalised, " \
        "every layer after the dense ones is sparse and the head is untied"
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
        n_routed_experts=published["num_experts"],
        experts_held=(deployment["experts_first"], config["num_experts"]),
        n_shared_experts=config["num_shared_experts"],
        num_experts_per_tok=config["num_experts_per_token"],
        routed_scaling_factor=config["routed_scaling_factor"],
        hc_mult=None,
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        rope_scaling=config["rope_scaling"],
        mla_use_nope=config["mla_use_nope"],
        layer_types=layer_types(config),
        linear_heads=spec["num_heads"], linear_head_dim=spec["head_dim"],
        linear_conv_size=spec["short_conv_kernel_size"],
        initializer_range=config["initializer_range"],
        router_bias_update_rate=config["router_bias_update_rate"],
        mtp_layers=config["num_nextn_predict_layers"],
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
    """One chip's softmax-attention problem, the latent layers': q and k of
    nope + 64, v of `v_head_dim`."""
    config = cell.config
    found = _trainer.attention_of(
        cell, config["num_attention_heads"],
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"], causal=True)
    found["v_head_dim"] = config["v_head_dim"]
    return found


def shapes(cell) -> dict:
    """What `flops_kda_mla_moe` counts from (and `hidden`, `held`,
    `expert_ffn` as `layer_metrics/_moe.py` finds the grouped products)."""
    config = cell.config
    spec, kinds = config["linear_attn_config"], layer_types(config)
    return dict(
        hidden=config["hidden_size"], linear_layers=kinds.count("linear"),
        linear_heads=spec["num_heads"], linear_head_dim=spec["head_dim"],
        taps=spec["short_conv_kernel_size"],
        full_layers=kinds.count("full"),
        heads=config["num_attention_heads"], kv_rank=config["kv_lora_rank"],
        nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
        v_dim=config["v_head_dim"], dense_ffn=config["intermediate_size"],
        expert_ffn=config["moe_intermediate_size"],
        shared=config["num_shared_experts"],
        dense_layers=config["first_k_dense_replace"],
        sparse_layers=config["num_hidden_layers"]
        - config["first_k_dense_replace"],
        router_outputs=config["published"]["num_experts"],
        held=config["num_experts"], k=config["num_experts_per_token"],
        vocab=config["vocab_size"], seq=cell.traffic["seq"])


def flops_per_token(cell) -> float:
    return flops_kda_mla_moe.train_flops_per_token(**shapes(cell))


_WHILE = re.compile(
    r'^\s*(?:ROOT )?%?[\w.\-]+ = (\(.*?\)) while\(.*?condition=%?([\w.\-]+)'
    r'.*?op_name="([^"]*)"')
_COMPUTATION = re.compile(r'^(?:ENTRY )?%?([\w.\-]+) \(.*\{$')
_BOUND = re.compile(r'\bconstant\((\d+)\)')


def state_loops(hlo_text: str, stage: str, heads: int, head_dim: int):
    """[(trips, rows)] of the `while` loops of a compiled step that stand
    under `stage` and carry a float32 [rows, heads, head_dim, head_dim]
    state: the trips are the loop's bound, the largest integer constant of
    its condition (None where it has none)."""
    state = re.compile(rf"f32\[(\d+),{heads},{head_dim},{head_dim}\]")
    conditions, inside, found = {}, None, []
    for line in hlo_text.splitlines():
        start = _COMPUTATION.match(line)
        if start:
            inside = conditions.setdefault(start.group(1), [])
        elif inside is not None:
            inside.extend(int(n) for n in _BOUND.findall(line))
        loop = _WHILE.match(line)
        if loop and stage in loop.group(3).split("/"):
            carried = state.search(loop.group(1))
            if carried:
                found.append((loop.group(2), int(carried.group(1))))
    return [(max(conditions.get(name) or [0]) or None, rows)
            for name, rows in found]


def linear_attention_of(cell, hlo_text: str) -> dict:
    """What the COMPILED step says of the chunked rule, from its loops that
    carry the rule's state (`state_loops`: the scan over chunks, forward,
    repeated and backward): their one trip count, S / C, the chunk that
    follows from it and the bytes of the carried state. A scan that became
    a loop over tokens reads S here; loops that disagree, or none, read
    nothing."""
    from benchmarks.layer_metrics import _linear_attn, _moe
    stage = _moe.stage_name(_linear_attn.STAGE)
    config, seq = cell.config, cell.traffic["seq"]
    spec, kinds = config["linear_attn_config"], layer_types(config)
    heads, d = spec["num_heads"], spec["head_dim"]
    loops = set(state_loops(hlo_text, stage, heads, d)) if stage else set()
    steps, rows = loops.pop() if len(loops) == 1 else (None, None)
    batch = cell.traffic["batch"] // cell.mesh_shape.get("dp", 1)
    return {"layers": {"kda": kinds.count("linear"),
                       "mla": kinds.count("full")},
            "scan_steps": steps, "chunk": seq // steps if steps else None,
            "heads": heads, "head_dim": d, "tokens": batch * seq,
            "state_bytes": rows * heads * d * d * 4 if rows else None}


def off(got, want) -> float:
    """|got - want|_2 / |want|_2 over every element, in float32."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def relative(got, want):
        want = want.astype(jnp.float32)
        diff = got.astype(jnp.float32) - want
        return jnp.sqrt((diff * diff).sum() / (want * want).sum())

    return float(relative(got, want))


def rule_cotangent(seed, cell):
    """What the rule's output is weighed by for its gradient: seeded
    normal values [2, S, H, d] in the configuration's dtype, so that
    program and reference are handed the same numbers."""
    import jax
    spec = cell.config["linear_attn_config"]
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (2, cell.traffic["seq"], spec["num_heads"], spec["head_dim"]),
        cell.config["dtype"])


def rule_offs(params, tokens, cotangent, c, want) -> tuple:
    """How far the chunked rule, as the model's first layer runs it on
    `tokens` [rows, S] at the timed widths, is from the recurrence it
    stands for, forward and backward: (`off` of o, `off` of the gradient of
    sum(o * cotangent) to the layer's input) against `want`, which is the
    reference's `check_rule` from the same weights: ITS convolutions, norms
    and decay, its token-by-token recurrence and that recurrence's
    transpose, all float32. The loss hardly feels the state's precision
    (the configuration's `rule_why`); these do."""
    import jax

    from paddle_tpu.models.mla_moe import first_rule

    @jax.jit
    def both(params, tokens, cotangent):
        o, back = jax.vjp(lambda x: first_rule(params, x, c),
                          params["wte"][tokens])
        return o, back(cotangent.astype(o.dtype))[0]

    return tuple(off(g, w) for g, w in zip(
        both(params, tokens, cotangent), want))


def compare(program: dict, reference: dict, tolerance: dict) -> list:
    """Problems found, empty when the program agrees with the reference:
    the step-0 loss and its fall after the first update and the biases'
    first move by `check.compare_losses`, the rule alone against the
    recurrence forward and backward (`rule_offs`), and a bias update that
    moved nothing."""
    from benchmarks import check
    problems = check.compare_losses(
        (program["loss0"], program["loss1"]),
        (reference["loss0"], reference["loss1"]), tolerance)
    for key, limit, what in (
            ("rule_off", "rule", "chunked rule"),
            ("rule_back_off", "rule_back", "chunked rule's gradient")):
        if not program[key] <= tolerance[limit]:
            problems.append(
                f"the {what} is {program[key]} off the recurrence's, "
                f"relative: more than {tolerance[limit]}")
    if reference["bias_moved_share"] and not program["bias_moved_share"]:
        problems.append("no selection bias moved in the first step; the "
                        f"reference moved {reference['bias_moved_share']} "
                        "of them")
    return problems


def set_up(cell, seed: int, devices, phases) -> Program:
    """`runners/mla_moe_mtp.set_up`'s order: compile on shapes, the
    reference's answer before the state takes its room, the state in one
    compiled call, two steps on the check batch, then the configuration's
    `balance_steps` moves of the biases alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.layer_metrics import _linear_attn, _moe
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
    linear = linear_attention_of(cell, step.as_text())
    if linear["scan_steps"] is None:
        problems.append(
            "the compiled step's loops under the linear-attention stage "
            "that carry the rule's float32 state are missing or disagree "
            "on their trip count")

    ring = generator.make_ring(traffic, vocab, seed)
    two, tiled = generator.make_check_batch(traffic, vocab, seed)
    seed = np.int32(seed)
    to_f32 = jax.jit(lambda s: jax.tree_util.tree_map(
        lambda a: a.astype("float32"), init_params(s)))
    fingerprint = jax.jit(lambda tree: jnp.stack(
        [jnp.abs(a).sum() for a in jax.tree_util.tree_leaves(tree)]))
    start = to_f32(seed)
    started_from = np.asarray(fingerprint(start))
    cotangent = rule_cotangent(seed, cell)
    # before `check_step`, which is given the masters to keep
    rule = cell.reference.check_rule(start, two[0], cotangent, config)
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

    rule_off, rule_back_off = rule_offs(
        state["params"], two[0], cotangent, c, rule)
    del rule
    before = facts_of(state)
    state, loss0 = step(state, *put(tiled))
    after = facts_of(state)         # before the next step takes the state
    state, loss1 = step(state, *put(tiled))
    answer = {"loss0": float(loss0), "loss1": float(loss1),
              "rule_off": rule_off, "rule_back_off": rule_back_off,
              "bias_moved_share": float(
                  (after["biases"] != before["biases"]).mean()),
              "bias_as_reference_share": float(
                  (after["biases"] == reference["biases1"]).mean()),
              "pairs_off": pairs_off(before["pairs"], reference["pairs0"])}
    reference = {k: v for k, v in reference.items()
                 if k not in ("biases1", "pairs0")}
    problems += compare(answer, reference, config["tolerance"])
    phases.end("two_check_steps")

    # the load the timed steps see is the one the biases have balanced
    balance = jax.jit(functools.partial(move_biases_only, config=c),
                      donate_argnums=(0,))
    for i in range(config["balance_steps"]):
        state = balance(state, *put(ring[i % len(ring)]))
    warm = facts_of(state, ring[0])
    phases.end("balance_steps")

    pairs = warm["pairs"]                           # [layers, all experts]
    first = config["deployment"]["experts_first"]
    held = pairs[:, first:first + config["num_experts"]]
    tokens = int(ring[0][0].size)
    sent = tokens * config["num_experts_per_token"]
    balanced = sent / pairs.shape[1]
    return Program(
        step=step, state=state, ring=ring, put=put, unit="tokens",
        units_per_step=traffic["batch"] * traffic["seq"],
        flops_per_unit=flops_per_token(cell), problems=problems,
        memory=memory_of(step), hlo_text=step.as_text,
        facts={"reference": reference, "program": answer,
               "attention": attention(cell),
               # under the program's own name for the stage
               _moe.stage_name(_linear_attn.STAGE): linear,
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
