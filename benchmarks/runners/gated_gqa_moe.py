"""Runner of the decoder family with gated grouped-query attention of two
head counts, window and full layers mixed, partial rotary, a leading dense
layer, a shared expert and a sigmoid router with a selection bias: the
system under test is `paddle_tpu.models.llama.build_train_step` (forward,
backward, AdamW and the biases' move in one XLA program,
`models/trainer.py`), as in `runners/window_gqa_moe.py`, whose conventions
for a chip's share (`published`, `deployment`) this file keeps, and with
`runners/kda_mla_moe.py`'s order of set-up, comparison of the biases and
balancing of the load by the program's own bias rule.

What is this runner's own: the configuration gives the query heads of each
layer (`num_attention_heads_per_layer`), so a kind's heads are read from its
layers, the attention facts name each kind's heads, K/V heads and window
(what `layer_metrics/_mixed_gqa_flash.py` reads), the lowered step must
hold no K or V repeated to either kind's query heads, and the first layer of
each kind has its attention sub-layer checked alone against the reference,
forward and backward (`attention_offs`): the loss of random weights hardly
feels the gate or the partial rotary, which this configuration adds."""
from __future__ import annotations

import functools
import re

from benchmarks import flops_gated_gqa_moe, generator
from benchmarks.runners import Program, _trainer, memory_of
from benchmarks.runners.mla_moe_mtp import pairs_off
from benchmarks.runners.window_gqa_moe import routing, tiles

WINDOW = "sliding_attention"


def heads_by_kind(config: dict) -> dict:
    """{kind: query heads} from the per-layer lists; a kind whose layers
    disagree is refused."""
    found = {}
    for kind, heads in zip(config["layer_types"],
                           config["num_attention_heads_per_layer"]):
        if found.setdefault(kind, heads) != heads:
            raise ValueError(f"the {kind} layers have {found[kind]} and "
                             f"{heads} query heads")
    return found


def program_config(config: dict):
    from paddle_tpu.models.llama import LlamaConfig
    kinds = config["mlp_layer_types"]
    dense = kinds.count("dense")
    assert config["gating"] is True and not config["attention_bias"] \
        and not config["tie_word_embeddings"] \
        and not config["moe_apply_router_weight_on_input"] \
        and kinds == ["dense"] * dense + ["sparse"] * (len(kinds) - dense), \
        "the family gates every layer's attention, has no bias and an " \
        "untied head, weighs the experts' outputs, and its dense layers lead"
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
        initializer_range=config["initializer_range"],
        dtype=config["dtype"], heads_by_kind=heads_by_kind(config),
        gating=True, first_k_dense=dense,
        shared_expert_intermediate_size=config[
            "shared_expert_intermediate_size"],
        router="sigmoid",
        moe_routed_scaling_factor=config["moe_routed_scaling_factor"],
        router_bias_update_rate=config["router_bias_update_rate"])


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
    """One chip's attention problem: `heads` is the published count (the
    full layers'), and `kinds` says for each kind of layer its query heads,
    K/V heads, window and layers, which is what a reader of the kernels
    takes (`layer_metrics/_mixed_gqa_flash.py`)."""
    config = cell.config
    found = _trainer.attention_of(cell, config["num_attention_heads"],
                                  config["head_dim"], causal=True)
    kv_heads = config["num_key_value_heads"]
    found["kinds"] = {
        kind: {"heads": heads, "kv_heads": kv_heads,
               "window": config["sliding_window"] if kind == WINDOW
               else None,
               "layers": config["layer_types"].count(kind)}
        for kind, heads in sorted(heads_by_kind(config).items())}
    return found


def shapes(cell) -> dict:
    """What `flops_gated_gqa_moe` counts from (and `hidden`, `held`,
    `expert_ffn` as `layer_metrics/_moe.py` finds the grouped products)."""
    config = cell.config
    return dict(
        hidden=config["hidden_size"], head_dim=config["head_dim"],
        kv_heads=config["num_key_value_heads"],
        layer_types=tuple(config["layer_types"]),
        layer_heads=tuple(config["num_attention_heads_per_layer"]),
        mlp_layer_types=tuple(config["mlp_layer_types"]),
        window=config["sliding_window"], gated=config["gating"],
        dense_ffn=config["intermediate_size"],
        expert_ffn=config["moe_intermediate_size"],
        shared_ffn=config["shared_expert_intermediate_size"],
        router_outputs=config["published"]["num_experts"],
        held=config["num_experts"], k=config["num_experts_per_tok"],
        vocab=config["vocab_size"], seq=cell.traffic["seq"])


def flops_per_token(cell) -> float:
    return flops_gated_gqa_moe.train_flops_per_token(**shapes(cell))


def lowered_problems(cell, lowered) -> list:
    """No Mosaic call, a [.., S, S] tensor under any leading shape of rank
    4 or more (q itself is [B, S, H d], and H d may equal S), or K or V
    broadcast to either kind's query heads, [B, S, H_kv, rep, d]."""
    text = lowered.as_text()
    a = attention(cell)
    problems = [] if "tpu_custom_call" in text else [
        "no tpu_custom_call in the lowered step"]
    if re.search(rf"\dx\d+x{a['seq']}x{a['seq']}x", text):
        problems.append(f"a [.., {a['seq']}, {a['seq']}] tensor is lowered")
    for kind in a["kinds"].values():
        repeated = (f"{a['batch']}x{a['seq']}x{kind['kv_heads']}x"
                    f"{kind['heads'] // kind['kv_heads']}x{a['head_dim']}x")
        if repeated in text:
            problems.append(f"K or V is repeated to the query heads: "
                            f"tensor<{repeated}..>")
    return problems


def checked_layers(config: dict) -> dict:
    """{kind: the first layer of that kind}: the layers whose attention
    sub-layers are checked alone, one of each kind."""
    kinds = config["layer_types"]
    return {kind: kinds.index(kind) for kind in sorted(set(kinds))}


def attention_cotangents(seed: int, cell) -> dict:
    """{layer: [2, S, H_n d]} for `checked_layers`, seeded normal values in
    the configuration's dtype, so that program and reference are handed
    the same numbers."""
    import jax
    config = cell.config
    return {n: jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(seed), n),
        (2, cell.traffic["seq"], config["num_attention_heads_per_layer"][n]
         * config["head_dim"]), config["dtype"])
        for n in checked_layers(config).values()}


def attention_offs(params, tokens, cotangents: dict, c, want: dict) -> dict:
    """How far each checked layer's attention sub-layer, as the timed step
    runs it (`llama.layer_attention`: the kernels, the partial rotary, the
    gate) on the embedding's rows of `tokens` [rows, S], is from the
    reference's, forward and backward: {layer: [`off` of o, `off` of the
    gradient of sum(o * cotangent) to the layer's input]} against `want`,
    the reference's `check_attention` from the same weights, float32. The
    loss of random weights hardly feels the positions or the gate (a
    wrong partial rotary or a lost gate moves it by less than the
    precision does); these do."""
    import jax

    from benchmarks.runners.kda_mla_moe import off
    from paddle_tpu.models.llama import layer_attention

    @functools.partial(jax.jit, static_argnums=3)
    def both(params, tokens, cotangent, n):
        o, back = jax.vjp(lambda x: layer_attention(params, x, n, c),
                          params["wte"][tokens])
        return o, back(cotangent.astype(o.dtype))[0]

    return {n: [off(g, w) for g, w in zip(
        both(params, tokens, cotangents[n], n), want[n])] for n in want}


def compare(program: dict, reference: dict, tolerance: dict) -> list:
    """Problems found, empty when the program agrees with the reference:
    the step-0 loss and its fall after the first update and the biases'
    first move by `check.compare_losses`, each checked layer's attention
    alone against the reference's forward and backward
    (`attention_offs`), and a bias update that moved nothing."""
    from benchmarks import check
    problems = check.compare_losses(
        (program["loss0"], program["loss1"]),
        (reference["loss0"], reference["loss1"]), tolerance)
    for n, offs in sorted(program["attention_offs"].items()):
        for found, limit, what in zip(offs, ("attention", "attention_back"),
                                      ("output", "gradient")):
            if not found <= tolerance[limit]:
                problems.append(
                    f"layer {n}'s attention {what} is {found} off the "
                    f"reference's, relative: more than {tolerance[limit]}")
    if reference["bias_moved_share"] and not program["bias_moved_share"]:
        problems.append("no selection bias moved in the first step; the "
                        f"reference moved {reference['bias_moved_share']} "
                        "of them")
    return problems


def set_up(cell, seed: int, devices, phases) -> Program:
    """`runners/kda_mla_moe.set_up`'s order: compile on shapes, the
    reference's answer before the state takes its room, the state in one
    compiled call, two steps on the check batch, then the configuration's
    `balance_steps` moves of the biases alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.llama import move_biases_only, step_facts
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
    cotangents = attention_cotangents(seed, cell)
    # before `check_step`, which is given the masters to keep
    alone = jax.device_get(cell.reference.check_attention(
        start, two[0], cotangents, config))
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

    offs = attention_offs(state["params"], two[0], cotangents, c, alone)
    del alone
    before = facts_of(state)
    state, loss0 = step(state, *put(tiled))
    after = facts_of(state)         # before the next step takes the state
    state, loss1 = step(state, *put(tiled))
    answer = {"loss0": float(loss0), "loss1": float(loss1),
              "attention_offs": offs,
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
    tokens = int(ring[0][0].size)
    cold = routing(facts_of(state, ring[0])["pairs"], config, tokens)
    balance = jax.jit(functools.partial(move_biases_only, config=c),
                      donate_argnums=(0,))
    for i in range(config["balance_steps"]):
        state = balance(state, *put(ring[i % len(ring)]))
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
                           bias_moved_share=answer["bias_moved_share"],
                           bias_max_abs=float(np.abs(warm["biases"]).max()),
                           before_balance_steps=cold, shapes=shapes(cell))})
