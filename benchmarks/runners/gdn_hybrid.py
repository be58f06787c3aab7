"""Runner of the dense hybrid of Gated DeltaNet linear-attention layers and
position-free, q/k-normed full-attention layers in Olmo's post-norm block,
with one chip's share of a ZeRO-1 optimizer state: the system under test is
`paddle_tpu.models.llama.build_train_step` with `opt_share` (forward,
backward and AdamW on the held share in one XLA program,
`models/trainer.py`), in `runners/kda_mla_moe.py`'s order of set-up and
with its check of the rule alone against the token recurrence.

What is this runner's own: the configuration gives a deployment in which
`count` data-parallel chips share each pipeline stage's optimizer state
(`deployment.opt_share` = [index, count]), so the reference updates the
same held slices, the masters the program holds are checked to be those
slices, and the first step is checked to have moved the held share of the
parameters and nothing outside it; the lowered step must hold the rule's
Mosaic kernels (the heads reach them through exact zero padding,
`ops/linear_attention.lane_widths`) and, from the compiled step, the scan
over chunks that carries a float32 state at those lane widths
(`linear_attention_of`)."""
from __future__ import annotations

import functools
import re

from benchmarks import flops_gdn_hybrid, generator
from benchmarks.runners import Program, _trainer, memory_of
from benchmarks.runners.kda_mla_moe import _BOUND, _COMPUTATION, _WHILE, off

KINDS = {"linear_attention": "linear", "full_attention": "full"}
LANES = 128         # what the rule's kernels take a head's widths in


def program_config(config: dict):
    from paddle_tpu.models.llama import LlamaConfig
    assert config["hidden_act"] == "silu" and not config["attention_bias"] \
        and not config["tie_word_embeddings"] \
        and config["rope_parameters"]["rope_theta"] is None \
        and config["linear_num_value_heads"] \
        == config["linear_num_key_heads"], \
        "the family has SwiGLU, no bias, an untied head, no rotary, and " \
        "as many value heads as key heads"
    return LlamaConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=None,
        layer_types=tuple(config["layer_types"]),
        linear_num_heads=config["linear_num_key_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=config["linear_allow_neg_eigval"],
        post_norm=True, qk_norm=True,
        initializer_range=config["initializer_range"],
        dtype=config["dtype"])


def build(config: dict, mesh, layout: dict):
    """(init_fn, step, init_params), as `runners/_trainer.py` asks."""
    from paddle_tpu.models.llama import build_train_step, init_llama_params
    c = program_config(config)
    opt = config["optimizer"]
    init_fn, step = build_train_step(
        c, mesh, lr=opt["lr"], wd=opt["wd"], b1=opt["b1"], b2=opt["b2"],
        remat=config["remat"],
        opt_share=tuple(config["deployment"]["opt_share"]))
    return init_fn, step, functools.partial(init_llama_params, c)


def attention(cell) -> dict:
    """One chip's softmax-attention problem, the full layers': MHA, heads
    of hidden / heads."""
    config = cell.config
    heads = config["num_attention_heads"]
    return _trainer.attention_of(cell, heads,
                                 config["hidden_size"] // heads, causal=True)


def shapes(cell) -> dict:
    """What `flops_gdn_hybrid` counts from."""
    config = cell.config
    kinds = [KINDS[k] for k in config["layer_types"]]
    return dict(
        hidden=config["hidden_size"], ffn=config["intermediate_size"],
        linear_layers=kinds.count("linear"),
        linear_heads=config["linear_num_key_heads"],
        key_dim=config["linear_key_head_dim"],
        value_dim=config["linear_value_head_dim"],
        taps=config["linear_conv_kernel_dim"],
        full_layers=kinds.count("full"), vocab=config["vocab_size"],
        seq=cell.traffic["seq"])


def flops_per_token(cell) -> float:
    return flops_gdn_hybrid.train_flops_per_token(**shapes(cell))


def lane_widths(d_k: int, d_v: int) -> tuple:
    """The widths the rule's kernels take heads of d_k and d_v in: each
    rounded up to whole lanes where the zeros are at most a third of it
    (`ops/linear_attention.lane_widths`, which the program decides by)."""
    def rounded(d):
        up = -(-d // LANES) * LANES
        return up if 2 * up <= 3 * d else d

    return rounded(d_k), rounded(d_v)


def state_loops(hlo_text: str, stage: str, heads: int, d_k: int, d_v: int):
    """[(trips, rows)] of the `while` loops of a compiled step that stand
    under `stage` and carry a float32 [rows, heads, d_k, d_v] state: the
    trips are the loop's bound, the largest integer constant of its
    condition (None where it has none). `runners/kda_mla_moe.state_loops`
    at two widths."""
    state = re.compile(rf"f32\[(\d+),{heads},{d_k},{d_v}\]")
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
    carry the rule's state at the kernels' lane widths (`state_loops`: the
    scan over chunks, forward, repeated and backward): their one trip
    count, S / C, the chunk that follows from it and the bytes of the
    carried state; beside them the layers of each kind, the heads and
    the published and lane widths. Loops that disagree, or none, read
    nothing."""
    from benchmarks.layer_metrics import _linear_attn, _moe
    stage = _moe.stage_name(_linear_attn.STAGE)
    config, seq = cell.config, cell.traffic["seq"]
    kinds = [KINDS[k] for k in config["layer_types"]]
    heads = config["linear_num_key_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    lanes = lane_widths(dk, dv)
    loops = set(state_loops(hlo_text, stage, heads, *lanes)) if stage \
        else set()
    steps, rows = loops.pop() if len(loops) == 1 else (None, None)
    batch = cell.traffic["batch"] // cell.mesh_shape.get("dp", 1)
    return {"layers": {"linear": kinds.count("linear"),
                       "full": kinds.count("full")},
            "scan_steps": steps, "chunk": seq // steps if steps else None,
            "heads": heads, "key_dim": dk, "value_dim": dv,
            "lane_widths": list(lanes), "tokens": batch * seq,
            "state_bytes": rows * heads * lanes[0] * lanes[1] * 4
            if rows else None}


def float32_of(init_params):
    """seed -> the parameters in float32 at exactly their values, jitted: a
    float32 -> bfloat16 -> float32 round trip is a pair of converts that
    the TPU's compiler may drop, which would hand the reference the
    unrounded draw (`reference/mla_moe_mtp.round_through` rounds on the
    bits). The program's held masters start at these values."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference.mla_moe_mtp import round_through

    def exact(a):
        wide = a.astype(jnp.float32)
        return wide if a.dtype == jnp.float32 else round_through(wide,
                                                                 a.dtype)

    return jax.jit(lambda s: jax.tree_util.tree_map(exact, init_params(s)))


def lowered_problems(cell, lowered) -> list:
    """The flash kernels without a fallback (`_trainer.flash_problems`),
    and the rule's two Mosaic kernels, forward and backward."""
    text = lowered.as_text()
    problems = _trainer.flash_problems(cell, lowered)
    for body in ("_terms_fwd", "_terms_bwd"):
        if f'kernel_name = "{body}"' not in text:
            problems.append(f"the rule's kernel {body} is not in the "
                            "lowered step")
    return problems


def rule_cotangent(seed, cell):
    """What the rule's output is weighed by for its gradient: seeded
    normal values [2, S, H, d_v] in the configuration's dtype, so that
    program and reference are handed the same numbers."""
    import jax
    config = cell.config
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (2, cell.traffic["seq"], config["linear_num_key_heads"],
         config["linear_value_head_dim"]), config["dtype"])


def rule_offs(params, tokens, cotangent, c, want) -> tuple:
    """How far the chunked rule, as the model's first layer runs it on
    `tokens` [rows, S] at the timed widths (`llama.layer_rule`), is from
    the recurrence it stands for, forward and backward: (`off` of o, `off`
    of the gradient of sum(o * cotangent) to the layer's input) against
    `want`, the reference's `check_rule` from the same weights."""
    import jax

    from paddle_tpu.models.llama import layer_rule

    @jax.jit
    def both(params, tokens, cotangent):
        o, back = jax.vjp(lambda x: layer_rule(params, x, 0, c),
                          params["wte"][tokens])
        return o, back(cotangent.astype(o.dtype))[0]

    return tuple(off(g, w) for g, w in zip(
        both(params, tokens, cotangent), want))


def share_moved(before, after, config: dict) -> dict:
    """Of the parameters before and after a step (bfloat16 trees alike):
    the share of the HELD elements that changed, and the share of the
    elements OUTSIDE the held slices that did, by the reference's own
    slicing (`reference/gdn_hybrid.held`)."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import gdn_hybrid as ref

    leaves = jax.tree_util.tree_leaves

    def changed(before, after):
        return sum((a != b).sum(dtype=jnp.int32)
                   for a, b in zip(leaves(before), leaves(after)))

    @jax.jit
    def counts(before, after):
        return changed(ref.held(before, config), ref.held(after, config)), \
            changed(before, after)

    inside, everywhere = (int(n) for n in counts(before, after))
    held = sum(a.size for a in leaves(jax.eval_shape(
        lambda tree: ref.held(tree, config), before)))
    whole = sum(a.size for a in leaves(before))
    return {"held": inside / held,
            "unheld": (everywhere - inside) / max(whole - held, 1)}


def compare(program: dict, reference: dict, tolerance: dict) -> list:
    """Problems found, empty when the program agrees with the reference:
    the step-0 loss and its fall after the first update of the held share
    by `check.compare_losses`, the rule alone against the recurrence
    forward and backward (`rule_offs`), and a first step that moved no
    held element or any element outside the held share."""
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
    moved = program["moved"]
    if not moved["held"] > 0:
        problems.append("the first step moved no held parameter")
    if moved["unheld"] != 0:
        problems.append(f"the first step moved {moved['unheld']} of the "
                        "parameters outside the held share")
    return problems


def set_up(cell, seed: int, devices, phases) -> Program:
    """`runners/kda_mla_moe.set_up`'s order: compile on shapes, the
    reference's answer before the state takes its room, the state in one
    compiled call, the rule alone, two steps on the check batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    config, traffic = cell.config, cell.traffic
    vocab = config["vocab_size"]
    # a seed may pass 2**31, and the device takes an int32
    seed %= 1 << 31

    lowered, init_fn, init_params = _trainer.lower_step(cell, devices)
    phases.end("trace_and_lower")
    on_chip = devices[0].platform == "tpu"
    problems = lowered_problems(cell, lowered) if on_chip else []
    step = lowered.compile()
    phases.end("compile_or_load_step")
    (state_sharding, *batch_shardings), _ = step.input_shardings
    linear = linear_attention_of(cell, step.as_text())
    if linear["scan_steps"] is None:
        problems.append(
            "the compiled step's loops under the linear-attention stage "
            "that carry the rule's float32 state at the lane widths are "
            "missing or disagree on their trip count")

    ring = generator.make_ring(traffic, vocab, seed)
    two, tiled = generator.make_check_batch(traffic, vocab, seed)
    seed = np.int32(seed)
    to_f32 = float32_of(init_params)
    fingerprint = jax.jit(lambda tree: jnp.stack(
        [jnp.abs(a).sum() for a in jax.tree_util.tree_leaves(tree)]))
    start = to_f32(seed)
    started_from = np.asarray(fingerprint(start))
    held_from = np.asarray(fingerprint(cell.reference.held(start, config)))
    cotangent = rule_cotangent(seed, cell)
    # before `check_step`, which is given the masters to keep
    rule = cell.reference.check_rule(start, two[0], cotangent, config)
    reference = cell.reference.check_step(start, two, config)
    del start
    phases.end("reference")

    state = jax.jit(init_fn, out_shardings=state_sharding)(seed)
    jax.block_until_ready(state)
    # the reference steps from the parameters and updates the slices it
    # holds: they have to be the program's parameters and held masters
    params_f32 = jax.jit(lambda tree: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), tree))(state["params"])
    same = (np.asarray(fingerprint(params_f32)) == started_from).all()
    del params_f32
    if not (same and (np.asarray(fingerprint(state["master"]))
                      == held_from).all()):
        problems.append("the reference did not start from the program's "
                        "parameters and held float32 masters")
    phases.end("init_state")

    def put(batch):
        return [jax.device_put(a, s) for a, s in zip(batch, batch_shardings)]

    c = program_config(config)
    rule_off, rule_back_off = rule_offs(
        state["params"], two[0], cotangent, c, rule)
    del rule
    # the parameters before the first step, on the host: the step donates
    before = jax.device_get(state["params"])
    state, loss0 = step(state, *put(tiled))
    moved = share_moved(before, state["params"], config)
    del before
    state, loss1 = step(state, *put(tiled))
    answer = {"loss0": float(loss0), "loss1": float(loss1),
              "rule_off": rule_off, "rule_back_off": rule_back_off,
              "moved": moved}
    problems += compare(answer, reference, config["tolerance"])
    phases.end("two_check_steps")

    held_bytes = 3 * 4 * sum(a.size for a in jax.tree_util.tree_leaves(
        state["master"]))
    return Program(
        step=step, state=state, ring=ring, put=put, unit="tokens",
        units_per_step=traffic["batch"] * traffic["seq"],
        flops_per_unit=flops_per_token(cell), problems=problems,
        memory=memory_of(step), hlo_text=step.as_text,
        facts={"reference": reference, "program": answer,
               "attention": attention(cell),
               # under the program's own name for the stage
               _stage_name(): linear,
               "opt_share": {"share": config["deployment"]["opt_share"],
                             "held_state_bytes": held_bytes}})


def _stage_name():
    from benchmarks.layer_metrics import _linear_attn, _moe
    return _moe.stage_name(_linear_attn.STAGE)
