"""What the gpt and bert runners share: a step that `models/trainer.py`
compiles into one XLA program (forward, backward, AdamW), fed batches of
token ids and labels from `benchmarks/generator.py`.

A family's runner is then three functions and `set_up = _trainer.set_up`:

    build(config, mesh, layout) -> (init_fn, step, init_params)
        `init_fn(seed)` builds the whole train state, `step(state, tokens,
        labels)` is the jitted program, `init_params(seed)` the parameters
        alone, for the reference
    attention(cell) -> `attention_of(...)`: one chip's attention problem
    flops_per_token(cell) -> required FLOPs (`benchmarks/flops.py`)
"""
from __future__ import annotations

from benchmarks import generator
from benchmarks.runners import Program, memory_of

# `benchmarks.check` imports JAX, which a runner's import must not do: the
# harness loads the cell before it decides which platform JAX may see.


def mesh_for(cell, devices):
    """The cell's mesh over the first `chips` of `devices`; None on one."""
    if not cell.layout:
        return None
    import numpy as np
    from jax.sharding import Mesh
    axes = cell.mesh_shape
    return Mesh(np.asarray(devices[:cell.chips]).reshape(tuple(axes.values())),
                tuple(axes))


def lower_step(cell, devices):
    """(lowered step, init_fn, init_params): the cell's step traced for
    `devices` on shapes alone, so that it compiles before any state exists
    (and, in aot_check.py, for chips that are described and not attached)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    mesh = mesh_for(cell, devices)
    init_fn, step, init_params = cell.runner.build(cell.config, mesh,
                                                   cell.layout)
    # on a mesh the jitted step carries its own shardings
    where = SingleDeviceSharding(devices[0]) if mesh is None else None
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where),
        jax.eval_shape(lambda: init_fn(0)))
    tokens = jax.ShapeDtypeStruct(
        (cell.traffic["batch"], cell.traffic["seq"]), jnp.int32,
        sharding=where)
    lowered = step.trace(state, tokens, tokens).lower(
        lowering_platforms=(devices[0].platform,))
    return lowered, init_fn, init_params


def attention_of(cell, heads: int, head_dim: int, causal: bool) -> dict:
    """One chip's attention problem: the rows and heads it holds of the
    global batch (inside a shard_map the shapes are one chip's)."""
    mesh = cell.mesh_shape
    return {"batch": cell.traffic["batch"] // mesh.get("dp", 1),
            "heads": heads // mesh.get("mp", 1), "seq": cell.traffic["seq"],
            "head_dim": head_dim, "causal": causal,
            "global_batch": cell.traffic["batch"], "global_heads": heads}


def flash_problems(cell, lowered) -> list:
    """A configuration that says `"attention": "flash"` is served by the
    Mosaic kernels; a step lowered without them fell back without saying."""
    if cell.config.get("attention") != "flash":
        return []
    from benchmarks import check
    attention = cell.runner.attention(cell)
    return check.flash_fallback_problems(
        lowered.as_text(), attention["seq"],
        {(attention["global_batch"], attention["global_heads"]),
         (attention["batch"], attention["heads"])})


def set_up(cell, seed: int, devices, phases) -> Program:
    """Compile the step on shapes, run the plain float32 reference on two
    seeded sequences, make the train state on the device in one compiled
    call from the seed, and step twice on the two sequences tiled to the
    cell's batch: the program's answer to the reference, and the warm-up of
    the only shapes the window uses."""
    import jax
    import numpy as np

    from benchmarks import check
    config, traffic = cell.config, cell.traffic
    vocab = config["vocab_size"]

    # the step executable, from shapes alone
    lowered, init_fn, init_params = lower_step(cell, devices)
    phases.end("trace_and_lower")
    on_chip = devices[0].platform == "tpu"
    problems = flash_problems(cell, lowered) if on_chip else []
    step = lowered.compile()
    phases.end("compile_or_load_step")
    (state_sharding, *batch_shardings), _ = step.input_shardings

    # the reference's answer, before the state takes its room on the chip
    ring = generator.make_ring(traffic, vocab, seed)
    two, tiled = generator.make_check_batch(traffic, vocab, seed)
    seed = np.int32(seed)
    to_f32 = jax.jit(lambda s: jax.tree_util.tree_map(
        lambda a: a.astype("float32"), init_params(s)))
    reference = check.reference_losses(cell.reference, to_f32(seed), two,
                                       config)
    phases.end("reference")

    # the state in one compiled call from the seed, in the cell's layout
    state = jax.jit(init_fn, out_shardings=state_sharding)(seed)
    jax.block_until_ready(state)
    phases.end("init_state")

    def put(batch):
        return [jax.device_put(a, s) for a, s in zip(batch, batch_shardings)]

    state, loss0 = step(state, *put(tiled))
    state, loss1 = step(state, *put(tiled))
    answer = (float(loss0), float(loss1))
    problems += check.compare_losses(answer, reference, config["tolerance"])
    phases.end("two_check_steps")
    return Program(
        step=step, state=state, ring=ring, put=put, unit="tokens",
        units_per_step=traffic["batch"] * traffic["seq"],
        flops_per_unit=cell.runner.flops_per_token(cell), problems=problems,
        memory=memory_of(step), hlo_text=step.as_text,
        facts={"reference": reference, "program": answer,
               "attention": cell.runner.attention(cell)})
