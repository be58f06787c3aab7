#!/usr/bin/env python3
"""Compile every program a cell runs, at its real size, for a described
`v5e:2x2`, here in the sandbox: no chip, no chip time.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_check.py [cell ...]

Prints one JSON line per cell: bytes per device of the step (arguments,
outputs, temp, aliased; their balance is what `hbm_peak_gb` reports on the
chip), Mosaic calls and collectives in the compiled step, and the bytes of
the set-up programs (state init, float32 reference). What libtpu's compiler
refuses here it refuses on the chip. A compile is not a run: it says nothing
of results or times.
"""
from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def count_ops(hlo_text: str) -> dict:
    """Mosaic calls and collectives among the compiled HLO's instructions
    (an asynchronous pair counts once, at its -start)."""
    counts = {"tpu_custom_call": hlo_text.count(
        'custom_call_target="tpu_custom_call"')}
    for kind in COLLECTIVES:
        counts[kind] = len(re.findall(
            rf" {kind}(?:-start)?\(", hlo_text))
    return counts


def check_cell(name: str, devices) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.cells import load_cell
    from benchmarks.runners import _trainer, memory_of
    cell = load_cell(name)
    if not hasattr(cell.runner, "build"):
        return {"cell": name, "skipped": "its runner compiles no step of "
                "models/trainer.py (runners/_trainer.py)"}
    lowered, init_fn, init_params = _trainer.lower_step(cell, devices)
    problems = _trainer.flash_problems(cell, lowered)
    compiled = lowered.compile()
    row = {"cell": name, "chips": cell.chips, "step": memory_of(compiled),
           "ops": count_ops(compiled.as_text()),
           "flash_problems": problems}

    one = SingleDeviceSharding(devices[0])
    platform = (devices[0].platform,)
    # on a mesh the seed lives wherever the state's shardings put it
    seed = jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=None if cell.layout else one)
    init = jax.jit(init_fn, out_shardings=compiled.input_shardings[0][0])
    row["init"] = memory_of(init.trace(seed).lower(
        lowering_platforms=platform).compile())

    # the float32 reference, one sequence on chip 0 (check.reference_losses)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32, sharding=one),
        jax.eval_shape(lambda: init_params(0)))
    seq = jax.ShapeDtypeStruct((cell.traffic["seq"],), jnp.int32,
                               sharding=one)

    def nll(p, t, l):
        return cell.reference.nll(p, t, l, cell.config)

    with jax.default_matmul_precision("highest"):
        fn = nll if cell.config["reference_check"] == "loss" \
            else jax.value_and_grad(nll, has_aux=True)
        row["reference"] = memory_of(jax.jit(fn).trace(params, seq, seq)
                                     .lower(lowering_platforms=platform)
                                     .compile())
    return row


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    from paddle_tpu._core import device
    # the package's one backend predicate: open its TPU gates (flash, Mosaic
    # instead of the interpreter) for a compile that targets the chip
    device.is_tpu = lambda: True
    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    names = (argv if argv is not None else sys.argv[1:]) or sorted(
        n[:-5] for n in os.listdir(os.path.join(ROOT, "benchmarks",
                                                "workloads")))
    bad = 0
    for name in names:
        row = check_cell(name, devices)
        row["device_kind"] = devices[0].device_kind
        print(json.dumps(row), flush=True)
        bad += bool(row.get("flash_problems"))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
