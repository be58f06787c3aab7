#!/usr/bin/env python3
"""Record the small real trace that tests/benchmark reads: a few steps of a
tiny scan of (matmul, flash attention) layers on whatever TPU chips this
process has, through the harness's own loop and spans.

    chiprun --chips 1 -- python3 benchmarks/testdata/record_trace.py

writes `chiprun_out/recorded_<n>chip.xplane.pb.gz` and the compiled step's
HLO text beside it; copy both into benchmarks/testdata/ to replace the
fixture. With four chips the batch is split over a `dp` mesh, so the trace
also holds the gradient all-reduce.
"""
from __future__ import annotations

import gzip
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

LAYERS, BATCH, SEQ, HEADS, HEAD_DIM = 2, 8, 256, 4, 64
STEPS, SYNC_EVERY = 4, 2


def make_step(devices):
    """(jitted step, sharding of the weights, sharding of a batch)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from paddle_tpu.ops.pallas.flash_attention import (mha_forward,
                                                       mha_sharded)
    mesh = Mesh(np.asarray(devices), ("dp",)) if len(devices) > 1 else None

    def loss_fn(w, x):
        def layer(h, wl):
            h = jnp.tanh(h @ wl)
            q = h.reshape(h.shape[0], SEQ, HEADS, HEAD_DIM).transpose(
                0, 2, 1, 3)
            a = mha_sharded(q, q, q, mesh, causal=True) if mesh is not None \
                else mha_forward(q, q, q, causal=True)
            return h + a.transpose(0, 2, 1, 3).reshape(h.shape), None
        h, _ = jax.lax.scan(layer, x, w)
        return (h.astype(jnp.float32) ** 2).mean()

    def step_fn(w, x, _second_batch_array):
        loss, g = jax.value_and_grad(loss_fn)(w, x)
        return w - (0.01 * g).astype(w.dtype), loss

    one = SingleDeviceSharding(devices[0])
    w_sh = NamedSharding(mesh, P()) if mesh else one
    x_sh = NamedSharding(mesh, P("dp")) if mesh else one
    return jax.jit(step_fn, in_shardings=(w_sh, x_sh, x_sh),
                   out_shardings=(w_sh, w_sh), donate_argnums=(0,)), w_sh, x_sh


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import run
    from benchmarks.runners import Program
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_trace.py: no TPU", file=sys.stderr)
        return 2
    n = len(devices)
    hidden = HEADS * HEAD_DIM
    jitted, w_sh, x_sh = make_step(devices)
    rng = np.random.default_rng(0)
    w = jax.device_put(jnp.asarray(
        rng.normal(0, 0.05, (LAYERS, hidden, hidden)), jnp.bfloat16), w_sh)
    ring = [tuple(jnp.asarray(rng.normal(0, 1, (BATCH, SEQ, hidden)),
                              jnp.bfloat16) for _ in range(2))
            for _ in range(2)]
    step = jitted.lower(w, *ring[0]).compile()
    w, _ = step(w, *[jax.device_put(a, x_sh) for a in ring[0]])     # warm

    name = f"recorded_{n}chip"
    program = Program(
        step=step, state=w, ring=ring,
        put=lambda batch: [jax.device_put(a, x_sh) for a in batch],
        unit="rows", units_per_step=BATCH, flops_per_unit=0.0, problems=[])
    (steps, _, losses), xplane = run.traced_window(
        name, {"sync_every": SYNC_EVERY, "trace_steps": STEPS}, program)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(xplane, "rb") as src, gzip.open(
            os.path.join(out, f"{name}.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(out, f"{name}.hlo.txt"), "w") as f:
        f.write(step.as_text())
    print(f"{steps} steps, losses {losses}, {os.path.getsize(xplane)} bytes "
          f"of trace from {n} chip(s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
