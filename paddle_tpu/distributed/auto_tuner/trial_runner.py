"""Trial-job runner: measure real step times for candidate parallel
configs. The trial is a pjit'd mini training step on the actual device
mesh — the same SPMD program shape the full job would compile.

Where the trial runs follows from who can open the devices. A TPU chip
belongs to one process at a time, and a tuner that has counted
`jax.devices()` holds every chip of the host, so on a TPU the trial runs
in the tuner's process. Off a TPU each trial is its own subprocess, like
the reference's auto_tuner (which launches trial JOBS and reads their
timings), so a compiler abort takes down the trial and not the tuner.

+inf is a COST: the config does not fit this device set. A trial that
produced no timing for any other reason (crashed, timed out, could not
start) raises `TrialLaunchError`; it is never reported as a cost.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, Optional

import numpy as np


class TrialLaunchError(RuntimeError):
    """A trial process ended without reporting a step time."""


def measure_step_time(config: Dict, steps: int = 5, warmup: int = 2,
                      timeout: float = 300.0) -> float:
    """Seconds/step of one trial job; +inf when the config does not fit
    the device set."""
    from ..._core import device
    if device.is_tpu():
        return _measure_in_process(config, steps=steps, warmup=warmup)
    payload = dict(config, _steps=steps, _warmup=warmup)
    env = dict(os.environ)
    env["PT_TRIAL_CONFIG"] = json.dumps(payload)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    try:
        proc = subprocess.run(
            [sys.executable, "-m",
             "paddle_tpu.distributed.auto_tuner.trial_runner"],
            env=env, capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired as e:
        raise TrialLaunchError(
            f"trial {config} reported nothing in {timeout:.0f} s") from e
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("PT_TRIAL_SECONDS="):
            return float(line.split("=", 1)[1])
    raise TrialLaunchError(
        f"trial {config} exited {proc.returncode} without a step time:\n"
        + proc.stderr[-2000:])


def _measure_in_process(config: Dict, steps: int = 5,
                        warmup: int = 2) -> float:
    """Build the flagship train step under `config`'s dp/mp/pp degrees
    on the real device set and measure seconds/step. Returns +inf when
    the config does not fit (mesh larger than the device set, out of
    device memory) so the tuner deprioritizes it — the reference's
    failed-trial path. Any other error propagates."""
    import jax

    from ..._core import device
    from ...models.gpt import GPTConfig, build_train_step
    from ..mesh import auto_mesh

    dp = int(config.get("dp_degree", 1))
    mp = int(config.get("mp_degree", 1))
    pp = int(config.get("pp_degree", 1))
    n = dp * mp * pp
    if n > len(jax.devices()):
        return float("inf")
    try:
        # bf16 only on real TPU: XLA:CPU check-fails compiling some
        # sharded bf16 programs (the multichip dryrun avoids it too)
        dtype = "bfloat16" if device.is_tpu() else "float32"
        model_cfg = GPTConfig(
            vocab_size=int(config.get("vocab_size", 8192)),
            hidden_size=int(config.get("hidden_size", 256)),
            num_layers=int(config.get("num_layers", 4)),
            num_heads=int(config.get("num_heads", 8)),
            max_position_embeddings=int(config.get("seq_len", 256)),
            dtype=dtype)
        mesh_axes = [("dp", dp)]
        if pp > 1:
            mesh_axes.append(("pp", pp))
        mesh_axes.append(("mp", mp))
        pm = auto_mesh(*[d for _, d in mesh_axes],
                       dim_names=[nm for nm, _ in mesh_axes])
        mesh = pm.jax_mesh()
        init_fn, step = build_train_step(
            model_cfg, mesh=mesh, lr=1e-4,
            remat=bool(config.get("recompute", True)))
        state = init_fn(0)
        gb = int(config.get("global_batch_size", max(8, dp)))
        seq = int(config.get("seq_len", 256))
        rng = np.random.RandomState(0)
        tokens = np.asarray(rng.randint(0, model_cfg.vocab_size,
                                        (gb, seq)), np.int32)
        labels = np.asarray(rng.randint(0, model_cfg.vocab_size,
                                        (gb, seq)), np.int32)

        def one():
            nonlocal state
            state, loss = step(state, tokens, labels)
            return loss

        for _ in range(warmup):
            jax.block_until_ready(one())
        t0 = time.perf_counter()
        for _ in range(steps):
            one()
        jax.block_until_ready(state)
        return (time.perf_counter() - t0) / steps
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        return float("inf")


def _main():
    cfg = json.loads(os.environ["PT_TRIAL_CONFIG"])
    steps = int(cfg.pop("_steps", 5))
    warmup = int(cfg.pop("_warmup", 2))
    sec = _measure_in_process(cfg, steps=steps, warmup=warmup)
    print(f"PT_TRIAL_SECONDS={sec}", flush=True)


if __name__ == "__main__":
    _main()
