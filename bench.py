"""Benchmark: GPT pretraining tokens/sec/chip on the local TPU.

Flagship = compiled functional trainer (paddle_tpu.models.gpt
build_train_step): full fwd+bwd(+remat)+AdamW fused into one XLA program,
bf16 compute + fp32 master weights.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} for the
headline, plus a "rows" list re-measuring EVERY BASELINE.md row each
round (LeNet eager / ResNet-50 @to_static AMP / BERT-base compiled /
GPT-2-medium), each with "metric", "value" and "unit", so every path is
visible in the recorded JSON instead of hiding behind the single
headline (VERDICT r3 weak #8). A row that raises is recorded with its
error and the run exits 1 after printing. BENCH_EXTRA=0 opts out of the
extra rows (BENCH_ROWS keeps its bench_suite.py row-selector meaning).

Baseline convention (BASELINE.md): the operative target is >=0.8x the
per-chip MFU of an A100+NCCL Megatron-style run (~40% MFU for GPT at this
scale), i.e. target MFU 0.32. vs_baseline = measured_MFU / 0.32, against
the published peak of the device_kind the run is on (an unknown device is
an error: there is no peak to fall back to).
"""
from __future__ import annotations

import json
import os
import sys
import time


def _extra_rows():
    import bench_suite
    rows = []
    for name in ("lenet", "resnet50", "bert"):
        try:  # a broken row must not hide the rest; main() exits 1 on it
            out = getattr(bench_suite, f"bench_{name}")()
        except Exception as e:
            out = {"metric": name, "error": f"{type(e).__name__}: {e}"}
        rows.append(out)
    return rows


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import dataclasses

    from paddle_tpu._core import device
    from paddle_tpu.models.gpt import GPT_CONFIGS, build_train_step

    device.enable_compile_cache()
    peak = device.chip_peaks().flops

    model = os.environ.get("BENCH_MODEL", "gpt2-medium")
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    steps = int(os.environ.get("BENCH_STEPS", "20"))

    config = dataclasses.replace(GPT_CONFIGS[model],
                                 max_position_embeddings=seq)

    init_fn, step = build_train_step(config, mesh=None, lr=1e-4,
                                     remat=True)
    state = init_fn(0)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, config.vocab_size, (batch, seq)),
                         jnp.int32)
    labels = jnp.asarray(rng.randint(0, config.vocab_size, (batch, seq)),
                         jnp.int32)

    # warmup/compile. block_until_ready blocks until the chip is done;
    # the steady-window redesign of this timing is ROADMAP A0.
    state, loss = step(state, tokens, labels)
    jax.block_until_ready(loss)

    t0 = time.time()
    for _ in range(steps):
        state, loss = step(state, tokens, labels)
    jax.block_until_ready(loss)
    dt = (time.time() - t0) / steps

    tokens_per_sec = batch * seq / dt

    # params for MFU: 12*L*h^2 (attn+mlp) + embeddings
    h, L, v = config.hidden_size, config.num_layers, config.vocab_size
    n_params = 12 * L * h * h + v * h + config.max_position_embeddings * h
    # fwd+bwd+remat ~= 6*N*tokens * (1 + remat fwd extra 1/3) -> use 6N
    # plus attention flops: 12*L*s*h per token fwd -> *3 for bwd-ish
    flops_per_token = 6 * n_params + 12 * L * seq * h
    achieved = flops_per_token * tokens_per_sec
    mfu = achieved / peak
    target_mfu = 0.32  # 0.8 x (~0.40 A100+NCCL MFU)

    headline = {
        "metric": f"{model} pretrain tokens/sec/chip (b{batch} s{seq} "
                  f"bf16 remat fused-adamw)",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / target_mfu, 3),
    }
    failed = False
    if os.environ.get("BENCH_EXTRA", "1") != "0":
        gpt_row = {k: headline[k] for k in ("metric", "value", "unit")}
        # free the GPT train state before the other rows compile/run on
        # the same chip (fp32 masters + AdamW moments are several GB)
        del state, tokens, labels
        headline["rows"] = [gpt_row] + _extra_rows()
        failed = any("error" in r for r in headline["rows"])
    print(json.dumps(headline))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
