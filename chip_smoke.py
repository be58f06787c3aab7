#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py                 # on a machine with a TPU
    python3 chip_smoke.py --cpu-dry-run   # here: tiny sizes, Pallas interpreter

One process drives the main path once through the entry points a user calls,
at the full width of gpt2-medium (h1024, L24, 16 heads, vocab 50304, b8 s1024
bf16, random weights from seed 0):

  train     models.gpt.build_train_step(mesh=None, remat=True): init_fn, one
            compiling step, a few steps on one repeated batch; loss finite and
            falling; the lowered step holds the Mosaic flash kernel
            (tpu_custom_call) and no [B,H,S,S] einsum-attention tensor
  flash     ops.pallas mha_forward [8*16,1024,64] bf16 causal against a
            float32 einsum reference: output and dq/dk/dv
  kernels   ops.pallas.fused _rms (h4096) and _swiglu (width 11008) against
            jnp; a flash call past the VMEM cap raises the named error
  eager     import paddle_tpu as paddle: LeNet, loss.backward(); opt.step()
            through the fusion window (_core/lazy.py); loss falling
  fourchip  only when jax.device_count() >= 4: the same trainer as dp4,
            dp2 x mp2 (seq-sharded) and pp2 x mp2, flash on

Every phase runs even after one fails. The last line of stdout is one JSON
object, {"ok": true, "device": {"platform", "kind", "count"}}; the exit code
is 0 only if every phase passed. Without an accelerator the bare command
exits 2 and prints no result. Per-phase lines (compile seconds, step
milliseconds, peak bytes, persistent-compile-cache hits/misses, eager record
path) are observations for CHANGES.md, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
import traceback

# normalized max error allowed against a float32 reference computed from the
# same bf16 inputs: bf16 keeps 8 mantissa bits (rounding 2^-9 = 0.2 % per
# value); the kernels round P and the outputs to bf16 and accumulate in fp32
BF16_TOL = 2e-2
# step-0 loss of a sharded layout against the one-chip loss on the same batch
# (relative): the layouts reduce the same bf16 values in other orders
LOSS_RTOL = 5e-3


@dataclasses.dataclass
class Sizes:
    """Full width on the chip; `tiny` is the --cpu-dry-run cut."""
    model: str = "gpt2-medium"
    layers: int = 24
    batch: int = 8
    seq: int = 1024
    steps: int = 5
    flash_shape: tuple = (8 * 16, 1024, 64)
    rms_shape: tuple = (2048, 4096)
    swiglu_shape: tuple = (2048, 11008)
    eager_steps: int = 12
    dtype: str = "bfloat16"
    dry_run: bool = False

    @classmethod
    def tiny(cls):
        return cls(layers=2, batch=2, seq=128, steps=3,
                   flash_shape=(4, 256, 64), rms_shape=(64, 256),
                   swiglu_shape=(64, 384), eager_steps=6,
                   dtype="float32",     # XLA:CPU aborts on sharded bf16
                   dry_run=True)


def cache_traffic() -> tuple:
    """(hits, misses) of JAX's persistent compile cache so far: the
    counters the program recorder keeps in the registry
    (`paddle_tpu/observability/programs.py`, registered by
    `enable_compile_cache()`; the one listener on JAX's events)."""
    from paddle_tpu import observability
    counters = observability.stats()["counters"]
    return (counters.get("programs.cache_hits", 0),
            counters.get("programs.cache_misses", 0))


def _peak_bytes(dev=None):
    import jax
    stats = (dev or jax.devices()[0]).memory_stats()
    return stats and stats.get("peak_bytes_in_use")


def _max_err(got, ref):
    """Largest error as a fraction of the reference's largest magnitude."""
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _gpt_config(sz: Sizes):
    from paddle_tpu.models.gpt import GPT_CONFIGS
    return dataclasses.replace(GPT_CONFIGS[sz.model], num_layers=sz.layers,
                               max_position_embeddings=sz.seq, dtype=sz.dtype)


def _batch(config, batch, seq, seed):
    import numpy as np
    rng = np.random.RandomState(seed)
    return tuple(rng.randint(0, config.vocab_size, (batch, seq))
                 .astype(np.int32) for _ in range(2))


def _run_steps(step, state, tokens, labels, n):
    """First call (trace + compile + run) timed apart from n steady steps,
    each synchronised: block_until_ready blocks until the chip is done."""
    import jax
    t0 = time.perf_counter()
    state, loss = step(state, tokens, labels)
    losses = [float(jax.block_until_ready(loss))]
    first_s = time.perf_counter() - t0
    step_ms = []
    for _ in range(n):
        t0 = time.perf_counter()
        state, loss = step(state, tokens, labels)
        jax.block_until_ready(loss)
        step_ms.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(loss))
    return state, losses, first_s, statistics.median(step_ms)


def _check_losses(losses):
    import math
    _check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")


# ------------------------------------------------------------------ phases

def phase_train(sz: Sizes):
    from paddle_tpu._core import device
    from paddle_tpu.models.blocks import use_flash_kernel
    from paddle_tpu.models.gpt import build_train_step

    config = _gpt_config(sz)
    init_fn, step = build_train_step(config, mesh=None, lr=1e-4, remat=True)
    state = init_fn(0)
    tokens, labels = _batch(config, sz.batch, sz.seq, seed=0)

    # which attention runs, read off the program itself
    text = step.lower(state, tokens, labels).as_text()
    ss_tensor = (f"{sz.batch}x{config.num_heads}x{sz.seq}x{sz.seq}x")
    out = {"tpu_custom_calls": text.count("tpu_custom_call"),
           "pallas_interpret": device.pallas_interpret()}
    if not sz.dry_run:
        _check(use_flash_kernel(config.use_flash_attention, sz.seq),
               "flash gate is closed")
        _check(not out["pallas_interpret"], "Pallas is in interpret mode")
        _check(out["tpu_custom_calls"] > 0,
               "no tpu_custom_call in the lowered train step")
        _check(ss_tensor not in text,
               f"einsum attention ran: a tensor<{ss_tensor}..> is lowered")
    out["attention"] = ("pallas-flash (mosaic)" if out["tpu_custom_calls"]
                        else "einsum")

    state, losses, first_s, step_ms = _run_steps(step, state, tokens,
                                                 labels, sz.steps)
    _check_losses(losses)
    out.update(first_step_s=round(first_s, 2), step_ms=round(step_ms, 2),
               loss_first=losses[0], loss_last=losses[-1])
    return out


def phase_flash(sz: Sizes):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import (head_group,
                                                       mha_seq_major)

    bh, s, d = sz.flash_shape
    heads = 16 if bh % 16 == 0 else 2
    b = bh // heads
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(kk, (bh, s, d), jnp.float32)
                  .astype(jnp.bfloat16) for kk in keys)
    scale = 1.0 / d ** 0.5

    def ref(q, k, v):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        hi = jax.lax.Precision.HIGHEST
        logits = jnp.einsum("bqd,bkd->bqk", q, k, precision=hi) * scale
        logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(logits, -1), v,
                          precision=hi)

    def rows(a):    # [bh, s, d] -> the projections' layout [b, s, heads d]
        return a.reshape(b, heads, s, d).swapaxes(1, 2).reshape(b, s, -1)

    def by_head(a):
        return a.reshape(b, s, heads, d).swapaxes(1, 2).reshape(bh, s, d)

    def run(attn, w, *qkv):
        def loss(q, k, v):
            o = attn(q, k, v)
            return (o.astype(jnp.float32) * w.astype(jnp.float32)).sum(), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(*qkv)
        return (o,) + grads

    # the same draws as a [bh, s, d] call saw, handed over as the
    # projections would write them
    t0 = time.perf_counter()
    got = run(lambda q, k, v: mha_seq_major(q, k, v, heads, causal=True,
                                            scale=scale),
              rows(w), rows(q), rows(k), rows(v))
    jax.block_until_ready(got)
    out = {"first_call_s": round(time.perf_counter() - t0, 2),
           "head_group": head_group(heads, d, d, s, s, q.dtype)}
    want = run(ref, w, q, k, v)
    for name, g, r in zip(("out", "dq", "dk", "dv"), got, want):
        out[f"err_{name}"] = round(_max_err(by_head(g), r), 5)
    out["tolerance"] = BF16_TOL
    bad = {n: e for n, e in out.items()
           if n.startswith("err_") and not e <= BF16_TOL}
    _check(not bad, f"flash kernel off its float32 reference: {bad}")
    return out


def phase_kernels(sz: Sizes):
    import importlib
    import jax
    import jax.numpy as jnp
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    fused = importlib.import_module("paddle_tpu.ops.pallas.fused")

    out = {}
    kx, kg, kw = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(kx, sz.rms_shape, jnp.float32).astype(jnp.bfloat16)
    w = (1 + 0.1 * jax.random.normal(kw, sz.rms_shape[-1:], jnp.float32)
         ).astype(jnp.bfloat16)
    xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
    rms_ref = xf * jax.lax.rsqrt((xf * xf).mean(-1, keepdims=True) + 1e-6) * wf
    out["err_rms"] = round(_max_err(
        jax.jit(lambda x, w: fused._rms(x, w, 1e-6))(x, w), rms_ref), 5)

    a = jax.random.normal(kx, sz.swiglu_shape, jnp.float32
                          ).astype(jnp.bfloat16)
    g = jax.random.normal(kg, sz.swiglu_shape, jnp.float32
                          ).astype(jnp.bfloat16)
    swiglu_ref = jax.nn.silu(a.astype(jnp.float32)) * g.astype(jnp.float32)
    out["err_swiglu"] = round(_max_err(jax.jit(fused._swiglu)(a, g),
                                       swiglu_ref), 5)
    out["tolerance"] = BF16_TOL
    _check(out["err_rms"] <= BF16_TOL and out["err_swiglu"] <= BF16_TOL,
           f"fused kernel off its jnp reference: {out}")

    if not sz.dry_run:
        # past the cap the named error comes before the compiler's
        long_seq = fa.max_seq(64, jnp.bfloat16, backward=True) + 512
        q = jax.ShapeDtypeStruct((16, long_seq, 64), jnp.bfloat16)
        try:
            jax.eval_shape(jax.grad(lambda q: fa.mha_forward(
                q, q, q, causal=True).astype(jnp.float32).sum()), q)
        except fa.FlashSequenceLimitError as e:
            out["seq_cap_fwd_bwd_d64_bf16"] = long_seq - 512
            out["seq_cap_error"] = str(e)[:80] + "..."
        else:
            raise AssertionError(
                f"no FlashSequenceLimitError at seq {long_seq}")
    return out


def phase_eager(sz: Sizes):
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu._core import dispatch, native
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(128, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (128,)).astype(np.int64))
    losses, times = [], []
    execs0 = dispatch.exec_count()
    for _ in range(sz.eager_steps):
        t0 = time.perf_counter()
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))      # the sync point
        times.append(time.perf_counter() - t0)
    _check_losses(losses)
    return {"first_steps_s": round(sum(times[:3]), 2),
            "step_ms": round(statistics.median(times[-3:]) * 1e3, 2),
            "xla_execs_per_step": round(
                (dispatch.exec_count() - execs0) / sz.eager_steps, 1),
            "loss_first": losses[0], "loss_last": losses[-1],
            "eager_record_path": native.eager_core_status()}


def _fourchip_layout(mesh, config, tokens, labels, state_bytes, dry_run, kw):
    import jax
    import numpy as np
    from paddle_tpu.models.gpt import build_train_step

    devs = list(mesh.devices.flat)
    init_fn, step = build_train_step(config, mesh, lr=1e-4, remat=True, **kw)
    state = init_fn(0)
    jax.block_until_ready(state)
    if not dry_run:
        _check("tpu_custom_call" in step.lower(state, tokens,
                                               labels).as_text(),
               "flash is not in the lowered step")
    # trainer.py builds the whole fp32 state on device 0, then device_puts
    row = {"dev0_peak_bytes_after_init": _peak_bytes(devs[0])}
    for path, leaf in jax.tree_util.tree_leaves_with_path(state):
        sh = leaf.sharding
        axes = [a for e in sh.spec if e is not None
                for a in (e if isinstance(e, tuple) else (e,))]
        want = int(np.prod([mesh.shape[a] for a in axes] or [1]))
        shards = len({str(s.index) for s in leaf.addressable_shards})
        _check(len(sh.device_set) == 4 and shards == want,
               f"{jax.tree_util.keystr(path)}: spec {sh.spec} implies "
               f"{want} distinct shards on 4 devices, got {shards} on "
               f"{len(sh.device_set)}")
    if devs[0].memory_stats():      # the CPU backend reports none
        in_use = [d.memory_stats()["bytes_in_use"] for d in devs]
        row["bytes_in_use_per_device"] = in_use
        _check(max(in_use) <= 0.5 * state_bytes
               and max(in_use) <= 1.5 * min(in_use),
               f"state not spread over four chips: {in_use} of "
               f"{state_bytes}")

    state, losses, first_s, step_ms = _run_steps(step, state, tokens,
                                                 labels, 3)
    _check_losses(losses)
    row.update(first_step_s=round(first_s, 2), step_ms=round(step_ms, 2),
               loss_first=losses[0], loss_last=losses[-1],
               peak_bytes_per_device=[_peak_bytes(d) for d in devs])
    return row


def phase_fourchip(sz: Sizes):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.models.gpt import gpt_loss, init_gpt_params

    devs = jax.devices()[:4]
    config = _gpt_config(sz)
    batch = 2 * sz.batch
    tokens, labels = _batch(config, batch, sz.seq, seed=1)
    shapes = jax.eval_shape(lambda: init_gpt_params(config, 0))
    state_bytes = sum(p.size for p in jax.tree_util.tree_leaves(shapes)) \
        * (2 + 3 * 4)       # bf16 params + fp32 master, m, v
    out = {"global_batch": batch, "state_bytes": state_bytes}

    # every layout runs, a failed one included; the phase fails at the end
    failed = {}
    layouts = (("dp4", (4, 1, 1), {}),
               ("dp2xmp2", (2, 1, 2), {"seq_shard": True}),
               ("pp2xmp2", (1, 2, 2), {"pp_microbatches": 2}))
    for name, shape, kw in layouts:
        mesh = Mesh(np.asarray(devs).reshape(shape), ("dp", "pp", "mp"))
        try:
            out[name] = _fourchip_layout(mesh, config, tokens, labels,
                                         state_bytes, sz.dry_run, kw)
        except Exception as e:   # boundary: the other layouts still run
            traceback.print_exc()
            failed[name] = f"{type(e).__name__}: {e}"[:1000]

    # the one-chip answer on the same batch: forward loss on device 0
    ref_loss = float(jax.jit(
        lambda p, t, l: gpt_loss(p, t, l, config, remat=False))(
            init_gpt_params(config, 0), tokens, labels))
    out["ref_loss_one_chip"] = ref_loss
    for name, *_ in layouts:
        if name in out and not abs(out[name]["loss_first"] - ref_loss) \
                <= LOSS_RTOL * abs(ref_loss):
            failed[name] = (f"step-0 loss {out[name]['loss_first']} vs one "
                            f"chip {ref_loss}")
    _check(not failed, f"four-chip layouts failed: {failed} (ran: {out})")
    return out


PHASES = (("train", phase_train), ("flash", phase_flash),
          ("kernels", phase_kernels), ("eager", phase_eager))


def run_phases(phases, sz: Sizes, traffic=None) -> list:
    """Run EVERY phase, a failure included; one 'PHASE {json}' line each,
    with the phase's share of `traffic()` (`cache_traffic`) where given."""
    results = []
    for name, fn in phases:
        before = traffic() if traffic else None
        t0 = time.perf_counter()
        row = {"phase": name, "ok": True}
        try:
            row.update(fn(sz))
        except Exception as e:   # boundary: report, keep going, fail at exit
            traceback.print_exc()
            row.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
        row["seconds"] = round(time.perf_counter() - t0, 2)
        row["peak_bytes_in_use"] = _peak_bytes()
        if traffic:
            hits, misses = (a - b for a, b in zip(traffic(), before))
            row.update(cache_requests=hits + misses, cache_hits=hits,
                       cache_misses=misses)
        print("PHASE " + json.dumps(row), flush=True)
        results.append(row)
    return results


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="tiny sizes on the CPU with Pallas in interpret "
                         "mode: checks the script, says nothing of the chip")
    ap.add_argument("--phases", default="",
                    help="comma-separated subset to run while debugging "
                         "(default: all); the result then says so")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print("DEVICE " + json.dumps(device), flush=True)
    if args.cpu_dry_run:
        print("CPU DRY RUN: tiny sizes, Pallas interpreter; not a chip result")
        sz = Sizes.tiny()
    elif dev.platform != "tpu":
        print(f"chip_smoke: no TPU (platform {dev.platform!r}); "
              "--cpu-dry-run checks the script at a tiny size",
              file=sys.stderr)
        return 2
    else:
        sz = Sizes()

    from paddle_tpu._core.device import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    phases = list(PHASES)
    if device["count"] >= 4:
        phases.append(("fourchip", phase_fourchip))
    else:
        print("fourchip: not run (needs jax.device_count() >= 4; this "
              f"process has {device['count']})")
    if args.phases:
        phases = [p for p in phases if p[0] in args.phases.split(",")]
    results = run_phases(phases, sz, cache_traffic)

    failed = [r["phase"] for r in results if not r["ok"]]
    summary = {"ok": not failed, "device": device}
    if failed:
        summary["failed"] = failed
    if sz.dry_run:
        summary["dry_run"] = True
    if args.phases:
        summary["only_phases"] = [p[0] for p in phases]
    print(json.dumps(summary), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
