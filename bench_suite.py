"""Multi-config benchmark suite filling the BASELINE.md table.

Separate from bench.py (the driver's single headline metric): runs the
reference-shaped configs on the local chip and prints one JSON line per
row. Select with BENCH_ROWS=1,2,3 (default all).

Row 1  LeNet/MNIST eager dynamic-graph   steps/sec
Row 2  ResNet-50 @to_static AMP(bf16)    images/sec/chip
Row 3  BERT-base pretrain-style step     tokens/sec/chip
Row 4  eager dispatch-overhead microbench  ops/sec through the lazy window
Row 5  static-check overhead sanity      asserts 0 sanitizer sweeps when
                                         off; reports warn-mode overhead %
Row 6  observability overhead sanity     asserts 0 registry mutations when
                                         off; reports enabled overhead % and
                                         a counter snapshot (cache_hit_rate,
                                         compiles) in the row json
Row 7  resilience recovery latency       asserts the faults-off path freezes
                                         every resilience.* counter (zero
                                         runtime work); reports the
                                         detect->restore->re-run latency for
                                         one injected elastic-step failure
Row 8  adaptive re-plan latency          asserts the faults-off path freezes
                                         every resilience.* counter (incl.
                                         the adaptive replans/member_epochs/
                                         ckpt_* set) across an
                                         AdaptiveTrainer loop; reports the
                                         membership-change -> first
                                         post-replan-step latency for one
                                         injected member::leave, plus the
                                         same drill re-run with the
                                         persistent executable cache warm
                                         (the post-replan fused step loads
                                         from disk; persist hits asserted)
Row 9  async dispatch pipeline         capped-chain speedup with
                                       FLAGS_async_flush on vs off;
                                       asserts the checks-off/faults-off
                                       counter freezes (rows 5/7) still
                                       hold with async on, and that the
                                       flush executor drains with no
                                       leaked worker thread; row json
                                       carries the per-step budget
                                       snapshot (observability budget)
Row 10 distributed telemetry plane   asserts the telemetry-off path
                                     (WITH async flush on) writes zero
                                     __telem/ store keys and freezes
                                     every registry counter; reports
                                     the per-step publication overhead
                                     with telemetry on
Row 11 memory telemetry plane     asserts the memory-telemetry-off path
                                  (WITH async flush on) keeps the
                                  live-buffer census empty, freezes
                                  every registry counter and makes zero
                                  memory_analysis calls; reports the
                                  enabled overhead us/step on the 32-op
                                  chain and embeds the LeNet
                                  steady-state peak/donated-bytes
                                  snapshot (peak participates in --diff
                                  as a bytes row, down-good)
Row 12 SPMD fused-step multichip dryrun   spawns subprocesses with
                                  XLA_FLAGS=--xla_force_host_platform_
                                  device_count=8 and measures the
                                  AMBIENT-MESH fused train step
                                  (distributed.spmd: dp-sharded batch,
                                  compiled gradient all-reduce, sharded
                                  donating optimizer) at mesh sizes
                                  1/2/4/8 — weak scaling, fixed
                                  per-device batch, tokens/s up-good —
                                  with the per-device peak/temp byte
                                  columns from the memory plane; also
                                  asserts a NO-mesh run never touches
                                  the sharding key path
                                  (lazy.SHARD_SIG_BUILDS frozen)

Row 13 perf static analyzer gate    runs `python -m paddle_tpu.analysis
                                  --perf --json` (fusion-break / host-
                                  sync / implicit-reshard counts over
                                  the bench models on the dryrun dp×mp
                                  mesh; subprocess rc gates the row)
                                  and asserts `budget.static_diff` on
                                  the LeNet budget model reconciles
                                  static predictions with the measured
                                  seal-reason counters; the per-class
                                  counts land as 'findings' rows that
                                  --diff compares with ZERO tolerance —
                                  a PR that introduces a new fusion
                                  break or implicit reshard on the
                                  bench models fails the gate

Row 14 compute telemetry plane  asserts the compute-telemetry-off path
                                (WITH async flush on) makes zero
                                cost_analysis calls, counts zero FLOPs
                                and freezes every registry counter;
                                reports the enabled overhead us/step on
                                the capped chain and embeds the LeNet
                                steady-state MFU / GFLOP/s snapshot
                                (both ride as nested diff rows with
                                up-good units so efficiency regressions
                                gate mechanically)

Row 15 mem static analyzer gate  runs `python -m paddle_tpu.analysis
                                --mem --json` (per-device train-step
                                peak priced at pod shapes {1x1, 4x2,
                                2x2x2} via static liveness — no
                                compile; subprocess rc gates the row)
                                under a 2MB/device planning budget so
                                the oom_risk verdicts stay live, and
                                asserts budget.static_diff's
                                memory.peak row reconciles the
                                liveness prediction with the measured
                                census watermark; the oom_risk count
                                is a 'findings' row (--diff zero
                                tolerance, matching row 13) and the
                                per-shape static totals ride as byte
                                rows (down-good)

Row 16 goodput plane  asserts the goodput-off path (WITH async flush
                                on and every new probe exercised:
                                ElasticStep marks, DevicePrefetcher
                                input-wait pull, CheckpointManager
                                save) freezes the registry AND the
                                goodput step ring; reports the LeNet
                                job goodput fraction over a budget
                                window ('goodput %', up-good in
                                --diff) with per-bucket us/step
                                badput rows (down-good; a 0 -> N
                                badput bucket gates like a findings
                                row) and the bucket-additivity
                                identity asserted from the same
                                ledger the budget spans feed

Row 17 record fast path   record-phase us/op on the 64-op dispatch
                                microbench for {fast path off,
                                pure-python fast path, native record
                                core, whole-step replay} — min of
                                interleaved rounds, the us/op legs
                                ride --diff as down-good rows; asserts
                                the off path does ZERO fast-path work
                                (lazy.FAST_OPS and REPLAY_STEPS
                                frozen), the pure-python prong alone
                                wins measurably, and (with the native
                                library built) fast-path-on cuts
                                record-phase us/op >= 3x AND the
                                promoted step-replay leg lands under
                                1 us/op amortized; embeds a gpt2-eager
                                budget snapshot so the host-gap row
                                prices the win on a real model

Row 18 warm restart   two fresh processes share one
                                FLAGS_executable_cache_dir: the cold
                                one compiles + persists, the warm one
                                must rebuild its steady state from
                                disk — zero fresh compiles.* and a ~0
                                goodput compile bucket are asserted,
                                and the cold-vs-warm first-step
                                latency rides --diff down-good; the
                                off leg proves both planes exactly
                                free when FLAGS_executable_cache_dir
                                and FLAGS_step_replay_after are off

Row 19 auto-parallel planner gate   `--plan --json` subprocess ranks
                                every dp×mp×pp factorization of world
                                8 for the row-12 dryrun model against
                                the static planes; asserts the pick ==
                                the sweep's measured-best shape (dp8)
                                and the validated winner carries zero
                                reshard/pipeline findings; plan
                                latency rides --diff as a ms row
                                (down-good)

Row 20 live monitoring plane   asserts the monitor-off path (WITH
                                async flush on) freezes every registry
                                counter, runs NO sampler thread and
                                binds NO port; reports the monitor-on
                                sampling overhead us/step on the 64-op
                                chain under ElasticStep (step hook +
                                sampler contention, down-good in
                                --diff) and the /metrics scrape
                                latency ms/scrape from the stdlib
                                exporter (down-good)

Row 21 numerics plane gate   `--numerics --json` subprocess sweeps the
                                model zoo (lenet/resnet50/bert/gpt2
                                under bf16 auto_cast + the gpt2 int8
                                bucket budget) — rc and zero
                                error-severity findings gate the row,
                                per-model finding counts ride --diff
                                with zero tolerance; asserts
                                checks-off (WITH async flush on)
                                freezes the sanitizer.diagnostics.
                                numerics.* counters and the sweep
                                count across a bf16 workload; reports
                                warn-mode overhead us/op on the same
                                chain (down-good)

Row 22 fleet elasticity   in-process 6->8 grow drill (injected
                                member::join, planner + sanitizer +
                                grow_world + state broadcast publish)
                                reports grow latency (membership ->
                                first post-grow step, down-good) and a
                                preempt-restore drill (preempt::notice
                                -> immediate checkpoint -> fresh-
                                trainer restore) reports recovery
                                badput bounded by ONE checkpoint
                                interval and priced in the goodput
                                recovery bucket; faults-off leg (WITH
                                async flush on) re-asserts the frozen
                                resilience.* counter freeze over every
                                NEW growth/preemption counter

(Multi-chip GPT/ERNIE hybrids need a pod; their single-chip proxies are
bench.py's headline + the dryrun_multichip compile check.)

`--diff` mode: compare the newest two BENCH_*.json in the cwd and fail
loudly (exit 1) on a >10% regression in any row present in both — so a
drift like ResNet r05's 790->752 is caught mechanically, not by a
reviewer squinting at tables.
"""
from __future__ import annotations

import json
import os
import time


def _timeit(fn, steps, warmup=3):
    """Times each step with its result fetched to the host (np.asarray)
    as the sync fence. On the chip jax.block_until_ready blocks, so the
    fetch is not needed as a fence and adds a device-to-host copy per
    step; the steady-window redesign of this timing is the benchmark
    issue's (ROADMAP A0)."""
    import numpy as np
    for _ in range(warmup):
        np.asarray(fn())
    t0 = time.perf_counter()
    for _ in range(steps):
        np.asarray(fn())
    return (time.perf_counter() - t0) / steps


def bench_lenet():
    """Row 1: eager dygraph LeNet on synthetic MNIST batches."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(1e-3, parameters=model.parameters())
    rng = np.random.RandomState(0)
    batch = 128
    x = paddle.to_tensor(rng.randn(batch, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (batch,)).astype(np.int64))

    def step():
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss._value

    sec = _timeit(step, steps=30, warmup=5)
    mfu, gflops = _measure_mfu(step, sec)
    return {"metric": "LeNet MNIST dygraph (b128 eager fwd+bwd+adam)",
            "value": round(1.0 / sec, 1), "unit": "steps/s",
            "mfu": mfu, "gflops": gflops}


def _measure_mfu(step, sec_per_step, steps=3):
    """Headline MFU / GFLOP/s columns: flip the compute telemetry
    plane on AFTER the timed rounds (entering the plane re-keys the
    executable caches, so the instrumented pass compiles fresh,
    cost-analyzed runners), count the per-step FLOPs over a few
    steps, and price them against the ALREADY-measured steady-state
    step time — the timed number is never perturbed."""
    import paddle_tpu as paddle
    from paddle_tpu.observability import compute as comptel

    paddle.set_flags({"FLAGS_compute_telemetry": True})
    try:
        step()                      # recompile under the plane
        f0 = comptel.executed_flops()
        for _ in range(steps):
            step()
        flops_per_step = (comptel.executed_flops() - f0) / steps
    finally:
        paddle.set_flags({"FLAGS_compute_telemetry": False})
    achieved = flops_per_step / sec_per_step if sec_per_step else 0.0
    return (round(comptel.mfu(achieved), 6),
            round(achieved / 1e9, 3))


def bench_resnet50():
    """Row 2: ResNet-50 @to_static with bf16 autocast (AMP role):
    fwd and bwd each one XLA executable, fused-momentum a third."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50()
    net = paddle.jit.to_static(model)
    opt = paddle.optimizer.Momentum(0.1, parameters=model.parameters())
    batch = int(os.environ.get("BENCH_RN50_BATCH", "64"))
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, 3, 224, 224).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype(np.int64))

    def step():
        with paddle.amp.auto_cast(level="O1"):
            loss = F.cross_entropy(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss._value

    sec = _timeit(step, steps=10, warmup=3)
    return {"metric":
            f"ResNet-50 @to_static train (b{batch} amp-bf16 fused-mom)",
            "value": round(batch / sec, 1), "unit": "images/s"}


def bench_bert():
    """Row 3: BERT-base MLM pretrain step (compiled trainer)."""
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.models.bert import BERT_CONFIGS, build_train_step

    config = BERT_CONFIGS["bert-base"]
    batch = int(os.environ.get("BENCH_BERT_BATCH", "16"))
    seq = int(os.environ.get("BENCH_BERT_SEQ", "512"))
    init_fn, step = build_train_step(config, mesh=None, lr=1e-4)
    state = init_fn(0)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(
        rng.randint(0, config.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(
        rng.randint(0, config.vocab_size, (batch, seq)), jnp.int32)

    holder = {"state": state}

    def one():
        holder["state"], loss = step(holder["state"], tokens, labels)
        return loss

    sec = _timeit(one, steps=15, warmup=3)
    return {"metric": f"BERT-base MLM pretrain (b{batch} s{seq} bf16)",
            "value": round(batch * seq / sec, 1), "unit": "tokens/s"}


def bench_dispatch():
    """Row 4: eager dispatch-overhead microbench — host-side ops/sec
    through the lazy fusion window on a 16-op elementwise chain. This
    isolates the per-op Python dispatch cost (record + signature +
    cache lookup) from device time: the chain is tiny, so steady-state
    throughput is dominated by the host, the exact ceiling 2011.03641
    describes."""
    import numpy as np
    import paddle_tpu as paddle

    x = paddle.to_tensor(np.ones((16, 16), "float32"))
    chain = 16

    def run():
        y = x
        for _ in range(chain):
            y = y * 1.0001 + 0.0001
        return y._value

    sec = _timeit(run, steps=200, warmup=20)
    return {"metric": f"eager dispatch overhead ({chain * 2}-op lazy chain)",
            "value": round(chain * 2 / sec, 1), "unit": "ops/s"}


def bench_static_checks():
    """Row 5: program-sanitizer overhead sanity. With
    FLAGS_static_checks=off the checkers must contribute ZERO work —
    asserted by counting sanitizer sweeps (hooks.segment_sweeps(), the
    sanitizer.segment_sweeps registry counter, frozen across the whole
    off-mode timing; exact, immune to machine noise, unlike a
    wall-clock delta between two identical code paths). Fix mode on
    the same (clean) program must perform ZERO rewrites — the
    sanitizer.fixes_applied counter stays frozen while the fix-mode
    sweeps run (the sanitizer must never rewrite correct code). The
    reported value is warn-mode overhead on the same 32-op lazy chain,
    min-of-interleaved-rounds; the row json carries the fix-mode
    overhead alongside."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.analysis import hooks

    x = paddle.to_tensor(np.ones((16, 16), "float32"))
    chain = 16

    def run():
        y = x
        for _ in range(chain):
            y = y * 1.0001 + 0.0001
        return y._value

    def timed(mode):
        paddle.set_flags({"FLAGS_static_checks": mode})
        try:
            return _timeit(run, steps=100, warmup=10)
        finally:
            paddle.set_flags({"FLAGS_static_checks": "off"})

    timed("off")               # prime: compile + cache warmup off-clock
    start = hooks.segment_sweeps()
    # interleave off/warn rounds so machine drift hits both equally
    rounds = []
    for _ in range(5):
        before = hooks.segment_sweeps()
        off_t = timed("off")
        assert hooks.segment_sweeps() == before, \
            "FLAGS_static_checks=off ran sanitizer sweeps (must be 0)"
        rounds.append((off_t, timed("warn")))
    assert hooks.segment_sweeps() > start, "warn mode never swept"

    # fix mode over a clean program: sweeps run, rewrites do not
    sweeps_before = hooks.segment_sweeps()
    fixes_before = hooks.fixes_applied()
    fix_t = timed("fix")
    assert hooks.segment_sweeps() > sweeps_before, "fix mode never swept"
    assert hooks.fixes_applied() == fixes_before, \
        "FLAGS_static_checks=fix rewrote a clean program (must be 0)"

    off = min(r[0] for r in rounds)
    warn = min(r[1] for r in rounds)
    warn_pct = (warn - off) / off * 100.0
    return {"metric": f"static-check overhead ({chain * 2}-op lazy "
                      f"chain; off = 0 sweeps, clean-program fix = 0 "
                      f"rewrites asserted)",
            "value": round(warn_pct, 1), "unit": "% warn-mode overhead",
            "fix_mode_overhead_pct": round((fix_t - off) / off * 100.0,
                                           1)}


def bench_observability():
    """Row 6: observability overhead sanity. With FLAGS_observability
    off the instrumentation must contribute ZERO registry work —
    asserted by the registry's MUTATIONS counter staying frozen across
    the whole off-mode timing (exact, immune to machine noise; the
    sanitizer-row technique). The reported value is enabled-mode
    overhead on the same 32-op lazy chain, min-of-interleaved-rounds,
    and the row json carries the counter snapshot the driver folds into
    BENCH (cache_hit_rate, compiles, flushes)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import metrics

    x = paddle.to_tensor(np.ones((16, 16), "float32"))
    chain = 16

    def run():
        y = x
        for _ in range(chain):
            y = y * 1.0001 + 0.0001
        return y._value

    def timed(on):
        paddle.set_flags({"FLAGS_observability": on,
                          "FLAGS_static_checks": "off"})
        try:
            return _timeit(run, steps=100, warmup=10)
        finally:
            paddle.set_flags({"FLAGS_observability": False})

    timed(False)               # prime: compile + cache warmup off-clock
    rounds = []
    for _ in range(5):
        before = metrics.MUTATIONS
        off_t = timed(False)
        assert metrics.MUTATIONS == before, \
            "FLAGS_observability=off did registry work (must be 0)"
        rounds.append((off_t, timed(True)))
    off = min(r[0] for r in rounds)
    on = min(r[1] for r in rounds)
    on_pct = (on - off) / off * 100.0

    # counter snapshot for the BENCH json: re-run the chain enabled
    # from a clean registry so the derived rates describe steady state
    obs.reset()
    paddle.set_flags({"FLAGS_observability": True})
    try:
        for _ in range(20):
            run()
    finally:
        paddle.set_flags({"FLAGS_observability": False})
    snap = obs.stats()
    return {"metric": f"observability overhead ({chain * 2}-op lazy "
                      f"chain; off = 0 registry mutations asserted)",
            "value": round(on_pct, 1), "unit": "% enabled overhead",
            "counters": {
                "cache_hit_rate": round(snap["cache_hit_rate"], 4)
                if snap["cache_hit_rate"] is not None else None,
                "step_cache_hit_rate": snap["step_cache_hit_rate"],
                "compiles": snap["compiles"],
                "segment_flushes":
                    snap["counters"].get("segment.flushes", 0),
                "segment_ops": snap["counters"].get("segment.ops", 0),
            }}


def bench_resilience():
    """Row 7: fault-tolerance overhead + recovery latency. With
    FLAGS_fault_inject off the resilience runtime must contribute ZERO
    registry work — asserted by every `resilience.*` counter staying
    FROZEN across the 32-op dispatch chain AND an ElasticStep-wrapped
    LeNet loop (the exact-counter technique of rows 5/6; wall-clock
    deltas between identical paths are machine noise, frozen counters
    are not). The reported value is the recovery latency — detect ->
    restore snapshot -> re-run to success — for ONE injected step
    failure; the row json carries the elastic vs plain per-step time
    so the snapshot cost (the price of rollback insurance, paid only
    when the wrapper is used) stays visible."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed.resilience import ElasticStep
    from paddle_tpu.observability import metrics
    from paddle_tpu.vision.models import LeNet

    x = paddle.to_tensor(np.ones((16, 16), "float32"))

    def chain():
        y = x
        for _ in range(16):
            y = y * 1.0001 + 0.0001
        return y._value

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(1e-3, parameters=model.parameters())
    rng = np.random.RandomState(0)
    bx = paddle.to_tensor(rng.randn(32, 1, 28, 28).astype(np.float32))
    by = paddle.to_tensor(rng.randint(0, 10, (32,)).astype(np.int64))
    elastic = ElasticStep(optimizer=opt)

    def step():
        loss = F.cross_entropy(model(bx), by)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss._value

    def res_counters():
        return {k: v for k, v in metrics.snapshot()["counters"].items()
                if k.startswith("resilience.")}

    # warm both paths off-clock (the snapshot's per-shape copy ops
    # compile on the first elastic step), then freeze-assert the
    # faults-off run
    _timeit(chain, steps=20, warmup=5)
    plain_t = _timeit(step, steps=5, warmup=2)
    _timeit(lambda: elastic.run(step), steps=1, warmup=2)
    before = res_counters()
    _timeit(chain, steps=100, warmup=0)
    elastic_t = _timeit(lambda: elastic.run(step), steps=5, warmup=0)
    assert res_counters() == before, \
        "FLAGS_fault_inject off did resilience work (must be 0)"

    # one injected transient step failure: measure the recovery
    fail_at = elastic.step_index + 2
    paddle.set_flags(
        {"FLAGS_fault_inject": f"step::{fail_at}=fail"})
    try:
        for _ in range(3):
            np.asarray(elastic.run(step))
    finally:
        paddle.set_flags({"FLAGS_fault_inject": ""})
    assert elastic.last_recovery_s is not None, "no recovery measured"
    return {"metric": "resilience recovery latency (LeNet elastic "
                      "step, detect -> restore -> re-run; faults-off "
                      "= frozen resilience.* counters asserted)",
            "value": round(elastic.last_recovery_s * 1000.0, 2),
            "unit": "ms",
            "plain_step_ms": round(plain_t * 1000.0, 2),
            "elastic_step_ms": round(elastic_t * 1000.0, 2)}


def bench_replan():
    """Row 8: adaptive re-plan latency. The faults-off freeze-assert of
    row 7, extended over an AdaptiveTrainer-wrapped loop so the NEW
    resilience counters (replans, member_epochs, ckpt_fallbacks,
    ckpt_restores, replan_fallback_plans) are proven frozen too — the
    membership poll must cost one module-level bool when injection is
    off. The reported value is the full adaptive-recovery latency for
    one injected member::leave: membership change -> quiesce -> tuner
    re-plan -> sanitizer validation -> mesh swap -> step-cache re-key
    -> first successful post-replan step (which recompiles the fused
    step against the new mesh epoch, so the compile is priced in).
    The mesh is logical (8 processes losing 2) so the row runs on any
    visible device count; row 7 already prices the data movement."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed.mesh import ProcessMesh
    from paddle_tpu.distributed.resilience import AdaptiveTrainer
    from paddle_tpu.observability import metrics
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(1e-3, parameters=model.parameters())
    rng = np.random.RandomState(0)
    bx = paddle.to_tensor(rng.randn(32, 1, 28, 28).astype(np.float32))
    by = paddle.to_tensor(rng.randint(0, 10, (32,)).astype(np.int64))
    mesh = ProcessMesh(list(range(8)), dim_names=["dp"])
    trainer = AdaptiveTrainer(optimizer=opt, mesh=mesh,
                              lost_ranks=[6, 7])

    def step():
        loss = F.cross_entropy(model(bx), by)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss._value

    def res_counters():
        return {k: v for k, v in metrics.snapshot()["counters"].items()
                if k.startswith("resilience.")}

    _timeit(lambda: trainer.run(step), steps=1, warmup=2)
    before = res_counters()
    adaptive_t = _timeit(lambda: trainer.run(step), steps=5, warmup=0)
    assert res_counters() == before, \
        "faults-off adaptive loop did resilience work (must be 0)"

    # occurrence counting starts when the plan is armed: the leave
    # fires on the SECOND post-arm membership poll
    paddle.set_flags({"FLAGS_fault_inject": "member::leave@2=die"})
    try:
        for _ in range(3):
            np.asarray(trainer.run(step))
    finally:
        paddle.set_flags({"FLAGS_fault_inject": ""})
    assert trainer.replans == 1 and \
        trainer.last_replan_latency_s is not None, "no replan measured"

    # ---------------- warm leg: persistent executable cache primed.
    # The same 8->6 drill runs twice against one shared
    # FLAGS_executable_cache_dir: the first run persists the
    # post-replan fused step under its mesh-epoch-zeroed, sharding-
    # salted key, so the second run's recompile (new epoch, same
    # survivor sharding) loads from disk instead of lowering — the
    # warm number prices adaptive recovery on a restarted process (or
    # a peer) that inherits a warm cache. Each drill builds a fresh
    # model/optimizer so no in-memory state leaks between legs.
    import shutil
    import tempfile
    from paddle_tpu._core import lazy

    def drill(tag):
        paddle.seed(0)
        m2 = LeNet()
        o2 = paddle.optimizer.Adam(1e-3, parameters=m2.parameters())
        t = AdaptiveTrainer(
            optimizer=o2,
            mesh=ProcessMesh(list(range(8)), dim_names=["dp"]),
            lost_ranks=[6, 7])

        def s2():
            loss = F.cross_entropy(m2(bx), by)
            loss.backward()
            o2.step()
            o2.clear_grad()
            return loss._value

        np.asarray(t.run(s2))          # settle pre-replan compiles
        paddle.set_flags({"FLAGS_fault_inject": "member::leave@2=die"})
        try:
            for _ in range(3):
                np.asarray(t.run(s2))
        finally:
            paddle.set_flags({"FLAGS_fault_inject": ""})
        assert t.replans == 1 and t.last_replan_latency_s is not None, \
            f"{tag} drill did not replan"
        return t

    cache_dir = tempfile.mkdtemp(prefix="ptxc_replan_")
    paddle.set_flags({"FLAGS_observability": True,
                      "FLAGS_executable_cache_dir": cache_dir})
    try:
        drill("store")                 # persists the post-replan step
        lazy.clear_segment_cache()     # next leg must go through disk
        warm = drill("warm")
    finally:
        paddle.set_flags({"FLAGS_observability": False,
                          "FLAGS_executable_cache_dir": ""})
        shutil.rmtree(cache_dir, ignore_errors=True)
    assert warm.last_replan_persist_hits, \
        "warm replan never loaded from the persistent executable cache"
    warm_ms = round(warm.last_replan_latency_s * 1000.0, 2)

    return {"metric": "adaptive re-plan latency (8->6 member::leave, "
                      "membership change -> first post-replan step; "
                      "faults-off = frozen resilience.* counters "
                      "asserted)",
            "value": round(trainer.last_replan_latency_s * 1000.0, 2),
            "unit": "ms",
            "adaptive_step_ms": round(adaptive_t * 1000.0, 2),
            "replan_warm_ms": warm_ms,
            "replan_warm_persist_hits": warm.last_replan_persist_hits,
            "plan": {k: trainer.last_plan.get(k) for k in
                     ("dp_degree", "mp_degree", "pp_degree")},
            "rows": [{"metric": "adaptive re-plan latency (persistent "
                                "executable cache warm)",
                      "value": warm_ms, "unit": "ms"}]}


def bench_async_flush():
    """Row 9: async dispatch pipeline. A 64-op chain over a 16-op
    segment cap seals 4 segments per step mid-record — exactly the
    run-ahead case the pipeline targets — timed with FLAGS_async_flush
    off vs on (min of interleaved rounds). Correctness riders, all
    exact-counter asserts in the row-5/6/7 style:

    - checks-off sweep freeze and faults-off resilience freeze both
      hold WITH async on (the pipeline must not smuggle sanitizer or
      resilience work onto the worker);
    - the executor drains clean and shutdown leaves no worker thread;
    - the row json carries the per-step budget snapshot (the
      observability `budget` mode over the LeNet fused step) so every
      bench round records where the step's host time went.
    """
    import threading

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu._core import async_flush
    from paddle_tpu.analysis import hooks
    from paddle_tpu.observability import budget as budget_mod
    from paddle_tpu.observability import metrics

    x = paddle.to_tensor(np.ones((16, 16), "float32"))
    chain = 64

    def run_phases():
        """One step, phase-split: the RECORD phase is everything the
        recording thread does until the last op is recorded (with sync
        flush this carries the 4 cap-sealed segments' cache lookup +
        dispatch inline; with async it is seal+submit only) — the
        dispatch-side time the pipeline removes from the critical
        path. The SYNC phase is the final fetch, where deferred work
        lands. On a CPU box both phases compete for the same cores, so
        total wall barely moves — on a real accelerator the sync phase
        is device time the host no longer serializes in front of."""
        t0 = time.perf_counter()
        y = x
        for _ in range(chain):
            y = y * 1.0001 + 0.0001
        t1 = time.perf_counter()
        import numpy as _np
        _np.asarray(y._value)
        return t1 - t0, time.perf_counter() - t1

    def timed(async_on, steps=100):
        paddle.set_flags({"FLAGS_async_flush": async_on,
                          "FLAGS_lazy_max_segment_ops": 16})
        try:
            for _ in range(10):
                run_phases()
            rec = tot = 0.0
            for _ in range(steps):
                r, s = run_phases()
                rec += r
                tot += r + s
            return rec / steps, tot / steps
        finally:
            async_flush.drain(raise_latched=False)
            paddle.set_flags({"FLAGS_async_flush": False,
                              "FLAGS_lazy_max_segment_ops": 256})

    def frozen_counters():
        snap = metrics.snapshot()["counters"]
        return {k: v for k, v in snap.items()
                if k.startswith("resilience.")}, hooks.segment_sweeps()

    timed(False, steps=20)     # prime: compile + cache warmup off-clock
    timed(True, steps=20)
    res_before, sweeps_before = frozen_counters()
    rounds = [(timed(False), timed(True)) for _ in range(5)]
    res_after, sweeps_after = frozen_counters()
    assert res_after == res_before, \
        "async pipeline did resilience work with faults off (must be 0)"
    assert sweeps_after == sweeps_before, \
        "async pipeline ran sanitizer sweeps with checks off (must be 0)"

    # drain/shutdown hygiene: no leaked flush worker
    async_flush.drain()
    async_flush.shutdown()
    assert not any(t.name == async_flush._WORKER_NAME
                   for t in threading.enumerate()), \
        "flush executor leaked its worker thread past shutdown"

    # per-step budget snapshot: the LeNet fused train step (the same
    # builder the observability CLI's budget mode uses)
    from paddle_tpu.observability.__main__ import _lenet_step
    snapshot = budget_mod.collect(_lenet_step(), steps=10, warmup=3)

    rec_off = min(r[0][0] for r in rounds)
    rec_on = min(r[1][0] for r in rounds)
    tot_off = min(r[0][1] for r in rounds)
    tot_on = min(r[1][1] for r in rounds)
    return {"metric": f"async dispatch pipeline ({chain}-op chain, "
                      f"16-op cap; recording-thread dispatch time off "
                      f"vs on; checks-off/faults-off freezes + clean "
                      f"drain asserted)",
            "value": round(rec_off / rec_on, 2) if rec_on else None,
            "unit": "x dispatch-side cut",
            "record_ms_sync": round(rec_off * 1000.0, 3),
            "record_ms_async": round(rec_on * 1000.0, 3),
            "total_ms_sync": round(tot_off * 1000.0, 3),
            "total_ms_async": round(tot_on * 1000.0, 3),
            "budget": snapshot}


def bench_telemetry():
    """Row 10: distributed telemetry plane. Telemetry-off contract,
    asserted EXACTLY (the rows-5..9 counter technique) with the async
    flush pipeline ON — the plane must not smuggle work into either
    path: (a) the registry's MUTATIONS counter stays frozen across a
    dispatch chain + an ElasticStep-wrapped loop with a publisher
    INITIALIZED but the flag off, and (b) the store holds zero
    __telem/ keys afterwards (seq-key probe per rank). The reported
    value is the publication overhead per step with telemetry on —
    frame build cost on the training thread; the store set is
    off-thread by construction."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu._core import async_flush
    from paddle_tpu.distributed.resilience import ElasticStep
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.observability import distributed as dtel
    from paddle_tpu.observability import metrics

    x = paddle.to_tensor(np.ones((16, 16), "float32"))

    def chain():
        y = x
        for _ in range(16):
            y = y * 1.0001 + 0.0001
        return y._value

    w = paddle.to_tensor(np.zeros((8, 8), "float32"))
    opt = paddle.optimizer.SGD(0.0, parameters=[w])
    elastic = ElasticStep(optimizer=opt)

    store = TCPStore("127.0.0.1", 0, is_master=True, world_size=1,
                     timeout=10)
    try:
        pub = dtel.init(store, rank=0, world_size=1)
        paddle.set_flags({"FLAGS_async_flush": True})
        try:
            _timeit(chain, steps=20, warmup=5)
            _timeit(lambda: elastic.run(chain), steps=2, warmup=2)
            async_flush.drain()
            # -------- telemetry OFF: frozen counters, zero store keys
            before = metrics.MUTATIONS
            off_t = _timeit(lambda: elastic.run(chain), steps=50,
                            warmup=0)
            async_flush.drain()
            assert metrics.MUTATIONS == before, \
                "telemetry-off loop did registry work (must be 0)"
            assert store.try_get("__telem/seq/0", timeout=0.05) \
                is None, "telemetry-off loop wrote __telem/ store keys"
            assert pub._seq == 0, \
                "telemetry-off loop built frames (must be 0)"
            # -------- telemetry ON: publication overhead per step
            paddle.set_flags({"FLAGS_distributed_telemetry": True})
            try:
                on_t = _timeit(lambda: elastic.run(chain), steps=50,
                               warmup=5)
                pub.flush()
            finally:
                paddle.set_flags(
                    {"FLAGS_distributed_telemetry": False})
            assert pub._seq > 0 and \
                store.try_get("__telem/seq/0") is not None, \
                "telemetry-on loop never published a frame"
        finally:
            paddle.set_flags({"FLAGS_async_flush": False})
            async_flush.drain(raise_latched=False)
        snap = metrics.snapshot()["histograms"].get(
            "telemetry.publish_us", {})
        return {"metric": "distributed telemetry publication (chain "
                          "elastic step; off = frozen counters + zero "
                          "__telem/ store keys asserted, async flush "
                          "on)",
                "value": round((on_t - off_t) * 1e6, 2),
                "unit": "us/step publication overhead",
                "frames": pub._seq,
                "publish_us_avg": (round(snap["total"] / snap["count"],
                                         2) if snap.get("count")
                                   else None)}
    finally:
        dtel.shutdown()
        store.close()


def bench_memory():
    """Row 11: memory telemetry plane. Off contract asserted EXACTLY
    (the rows-5..10 counter technique) with the async flush pipeline
    ON: across a capped 32-op dispatch chain the census stays empty,
    the registry's MUTATIONS counter stays frozen, and zero
    ``memory_analysis()`` calls happen. The reported value is the
    enabled-mode overhead per step on the same chain (census
    registration + watermark upkeep on the record path). The row json
    embeds the LeNet steady-state byte snapshot — census peak
    watermark, donated bytes per step (lazy-flush mask + fused
    optimizer donate_argnums), and the compiled executables' temp
    footprint from the cached memory analysis; peak rides as a nested
    diff row with a bytes unit (down-good) so bench_suite --diff
    catches footprint regressions mechanically."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu._core import async_flush
    from paddle_tpu.observability import memory as memtel
    from paddle_tpu.observability import metrics

    x = paddle.to_tensor(np.ones((16, 16), "float32"))

    def chain():
        y = x
        for _ in range(32):
            y = y * 1.0001 + 0.0001
        return y._value

    from paddle_tpu._core.flags import flag_value
    checks_was = flag_value("FLAGS_static_checks")
    # checks off for the freeze window: the warn-mode sanitizer sweep
    # counts registry work by design (the row-10 precedent)
    paddle.set_flags({"FLAGS_async_flush": True,
                      "FLAGS_lazy_max_segment_ops": 16,
                      "FLAGS_static_checks": "off"})
    try:
        _timeit(chain, steps=20, warmup=5)
        async_flush.drain()
        # ---------------- memory telemetry OFF: the freeze contract
        before = metrics.MUTATIONS
        calls0 = memtel.ANALYSIS_CALLS
        census0 = memtel.census_size()
        off_t = _timeit(chain, steps=100, warmup=0)
        async_flush.drain()
        assert metrics.MUTATIONS == before, \
            "memory-telemetry-off loop did registry work (must be 0)"
        assert memtel.census_size() == census0 == 0, \
            "memory-telemetry-off loop registered census entries"
        assert memtel.ANALYSIS_CALLS == calls0, \
            "memory-telemetry-off loop called memory_analysis"
        # ---------------- ON: enabled overhead per step
        paddle.set_flags({"FLAGS_memory_telemetry": True})
        try:
            on_t = _timeit(chain, steps=100, warmup=5)
            async_flush.drain()
            assert memtel.census_size() > 0, \
                "memory-telemetry-on loop registered nothing"
        finally:
            paddle.set_flags({"FLAGS_memory_telemetry": False})
    finally:
        paddle.set_flags({"FLAGS_async_flush": False,
                          "FLAGS_lazy_max_segment_ops": 256,
                          "FLAGS_static_checks": checks_was})
        async_flush.drain(raise_latched=False)

    # ---------------- LeNet steady-state byte snapshot
    paddle.set_flags({"FLAGS_memory_telemetry": True})
    try:
        seq0 = memtel.exec_seq()    # scope the analysis log to LeNet
        from paddle_tpu.vision.models import LeNet
        paddle.seed(0)
        model = LeNet()
        opt = paddle.optimizer.Adam(1e-3, parameters=model.parameters())
        rng = np.random.RandomState(0)
        xb = paddle.to_tensor(rng.randn(32, 1, 28, 28).astype(np.float32))
        yb = paddle.to_tensor(rng.randint(0, 10, (32,)).astype(np.int64))

        def step():
            loss = F.cross_entropy(model(xb), yb)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss._value

        _timeit(step, steps=2, warmup=3)       # warm the step cache
        memtel.reset_peak()
        d0 = memtel.donated_bytes()
        steps = 4
        _timeit(step, steps=steps, warmup=0)
        peak = memtel.peak_bytes()
        donated = (memtel.donated_bytes() - d0) / steps
        temps = [e.get("temp_bytes") or 0
                 for e in memtel.executable_stats()
                 if e.get("seq", 0) > seq0]
    finally:
        paddle.set_flags({"FLAGS_memory_telemetry": False})

    return {"metric": "memory telemetry overhead (32-op capped chain; "
                      "off = empty census + frozen counters + zero "
                      "memory_analysis calls, async flush on)",
            "value": round((on_t - off_t) * 1e6, 2),
            "unit": "us/step overhead",
            "lenet_peak_bytes": int(peak),
            "lenet_donated_bytes_per_step": round(donated, 1),
            "lenet_temp_bytes_max": int(max(temps)) if temps else 0,
            "census_entries_on": memtel.census_size(),
            "rows": [{"metric": "LeNet steady-state peak HBM "
                                "(b32 census watermark)",
                      "value": int(peak), "unit": "bytes peak"}]}


def _spmd_dryrun_worker(n: int):
    """Row-12 subprocess body (`bench_suite.py --spmd-dryrun N`): one
    fused-step workload under an n-device ambient dp mesh, weak scaling
    (fixed per-device batch). Prints ONE json line. Runs in a fresh
    process so the forced 8-device CPU backend and the mesh size are
    set before any jax init."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.observability import memory as memtel
    from paddle_tpu.observability import metrics

    # few params (each replicated grad = one compiled all-reduce), a
    # short program (per-op execute cost multiplies with the virtual
    # device count on a shared host), small per-device compute: the
    # shape that exposes scaling on small hosts while staying a real
    # fwd+vjp+optimizer step
    B0 = int(os.environ.get("SPMD_DRYRUN_B0", 8))
    S = int(os.environ.get("SPMD_DRYRUN_S", 32))
    H = int(os.environ.get("SPMD_DRYRUN_H", 64))
    paddle.set_flags({"FLAGS_static_checks": "off",
                      "FLAGS_memory_telemetry": True,
                      "FLAGS_compute_telemetry": True,
                      "FLAGS_observability": True})
    paddle.seed(0)
    r = np.random.RandomState(0)
    B = B0 * n
    x_np = r.randn(B, S, H).astype("float32")
    y_np = r.randint(0, H, (B * S,)).astype("int64")

    with dist.auto_mesh(n, dim_names=["dp"]):
        net = nn.Sequential(nn.Linear(H, H, bias_attr=False),
                            nn.Linear(H, H, bias_attr=False))
        opt = paddle.optimizer.Adam(1e-3, parameters=net.parameters())
        dp = dist.DataParallel(net)
        x = paddle.to_tensor(x_np)
        y = paddle.to_tensor(y_np)

        def step():
            # one expression: a surviving grad-requiring intermediate
            # would route backward() to the generic engine instead of
            # the fused fwd+vjp step
            loss = F.cross_entropy(dp(x).reshape([B * S, H]), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        from paddle_tpu.observability import compute as comptel
        _timeit(lambda: step()._value, steps=2, warmup=3)
        memtel.reset_peak()
        f0 = comptel.executed_flops()
        t_f = time.perf_counter()
        # min-of-rounds (the row 5/6 technique): this row runs on
        # whatever shares the host, and the scale column divides two
        # of these numbers
        dt = min(_timeit(lambda: step()._value, steps=8, warmup=0)
                 for _ in range(3))
        # per-CHIP achieved FLOP/s over the whole 3x8-step window
        # (cost analysis prices the partitioned module, so the counted
        # FLOPs are already per-device)
        d_flops = comptel.executed_flops() - f0
        d_t = time.perf_counter() - t_f
        achieved = d_flops / d_t if d_t > 0 else 0.0
        snap = metrics.snapshot()["counters"]
    temps = [int(e.get("temp_bytes") or 0)
             for e in memtel.executable_stats()]
    print(json.dumps({
        "n": n, "step_ms": round(dt * 1e3, 3),
        "tokens_s": round(B * S / dt, 1),
        "mfu": round(comptel.mfu(achieved), 6),
        "gflops": round(achieved / 1e9, 3),
        "peak_pd_bytes": memtel.peak_per_device_bytes(),
        "peak_bytes": memtel.peak_bytes(),
        "temp_bytes_max": max(temps) if temps else 0,
        "compiled_comm_bytes": int(sum(
            v for k, v in snap.items()
            if k.startswith("comm.bytes.compiled."))),
        "host_comm_calls": int(sum(
            v for k, v in snap.items() if k.startswith("comm.calls."))),
    }), flush=True)


def bench_spmd_multichip():
    """Row 12: SPMD fused-step multichip dryrun. Weak scaling (fixed
    per-device batch) of the ambient-mesh fused step at mesh sizes
    1/2/4/8 over the forced 8-device CPU backend, with per-device
    peak/temp byte columns; plus the no-mesh off-freeze: a meshless
    run must never build a sharding key component."""
    import subprocess
    import sys

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu._core import lazy

    # ---------------- no-mesh off-freeze (in-process)
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(16, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(1e-3, parameters=net.parameters())
    r = np.random.RandomState(0)
    x = paddle.to_tensor(r.randn(8, 16).astype("float32"))
    y = paddle.to_tensor(r.randint(0, 4, (8,)).astype("int64"))
    builds0 = lazy.SHARD_SIG_BUILDS
    for _ in range(5):
        loss = F.cross_entropy(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert lazy.SHARD_SIG_BUILDS == builds0, \
        "no-mesh run touched the sharding key path"

    # ---------------- subprocess sweep over mesh sizes
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    here = os.path.abspath(__file__)
    results = {}
    for n in (1, 2, 4, 8):
        out = subprocess.run(
            [sys.executable, here, "--spmd-dryrun", str(n)],
            capture_output=True, text=True, env=env, timeout=600)
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("{")]
        if out.returncode != 0 or not line:
            raise RuntimeError(
                f"spmd dryrun n={n} failed rc={out.returncode}: "
                f"{out.stderr[-2000:]}")
        results[n] = json.loads(line[-1])
    base = results[1]["tokens_s"]
    scale8 = round(results[8]["tokens_s"] / base, 2) if base else 0.0
    rows = [{"metric": f"spmd dryrun fused-step tokens/s (mesh=dp{n}, "
                       "weak scaling)",
             "value": results[n]["tokens_s"], "unit": "tokens/s",
             "step_ms": results[n]["step_ms"],
             "mfu": results[n].get("mfu"),
             "gflops": results[n].get("gflops"),
             "peak_pd_bytes": results[n]["peak_pd_bytes"],
             "temp_bytes_max": results[n]["temp_bytes_max"],
             "compiled_comm_bytes": results[n]["compiled_comm_bytes"],
             "host_comm_calls": results[n]["host_comm_calls"]}
            for n in (1, 2, 4, 8)]
    return {"metric": "spmd multichip dryrun fused-step tokens/s "
                      "(mesh=dp8, weak scaling, 8 virtual CPU devices)",
            "value": results[8]["tokens_s"], "unit": "tokens/s",
            "scale_8x_vs_1x": scale8,
            "mfu": results[8].get("mfu"),
            "gflops": results[8].get("gflops"),
            # 8 virtual devices share the host's real cores: the
            # achievable dryrun scale is bounded by them, so the scale
            # column reads against this, not against 8
            "host_cores": os.cpu_count(),
            "host_comm_calls_total": sum(results[n]["host_comm_calls"]
                                         for n in (1, 2, 4, 8)),
            "rows": rows}


def bench_perf_lint():
    """Row 13: the perf static analyzer as a mechanical regression
    gate. The --perf CLI sweeps the bench models (eager-GPT fusion
    breaks, eager-ResNet BN-sync class, sharded models' implicit
    reshards on the dryrun dp×mp mesh) in a subprocess — its exit code
    gates the row — and budget.static_diff proves the analyzer's
    predictions match the measured seal-reason counters in-process.
    Per-class counts become 'findings' rows: --diff treats any
    INCREASE as a regression (zero tolerance)."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PT_PERF_NO_REEXEC="1")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--perf",
         "--json"],
        capture_output=True, text=True, env=env, timeout=1800)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        raise RuntimeError(
            f"analysis --perf failed rc={out.returncode}: "
            f"{out.stderr[-2000:]}")
    payload = json.loads(lines[-1])

    def count(model, key):
        return sum(d.get(key, 0) for d in payload["models"].get(model,
                                                                ()))

    # static-vs-measured reconciliation on the LeNet budget model (the
    # deterministic fused-path workload): the analyzer is held to the
    # meters, in-process
    from paddle_tpu.observability import budget
    from paddle_tpu.observability.__main__ import _lenet_step
    sd = budget.static_diff(_lenet_step(), steps=3)
    assert sd["ok"], \
        f"static seal predictions diverge from measured counters: {sd}"

    rows = [
        {"metric": "perf lint fusion breaks (eager-GPT bench model)",
         "value": count("gpt2-eager", "breaks"), "unit": "findings"},
        {"metric": "perf lint host syncs (eager-ResNet BN-stat class)",
         "value": count("resnet50-eager", "syncs"), "unit": "findings"},
        {"metric": "perf lint implicit reshards (sharded dryrun "
                   "models)",
         "value": (count("lenet-sharded", "reshards")
                   + count("tp-sharded", "reshards")),
         "unit": "findings"},
    ]
    return {"metric": "perf static analyzer gate (fusion breaks + "
                      "host syncs + implicit reshards on the bench "
                      "models; static-diff reconciled)",
            "value": payload["breaks"] + payload["syncs"]
            + payload["reshards"],
            "unit": "findings",
            "static_diff_ok": bool(sd["ok"]),
            "rows": rows}


def bench_compute():
    """Row 14: compute telemetry plane. Off contract asserted EXACTLY
    (the rows-5..11 counter technique) with the async flush pipeline
    ON: across a capped 32-op dispatch chain zero ``cost_analysis()``
    calls happen, zero FLOPs are counted, and the registry's MUTATIONS
    counter stays frozen. The reported value is the enabled-mode
    overhead per step on the same chain (per-op src capture + the
    per-execution FLOP count). The row json embeds the LeNet
    steady-state compute snapshot — MFU, achieved GFLOP/s, arithmetic
    intensity — via budget.collect; MFU and GFLOP/s ride as nested
    diff rows with up-good units so an efficiency regression gates
    mechanically."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu._core import async_flush
    from paddle_tpu.observability import budget as budget_mod
    from paddle_tpu.observability import compute as comptel
    from paddle_tpu.observability import metrics

    x = paddle.to_tensor(np.ones((16, 16), "float32"))

    def chain():
        y = x
        for _ in range(32):
            y = y * 1.0001 + 0.0001
        return y._value

    from paddle_tpu._core.flags import flag_value
    checks_was = flag_value("FLAGS_static_checks")
    # checks off for the freeze window: the warn-mode sanitizer sweep
    # counts registry work by design (the row-10/11 precedent)
    paddle.set_flags({"FLAGS_async_flush": True,
                      "FLAGS_lazy_max_segment_ops": 16,
                      "FLAGS_static_checks": "off"})
    try:
        _timeit(chain, steps=20, warmup=5)
        async_flush.drain()
        # ---------------- compute telemetry OFF: the freeze contract
        before = metrics.MUTATIONS
        calls0 = comptel.COST_CALLS
        flops0 = comptel.executed_flops()
        off_t = _timeit(chain, steps=100, warmup=0)
        async_flush.drain()
        assert metrics.MUTATIONS == before, \
            "compute-telemetry-off loop did registry work (must be 0)"
        assert comptel.COST_CALLS == calls0, \
            "compute-telemetry-off loop called cost_analysis"
        assert comptel.executed_flops() == flops0, \
            "compute-telemetry-off loop counted FLOPs (must be 0)"
        # ---------------- ON: enabled overhead per step
        paddle.set_flags({"FLAGS_compute_telemetry": True})
        try:
            on_t = _timeit(chain, steps=100, warmup=5)
            async_flush.drain()
            assert comptel.COST_CALLS > calls0, \
                "compute-telemetry-on loop captured no cost analysis"
            assert comptel.executed_flops() > flops0, \
                "compute-telemetry-on loop counted no FLOPs"
        finally:
            paddle.set_flags({"FLAGS_compute_telemetry": False})
    finally:
        paddle.set_flags({"FLAGS_async_flush": False,
                          "FLAGS_lazy_max_segment_ops": 256,
                          "FLAGS_static_checks": checks_was})
        async_flush.drain(raise_latched=False)

    # ---------------- LeNet steady-state compute snapshot
    from paddle_tpu.observability.__main__ import _lenet_step
    snap = budget_mod.collect(_lenet_step(), steps=8, warmup=3)
    comp = snap["compute"]
    assert comp["cost_analysis_calls_measured"] == 0, \
        "steady-state LeNet window re-ran cost_analysis (must be " \
        "captured once per compile)"
    return {"metric": "compute telemetry overhead (32-op capped chain; "
                      "off = zero cost_analysis calls + zero FLOPs "
                      "counted + frozen counters, async flush on)",
            "value": round((on_t - off_t) * 1e6, 2),
            "unit": "us/step overhead",
            "lenet_mfu": comp["mfu"],
            "lenet_gflops": comp["gflops_per_s"],
            "lenet_flops_per_step": comp["flops_per_step"],
            "lenet_arith_intensity": comp["arith_intensity"],
            "lenet_bound": comp["bound"],
            "rows": [{"metric": "LeNet steady-state MFU (b32 budget "
                                "window, per-chip peak)",
                      "value": comp["mfu"], "unit": "mfu"},
                     {"metric": "LeNet steady-state achieved GFLOP/s "
                                "(b32 budget window)",
                      "value": comp["gflops_per_s"],
                      "unit": "gflops"}]}


def bench_mem_lint():
    """Row 15: the mem static analyzer as a mechanical regression gate,
    the row-13 pattern in the BYTE domain. The --mem CLI records the
    bench models and prices the per-device train-step peak at the
    candidate pod shapes ({1x1, 4x2, 2x2x2}) in a subprocess — its
    exit code gates the row, and a planning budget is set so the
    oom_risk machinery is LIVE (a model that stops fitting its
    historical shape produces a new finding). The oom_risk count rides
    as a 'findings' row: --diff treats ANY increase as a regression
    (zero tolerance, matching row 13); the static per-device totals
    ride as byte rows (down-good) so footprint growth gates too.
    In-process, budget.static_diff proves the liveness prediction
    reconciles with the measured census watermark (the memory.peak
    no-false-clean row)."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # a 2 MB/device planning budget: lenet fits every shape (0
    # findings), gpt2-mini's activation-heavy step fits none (3) —
    # both verdict classes stay exercised, so the gate can neither rot
    # into always-clean nor mask a model growing past its shape
    env["FLAGS_memory_budget_bytes"] = str(2 << 20)
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--mem",
         "--json"],
        capture_output=True, text=True, env=env, timeout=1800)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        raise RuntimeError(
            f"analysis --mem failed rc={out.returncode}: "
            f"{out.stderr[-2000:]}")
    payload = json.loads(lines[-1])

    def shape_total(model, shape):
        for d in payload["models"].get(model, ()):
            for r in d["rows"]:
                if r["shape"] == shape:
                    return r["total_pd_bytes"]
        # a missing model/shape must FAIL the row, not feed a 0-byte
        # "improvement" into the down-good --diff gate
        raise RuntimeError(
            f"--mem payload missing {model} @ {shape}: "
            f"{sorted(payload['models'])}")

    # static-vs-measured reconciliation (memory.peak row) in-process
    from paddle_tpu.observability import budget
    from paddle_tpu.observability.__main__ import _lenet_step
    sd = budget.static_diff(_lenet_step(), steps=3)
    peak_rows = [r for r in sd["rows"] if r["class"] == "memory.peak"]
    assert peak_rows and peak_rows[0]["match"], \
        f"static liveness peak diverges from the byte plane: {sd}"
    assert sd["ok"], f"static-diff failed: {sd}"

    rows = [
        {"metric": "mem lint per-device step total "
                   "(lenet @ dp4xmp2 static plan)",
         "value": shape_total("lenet", [4, 2]), "unit": "bytes"},
        {"metric": "mem lint per-device step total "
                   "(gpt2-mini @ dp2xmp2xpp2 static plan)",
         "value": shape_total("gpt2-mini", [2, 2, 2]),
         "unit": "bytes"},
    ]
    return {"metric": "mem static analyzer gate (oom_risk findings on "
                      "the bench models' pod-shape sweep, 2MB/device "
                      "planning budget; memory.peak static-diff "
                      "reconciled)",
            "value": payload["oom_risk"],
            "unit": "findings",
            "budget_bytes": payload["budget_bytes"],
            "static_diff_ok": bool(sd["ok"]),
            "rows": rows}


def bench_goodput():
    """Row 16: goodput plane. Off contract asserted EXACTLY (the
    rows-5..15 counter technique) with the async flush pipeline ON and
    every new probe exercised on the off path: an ElasticStep-wrapped
    capped chain (step marks + recovery probes), a DevicePrefetcher
    pull from an exhausted-then-refilled source (the io::input_wait
    stall probe) and a CheckpointManager save (the ckpt::save span
    site) — across all of it the registry's MUTATIONS counter AND the
    goodput step ring stay frozen, and the ledger never starts. The
    reported value is the LeNet job goodput fraction over a budget
    window (unit 'goodput %', up-good in --diff); the structural
    badput buckets ride as us/step rows (down-good, 0 -> N gates like
    a findings row) and the bucket-additivity identity is asserted
    from the SAME ledger the budget's spans feed."""
    import tempfile

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu._core import async_flush
    from paddle_tpu.distributed.checkpoint import CheckpointManager
    from paddle_tpu.distributed.resilience import ElasticStep
    from paddle_tpu.io import DevicePrefetcher
    from paddle_tpu.observability import budget as budget_mod
    from paddle_tpu.observability import goodput as goodtel
    from paddle_tpu.observability import metrics

    x = paddle.to_tensor(np.ones((16, 16), "float32"))

    def chain():
        y = x
        for _ in range(16):
            y = y * 1.0001 + 0.0001
        return np.asarray(y._value)

    w = paddle.to_tensor(np.zeros((8, 8), "float32"))
    opt = paddle.optimizer.SGD(0.0, parameters=[w])
    elastic = ElasticStep(optimizer=opt)
    ckpt_dir = tempfile.mkdtemp(prefix="pt_goodput_ckpt_")

    from paddle_tpu._core.flags import flag_value
    checks_was = flag_value("FLAGS_static_checks")
    # checks off for the freeze window: the warn-mode sanitizer sweep
    # counts registry work by design (the rows-10..14 precedent)
    paddle.set_flags({"FLAGS_async_flush": True,
                      "FLAGS_lazy_max_segment_ops": 16,
                      "FLAGS_static_checks": "off"})
    try:
        _timeit(chain, steps=10, warmup=5)
        elastic.run(chain)           # warm the elastic path
        async_flush.drain()
        # ---------------- goodput OFF: the freeze contract
        before = metrics.MUTATIONS
        ring0 = goodtel.RING_MUTATIONS
        for _ in range(30):
            elastic.run(chain)
        for _ in DevicePrefetcher(iter([np.ones((4, 4), "float32")])):
            pass
        CheckpointManager(ckpt_dir, keep=1).save(
            {"w": np.zeros((8, 8), "float32")}, step=0)
        async_flush.drain()
        assert metrics.MUTATIONS == before, \
            "goodput-off loop did registry work (must be 0)"
        assert goodtel.RING_MUTATIONS == ring0, \
            "goodput-off loop mutated the step ring (must be 0)"
        assert not goodtel.LEDGER._started, \
            "goodput-off loop started the ledger"
    finally:
        paddle.set_flags({"FLAGS_async_flush": False,
                          "FLAGS_lazy_max_segment_ops": 256,
                          "FLAGS_static_checks": checks_was})
        async_flush.drain(raise_latched=False)
        elastic.shutdown()

    # ---------------- LeNet job goodput over a budget window (the
    # collect call turns the plane on, wraps each step with ledger
    # marks, and budget_section asserts the additivity identity)
    from paddle_tpu.observability.__main__ import _lenet_step
    snap = budget_mod.collect(_lenet_step(), steps=8, warmup=3)
    g = snap["goodput"]
    assert g["additivity_ok"], g
    per = g["buckets_us_per_step"]
    # the structural stall classes gate in --diff; host/idle are box
    # noise and ride the row json as plain fields instead
    rows = [{"metric": f"LeNet goodput badput: {b} "
                       "(b32 budget window)",
             "value": per.get(b, 0.0), "unit": "us/step badput"}
            for b in ("compile", "input_wait", "comm_wait", "ckpt_io",
                      "recovery")]
    rows.insert(0, {"metric": "LeNet job goodput fraction "
                              "(b32 budget window)",
                    "value": round((g["goodput_frac"] or 0.0) * 100.0,
                                   2),
                    "unit": "goodput %"})
    return {"metric": "goodput plane (off = frozen counters + frozen "
                      "step ring across elastic/prefetch/ckpt probes, "
                      "async flush on; LeNet bucket additivity "
                      "asserted)",
            "value": round((g["goodput_frac"] or 0.0) * 100.0, 2),
            "unit": "goodput %",
            "lenet_wall_us_per_step": g["wall_us_per_step"],
            "lenet_host_us_per_step": per.get("host", 0.0),
            "lenet_idle_us_per_step": per.get("idle", 0.0),
            "buckets_us_per_step": per,
            "rows": rows}


def bench_record_fastpath():
    """Row 17: the trace-stable record fast path + native record core.
    A 64-op elementwise chain under the default segment cap seals once
    per step, so the RECORD phase (time until the last op is recorded,
    the row-9 phase split) is pure per-op record work — the exact
    ~us/op tax BUDGET_r06 attributed the single-chip plateau to. Three
    legs, min of interleaved rounds:

      off     FLAGS_record_fast_path=false — the frozen pre-existing
              path (lazy.FAST_OPS asserted frozen across it);
      python  fast path on, native core forced out (lazy._NC /
              dispatch._EAGER_CORE = None) — the pure-python skeleton
              replay, which must stand alone and win measurably;
      native  fast path on with csrc/eager_core.cc's skel_record —
              match + commit in one C call per op (step replay held
              OFF so the leg keeps its per-op meaning);
      replay  fast path + FLAGS_step_replay_after=3: the promoted
              steady state hands the segment to the whole-step driver
              (eager_core.drive_record, one C call per op, no python
              gate) and the seal skips signature reconstruction.

    Gates: with the native library built, per-op-native record us/op
    must be >= 3x below the off leg, and the REPLAY leg must land
    under 1 us/op AMORTIZED over the 64-op step (the pure-python leg
    gates at a measurable >= 1.2x; REPLAY_STEPS is asserted advancing
    during the replay leg, frozen during off). The row json embeds a
    small gpt2-eager budget snapshot (host gap + record counters) so
    the win is priced on a real model's step, not just the
    microbench."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu._core import async_flush, dispatch, lazy
    from paddle_tpu.observability import budget as budget_mod

    x = paddle.to_tensor(np.ones((16, 16), "float32"))
    chain = 32          # 64 recorded ops, one materialize seal per step
    n_ops = chain * 2

    def run_phases():
        t0 = time.perf_counter()
        y = x
        for _ in range(chain):
            y = y * 1.0001 + 0.0001
        t1 = time.perf_counter()
        np.asarray(y._value)
        return t1 - t0

    native_mod = dispatch._eager_core()
    have_native = native_mod is not None \
        and hasattr(native_mod, "skel_record")

    def force_native(on):
        # the two prongs resolve/cached independently; the bench legs
        # force them in-process (the documented test/bench hook). The
        # on path RE-RESOLVES through lazy._native_core so bind_types
        # runs — handing lazy._NC a module whose types were never
        # bound would make every skel_record punt to python.
        if on and have_native:
            lazy._NC = None
            lazy._NC_TRIED = False
            dispatch._EAGER_CORE = native_mod
            lazy._native_core()
        else:
            lazy._NC = None
            lazy._NC_TRIED = True
            dispatch._EAGER_CORE = None if not on else native_mod

    def leg(fast_on, native_on, steps=60, replay=0):
        # replay=0 keeps the off/python/native legs per-op (their
        # historical --diff meaning); the replay leg re-enables the
        # default promotion threshold. Warmup covers arming (2 seals)
        # + the promotion streak (3 more), so the measured iterations
        # are all steady state.
        paddle.set_flags({"FLAGS_record_fast_path": fast_on,
                          "FLAGS_step_replay_after": replay})
        force_native(native_on)
        try:
            for _ in range(8):
                run_phases()
            return min(run_phases() for _ in range(steps))
        finally:
            paddle.set_flags({"FLAGS_record_fast_path": True,
                              "FLAGS_step_replay_after": 3})
            force_native(True)

    leg(False, True, steps=10)       # prime compiles off-clock
    leg(True, False, steps=10)
    fast0 = lazy.FAST_OPS
    replay0 = lazy.REPLAY_STEPS
    off_probe = leg(False, True, steps=10)
    assert lazy.FAST_OPS == fast0, \
        "FLAGS_record_fast_path=false did fast-path work (must be 0)"
    assert lazy.REPLAY_STEPS == replay0, \
        "fast-path-off leg sealed through a step plan (must be 0)"
    del off_probe

    rounds = []
    for _ in range(5):
        rounds.append((leg(False, True), leg(True, False),
                       leg(True, True) if have_native else None,
                       leg(True, True, replay=3)))
    replay_delta = lazy.REPLAY_STEPS - replay0
    assert replay_delta > 0, \
        "replay legs never promoted to whole-step replay"
    off = min(r[0] for r in rounds)
    py = min(r[1] for r in rounds)
    nat = min(r[2] for r in rounds) if have_native else None
    rep = min(r[3] for r in rounds)
    off_us = off * 1e6 / n_ops
    py_us = py * 1e6 / n_ops
    nat_us = nat * 1e6 / n_ops if nat else None
    rep_us = rep * 1e6 / n_ops
    best_us = rep_us if have_native else min(py_us, rep_us)

    assert off_us / py_us >= 1.2, \
        f"pure-python fast path shows no measurable win " \
        f"({off_us:.2f} -> {py_us:.2f} us/op)"
    if have_native:
        assert off_us / nat_us >= 3.0, \
            f"record fast path below the 3x gate " \
            f"({off_us:.2f} -> {nat_us:.2f} us/op)"
        assert rep_us < 1.0, \
            f"step replay above the 1 us/op amortized gate " \
            f"({rep_us:.3f} us/op over the {n_ops}-op step)"

    # gpt2-eager budget snapshot: the host-gap row prices the win on a
    # real model (small config so the row stays affordable)
    genv = {"BUDGET_GPT_LAYERS": "2", "BUDGET_GPT_HIDDEN": "64",
            "BUDGET_GPT_SEQ": "64", "BUDGET_BATCH": "2"}
    saved_env = {k: os.environ.get(k) for k in genv}
    os.environ.update(genv)
    try:
        from paddle_tpu.observability.__main__ import _gpt2_step
        fast0 = lazy.FAST_OPS
        snap = budget_mod.collect(_gpt2_step(), steps=4, warmup=2)
        gpt2 = {"wall_us_per_step": snap["wall_us_per_step"],
                "host_gap_us_per_step": snap["host_gap_us_per_step"],
                "record_fast_ops": lazy.FAST_OPS - fast0,
                "counters": {k: v for k, v in snap["counters"].items()
                             if k.startswith(("record.", "segment.ops",
                                              "fusion."))}}
    finally:
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        async_flush.drain(raise_latched=False)

    rows = [{"metric": "record-phase overhead (fast path on, best "
                       "available core)",
             "value": round(best_us, 3), "unit": "us/op"},
            {"metric": "record-phase overhead (pure-python fast path)",
             "value": round(py_us, 3), "unit": "us/op"},
            {"metric": "record-phase overhead (whole-step replay, "
                       "amortized)",
             "value": round(rep_us, 3), "unit": "us/op"}]
    return {"metric": f"record fast path ({n_ops}-op microbench; "
                      f"off-freeze + pure-python win asserted"
                      + (" + native 3x + replay <1us/op gates"
                         if have_native else "") + ")",
            "value": round(off_us / best_us, 2),
            "unit": "x record-phase cut",
            "record_us_per_op_off": round(off_us, 3),
            "record_us_per_op_python": round(py_us, 3),
            "record_us_per_op_native": (round(nat_us, 3)
                                        if nat_us else None),
            "record_us_per_op_replay": round(rep_us, 3),
            "replay_steps_sealed": int(replay_delta),
            "native_core_available": bool(have_native),
            "gpt2_budget": gpt2,
            "rows": rows}


def _warm_restart_worker(cache_dir: str) -> None:
    """Row-18 subprocess body (`bench_suite.py --warm-restart-worker
    DIR`): one fresh-process run against a shared persistent
    executable cache. Emits one json line with the first-seal latency
    (real compile when cold, disk load when warm), the goodput compile
    bucket over a distinct-shape step window, and the full compiles.*
    / cache.persist.* counter snapshots the parent asserts on."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.observability import budget as budget_mod
    from paddle_tpu.observability import metrics

    paddle.set_flags({"FLAGS_static_checks": "off",
                      "FLAGS_observability": True,
                      "FLAGS_executable_cache_dir": cache_dir})
    x = paddle.to_tensor(np.full((32, 32), 1.5, "float32"))

    def first_seal():
        y = x
        for _ in range(16):
            y = y * 1.001 + 0.001
        return np.asarray(y._value)

    t0 = time.perf_counter()
    first_seal()
    first_ms = (time.perf_counter() - t0) * 1000.0

    # a second, distinct-shape step so its cold compiles (or warm disk
    # loads) land INSIDE the goodput budget window (warmup=0)
    z = paddle.to_tensor(np.full((16, 48), 0.5, "float32"))

    def step():
        w = z
        for _ in range(12):
            w = w * 1.002 + 0.002
        return np.asarray(w._value)

    snap = budget_mod.collect(step, steps=4, warmup=0)
    counters = metrics.snapshot()["counters"]
    print(json.dumps(
        {"first_step_ms": round(first_ms, 3),
         "compile_us_per_step":
             snap["goodput"]["buckets_us_per_step"].get("compile", 0.0),
         "compiles": {k: v for k, v in counters.items()
                      if k.startswith("compiles.")},
         "persist": {k: v for k, v in counters.items()
                     if k.startswith("cache.persist.")}}), flush=True)


def bench_warm_restart():
    """Row 18: warm-restart drill over the persistent executable
    cache. Two FRESH python processes run the same worker body
    (`--warm-restart-worker`) against one shared
    FLAGS_executable_cache_dir: the first (cold) pays real
    lower().compile() for every segment and persists each executable;
    the second (warm) must reconstruct its steady state from disk —
    ZERO fresh compiles.* counters (asserted exactly), cache.persist
    hits > 0, and a goodput compile bucket ~0 in its budget window
    (<= max(50us, 5% of cold)). The reported value is the warm
    first-step latency; cold rides alongside so --diff prices restart
    time down-good. An in-process off leg then holds BOTH
    FLAGS_executable_cache_dir="" and FLAGS_step_replay_after=0 and
    asserts the disabled planes are exactly free: persist inactive,
    cache.persist.* counters frozen (zero disk traffic), and
    lazy.REPLAY_STEPS frozen."""
    import shutil
    import subprocess
    import sys
    import tempfile

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu._core import lazy, persist
    from paddle_tpu._core.flags import flag_value
    from paddle_tpu.observability import metrics

    cache_dir = tempfile.mkdtemp(prefix="ptxc_restart_")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.dirname(os.path.abspath(__file__)),
                    env.get("PYTHONPATH")) if p)

    def run_once(tag):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--warm-restart-worker", cache_dir],
            capture_output=True, text=True, env=env, timeout=600)
        lines = [ln for ln in out.stdout.splitlines()
                 if ln.startswith("{")]
        if out.returncode != 0 or not lines:
            raise RuntimeError(
                f"{tag} warm-restart worker failed "
                f"rc={out.returncode}: {out.stderr[-2000:]}")
        return json.loads(lines[-1])

    try:
        cold = run_once("cold")
        warm = run_once("warm")
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)

    def fresh_compiles(snap):
        # compiles.bytes.* are the byte-plane meters — warm sidecar
        # loads re-note them by design; only the cache-miss counters
        # (compiles.segment / fused_step / spmd) mean a real lower()
        return {k: v for k, v in snap["compiles"].items()
                if not k.startswith("compiles.bytes.")}

    assert sum(fresh_compiles(cold).values()) > 0, \
        "cold run compiled nothing — the drill proves nothing"
    assert sum(fresh_compiles(warm).values()) == 0, \
        f"warm restart recompiled: {fresh_compiles(warm)}"
    assert warm["persist"].get("cache.persist.hit", 0) > 0, \
        "warm restart never consulted the persistent cache"
    cold_c = cold["compile_us_per_step"]
    warm_c = warm["compile_us_per_step"]
    assert warm_c <= max(50.0, 0.05 * cold_c), \
        f"warm goodput compile bucket not ~0: {warm_c} us/step " \
        f"(cold {cold_c})"

    # ---------------- off leg: both planes disabled must be free
    checks_was = flag_value("FLAGS_static_checks")
    paddle.set_flags({"FLAGS_static_checks": "off",
                      "FLAGS_step_replay_after": 0,
                      "FLAGS_executable_cache_dir": ""})
    try:
        assert not persist.ACTIVE, \
            "persist plane active without a cache dir"
        x = paddle.to_tensor(np.full((24, 24), 1.25, "float32"))

        def chain():
            y = x
            for _ in range(12):
                y = y * 1.003 + 0.003
            return np.asarray(y._value)

        chain()                        # settle the compile off-clock

        def persist_counters():
            return {k: v for k, v in
                    metrics.snapshot()["counters"].items()
                    if k.startswith("cache.persist.")}

        p0 = persist_counters()
        r0 = lazy.REPLAY_STEPS
        for _ in range(10):
            chain()
        assert persist_counters() == p0, \
            "persist-off loop touched the disk cache (must be 0)"
        assert lazy.REPLAY_STEPS == r0, \
            "FLAGS_step_replay_after=0 sealed through a step plan " \
            "(must be 0)"
    finally:
        paddle.set_flags({"FLAGS_static_checks": checks_was,
                          "FLAGS_step_replay_after": 3})

    rows = [{"metric": "warm-restart first-step latency "
                       "(persistent cache warm, fresh process)",
             "value": warm["first_step_ms"], "unit": "ms"},
            {"metric": "cold-start first-step latency "
                       "(fresh process, empty cache)",
             "value": cold["first_step_ms"], "unit": "ms"},
            {"metric": "warm-restart goodput compile bucket "
                       "(budget window, fresh process)",
             "value": warm_c, "unit": "us/step badput"}]
    return {"metric": "warm restart (two fresh processes, shared "
                      "executable cache; zero fresh compiles.* + "
                      "compile bucket ~0 asserted on the second; "
                      "off leg = frozen persist/replay counters)",
            "value": warm["first_step_ms"],
            "unit": "ms",
            "cold_first_step_ms": cold["first_step_ms"],
            "warm_first_step_ms": warm["first_step_ms"],
            "cold_compile_us_per_step": cold_c,
            "warm_compile_us_per_step": warm_c,
            "cold_compiles": cold["compiles"],
            "warm_persist_hits":
                warm["persist"].get("cache.persist.hit", 0),
            "rows": rows}


def bench_plan():
    """Row 19: the static auto-parallelism planner as a regression
    gate. `--plan --json` records the row-12 dryrun-sweep model in a
    subprocess and ranks EVERY dp×mp×pp factorization of world 8
    against the static planes (propagated comm bytes, liveness peak,
    per-chip FLOPs + pipeline bubble). The gate asserts the planner's
    pick equals the sweep's measured-best shape (dp8 — the dp ladder
    row 12 times is fastest at full data parallelism for this model),
    that the validated winner carries ZERO reshard/pipeline findings,
    and plan latency rides --diff as a ms row (down-good) so planner
    cost creep gates too."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--plan",
         "--json", "--world", "8"],
        capture_output=True, text=True, env=env, timeout=1800)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        raise RuntimeError(
            f"analysis --plan failed rc={out.returncode}: "
            f"{out.stderr[-2000:]}")
    payload = json.loads(lines[-1])
    best = payload.get("best")
    if not best or best["shape"] != [8, 1, 1]:
        raise RuntimeError(
            f"planner pick {best and best['shape']} != the "
            f"measured-best dp8 of the dryrun sweep: "
            f"{[c['desc'] for c in payload.get('candidates', ())[:4]]}")
    assert payload["validated"], "winner skipped validation"
    assert payload["winner_findings"] == 0, \
        f"validated winner carries findings: {payload}"
    n_feasible = sum(1 for c in payload["candidates"] if c["feasible"])
    rows = [
        {"metric": "auto-parallel plan latency (world-8 full "
                   "dp×mp×pp factorization sweep)",
         "value": payload["plan_ms"], "unit": "ms"},
    ]
    return {"metric": "auto-parallel planner gate (pick == "
                      "measured-best dp8 on the dryrun sweep; winner "
                      "validated through reshard+pipeline checkers, "
                      "findings)",
            "value": payload["winner_findings"],
            "unit": "findings",
            "best": best["desc"],
            "candidates": len(payload["candidates"]),
            "feasible": n_feasible,
            "rows": rows}


# ------------------------------------------------------------- diff mode

def bench_monitor():
    """Row 20: live monitoring plane. With FLAGS_monitor off (and the
    async flush pipeline on — the hardest freeze regime) the plane must
    be exactly free: frozen registry MUTATIONS across the workload, no
    sampler thread, no bound port (the rows 6/10/11 gate pattern). The
    reported value is monitor-on sampling overhead us/step on the
    64-op chain driven through ElasticStep (so the step hook is on the
    measured path), min-of-interleaved-rounds; the nested row is the
    /metrics scrape latency of the stdlib exporter."""
    import sys
    import time as _time
    import urllib.request

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed.resilience import ElasticStep
    from paddle_tpu.observability import metrics

    x = paddle.to_tensor(np.ones((16, 16), "float32"))
    chain = 32                      # 64 ops: mul + add per iteration
    w = paddle.to_tensor(np.zeros((4, 4), "float32"))
    opt = paddle.optimizer.SGD(0.0, parameters=[w])
    elastic = ElasticStep(optimizer=opt)

    def run():
        def step():
            y = x
            for _ in range(chain):
                y = y * 1.0001 + 0.0001
            return y._value
        return elastic.run(step)

    # ---- off-freeze: monitor off + async flush on does ZERO work
    paddle.set_flags({"FLAGS_monitor": False, "FLAGS_async_flush": True})
    try:
        _timeit(run, steps=20, warmup=10)   # prime compile/cache
        from paddle_tpu._core import async_flush
        async_flush.drain()
        before = metrics.MUTATIONS
        _timeit(run, steps=50, warmup=0)
        async_flush.drain()
        assert metrics.MUTATIONS == before, \
            "FLAGS_monitor=off did registry work (must be 0)"
        ts = sys.modules.get("paddle_tpu.observability.timeseries")
        assert ts is None or not ts.sampler_alive(), \
            "FLAGS_monitor=off left a sampler thread running"
        from paddle_tpu.observability import exporter
        assert exporter.bound_port() is None, \
            "FLAGS_monitor=off left the exporter port bound"
    finally:
        paddle.set_flags({"FLAGS_async_flush": False})

    # ---- sampling overhead: interleaved off/on rounds
    def timed(on):
        paddle.set_flags({"FLAGS_monitor": on,
                          "FLAGS_monitor_interval_s": 0.05,
                          "FLAGS_monitor_port": 0})
        try:
            return _timeit(run, steps=100, warmup=10)
        finally:
            paddle.set_flags({"FLAGS_monitor": False})

    rounds = [(timed(False), timed(True)) for _ in range(5)]
    off = min(r[0] for r in rounds)
    on = min(r[1] for r in rounds)
    overhead_us = (on - off) * 1e6

    # ---- /metrics scrape latency (ephemeral loopback port)
    from paddle_tpu.observability import exporter, timeseries
    paddle.set_flags({"FLAGS_monitor": True,
                      "FLAGS_monitor_interval_s": 0.05,
                      "FLAGS_monitor_port": 0})
    try:
        port = exporter.start(0)
        for _ in range(10):
            run()
        timeseries.sample_once({})
        url = f"http://127.0.0.1:{port}/metrics"
        body = urllib.request.urlopen(url, timeout=10).read()  # warm
        assert b"# TYPE" in body, "scrape returned no typed metrics"
        t0 = _time.perf_counter()
        n = 20
        for _ in range(n):
            urllib.request.urlopen(url, timeout=10).read()
        scrape_ms = (_time.perf_counter() - t0) / n * 1e3
    finally:
        paddle.set_flags({"FLAGS_monitor": False})

    return {"metric": f"monitor sampling overhead ({chain * 2}-op "
                      f"chain under ElasticStep; off = 0 mutations / "
                      f"no thread / no port asserted)",
            "value": round(overhead_us, 2),
            "unit": "us/step sampling overhead",
            "rows": [{"metric": "monitor /metrics scrape latency "
                                "(stdlib exporter, loopback)",
                      "value": round(scrape_ms, 2),
                      "unit": "ms/scrape"}]}


def bench_numerics():
    """Row 21: the numerics plane as a mechanical regression gate. The
    --numerics CLI sweeps the model zoo under bf16 auto_cast in a
    subprocess (exit code + zero error-severity findings gate the
    row; per-model finding counts become zero-tolerance diff rows).
    Off contract asserted exactly (the rows-5..11 counter technique)
    WITH the async flush pipeline on: across a bf16 matmul+softmax
    chain — a segment the pre-scan cannot skip — checks-off freezes
    every sanitizer.diagnostics.numerics.* counter and the sweep
    count. The reported value is warn-mode overhead us/op (range
    propagation + the three segment checkers) on the same chain,
    min-of-interleaved-rounds."""
    import subprocess
    import sys

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu._core import async_flush
    from paddle_tpu.analysis import hooks
    from paddle_tpu.observability import metrics

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.analysis", "--numerics",
         "--json"],
        capture_output=True, text=True, env=env, timeout=1800)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not lines:
        raise RuntimeError(
            f"analysis --numerics failed rc={out.returncode}: "
            f"{out.stderr[-2000:]}")
    payload = json.loads(lines[-1])
    assert payload["errors"] == 0, \
        f"numerics zoo sweep found error-severity findings: {payload}"

    # ---- workload with a numerics surface (bf16 outputs force the
    # propagation; matmul+softmax keeps the lattice bounded -> clean)
    x = paddle.to_tensor(np.full((16, 16), 1.0 / 16.0, "float32"))
    chain = 16

    def run():
        y = x.astype("bfloat16")
        for _ in range(chain):
            y = F.softmax(paddle.matmul(y, y))
        return y.astype("float32")._value

    n_ops = 2 * chain + 2            # casts + (matmul, softmax) * chain

    # ---- off-freeze: checks off + async flush on does ZERO numerics
    # work (no sweeps, no counters)
    paddle.set_flags({"FLAGS_static_checks": "off",
                      "FLAGS_async_flush": True})
    try:
        _timeit(run, steps=10, warmup=5)     # prime compile/cache
        async_flush.drain()

        def _numerics_counters():
            return {k: v for k, v
                    in metrics.snapshot()["counters"].items()
                    if k.startswith("sanitizer.diagnostics.numerics.")}

        before = _numerics_counters()
        sweeps = hooks.segment_sweeps()
        _timeit(run, steps=30, warmup=0)
        async_flush.drain()
        assert _numerics_counters() == before, \
            "FLAGS_static_checks=off moved a numerics counter"
        assert hooks.segment_sweeps() == sweeps, \
            "FLAGS_static_checks=off ran a sanitizer sweep"
    finally:
        paddle.set_flags({"FLAGS_async_flush": False})

    # ---- warn-mode overhead: interleaved off/warn rounds
    def timed(mode):
        paddle.set_flags({"FLAGS_static_checks": mode})
        try:
            return _timeit(run, steps=50, warmup=10)
        finally:
            paddle.set_flags({"FLAGS_static_checks": "off"})

    rounds = [(timed("off"), timed("warn")) for _ in range(5)]
    off = min(r[0] for r in rounds)
    on = min(r[1] for r in rounds)
    overhead_us_op = (on - off) * 1e6 / n_ops

    rows = [
        {"metric": f"numerics zoo findings ({m})",
         "value": sum(d.get("findings", 0) for d in ds),
         "unit": "findings"}
        for m, ds in sorted(payload["models"].items())
    ]
    return {"metric": "numerics plane gate (zoo sweep under bf16 "
                      "auto_cast + int8 bucket budget; off = frozen "
                      "numerics counters / no sweeps asserted)",
            "value": round(overhead_us_op, 3),
            "unit": "us/op warn-mode overhead",
            "zoo_findings": payload["findings"],
            "rows": rows}


def bench_elastic_grow():
    """Row 22: fleet elasticity. Three legs:

    - faults-off freeze (WITH async flush on): an AdaptiveTrainer loop
      wired for growth (joined_ranks set, checkpoint manager attached)
      must keep EVERY resilience.* counter frozen — including all the
      new growth/preemption ones (world_grows, grows, grow_bcast_*,
      grow_joins, bcast_restores, preempt_notices, preempt_ckpts) —
      when no event fires; the membership poll stays one module-level
      bool.
    - grow drill: an injected member::join grows a logical 6-mesh to 8
      through the planner + sanitizer + grow_world + broadcast-publish
      pipeline; the reported value is grow latency, membership change
      -> first post-grow step (recompile priced in), down-good under
      --diff.
    - preempt-restore drill: FLAGS_checkpoint_interval_steps bounds
      the interval-only badput to < interval steps; a preempt::notice
      checkpoints IMMEDIATELY so the noticed badput is 0 steps; the
      replacement's restore+replay wall is priced in the goodput
      `recovery` bucket (asserted > 0) and rides --diff as ms
      down-good."""
    import shutil
    import tempfile

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu._core import async_flush
    from paddle_tpu.distributed.mesh import ProcessMesh
    from paddle_tpu.distributed.resilience import AdaptiveTrainer
    from paddle_tpu.observability import goodput, metrics
    from paddle_tpu.vision.models import LeNet

    def build(world, **kw):
        paddle.seed(0)
        model = LeNet()
        opt = paddle.optimizer.Adam(1e-3,
                                    parameters=model.parameters())
        rng = np.random.RandomState(0)
        bx = paddle.to_tensor(
            rng.randn(32, 1, 28, 28).astype(np.float32))
        by = paddle.to_tensor(
            rng.randint(0, 10, (32,)).astype(np.int64))
        trainer = AdaptiveTrainer(
            optimizer=opt,
            mesh=ProcessMesh(list(range(world)), dim_names=["dp"]),
            **kw)

        def step():
            loss = F.cross_entropy(model(bx), by)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss._value

        return trainer, step

    def res_counters():
        return {k: v for k, v in metrics.snapshot()["counters"].items()
                if k.startswith("resilience.")}

    # ---------------- faults-off freeze over the NEW counters
    trainer, step = build(6, joined_ranks=[6, 7])
    paddle.set_flags({"FLAGS_async_flush": True})
    try:
        np.asarray(trainer.run(step))        # settle compiles
        async_flush.drain()
        before = res_counters()
        _timeit(lambda: trainer.run(step), steps=5, warmup=0)
        async_flush.drain()
        after = res_counters()
        assert after == before, \
            f"faults-off growth-wired loop did resilience work: " \
            f"{before} -> {after}"
    finally:
        paddle.set_flags({"FLAGS_async_flush": False})

    # ---------------- grow drill: 6 -> 8 through the full pipeline
    paddle.set_flags({"FLAGS_fault_inject": "member::join@2=die"})
    try:
        for _ in range(3):
            np.asarray(trainer.run(step))
    finally:
        paddle.set_flags({"FLAGS_fault_inject": ""})
    assert trainer.grows == 1 and trainer.last_grow_latency_s, \
        "no grow measured"
    assert trainer.mesh.size == 8
    grow_ms = round(trainer.last_grow_latency_s * 1000.0, 2)

    # ---------------- preempt-restore drill
    interval = 3
    kill_step = 8
    ckpt_dir = tempfile.mkdtemp(prefix="ptxc_preempt_")
    paddle.set_flags({"FLAGS_checkpoint_interval_steps": interval})
    try:
        # leg A: interval checkpoints only — lost work < one interval
        t_a, s_a = build(8, checkpoint_dir=ckpt_dir)
        for _ in range(kill_step):
            np.asarray(t_a.run(s_a))         # saves at steps 3 and 6
        t_a.shutdown()                        # "SIGKILL" at step 8
        paddle.set_flags({"FLAGS_goodput": True})
        try:
            t0 = time.perf_counter()
            goodput.recovery_begin()
            fresh, s_f = build(8, checkpoint_dir=ckpt_dir)
            fresh.restore_from_checkpoint()
            badput_steps = kill_step - fresh.step_index
            while fresh.step_index < kill_step:   # replay = badput
                np.asarray(fresh.run(s_f))
            goodput.recovery_end()
            recover_ms = (time.perf_counter() - t0) * 1000.0
            bucket = goodput.snapshot()["buckets"]["recovery"]
            assert bucket > 0, \
                "recovery wall not priced in the goodput bucket"
        finally:
            paddle.set_flags({"FLAGS_goodput": False})
        assert 0 < badput_steps < interval, \
            f"interval-only badput {badput_steps} not bounded by " \
            f"the {interval}-step checkpoint interval"
        fresh.shutdown()

        # leg B: a preemption NOTICE checkpoints immediately — the
        # replacement resumes at the kill step, zero lost steps
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        t_b, s_b = build(8, checkpoint_dir=ckpt_dir)
        notices = metrics.counter("resilience.preempt_notices").value
        paddle.set_flags({"FLAGS_fault_inject":
                          f"preempt::notice@{kill_step}=fail"})
        try:
            for _ in range(kill_step):
                np.asarray(t_b.run(s_b))
        finally:
            paddle.set_flags({"FLAGS_fault_inject": ""})
        assert metrics.counter("resilience.preempt_notices").value \
            == notices + 1
        assert t_b.preempt_checkpoints == 1
        t_b.shutdown()
        fresh_b, s_fb = build(8, checkpoint_dir=ckpt_dir)
        fresh_b.restore_from_checkpoint()
        noticed_badput = (kill_step - 1) - fresh_b.step_index
        assert noticed_badput == 0, \
            f"preemption notice left {noticed_badput} lost step(s)"
        fresh_b.shutdown()
    finally:
        paddle.set_flags({"FLAGS_checkpoint_interval_steps": 0})
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    trainer.shutdown()

    return {"metric": "elastic grow latency (6->8 member::join, "
                      "membership change -> first post-grow step; "
                      "faults-off = frozen resilience.* counters over "
                      "every growth/preemption counter, async flush "
                      "on)",
            "value": grow_ms,
            "unit": "ms",
            "grow_plan": {k: trainer.last_plan.get(k) for k in
                          ("dp_degree", "mp_degree", "pp_degree")},
            "interval_badput_steps": badput_steps,
            "noticed_badput_steps": noticed_badput,
            "checkpoint_interval_steps": interval,
            "recovery_bucket_us": round(bucket, 1),
            "rows": [{"metric": "preempt-restore recovery wall "
                                "(verified-generation restore + "
                                "replay, goodput recovery bucket)",
                      "value": round(recover_ms, 2), "unit": "ms"}]}


def _rows_of(path: str) -> dict:
    """metric -> (value, unit) extracted from one driver BENCH_*.json
    (json lines live in its 'tail' string; the headline row carries
    nested 'rows')."""
    with open(path) as f:
        doc = json.load(f)
    out = {}

    def adopt(obj):
        if isinstance(obj, dict) and "metric" in obj \
                and isinstance(obj.get("value"), (int, float)):
            out[obj["metric"]] = (float(obj["value"]),
                                  str(obj.get("unit", "")))
        if isinstance(obj, dict):
            for r in obj.get("rows", ()):
                adopt(r)

    for line in str(doc.get("tail", "")).splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            adopt(json.loads(line))
        except ValueError:
            continue
    return out


def _lower_is_better(metric: str, unit: str) -> bool:
    """Direction from the UNIT first: a rate (tokens/s, images/s,
    ops/s, 'x' speedup) is higher-is-better even when the metric NAME
    says 'overhead' (row 4 reports dispatch overhead AS a rate). Byte
    units (row 11's peak-HBM snapshot) are cost: down-good. Only
    unit-less cost words fall back to the name."""
    u = unit.lower()
    # a RATE unit ends its first token with '/s' (tokens/s, ops/s);
    # 'us/step publication overhead' must not match. Efficiency units
    # (mfu, gflops — bench row 14's LeNet snapshot rows — and row 16's
    # 'goodput %') are up-good: an efficiency drop is exactly the
    # regression those planes gate.
    first = u.split()[0] if u.split() else ""
    if first.endswith("/op") or first.endswith("/step") \
            or first.endswith("/scrape"):
        # per-op cost (row 17's record-phase us/op legs), per-step
        # cost (row 20's sampling overhead) and per-scrape latency
        # (row 20's exporter leg): down-good
        return True
    if first.endswith("/s") or u.startswith("x ") \
            or first in ("mfu", "gflops", "goodput"):
        return False
    text = f"{metric} {u}".lower()
    return any(w in text for w in ("overhead", "latency", "ms", "% ",
                                   "bytes", "badput"))


def diff_mode(threshold: float = 0.10) -> int:
    """Compare the newest two BENCH_*.json in the cwd; exit non-zero on
    a >threshold regression in any metric present in both."""
    import glob
    # name order, not mtime: the driver writes BENCH_r<NN>.json with
    # zero-padded round numbers; checkouts scramble mtimes
    files = sorted(glob.glob("BENCH_*.json"))
    if len(files) < 2:
        print(f"bench --diff: need two BENCH_*.json, found {files}")
        return 2
    old_path, new_path = files[-2], files[-1]
    old, new = _rows_of(old_path), _rows_of(new_path)
    # a zero old value is only comparable for count rows ('findings')
    # and row 16's badput buckets: 0 -> 1 findings (or 0 -> a new
    # stall class) is exactly the regression those gates exist to
    # catch, while a 0 rate/latency row is a broken sample
    shared = [m for m in new
              if m in old and (old[m][0] or old[m][1] == "findings"
                               or "badput" in old[m][1])]
    regressions = []
    for m in shared:
        ov, unit = old[m]
        nv = new[m][0]
        if unit == "findings":
            # perf-lint counts gate with ZERO tolerance: any new
            # fusion break / host sync / implicit reshard on the bench
            # models is a regression, however small the percentage
            change = (nv - ov) / abs(ov) if ov else (1.0 if nv else 0.0)
            worse = nv > ov
        elif "badput" in unit and not ov:
            # a badput bucket appearing from zero is a NEW stall class
            # (injected feed stall, recovery in a clean run) — gate it
            # above a 50us/step floor so rounding noise cannot trip it
            change = 1.0 if nv else 0.0
            worse = nv > 50.0
        else:
            change = (nv - ov) / abs(ov)
            worse = change > threshold if _lower_is_better(m, unit) \
                else change < -threshold
        mark = "REGRESSION" if worse else "ok"
        print(f"  [{mark:>10}] {change * 100:+7.1f}%  {m}  "
              f"({ov:g} -> {nv:g} {unit})")
        if worse:
            regressions.append(m)
    print(f"bench --diff: {old_path} -> {new_path}, "
          f"{len(shared)} shared row(s), "
          f"{len(regressions)} regression(s)")
    if not shared:
        # a gate that compared nothing must not pass: zero shared rows
        # means the BENCH format drifted (renamed 'tail', truncated
        # file, re-worded metrics) — exactly when silent drift hides
        print("FAILED: no shared rows — BENCH format drift?")
        return 2
    if regressions:
        print("FAILED rows:\n  " + "\n  ".join(regressions))
        return 1
    return 0


def main():
    import sys
    if "--diff" in sys.argv[1:]:
        raise SystemExit(diff_mode())
    if "--spmd-dryrun" in sys.argv[1:]:
        i = sys.argv.index("--spmd-dryrun")
        _spmd_dryrun_worker(int(sys.argv[i + 1]))
        return
    if "--warm-restart-worker" in sys.argv[1:]:
        i = sys.argv.index("--warm-restart-worker")
        _warm_restart_worker(sys.argv[i + 1])
        return
    from paddle_tpu._core.device import enable_compile_cache
    enable_compile_cache()
    rows = os.environ.get(
        "BENCH_ROWS",
        "1,2,3,4,5,6,7,8,9,10,11,12,13,14,15,16,17,18,19,20,21,22"
        ).split(",")
    table = {"1": bench_lenet, "2": bench_resnet50, "3": bench_bert,
             "4": bench_dispatch, "5": bench_static_checks,
             "6": bench_observability, "7": bench_resilience,
             "8": bench_replan, "9": bench_async_flush,
             "10": bench_telemetry, "11": bench_memory,
             "12": bench_spmd_multichip, "13": bench_perf_lint,
             "14": bench_compute, "15": bench_mem_lint,
             "16": bench_goodput, "17": bench_record_fastpath,
             "18": bench_warm_restart, "19": bench_plan,
             "20": bench_monitor, "21": bench_numerics,
             "22": bench_elastic_grow}
    for r in rows:
        r = r.strip()
        out = table[r]()
        out["row"] = int(r)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
