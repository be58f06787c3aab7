"""CLI: trace the model zoo + distributed configs, run the sanitizer.

    python -m paddle_tpu.analysis
        [--models lenet,resnet50,bert,reshard,replan,pipeline]
        [--execute] [--verbose] [--json] [--fix]
    python -m paddle_tpu.analysis --perf
        [--models gpt2-eager,resnet50-eager,lenet-sharded,tp-sharded]
        [--json]
    python -m paddle_tpu.analysis --mem
        [--mesh dp,mp[,pp]] [--models lenet,gpt2-mini] [--json]

Default is record-only: each model's forward(+loss) is RECORDED into a
lazy capture window (aval inference, no XLA compile/run), the segment
checkers sweep the pending program, and for the eager models a static
Program is also recorded and swept through the default IR pass pipeline
with the post-pass verify hook armed. The distributed models sweep the
reshard placement-transition matrix and the four pipeline schedules.
`--execute` additionally flushes each segment end to end. `--json`
emits the machine-readable report (the observability CLI's snapshot
shape: headline numbers + a `counters` block). `--fix` plans the
mechanical repairs for every finding and prints the dry-run diff (the
runtime equivalent is `FLAGS_static_checks=fix`). Exit code 0 = no
findings (post-fix findings when --fix).

``--perf`` switches to the PERFORMANCE lint (analysis/perf_checks.py +
sharding_prop.py): the eager bench models are traced for one step and
every fusion-window break (eager-GPT's per-layer `record_fallback`)
and host sync (eager-ResNet's batch-norm running-stat class) is
reported with source attribution and the predicted seal-reason
histogram (`budget --static-diff` reconciles these against measured
counters); the sharded models record under a dryrun dp×mp mesh and
run the PartitionSpec propagation sweep (implicit reshards, mp-layer
round trips, comm-hotspot ranking). Needs ≥4 devices for the dryrun
mesh — on a single-device host the CLI re-execs itself with 8 forced
CPU devices. Perf findings are expected (exit 0 reports them; nothing
compares their counts from one PR to the next: ROADMAP C5).

``--mem`` switches to the MEM lint (analysis/mem_liveness.py): each
bench model's forward+loss is recorded (aval inference only) and the
full train-step per-device footprint — liveness peak + optimizer
state + compiled-temp estimate — is priced at candidate pod shapes
(default dp×mp ∈ {1×1, 4×2, 2×2×2}; ``--mesh 4,2`` picks one) via
`CandidateMesh`, i.e. WITHOUT compiling and on a host that cannot
build the mesh. With FLAGS_memory_budget_bytes set, shapes that do
not fit carry ``oom_risk`` findings (tests/test_mem_analysis.py seeds
one; no test gates the CLI's count: ROADMAP C5). Exit 0 reports
findings, like --perf.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_JSON = {"models": {}}
_FIX = False        # set by --fix: plan repairs + print dry-run diffs


def _note(name: str, report):
    _JSON["models"].setdefault(name, []).append(report.to_dict())


def _trace_eager(build_fn, name: str, execute: bool, verbose: bool):
    """Record one train-shaped forward into a capture window and sweep
    it. Returns the CheckReport (the dry-run residual under --fix)."""
    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu._core import lazy

    with lazy.lazy_guard(max_segment_ops=1 << 30) as ctx:
        # hold the root alive through the sweep: a dropped loss tensor
        # would (correctly) flag the whole trace as dead captures
        out = build_fn()
        report = analysis.check_segment(ctx, process=True)
        n_ops = len(ctx.pending)
        if _FIX and not report.ok:
            result, report = analysis.fix_segment(ctx, report,
                                                  dry_run=True)
            print(result.diff())
        if execute:
            ctx.flush("cli")
        else:
            ctx._reset_segment()
    print(f"[{name}] eager segment: {n_ops} ops recorded, "
          f"{len(report.diagnostics)} finding(s)"
          + (" (executed)" if execute else ""))
    if verbose or not report.ok:
        for d in report.diagnostics:
            print("   ", d.render())
    _note(name, report)
    return report


def _trace_static(build_fn, feeds, name: str, verbose: bool):
    """Record a static Program, run the default pass pipeline with the
    verify hook armed, and sweep the result."""
    from paddle_tpu import analysis, static
    from paddle_tpu.ir import Workspace, default_pass_manager

    prog = static.Program()
    static.enable_static()
    try:
        with static.program_guard(prog):
            vars_ = {n: static.data(n, shape, dtype)
                     for n, (shape, dtype) in feeds.items()}
            outs = build_fn(vars_)
    finally:
        static.disable_static()
    ws = Workspace(prog)
    outs = outs if isinstance(outs, (list, tuple)) else [outs]
    default_pass_manager().run(ws, protected=list(outs))
    report = analysis.check_program(ws)
    print(f"[{name}] static program: {len(prog.ops)} ops recorded, "
          f"{len(ws.ops)} after passes, "
          f"{len(report.diagnostics)} finding(s)")
    if verbose or not report.ok:
        for d in report.diagnostics:
            print("   ", d.render())
    _note(name, report)
    return report


def run_lenet(execute: bool, verbose: bool):
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(8, 1, 28, 28).astype("float32"))
    y = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 10, (8,)).astype("int64"))

    reports = [_trace_eager(
        lambda: F.cross_entropy(model(x), y),
        "lenet", execute, verbose)]

    def build(v):
        h = v["x"] * 2.0 + 1.0
        return F.relu(h).sum()

    reports.append(_trace_static(
        build, {"x": ([8, 16], "float32")}, "lenet-static", verbose))
    return reports


def run_resnet50(execute: bool, verbose: bool):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50()
    model.eval()      # frozen running stats: a pure recordable forward
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(2, 3, 64, 64).astype("float32"))
    return [_trace_eager(lambda: model(x).mean(), "resnet50", execute,
                         verbose)]


def run_bert(execute: bool, verbose: bool):
    """models/bert.py is a pure-jax compiled trainer: there is no
    framework-level program to lint, so the sweep covers the process-wide
    tracer caches after building the step, plus an eager proxy of the
    attention arithmetic."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.models.bert import BERT_CONFIGS, build_train_step

    cfg = BERT_CONFIGS["bert-base"]
    build_train_step(cfg, mesh=None, lr=1e-4)   # compile-time tracing
    report = analysis.CheckReport("bert trainer (process caches)")
    analysis.check_process_tracer_leaks(report)
    print(f"[bert] jax-level trainer: no framework segments; process "
          f"tracer sweep: {len(report.diagnostics)} finding(s)")
    for d in report.diagnostics:
        print("   ", d.render())
    _note("bert", report)

    def attn_proxy():
        q = paddle.to_tensor(
            np.random.RandomState(0).randn(2, 4, 16).astype("float32"))
        s = paddle.matmul(q, q.transpose([0, 2, 1])) * (1.0 / 4.0)
        return paddle.nn.functional.softmax(s, axis=-1).sum()

    return [report,
            _trace_eager(attn_proxy, "bert-attn-proxy", execute, verbose)]


def run_reshard(execute: bool, verbose: bool):
    """Distributed sweep 1: the reshard placement-transition matrix on
    a mesh built from the visible devices — every pairwise {r,s,p}
    move plus an nd-mesh multi-axis change, each validated against the
    SPMD rules AND executed (reshard_value runs under the sanitizer
    hook, so this sweeps the live lowering path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu import analysis
    from paddle_tpu.distributed import ProcessMesh
    from paddle_tpu.distributed.auto_parallel.reshard_functions import (
        DistAttrLite, reshard_value)
    from paddle_tpu.distributed.placements import (Partial, Replicate,
                                                   Shard)

    n = jax.device_count()
    mesh = ProcessMesh(list(range(n)), dim_names=["x"])
    # both dims multiples of every mesh-axis size in play, whatever
    # the visible device count, so the clean sweep stays clean
    val = jnp.asarray(np.random.RandomState(0)
                      .randn(2 * n, 4 * n).astype("float32"))
    report = analysis.CheckReport("reshard transition matrix")
    transitions = [
        (mesh, [Replicate()], [Shard(0)]),
        (mesh, [Shard(0)], [Replicate()]),
        (mesh, [Shard(0)], [Shard(1)]),
        (mesh, [Replicate()], [Partial()]),
        (mesh, [Partial()], [Replicate()]),    # stacked-Partial source
    ]
    if n >= 4 and n % 2 == 0:
        mesh2 = ProcessMesh(
            np.arange(n).reshape(2, n // 2), dim_names=["a", "b"])
        transitions.append((mesh2, [Shard(0), Replicate()],
                            [Replicate(), Shard(1)]))
    import warnings as _warnings
    from paddle_tpu.analysis import StaticCheckWarning
    ran = 0
    for m, src_p, dst_p in transitions:
        v = val
        if any(p.is_partial() for p in src_p):
            v = jnp.stack([val] * n)
        # checker findings collected directly (the CLI sweeps in warn
        # mode, where the hook warns instead of raising), THEN the
        # live lowering path runs under the same hook — its duplicate
        # warning for findings already in the report is silenced
        analysis.check_reshard(
            v.ndim, DistAttrLite(m, src_p), DistAttrLite(m, dst_p),
            report, global_shape=tuple(val.shape))
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore", StaticCheckWarning)
            reshard_value(v, m, src_p, m, dst_p)
        ran += 1
    print(f"[reshard] {ran} transitions lowered under the sanitizer, "
          f"{len(report.diagnostics)} finding(s)")
    if verbose or not report.ok:
        for d in report.diagnostics:
            print("   ", d.render())
    _note("reshard", report)
    return [report]


def run_replan(execute: bool, verbose: bool):
    """Distributed sweep 3: shrunk + re-planned mesh configs. For an
    8-way world losing ranks, the adaptive re-planner picks a
    survivor-feasible dp/mp plan (divisor degree space) and every
    planned placement transition — kept-rank, flattened-1D-reshard,
    and forced-replicate cases — is validated against the SPMD rules,
    exactly the sweep `shrink_world`/`AdaptiveTrainer` run before any
    recovery data moves."""
    from paddle_tpu import analysis
    from paddle_tpu.distributed.auto_parallel.reshard_functions import \
        DistAttrLite
    from paddle_tpu.distributed.mesh import ProcessMesh
    from paddle_tpu.distributed.placements import Replicate, Shard
    from paddle_tpu.distributed.resilience.adaptive import (Replanner,
                                                            mesh_for_plan)
    from paddle_tpu.distributed.resilience.elastic import \
        _shrunk_placements

    import numpy as np
    old_mesh = ProcessMesh(np.arange(8).reshape(4, 2),
                           dim_names=["dp", "mp"])
    # tensors the old mesh laid out: (ndim, placements, global_shape)
    tensors = [
        (2, [Shard(0), Replicate()], (48, 16)),
        (2, [Replicate(), Shard(1)], (16, 48)),
        (2, [Replicate(), Replicate()], (8, 8)),
        (1, [Shard(0), Replicate()], (40,)),
    ]
    llm = {"hidden_size": 1024, "num_layers": 8}
    cases = [
        # 6 survivors: the tuner re-plans (4,2) -> (3,2); same mesh
        # rank, so per-axis shards survive where the dim divides and
        # the 40-dim falls back to replicate (40 % 3 != 0)
        ([6, 7], llm),
        # 7 survivors (prime): 1-D dp=7, undivisible dims replicate
        ([7], llm),
        # 4 survivors with a dp-bounding batch: a flattened 1-D plan
        # where divisible dims re-shard for real (48 % 4 == 0)
        ([4, 5, 6, 7], dict(llm, global_batch_size=2)),
    ]
    reports = []
    for lost, config in cases:
        survivors = [p for p in range(8) if p not in lost]
        plan = Replanner(config).replan(len(survivors))
        new_mesh = mesh_for_plan(survivors, plan)
        report = analysis.CheckReport(
            f"replanned shrink 8->{len(survivors)} "
            f"(dp={plan.get('dp_degree', 1)}, "
            f"mp={plan.get('mp_degree', 1)}, mesh {new_mesh.shape})")
        for ndim, placements, gshape in tensors:
            dst_p = _shrunk_placements(placements, old_mesh, new_mesh,
                                       gshape)
            analysis.check_reshard(
                ndim, DistAttrLite(old_mesh, placements),
                DistAttrLite(new_mesh, dst_p), report,
                global_shape=gshape)
        print(f"[replan] {report.subject}: "
              f"{len(report.diagnostics)} finding(s)")
        if verbose or not report.ok:
            for d in report.diagnostics:
                print("   ", d.render())
        _note("replan", report)
        reports.append(report)
    return reports


def run_pipeline(execute: bool, verbose: bool):
    """Distributed sweep 2: lower and simulate every host-driven
    pipeline schedule for a pod-shaped config (deadlock / P2P-ordering
    verification over the exact generators the runtimes execute)."""
    from paddle_tpu import analysis

    reports = []
    configs = [("FThenB", 4, 8, 1), ("1F1B", 4, 8, 1),
               ("VPP", 4, 8, 2), ("ZeroBubble", 4, 8, 1)]
    for sched, P, m, C in configs:
        r = analysis.check_pipeline_schedule(sched, P, m, num_chunks=C)
        print(f"[pipeline] {sched} (P={P}, m={m}"
              + (f", C={C}" if C != 1 else "")
              + f"): {len(r.diagnostics)} finding(s)")
        if verbose or not r.ok:
            for d in r.diagnostics:
                print("   ", d.render())
        _note("pipeline", r)
        reports.append(r)
    # the COMPILED pipeline's ppermute order (validated from the real
    # lowering's exported permutation lists, pipeline_compiled.py)
    for kind, P, m in (("stream", 4, 8), ("1f1b", 4, 8)):
        r = analysis.check_compiled_pipeline(kind, P, m)
        print(f"[pipeline] compiled-{kind} (P={P}, m={m}): "
              f"{len(r.diagnostics)} finding(s)")
        if verbose or not r.ok:
            for d in r.diagnostics:
                print("   ", d.render())
        _note("pipeline", r)
        reports.append(r)
    return reports


# ------------------------------------------------------------ perf lint

def _perf_note(name: str, report, seal_counts=None, extra=None):
    d = report.to_dict()
    breaks = sum((x["data"] or {}).get("count", 1)
                 for x in d["diagnostics"]
                 if x["checker"] == "fusion_break")
    syncs = sum((x["data"] or {}).get("count", 1)
                for x in d["diagnostics"]
                if x["checker"] == "host_sync")
    reshards = sum(1 for x in d["diagnostics"]
                   if x["checker"] == "implicit_reshard")
    d.update({"breaks": breaks, "syncs": syncs, "reshards": reshards,
              "seal_counts": seal_counts or {}})
    if extra:
        d.update(extra)
    _JSON["models"].setdefault(name, []).append(d)
    return d


def _perf_print(name: str, d, report, verbose: bool):
    print(f"[{name}] perf lint: {d['breaks']} fusion break(s), "
          f"{d['syncs']} host sync(s), {d['reshards']} implicit "
          f"reshard(s) per step"
          + (f"; seals {d['seal_counts']}" if d["seal_counts"] else ""))
    if verbose or report.diagnostics:
        for diag in report.diagnostics:
            print("   ", diag.render())


def perf_gpt2_eager(verbose: bool):
    """Eager-GPT, the BUDGET_r06 configuration (hidden 128, 4 layers,
    seq 128): one traced train step. Expected steady-state shape:
    ZERO breaks — the flash-attention record-time aval inference
    succeeds (the kernels trace under the scoped ``jax.enable_x64(False)``),
    so the step stays in one fusion window and reaches the fused fwd+vjp
    steady state. This row was the
    4-`record_fallback`-breaks/step finding of BUDGET_r06; the gate
    now exists to catch the class COMING BACK."""
    from paddle_tpu.observability.__main__ import _gpt2_step
    from paddle_tpu import analysis
    report, counts, _ = analysis.trace_step(_gpt2_step())
    d = _perf_note("gpt2-eager", report, counts)
    _perf_print("gpt2-eager", d, report, verbose)
    return report


def perf_resnet50_eager(verbose: bool):
    """Eager ResNet-50 in TRAIN mode (running stats live), small input
    so the CLI stays quick: one traced step. Expected: ZERO host
    syncs — the batch-norm running-stat update is pure in-window
    elementwise state math now (nn/functional/norm.py set_value
    aliases the pending result instead of reading ``mean._value``
    back) — and ZERO breaks: the step records 547 ops, so the config
    applies the lint's own segment_cap remedy
    (``set FLAGS_lazy_max_segment_ops >= 547``) and the whole step
    seals once at backward instead of paying 2 cap breaks/step. This
    row was the 53-materialize-seals/step finding of BUDGET_r06; the
    gate now exists to catch either class COMING BACK."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu import analysis
    from paddle_tpu._core.flags import flag_value
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50()
    model.train()
    opt = paddle.optimizer.Momentum(0.1, parameters=model.parameters())
    r = np.random.RandomState(0)
    x = paddle.to_tensor(r.randn(2, 3, 64, 64).astype("float32"))
    y = paddle.to_tensor(r.randint(0, 1000, (2,)).astype("int64"))

    def step():
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        np.asarray(loss._value)

    cap_was = flag_value("FLAGS_lazy_max_segment_ops")
    paddle.set_flags({"FLAGS_lazy_max_segment_ops": 1024})
    try:
        report, counts, _ = analysis.trace_step(step)
    finally:
        paddle.set_flags({"FLAGS_lazy_max_segment_ops": cap_was})
    d = _perf_note("resnet50-eager", report, counts)
    _perf_print("resnet50-eager", d, report, verbose)
    return report


def _dryrun_mesh():
    import jax
    import paddle_tpu.distributed as dist
    n = jax.device_count()
    if n >= 4:
        return dist.auto_mesh(2, 2, dim_names=["dp", "mp"])
    # degraded single-device fallback (the CLI normally re-execs with
    # 8 forced CPU devices before getting here)
    return dist.auto_mesh(1, 1, dim_names=["dp", "mp"])


def perf_lenet_sharded(verbose: bool):
    """LeNet forward recorded under the dryrun dp×mp mesh with a
    dp-sharded batch: the PartitionSpec propagation sweep. A correctly
    laid-out model: zero reshard findings, batch sharding propagates
    end to end, the loss reduction is the only priced collective."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn.functional as F
    from paddle_tpu import analysis
    from paddle_tpu._core import lazy
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    r = np.random.RandomState(0)
    with _dryrun_mesh():
        model = LeNet()
        x = dist.shard_batch(paddle.to_tensor(
            r.randn(8, 1, 28, 28).astype("float32")))
        y = paddle.to_tensor(r.randint(0, 10, (8,)).astype("int64"))
        lazy.PERF_SRC += 1
        try:
            with lazy.lazy_guard(max_segment_ops=1 << 30) as ctx:
                out = F.cross_entropy(model(x), y)
                res, report = analysis.propagate_specs(ctx)
                analysis.sharding_prop.summarize_comm(res, report)
                ctx._reset_segment()
        finally:
            lazy.PERF_SRC -= 1
    d = _perf_note("lenet-sharded", report,
                   extra={"comm_bytes": res.comm_total(),
                          "comm": res.comm})
    _perf_print("lenet-sharded", d, report, verbose)
    return report


def perf_tp_sharded(verbose: bool):
    """Column→Row parallel mp-layers under the dryrun mesh: the TP
    boundary contract — specs must round-trip the sharding-constraint
    ops (zero implicit_reshard findings) and the row exchange prices
    as the one intended all-reduce."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu import analysis
    from paddle_tpu._core import lazy

    paddle.seed(3)
    r = np.random.RandomState(3)
    with _dryrun_mesh():
        col = dist.fleet.mp_layers.ColumnParallelLinear(
            8, 16, gather_output=False, has_bias=False)
        row = dist.fleet.mp_layers.RowParallelLinear(
            16, 8, has_bias=False, input_is_parallel=True)
        x = paddle.to_tensor(r.randn(4, 8).astype("float32"))
        lazy.PERF_SRC += 1
        try:
            with lazy.lazy_guard(max_segment_ops=1 << 30) as ctx:
                out = row(col(x))
                res, report = analysis.propagate_specs(ctx)
                analysis.sharding_prop.summarize_comm(res, report)
                ctx._reset_segment()
        finally:
            lazy.PERF_SRC -= 1
    d = _perf_note("tp-sharded", report,
                   extra={"comm_bytes": res.comm_total(),
                          "comm": res.comm})
    _perf_print("tp-sharded", d, report, verbose)
    return report


_PERF_TABLE = {
    "gpt2-eager": perf_gpt2_eager,
    "resnet50-eager": perf_resnet50_eager,
    "lenet-sharded": perf_lenet_sharded,
    "tp-sharded": perf_tp_sharded,
}
_PERF_DEFAULT_MODELS = "gpt2-eager,resnet50-eager,lenet-sharded," \
                       "tp-sharded"


# ------------------------------------------------------------- mem lint

# the acceptance sweep: pure data-parallel, the dp×mp pod slice, and a
# 3D dp×mp×pp shape — all priced WITHOUT compiling, on any host
_MEM_DEFAULT_SHAPES = ((1, 1), (4, 2), (2, 2, 2))


def _mem_record_and_sweep(build_fn, name: str, shapes, optimizer: str,
                          verbose: bool):
    """Record one model's forward+loss into a capture window (aval
    inference only — no compile, no devices) and price the full
    train-step footprint at every candidate pod shape."""
    from paddle_tpu import analysis
    from paddle_tpu._core import lazy
    from paddle_tpu.analysis.mem_liveness import render_sweep

    lazy.PERF_SRC += 1      # top-buffer rows carry file:line provenance
    try:
        with lazy.lazy_guard(max_segment_ops=1 << 30) as ctx:
            out = build_fn()    # root held alive through the sweep
            n_ops = len(ctx.pending)
            rows = analysis.sweep_pod_shapes(ctx, shapes=shapes,
                                             optimizer=optimizer)
            ctx._reset_segment()
    finally:
        lazy.PERF_SRC -= 1
    oom = sum(r["oom_risk"] for r in rows)
    print(f"[{name}] mem lint: {n_ops} ops recorded, "
          f"{len(rows)} pod shape(s) priced, {oom} oom_risk finding(s)")
    print(render_sweep(rows, title=f"{name}: per-device peak by pod "
                                   f"shape ({optimizer} step)"))
    if verbose:
        for r in rows:
            for t in r["top"]:
                print(f"    {r['mesh']}: {t['pd_bytes']} B/dev "
                      f"{t['kind']} {t['dtype']}{t['shape']}"
                      + (f" @ {t['src']}" if t.get("src") else ""))
    d = {"n_ops": n_ops, "rows": rows, "oom_risk": oom}
    _JSON["models"].setdefault(name, []).append(d)
    return d


def mem_lenet(shapes, verbose: bool):
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    r = np.random.RandomState(0)
    x = paddle.to_tensor(r.randn(8, 1, 28, 28).astype("float32"))
    y = paddle.to_tensor(r.randint(0, 10, (8,)).astype("int64"))
    return _mem_record_and_sweep(
        lambda: F.cross_entropy(model(x), y), "lenet", shapes, "adam",
        verbose)


def mem_gpt2(shapes, verbose: bool):
    """Miniature eager GPT (the pod-planning shape class that actually
    needs mp: embedding + attention + mlp weights shard on the model
    axis under the TP assumption)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTPretrainingCriterion)

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, dtype="float32",
                    use_flash_attention=False,
                    max_position_embeddings=32)
    model = GPTForPretraining(cfg)
    crit = GPTPretrainingCriterion()
    r = np.random.RandomState(0)
    x = paddle.to_tensor(r.randint(0, 512, (8, 32)).astype("int64"))
    y = paddle.to_tensor(r.randint(0, 512, (8, 32)).astype("int64"))
    return _mem_record_and_sweep(
        lambda: crit(model(x), y), "gpt2-mini", shapes, "adamw",
        verbose)


_MEM_TABLE = {"lenet": mem_lenet, "gpt2-mini": mem_gpt2}


def _parse_mesh(spec: str):
    try:
        shape = tuple(int(s) for s in spec.replace("x", ",").split(",")
                      if s.strip())
    except ValueError:
        shape = ()
    if not shape or len(shape) > 3 or any(s < 1 for s in shape):
        raise SystemExit(
            f"--mesh {spec!r}: expected dp,mp[,pp] positive degrees "
            f"(e.g. --mesh 4,2)")
    return shape


def _mem_main(args) -> int:
    import paddle_tpu as paddle  # noqa: F401 (backend init)
    _JSON["models"] = {}
    shapes = [_parse_mesh(args.mesh)] if args.mesh \
        else list(_MEM_DEFAULT_SHAPES)
    models = args.models if args.models is not None \
        else ",".join(_MEM_TABLE)
    results = []
    for m in models.split(","):
        m = m.strip()
        if not m:
            continue
        if m not in _MEM_TABLE:
            print(f"unknown mem model '{m}' (have: {sorted(_MEM_TABLE)})")
            return 2
        results.append(_MEM_TABLE[m](shapes, args.verbose))
    from paddle_tpu._core.flags import flag_value
    total_oom = sum(d["oom_risk"] for d in results)
    budget = int(flag_value("FLAGS_memory_budget_bytes"))
    print(f"== mem lint: {len(shapes)} pod shape(s) x "
          f"{len(results)} model(s), {total_oom} oom_risk finding(s)"
          + (f" against a {budget} B/device budget" if budget
             else " (no FLAGS_memory_budget_bytes set — sweep is "
                  "informational)"))
    if args.json:
        print(json.dumps({"oom_risk": total_oom,
                          "budget_bytes": budget,
                          "shapes": [list(s) for s in shapes],
                          "models": _JSON["models"]}))
    return 0


def _plan_main(args) -> int:
    """--plan: record the dryrun sweep model (the shape
    tests/test_planner.py sweeps: two bias-free Linear(64,64) over [8, 32, 64] + cross-entropy) and
    run the whole-program auto-parallelism planner over every dp×mp×pp
    factorization of --world. Static end to end: no devices, no
    compile — a laptop plans a pod. Exit code 0 only when a feasible
    plan exists AND the winner validated clean through the reshard +
    pipeline checkers."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu import analysis
    from paddle_tpu._core import lazy

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(64, 64, bias_attr=False),
                          nn.Linear(64, 64, bias_attr=False))
    r = np.random.RandomState(0)
    x = paddle.to_tensor(r.randn(8, 32, 64).astype("float32"))
    y = paddle.to_tensor(r.randint(0, 64, (8, 32)).astype("int64"))
    lazy.PERF_SRC += 1      # diagnostics carry file:line provenance
    try:
        with lazy.lazy_guard(max_segment_ops=1 << 30) as ctx:
            F.cross_entropy(model(x), y)
            rep = analysis.plan_program(ctx, world=args.world)
            ctx._reset_segment()
    finally:
        lazy.PERF_SRC -= 1
    print(rep.render())
    best = rep.best()
    winner_findings = 0 if best is None else sum(
        1 for d in rep.diagnostics.diagnostics
        if d.checker in ("reshard_placement", "pipeline_schedule"))
    if args.json:
        print(json.dumps(dict(rep.to_dict(),
                              winner_findings=winner_findings)))
    return 0 if (best is not None and rep.validated
                 and winner_findings == 0) else 1


def _numerics_trace(build_fn, name: str, verbose: bool):
    """Record one model forward(+loss) under amp auto_cast O1 into a
    single capture window (the _meta_aval-based amp hook keeps the
    whole trace in one segment) and run the numerics plane over it:
    range propagation + overflow_risk / accum_dtype / cast_churn."""
    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu._core import lazy

    with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
        with lazy.lazy_guard(max_segment_ops=1 << 30) as ctx:
            out = build_fn()   # noqa: F841 (root held through the sweep)
            view = analysis.SegmentView.from_context(ctx)
            n_ops = len(ctx.pending)
            report = analysis.CheckReport(
                f"{name} numerics ({n_ops} ops under auto_cast O1)")
            analysis.check_numerics_segment(view, report)
            ctx._reset_segment()
    low = sum(1 for p in view.pending for r in p.out_refs
              if str(r.aval.dtype) in ("bfloat16", "float16"))
    print(f"[{name}] numerics: {n_ops} ops recorded under auto_cast "
          f"O1 (bf16), {low} low-precision output(s), "
          f"{len(report.diagnostics)} finding(s)")
    if verbose or not report.ok:
        for d in report.diagnostics:
            print("   ", d.render())
    _note(name, report)
    return report


def numerics_lenet(verbose: bool):
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    r = np.random.RandomState(0)
    x = paddle.to_tensor(r.randn(8, 1, 28, 28).astype("float32"))
    y = paddle.to_tensor(r.randint(0, 10, (8,)).astype("int64"))
    return [_numerics_trace(lambda: F.cross_entropy(model(x), y),
                            "lenet", verbose)]


def numerics_resnet50(verbose: bool):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50()
    model.eval()
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(2, 3, 64, 64).astype("float32"))
    return [_numerics_trace(lambda: model(x).mean(), "resnet50",
                            verbose)]


def numerics_bert(verbose: bool):
    """The bench bert trainer is pure jax (no framework segments); the
    numerics subject is the attention arithmetic the amp rules govern —
    scaled q@k^T, softmax, the value matmul."""
    import numpy as np
    import paddle_tpu as paddle

    def attn_proxy():
        r = np.random.RandomState(0)
        q = paddle.to_tensor(r.randn(2, 8, 32).astype("float32"))
        k = paddle.to_tensor(r.randn(2, 8, 32).astype("float32"))
        v = paddle.to_tensor(r.randn(2, 8, 32).astype("float32"))
        s = paddle.matmul(q, k.transpose([0, 2, 1])) * (32 ** -0.5)
        a = paddle.nn.functional.softmax(s, axis=-1)
        return paddle.matmul(a, v).sum()

    return [_numerics_trace(attn_proxy, "bert", verbose)]


def numerics_gpt2(verbose: bool):
    """Miniature eager GPT under auto_cast — the AMP headline shape —
    plus the quant_error_budget pre-flight over its parameter buckets
    (per-bucket int8 scaling, the EQuARX gate)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTPretrainingCriterion)

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                    num_heads=4, dtype="float32",
                    use_flash_attention=False,
                    max_position_embeddings=32)
    model = GPTForPretraining(cfg)
    crit = GPTPretrainingCriterion()
    r = np.random.RandomState(0)
    x = paddle.to_tensor(r.randint(0, 512, (8, 32)).astype("int64"))
    y = paddle.to_tensor(r.randint(0, 512, (8, 32)).astype("int64"))
    reports = [_numerics_trace(lambda: crit(model(x), y), "gpt2",
                               verbose)]

    named = [(n, p) for n, p in model.named_parameters()]
    buckets = analysis.quant_bucket_plan(named, bucket_numel=1 << 16)
    qreport = analysis.check_quant_budget(buckets, fmt="int8",
                                          per_bucket_scale=True)
    print(f"[gpt2] quant budget: {len(buckets)} bucket(s) priced "
          f"(int8, per-bucket scale), "
          f"{len(qreport.diagnostics)} finding(s)")
    if verbose or not qreport.ok:
        for d in qreport.diagnostics:
            print("   ", d.render())
    _note("gpt2-quant", qreport)
    reports.append(qreport)
    return reports


_NUMERICS_TABLE = {"lenet": numerics_lenet, "resnet50": numerics_resnet50,
                   "bert": numerics_bert, "gpt2": numerics_gpt2}


def _numerics_main(args) -> int:
    import paddle_tpu as paddle
    # provenance is captured at record time only when checks are on
    paddle.set_flags({"FLAGS_static_checks": "warn"})
    _JSON["models"] = {}
    models = args.models if args.models is not None \
        else ",".join(_NUMERICS_TABLE)
    reports = []
    for m in models.split(","):
        m = m.strip()
        if not m:
            continue
        if m not in _NUMERICS_TABLE:
            print(f"unknown numerics model '{m}' "
                  f"(have: {sorted(_NUMERICS_TABLE)})")
            return 2
        reports.extend(_NUMERICS_TABLE[m](args.verbose))
    findings = sum(len(r.diagnostics) for r in reports)
    errors = sum(len(r.errors) for r in reports)
    print(f"== numerics lint: {findings} finding(s) "
          f"({errors} error-severity) across {len(reports)} program(s)")
    if args.json:
        from ..observability import metrics
        snap = metrics.snapshot()
        print(json.dumps({
            "findings": findings, "errors": errors,
            "models": _JSON["models"],
            "counters": {k: v for k, v in snap["counters"].items()
                         if k.startswith("sanitizer.")},
        }))
    # the zoo's error bar is zero: warnings are informational, an
    # error-severity numerics finding fails the sweep
    return 0 if errors == 0 else 1


def _maybe_reexec_for_devices(argv) -> int:
    """--perf wants the dryrun dp×mp mesh (≥4 devices). On a
    single-device host, re-exec with 8 forced CPU devices BEFORE jax
    initializes in this process. Returns the child's exit code, or -1
    to continue in-process."""
    if os.environ.get("PT_PERF_NO_REEXEC") == "1":
        return -1
    if "xla_force_host_platform_device_count" in \
            os.environ.get("XLA_FLAGS", ""):
        return -1
    import jax
    if jax.device_count() >= 4:
        return -1
    import subprocess
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PT_PERF_NO_REEXEC"] = "1"
    return subprocess.call(
        [sys.executable, "-m", "paddle_tpu.analysis"] + list(argv),
        env=env)


def _perf_main(args, argv) -> int:
    rc = _maybe_reexec_for_devices(argv)
    if rc >= 0:
        return rc
    import paddle_tpu as paddle  # noqa: F401 (jax/backend init)
    _JSON["models"] = {}
    models = args.models if args.models is not None \
        else _PERF_DEFAULT_MODELS
    reports = []
    for m in models.split(","):
        m = m.strip()
        if not m:
            continue
        if m not in _PERF_TABLE:
            print(f"unknown perf model '{m}' "
                  f"(have: {sorted(_PERF_TABLE)})")
            return 2
        reports.append(_PERF_TABLE[m](args.verbose))
    totals = {
        "breaks": sum(d["breaks"] for v in _JSON["models"].values()
                      for d in v),
        "syncs": sum(d["syncs"] for v in _JSON["models"].values()
                     for d in v),
        "reshards": sum(d["reshards"] for v in _JSON["models"].values()
                        for d in v),
    }
    print(f"== perf lint: {totals['breaks']} fusion break(s), "
          f"{totals['syncs']} host sync(s), {totals['reshards']} "
          f"implicit reshard(s) across {len(reports)} model(s)")
    if args.json:
        print(json.dumps(dict(totals, models=_JSON["models"])))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m paddle_tpu.analysis")
    ap.add_argument("--models", default=None,
                    help="comma list: lenet,resnet50,bert,reshard,"
                         "replan,pipeline (sanitizer mode) or "
                         "gpt2-eager,resnet50-eager,lenet-sharded,"
                         "tp-sharded (--perf mode)")
    ap.add_argument("--perf", action="store_true",
                    help="performance lint: trace the eager bench "
                         "models for fusion-window breaks / host syncs "
                         "and sweep the sharded models' PartitionSpec "
                         "propagation on a dryrun dp×mp mesh")
    ap.add_argument("--mem", action="store_true",
                    help="mem lint: record the bench models and price "
                         "the per-device train-step peak at candidate "
                         "pod shapes (static liveness — no compile, no "
                         "devices); oom_risk findings gate against "
                         "FLAGS_memory_budget_bytes")
    ap.add_argument("--numerics", action="store_true",
                    help="numerics lint: record the model zoo (lenet,"
                         "resnet50,bert,gpt2) under amp auto_cast O1 "
                         "and run the precision dataflow checkers "
                         "(overflow_risk, accum_dtype, cast_churn) "
                         "plus the int8 quant_error_budget pre-flight "
                         "over gpt2's parameter buckets; exit 0 = zero "
                         "error-severity findings")
    ap.add_argument("--plan", action="store_true",
                    help="auto-parallelism planner: record the dryrun "
                         "sweep model and rank every dp×mp×pp "
                         "factorization of --world against the static "
                         "comm/memory/FLOP planes; the winner is "
                         "validated through the reshard + pipeline "
                         "checkers (error mode)")
    ap.add_argument("--world", type=int, default=8,
                    help="world size the --plan search factorizes "
                         "(default 8, the dryrun sweep world)")
    ap.add_argument("--mesh", default=None, metavar="DP,MP[,PP]",
                    help="restrict the --mem sweep to one candidate "
                         "shape (e.g. --mesh 4,2); default sweeps "
                         "1x1, 4x2 and 2x2x2")
    ap.add_argument("--execute", action="store_true",
                    help="also flush/execute each recorded segment")
    ap.add_argument("--verbose", action="store_true",
                    help="print every diagnostic, not just findings")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable report (the "
                         "observability CLI's snapshot shape)")
    ap.add_argument("--fix", action="store_true",
                    help="plan the mechanical repairs and print the "
                         "dry-run diff; exit code reflects the "
                         "post-fix residual")
    raw_argv = list(sys.argv[1:] if argv is None else argv)
    args = ap.parse_args(argv)

    if args.perf:
        return _perf_main(args, raw_argv)
    if args.mem:
        return _mem_main(args)
    if args.plan:
        return _plan_main(args)
    if args.numerics:
        return _numerics_main(args)

    global _FIX
    _FIX = bool(args.fix)
    _JSON["models"] = {}     # fresh accumulator per invocation
    if args.models is None:
        args.models = "lenet,resnet50,bert,reshard,replan,pipeline"

    import paddle_tpu as paddle
    # provenance is captured at record time only when checks are on
    paddle.set_flags({"FLAGS_static_checks": "warn"})

    table = {"lenet": run_lenet, "resnet50": run_resnet50,
             "bert": run_bert, "reshard": run_reshard,
             "replan": run_replan, "pipeline": run_pipeline}
    reports = []
    for m in args.models.split(","):
        m = m.strip()
        if not m:
            continue
        if m not in table:
            print(f"unknown model '{m}' (have: {sorted(table)})")
            return 2
        reports.extend(table[m](args.execute, args.verbose))

    findings = sum(len(r.diagnostics) for r in reports)
    print(f"== static analysis: {findings} finding(s) across "
          f"{len(reports)} program(s)")
    if args.json:
        from .hooks import fixes_applied, segment_sweeps
        from ..observability import metrics
        snap = metrics.snapshot()
        payload = {
            "findings": findings,
            "programs": sum(len(v) for v in _JSON["models"].values()),
            "segment_sweeps": segment_sweeps(),
            "fixes_applied": fixes_applied(),
            "models": _JSON["models"],
            "counters": {k: v for k, v in snap["counters"].items()
                         if k.startswith("sanitizer.")},
        }
        print(json.dumps(payload))
    return 0 if findings == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
