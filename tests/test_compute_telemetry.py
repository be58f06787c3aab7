"""Compute telemetry plane (FLAGS_compute_telemetry) — the FLOP-domain
acceptance contract (ISSUE 12):

- **off is free**: with the flag off, a capped chain + LeNet train loop
  (async flush on) does zero registry work, makes zero
  ``cost_analysis()`` calls, and counts zero FLOPs;
- **analysis cached per executable**: one ``cost_analysis()`` call per
  compile, landing on the ExecCache entry (``cost_info``, pruned with
  the entry); a steady-state cache hit makes zero calls;
- **per-chip pricing**: under a dryrun dp mesh the captured FLOPs
  describe the PARTITIONED module — global/mesh_size;
- **MFU / roofline math**: achieved-vs-peak and intensity-vs-ridge
  columns from seeded peak flags;
- **source attribution**: each recorded op's lowering carries a
  named_scope with its paddle file:line, the compiled HLO round-trips
  it into the provenance map, device-trace events group by
  ``op@file:line`` in the profiler statistic table and the exported
  trace;
- **static FLOP model**: sharding_prop's rule-table model
  cross-validates against ``cost_analysis()`` on LeNet and a TP layer;
- **satellites**: BatchNorm running stats update in-window (0 host
  syncs) and flash_attention records into the window (0 fusion
  breaks) on this toolchain.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from conftest import with_flag
from paddle_tpu import analysis
from paddle_tpu._core import async_flush, lazy
from paddle_tpu.observability import compute as comptel
from paddle_tpu.observability import metrics


@pytest.fixture
def compute_on():
    """The plane on over a zeroed ledger, and both put back. The ledger's
    totals are the process's: whatever flipped the plane on before this
    test in the same worker (budget.static_diff in test_record_fastpath.py
    does, over this file's very train step) leaves its executions in
    site_flops() and executed_flops(), and a test that reads them after
    its own first step then sees several steps' worth."""
    comptel.reset()
    paddle.set_flags({"FLAGS_compute_telemetry": True})
    try:
        yield
    finally:
        paddle.set_flags({"FLAGS_compute_telemetry": False})
        comptel.reset()


def _train_step_fn(batch=8):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.Adam(1e-3, parameters=net.parameters())
    r = np.random.RandomState(0)
    x = paddle.to_tensor(r.randn(batch, 8).astype("float32"))
    y = paddle.to_tensor(r.randint(0, 4, (batch,)).astype("int64"))

    def step():
        loss = F.cross_entropy(net(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return float(np.asarray(loss._value))

    return step


# ----------------------------------------------------------- off contract

def test_compute_telemetry_off_is_free():
    """Capped chain + fused train loop with async flush on, plane off:
    zero registry mutations, zero cost_analysis calls, zero FLOPs
    counted (checks off for the freeze window — the warn-mode
    sanitizer counts by design)."""
    step = _train_step_fn()
    x = paddle.to_tensor(np.ones((16, 16), "float32"))

    def chain():
        y = x
        for _ in range(32):
            y = y * 1.0001 + 0.0001
        np.asarray(y._value)

    step()
    chain()      # warm every compile off-window
    with with_flag("FLAGS_static_checks", "off"), \
            with_flag("FLAGS_async_flush", True), \
            with_flag("FLAGS_lazy_max_segment_ops", 16):
        before = metrics.MUTATIONS
        calls0 = comptel.COST_CALLS
        flops0 = comptel.executed_flops()
        for _ in range(3):
            chain()
            step()
        async_flush.drain()
        assert metrics.MUTATIONS == before, \
            "compute-telemetry-off loop did registry work"
        assert comptel.COST_CALLS == calls0, \
            "compute-telemetry-off loop called cost_analysis"
        assert comptel.executed_flops() == flops0, \
            "compute-telemetry-off loop counted FLOPs"
    async_flush.drain(raise_latched=False)


# ------------------------------------------- once-per-compile + pruning

def test_cost_analysis_once_per_compile_all_sites(compute_on):
    """A fused train step compiles two executables under the plane
    (fused fwd+vjp step + optimizer update): exactly two cost_analysis
    calls, FLOPs counted per execution on every later cache hit with
    ZERO further calls, and the fused-step ExecCache entry carries its
    cost_info."""
    step = _train_step_fn()
    step()       # compile both sites under the plane
    calls_after_compile = comptel.COST_CALLS
    assert calls_after_compile >= 2, comptel.COST_CALLS
    sites0 = comptel.site_flops()
    assert sites0.get("fused_step", 0) > 0, sites0
    assert sites0.get("optimizer", 0) > 0, sites0

    flops0 = comptel.executed_flops()
    for _ in range(3):
        step()
    assert comptel.COST_CALLS == calls_after_compile, \
        "steady-state cache hits re-ran cost_analysis"
    per_step = (comptel.executed_flops() - flops0) / 3
    assert per_step == sites0["fused_step"] + sites0["optimizer"]

    # the cached info sits on the fused-step cache entry
    infos = [lazy._FUSED_CACHE.cost_info(k)
             for k in list(lazy._FUSED_CACHE)]
    assert any(i and i.get("flops", 0) > 0 for i in infos), infos


def test_cost_info_pruned_with_entry(compute_on):
    """ExecCache eviction drops the entry's cost_info with it — the
    analysis side-tables never outlive the runners they describe."""
    from paddle_tpu._core.cache import ExecCache
    c = ExecCache()
    with with_flag("FLAGS_executable_cache_capacity", 2):
        c["a"] = 1
        c.note_cost("a", {"flops": 10})
        c["b"] = 2
        c.note_cost("b", {"flops": 20})
        c["c"] = 3          # evicts "a"
        assert "a" not in c
        assert c.cost_info("a") is None
        assert c.cost_info("b")["flops"] == 20
    c.clear()
    assert c.cost_info("b") is None


# -------------------------------------------------------- per-chip pricing

def test_per_chip_pricing_under_dryrun_mesh(compute_on):
    """The same matmul compiled no-mesh vs under a dp×mp dryrun mesh
    with a dp-sharded batch: the sharded executable's captured FLOPs
    are the per-chip share (global / mesh_size) and the entry records
    its pricing basis."""
    import paddle_tpu.distributed as dist
    r = np.random.RandomState(0)
    w = paddle.to_tensor(r.randn(128, 32).astype("float32"))

    x = paddle.to_tensor(r.randn(64, 128).astype("float32"))
    np.asarray(paddle.matmul(x, w)._value)
    nomesh = comptel.executable_stats()[-1]

    with dist.auto_mesh(2, 2, dim_names=["dp", "mp"]):
        xs = dist.shard_batch(paddle.to_tensor(
            r.randn(64, 128).astype("float32")))
        np.asarray(paddle.matmul(xs, w)._value)
    sharded = comptel.executable_stats()[-1]

    assert nomesh["flops"] == 2 * 64 * 128 * 32
    assert sharded["n_devices"] == 4
    # the batch shards over dp=2 (mp unused by this program): each
    # chip computes 1/2 of the global matmul
    assert sharded["flops"] * 2 == nomesh["flops"], (nomesh, sharded)


# ------------------------------------------------------- MFU / roofline

def test_mfu_and_roofline_math():
    with with_flag("FLAGS_device_peak_flops", 1e12):
        assert comptel.peak_flops() == 1e12
        assert comptel.mfu(5e11) == 0.5
        assert comptel.mfu(0.0) == 0.0
        with with_flag("FLAGS_device_peak_membw", 1e11):
            # ridge = 1e12 / 1e11 = 10 FLOP/B
            r = comptel.roofline(flops=1000, bytes_accessed=50)
            assert r["ridge_intensity"] == 10.0
            assert r["arith_intensity"] == 20.0
            assert r["bound"] == "compute-bound"
            r2 = comptel.roofline(flops=100, bytes_accessed=50)
            assert r2["arith_intensity"] == 2.0
            assert r2["bound"] == "memory-bound"
    # no-compute window: no verdict rather than a fake one
    assert comptel.roofline(0, 0)["bound"] is None
    # autodetect path returns something positive on every backend
    assert comptel.peak_flops() > 0
    assert comptel.peak_membw() > 0


def test_budget_gains_compute_columns():
    """budget.collect turns the plane on for the run: the result
    carries mfu / flops_per_step / arith_intensity (the --json fields
    --static-diff consumes), the steady-state measured window re-runs
    ZERO cost_analysis calls, and render shows the MFU line."""
    from paddle_tpu.observability import budget
    step = _train_step_fn()
    out = budget.collect(step, steps=4)
    comp = out["compute"]
    assert comp["flops_per_step"] > 0
    assert 0 < comp["mfu"] < 1
    assert comp["gflops_per_s"] > 0
    assert comp["arith_intensity"] > 0
    assert comp["bound"] in ("compute-bound", "memory-bound")
    assert comp["cost_analysis_calls_measured"] == 0
    text = budget.render(out)
    assert "MFU" in text and "GFLOP/s" in text and "ridge" in text


def test_static_diff_compute_flops_no_false_clean():
    """The --static-diff gate: the rule-table FLOP model must predict
    non-zero compute exactly when the measured compute.flops.* meters
    count some."""
    from paddle_tpu.observability import budget
    step = _train_step_fn()
    diff = budget.static_diff(step, steps=3)
    assert diff["ok"], budget.render_static_diff(diff)
    rows = {r_["class"]: r_ for r_ in diff["rows"]}
    assert rows["compute.flops"]["static"] > 0
    assert rows["compute.flops"]["measured_per_step"] > 0


# ------------------------------------------------- source attribution

def test_named_scope_provenance_round_trip(compute_on):
    """With the plane on, a recorded op's compiled lowering carries a
    named_scope with THIS file's line; the provenance map resolves
    HLO instruction names back to ``op@file:line``."""
    x = paddle.to_tensor(np.ones((8, 16), "float32"))
    w = paddle.to_tensor(np.ones((16, 4), "float32"))
    np.asarray(paddle.matmul(x, w)._value)      # fresh compile
    vals = set()
    for name in list(comptel._HLO_SRC):
        vals.add(comptel.source_of(name))
    mine = [v for v in vals
            if v and "test_compute_telemetry.py" in v]
    assert mine, sorted(vals)
    assert any(v.startswith("matmul@") for v in mine), mine


def test_profiler_groups_device_time_by_source(compute_on, tmp_path):
    """The acceptance loop: a traced LeNet step (device tracing on)
    yields a statistic table whose device time groups under paddle
    ``op@file:line`` rows, and the exported trace carries the
    provenance-named events."""
    from paddle_tpu.profiler import Profiler, ProfilerTarget
    from paddle_tpu.vision.models import LeNet
    paddle.seed(0)
    model = LeNet()
    r = np.random.RandomState(0)
    x = paddle.to_tensor(r.randn(4, 1, 28, 28).astype("float32"))

    def fwd():
        np.asarray(model(x)._value)

    fwd()        # compile under the plane: scopes baked, provenance read
    assert comptel.provenance_size() > 0
    with Profiler(targets=[ProfilerTarget.CPU, ProfilerTarget.TPU],
                  fused_runtime=True) as prof:
        fwd()
    devs = prof.device_events()
    if not devs:                                   # pragma: no cover
        pytest.skip("backend produced no device trace events")
    attributed = [comptel.source_of(e["name"]) for e in devs]
    hits = sorted({a for a in attributed if a})
    assert hits, "no device event mapped to paddle provenance"
    assert any("@" in h and ".py:" in h for h in hits), hits
    # the statistic table groups device time under the provenance rows
    # (the name column truncates long paths — match the grouped head)
    table = prof.source_summary()
    assert any("@" in line.split()[0] for line in table.splitlines()
               if line and line[0].isalpha()), table
    # and the exported chrome trace carries the provenance on events
    path = prof.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    srcs = [e["args"]["src"] for e in doc["traceEvents"]
            if e.get("args", {}).get("src")]
    assert any("@" in s and ".py:" in s for s in srcs), srcs[:5]


# ----------------------------------------------------- static FLOP model

def test_static_flop_model_cross_validated_lenet(compute_on):
    """The rule-table FLOP model vs cost_analysis on a LeNet forward:
    conv/matmul dominate, so the static estimate lands within 2x of
    XLA's count (an estimator gate, not byte equality)."""
    from paddle_tpu.analysis.sharding_prop import segment_flops
    from paddle_tpu.vision.models import LeNet
    paddle.seed(0)
    model = LeNet()
    r = np.random.RandomState(0)
    x = paddle.to_tensor(r.randn(8, 1, 28, 28).astype("float32"))
    with lazy.lazy_guard(max_segment_ops=1 << 30) as ctx:
        out = model(x).mean()
        static = segment_flops(ctx.pending, ctx._in_vals)
        ctx.flush("cli")           # compile + run: captures the cost
    assert out is not None
    measured = comptel.executable_stats()[-1]["flops"]
    assert measured > 0 and static > 0
    ratio = static / measured
    assert 0.5 <= ratio <= 2.0, (static, measured, ratio)


def test_static_flop_model_cross_validated_tp_layer(compute_on):
    """Same cross-validation on a TP Column→Row parallel pair under
    the dryrun mesh — the per-chip measured count matches the static
    model sliced by the mesh's mp degree within 2x."""
    import jax
    import paddle_tpu.distributed as dist
    from paddle_tpu.analysis.sharding_prop import segment_flops
    paddle.seed(3)
    r = np.random.RandomState(3)
    with dist.auto_mesh(2, 2, dim_names=["dp", "mp"]):
        col = dist.fleet.mp_layers.ColumnParallelLinear(
            8, 16, gather_output=False, has_bias=False)
        row = dist.fleet.mp_layers.RowParallelLinear(
            16, 8, has_bias=False, input_is_parallel=True)
        x = paddle.to_tensor(r.randn(4, 8).astype("float32"))
        with lazy.lazy_guard(max_segment_ops=1 << 30) as ctx:
            out = row(col(x))
            static = segment_flops(ctx.pending, ctx._in_vals)
            ctx.flush("cli")
    assert out is not None
    entry = comptel.executable_stats()[-1]
    assert entry["n_devices"] == 4
    # weights shard over mp=2: each chip runs ~half the matmul FLOPs
    per_chip_static = static / 2
    ratio = per_chip_static / max(entry["flops"], 1)
    assert 0.5 <= ratio <= 2.0, (static, entry, ratio)


def test_op_flops_rule_table():
    from paddle_tpu.analysis.sharding_prop import op_flops

    class _A:
        def __init__(self, shape):
            self.shape = shape

    # matmul 2MNK
    assert op_flops("matmul", {}, [_A((64, 128)), _A((128, 32))],
                    [_A((64, 32))]) == 2 * 64 * 32 * 128
    # conv2d 2·|out|·C·R·S
    assert op_flops("conv2d", {}, [_A((2, 3, 8, 8)), _A((4, 3, 3, 3))],
                    [_A((2, 4, 6, 6))]) == 2 * (2 * 4 * 6 * 6) * 3 * 3 * 3
    # reduction: one op per input element
    assert op_flops("mean", {}, [_A((8, 8))], [_A(())]) == 64
    # elementwise: one op per output element
    assert op_flops("add", {}, [_A((8, 8)), _A((8, 8))],
                    [_A((8, 8))]) == 64


# ------------------------------------------------------------ frames

def test_frame_carries_compute_section(compute_on):
    from paddle_tpu.observability import distributed as dtel

    class _Store:
        def set(self, k, v):
            pass

    step = _train_step_fn()
    step()
    pub = dtel.TelemetryPublisher(_Store(), rank=0, world_size=1)
    try:
        pub.on_step(1)
        step()
        pub.on_step(2)
        frame = pub.frames[-1]
        comp = frame["compute"]
        assert comp["peak"] > 0
        assert comp["flops"] > 0
        assert "mfu" in comp and "gflops" in comp
    finally:
        pub.shutdown()


def test_step_table_compute_column_and_straggler_verdict():
    """Per-rank MFU column + the straggler evidence upgrade: the
    flagged slow rank reads "idle" when its MFU is far below the
    cross-rank median (device starving) and "saturated" otherwise."""
    from paddle_tpu.observability import distributed as dtel

    def frame(rank, dur_us, mfu):
        return {"v": 1, "rank": rank, "seq": 1, "step": 1,
                "t_wall": 0.0, "t_perf_us": 0.0, "counters": {},
                "hists": {}, "spans": [],
                "marks": [[1, 1000.0 * (rank + 1), dur_us]],
                "compute": {"flops": 1000, "peak": 1e12,
                            "gflops": mfu * 1000.0, "mfu": mfu}}

    # rank 2 is slow AND idle (low mfu): wall straggler, verdict idle
    agg = dtel.TelemetryAggregator()
    agg.add_frame(frame(0, 1000.0, 0.5))
    agg.add_frame(frame(1, 1000.0, 0.5))
    agg.add_frame(frame(2, 5000.0, 0.05))
    table = agg.step_table()
    assert table["compute"]["ranks"]["2"]["mfu"] == 0.05
    row = table["steps"][0]
    assert row["straggler"] == 2 and row["straggler_via"] == "wall"
    assert row["straggler_compute"] == "idle"
    text = dtel.render_step_table(table)
    assert "per-rank MFU" in text and "idle" in text

    # slow but saturated: comparable mfu
    agg2 = dtel.TelemetryAggregator()
    agg2.add_frame(frame(0, 1000.0, 0.5))
    agg2.add_frame(frame(1, 1000.0, 0.5))
    agg2.add_frame(frame(2, 5000.0, 0.48))
    row2 = agg2.step_table()["steps"][0]
    assert row2["straggler"] == 2
    assert row2["straggler_compute"] == "saturated"


# ---------------------------------------------------------- satellites

def test_bn_running_stats_update_in_window():
    """Satellite: the BN running-stat update is in-window elementwise
    state math — a train-mode BN step seals at backward with ZERO
    host syncs, and the stats still match the reference formula."""
    paddle.seed(0)
    model = nn.Sequential(nn.Conv2D(1, 4, 3), nn.BatchNorm2D(4),
                          nn.ReLU())
    model.train()
    opt = paddle.optimizer.SGD(0.1, parameters=model.parameters())
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(2, 1, 8, 8).astype("float32"))

    def step():
        loss = model(x).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        np.asarray(loss._value)

    report, counts, rec = analysis.trace_step(step)
    assert rec.sync_count() == 0, counts
    assert rec.break_count() == 0, counts
    assert not report.by_checker("host_sync"), report.render()

    # numerics: 2 fresh steps against the manual formula
    bn = nn.BatchNorm2D(4)
    bn.train()
    r = np.random.RandomState(1)
    rm = np.zeros(4, "float32")
    rv = np.ones(4, "float32")
    for _ in range(2):
        xb = r.randn(2, 4, 5, 5).astype("float32")
        np.asarray(bn(paddle.to_tensor(xb))._value)
        rm = 0.9 * rm + 0.1 * xb.mean(axis=(0, 2, 3))
        rv = 0.9 * rv + 0.1 * xb.var(axis=(0, 2, 3))
    assert np.allclose(bn._mean.numpy(), rm, atol=1e-5)
    assert np.allclose(bn._variance.numpy(), rv, atol=1e-5)


def test_flash_attention_records_into_window():
    """Satellite: flash_attention's record-time aval inference works
    — the op joins the fusion window (no record_fallback) and matches
    the SDPA reference."""
    from paddle_tpu.nn.functional.attention import \
        scaled_dot_product_attention
    r = np.random.RandomState(0)
    q = paddle.to_tensor(r.randn(2, 128, 4, 16).astype("float32"))
    with lazy.lazy_guard(max_segment_ops=1 << 30) as ctx:
        out, _ = F.flash_attention(q, q, q, causal=True)
        assert ctx._last_record_error is None
        assert any(p.op.name == "flash_attention" for p in ctx.pending)
    got = np.asarray(out._value)
    ref = np.asarray(scaled_dot_product_attention(
        q, q, q, None, 0.0, True, True)._value)
    assert np.abs(got - ref).max() < 1e-5


def test_gpt_step_reaches_fused_steady_state():
    """Satellite acceptance: the eager-GPT budget model (flash
    attention on the record path) stays in ONE fusion window and
    seals at the fused fwd+vjp backward — zero breaks, zero syncs."""
    from paddle_tpu.models.gpt import (GPTConfig, GPTForPretraining,
                                       GPTPretrainingCriterion)
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=32, num_layers=2,
                    num_heads=2, dtype="float32",
                    use_flash_attention=False,
                    max_position_embeddings=128)
    model = GPTForPretraining(cfg)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    r = np.random.RandomState(0)
    x = paddle.to_tensor(r.randint(0, 256, (1, 128)).astype("int64"))
    y = paddle.to_tensor(r.randint(0, 256, (1, 128)).astype("int64"))

    def step():
        loss = crit(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        np.asarray(loss._value)

    report, counts, rec = analysis.trace_step(step)
    assert rec.break_count() == 0, counts
    assert rec.sync_count() == 0, counts
    assert counts.get("backward_fused") == 1, counts
