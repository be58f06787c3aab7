"""Fused-runtime telemetry: metrics registry, structured spans in the
chrome trace, flight recorder, and the observability-off zero-work
contract (ISSUE 3 tentpole)."""
import json
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import _state, flight, metrics

from conftest import with_flag


@pytest.fixture
def obs_on():
    """Metrics collection on for the test, restored (and registry
    cleaned) afterwards."""
    with with_flag("FLAGS_observability", True):
        obs.reset()
        yield
    obs.reset()


# ------------------------------------------------------------- registry

def test_counter_gauge_histogram_snapshot(obs_on):
    metrics.counter("t.c").inc()
    metrics.counter("t.c").inc(4)
    metrics.gauge("t.g").set(2.5)
    for v in (3.0, 7.0, 100.0):
        metrics.histogram("t.h").observe(v)
    snap = metrics.snapshot()
    assert snap["counters"]["t.c"] == 5
    assert snap["gauges"]["t.g"] == 2.5
    h = snap["histograms"]["t.h"]
    assert (h["count"], h["min"], h["max"]) == (3, 3.0, 100.0)
    assert h["avg"] == pytest.approx(110.0 / 3)


def test_reset_zeroes_in_place(obs_on):
    """Instrumentation sites hold direct Counter references (ExecCache
    hit/miss); reset must zero the OBJECT, not orphan it."""
    c = metrics.counter("t.held")
    c.inc(3)
    obs.reset()
    assert c.value == 0
    c.inc()
    assert metrics.snapshot()["counters"]["t.held"] == 1


def test_threaded_increments(obs_on):
    c = metrics.counter("t.threads")

    def worker():
        for _ in range(1000):
            c.inc()

    ts = [threading.Thread(target=worker) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 8000


def test_set_flags_partial_failure_is_atomic():
    """A typo'd name mid-dict must not leave earlier flags written with
    their watcher-cached gates stale — set_flags validates everything
    before mutating anything."""
    from paddle_tpu._core import flags as F

    before = F.flag_value("FLAGS_static_checks")
    with pytest.raises(ValueError):
        paddle.set_flags({"FLAGS_static_checks": "error",
                          "FLAGS_definitely_not_a_flag": 1})
    assert F.flag_value("FLAGS_static_checks") == before
    assert F.STATIC_CHECKS_ACTIVE == (before not in F.STATIC_CHECKS_OFF)


def test_flag_gate_sync():
    assert not _state.METRICS
    with with_flag("FLAGS_observability", True):
        assert _state.METRICS and _state.ACTIVE
    assert not _state.METRICS
    with with_flag("FLAGS_flight_recorder", True):
        assert _state.FLIGHT and _state.ACTIVE
    assert not _state.FLIGHT


# ------------------------------------------------- zero work when off

def test_off_mode_zero_registry_work():
    """With observability off, the dispatch microbench must do ZERO
    registry mutations, asserted exactly (the
    sanitizer is silenced too: its sweep counter is a legitimate
    registry write gated by its own flag)."""
    x = paddle.to_tensor(np.ones((8, 8), "float32"))
    with with_flag("FLAGS_static_checks", "off"):
        with with_flag("FLAGS_observability", False):
            y = x
            for _ in range(8):
                y = y * 1.001 + 0.1
            np.asarray(y._value)     # warm the caches off-meter
            before = metrics.MUTATIONS
            for _ in range(5):
                y = x
                for _ in range(8):
                    y = y * 1.001 + 0.1
                np.asarray(y._value)
            assert metrics.MUTATIONS == before


# -------------------------------------------------- runtime counters

def test_segment_flush_counters(obs_on):
    from paddle_tpu._core import lazy
    lazy.clear_segment_cache()
    x = paddle.to_tensor(np.ones((5, 7), "float32"))
    obs.reset()
    y = (x * 2.0 + 1.0).sum()
    float(y.numpy())                     # flush (cold -> compile)
    y2 = (x * 2.0 + 1.0).sum()
    float(y2.numpy())                    # same signature -> cache hit
    snap = obs.stats()
    c = snap["counters"]
    assert c["segment.flushes"] == 2
    assert c["segment.flush_reason.materialize"] == 2
    assert c["cache.segment.miss"] == 1
    assert c["cache.segment.hit"] == 1
    assert c["compiles.segment"] == 1
    assert snap["compiles"] == 1
    assert snap["cache_hit_rate"] == 0.5
    h = snap["histograms"]
    assert h["segment.flush_us"]["count"] == 2
    assert h["segment.compile_us"]["count"] == 1
    assert h["segment.execute_us"]["count"] == 1


def test_sanitizer_sweeps_live_in_registry():
    """The ad-hoc hooks.SEGMENT_SWEEPS module counter is folded into
    the registry and counts even with observability off (its own flag
    gates the path)."""
    from paddle_tpu.analysis import hooks
    x = paddle.to_tensor(np.ones((4, 4), "float32"))
    with with_flag("FLAGS_static_checks", "warn"):
        before = hooks.segment_sweeps()
        float((x + 1.0).sum().numpy())
        assert hooks.segment_sweeps() == before + 1


def test_eager_ops_counter_when_fusion_off(obs_on):
    before = obs.stats()["counters"].get("eager.ops", 0)
    with with_flag("FLAGS_eager_fusion", False):
        x = paddle.to_tensor(np.ones((4, 4), "float32"))
        _ = (x * 3.0).numpy()
    assert obs.stats()["counters"]["eager.ops"] > before


# ------------------------------------------- steady-state acceptance

def _lenet_step_fn():
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(1e-3, parameters=model.parameters())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(16, 1, 28, 28).astype(np.float32))
    y = paddle.to_tensor(rng.randint(0, 10, (16,)).astype(np.int64))

    def step():
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return step


def test_lenet_steady_state_stats(obs_on):
    """Acceptance: after warm steps the step cache serves the train
    loop (hit rate >= 0.8) and `compiles` froze at the cold-step
    count."""
    from paddle_tpu._core import lazy
    lazy.clear_segment_cache()
    step = _lenet_step_fn()
    obs.reset()
    for _ in range(2):                    # cold steps: compiles happen
        step()
    cold = obs.stats()
    assert cold["compiles"] > 0
    for _ in range(10):                   # warm steps: zero compiles
        step()
    snap = obs.stats()
    assert snap["compiles"] == cold["compiles"]
    assert snap["step_cache_hit_rate"] >= 0.8
    assert snap["counters"]["autograd.fused_steps"] == 12
    assert snap["counters"]["optimizer.steps"] == 12


def test_lenet_trace_spans(obs_on, tmp_path):
    """Acceptance: an exported chrome trace of LeNet train steps shows
    segment::flush[reason] spans with compile vs. cached-execute
    children alongside host events."""
    from paddle_tpu._core import lazy
    from paddle_tpu.profiler import Profiler, ProfilerTarget, RecordEvent

    lazy.clear_segment_cache()
    step = _lenet_step_fn()
    with Profiler(targets=[ProfilerTarget.CPU],
                  fused_runtime=True) as p:
        with RecordEvent("train_loop"):
            for _ in range(3):            # step 1 cold, 2-3 warm
                step()
    path = p.export(str(tmp_path / "lenet.trace.json"))
    trace = json.load(open(path))["traceEvents"]
    spans = [e for e in trace if e.get("cat") == "runtime"]
    names = [e["name"] for e in spans]
    assert "segment::flush[backward_fused]" in names
    assert "segment::compile" in names    # the cold step
    assert "segment::execute" in names    # the warm steps
    assert "optimizer::fused_step" in names
    # flush spans carry the structured reason in args
    fl = next(e for e in spans
              if e["name"] == "segment::flush[backward_fused]")
    assert fl["args"]["reason"] == "backward_fused"
    assert fl["args"]["ops"] > 0
    # host events coexist on the same timeline
    assert any(e["name"] == "train_loop" for e in trace)


# ------------------------------------------------------ flight recorder

def test_flight_recorder_dump_on_flush_failure(tmp_path, monkeypatch):
    """A failed segment flush dumps the ring to a readable report."""
    from paddle_tpu._core import lazy

    def boom(pending, live):
        raise RuntimeError("seeded flush failure")

    with with_flag("FLAGS_flight_recorder", True), \
            with_flag("FLAGS_flight_recorder_dir", str(tmp_path)), \
            with_flag("FLAGS_observability", True):
        obs.reset()
        x = paddle.to_tensor(np.ones((3, 3), "float32"))
        float((x * 2.0).sum().numpy())    # a healthy flush first
        monkeypatch.setattr(lazy, "_build_segment_fn", boom)
        y = x * 5.0 + 1.0                 # fresh signature -> miss
        with pytest.raises(RuntimeError, match="seeded flush failure"):
            float(y.sum().numpy())
        monkeypatch.undo()
        dumps = list(tmp_path.glob("flight_*.txt"))
        assert dumps, "flush failure did not dump a flight record"
        body = dumps[0].read_text()
        assert "flush_failed" in body
        assert "seeded flush failure" in body
        assert "segment::flush" in body   # the healthy flush's span
        # the FAILING flush's own span made it into the report too
        # (spans end before the dump), tagged with the error
        assert any("segment::flush" in ln and "error=" in ln
                   for ln in body.splitlines())
        assert obs.stats()["counters"]["flight.dumps"] >= 1
    obs.reset()


def test_flight_recorder_dump_on_enforce(tmp_path):
    from paddle_tpu.base.core import InvalidArgumentError

    with with_flag("FLAGS_flight_recorder", True), \
            with_flag("FLAGS_flight_recorder_dir", str(tmp_path)):
        flight.reset()
        flight.note("span", "segment::flush[test]", dur_us=1.0)
        with pytest.raises(InvalidArgumentError):
            raise InvalidArgumentError("seeded enforce", "hint")
        dumps = list(tmp_path.glob("flight_*.txt"))
        assert dumps
        body = dumps[0].read_text()
        assert "enforce" in body and "seeded enforce" in body
    flight.reset()


def test_fused_backward_nan_trip_drops_trace(obs_on):
    """A FLAGS_check_nan_inf trip inside the fused step must drop the
    consumed trace like a failed compile — leaving it armed would
    re-execute the whole forward as a plain segment on the next read."""
    from paddle_tpu._core import lazy

    x = paddle.to_tensor(np.ones((2, 2), "float32"))
    x.stop_gradient = False
    y = (x * float("nan")).sum()
    # flag flips on AFTER recording (with it on at record time the
    # executor bypasses the fusion window entirely)
    with with_flag("FLAGS_check_nan_inf", True):
        with pytest.raises(FloatingPointError):
            y.backward()
    ctx = lazy.current_context()
    assert ctx is not None and not ctx.pending
    assert float((x.detach() + 1.0).sum().numpy()) == 8.0


def test_flight_capacity_change_is_live():
    """set_flags on the ring capacity resizes a live ring in place."""
    with with_flag("FLAGS_flight_recorder", True), \
            with_flag("FLAGS_flight_recorder_capacity", 8):
        flight.reset()
        for i in range(8):
            flight.note("span", f"c{i}")
        paddle.set_flags({"FLAGS_flight_recorder_capacity": 3})
        rec = obs.flight_record()
        assert "3 event(s)" in rec and "c7" in rec and "c4" not in rec
    flight.reset()


def test_flight_ring_is_bounded():
    with with_flag("FLAGS_flight_recorder", True), \
            with_flag("FLAGS_flight_recorder_capacity", 8):
        flight.reset()
        for i in range(50):
            flight.note("span", f"e{i}")
        rec = obs.flight_record()
        assert "e49" in rec and "e0 " not in rec
        assert rec.count("span") <= 9
    flight.reset()


# ----------------------------------------------------------------- CLI

def test_cli_chain_json(capsys):
    from paddle_tpu.observability.__main__ import main

    with with_flag("FLAGS_observability", False):
        assert main(["--steps", "3", "--json"]) == 0
        out = capsys.readouterr().out
    snap = json.loads(out.strip().splitlines()[-1])
    assert snap["counters"]["segment.flushes"] >= 3
    assert "compiles" in snap and "cache_hit_rate" in snap
    obs.reset()


def test_stats_without_enable_is_well_formed():
    snap = obs.stats()
    assert set(snap) >= {"counters", "gauges", "histograms", "compiles",
                         "cache_hit_rate", "step_cache_hit_rate"}


# -------------------------------------------------------------- budget

def test_budget_mode_ranks_components(capsys):
    """`python -m paddle_tpu.observability budget` aggregates the span
    histograms into a ranked per-step table whose entries (incl. the
    unspanned host gap) sum to the wall time."""
    from paddle_tpu.observability.__main__ import main

    assert main(["budget", "--model", "chain", "--steps", "3",
                 "--json"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["model"] == "chain" and out["steps"] == 3
    assert out["wall_us_per_step"] > 0
    names = [e["name"] for e in out["entries"]]
    assert any("host gap" in n for n in names)
    assert any(n.startswith("segment::") for n in names)
    total = sum(e["us_per_step"] for e in out["entries"])
    want = out["accounted_us_per_step"] + out["host_gap_us_per_step"]
    assert abs(total - want) < max(1.0, 0.01 * want)
    # ranked: descending per-step cost
    costs = [e["us_per_step"] for e in out["entries"]]
    assert costs == sorted(costs, reverse=True)
    obs.reset()


def test_budget_collect_restores_metrics_flag():
    from paddle_tpu.observability import budget as budget_mod

    x = paddle.to_tensor(np.ones((4, 4), "float32"))

    def step():
        np.asarray((x * 1.5)._value)

    with with_flag("FLAGS_observability", False):
        out = budget_mod.collect(step, steps=2, warmup=1)
        assert not obs.enabled()       # collect turned it back off
    assert out["wall_us_per_step"] > 0
    assert out["host_gap_us_per_step"] <= out["wall_us_per_step"]
    obs.reset()
