"""Live monitoring plane (observability/timeseries.py + exporter.py):
Prometheus exposition validity, /healthz hang mapping, ring bounding,
the EWMA regression watchdog on seeded series, monitor-off zero work,
deep-capture trace retention, and `top` rendering from dumped frames.
"""
import json
import os
import re
import sys
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import (_state, exporter, flight, metrics,
                                      timeseries)

from conftest import with_flag


@pytest.fixture
def monitor_on():
    """Monitor plane on with a huge interval (ticks driven by hand via
    sample_once) and no auto-bound port; everything torn down after."""
    timeseries.reset()
    with with_flag("FLAGS_monitor_interval_s", 3600.0), \
            with_flag("FLAGS_monitor_port", 0), \
            with_flag("FLAGS_monitor", True):
        yield
    exporter.stop()
    timeseries.reset()


def _feed_steps(n=4, dur_s=0.01, tokens=128):
    """Seed the monitor's step accounting without wall-clock sleeps."""
    for _ in range(n):
        timeseries.on_step(0)
        timeseries.note_tokens(tokens)
    with timeseries._LOCK:
        timeseries._WIN_DUR_S += n * dur_s
        timeseries._WIN_N += n


def _tick(prev, at):
    """One deterministic sampler tick at wall time `at`."""
    prev["t"] = prev.get("t")  # no-op; keeps call sites readable
    real_time = timeseries.time.time
    timeseries.time.time = lambda: at
    try:
        timeseries.sample_once(prev)
    finally:
        timeseries.time.time = real_time


# ------------------------------------------------------ /metrics format

_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*\{[a-zA-Z0-9_=\",. \-/()\[\]:]*\} "
    r"-?[0-9.e+\-]+$")


def test_metrics_prometheus_validity(monitor_on):
    metrics.inc("cache.fused_step.hit", 3)
    metrics.inc("weird-name.with.dots", 2)     # sanitization input
    metrics.gauge("some.gauge").set(7)
    metrics.observe("step.flush_us", 123.0)
    prev = {}
    _feed_steps(4)
    _tick(prev, 100.0)
    _feed_steps(4)
    _tick(prev, 101.0)

    body = exporter.render_metrics()
    lines = body.strip().splitlines()
    assert lines, "empty exposition"
    types = {}
    for ln in lines:
        if ln.startswith("# TYPE "):
            _, _, name, kind = ln.split()
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = kind
        else:
            assert _SAMPLE_RE.match(ln), f"malformed sample line: {ln!r}"
            mname = ln.split("{", 1)[0]
            assert mname in types, f"sample before TYPE: {ln!r}"
            assert 'rank="0"' in ln, f"missing rank label: {ln!r}"

    # sanitization: dots/dashes become underscores, prefix applied
    assert types.get("paddle_tpu_weird_name_with_dots_total") \
        == "counter"
    # counter-vs-gauge typing
    assert types.get("paddle_tpu_cache_fused_step_hit_total") \
        == "counter"
    assert types.get("paddle_tpu_some_gauge") == "gauge"
    assert types.get("paddle_tpu_step_flush_us_count") == "counter"
    # monitor rings surface as gauges, incl. the headline rates
    assert types.get("paddle_tpu_monitor_steps_per_s") == "gauge"
    assert types.get("paddle_tpu_monitor_tokens_per_s") == "gauge"
    assert types.get("paddle_tpu_monitor_mem_peak_bytes") == "gauge"
    # the second tick had 4 steps over 1s of wall
    line = next(ln for ln in lines
                if ln.startswith("paddle_tpu_monitor_steps_per_s{"))
    assert abs(float(line.rsplit(" ", 1)[1]) - 4.0) < 0.5


# ---------------------------------------------------------- endpoints

def test_http_endpoints_and_healthz_503(monitor_on):
    port = exporter.start(0)
    _feed_steps(2)
    _tick({}, 10.0)

    def get(path):
        try:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10)
            return resp.status, resp.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    code, body = get("/metrics")
    assert code == 200 and "# TYPE" in body

    code, body = get("/healthz")
    h = json.loads(body)
    assert code == 200 and h["ok"] and h["membership_epoch"] >= 0
    assert h["steps"] == 2 and h["last_step_age_s"] is not None

    code, body = get("/snapshot")
    snap = json.loads(body)
    assert code == 200 and snap["monitor"]["steps"] == 2
    assert "counters" in snap

    code, body = get("/timeseries")
    assert code == 200 and "mem_peak_bytes" in \
        json.loads(body)["series"]
    code, body = get("/timeseries?name=mem_peak_bytes")
    assert code == 200 and json.loads(body)["samples"]

    code, _ = get("/nonsense")
    assert code == 404

    # a tripped hang watchdog maps to 503 (external prober pages)
    from paddle_tpu.observability import goodput
    old = goodput.LEDGER.last_hang
    goodput.LEDGER.last_hang = {"bucket": "comm_wait", "timeout_s": 8.0,
                                "latency_s": 9.1, "t_wall": 1.0}
    try:
        code, body = get("/healthz")
        assert code == 503
        assert json.loads(body)["hang"]["bucket"] == "comm_wait"
    finally:
        goodput.LEDGER.last_hang = old


def test_exporter_bound_by_flag_and_torn_down():
    timeseries.reset()
    with with_flag("FLAGS_monitor_interval_s", 3600.0), \
            with_flag("FLAGS_monitor_port", 0):
        with with_flag("FLAGS_monitor", True):
            # port flag 0 = no HTTP, but the sampler runs
            assert timeseries.sampler_alive()
            assert exporter.bound_port() is None
        assert not timeseries.sampler_alive()
    timeseries.reset()


# ------------------------------------------------------- ring bounding

def test_ring_bounding(monitor_on):
    with with_flag("FLAGS_monitor_ring", 8):
        prev = {}
        for i in range(30):
            _feed_steps(1)
            _tick(prev, 100.0 + i)
        samples = timeseries.series("steps_per_s")
        assert len(samples) == 8, \
            f"ring not bounded: {len(samples)} samples"
        # newest kept, oldest dropped
        assert samples[-1][0] == 129.0 and samples[0][0] == 122.0


# ------------------------------------------------- regression watchdog

def test_ewma_watchdog_fire_and_no_fire(monitor_on):
    wd = timeseries._Regression(factor=1.5, steps=3)
    base = metrics.counter("monitor.regressions").value

    # stable series: no fire
    for i in range(10):
        wd.judge("step_time_ms", 10.0 + 0.1 * (i % 2), float(i))
    assert not timeseries.REGRESSIONS

    # brief 2x spike (shorter than the sustain window): no fire
    for i in range(2):
        wd.judge("step_time_ms", 20.0, 10.0 + i)
    for i in range(5):
        wd.judge("step_time_ms", 10.0, 12.0 + i)
    assert not timeseries.REGRESSIONS

    # sustained 2x slowdown: exactly ONE event, then quiet
    for i in range(10):
        wd.judge("step_time_ms", 20.0, 20.0 + i)
    assert len(timeseries.REGRESSIONS) == 1
    ev = timeseries.REGRESSIONS[0]
    assert ev["series"] == "step_time_ms"
    assert ev["current"] == 20.0 and ev["baseline"] < 12.0
    assert metrics.counter("monitor.regressions").value == base + 1

    # down-bad series: a tokens/s collapse fires too
    for i in range(10):
        wd.judge("tokens_per_s", 1000.0, 40.0 + i)
    for i in range(10):
        wd.judge("tokens_per_s", 400.0, 50.0 + i)
    assert len(timeseries.REGRESSIONS) == 2
    assert timeseries.REGRESSIONS[1]["series"] == "tokens_per_s"


def test_seeded_slowdown_fires_once_with_flight_evidence(
        monitor_on, tmp_path):
    """The acceptance drill's seeded 2x step-time slowdown, driven
    deterministically through sample_once: one regression event, with
    the baseline-vs-current evidence on the flight ring."""
    with with_flag("FLAGS_flight_recorder", True), \
            with_flag("FLAGS_flight_recorder_dir", str(tmp_path)):
        prev = {}
        for i in range(8):                      # healthy baseline
            _feed_steps(4, dur_s=0.010)
            _tick(prev, 100.0 + i)
        for i in range(10):                     # sustained 2.5x
            _feed_steps(4, dur_s=0.025)
            _tick(prev, 110.0 + i)
        assert len(timeseries.REGRESSIONS) == 1
        ev = timeseries.REGRESSIONS[0]
        assert ev["series"] == "step_time_ms"
        assert ev["current"] >= 2.0 * ev["baseline"]
        notes = [e for e in flight.entries()
                 if e[1] == "monitor" and e[2] == "regression"]
        assert len(notes) == 1
        assert notes[0][3]["baseline"] == ev["baseline"]
        assert notes[0][3]["current"] == ev["current"]


# ------------------------------------------------------ off-freeze gate

def test_monitor_off_is_free_across_lenet_loop():
    """Satellite: with FLAGS_monitor off (async flush ON — the hardest
    regime) a LeNet train loop must see zero sampler threads, no bound
    port, and a frozen registry (the off-path discipline of every plane)."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import LeNet

    paddle.seed(0)
    model = LeNet()
    opt = paddle.optimizer.Adam(1e-3, parameters=model.parameters())
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(8, 1, 28, 28).astype("float32"))
    y = paddle.to_tensor(rng.randint(0, 10, (8,)).astype("int64"))

    def step():
        loss = F.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        np.asarray(loss._value)

    # static checks off for the freeze window: the sanitizer plane
    # (conftest runs the suite in warn mode) legitimately counts its
    # sweeps — the frozen-registry assertion is about the MONITOR
    # being free, as tests/test_observability.py holds the registry
    with with_flag("FLAGS_async_flush", True), \
            with_flag("FLAGS_static_checks", "off"):
        step()                                  # warm off-clock
        from paddle_tpu._core import async_flush
        async_flush.drain()
        assert not _state.MONITOR
        before = metrics.MUTATIONS
        for _ in range(3):
            step()
        async_flush.drain()
        assert metrics.MUTATIONS == before, \
            "monitor-off LeNet loop mutated the registry"
        ts = sys.modules.get("paddle_tpu.observability.timeseries")
        assert ts is None or not ts.sampler_alive()
        assert exporter.bound_port() is None


# ------------------------------------------- deep-capture trace retention

def test_flight_retention_covers_deep_capture_traces(tmp_path):
    """Satellite: monitor deep-capture traces (auto-named .json beside
    the flight ring) prune under the same rank-aware
    FLAGS_flight_max_dumps policy; explicit-path dumps stay exempt."""
    with with_flag("FLAGS_flight_recorder_dir", str(tmp_path)), \
            with_flag("FLAGS_flight_max_dumps", 2):
        keep = tmp_path / "explicit_trace.json"
        keep.write_text("{}")
        protected = tmp_path / "flight_distributed_1_1.txt"
        protected.write_text("postmortem")
        paths = []
        for i in range(4):
            p = flight.trace_path()
            with open(p, "w") as f:
                f.write("{}")
            os.utime(p, (1000 + i, 1000 + i))
            paths.append(p)
            flight.prune_dumps()
        survivors = sorted(str(p) for p in tmp_path.glob("flight_trace_*"))
        assert survivors == sorted(paths[-2:]), \
            f"retention kept {survivors}, wanted newest 2"
        assert keep.exists(), "explicit-path file was pruned"
        assert protected.exists(), "distributed postmortem was pruned"
        # mixed pool: a text dump prunes against the same per-rank cap
        flight.dump(reason="mixed-pool")
        names = {p.name for p in tmp_path.glob("flight_*")}
        auto = [n for n in names if flight._PRUNABLE_RE.match(n)]
        assert len(auto) == 2


def test_prunable_pattern():
    m = flight._PRUNABLE_RE.match
    assert m("flight_12345_1.txt")
    assert m("flight_r3_12345_2.txt").group(1) == "3"
    assert m("flight_oom_r1_99_1.txt").group(1) == "1"
    assert m("flight_trace_12345_3.json")
    assert m("flight_trace_r2_12345_4.json").group(1) == "2"
    assert not m("flight_distributed_12345_1.txt")
    assert not m("flight_trace_12345_3.txt.bak")
    assert not m("my_trace.json")


# ------------------------------------------------------------- cluster

def _fake_dump(path, rank, durs_us, *, mfu=None, peak=None,
               goodput=None):
    """One telem_rank<R>.json with per-step marks and optional
    mem/compute/goodput frame sections."""
    from paddle_tpu.observability import distributed as dtel
    marks, t = [], 1000.0
    for i, d in enumerate(durs_us, start=1):
        t += d
        marks.append([i, t, d])
    frame = {"v": dtel.FRAME_VERSION, "rank": rank, "pid": 1000 + rank,
             "seq": 1, "step": len(durs_us), "mesh_epoch": 0,
             "t_wall": 2000.0, "t_perf_us": t, "counters": {},
             "hists": {}, "spans": [], "marks": marks}
    if mfu is not None:
        frame["compute"] = {"mfu": mfu, "gflops": 1.0, "flops": 10,
                            "peak": 1e9}
    if peak is not None:
        frame["mem"] = {"live": peak // 2, "peak": peak, "donated": 0,
                        "census": 3}
    if goodput is not None:
        frame["goodput"] = {"buckets": goodput, "steps": len(durs_us)}
    with open(path, "w") as f:
        json.dump({"rank": rank, "frames": [frame]}, f)


def test_cluster_rows_and_top_render(tmp_path):
    from paddle_tpu.observability import distributed as dtel
    _fake_dump(tmp_path / "telem_rank0.json", 0, [10000.0] * 4,
               mfu=0.41, peak=64 << 20,
               goodput={"execute": 36000.0, "input_wait": 4000.0})
    _fake_dump(tmp_path / "telem_rank1.json", 1, [30000.0] * 4,
               mfu=0.12, peak=96 << 20,
               goodput={"execute": 40000.0, "comm_wait": 80000.0})
    agg = dtel.TelemetryAggregator()
    for p in sorted(tmp_path.glob("telem_rank*.json")):
        agg.add_dump(str(p))
    rows = exporter.cluster_rows(agg)
    assert [r["rank"] for r in rows] == [0, 1]
    assert abs(rows[0]["steps_per_s"] - 100.0) < 1.0
    assert rows[0]["mfu"] == 0.41
    assert rows[1]["peak_bytes"] == 96 << 20
    assert abs(rows[0]["goodput_frac"] - 0.9) < 0.01
    assert rows[1]["straggler_steps"] >= 1     # 3x the median, flagged
    assert rows[1]["top_badput"] == "comm_wait"

    text = exporter.render_top(rows, title="test")
    assert "r0" in text and "r1" in text and "YES" in text
    assert "MFU" in text and "goodput" in text

    # the cluster section rides /metrics with per-rank labels
    exporter.attach_cluster(agg)
    try:
        body = exporter.render_metrics()
        assert 'paddle_tpu_cluster_mfu{rank="1"} 0.12' in body
        assert 'paddle_tpu_cluster_straggler_steps{rank="1"}' in body
    finally:
        exporter.detach_cluster()


def test_top_cli_from_dumped_frames(tmp_path, capsys):
    _fake_dump(tmp_path / "telem_rank0.json", 0, [5000.0] * 3)
    _fake_dump(tmp_path / "telem_rank1.json", 1, [5200.0] * 3)
    from paddle_tpu.observability.__main__ import main
    rc = main(["top", "--store", str(tmp_path), "--count", "1"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "paddle_tpu top" in out
    assert "r0" in out and "r1" in out
    # refuses to run with neither a live endpoint nor a store
    assert main(["top", "--count", "1"]) == 2
