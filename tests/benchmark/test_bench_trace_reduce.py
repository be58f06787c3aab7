"""trace_reduce against a hand-made trace whose figures are worked out below,
and against a trace recorded on a v5e (benchmarks/testdata)."""
import gzip
import os
import types

import pytest
from jax.profiler import ProfileData

from benchmarks import peaks
from benchmarks import trace_reduce as tr
from benchmarks.layer_metrics import Run, _flash

DATA = os.path.join(os.path.dirname(tr.__file__), "testdata")
US = 1e-6


@pytest.fixture(scope="module")
def hand():
    with open(os.path.join(DATA, "hand_step.hlo.txt")) as f:
        ops = tr.parse_hlo(f.read())
    with open(os.path.join(DATA, "hand_trace.textproto")) as f:
        trace = tr.from_profile(ProfileData.from_text_proto(f.read()))
    return ops, trace, tr.reduce(trace, ops, steps=2)


def test_hlo_categories(hand):
    ops, _, _ = hand
    want = {"fusion.1": "matmul", "fusion.3": "matmul", "fusion.2": "other",
            "convolution.1": "matmul", "closed_call.9": "mosaic",
            "checkpoint.23": "mosaic", "all-gather.3": "collective",
            "while.1": "container",
            "collective-permute-start.1": "collective",
            "collective-permute-done.1": "collective"}
    assert {k: ops[k].category for k in want} == want
    assert ops["collective-permute-done.1"].waits_for \
        == "collective-permute-start.1"
    assert ops["fusion.1"].label == \
        "matmul: closed_call/bsh,hk->bsk/dot_general"
    # a Mosaic call is known by its shapes: no name of the program's is read
    assert ops["closed_call.9"].operands == ((8, 8),) * 3
    assert ops["closed_call.9"].results == ((8, 8), (8, 1))
    assert ops["checkpoint.23"].operands == ((8, 8),) * 4 + ((8, 1),) * 2
    assert ops["checkpoint.23"].results == ((8, 8), (8, 8))
    # a fusion holds the shapes of what it fuses
    assert "1,1,8,8" in ops["fusion.2"].shapes
    assert "1,1,8,8" not in ops["fusion.1"].shapes
    assert [k for k, o in ops.items() if o.remat] == ["fusion.3"]


def test_only_device_planes_and_bench_spans_are_read(hand):
    _, trace, _ = hand
    assert sorted(trace.ops) == ["/device:TPU:0", "/device:TPU:1"]
    assert {s.name for s in trace.host} == {
        "bench/window", "bench/make_batch", "bench/step_call", "bench/sync",
        "bench/h2d"}


def test_busy_idle_and_categories_by_hand(hand):
    """Chip 0: while 100-500 holds matmul 100, fwd 60, all-gather 40, other
    50, dkv 100 and 50 of its own; then permute-start 10, matmul 90,
    permute-done 50 (to 650); then matmul 700-900. Busy 400+150+200 = 750.
    Chip 1: matmul 200, all-gather 100, fwd 40; busy 340."""
    _, _, s = hand
    assert s.chips == 2 and s.steps == 2
    # the window is the device's own: first operation 100, last end 900
    assert s.window_s == pytest.approx(800 * US)
    assert s.busy_s == pytest.approx((750 + 340) / 2 * US)
    assert s.category_s["matmul"] == pytest.approx((390 + 200) / 2 * US)
    assert s.category_s["mosaic"] == pytest.approx((160 + 40) / 2 * US)
    assert s.category_s["collective"] == pytest.approx((100 + 100) / 2 * US)
    assert s.category_s["other"] == pytest.approx((100 + 0) / 2 * US)
    assert sum(s.category_s.values()) == pytest.approx(s.busy_s)


HAND_ATTENTION = {"batch": 1, "heads": 1, "seq": 8, "head_dim": 8,
                  "causal": True}


def _run(cell, summary, attention=HAND_ATTENTION, memory=None, step_ms=()):
    program = types.SimpleNamespace(memory=memory,
                                    facts={"attention": attention})
    v5e = peaks.peaks_of("TPU v5 lite") if summary is not None else None
    return Run(cell, program, v5e, 4, 4, 0, list(step_ms), summary)


def test_attention_kernels_are_told_from_shapes_by_hand(hand):
    """closed_call.9 takes three [8,8] (q, k, v): a forward call, 60 us on
    chip 0 and 40 on chip 1. checkpoint.23 takes four and gives two (dK,
    dV): two thirds of a backward pass, 100 us, on chip 0 only."""
    _, _, s = hand
    found = _flash.passes(_run(None, s))
    assert found["fwd"] == (pytest.approx(50 * US), 1.0)
    assert found["bwd"] == (pytest.approx(50 * US), pytest.approx(1 / 3))
    # another attention size: these calls are some other Pallas kernel
    other = dict(HAND_ATTENTION, seq=16)
    assert _flash.passes(_run(None, s, other)) \
        == {"fwd": (0, 0), "bwd": (0, 0)}
    assert _flash.passes(_run(None, None)) is None


def test_exposed_collective_time_by_hand(hand):
    """Worst chip is chip 0: all-gather 40 + permute-start 10 + permute-done
    50 block the core (exposed 100); the pair hides 600 - 510 = 90 behind
    the matmul between them."""
    _, _, s = hand
    assert s.collective_exposed_s == pytest.approx(100 * US)
    assert s.collective_s == pytest.approx(190 * US)


def test_breakdown_by_hand(hand):
    _, _, s = hand
    assert s.device_ops[0] == [
        "fusion.1 (matmul: closed_call/bsh,hk->bsk/dot_general)",
        pytest.approx(250 * US)]
    assert [n.split(" ")[0] for n, _ in s.device_ops[:2]] \
        == ["fusion.1", "all-gather.3"]
    assert len(s.device_ops) <= 10
    # the idlest chip is chip 1: idle from 440 to the window's end at 900,
    # of which bench/sync (600-700) covers the most
    assert s.idle_gaps == [["bench/sync", pytest.approx(460 * US)]]


def test_a_gap_inside_a_program_run_is_the_devices_own(hand):
    ops, trace, _ = hand
    chip0 = tr._reduce_chip(trace.ops["/device:TPU:0"], ops, (0, 1e6))
    named = tr._name_gaps(chip0.gaps, trace.modules["/device:TPU:0"],
                          trace.host)
    # 650-700 lies between two runs under bench/sync; 0-100 and 900-1000
    # under step_call and h2d; the while's own 350-400 is busy, not a gap
    assert named == {"bench/step_call": pytest.approx(100 * US),
                     "bench/sync": pytest.approx(50 * US),
                     "bench/h2d": pytest.approx(100 * US)}


def test_readers_on_the_hand_trace(hand):
    from benchmarks.cells import load_cell
    _, _, s = hand
    cell = load_cell("gpt3xl-dp2mp2-s2048")
    run = _run(cell, s, memory={"temp": 4.5e9}, step_ms=[10.0, 12.0, 11.0])
    got = {m: r.read(run) for m, r in cell.layer_metrics.items()}
    assert got["compile_cache_hit_share"] == 1.0
    assert got["compiles_in_window"] == 0
    assert got["step_ms_p50"] == 11.0
    assert got["step_temp_gb"] == 4.5
    # matmul 295 of 545 busy; fusion.3 (90 on chip 0) is what remat repeats
    assert got["matmul_share"] == pytest.approx(250 / 545)
    assert got["remat_share"] == pytest.approx(45 / 545)
    assert got["flash_ms_per_step"] == pytest.approx(0.1 / 2)
    assert got["collective_ms_per_step"] == pytest.approx(0.19 / 2)
    assert got["collective_exposed_share"] == pytest.approx(100 / 800)
    # at [1,8,8] both passes are memory-bound: 4 (8) arrays of 64
    # two-byte elements and one row of 8 float32
    assert got["flash_fwd_roofline"] == pytest.approx(
        100 * (544 / 819e9) * 1.0 / (50 * US))
    assert got["flash_bwd_roofline"] == pytest.approx(
        100 * (1056 / 819e9) * (1 / 3) / (50 * US))
    # no trace: nothing from the trace readers
    bare = _run(cell, None, memory={"temp": 1e9})
    assert {m for m, r in cell.layer_metrics.items()
            if r.read(bare) is not None} \
        == {"compile_cache_hit_share", "compiles_in_window", "step_temp_gb"}


def test_a_trace_with_no_such_operation_reads_zero_not_none(hand):
    """A reader whose operation is gone from the step says 0: None is for a
    run without a trace, and on the chip None makes `correct` false."""
    from benchmarks.cells import load_cell
    from benchmarks.layer_metrics import einsum_attention_ms_per_step as m
    from benchmarks.layer_metrics import flash_ms_per_step
    _, _, s = hand
    cell = load_cell("bertl-mlm-s512")
    # fusion.2 holds a [1,1,8,8] array: 50 us on chip 0, two steps
    assert m.read(_run(cell, s)) == pytest.approx(1e3 * 25 * US / 2)
    gone = dict(HAND_ATTENTION, seq=16)
    assert m.read(_run(cell, s, gone)) == 0.0
    assert flash_ms_per_step.read(_run(cell, s, gone)) == 0.0
    assert m.read(_run(cell, None)) is None


# benchmarks/testdata/record_trace.py: 8 rows x 4 heads, seq 256, head 64
RECORDED_ATTENTION = {"batch": 8, "heads": 4, "seq": 256, "head_dim": 64,
                      "causal": True}


def _recorded(name):
    """Four steps of benchmarks/testdata/record_trace.py on a v5e (my chip
    runs, PR 22): a scan of two (matmul, flash attention) layers."""
    with gzip.open(os.path.join(DATA, f"{name}.hlo.txt.gz"), "rt") as f:
        ops = tr.parse_hlo(f.read())
    with gzip.open(os.path.join(DATA, f"{name}.xplane.pb.gz")) as f:
        trace = tr.from_profile(ProfileData.from_serialized_xspace(f.read()))
    return ops, trace, tr.reduce(trace, ops, steps=4)


@pytest.fixture(scope="module")
def recorded():
    return _recorded("recorded_1chip")


def test_recorded_trace_is_read_as_a_tpu_trace_is_laid_out(recorded):
    ops, trace, _ = recorded
    assert list(trace.ops) == ["/device:TPU:0"]     # not '#Chip0 ...' planes
    events = trace.ops["/device:TPU:0"]
    assert len(events) == 308 and len(trace.modules["/device:TPU:0"]) == 4
    # event names are whole HLO lines in a real trace: all resolve
    assert all(e.name in ops for e in events)
    assert {s.name for s in trace.host} == {
        "bench/window", "bench/make_batch", "bench/h2d", "bench/step_call",
        "bench/sync"}


def test_recorded_trace_figures_read_by_hand(recorded):
    """From the trace's own lines: the four program runs last 314.5, 314.4,
    314.3 and 314.5 us (1257.8 us) from 46813.9 to 52437.3 us."""
    _, trace, s = recorded
    runs = trace.modules["/device:TPU:0"]
    assert sum(m.end - m.start for m in runs) * 1e-9 \
        == pytest.approx(1257.75 * US, rel=1e-4)
    assert s.window_s == pytest.approx(5622.6 * US, rel=1e-4)
    # the operations fill the runs but for their first microsecond
    assert s.busy_s == pytest.approx(1253.2 * US, rel=1e-3)
    assert sum(s.category_s.values()) == pytest.approx(s.busy_s)
    assert s.category_s["mosaic"] / s.busy_s == pytest.approx(0.771, abs=2e-3)
    # two layers x four steps: eight forward calls, and eight backward
    # passes that a dKV and a dQ call share, found by their shapes
    found = _flash.passes(_run(None, s, RECORDED_ATTENTION))
    assert found["fwd"] == (pytest.approx(341.1 * US, rel=1e-3), 8)
    assert found["bwd"][1] == pytest.approx(8)
    assert found["fwd"][0] + found["bwd"][0] \
        == pytest.approx(s.category_s["mosaic"])
    assert s.collective_s == 0
    # the device waits longest while the host reads the loss
    assert s.idle_gaps[0][0] == "bench/sync"
    assert s.idle_gaps[0][1] == pytest.approx(4290 * US, rel=1e-3)
    assert s.device_ops[0][0].startswith("closed_call.49 (mosaic: ")


def test_recorded_four_chip_trace_has_the_gradient_all_reduce():
    """The same program with its batch split over a dp mesh of four chips:
    per step one all-reduce of the loss and one per layer of the weight
    gradient, each an event of its own that blocks the core."""
    ops, trace, s = _recorded("recorded_4chip")
    assert sorted(trace.ops) == [f"/device:TPU:{i}" for i in range(4)]
    assert s.chips == 4
    collectives = {k for k, o in ops.items() if o.category == "collective"}
    assert collectives == {"all-reduce.1", "all-reduce.4"}
    for events in trace.ops.values():
        calls = [e.name for e in events if e.name in collectives]
        assert (calls.count("all-reduce.1"), calls.count("all-reduce.4")) \
            == (4, 8)
    # read off chip 0, the worst: 19.9 + 55.9 us in all-reduces, none hidden
    assert s.collective_exposed_s == pytest.approx(75.8 * US, rel=1e-3)
    assert s.collective_s == s.collective_exposed_s
    assert s.category_s["collective"] == pytest.approx(72.2 * US, rel=1e-3)
    assert s.busy_s == pytest.approx(395.8 * US, rel=1e-3)
    found = _flash.passes(_run(None, s, dict(RECORDED_ATTENTION, batch=2)))
    assert (found["fwd"][1], found["bwd"][1]) == (8, pytest.approx(8))


def test_a_trace_without_a_device_plane_is_refused():
    trace = tr.Trace({}, {}, [])
    with pytest.raises(ValueError, match="no operation ran on a device"):
        tr.reduce(trace, {}, steps=1)
