"""The stage metrics (benchmarks/layer_metrics/_stages.py) and
benchmarks/stage_table.py against a hand-made pair whose figures are worked
out below, and against a tiny gpt step recorded on a v5e
(benchmarks/testdata)."""
import gzip
import os
import types

import pytest
from jax.profiler import ProfileData

from benchmarks import trace_reduce as tr
from benchmarks.layer_metrics import Run, _stages
from paddle_tpu.models import stages

DATA = os.path.join(os.path.dirname(tr.__file__), "testdata")
ROOT = os.path.dirname(os.path.dirname(DATA))
US = 1e-6
READERS = ("attention_ms_per_step", "loss_head_ms_per_step",
           "optimizer_ms_per_step", "forward_ms_per_step",
           "backward_ms_per_step", "dense_ms_per_step", "unscoped_share")


def _read(name, run):
    got = _stages.metrics(run)
    return got and got[name]


def _run(hlo_text, trace, steps):
    summary = trace and tr.reduce(trace, tr.parse_hlo(hlo_text), steps)
    program = types.SimpleNamespace(hlo_text=lambda: hlo_text, facts={},
                                    memory=None)
    return Run(None, program, None, 0, 0, 0, [], summary)


@pytest.fixture(scope="module")
def hand():
    with open(os.path.join(DATA, "hand_stages_step.hlo.txt")) as f:
        hlo_text = f.read()
    with open(os.path.join(DATA, "hand_stages_trace.textproto")) as f:
        trace = tr.from_profile(ProfileData.from_text_proto(f.read()))
    return hlo_text, trace


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp()/while/body/closed_call/attn_core/dot_general",
     ("attn_core", "forward")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "attn_core/transpose", ("attn_core", "backward")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn_core/pallas_call", ("attn_core", "remat")),
    ("jit(step)/jvp(loss_head)/reduce_sum", ("loss_head", "forward")),
    ("jit(step)/transpose(jvp(loss_head))/mul", ("loss_head", "backward")),
    ("jit(step)/optimizer/mul", ("optimizer", "update")),
    # the optimizer's whatever else the path holds
    ("jit(step)/transpose(jvp())/optimizer/all_gather",
     ("optimizer", "update")),
    ("jit(step)/jvp()/while/body/dynamic_slice", (None, "forward")),
    ("jit(step)/transpose(jvp())/while", (None, "backward")),
    ("", (None, "forward")),
    # a stage's name inside another word, or in another wrapper, is no scope
    ("jit(step)/jvp()/mlp_like/attn_core2/mul", (None, "forward")),
    ("jit(mlp)/jvp()/mul", (None, "forward")),
    # several paths: the first that is scoped decides stage and direction
    ("jit(step)/jvp()/while/body/dynamic_slice;jit(step)/transpose(jvp())/"
     "while/body/closed_call/checkpoint/mlp/add_any;jit(step)/jvp()/while/"
     "body/closed_call/attn_out/add", ("mlp", "backward")),
    ("jit(step)/transpose(jvp())/while/body/sub;jit(step)/jvp()/while",
     (None, "backward")),
])
def test_place_by_hand(op_name, want):
    assert _stages.place(op_name, stages) == want


def test_whole_op_names_are_read_from_the_hlo_text(hand):
    hlo_text, _ = hand
    names = _stages.op_names(hlo_text)
    assert names["closed_call.2"] == \
        "jit(step_fn)/jvp()/while/body/closed_call/attn_core/pallas_call"
    assert names["multiply.15"].count(";") == 1
    assert "copy.1" not in names and "out" not in names
    assert len(names) == 21


def test_seconds_by_stage_and_direction_by_hand(hand):
    """One chip, every instruction once, back to back from 100 to 680 us.
    while.1 (100) holds qkv 20, kernel 30, out 10, mlp 30, a slice 5 and 5
    of its own; while.2 (300) holds those four again (remat), their
    backwards 40, 50 (an all-to-all), 20, 60, a two-path fusion 15 whose
    second path is mlp's backward, a copy without op_name 5 and 20 of its
    own. Outside: embed 10 and 15, loss head 40 and 60, optimizer 50, and
    5 of an event the HLO text does not hold."""
    run = _run(*hand, steps=2)
    got = {k: round(v / US) for k, v in _stages.seconds(run).items()}
    assert got == {
        ("embed", "forward"): 10, ("embed", "backward"): 15,
        ("attn_qkv", "forward"): 20, ("attn_qkv", "remat"): 20,
        ("attn_qkv", "backward"): 40,
        ("attn_core", "forward"): 30, ("attn_core", "remat"): 30,
        ("attn_core", "backward"): 50,
        ("attn_out", "forward"): 10, ("attn_out", "remat"): 10,
        ("attn_out", "backward"): 20,
        ("mlp", "forward"): 30, ("mlp", "remat"): 30,
        ("mlp", "backward"): 75,
        ("loss_head", "forward"): 40, ("loss_head", "backward"): 60,
        ("optimizer", "update"): 50,
        # slice 5, while.1's own 5, the copy 5, the unknown event 5
        (None, "forward"): 20,
        (None, "backward"): 20}         # while.2's own
    assert sum(got.values()) == round(run.trace.busy_s / US) == 580


def test_readers_on_the_hand_trace(hand):
    run = _run(*hand, steps=2)
    got = {name: _read(name, run) for name in READERS}
    per_step = 1e3 * US / 2             # us in the window -> ms per step
    assert got == {
        "attention_ms_per_step": pytest.approx(110 * per_step),
        "loss_head_ms_per_step": pytest.approx(100 * per_step),
        "optimizer_ms_per_step": pytest.approx(50 * per_step),
        "forward_ms_per_step": pytest.approx(160 * per_step),
        "backward_ms_per_step": pytest.approx(370 * per_step),
        "dense_ms_per_step": pytest.approx((80 + 40 + 135) * per_step),
        "unscoped_share": pytest.approx(40 / 580)}
    # the three directions partition the step
    assert got["forward_ms_per_step"] + got["backward_ms_per_step"] \
        + got["optimizer_ms_per_step"] \
        == pytest.approx(1e3 * run.trace.busy_s / 2)


def test_the_hlo_text_is_parsed_once_per_run(hand):
    hlo_text, trace = hand
    run = _run(hlo_text, trace, steps=2)
    calls = []
    run.program.hlo_text = lambda: calls.append(1) or hlo_text
    for name in READERS:
        assert _read(name, run) is not None
    assert len(calls) == 1


def test_a_lost_scope_reads_none_not_zero(hand):
    """The trace is there and no instruction carries `attn_core`: the
    program lost the span, and on the chip None makes the run incorrect."""
    hlo_text, trace = hand
    run = _run(hlo_text.replace("/attn_core/", "/attention/"), trace, 2)
    assert _read("attention_ms_per_step", run) is None
    assert _read("dense_ms_per_step", run) is not None
    assert _read("unscoped_share", run) == pytest.approx((40 + 110) / 580)
    # one of a reader's several scopes gone: it reads what is left
    run = _run(hlo_text.replace("/attn_out/", "/o/"), trace, 2)
    assert _read("dense_ms_per_step", run) \
        == pytest.approx((80 + 135) * 1e3 * US / 2)


def test_nothing_to_read_is_none(hand, monkeypatch):
    hlo_text, trace = hand
    no_trace = _run(hlo_text, None, 2)
    assert [_read(name, no_trace) for name in READERS] == [None] * 7
    no_text = _run(hlo_text, trace, 2)
    no_text.program.hlo_text = None     # a family with no one executable
    assert [_read(name, no_text) for name in READERS] == [None] * 7
    # a program from before the scopes has no module of names
    monkeypatch.setattr(_stages, "vocabulary", lambda: None)
    assert [_read(name, _run(hlo_text, trace, 2)) for name in READERS] \
        == [None] * 7


def test_the_existing_readers_read_the_hand_pair_as_before(hand):
    """They go by categories, shapes and `rematted_computation`, which a
    scope does not change: dots 20+10+30 forward, 60 head, 40+20+60
    backward are the required matmuls; remat is 20+30+10+30."""
    from benchmarks.layer_metrics import matmul_share, remat_share
    run = _run(*hand, steps=2)
    assert matmul_share.read(run) == pytest.approx(240 / 580)
    assert remat_share.read(run) == pytest.approx(90 / 580)


def test_the_breakdown_labels_carry_the_stage(hand):
    run = _run(*hand, steps=2)
    labels = dict(run.trace.device_ops)
    assert "dot.14 (matmul: mlp/bsf,fh->bsh/dot_general)" in labels
    assert "dot.6 (matmul: transpose(jvp(loss_head))/bsh,vh->bsv/" \
        "dot_general)" in labels


def test_stage_table_on_the_hand_pair(hand):
    """benchmarks/stage_table.py's table: with a thousandth of a step in
    the window, its ms per step are the window's microseconds."""
    import dataclasses

    from benchmarks import stage_table
    run = _run(*hand, steps=2)
    summary = dataclasses.replace(run.trace, steps=1e-3)
    where = _stages.placed(run)
    lines = stage_table.table(summary, where)
    cells = [[c.strip() for c in line.strip("|").split("|")]
             for line in lines]
    assert cells[0] == ["stage", "forward", "remat", "backward", "update",
                        "all", "matmul", "mosaic", "collective", "other"]
    rows = {row[0]: row[1:] for row in cells[2:]}
    assert list(rows) == list(stages.ALL) + ["(no stage)", "all"]
    assert rows["attn_core"] == ["30.0", "30.0", "50.0", "—", "110.0",
                                 "—", "60.0", "50.0", "—"]
    assert rows["mlp"] == ["30.0", "30.0", "75.0", "—", "135.0",
                           "120.0", "—", "—", "15.0"]
    assert rows["optimizer"] == ["—", "—", "—", "50.0", "50.0",
                                 "—", "—", "—", "50.0"]
    assert rows["(no stage)"] == ["20.0", "—", "20.0", "—", "40.0",
                                  "—", "—", "—", "40.0"]
    assert rows["all"] == ["160.0", "90.0", "280.0", "50.0", "580.0",
                           "300.0", "60.0", "50.0", "170.0"]
    listed = stage_table.listing(summary, where, None)
    assert listed[0].split()[:4] == ["20.000", "ms", "backward", "while.2"]
    assert {line.split()[3] for line in listed[1:]} == {
        "dynamic-slice.1", "while.1", "copy.1", "unknown.9"}


@pytest.fixture(scope="module")
def recorded():
    """Two steps of benchmarks/testdata/record_stages_trace.py on a v5e (my
    chip run, PR 24): the real gpt train step at two layers, hidden 256."""
    name = os.path.join(DATA, "recorded_stages_1chip")
    with gzip.open(name + ".hlo.txt.gz", "rt") as f:
        hlo_text = f.read()
    with gzip.open(name + ".textproto.gz", "rt") as f:
        trace = tr.from_profile(ProfileData.from_text_proto(f.read()))
    return _run(hlo_text, trace, steps=2)


def test_the_recorded_real_step_by_stage_and_direction(recorded):
    """Microseconds in the two-step window, read off the trace once: the
    attention layer is 747.8 of 1524.9 busy at this size, 659.4 of it the
    four kernels; under no scope stand the two loops' own time, the copies
    of the state's layout and the scan's slices (12 % here, where a layer
    is 100 us; 3 % at the cells' sizes)."""
    got = {k: round(v / US, 1) for k, v in _stages.seconds(recorded).items()}
    assert got == {
        ("embed", "forward"): 15.8, ("embed", "backward"): 43.6,
        ("attn_qkv", "forward"): 31.6, ("attn_qkv", "remat"): 31.6,
        ("attn_qkv", "backward"): 59.6,
        ("attn_core", "forward"): 201.3, ("attn_core", "remat"): 196.9,
        ("attn_core", "backward"): 349.7,
        ("attn_out", "forward"): 6.6, ("attn_out", "remat"): 6.8,
        ("attn_out", "backward"): 21.4,
        ("mlp", "forward"): 52.9, ("mlp", "remat"): 26.7,
        ("mlp", "backward"): 127.8,
        ("loss_head", "forward"): 71.8, ("loss_head", "backward"): 45.5,
        ("optimizer", "update"): 51.4,
        (None, "forward"): 124.2, (None, "backward"): 59.9}
    assert sum(_stages.seconds(recorded).values()) \
        == pytest.approx(recorded.trace.busy_s) == pytest.approx(1524.875 * US)
    # the four Mosaic calls (forward, what remat repeats, dQ and dKV), two
    # layers times two steps each, stand under the attention's scope
    where = _stages.placed(recorded)
    kernels = {where[name]: (round(recorded.trace.op_s[name] / US, 1),
                             recorded.trace.op_calls[name])
               for name, op in recorded.trace.ops.items()
               if op.category == "mosaic" and name in recorded.trace.op_s}
    assert len(kernels) == 3 and kernels[stages.ATTN_CORE, "forward"] \
        == (175.0, 4) and kernels[stages.ATTN_CORE, "remat"] == (170.5, 4)
    assert recorded.trace.seconds(lambda op: op.category == "mosaic") \
        == pytest.approx(659.365 * US)


def test_metrics_of_the_recorded_real_step(recorded):
    got = _stages.metrics(recorded)
    assert got == {
        "attention_ms_per_step": pytest.approx(0.373916),
        "loss_head_ms_per_step": pytest.approx(0.0586205),
        "optimizer_ms_per_step": pytest.approx(0.025705),
        "forward_ms_per_step": pytest.approx(0.2520575),
        "backward_ms_per_step": pytest.approx(0.484675),
        "dense_ms_per_step": pytest.approx(0.182463),
        "unscoped_share": pytest.approx(0.1207266)}
    assert got["forward_ms_per_step"] + got["backward_ms_per_step"] \
        + got["optimizer_ms_per_step"] \
        == pytest.approx(1e3 * recorded.trace.busy_s / 2)


def test_the_scope_strings_are_written_in_one_place():
    """In paddle_tpu/models/stages.py, and nowhere else in the package or
    the benchmark (test data and documents apart): no quoted stage name
    (those of one plain word, which are also dictionary keys, left out),
    and no scope opened on a literal."""
    import re
    quoted = re.compile("[\"'](?:%s)[\"']" % "|".join(
        s for s in stages.ALL if "_" in s))
    literal_scope = re.compile(r"named_scope\((?!stages\.[A-Z_]+\))")
    found = set()
    for top, pattern in (("paddle_tpu", quoted), ("benchmarks", quoted),
                         ("paddle_tpu/models", literal_scope),
                         ("benchmarks", literal_scope)):
        for base, dirs, files in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [d for d in dirs if d not in ("__pycache__",
                                                    "testdata")]
            for name in files:
                if not name.endswith(".py"):
                    continue
                with open(os.path.join(base, name)) as f:
                    if pattern.search(f.read()):
                        found.add(os.path.relpath(
                            os.path.join(base, name), ROOT))
    assert found == {"paddle_tpu/models/stages.py"}
