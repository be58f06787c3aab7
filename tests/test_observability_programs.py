"""The recorder of program-building (`paddle_tpu/observability/programs.py`):
a span for every program the process traces, lowers, compiles or loads,
from JAX's own monitoring events, and one around every `pl.pallas_call` the
package builds."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import unregister_program_recorder

from paddle_tpu import observability as obs
from paddle_tpu.observability import metrics, programs, spans

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


@pytest.fixture
def compile_cache(tmp_path):
    """JAX's persistent cache in a directory of the test's own."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in names}
    jax.config.update(names[0], str(tmp_path))
    jax.config.update(names[1], 0.0)
    jax.config.update(names[2], 0)
    compilation_cache.reset_cache()
    yield tmp_path
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def _count(name):
    return metrics.snapshot()["counters"].get(name, 0)


def _program(summary, fun_name):
    return [p for p in summary["programs"] if p["fun_name"] == fun_name]


def test_aot_is_one_program_a_miss_then_a_hit(recorder, compile_cache):
    @jax.jit
    def prog_inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def prog_step(x):
        return prog_inner(x) + prog_inner(x + 1).sum()

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    hits, misses = _count("programs.cache_hits"), _count(
        "programs.cache_misses")
    prog_step.trace(x).lower().compile()
    summary = obs.stats()["programs"]
    (first,) = _program(summary, "prog_step")
    assert first["program"] == "prog_step#1" and first["cache"] == "miss"
    assert first["lower_s"] > 0 and first["compile_s"] > 0
    assert first["cache_load_s"] == 0
    # the inner function's traces are inside the step's, as its children
    assert first["trace_children_s"] > 0 and first["trace_self_s"] > 0
    rows = programs.rows()
    (trace,) = [r for r in rows if r["kind"] == "trace"
                and r["fun_name"] == "prog_step"]
    inner = [r for r in rows if r["fun_name"] == "prog_inner"]
    assert trace["parent"] is None
    assert trace["folded"] + len(inner) >= 2
    assert all(r["parent"] == trace["id"] and r["within"] == "prog_step#1"
               for r in inner)
    # trace, lowering and compile share the program id
    assert {r["kind"] for r in rows if r["program"] == "prog_step#1"} == {
        "trace", "lower", "compile"}
    assert summary["totals"]["programs"] == 1
    assert _count("programs.cache_misses") == misses + 1

    jax.clear_caches()
    prog_step.trace(x).lower().compile()
    summary = obs.stats()["programs"]
    second = [p for p in _program(summary, "prog_step")
              if p["program"] == "prog_step#2"][0]
    assert second["cache"] == "hit" and second["cache_load_s"] > 0
    assert second["compile_s"] == 0 and second["retrieval_s"] > 0
    assert second["saved_s"] is not None
    assert _count("programs.cache_hits") == hits + 1
    assert summary["totals"]["cache_loads"] == 1
    assert summary["totals"]["compiled"] == 1


def test_self_time_is_duration_less_children():
    """A hand-made nest of three: A [0, 10 ms] holds B [1, 6], which holds
    C [2, 3]; spans come in the order they end."""
    rec = programs.Recorder()
    ms = 1_000_000
    rec.ended("trace", 2 * ms, 3 * ms, "c")
    rec.ended("trace", 1 * ms, 6 * ms, "b")
    rec.ended("trace", 0, 10 * ms, "a")
    rec.ended("lower", 11 * ms, 12 * ms, "jit(a)")     # after, not inside
    rows = {r["fun_name"]: r for r in rec.rows()}
    assert rows["a"]["parent"] is None
    assert rows["b"]["parent"] == rows["a"]["id"]
    assert rows["c"]["parent"] == rows["b"]["id"]
    assert rows["jit(a)"]["parent"] is None
    assert rows["c"]["self_s"] == pytest.approx(1e-3)
    assert rows["b"]["self_s"] == pytest.approx(4e-3)
    assert rows["a"]["self_s"] == pytest.approx(5e-3)
    assert rows["jit(a)"]["program"] == rows["a"]["program"] == "a#1"
    totals = rec.summary()["totals"]
    assert totals["trace_s"] == pytest.approx(10e-3)    # self times add up
    assert totals["lower_s"] == pytest.approx(1e-3)


def test_a_jitted_partial_is_one_program(recorder):
    """JAX traces a jitted `functools.partial` under its function's name
    and lowers and compiles it as `<unknown>`; the three are one program
    all the same (the runners jit `step_facts` and the moves so)."""
    import functools

    def facts_of(x, config):
        return jnp.tanh(x).sum() * config

    jax.jit(functools.partial(facts_of, config=2))(jnp.ones((4, 4)))
    kinds = {r["kind"]: r["fun_name"] for r in programs.rows()
             if r["program"] == "facts_of#1"}
    assert set(kinds) == {"trace", "lower", "compile"}
    assert kinds["trace"] == "facts_of" and "unknown" in kinds["lower"]
    (row,) = [p for p in programs.summary()["programs"]
              if p["fun_name"] == "facts_of"]
    assert row["lower_s"] > 0 and row["compile_s"] > 0


def test_small_traces_fold_and_self_times_still_add_up():
    """Thousands of `jax.numpy` helpers fire a trace event each inside a
    model's trace: a count and two sums by name under their parent."""
    rec = programs.Recorder()
    t = 1000.0
    for i in range(40):         # 40 helpers of 10 us inside one 1 ms trace
        rec.on_time_span(TRACE_EVENT, t + 20e-6 * i, t + 20e-6 * i + 10e-6,
                         fun_name=f"helper{i % 4}")
    rec.on_time_span(TRACE_EVENT, t - 100e-6, t + 900e-6, fun_name="model")
    (row,) = rec.rows()
    assert row["fun_name"] == "model" and row["folded"] == 40
    assert row["self_s"] == pytest.approx(1e-3 - 40 * 10e-6, rel=1e-3)
    assert row["folded_self_s"] == pytest.approx(400e-6, rel=1e-3)
    totals = rec.summary()["totals"]
    assert totals["traced"] == 41
    assert totals["trace_s"] == pytest.approx(1e-3, rel=1e-3)


def test_a_function_traced_twice_for_one_shape_counts_two(recorder):
    @jax.jit
    def traced_twice(x):
        return x + 1

    x = jax.ShapeDtypeStruct((4,), jnp.float32)
    before = _count("programs.traces.traced_twice")
    traced_twice.trace(x)
    traced_twice.trace(x)
    obs.stats()
    assert _count("programs.traces.traced_twice") == before + 2


def test_spans_are_on_the_epoch_clock(recorder):
    t0 = time.time_ns()
    jax.jit(lambda x: x * 3).trace(jax.ShapeDtypeStruct((4,), jnp.float32))
    t1 = time.time_ns()
    rows = programs.rows()
    assert rows
    for row in rows:
        assert t0 - 10**9 <= row["start_ns"] <= row["end_ns"] <= t1 + 10**9
    # a live span stamps it too, and one recorded after its end keeps it
    live = spans.span("x").begin()
    live.end()
    assert abs(live.start_ns - time.time_ns()) < 10**9
    child = spans.record("y", live.start_ns, 5.0, parent=live, k=1)
    assert child.parent is live and live.children_us == 5.0
    assert child.end_ns == live.start_ns + 5000


def test_registering_twice_records_once(recorder):
    programs.register()
    programs.register()

    @jax.jit
    def once(x):
        return x - 1

    before = _count("programs.traces.once")
    once.trace(jax.ShapeDtypeStruct((4,), jnp.float32))
    obs.stats()
    assert _count("programs.traces.once") == before + 1
    assert len([r for r in programs.rows() if r["fun_name"] == "once"]) == 1


def test_warm_calls_fire_nothing(recorder):
    """No event fires while cached executables run: 200 calls of a warm
    function add no span and leave the registry where it was."""
    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones((16,), jnp.float32)
    jax.block_until_ready(f(x))
    obs.stats()                         # what was written down, taken in
    rows, callbacks = len(programs.rows()), recorder.callbacks
    before = metrics.MUTATIONS
    for _ in range(200):
        y = f(x)
    jax.block_until_ready(y)
    assert recorder.callbacks == callbacks
    assert not recorder._raw
    assert metrics.MUTATIONS == before
    assert len(programs.rows()) == rows


def _small_step():
    from paddle_tpu.ops.pallas.grouped_matmul import grouped_dot

    def loss(lhs, rhs, sizes):
        with jax.named_scope("moe_experts"):
            return grouped_dot(lhs, rhs, sizes).sum()

    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    args = (jax.ShapeDtypeStruct((256, 128), jnp.float32),
            jax.ShapeDtypeStruct((2, 128, 128), jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.int32))
    return step, args


def test_mosaic_site_nests_under_its_callers_trace(recorder):
    step, args = _small_step()
    jax.clear_caches()
    step.trace(*args)
    rows = programs.rows()
    by_id = {r["id"]: r for r in rows}
    sites = [r for r in rows if r["kind"] == "mosaic_site"]
    assert {r["fun_name"] for r in sites} == {"_gmm_kernel", "_tgmm_kernel"}
    for site in sites:
        holder = by_id[site["parent"]]
        assert holder["kind"] == "trace"
        assert holder["fun_name"] in ("_gmm", "_tgmm")
        assert holder["start_ns"] <= site["start_ns"]
        assert site["end_ns"] <= holder["end_ns"]
        assert site["within"] == by_id[holder["parent"]]["program"]
        assert site["shapes"][0] == "float32[256, 128]"
    summary = programs.summary()
    assert summary["totals"]["mosaic_sites"] == len(sites)
    assert set(summary["retraced"]) == {"_gmm", "_tgmm"}
    for r in summary["retraced"].values():
        assert r["traces"] >= r["built"] >= r["distinct"] >= 1
    (outer,) = [p for p in summary["programs"] if p["mosaic_sites"]]
    assert outer["mosaic_sites"] == len(sites)


def test_the_lowered_step_is_the_same_with_the_recorder_and_without():
    """Nothing of a span reaches the jaxpr: the lowered text of a small
    step that holds Mosaic sites is byte-identical (source locations off,
    as PERF.md section 6 compares whole cells)."""
    before = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        texts = []
        for register in (False, True):
            unregister_program_recorder()
            if register:
                programs.register()
            jax.clear_caches()
            step, args = _small_step()
            texts.append(step.trace(*args).lower().as_text(debug_info=True))
        assert programs.summary()["totals"]["mosaic_sites"] >= 2
    finally:
        jax.config.update("jax_traceback_in_locations_limit", before)
        unregister_program_recorder()
    assert "moe_experts" in texts[0] and texts[0] == texts[1]


def test_stats_has_programs_only_once_registered():
    unregister_program_recorder()
    assert "programs" not in obs.stats()
    programs.register()
    try:
        found = obs.stats()["programs"]
        assert found["totals"]["traced"] == 0 and found["programs"] == []
        assert "callback_s" in found
        assert programs.render(found).startswith("== programs built")
    finally:
        unregister_program_recorder()


def test_the_oldest_outermost_programs_give_way_and_the_sums_stay():
    rec = programs.Recorder()
    n = programs.MAX_PENDING + 10
    for i in range(n):
        rec.ended("trace", 2_000_000 * i, 2_000_000 * i + 1_500_000, "f")
    totals = rec.summary()["totals"]
    assert len(rec.rows()) <= programs.MAX_PENDING
    assert totals["traced"] == n
    assert totals["trace_s"] == pytest.approx(n * 1.5e-3)


def test_numbers_are_plain(recorder):
    """`stats()` goes to JSON as it is (the CLI's --json, the exporter)."""
    import json
    jax.jit(lambda x: x + 2).trace(jax.ShapeDtypeStruct((4,), np.float32))
    json.dumps(obs.stats()["programs"])
    json.dumps(programs.rows())
