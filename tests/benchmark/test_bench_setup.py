"""Set-up by layer: `benchmarks/layer_metrics/_setup.py` over the spans the
program records of itself, and `benchmarks/setup_table.py`, which lays them
beside the harness's phases. The table's run is a process of its own, as on
the chip; the readers' are this one, with a recorder of the test's own."""
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest
from conftest import unregister_program_recorder

from benchmarks.layer_metrics import Run, _setup

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PHASES = ("python_and_jax_import", "backend_start", "package_import",
          "trace_and_lower", "compile_or_load_step", "reference",
          "init_state", "two_check_steps")
KINDS = ("trace", "lower", "compile", "cache_load", "mosaic_site")


def _run_of(step):
    program = types.SimpleNamespace(hlo_text=step.as_text, facts={},
                                    memory=None)
    return Run(None, program, None, 0, 0, 0, [])


def test_the_table_states_what_each_reader_measures():
    assert _setup.LAYER == "compile_cache" and _setup.MOVES == "setup_s"
    assert _setup.BETTER == "lower"
    for name, (source, unit, what) in _setup.TABLE.items():
        assert name.startswith(("setup_", "step_"))
        assert source in ("program_span", "program_counter")
        assert unit == ("s" if source == "program_span" else "count")
        assert name.endswith("_s") == (unit == "s") and what


def test_metrics_on_a_recorded_process(recorder):
    def step_fn(x, y):
        return jnp.tanh(x @ y).sum()

    x = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    step = jax.jit(step_fn).trace(x, x).lower().compile()
    jax.jit(lambda a: a + 1).trace(x).lower().compile()     # another program
    got = _setup.metrics(_run_of(step))
    assert set(got) == set(_setup.TABLE)
    assert all(value is not None for value in got.values())
    assert got["setup_programs"] == 2 and got["setup_mosaic_sites"] == 0
    assert got["setup_trace_s"] > 0 and got["setup_lower_s"] > 0
    # no persistent cache here: every program was compiled
    assert got["setup_compile_s"] > 0 and got["setup_cache_load_s"] == 0
    assert 0 < got["step_trace_s"] <= got["setup_trace_s"]
    assert 0 < got["step_lower_s"] < got["setup_lower_s"]
    assert 0 < got["step_compile_or_load_s"] < got["setup_compile_s"]
    assert _setup.step_name(_run_of(step)) == "step_fn"


def test_metrics_are_none_where_nothing_was_recorded(recorder):
    run = types.SimpleNamespace(program=types.SimpleNamespace(hlo_text=None))
    assert _setup.metrics(run) == dict.fromkeys(_setup.TABLE)
    unregister_program_recorder()       # and where none is registered at all
    assert _setup.programs() is None
    assert _setup.metrics(run) == dict.fromkeys(_setup.TABLE)


def test_setup_table_dry_run_rows_add_up():
    done = subprocess.run(
        [sys.executable, "benchmarks/setup_table.py", "gpt2m-pretrain-s1024",
         "--cpu-dry-run", "--json"], cwd=ROOT, capture_output=True, text=True,
        timeout=170, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-3000:]
    lines = done.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["dry_run"] and out["platform"] == "cpu"
    assert out["problems"] == []
    assert [row["phase"] for row in out["phases"]] == list(PHASES)
    for phase in PHASES:        # a printed row for every phase
        assert any(line.startswith(f"| {phase} |") for line in lines)
    for row in out["phases"]:
        named = sum(row[kind] for kind in KINDS)
        assert 0 <= named <= row["s"] + 1e-9
        assert named + row["rest"] == pytest.approx(row["s"], abs=1e-9)
    by_phase = {row["phase"]: row for row in out["phases"]}
    # nothing is built before the package is there; the step is traced and
    # lowered in its phase and compiled in the next
    for phase in PHASES[:3]:
        assert sum(by_phase[phase][kind] for kind in KINDS) == 0
    assert by_phase["trace_and_lower"]["trace"] > 0
    assert by_phase["trace_and_lower"]["lower"] > 0
    assert by_phase["trace_and_lower"]["compile"] == 0
    assert by_phase["compile_or_load_step"]["compile"] > 0
    assert by_phase["compile_or_load_step"]["programs"] == 1
    assert sum(row["s"] for row in out["phases"]) == pytest.approx(
        out["setup_s"], rel=0.05)
    readers = out["readers"]
    assert set(readers) == set(_setup.TABLE)
    assert readers["step_lower_s"] == pytest.approx(
        by_phase["trace_and_lower"]["lower"], rel=0.2)
    assert readers["setup_programs"] >= 3       # step, reference, state
    assert out["programs"][0]["fun_name"] and out["callbacks"] > 100
    assert out["callback_s"] < 1.0
