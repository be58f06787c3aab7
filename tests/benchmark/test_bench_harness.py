"""The harness end to end: BENCHMARK.json against the files it names, a
--cpu-dry-run of every cell, a cell made of new files only, and the bare
command without a TPU. Each run is a process of its own, as on the chip."""
import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def _run(argv, cwd=ROOT, **env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run([sys.executable] + argv, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=170)


def _last_json(done):
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_benchmark_json_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(len(e["why"]) <= 200
               for k in ("configs", "workloads") for e in BENCH[k])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    assert {w["chips"] for w in BENCH["workloads"]} <= {1, 4}
    e2e = {e["name"]: e for e in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1
    assert all(0.01 <= e["bound"] <= 0.1 and
               e["source"] in ("host_clock", "device_trace")
               for e in e2e.values())
    for path in BENCH["paths"]:
        for base, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in base:
                continue
            assert all(re.fullmatch(r"[A-Za-z0-9_.\-]+", f) for f in files)


def test_benchmark_json_agrees_with_the_files_it_names():
    from benchmarks.cells import load_cell
    e2e = {e["name"] for e in BENCH["end_to_end"]}
    per_layer = {e["name"]: e for e in BENCH["per_layer"]}
    used_configs = set()
    for w in BENCH["workloads"]:
        cell = load_cell(w["name"])
        used_configs.add(w["config"])
        assert (cell.chips, cell.traffic["name"], cell.config["name"]) \
            == (w["chips"], w["traffic"], w["config"])
        for name, reader in cell.layer_metrics.items():
            entry = per_layer[name]
            assert (entry["unit"], entry["better"], entry["source"],
                    entry["layer"], entry["moves"]) == (
                reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER,
                reader.MOVES), name
            assert reader.MOVES in e2e
            if name.endswith("_roofline"):
                assert reader.UNIT == "%"
    # which cell reads which metric is said once, in the cells' files:
    # an entry's `workloads` is what follows from them
    for entry in per_layer.values():
        readers = [w["name"] for w in BENCH["workloads"]
                   if entry["name"] in load_cell(w["name"]).layer_metrics]
        assert readers and entry.get("workloads", CELLS) == readers, entry
    for c in BENCH["configs"]:
        assert c["name"] in used_configs
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert (config["source"], config["reduced"]) \
            == (c["source"], c["reduced"])
        assert not [k for k in c["reduced"]
                    if k.endswith(("_dim", "_rank", "_size"))
                    and k != "vocab_size"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_cpu_dry_run(cell_name):
    out = _last_json(_run(["benchmarks/run.py", "--workload", cell_name,
                           "--seed", "3", "--seconds", "0.5",
                           "--cpu-dry-run"]))
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device", "dry_run", "readers"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert out["metrics"] == {}             # no device metric from a CPU
    chips = next(w["chips"] for w in BENCH["workloads"]
                 if w["name"] == cell_name)
    assert out["device"]["count"] >= chips


def test_bare_command_without_a_tpu_exits_nonzero_and_prints_no_result():
    done = _run(BENCH["command"][1:] + ["--workload", CELLS[0], "--seed",
                                        "0", "--seconds", "1", "--trace",
                                        "0"])
    assert done.returncode == 2
    assert done.stdout.strip() == ""
    assert "needs 1 TPU chip" in done.stderr


def test_no_topology_call_and_no_backend_at_import():
    """on-chip-measurement section 2: importing the benchmark's modules
    may not describe a TPU topology nor start a backend."""
    done = _run(["-c", (
        "import sys, benchmarks.run, benchmarks.aot_check, benchmarks.cells, "
        "benchmarks.spread\n"
        "assert 'jax.experimental.topologies' not in sys.modules\n"
        "import jax\n"
        "assert not jax._src.xla_bridge._backends, 'a backend was started'")])
    assert done.returncode == 0, done.stderr[-2000:]


DUMMY_RUNNER = '''"""A family of its own: weighted least squares on rows of floats, stepped
eagerly. No token, no compiled executable, three arrays to a batch."""
from benchmarks.runners import Program


def set_up(cell, seed, devices, phases):
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    rows, width = cell.traffic["rows"], cell.config["width"]
    ring = [(rng.normal(size=(rows, width)).astype(np.float32),
             rng.normal(size=rows).astype(np.float32),
             rng.random(rows).astype(np.float32))
            for _ in range(cell.traffic["ring"])]

    def loss_fn(w, x, y, weight):
        return (weight * (y - x @ w) ** 2).mean()

    def step(w, x, y, weight):
        w = w - cell.config["lr"] * jax.grad(loss_fn)(w, x, y, weight)
        return w, loss_fn(w, x, y, weight)

    _, loss = step(jnp.zeros(width), *ring[0])
    want = cell.reference.loss_after_one_update(
        np.zeros(width), *ring[0], cell.config["lr"])
    problems = [] if abs(float(loss) - want) <= 1e-4 * want else [
        f"loss after one update {float(loss)}, the reference has {want}"]
    phases.end("dummy")
    return Program(step=step, state=jnp.zeros(width), ring=ring,
                   put=lambda batch: [jnp.asarray(a) for a in batch],
                   unit="rows", units_per_step=rows,
                   flops_per_unit=6.0 * width, problems=problems)
'''
DUMMY_REFERENCE = '''"""Weighted least squares in numpy, the gradient written out."""


def loss_after_one_update(w, x, y, weight, lr):
    gradient = -2 * x.T @ (weight * (y - x @ w)) / len(y)
    w = w - lr * gradient
    return float((weight * (y - x @ w) ** 2).mean())
'''
DUMMY_METRIC = '''LAYER = "compiled_trainer"
SOURCE = "host_clock"
UNIT = "rows"
BETTER = "higher"
MOVES = "mfu"


def read(run):
    return run.program.units_per_step
'''


def test_a_new_family_is_new_files_only(tmp_path):
    """A later PR adds a family (a runner, a reference, inputs of another
    kind), a configuration, a traffic mix, a cell and a per-layer metric as
    files, and edits none that is there."""
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in copy.rglob("*") if p.is_file()}
    bench = copy / "benchmarks"
    (bench / "runners" / "dummy_rows.py").write_text(DUMMY_RUNNER)
    (bench / "reference" / "dummy_rows.py").write_text(DUMMY_REFERENCE)
    (bench / "layer_metrics" / "dummy_rows_per_step.py").write_text(
        DUMMY_METRIC)
    (bench / "configs" / "dummy-lsq.json").write_text(json.dumps({
        "name": "dummy-lsq", "runner": "dummy_rows",
        "reference": "dummy_rows", "width": 16, "lr": 0.05}))
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps({
        "name": "dummy-mix", "rows": 32, "ring": 3, "sync_every": 1,
        "trace_steps": 2}))
    (bench / "workloads" / "dummy-cell.json").write_text(json.dumps({
        "name": "dummy-cell", "config": "dummy-lsq", "traffic": "dummy-mix",
        "chips": 1, "layout": None, "why": "a test",
        "layer_metrics": ["dummy_rows_per_step", "compiles_in_window",
                          "matmul_share", "step_temp_gb"]}))
    out = _last_json(_run(
        [str(bench / "run.py"), "--workload", "dummy-cell", "--seed", "1",
         "--trace", "1", "--cpu-dry-run"], cwd=str(copy), PYTHONPATH=ROOT))
    assert out["correct"] is True and out["attempted"] == 2
    # found by name and read; no device trace and no executable to read
    assert out["readers"] == ["compiles_in_window", "dummy_rows_per_step"]
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_cell_reports_the_end_to_end_metrics_its_family_has():
    """The arithmetic alone, on made-up figures: a step that consumes
    tokens in one executable has all four; one that does neither has `mfu`
    and `setup_s`."""
    import types

    from benchmarks import peaks, run
    v5e = peaks.peaks_of("TPU v5 lite")
    tokens = types.SimpleNamespace(unit="tokens", flops_per_unit=2e9,
                                   memory={"total": 9.5e9})
    got = run.end_to_end(tokens, 39400.0, v5e, 20.0)
    assert {k: v["value"] for k, v in got.items()} == {
        "tokens_per_s_chip": 39400.0, "mfu": pytest.approx(0.4),
        "hbm_peak_gb": 9.5, "setup_s": 20.0}
    assert set(got) == {e["name"] for e in BENCH["end_to_end"]}
    assert all(got[e["name"]]["unit"] == e["unit"]
               for e in BENCH["end_to_end"])
    images = types.SimpleNamespace(unit="images", flops_per_unit=24e9,
                                   memory=None)
    assert set(run.end_to_end(images, 1000.0, v5e, 30.0)) \
        == {"mfu", "setup_s"}


def test_on_the_chip_a_listed_metric_that_reads_nothing_is_a_problem():
    import types

    from benchmarks import run
    found = types.SimpleNamespace(UNIT="ms", read=lambda run: 0.0)
    lost = types.SimpleNamespace(UNIT="%", read=lambda run: None)
    cell = types.SimpleNamespace(layer_metrics={"a_ms": found,
                                                "b_roofline": lost})
    problems = []
    assert run.per_layer(cell, None, problems) \
        == {"a_ms": {"value": 0.0, "unit": "ms"}}
    assert len(problems) == 1 and "b_roofline" in problems[0]


def test_an_unknown_cell_names_the_ones_there():
    done = _run(["benchmarks/run.py", "--workload", "nope", "--cpu-dry-run"])
    assert done.returncode != 0 and done.stdout.strip() == ""
    assert CELLS[0] in done.stderr


def test_layer_metric_modules_state_what_they_measure():
    here = os.path.join(ROOT, "benchmarks", "layer_metrics")
    listed = {e["name"] for e in BENCH["per_layer"]}
    found = {f[:-3] for f in os.listdir(here)
             if f.endswith(".py") and not f.startswith("_")}
    assert found == listed
    for name in found:
        mod = importlib.import_module(f"benchmarks.layer_metrics.{name}")
        assert mod.SOURCE in ("device_trace", "program_span",
                              "program_counter", "host_clock")
        assert mod.BETTER in ("higher", "lower") and callable(mod.read)
