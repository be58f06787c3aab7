"""The device boundary: nothing between the program and the chip may answer
for a device that is not there. `_core/device.py` (places, the TPU
predicate, the peak table, the compile-cache helper), the two parents that
would start chip-needing children, and `chip_smoke.py`'s exit contract."""
import importlib
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from paddle_tpu._core import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ places

def test_jax_device_does_not_clamp_or_substitute():
    assert device.jax_device(device.CPUPlace()).platform == "cpu"
    assert device.jax_device(None) == device.jax.devices()[0]
    with pytest.raises(ValueError, match="sees 8 cpu device"):
        device.jax_device(device.CustomPlace("cpu", 8))
    # there is no TPU backend in the suite: asking for a chip is an error,
    # not chip 0 and not a CPU
    with pytest.raises(RuntimeError):
        device.jax_device(device.TPUPlace(3))


def test_predicates_off_tpu():
    assert not device.is_tpu()
    assert device.pallas_interpret()
    assert device.is_compiled_with_tpu() == (device.tpu_chips_on_host() > 0)


# ------------------------------------------------------------------- peaks

def test_chip_peaks_keyed_by_reported_device_kind():
    v5e = device.chip_peaks("TPU v5 lite")
    assert (v5e.flops, v5e.membw) == (197e12, 819e9)
    # "TPU v5" is a v5p: the substring table answered 197e12 for it
    assert device.chip_peaks("TPU v5").flops == 459e12
    for unknown in ("cpu", "TPU v5e", "TPU v9", ""):
        with pytest.raises(LookupError, match="no published peaks"):
            device.chip_peaks(unknown)
    with pytest.raises(LookupError):
        device.chip_peaks()          # this process's device 0 is a CPU


def test_observability_prices_unknown_tpu_as_error(monkeypatch):
    from paddle_tpu.observability import compute
    assert compute.peak_flops() > 0          # documented CPU envelope
    monkeypatch.setattr(device, "is_tpu", lambda: True)
    with pytest.raises(LookupError):         # device_kind "cpu": no row
        compute.peak_flops()
    with pytest.raises(LookupError):
        compute.peak_membw()


# ----------------------------------------------------------- compile cache

_CACHE_PROBE = """
import jax
from paddle_tpu._core.device import COMPILE_CACHE_DIR, enable_compile_cache
before = jax.config.jax_compilation_cache_dir
used = enable_compile_cache()
print(repr((before, jax.config.jax_compilation_cache_dir, used,
            COMPILE_CACHE_DIR,
            jax.config.jax_persistent_cache_min_compile_time_secs)))
"""


def _probe_cache(env_dir):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout
    return eval(out.strip().splitlines()[-1])


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path):
    placed = str(tmp_path / "elsewhere")
    before, after, used, _, min_secs = _probe_cache(placed)
    assert before == after == used == placed
    assert min_secs == 0.0


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout():
    before, after, used, fixed, min_secs = _probe_cache(None)
    assert before is None
    assert after == used == fixed == os.path.join(REPO, ".jax_compile_cache")
    assert min_secs == 0.0
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_compile_cache/" in ignored


_SCOPE_PROBE = """
import sys
import jax
import jax.numpy as jnp
from paddle_tpu._core.device import enable_compile_cache
enable_compile_cache()
def step_fn(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.tanh(x @ x).sum()
text = jax.jit(step_fn).lower(jnp.ones((64, 64))).compile().as_text()
print(sys.argv[1] in text)
"""


def test_a_cached_executable_keeps_no_other_programs_scopes(tmp_path):
    """Two programs that differ by a scope's name alone share no cache
    entry: the second's compiled text names its own scope, not the one the
    cache was filled under (by JAX's default key it would)."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    for scope in ("attention_a", "attention_b", "attention_b"):
        out = subprocess.run([sys.executable, "-c", _SCOPE_PROBE, scope],
                             env=env, capture_output=True, text=True,
                             timeout=120, check=True).stdout
        assert out.strip().splitlines()[-1] == "True", scope
    assert os.listdir(tmp_path)     # the cache was in use


# ------------------------------------------- parents that would need a chip

def test_launcher_refuses_sibling_workers_on_a_tpu_host(monkeypatch):
    launch = importlib.import_module("paddle_tpu.distributed.launch.main")
    monkeypatch.setattr(device, "tpu_chips_on_host", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit, match="--nproc_per_node 1"):
        launch.main(["--nproc_per_node", "2", "never_started.py"])
    # grow-mode hot spares are sibling processes too
    with pytest.raises(SystemExit, match="4 TPU chip"):
        launch.main(["--elastic_mode", "grow", "--max_np", "2",
                     "never_started.py"])
    # CPU-simulated pods (the suite's own launcher tests) stay allowed
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    launch._refuse_chip_contention(2)
    monkeypatch.delenv("JAX_PLATFORMS")
    launch._refuse_chip_contention(1)


def test_trials_run_in_process_on_a_tpu(monkeypatch):
    """The tuner's process holds the chips, so a child could not open them:
    on a TPU no trial process is started."""
    from paddle_tpu.distributed.auto_tuner import trial_runner
    monkeypatch.setattr(device, "is_tpu", lambda: True)
    monkeypatch.setattr(trial_runner.subprocess, "run", lambda *a, **k: 1 / 0)
    monkeypatch.setattr(trial_runner, "_measure_in_process",
                        lambda config, steps, warmup: 0.125)
    assert trial_runner.measure_step_time({"dp_degree": 1}) == 0.125


def test_failed_trial_launch_is_an_error_not_a_cost(monkeypatch):
    from paddle_tpu.distributed.auto_tuner import trial_runner
    # the child dies before it reports (here: a config it cannot parse)
    with pytest.raises(trial_runner.TrialLaunchError, match="exited 1"):
        trial_runner.measure_step_time({"dp_degree": "not-a-number"})


# -------------------------------------------------------------- chip_smoke

def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod      # dataclasses resolves the module
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_bare_command_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 2
    assert proc.stdout.startswith('DEVICE {"platform": "cpu"')
    with pytest.raises(json.JSONDecodeError):    # no result line
        json.loads(proc.stdout.strip().splitlines()[-1])


def test_chip_smoke_runs_every_phase_and_fails_on_any(capsys):
    smoke = _load_chip_smoke()
    ran = []

    def ok(sz):
        ran.append("ok")
        return {"step_ms": 1.0}

    def boom(sz):
        ran.append("boom")
        raise RuntimeError("injected")

    results = smoke.run_phases([("a", ok), ("b", boom), ("c", ok)],
                               smoke.Sizes.tiny())
    assert ran == ["ok", "boom", "ok"]            # b's failure did not stop c
    assert [r["ok"] for r in results] == [True, False, True]
    assert "RuntimeError: injected" in results[1]["error"]
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("PHASE ")]
    assert len(lines) == 3


def test_chip_smoke_exit_code_follows_the_phases(monkeypatch, capsys):
    smoke = _load_chip_smoke()

    def failing(sz):
        raise AssertionError("loss did not fall")

    monkeypatch.setattr(smoke, "PHASES", (("train", failing),
                                          ("flash", lambda sz: {})))
    monkeypatch.setattr(
        "paddle_tpu._core.device.enable_compile_cache", lambda: "unused")
    assert smoke.main(["--cpu-dry-run"]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False and last["failed"] == ["train"]
    assert last["dry_run"] is True and last["device"]["platform"] == "cpu"

    monkeypatch.setattr(smoke, "PHASES", (("flash", lambda sz: {}),))
    assert smoke.main(["--cpu-dry-run"]) == 0
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1])["ok"]
