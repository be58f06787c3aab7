"""Test config: run on a virtual 8-device CPU mesh (the driver validates the
real-TPU path separately via __graft_entry__). Mirrors the reference's
fake-device testing approach (phi/backends/custom/fake_cpu_device.h,
SURVEY.md §4)."""
import os

# The suite self-lints: every flushed lazy segment and IR pass pipeline
# runs the paddle_tpu.analysis checkers (donation safety, in-place
# races, tracer leaks, shape/dtype drift, pass purity) in warn mode —
# a checker false positive shows up as a StaticCheckWarning in test
# output, a real violation in framework code fails the seeded tests.
# Env (not set_flags) so the flag is live from the first import.
os.environ.setdefault("FLAGS_static_checks", "warn")

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite never opens a chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import signal  # noqa: E402

import pytest  # noqa: E402

# Modules that spawn real OS processes (TCPStore rendezvous, multi-rank
# collectives, launcher pods) — the analog of the reference's
# RUN_TYPE=DIST ctest label (test/collective/CMakeLists.txt:1-4). The
# smoke path is `pytest -m fast`; the full suite is documented as two
# shards in README.md.
_DIST_MODULES = {
    "test_comm_context",
    "test_data_parallel",
    "test_hybrid_optimizer",
    "test_launch",
    "test_pipeline_hostdriven",
    "test_process_group",
    "test_ps_service",
    "test_rpc_onnx",
    "test_sharding_eager",
    "test_engine_tuner_elastic",
    "test_auto_tuner_trials",
    "test_mp_multiproc",
    "test_acc_align",
    "test_ps_runtime",
}

# Compile-heavy single-process suites (>= ~10 s each on one core):
# still part of the full run, excluded from the `-m fast` smoke path.
_SLOW_MODULES = {
    "test_inference_vision",
    "test_pipeline_compiled",
    "test_flash_sharded",
    "test_flash_varlen",
    "test_mp_ops",
    "test_context_parallel",
    "test_lenet_e2e",
    "test_model_families",
    "test_moe",
    "test_distributed",
    "test_rnn",
    "test_op_suite_ext",
    "test_quantization",
    "test_lbfgs_fused",
    "test_math_namespaces",
    "test_hapi",
    "test_dist_passes",
}

# Per-test wall-clock budgets (seconds); override with
# @pytest.mark.timeout(N). Mirrors the reference's per-test ctest
# timeouts so one hung socket cannot eat a whole round.
_FAST_TIMEOUT = 180
_DIST_TIMEOUT = 420


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1] if item.module else ""
        if mod in _DIST_MODULES:
            item.add_marker(pytest.mark.dist)
        elif mod in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.fast)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    # SIGALRM-based timeout (tests run in the main thread); vendored
    # because pip installs are unavailable in this environment. Wraps
    # the whole protocol so fixture setup/teardown hangs (rendezvous,
    # trainer-process spawns) are bounded too, not just the call phase.
    mark = item.get_closest_marker("timeout")
    if mark and mark.args:
        limit = int(mark.args[0])
    else:
        limit = _DIST_TIMEOUT if item.get_closest_marker("dist") else _FAST_TIMEOUT

    def _on_alarm(signum, frame):
        raise TimeoutError(f"{item.nodeid} exceeded {limit}s timeout")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(limit)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu
    paddle_tpu.seed(2024)
    yield


def with_flag(name, value):
    """Context manager: set a runtime flag, restore the old value on
    exit. Shared by the flag-surface and analysis suites (import as
    `from conftest import with_flag`)."""
    from paddle_tpu._core.flags import flag_value, set_flags

    class _Ctx:
        def __enter__(self):
            self.old = flag_value(name)
            set_flags({name: value})

        def __exit__(self, *a):
            set_flags({name: self.old})
    return _Ctx()


def unregister_program_recorder():
    """Take JAX's listeners back from the recorder of program-building
    (`paddle_tpu/observability/programs.py`) and empty it. The recorder has
    no switch; a test that registers it undoes that by hand, so that the
    tests after it in this process see none (import as `from conftest
    import unregister_program_recorder`)."""
    from jax._src import monitoring
    from paddle_tpu.observability import programs
    if programs.registered():
        monitoring.unregister_event_listener(programs.RECORDER.on_event)
        monitoring.unregister_event_duration_listener(
            programs.RECORDER.on_duration)
        monitoring.unregister_event_time_span_listener(
            programs.RECORDER.on_time_span)
        programs._REGISTERED = False
    programs.reset()


@pytest.fixture
def recorder():
    """The process's recorder of program-building, registered and empty
    for one test."""
    from paddle_tpu.observability import programs
    unregister_program_recorder()
    programs.register()
    yield programs.RECORDER
    unregister_program_recorder()
