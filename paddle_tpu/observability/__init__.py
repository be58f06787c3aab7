"""paddle_tpu.observability — fused-runtime telemetry.

Three consumers over one set of instrumentation points (segment
record→flush, compile vs. cached execute, executable-cache hit/miss,
donation decisions, fused-backward step cache, per-op replay, SOT guard
evaluation, distributed collectives, optimizer updates):

- **metrics registry** (`FLAGS_observability` / `enable()`): process-
  wide counters/gauges/histograms, snapshot via `stats()`;
- **structured spans**: while a `paddle_tpu.profiler.Profiler` is
  recording, the same points emit timed span events into the chrome
  trace (`segment::flush[reason]` with compile/execute children);
- **flight recorder** (`FLAGS_flight_recorder`): bounded ring of recent
  events, auto-dumped to a report on enforce errors, failed flushes,
  and sanitizer error-mode trips (rank-aware retention via
  FLAGS_flight_max_dumps).

Plus the byte-domain plane (`FLAGS_memory_telemetry`, memory.py):
live-buffer census with birth-site provenance, per-executable XLA
memory analysis cached at compile time, donation savings accounting,
and OOM postmortems with a typed re-raise — `stats()` gains a
``memory`` section while it is on.

Cost when everything is off: one module-level boolean check per
instrumentation point (`observability._state.ACTIVE`), zero registry
work — asserted by tests/test_observability.py
(`test_off_mode_zero_registry_work`).

Program building is the one part with no flag (`programs.py`): once an
entry point has called `_core.device.enable_compile_cache()` (or
`enable()` here), every program the process traces, lowers, compiles or
loads from JAX's persistent cache is a span, and `stats()["programs"]` is
the operator's table for "why did this job take five minutes to its first
step, and which program missed the cache": one row a program (trace self /
trace of children / lower / compile or load, hit or miss), the totals, the
functions traced twice for one shape, the recorder's own cost.
`benchmarks/setup_table.py` lays the same spans beside a benchmark cell's
set-up phases.

    python -m paddle_tpu.observability        # demo workload + stats,
                                              # the programs' table under it
"""
from __future__ import annotations

from .._core import flags as _flags
from . import _state, flight, metrics, programs, spans
from .metrics import counter, gauge, histogram
from .spans import span

__all__ = ["stats", "reset", "enable", "disable", "enabled",
           "counter", "gauge", "histogram", "span",
           "flight_record", "dump_flight_record"]

# keep the module-level fast gates coherent with the flags (env spelling
# FLAGS_observability=1 works from first import; set_flags mid-session
# flips the gate immediately)
_flags.watch_flag("FLAGS_observability", _state.set_metrics)
_flags.watch_flag("FLAGS_flight_recorder", _state.set_flight)
_flags.watch_flag("FLAGS_distributed_telemetry", _state.set_dist)
_flags.watch_flag("FLAGS_memory_telemetry", _state.set_mem)


def _on_compute_flag(on):
    was = _state.COMPUTE
    _state.set_compute(on)
    if on and not was:
        # ENTERING the compute plane re-keys the compiled-program
        # caches (mesh-epoch salt): the next execution of each
        # workload compiles exactly ONE fresh executable whose
        # cost_analysis() and named-scope provenance are captured —
        # a warm pre-plane cache would otherwise report zero FLOPs
        # forever (analyses are captured at compile time only). Only
        # when some runner was actually cached without cost capture
        # (COST_STALE): a monitoring loop flipping the plane around
        # each budget sample must not recompile the world per sample
        # once the warm entries already carry their analyses.
        from .._core import lazy
        if lazy.COST_STALE:
            lazy.bump_mesh_epoch()
            lazy.COST_STALE = False


_flags.watch_flag("FLAGS_compute_telemetry", _on_compute_flag)


def _on_goodput_flag(on):
    import sys as _sys
    _state.set_goodput(bool(on))
    # the goodput module is only imported once the plane is first
    # turned ON (the resilience-package laziness discipline); after
    # that, flips keep its ledger/watchdog coherent
    mod = _sys.modules.get(__name__ + ".goodput")
    if on:
        from . import goodput as mod
    if mod is not None:
        mod._sync(bool(on))


_flags.watch_flag("FLAGS_goodput", _on_goodput_flag)


def _on_monitor_flag(on):
    import sys as _sys
    _state.set_monitor(bool(on))
    # same laziness discipline as the goodput plane: the timeseries
    # module (sampler thread + HTTP exporter) is only imported once the
    # monitor is first turned ON; later flips start/stop in place
    mod = _sys.modules.get(__name__ + ".timeseries")
    if on:
        from . import timeseries as mod
    if mod is not None:
        mod._sync(bool(on))


_flags.watch_flag("FLAGS_monitor", _on_monitor_flag)


def enable(flight_recorder: bool = None):
    """Turn on metrics collection (and optionally the flight recorder),
    and the recorder of program-building, which then stays."""
    programs.register()
    f = {"FLAGS_observability": True}
    if flight_recorder is not None:
        f["FLAGS_flight_recorder"] = bool(flight_recorder)
    _flags.set_flags(f)


def disable():
    _flags.set_flags({"FLAGS_observability": False})


def enabled() -> bool:
    return _state.METRICS


def reset():
    """Zero every metric, drop the flight ring and the programs' spans
    (counter snapshots restart from a clean baseline)."""
    metrics.reset()
    flight.reset()
    programs.reset()


def _derived(counters: dict) -> dict:
    hits = misses = 0
    for k, v in counters.items():
        if k.startswith("cache."):
            if k.endswith(".hit"):
                hits += v
            elif k.endswith(".miss"):
                misses += v
    step_hit = counters.get("cache.fused_step.hit", 0)
    step_miss = counters.get("cache.fused_step.miss", 0)
    return {
        "compiles": sum(v for k, v in counters.items()
                        if k.startswith("compiles.")),
        "cache_hit_rate": (hits / (hits + misses)
                           if hits + misses else None),
        "step_cache_hit_rate": (step_hit / (step_hit + step_miss)
                                if step_hit + step_miss else None),
        # every fusion-window break costs the step cache + optimizer
        # donation — the BUDGET_r06 eager-GPT finding, now a headline
        # number instead of raw span archaeology
        "fusion_window_breaks": counters.get("fusion.window_breaks", 0),
    }


def stats(reset_after: bool = False) -> dict:
    """Snapshot of the registry plus derived headline numbers:

    - ``compiles``: framework-issued XLA compilations (sum of the
      ``compiles.*`` counters) — steady state adds zero;
    - ``cache_hit_rate``: hit fraction across every executable cache;
    - ``step_cache_hit_rate``: the fused fwd+vjp "step cache" alone —
      THE steady-state train-step health signal.
    """
    # the programs the process built, a row each (no flag: it is there once
    # an entry point asked for the compile cache); first, because reading
    # them brings their counters in the registry up to date
    built = programs.summary() if programs.registered() else None
    snap = metrics.snapshot()
    snap.update(_derived(snap["counters"]))
    if built is not None:
        snap["programs"] = built
    if _state.MEM:
        # byte-domain headline (census watermark + cached per-
        # executable memory analysis) rides along whenever the memory
        # telemetry plane is on
        from . import memory as _memory
        snap["memory"] = _memory.summary()
    if _state.COMPUTE:
        # FLOP-domain headline (cost-analysis log + executed-FLOPs
        # totals + the per-chip peak the MFU column divides by)
        from . import compute as _compute
        snap["compute"] = _compute.summary()
    if _state.GOODPUT:
        # job-level wall attribution: the exclusive bucket partition,
        # goodput fraction and top badput source from the ledger
        from . import goodput as _goodtel
        snap["goodput"] = _goodtel.summary()
    if reset_after:
        reset()
    return snap


def flight_record() -> str:
    """The flight-recorder ring formatted as a report."""
    return flight.record()


def dump_flight_record(path: str = None) -> str:
    """Write the flight record to a file; returns the path."""
    return flight.dump(reason="manual dump", path=path)
