#!/usr/bin/env python3
"""Measure one cell of the benchmark on the chip this process is started on.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmarks/run.py --workload <cell> --cpu-dry-run      # here, tiny

A cell is `benchmarks/workloads/<cell>.json` (see `cells.py`). One run:

  set-up   the runner of the cell's family (`runners/__init__.py`) builds
           the step, the state and the ring of batches from --seed, holds
           the program to its plain reference, and warms the only shapes the
           window uses. This file knows nothing of what a batch or a step is.
  window   --trace 0: a closed training loop for --seconds seconds, the
           ring's batches sent to the device one a step, the loss read every
           `sync_every` steps; both ends of the window sit on such a read.
           --trace 1: the same loop for `trace_steps` steps under the
           profiler, then ten steps each synchronised with the profiler off.

The last line of stdout is one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics with --trace 0, its per-layer
metrics with --trace 1), `device`, and with --trace 1 `breakdown`. What else
a reader may want (losses, memory, cache traffic) goes to stderr. Without a
TPU, or with fewer chips than the cell asks for, the exit code is 2 and
there is no result; --cpu-dry-run says `platform: cpu` and gives no device
metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # process start, as early as Python sees

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BENCH_CACHE = os.path.join(ROOT, ".bench_cache")    # traces; git-ignored
SYNCED_STEPS = 10       # steps timed one by one in a traced run

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileWatch:
    """Compilations and persistent-cache traffic, from JAX's own monitoring
    events (a copy of chip_smoke.CacheCounter, plus every trip to the
    compiler, cached or not)."""

    def __init__(self):
        import jax
        self.compiles = self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        self.requests += event == CACHE_REQUEST
        self.hits += event == CACHE_HIT

    def _on_duration(self, event, _seconds, **_kw):
        self.compiles += event == COMPILE_EVENT


def log(**facts):
    print("INFO " + json.dumps(facts, default=str), file=sys.stderr,
          flush=True)


def run_window(program, sync_every, stop):
    """The training loop. `stop(steps, seconds)` is asked at each read of
    the loss. Returns (steps, seconds, losses read) and leaves the state in
    `program`; the clock starts on a drained device and stops on a read, so
    both ends are synchronised."""
    from jax.profiler import TraceAnnotation
    step, put, ring, state = (program.step, program.put, program.ring,
                              program.state)
    losses, steps = [], 0
    t0 = time.perf_counter()
    with TraceAnnotation("bench/window"):
        while True:
            for _ in range(sync_every):
                with TraceAnnotation("bench/make_batch"):
                    batch = ring[steps % len(ring)]
                with TraceAnnotation("bench/h2d"):
                    batch = put(batch)
                with TraceAnnotation("bench/step_call"):
                    state, loss = step(state, *batch)
                steps += 1
            with TraceAnnotation("bench/sync"):
                losses.append(float(loss))      # waits for the device
            seconds = time.perf_counter() - t0
            if stop(steps, seconds):
                program.state = state
                return steps, seconds, losses


def traced_window(name, traffic, program):
    """`trace_steps` steps of the same loop under the profiler; returns the
    loop's results and the path of the `.xplane.pb`, kept under `name`."""
    import jax
    trace_dir = os.path.join(BENCH_CACHE, "trace", name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # our spans, not every Python call
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        out = run_window(program, traffic["sync_every"],
                         lambda steps, _: steps >= traffic["trace_steps"])
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return out, found[0]


def synced_steps(program, n):
    import jax
    step_ms = []
    for i in range(n):
        t0 = time.perf_counter()
        batch = program.put(program.ring[i % len(program.ring)])
        program.state, loss = program.step(program.state, *batch)
        jax.block_until_ready(loss)
        step_ms.append((time.perf_counter() - t0) * 1e3)
    return step_ms


class Phases:
    """Seconds of set-up by phase, for the log: what a later PR may shorten."""

    def __init__(self):
        self.seconds, self._last = {}, T_START

    def end(self, phase):
        now = time.perf_counter()
        self.seconds[phase], self._last = now - self._last, now


def end_to_end(program, units_per_s_chip: float, peaks, setup_s: float):
    """The cell's end-to-end metrics. `mfu` and `setup_s` every cell has;
    the rate under its own name where a step consumes tokens, and the
    compiler's balance of memory where one executable is the step."""
    metrics = {
        "mfu": {"value": program.flops_per_unit * units_per_s_chip
                / peaks.flops, "unit": "fraction"},
        "setup_s": {"value": setup_s, "unit": "s"}}
    if program.unit == "tokens":
        metrics["tokens_per_s_chip"] = {"value": units_per_s_chip,
                                        "unit": "tokens/s"}
    if program.memory:
        metrics["hbm_peak_gb"] = {"value": program.memory["total"] / 1e9,
                                  "unit": "GB"}
    return metrics


def per_layer(cell, run, problems: list) -> dict:
    """The cell's per-layer metrics from a traced run on the chip. There,
    every reader the cell lists has something to read: one that does not has
    lost what it measured, which is a problem and not an absent metric."""
    metrics = {}
    for name, reader in cell.layer_metrics.items():
        value = reader.read(run)
        if value is None:
            problems.append(f"per-layer metric {name} found nothing to "
                            "read in this run")
        else:
            metrics[name] = {"value": value, "unit": reader.UNIT}
    return metrics


def measure(cell, args, devices, dry_run: bool, phases: Phases) -> dict:
    from benchmarks import check, trace_reduce
    from benchmarks.layer_metrics import Run
    from benchmarks.peaks import peaks_of

    kind = devices[0].device_kind
    peaks = None if dry_run else peaks_of(kind)     # unknown chip: an error
    watch = CompileWatch()
    program = cell.runner.set_up(cell, args.seed, devices, phases)
    problems = program.problems

    compiles_before = watch.compiles
    setup_s = time.perf_counter() - T_START
    if args.trace:
        (steps, seconds, losses), xplane = traced_window(
            cell.name, cell.traffic, program)
    else:
        steps, seconds, losses = run_window(
            program, cell.traffic["sync_every"],
            lambda _, elapsed: elapsed >= args.seconds)
    compiles_in_window = watch.compiles - compiles_before
    failed = check.finite(losses)
    if failed:
        problems.append(f"{failed} of {len(losses)} losses read in the "
                        f"window are not finite")
    if compiles_in_window:
        problems.append(f"{compiles_in_window} compilations in the window")

    # the runtime's counter leaves out a program's temporaries (settled on
    # the chip, PERF.md section 7), so the step executable's own balance
    # from the compiler is the peak unless buffers outside it were larger
    runtime_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices[:cell.chips])
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  runtime_peak, (program.memory or {}).get("total", 0))}
    units_per_s_chip = steps * program.units_per_step / seconds / cell.chips
    log(cell=cell.name, seed=args.seed, steps=steps, window_s=seconds,
        setup_s=setup_s, setup_phases_s=phases.seconds, losses=losses,
        runtime_peak_bytes=runtime_peak, cache_requests=watch.requests,
        cache_hits=watch.hits, compiles=watch.compiles,
        unit=program.unit, units_per_s_chip=units_per_s_chip,
        step_memory=program.memory, facts=program.facts)
    result = {"correct": not problems, "attempted": steps, "failed": failed,
              "metrics": {}, "device": device}
    run = Run(cell, program, peaks, watch.requests, watch.hits,
              compiles_in_window, [])
    if dry_run:
        # a CPU says nothing of a device metric: no number, only which of
        # the cell's readers found something to read
        result["dry_run"] = True
        result["readers"] = sorted(
            name for name, reader in cell.layer_metrics.items()
            if args.trace and reader.read(run) is not None)
    elif not args.trace:
        result["metrics"] = end_to_end(program, units_per_s_chip, peaks,
                                       setup_s)
    else:
        hlo_text = program.hlo_text() if program.hlo_text else ""
        with open(os.path.join(os.path.dirname(xplane), "step.hlo.txt"),
                  "w") as f:     # beside the trace, for a reader by hand
            f.write(hlo_text)
        run.trace = trace_reduce.reduce(
            trace_reduce.load(xplane), trace_reduce.parse_hlo(hlo_text),
            steps)
        run.step_ms = synced_steps(program, SYNCED_STEPS)
        result["metrics"] = per_layer(cell, run, problems)
        result["correct"] = not problems
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.device_ops,
                               "idle_gaps": run.trace.idle_gaps}
        log(trace=xplane, category_s=run.trace.category_s)
    log(problems=problems)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="tiny sizes on the CPU: checks the harness, says "
                         "nothing of the chip")
    args = ap.parse_args(argv)
    phases = Phases()

    from benchmarks.cells import load_cell
    cell = load_cell(args.workload, tiny=args.cpu_dry_run)
    if args.cpu_dry_run:        # before JAX starts: the CPU, with a mesh
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cell.chips}").strip()
    import jax
    phases.end("python_and_jax_import")
    devices = jax.devices()
    phases.end("backend_start")
    if not args.cpu_dry_run:
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            print(f"benchmarks/run.py: cell {cell.name!r} needs {cell.chips} "
                  f"TPU chip(s); JAX found {len(devices)} "
                  f"{devices[0].platform} device(s). --cpu-dry-run checks "
                  "the harness at a tiny size.", file=sys.stderr)
            return 2
        from paddle_tpu._core.device import enable_compile_cache
        log(compile_cache=enable_compile_cache())
    phases.end("package_import")
    print(json.dumps(measure(cell, args, devices, args.cpu_dry_run, phases)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
