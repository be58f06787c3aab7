#!/usr/bin/env python3
"""Where a cell's set-up goes: the harness's phases beside the program's own
spans that fell inside each, by clock. The table of PERF.md section 5.

    python3 benchmarks/setup_table.py <cell> [--seed n] [--cpu-dry-run]
                                      [--profile] [--json]

One run of the cell's set-up as `run.py` makes it (`load_cell`,
`enable_compile_cache()`, the runner's `set_up`), with a `run.Phases` that
also keeps each phase's end on the epoch clock. `set_up` registers nothing:
`enable_compile_cache()` registers the program's recorder
(`paddle_tpu/observability/programs.py`; under `--cpu-dry-run`, where
`run.py` leaves the cache off, this file registers it), and from then on
every trace, lowering, compile, cache load and Mosaic call site of the
process is a span on the same clock. The table has a row for each phase of
`setup_phases_s`: its seconds, then the SELF seconds of the spans that began
inside it on the thread that ran the set-up (trace / lower / compile / cache
load / Mosaic sites), then the rest: the device running, data made on the
host, and whatever is still unnamed. Under it the ten programs that cost
most, each a hit or a miss of the persistent cache, what the Mosaic sites
cost by kernel, the entries the run wrote to the persistent cache (a cold
run's: what a warm run then loads, by bytes), the readers of
`layer_metrics/_setup.py`, and what the recorder's own callbacks took.

`--profile` runs the same set-up under `jax.profiler` and adds the share of
each phase in which an operation ran on the device (the union of the `XLA
Ops` events; a profile counts from its own start, so a host span this file
stamps on the epoch clock, `bench/setup_clock`, says how far the two are
apart): whether a long phase is the chip or the host. `--json` prints the table as one JSON object on the last
line. No window is run and no metric is measured: this is a second run of
the cell's set-up, for a reader by hand.
"""
from __future__ import annotations

import time

T0 = time.time()        # process start on the epoch clock, as `run.T_START`
#                         is on the monotonic one: as early as Python sees

import argparse  # noqa: E402
import collections  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as harness  # noqa: E402

KINDS = ("trace", "lower", "compile", "cache_load", "mosaic_site")
BUILDS = ("lower", "compile", "cache_load")    # a program was built
LISTED = 10             # programs under the table
CLOCK_SPAN = "bench/setup_clock"    # where a profile's clock meets ours


class Phases(harness.Phases):
    """`run.Phases`, which also keeps where each phase ended on the epoch
    clock."""

    def __init__(self):
        super().__init__()
        self.ended = {}

    def end(self, phase):
        super().end(phase)
        self.ended[phase] = time.time()


def busy_in(intervals, start: float, end: float) -> float:
    """Seconds of [start, end) that the merged `intervals` cover."""
    return sum(max(0.0, min(e, end) - max(s, start)) for s, e in intervals)


def device_busy(trace_dir: str, marked: float):
    """Merged [(start s, end s)] on the epoch clock in which an operation
    ran on any chip, from the one `.xplane.pb` under `trace_dir`, whose
    host span `CLOCK_SPAN` began at `marked` on the epoch clock."""
    from benchmarks import trace_reduce
    (xplane,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
    trace = trace_reduce.load(xplane)
    (mark,) = [ev for ev in trace.host if ev.name == CLOCK_SPAN]
    apart = marked - mark.start / 1e9
    return [(apart + s / 1e9, apart + e / 1e9)
            for s, e in trace_reduce._union(
                (ev.start, ev.end) for events in trace.ops.values()
                for ev in events)]


def table(phases: Phases, spans: list, thread: int, busy=None) -> list:
    """A row a phase: {phase, s, one key a kind, rest, programs (outermost
    ones lowered, compiled or loaded in it)[, device_busy_share]}. A span
    counts where it began; a folded trace where its parent did."""
    rows, start = [], T0
    for phase, end in phases.ended.items():
        row = {"phase": phase, "s": end - start, **dict.fromkeys(KINDS, 0.0)}
        built = set()
        for span in spans:
            if span["thread"] != thread or not (
                    start <= span["start_ns"] / 1e9 < end):
                continue
            row[span["kind"]] += span["self_s"]
            row["trace"] += span["folded_self_s"]
            if span["parent"] is None and span["kind"] in BUILDS:
                built.add(span["within"])
        row["programs"] = len(built)
        row["rest"] = row["s"] - sum(row[k] for k in KINDS)
        if busy is not None:
            row["device_busy_share"] = (busy_in(busy, start, end) / row["s"]
                                        if row["s"] else 0.0)
        rows.append(row)
        start = end
    return rows


def sites(spans: list) -> list:
    """[[kernel, stage or '-', sites, distinct operand shapes, whole
    seconds]], most seconds first. The stage is the first scope of the
    site's stack that the program's `models/stages.py` names (a
    `jax.custom_vjp`'s forward rule is traced with an empty stack)."""
    from benchmarks.layer_metrics import _stages
    names = _stages.vocabulary()
    found = collections.defaultdict(lambda: [0, set(), 0.0])
    for span in spans:
        if span["kind"] != "mosaic_site":
            continue
        stage = _stages.place(span.get("scope", ""), names)[0] or "-"
        entry = found[span["fun_name"], stage]
        entry[0] += 1
        entry[1].add(str(span.get("shapes")))
        entry[2] += span["s"]
    return sorted(([kernel, stage, n, len(shapes), s]
                   for (kernel, stage), (n, shapes, s) in found.items()),
                  key=lambda r: -r[4])


def cache_files(directory: str) -> dict:
    """{file: bytes} of the persistent cache's entries in `directory`."""
    found = {}
    for path in glob.glob(os.path.join(directory, "*-cache")):
        try:
            found[os.path.basename(path)] = os.path.getsize(path)
        except OSError:             # evicted meanwhile
            continue
    return found


def render(cell: str, rows: list, summary: dict, site_rows: list,
           readers: dict, written: list) -> str:
    columns = ["s", *KINDS, "rest"]
    if rows and "device_busy_share" in rows[0]:
        columns.append("device_busy_share")
    lines = [f"`{cell}`: set-up by phase, seconds; the spans' self time by "
             "kind inside each\n",
             "| phase | " + " | ".join(columns) + " | programs |",
             "| --- |" + " ---: |" * (len(columns) + 1)]
    total = {c: sum(r[c] for r in rows) for c in columns[:-1] + ["rest"]}
    for row in rows + [{"phase": "all", **total,
                        "programs": sum(r["programs"] for r in rows)}]:
        lines.append(f"| {row['phase']} | " + " | ".join(
            "—" if c not in row else f"{row[c]:.3f}" for c in columns)
            + f" | {row['programs']} |")
    lines.append("\nprograms (s: trace self / trace of children / lower / "
                 "compile / cache load):")
    for p in summary["programs"][:LISTED]:
        per_mb = ""
        if p.get("retrieval_s") is not None:
            per_mb = (f", retrieval {p['retrieval_s']:.3f} s, compile time "
                      f"saved {p['saved_s']:.1f} s")
        lines.append(
            f"  {p['program']:<32} {p['trace_self_s']:7.3f} "
            f"{p['trace_children_s']:7.3f} {p['lower_s']:7.3f} "
            f"{p['compile_s']:7.3f} {p['cache_load_s']:7.3f}  "
            f"{p['cache'] or '-':<4} {p['mosaic_sites']} sites{per_mb}")
    if site_rows:
        lines.append("\nMosaic sites (kernel, stage, sites, distinct operand "
                     "shapes, seconds with the traces inside them):")
        lines += [f"  {kernel:<24} {stage:<14} {n:3d} {distinct:3d} {s:7.3f}"
                  for kernel, stage, n, distinct, s in site_rows]
    for name, r in summary["retraced"].items():
        if r["built"] > r["distinct"]:
            lines.append(f"  {name}: body traced {r['built']} times for "
                         f"{r['distinct']} distinct shapes "
                         f"({r['traces']} traces with the cached ones)")
    if written:
        lines.append("\nwritten to the persistent cache (MB): " + ", ".join(
            f"{name} {size / 1e6:.1f}" for name, size in written))
    lines.append("\n" + ", ".join(
        f"`{name}` {'None' if value is None else format(value, '.4g')}"
        for name, value in readers.items()))
    lines.append(f"\nthe recorder's own callbacks: {summary['callbacks']} in "
                 f"{1e3 * summary['callback_s']:.2f} ms")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="tiny sizes on the CPU: checks the tool, says "
                         "nothing of the chip")
    ap.add_argument("--profile", action="store_true",
                    help="under jax.profiler: adds the device's busy share "
                         "of each phase")
    ap.add_argument("--json", action="store_true",
                    help="the table as one JSON object on the last line")
    args = ap.parse_args(argv)
    phases = Phases()

    from benchmarks.cells import load_cell
    cell = load_cell(args.cell, tiny=args.cpu_dry_run)
    if args.cpu_dry_run:        # as run.py: the CPU, with the cell's mesh
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
    from paddle_tpu.observability import programs, stats
    cache_dir = None
    if args.cpu_dry_run:
        programs.register()
    elif devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"benchmarks/setup_table.py: cell {cell.name!r} needs "
              f"{cell.chips} TPU chip(s); JAX found {len(devices)} "
              f"{devices[0].platform} device(s). --cpu-dry-run checks the "
              "tool at a tiny size.", file=sys.stderr)
        return 2
    else:
        from paddle_tpu._core.device import enable_compile_cache
        cache_dir = enable_compile_cache()
        harness.log(compile_cache=cache_dir)
    cached = cache_files(cache_dir) if cache_dir else {}
    phases.end("package_import")

    trace_dir = os.path.join(harness.BENCH_CACHE, "setup_trace", cell.name)
    if args.profile:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        marked = time.time()
        with jax.profiler.TraceAnnotation(CLOCK_SPAN):
            pass
    try:
        program = cell.runner.set_up(cell, args.seed, devices, phases)
    finally:
        if args.profile:
            jax.profiler.stop_trace()
    setup_s = time.time() - T0

    from benchmarks.layer_metrics import _setup
    summary = stats()["programs"]
    spans = programs.rows()
    rows = table(phases, spans, threading.get_ident(),
                 device_busy(trace_dir, marked) if args.profile else None)
    readers = _setup.metrics(types.SimpleNamespace(program=program))
    site_rows = sites(spans)
    # what a cold run wrote: `<module name>-<key>-cache`, most bytes first
    written = sorted(([name.rsplit("-", 2)[0], size] for name, size in
                      (cache_files(cache_dir) if cache_dir else {}).items()
                      if name not in cached), key=lambda r: -r[1])
    print(render(cell.name, rows, summary, site_rows, readers, written),
          flush=True)
    harness.log(cell=cell.name, seed=args.seed, setup_s=setup_s,
                setup_phases_s=phases.seconds, problems=program.problems)
    if args.json:
        print(json.dumps({
            "cell": cell.name, "seed": args.seed, "setup_s": setup_s,
            "platform": devices[0].platform, "dry_run": args.cpu_dry_run,
            "phases": rows, "totals": summary["totals"],
            "programs": summary["programs"][:LISTED],
            "retraced": summary["retraced"], "sites": site_rows,
            "cache_written": written,
            "readers": readers, "callbacks": summary["callbacks"],
            "callback_s": summary["callback_s"],
            "problems": program.problems}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
