#!/usr/bin/env python3
"""Where a cell's step spends its device time, by stage of the program and
by direction: the table of PERF.md section 5.

    python3 benchmarks/stage_table.py <cell> [--dir <directory>] [--list <stage>]

Reads the newest trace a `--trace 1` run of the cell kept under
`.bench_cache/trace/<cell>/` and the `step.hlo.txt` beside it (or the
`.xplane.pb` and `step.hlo.txt` in `--dir`), and prints device self time in
ms per step, mean over the chips: a row per stage the program names
(`paddle_tpu/models/stages.py`) and one for what stands under none, a column
per direction (`layer_metrics/_stages.py` says how each is told), then the
stage's whole time split by what the instructions are (`trace_reduce.py`'s
categories), and under the table the seven per-layer metrics of
`layer_metrics/_stages.py`. `--list <stage>` (`none` for no stage) adds that
stage's instructions, most time first. It needs no chip: the trace holds the
times.
"""
from __future__ import annotations

import argparse
import collections
import glob
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace_reduce  # noqa: E402
from benchmarks.layer_metrics import _stages  # noqa: E402

CATEGORIES = ("matmul", "mosaic", "collective", "other")
NO_STAGE = "none"
LISTED = 25         # instructions a --list shows


def newest_trace(cell: str) -> str:
    """The directory of the newest trace kept for `cell`."""
    found = glob.glob(os.path.join(ROOT, ".bench_cache", "trace", cell,
                                   "plugins", "profile", "*", "*.xplane.pb"))
    if not found:
        raise SystemExit(f"no trace of {cell!r} under .bench_cache/: run "
                         f"benchmarks/run.py --workload {cell} --trace 1 on "
                         "the chip first")
    return os.path.dirname(max(found, key=os.path.getmtime))


def load(directory: str, steps: int):
    """What the stage readers take of a run (`layer_metrics.Run`): the
    reduced trace and the HLO text in `directory`."""
    (xplane,) = glob.glob(os.path.join(directory, "*.xplane.pb"))
    with open(os.path.join(directory, "step.hlo.txt")) as f:
        hlo_text = f.read()
    summary = trace_reduce.reduce(trace_reduce.load(xplane),
                                  trace_reduce.parse_hlo(hlo_text), steps)
    return types.SimpleNamespace(trace=summary, program=types.SimpleNamespace(
        hlo_text=lambda: hlo_text))


def category(summary, name: str) -> str:
    op = summary.ops.get(name)
    return op.category if op and op.category in CATEGORIES else "other"


def table(summary, where) -> list:
    """Markdown lines: stage x (directions, all, categories), ms per step."""
    stages = list(_stages.vocabulary().ALL) + [None]
    cell_ms = collections.defaultdict(float)
    for name, s in summary.op_s.items():
        stage, direction = where[name]
        ms = 1e3 * s / summary.steps
        for column in (direction, "all", category(summary, name)):
            cell_ms[stage, column] += ms
            cell_ms["all", column] += ms
    columns = _stages.DIRECTIONS + ("all",) + CATEGORIES
    lines = ["| stage | " + " | ".join(columns) + " |",
             "| --- |" + " ---: |" * len(columns)]
    for stage in stages + ["all"]:
        lines.append(f"| {stage or '(no stage)'} | " + " | ".join(
            f"{cell_ms[stage, c]:.1f}" if cell_ms[stage, c] else "—"
            for c in columns) + " |")
    return lines


def listing(summary, where, stage) -> list:
    """The instructions of one stage, most time first."""
    rows = sorted(((s, name) for name, s in summary.op_s.items()
                   if where[name][0] == stage), reverse=True)
    labels = {name: op.label for name, op in summary.ops.items()}
    lines = [f"{1e3 * s / summary.steps:9.3f} ms  {where[name][1]:8s} "
             f"{name} ({labels.get(name, '?')})" for s, name in rows[:LISTED]]
    rest = sum(s for s, _ in rows[LISTED:])
    if rest:
        lines.append(f"{1e3 * rest / summary.steps:9.3f} ms  in "
                     f"{len(rows) - LISTED} more")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cell")
    ap.add_argument("--dir", help="where the .xplane.pb and step.hlo.txt "
                    "are, if not the cell's newest under .bench_cache/")
    ap.add_argument("--list", action="append", default=[], metavar="STAGE",
                    help="also list this stage's instructions")
    args = ap.parse_args(argv)
    from benchmarks.cells import load_cell
    steps = load_cell(args.cell).traffic["trace_steps"]
    run = load(args.dir or newest_trace(args.cell), steps)
    summary, where = run.trace, _stages.placed(run)
    if where is None:
        raise SystemExit("this checkout's program names no stages "
                         "(paddle_tpu/models/stages.py)")
    print(f"`{args.cell}`: {steps} traced steps, {summary.chips} chip(s), "
          f"device busy {1e3 * summary.busy_s / steps:.1f} ms per step, idle "
          f"share {100 * (1 - summary.busy_s / summary.window_s):.2f} %\n")
    print("\n".join(table(summary, where)))
    print("\n" + ", ".join(
        f"`{name}` {'None' if value is None else format(value, '.4g')}"
        for name, value in _stages.metrics(run).items()))
    for stage in args.list:
        print(f"\n{stage}:")
        print("\n".join(listing(summary, where,
                                None if stage == NO_STAGE else stage)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
