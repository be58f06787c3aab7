#!/usr/bin/env python3
"""Measure cells as the driver admits them, and print each end-to-end
metric's spread (the distance between the quartiles over the median):

    chiprun -- python3 benchmarks/spread.py <cell> [<cell> ...]

For each cell: a first run, which compiles and is kept apart; two sets of
six runs; one traced run. Every run is a new process with another --seed,
and its result and INFO lines go to `chiprun_out/spread_<cell>.jsonl`. This
parent never touches JAX, so the chip is free for each child. A bound is
about five times the widest spread over the cells, and never under 1 %.
"""
from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SETS, RUNS, FIRST_SEED = 2, 6, 100


def one_run(command, workload, seed, seconds, trace):
    argv = command + ["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)]
    done = subprocess.run(argv, cwd=ROOT, capture_output=True, text=True)
    row = {"workload": workload, "seed": seed, "trace": trace,
           "rc": done.returncode, "result": None,
           "info": [line[5:] for line in done.stderr.splitlines()
                    if line.startswith("INFO ")]}
    if done.returncode == 0:
        row["result"] = json.loads(done.stdout.strip().splitlines()[-1])
    else:
        row["stderr_tail"] = done.stderr[-4000:]
    out = os.path.join(ROOT, "chiprun_out", f"spread_{workload}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        f.write(json.dumps(row) + "\n")
    return row


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return (q3 - q1) / statistics.median(values)


def measure(command, workload, seconds) -> bool:
    """True if every run of the cell ended and was correct."""
    seeds = iter(range(FIRST_SEED, FIRST_SEED + 2 + SETS * RUNS))
    first = one_run(command, workload, next(seeds), seconds, 0)
    print(f"{workload} first run (compiles):", json.dumps(first["result"]),
          flush=True)
    if first["rc"]:     # what stops the first run would stop them all
        print(first["stderr_tail"], file=sys.stderr)
        return False
    good = first["result"]["correct"]
    for s in range(SETS):
        rows = [one_run(command, workload, next(seeds), seconds, 0)
                for _ in range(RUNS)]
        results = [r["result"] for r in rows if r["rc"] == 0]
        good &= len(results) == RUNS and all(r["correct"] for r in results)
        for name in results[0]["metrics"] if len(results) > 1 else ():
            values = [r["metrics"][name]["value"] for r in results]
            print(f"{workload} set {s} {name}: median "
                  f"{statistics.median(values):.6g} spread "
                  f"{spread(values):.5f} values {values}", flush=True)
    traced = one_run(command, workload, next(seeds), seconds, 1)
    print(f"{workload} traced run:", json.dumps(traced["result"]), flush=True)
    return good and traced["rc"] == 0 and traced["result"]["correct"]


def main(argv=None) -> int:
    workloads = sys.argv[1:] if argv is None else argv
    if not workloads or workloads[0].startswith("-"):
        print(__doc__, file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # list() first: every cell is measured, whatever an earlier one did
    return 0 if all(list(measure(bench["command"], w, bench["run_seconds"])
                         for w in workloads)) else 1


if __name__ == "__main__":
    sys.exit(main())
