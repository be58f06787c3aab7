"""Per-layer metrics: one small reader per file, found by name.

A reader module states what it measures in five constants and has one
function:

    LAYER    the layer's name in PERF.md section 3
    SOURCE   device_trace | program_span | program_counter | host_clock
    UNIT, BETTER ("higher" | "lower")
    MOVES    the end-to-end metric it should move
    read(run) -> a number, or None when there is nothing to read

`run` is what a `--trace 1` run of a cell gathered (`Run`, below). The harness
leaves a metric whose reader returns None out of the line, and on the chip
says so and sets `correct` to false: a reader that stops finding what it
measured must not pass for a metric that is merely absent. So a reader finds
its operations by what they are (category, shapes, JAX's own scope names),
never by a name the program chose, and where the trace is there and the
operation is not, the time is 0 and not None. A cell's file lists the
readers it uses; `BENCHMARK.json` repeats their constants for the driver,
and a test holds the two together.
"""
from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass
class Run:
    cell: Any               # benchmarks.cells.Cell
    program: Any            # benchmarks.runners.Program: memory, facts
    peaks: Any              # benchmarks.peaks.Peaks of the device; None on CPU
    cache_requests: int     # persistent-cache lookups since process start
    cache_hits: int
    compiles_in_window: int     # backend compilations inside the window
    step_ms: list           # host clock, each step synchronised
    trace: Any = None       # trace_reduce.Summary; None without a device trace
