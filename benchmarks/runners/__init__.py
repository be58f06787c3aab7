"""Runners: one module per family of system under test, found by the name in
the configuration's file (`"runner": "<module>"`). Everything that is one
family's own lives in its runner: what a batch is and how the ring is made
from the seed, how the step is built, the comparison with the plain
reference, what the lowered step must hold, what a step consumes and
whether one executable stands for it. `run.py` sees only what comes back:

    set_up(cell, seed, devices, phases) -> Program

`cell` is `cells.Cell`, `devices` what JAX found (TPU chips, or the CPU in a
--cpu-dry-run, where a runner checks nothing that only a chip has), `phases`
takes `phases.end("<name>")` after each part of set-up, for the log. A new
family is a new module here (with its reference under `reference/` and, if
its inputs are of a new kind, its generator), and edits none that is there.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable


@dataclasses.dataclass
class Program:
    """What the harness runs and measures, warm: every shape the window
    uses has run once when `set_up` returns."""
    step: Callable          # step(state, *batch) -> (state, loss); enqueues
    state: Any
    ring: list              # the batches, as the host holds them
    put: Callable           # batch -> the arguments `step` takes
    unit: str               # what a step consumes: "tokens", "images"
    units_per_step: int     # over all the cell's chips
    flops_per_unit: float   # required, `benchmarks/flops.py`
    problems: list          # why the run is not correct; empty when it is
    memory: dict | None = None      # `memory_of` the one executable the
    #                                 window runs; None where there is none
    hlo_text: Callable | None = None    # () -> that executable's HLO text
    facts: dict = dataclasses.field(default_factory=dict)   # for the log
    #                       and the readers, e.g. one chip's attention shapes


def memory_of(compiled) -> dict:
    """Bytes per device of one executable, from the compiler."""
    m = compiled.memory_analysis()
    out = {"argument": m.argument_size_in_bytes,
           "output": m.output_size_in_bytes,
           "temp": m.temp_size_in_bytes,
           "alias": m.alias_size_in_bytes}
    out["total"] = out["argument"] + out["output"] + out["temp"] \
        - out["alias"]
    return out
