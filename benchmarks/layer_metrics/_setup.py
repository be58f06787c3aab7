"""Set-up by layer: what the process spent building programs before its
first step, from the spans the program records of itself.

`paddle_tpu/observability/programs.py` records a span for every program
the process traces, lowers, compiles or loads from JAX's persistent cache,
and one around every `pl.pallas_call` the package builds (a Mosaic site);
`paddle_tpu.observability.stats()["programs"]` sums them. A reader runs in
the process that did the set-up, so the numbers are that run's own and
`Run` needs no field for them. Seconds are SELF seconds (a span's duration
less what its children cover), so the four kinds and the sites add up to
the time spent building programs and nothing is counted twice.

`metrics(run)` gives every number under the name its reader will have,
`None` for each where no program was recorded (a program from before the
recorder has no such key: everything reads `None` there, and nothing
raises). `TABLE` states what each measures as a reader's constants do; a
reader is then ten lines over the two, as `attention_ms_per_step.py` is over
`_stages.metrics`:

    from benchmarks.layer_metrics import _setup
    LAYER, MOVES, BETTER = _setup.LAYER, _setup.MOVES, _setup.BETTER
    SOURCE, UNIT = _setup.TABLE["setup_trace_s"][:2]
    def read(run):
        return _setup.metrics(run)["setup_trace_s"]

`benchmarks/setup_table.py` prints them beside the harness's phases until a
cell lists them (PERF.md section 7 says what that takes).
"""
from __future__ import annotations

import re

LAYER = "compile_cache"
MOVES = "setup_s"
BETTER = "lower"

SPAN, COUNTER = "program_span", "program_counter"
# name -> (SOURCE, UNIT, what it is)
TABLE = {
    "setup_trace_s": (SPAN, "s", "self time of every `trace` span: Python "
                      "that turns functions into jaxprs, which no cache "
                      "saves"),
    "setup_lower_s": (SPAN, "s", "jaxpr to MLIR, the Mosaic bodies' "
                      "lowering and serialisation inside it"),
    "setup_compile_s": (SPAN, "s", "XLA compiling what the persistent cache "
                        "did not have (0 in a warm run)"),
    "setup_cache_load_s": (SPAN, "s", "programs the persistent cache "
                           "answered: read, deserialise, load"),
    "setup_mosaic_sites": (COUNTER, "count", "`pl.pallas_call` sites built "
                           "while tracing"),
    "setup_mosaic_site_s": (SPAN, "s", "self time of the sites: building "
                            "the call and tracing the kernel body"),
    "setup_programs": (COUNTER, "count", "outermost programs lowered, "
                       "compiled or loaded"),
    "setup_retraced_functions": (COUNTER, "count", "functions traced more "
                                 "often than their Mosaic sites have "
                                 "distinct shapes"),
    "step_trace_s": (SPAN, "s", "the trainer's step: its trace, children "
                     "included"),
    "step_lower_s": (SPAN, "s", "the trainer's step: its lowering"),
    "step_compile_or_load_s": (SPAN, "s", "the trainer's step: XLA's "
                               "compile, or the cache's load"),
}

_MODULE = re.compile(r"^HloModule ([^\s,]+)")


def programs():
    """`stats()["programs"]` of this process; None where the program has no
    recorder or nothing registered it."""
    try:
        from paddle_tpu import observability
    except ImportError:
        return None
    return observability.stats().get("programs")


def step_name(run):
    """The `fun_name` of the program the window runs, from the executable's
    own text (`HloModule jit_step_fn, ...` -> `step_fn`); None where the
    run holds no executable's text."""
    hlo_text = getattr(run.program, "hlo_text", None)
    if not hlo_text:
        return None
    cached = getattr(run, "_setup_step_name", None)
    if cached is None:
        found = _MODULE.match(hlo_text())
        cached = run._setup_step_name = found.group(1) if found else ""
    return cached.removeprefix("jit_") or None


def metrics(run) -> dict:
    found = programs()
    if not found or not (found["totals"]["traced"]
                         or found["totals"]["lowered"]):
        return dict.fromkeys(TABLE)
    totals = found["totals"]
    out = {
        "setup_trace_s": totals["trace_s"],
        "setup_lower_s": totals["lower_s"],
        "setup_compile_s": totals["compile_s"],
        "setup_cache_load_s": totals["cache_load_s"],
        "setup_mosaic_sites": totals["mosaic_sites"],
        "setup_mosaic_site_s": totals["mosaic_site_s"],
        "setup_programs": totals["programs"],
        "setup_retraced_functions": totals["retraced_functions"],
        "step_trace_s": None, "step_lower_s": None,
        "step_compile_or_load_s": None}
    name = step_name(run)
    # the step is built once a run; were it built again, the first is the
    # one set-up waited for
    step = min((p for p in found["programs"]
                if p["fun_name"] == name and p["lower_s"]),
               key=lambda p: p["start_ns"], default=None)
    if step is not None:
        out["step_trace_s"] = step["trace_self_s"] + step["trace_children_s"]
        out["step_lower_s"] = step["lower_s"]
        out["step_compile_or_load_s"] = (step["compile_s"]
                                         + step["cache_load_s"])
    return out
