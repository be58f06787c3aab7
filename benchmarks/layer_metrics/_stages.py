"""Device time by stage of the train step and by direction, from the scopes
the program opens itself, and the seven per-layer metrics that follow.

`paddle_tpu/models/stages.py` names seven stages, and the trainer and the
models open one `jax.named_scope` for each, so the `op_name` of every
compiled instruction carries its stage as a component of a jaxpr path:

    jit(step)/jvp()/while/body/closed_call/attn_core/dot_general
    jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/mul
    .../checkpoint/rematted_computation/attn_qkv/dot_general
    jit(step)/jvp(loss_head)/reduce_sum        (opened outside the scan)
    jit(step)/transpose(jvp(loss_head))/mul
    jit(step)/optimizer/mul

`trace_reduce.Op.label` keeps a path's last three components only, so this
file makes its own small pass over the HLO text for the whole `op_name`. A
fusion may carry several paths joined by `;`: it goes to the stage of its
first scoped path. Direction is JAX's own writing, decided in this order so
that the four partition the step: update if the stage is the optimizer's,
remat under `rematted_computation`, backward under `transpose(`, forward
otherwise. An instruction without an `op_name` (or that the HLO text does
not hold) is forward and of no stage.

This is the only code of the benchmark that goes by names the program
chose, and it imports the names from the program: a stage whose scope is
gone reads None and not 0, and a program from before the scopes has no such
module, so everything reads None there. `metrics` gives the numbers under
the names their readers will have; `benchmarks/stage_table.py` prints them
(PERF.md section 7 says what listing them in the cells takes).
"""
from __future__ import annotations

import collections
import functools
import re

from benchmarks.trace_reduce import REMAT_SCOPE

FORWARD, REMAT, BACKWARD, UPDATE = "forward", "remat", "backward", "update"
DIRECTIONS = (FORWARD, REMAT, BACKWARD, UPDATE)
TRANSPOSE = "transpose("                # JAX's name for the backward pass

_INSTRUCTION = re.compile(
    r'^\s+(?:ROOT )?%?([\w.\-]+) = .*?\bop_name="([^"]*)"', re.MULTILINE)
# a scope opened outside the scan lands inside JAX's wrapper
_WRAPPED = ("{}", "jvp({})", "transpose(jvp({}))")


def vocabulary():
    """The program's module of stage names; None from a program that has
    none (one from before the scopes)."""
    try:
        from paddle_tpu.models import stages
    except ImportError:
        return None
    return stages


def op_names(hlo_text: str) -> dict:
    """{instruction name: its whole `op_name`} for every instruction of
    every computation that has one."""
    return dict(_INSTRUCTION.findall(hlo_text))


@functools.lru_cache(maxsize=None)
def _components(names: tuple) -> dict:
    """{path component: the stage it names}."""
    return {form.format(s): s for s in names for form in _WRAPPED}


def place(op_name: str, stages) -> tuple:
    """(stage or None, direction) of one instruction's `op_name`."""
    known = _components(stages.ALL)
    paths = op_name.split(";")
    stage, path = None, paths[0]
    for candidate in paths:
        found = [known[c] for c in candidate.split("/") if c in known]
        if found:
            stage, path = found[0], candidate
            break
    if stage == stages.OPTIMIZER:
        return stage, UPDATE
    if REMAT_SCOPE in path:
        return stage, REMAT
    if TRANSPOSE in path:
        return stage, BACKWARD
    return stage, FORWARD


def placed(run):
    """{instruction name: (stage or None, direction)} for every instruction
    that ran in the traced window; None without a device trace, the step's
    HLO text or the program's stage names. Worked out once per run."""
    stages = vocabulary()
    hlo_text = getattr(run.program, "hlo_text", None)
    if run.trace is None or not hlo_text or stages is None:
        return None
    cached = getattr(run, "_stages_placed", None)
    if cached is None or cached[0] is not run.trace:
        names = op_names(hlo_text())
        cached = run._stages_placed = (run.trace, {
            name: place(names.get(name, ""), stages)
            for name in run.trace.op_s})
    return cached[1]


def seconds(run):
    """{(stage or None, direction): self seconds in the traced window, mean
    over the chips}; None where `placed` is."""
    where = placed(run)
    if where is None:
        return None
    table = collections.defaultdict(float)
    for name, s in run.trace.op_s.items():
        table[where[name]] += s
    return dict(table)


def metrics(run):
    """The seven per-layer metrics of a traced run, ms per step unless
    said; None where `placed` is. One that names stages is None where no
    instruction that ran carries any of them: the program lost the span.

        attention_ms_per_step   the attention layer between its projections,
                                whatever computes it (`attn_core`)
        loss_head_ms_per_step   final norm or MLM transform, logits,
                                log-softmax or vocabulary-parallel CE
        optimizer_ms_per_step   everything after the gradients
        forward_ms_per_step     every instruction, of any stage or none,
                                that is neither backward, remat nor update
        backward_ms_per_step    backward and what remat repeats for it; the
                                three add up to the device's busy time
        dense_ms_per_step       `attn_qkv` + `attn_out` + `mlp`: norms,
                                projections, activation, residual adds
        unscoped_share          under no scope over busy time, a fraction:
                                the scan's own slices and stacking
    """
    table, s = seconds(run), vocabulary()
    if table is None:
        return None

    def ms(stages=None, directions=DIRECTIONS):
        if stages and not any(stage in stages for stage, _ in table):
            return None
        return 1e3 * sum(
            t for (stage, direction), t in table.items()
            if (stages is None or stage in stages)
            and direction in directions) / run.trace.steps

    return {
        "attention_ms_per_step": ms((s.ATTN_CORE,)),
        "loss_head_ms_per_step": ms((s.LOSS_HEAD,)),
        "optimizer_ms_per_step": ms((s.OPTIMIZER,)),
        "forward_ms_per_step": ms(directions=(FORWARD,)),
        "backward_ms_per_step": ms(directions=(BACKWARD, REMAT)),
        "dense_ms_per_step": ms((s.ATTN_QKV, s.ATTN_OUT, s.MLP)),
        "unscoped_share": sum(t for (stage, _), t in table.items()
                              if stage is None) / run.trace.busy_s
        if run.trace.busy_s else None}
