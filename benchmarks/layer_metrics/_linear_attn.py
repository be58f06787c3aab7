"""What the readers of a linear-attention layer share: the device time under
the program's linear-attention stage (imported through
`_stages.vocabulary()`, never spelt here) and the least time the chip could
take for the layers' rule (benchmarks/flops_kda_mla_moe.py). On a program
whose vocabulary lacks the stage (one from before it) or whose runner logs
no facts under the stage's name, everything here reads None.

The compiler leaves some fusions of the rule's elementwise chains without an
`op_name` on the fusion instruction itself (clones of loop fusions: 91 ms of
the layer's 916 a step on a v5e, PERF.md section 6, PR 37), and
`_stages.place` files an instruction without one under no stage. The
instructions INSIDE such a fusion keep theirs, so this file follows the
fusion's `calls=` and takes it for the stage's when every stage named in
there is this one."""
import functools
import re

from benchmarks import flops_kda_mla_moe
from benchmarks.layer_metrics import _moe, _stages

STAGE = "LINEAR_ATTN"
_COMPUTATION = re.compile(r'^(?:ENTRY )?%?([\w.\-]+) \(.*\{$')
_NAMED = re.compile(r'\bop_name="([^"]*)"')
_CALLER = re.compile(
    r'^\s+(?:ROOT )?%?([\w.\-]+) = .*?\bcalls=%?([\w.\-]+)')


def facts(run):
    """What the runner logged of the compiled rule, under the stage's own
    name in `Program.facts`; None from a runner or a program without."""
    return run.program.facts.get(_moe.stage_name(STAGE))


@functools.lru_cache(maxsize=1)
def stages_inside(hlo_text: str) -> dict:
    """{instruction without an `op_name` that `calls=` a computation: the
    set of stages its called computation's instructions stand under}."""
    named_in, callers, inside = {}, {}, None
    for line in hlo_text.splitlines():
        start = _COMPUTATION.match(line)
        if start:
            inside = named_in.setdefault(start.group(1), set())
            continue
        named = _NAMED.search(line)
        if named:
            if inside is not None:
                inside.add(named.group(1))
            continue
        caller = _CALLER.match(line)
        if caller:
            callers[caller.group(1)] = caller.group(2)
    vocabulary = _stages.vocabulary()
    return {name: {_stages.place(op_name, vocabulary)[0]
                   for op_name in named_in.get(called, ())} - {None}
            for name, called in callers.items()}


def unnamed_seconds(run, name: str) -> float:
    """Seconds in the traced window of the instructions that ran, carry no
    `op_name` and call a computation whose every staged instruction stands
    under the stage `name`."""
    found = stages_inside(run.program.hlo_text())
    return sum(s for op, s in run.trace.op_s.items()
               if found.get(op) == {name})


def ms_per_step(run):
    """ms per step under the stage, every direction, the fusions it is
    filed under by what they call included; None without a trace, the
    stage's name, or any instruction under it."""
    named = _moe.stage_ms_per_step(run, STAGE)
    if named is None:
        return None
    return named + 1e3 * unnamed_seconds(
        run, _moe.stage_name(STAGE)) / run.trace.steps


def rule_least_seconds(run):
    """The least time a step's rule could take: forward and backward of
    every linear layer on this chip's tokens, each pass at the larger of
    its required FLOPs over the peak and its required bytes over the
    bandwidth (remat's repeat is not required)."""
    found = facts(run)
    if not found or run.peaks is None:
        return None
    least = 0.0
    for kind in ("fwd", "bwd"):
        flop, byte = flops_kda_mla_moe.rule_pass_cost(
            kind, tokens=found["tokens"], heads=found["heads"],
            head_dim=found["head_dim"])
        least += flops_kda_mla_moe.least_seconds(flop, byte, run.peaks)[0]
    return least * found["layers"]["kda"]
