"""Which Mosaic calls are latent-attention kernels, told from shapes alone
(as `_flash.py` tells the one-width ones): q and k have as many elements as
one chip's [batch * heads, seq, head_dim], v as [batch * heads, seq,
v_head_dim] (the runner's `facts["attention"]`), or as q where the program
pads v to the query's width. With three such operands a call is a forward;
with more it belongs to the backward, and three such results make one
backward pass. Required work is counted at the two published widths either
way, so a padded program shows its waste."""
import math

from benchmarks import flops_mla_moe


def passes(run):
    """{"fwd" | "bwd": (seconds, passes)} in the traced window, mean over
    the chips; None without a device trace or a two-width attention."""
    attention = run.program.facts.get("attention")
    if run.trace is None or not attention or "v_head_dim" not in attention:
        return None
    rows = attention["batch"] * attention["heads"] * attention["seq"]
    sizes = {rows * attention["head_dim"], rows * attention["v_head_dim"]}
    found = {"fwd": [0.0, 0.0], "bwd": [0.0, 0.0]}
    for name, op in run.trace.ops.items():
        if op.category != "mosaic" or name not in run.trace.op_s:
            continue
        taken = sum(math.prod(dims) in sizes for dims in op.operands)
        given = sum(math.prod(dims) in sizes for dims in op.results)
        if taken < 3:
            continue            # some other kernel: a grouped product
        kind = "fwd" if taken == 3 else "bwd"
        found[kind][0] += run.trace.op_s[name]
        found[kind][1] += run.trace.op_calls[name] * (
            1 if kind == "fwd" else given / 3)
    return {k: tuple(v) for k, v in found.items()}


def roofline_percent(run, kind: str):
    """Least time by required FLOPs and bytes over measured time, in %."""
    found = passes(run)
    if found is None or run.peaks is None:
        return None
    seconds, n = found[kind]
    if not (seconds and n):
        return None
    attention = run.program.facts["attention"]
    flop, byte = flops_mla_moe.mla_flash_pass_cost(
        kind, bh=attention["batch"] * attention["heads"],
        seq=attention["seq"], d_qk=attention["head_dim"],
        d_v=attention["v_head_dim"], causal=attention["causal"])
    least, _ = flops_mla_moe.least_seconds(flop, byte, run.peaks)
    return 100.0 * least * n / seconds
