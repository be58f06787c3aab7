"""What the flash readers share: which Mosaic calls are attention kernels,
told from shapes alone. An attention kernel takes q, k and v, each with as
many elements as one chip's [batch * heads, seq, head_dim] (the runner's
`facts["attention"]`), in whatever layout. With exactly those three it is a
forward call; with more (the output's gradient, the output) it belongs to
the backward, and each result of that size is one of dQ, dK, dV: three of
them make one backward pass, however many kernels share the work."""
import math

from benchmarks import flops


def passes(run):
    """{"fwd" | "bwd": (seconds, passes)} in the traced window, mean over
    the chips; None without a device trace or an attention to look for."""
    attention = run.program.facts.get("attention")
    if run.trace is None or not attention:
        return None
    size = (attention["batch"] * attention["heads"] * attention["seq"]
            * attention["head_dim"])
    found = {"fwd": [0.0, 0.0], "bwd": [0.0, 0.0]}
    for name, op in run.trace.ops.items():
        if op.category != "mosaic" or name not in run.trace.op_s:
            continue
        taken = sum(math.prod(dims) == size for dims in op.operands)
        given = sum(math.prod(dims) == size for dims in op.results)
        if taken < 3:
            continue            # some other Pallas kernel
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
    flop, byte = flops.flash_pass_cost(
        kind, bh=attention["batch"] * attention["heads"],
        seq=attention["seq"], head_dim=attention["head_dim"],
        causal=attention["causal"])
    least, _ = flops.least_seconds(flop, byte, run.peaks)
    return 100.0 * least * n / seconds
