"""Which Mosaic calls are grouped-query attention kernels, told from shapes
alone (as `_flash.py` tells the one-head-count ones): q has as many elements
as one chip's [batch, seq, heads, head_dim], k and v as [batch, seq,
kv_heads, head_dim] (the runner's `facts["attention"]`, which has
`kv_heads`; without it everything here reads None). A call that takes two
operands of k's size is an attention kernel: with one of q's size it is a
forward, with more (the output's gradient) it belongs to the backward.
Window and full layers have the same shapes, so a step's calls are read
together and held to the required work of the layers of both kinds
together (`flops_window_gqa_moe.gqa_flash_pass_cost`, K and V read once per
K/V head): a kernel that visits the tiles outside a window, or loads a K/V
head once per query head, shows as a fall."""
import math

from benchmarks import flops_window_gqa_moe

WINDOW = "sliding_attention"


def passes(run):
    """{"fwd" | "bwd": (seconds, calls)} in the traced window, mean over
    the chips; None without a device trace or a grouped-query attention."""
    attention = run.program.facts.get("attention")
    if run.trace is None or not attention or "kv_heads" not in attention:
        return None
    rows = attention["batch"] * attention["seq"] * attention["head_dim"]
    wide, narrow = rows * attention["heads"], rows * attention["kv_heads"]
    found = {"fwd": [0.0, 0.0], "bwd": [0.0, 0.0]}
    for name, op in run.trace.ops.items():
        if op.category != "mosaic" or name not in run.trace.op_s:
            continue
        sizes = [math.prod(dims) for dims in op.operands]
        if sizes.count(narrow) != 2 or not sizes.count(wide):
            continue            # some other kernel: a grouped product
        kind = "fwd" if sizes.count(wide) == 1 else "bwd"
        found[kind][0] += run.trace.op_s[name]
        found[kind][1] += run.trace.op_calls[name]
    return {k: tuple(v) for k, v in found.items()}


def roofline_percent(run, kind: str):
    """Least time by required FLOPs and bytes of the layers of every kind
    over the measured time of their calls, in %. A pass over the layers is
    one call a layer; the calls found are a whole number of such passes
    (forward: the pass and its remat)."""
    found = passes(run)
    if found is None or run.peaks is None:
        return None
    seconds, calls = found[kind]
    if not (seconds and calls):
        return None
    attention = run.program.facts["attention"]
    least = 0.0
    for layer_kind, count in attention["layers"].items():
        flop, byte = flops_window_gqa_moe.gqa_flash_pass_cost(
            kind, batch=attention["batch"], heads=attention["heads"],
            kv_heads=attention["kv_heads"], seq=attention["seq"],
            head_dim=attention["head_dim"],
            window=attention["window"] if layer_kind == WINDOW else None)
        least += count * flops_window_gqa_moe.least_seconds(
            flop, byte, run.peaks)[0]
    return 100.0 * least * calls / sum(attention["layers"].values()) \
        / seconds
