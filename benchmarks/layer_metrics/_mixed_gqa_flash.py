"""Which Mosaic calls are grouped-query attention kernels when the kinds of
layer have query heads of their own, told from shapes alone, and the least
time their layers could take. The runner's `facts["attention"]` names, for
each kind, its query heads, K/V heads, window and layers (`kinds`); facts
that give one count of heads for every kind (`heads`, `kv_heads`, `window`
and `layers` by kind: the Mellum cell's) are read as if each kind had that
count, so this file reads such a cell as `_gqa_flash.py` does. Kinds whose
q and k have the same sizes cannot be told apart: their calls are read
together and held to their layers' required work together
(`flops_window_gqa_moe.gqa_flash_pass_cost` at each kind's own heads and
window). A call with two operands of a group's k size and one of its q size
is its forward, with more (the output's gradient) its backward. Without
`kv_heads` or `kinds` everything here reads None."""
import math

from benchmarks import flops_window_gqa_moe

WINDOW = "sliding_attention"


def kinds(attention: dict):
    """{kind: {"heads", "kv_heads", "window", "layers"}}; None where the
    facts name no K/V heads."""
    if "kinds" in attention:
        return attention["kinds"]
    if "kv_heads" not in attention or "layers" not in attention:
        return None
    return {kind: {"heads": attention["heads"],
                   "kv_heads": attention["kv_heads"],
                   "window": attention["window"] if kind == WINDOW else None,
                   "layers": count}
            for kind, count in attention["layers"].items()}


def passes(run):
    """{(q size, k size): {"fwd" | "bwd": (seconds, calls)}} in the traced
    window, mean over the chips, with the kinds of each group; None
    without a device trace or a grouped-query attention."""
    attention = run.program.facts.get("attention")
    found_kinds = kinds(attention) if attention else None
    if run.trace is None or not found_kinds:
        return None
    rows = attention["batch"] * attention["seq"] * attention["head_dim"]
    groups = {}
    for kind, shape in sorted(found_kinds.items()):
        groups.setdefault((rows * shape["heads"], rows * shape["kv_heads"]),
                          []).append(kind)
    found = {key: {"fwd": [0.0, 0.0], "bwd": [0.0, 0.0]} for key in groups}
    for name, op in run.trace.ops.items():
        if op.category != "mosaic" or name not in run.trace.op_s:
            continue
        sizes = [math.prod(dims) for dims in op.operands]
        for wide, narrow in groups:
            if sizes.count(narrow) == 2 and sizes.count(wide):
                direction = "fwd" if sizes.count(wide) == 1 else "bwd"
                found[wide, narrow][direction][0] += run.trace.op_s[name]
                found[wide, narrow][direction][1] += \
                    run.trace.op_calls[name]
                break
    return {key: dict({d: tuple(v) for d, v in found[key].items()},
                      kinds=groups[key]) for key in groups}


def ms_per_step(run):
    found = passes(run)
    if found is None:
        return None
    return 1e3 * sum(group[d][0] for group in found.values()
                     for d in ("fwd", "bwd")) / run.trace.steps


def roofline_percent(run, direction: str):
    """Least time by required FLOPs and bytes of each group's layers over
    the measured time of their calls, in %; None where a group has no
    call. A pass over a group's layers is one call a layer; the calls
    found are a whole number of such passes (forward: the pass and its
    remat)."""
    found = passes(run)
    if found is None or run.peaks is None:
        return None
    attention = run.program.facts["attention"]
    shapes = kinds(attention)

    def least(kind):
        shape = shapes[kind]
        cost = flops_window_gqa_moe.gqa_flash_pass_cost(
            direction, batch=attention["batch"], heads=shape["heads"],
            kv_heads=shape["kv_heads"], seq=attention["seq"],
            head_dim=attention["head_dim"], window=shape["window"])
        return shape["layers"] * flops_window_gqa_moe.least_seconds(
            *cost, run.peaks)[0]

    required = seconds = 0.0
    for group in found.values():
        spent, calls = group[direction]
        if not (spent and calls):
            return None
        layers = sum(shapes[kind]["layers"] for kind in group["kinds"])
        required += sum(map(least, group["kinds"])) * calls / layers
        seconds += spent
    return 100.0 * required / seconds
