"""What the readers of the sparse family share: device time of one of the
stages that family adds to `paddle_tpu/models/stages.py` (imported through
`_stages.vocabulary()`, never spelt here), and the routed experts' grouped
products, under the experts stage or told by shape. On a program whose vocabulary lacks the
stage (one from before it) everything here reads None."""
from benchmarks import flops_mla_moe
from benchmarks.layer_metrics import _stages


def stage_name(attribute: str):
    return getattr(_stages.vocabulary(), attribute, None)


def stage_ms_per_step(run, attribute: str):
    """ms per step under the stage `stages.<attribute>`, every direction;
    None without a trace, the stage's name, or any instruction under it."""
    name, table = stage_name(attribute), _stages.seconds(run)
    if name is None or table is None:
        return None
    found = [t for (stage, _), t in table.items() if stage == name]
    if not found:
        return None
    return 1e3 * sum(found) / run.trace.steps


def grouped_products(run):
    """Names of the Mosaic calls that take or give one stack of the held
    experts' matrices, [held, hidden, width] or [held, width, hidden]:
    grouped products told by shape. XLA's own grouped-matmul kernels (what
    `jax.lax.ragged_dot` becomes on a TPU) carry XLA's `op_name` and so no
    stage of the program's; this finds them all the same."""
    moe = run.program.facts.get("moe")
    if run.trace is None or not moe:
        return set()
    s = moe["shapes"]
    stacks = {(s["held"], s["hidden"], s["expert_ffn"]),
              (s["held"], s["expert_ffn"], s["hidden"])}
    return {name for name, op in run.trace.ops.items()
            if op.category == "mosaic" and name in run.trace.op_s
            and stacks & (set(op.operands) | set(op.results))}


def experts_ms_per_step(run):
    """The experts stage and the grouped products that stand under no
    stage, ms per step; None where the stage reads None."""
    under_stage = stage_ms_per_step(run, "EXPERTS")
    if under_stage is None:
        return None
    name, where = stage_name("EXPERTS"), _stages.placed(run)
    outside = sum(run.trace.op_s[op] for op in grouped_products(run)
                  if where[op][0] != name)
    return under_stage + 1e3 * outside / run.trace.steps


def experts_product_seconds(run):
    """Seconds in the traced window of the routed experts' products: the
    matmul- or Mosaic-category instructions under the experts stage and
    the grouped products found by shape, forward, remat and backward; None
    where `stage_ms_per_step` is, or where there is none."""
    name, where = stage_name("EXPERTS"), _stages.placed(run)
    if name is None or where is None:
        return None
    found = {op for op, (stage, _) in where.items()
             if stage == name and op in run.trace.ops
             and run.trace.ops[op].category in ("matmul", "mosaic")}
    found |= grouped_products(run)
    return sum(run.trace.op_s[op] for op in found) if found else None


def experts_least_seconds(run):
    """The least time a step's grouped products could take: forward and
    backward of every sparse layer on the pairs balanced routing sends to
    the held experts (remat's repeat is not required)."""
    moe = run.program.facts.get("moe")
    if not moe or run.peaks is None:
        return None
    s = moe["shapes"]
    pairs = moe["tokens"] * flops_mla_moe.pairs_per_token(
        k=s["k"], held=s["held"], router_outputs=s["router_outputs"])
    least = 0.0
    for kind in ("fwd", "bwd"):
        flop, byte = flops_mla_moe.grouped_pass_cost(
            kind, pairs=pairs, held=s["held"], hidden=s["hidden"],
            width=s["expert_ffn"])
        least += flops_mla_moe.least_seconds(flop, byte, run.peaks)[0]
    return least * moe["layers"]
