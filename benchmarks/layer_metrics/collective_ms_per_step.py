"""Time the collectives take per step on the worst chip: what blocks the
core plus what an asynchronous pair hides behind compute. An all-gather
that XLA fused into a matmul is not in it (see trace_reduce.py)."""
LAYER = "sharded_dispatch"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    if run.trace is None or run.cell.chips == 1:
        return None
    return 1e3 * run.trace.collective_s / run.trace.steps
