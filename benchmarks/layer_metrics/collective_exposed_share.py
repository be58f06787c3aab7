"""Collective time during which no compute runs on that chip, over the
step's time: what overlap or fewer bytes could win back. Worst chip."""
LAYER = "sharded_dispatch"
SOURCE = "device_trace"
UNIT = "fraction"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    if run.trace is None or run.cell.chips == 1 or not run.trace.window_s:
        return None
    return run.trace.collective_exposed_s / run.trace.window_s
