"""Device time in the matmul operations the step requires (convolution,
dot and the fusions that hold one, without those that remat repeats) over
device busy time. Less recomputation, faster kernels and less waiting all
raise it; `remat_share` is what was left out."""
LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "fraction"
BETTER = "higher"
MOVES = "mfu"


def read(run):
    if run.trace is None or not run.trace.busy_s:
        return None
    return run.trace.seconds(
        lambda op: op.category == "matmul" and not op.remat) \
        / run.trace.busy_s
