"""Device time of everything `jax.checkpoint` repeats for the backward
(JAX's own scope `rematted_computation`: matmuls, attention kernels, the
rest of the block's forward) over device busy time. What a remat policy
trades against `hbm_peak_gb`."""
LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "fraction"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    if run.trace is None or not run.trace.busy_s:
        return None
    return run.trace.seconds(lambda op: op.remat) / run.trace.busy_s
