"""Device time per step of the routed experts: what stands under their
stage (sort, gather, activation, the weighted combine) and the grouped
products, which as XLA's own kernels may stand under no stage and are then
told by shape; forward, remat and backward."""
from benchmarks.layer_metrics import _moe

LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    return _moe.experts_ms_per_step(run)
