"""Device time per step under the residual-mix stage: the norm of the
flattened streams, the three projections, Sinkhorn, read-in and write-back
of every sub-layer, forward, remat and backward."""
from benchmarks.layer_metrics import _moe

LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    return _moe.stage_ms_per_step(run, "RESIDUAL_MIX")
