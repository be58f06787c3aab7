"""Device time per step under the router's stage (expert scores in float32,
the top-k, the routing weights), forward, remat and backward."""
from benchmarks.layer_metrics import _moe

LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    return _moe.stage_ms_per_step(run, "ROUTER")
