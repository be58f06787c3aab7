"""Device time per step of the routed feed-forward, whatever computes it:
what stands under the router's stage (the product, softmax, top-k, weights
and the balance term) and the experts' (sort, gather, activation, the
weighted combine), and XLA's grouped matmuls, which stand under no stage and
are told by shape; forward, remat and backward."""
from benchmarks.layer_metrics import _moe

LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    router = _moe.stage_ms_per_step(run, "ROUTER")
    experts = _moe.experts_ms_per_step(run)
    if router is None or experts is None:
        return None
    return router + experts
