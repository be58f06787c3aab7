"""Device time per step of the loss head: final norm, logits, float32
log-softmax, the pick and the mean, forward and backward, of the main pass
(`_stages.metrics`; a prediction module's pass through the same head stands
under the module's stage)."""
from benchmarks.layer_metrics import _stages

LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    found = _stages.metrics(run)
    return None if found is None else found["loss_head_ms_per_step"]
