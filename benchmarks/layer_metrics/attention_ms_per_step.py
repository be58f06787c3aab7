"""Device time per step of the attention layer between its projections,
whatever computes it: the trunk's `attn_core` stage, every direction
(`_stages.metrics`; a prediction module's attention stands under the
module's stage)."""
from benchmarks.layer_metrics import _stages

LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    found = _stages.metrics(run)
    return None if found is None else found["attention_ms_per_step"]
