"""Device time of the grouped-query flash kernels per step, forward (and
its remat) and backward, window and full layers together, mean over the
chips."""
from benchmarks.layer_metrics import _gqa_flash

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    found = _gqa_flash.passes(run)
    if found is None:
        return None
    return 1e3 * sum(s for s, _ in found.values()) / run.trace.steps
