"""Device time of the grouped-query flash kernels per step, forward (and
its remat) and backward, every kind of layer whatever its query heads,
mean over the chips (`_mixed_gqa_flash.passes`)."""
from benchmarks.layer_metrics import _mixed_gqa_flash

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    return _mixed_gqa_flash.ms_per_step(run)
