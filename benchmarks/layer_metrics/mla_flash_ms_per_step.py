"""Device time of the latent-attention flash kernels per step, forward and
backward, mean over the chips."""
from benchmarks.layer_metrics import _mla_flash

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    found = _mla_flash.passes(run)
    if found is None:
        return None
    return 1e3 * sum(s for s, _ in found.values()) / run.trace.steps
