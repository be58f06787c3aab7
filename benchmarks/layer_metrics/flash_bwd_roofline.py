"""Roofline share of the flash backward pass: the least time the chip could
take for what one backward REQUIRES (five s x s x d products, whatever
kernels share them; today a dKV and a dQ kernel execute seven) over the
measured time of the backward kernels."""
from benchmarks.layer_metrics import _flash

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s_chip"


def read(run):
    return _flash.roofline_percent(run, "bwd")
