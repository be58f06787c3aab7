"""Roofline share of the flash forward kernel: the least time the chip
could take for one call's shapes (benchmarks/flops.py) over its measured
time. The forward that remat repeats is a call like any other."""
from benchmarks.layer_metrics import _flash

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s_chip"


def read(run):
    return _flash.roofline_percent(run, "fwd")
