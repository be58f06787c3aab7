"""Roofline share of the grouped-query flash forward kernel: the least time
the chip could take for the forward calls of the window layers and the full
one together, on the pairs each kind's mask keeps and with K and V read once
per K/V head (flops_window_gqa_moe.py), over their measured time. The
forward that remat repeats is a call like any other; a lost window shows as
a fall."""
from benchmarks.layer_metrics import _gqa_flash

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s_chip"


def read(run):
    return _gqa_flash.roofline_percent(run, "fwd")
