"""Roofline share of the grouped-query flash forward kernel where the kinds
of layer have query heads of their own: the least time the chip could take
for each kind's forward calls at its own heads, K/V heads and window (K and
V read once per K/V head, flops_window_gqa_moe.py), summed, over their
measured time. The forward that remat repeats is a call like any other."""
from benchmarks.layer_metrics import _mixed_gqa_flash

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s_chip"


def read(run):
    return _mixed_gqa_flash.roofline_percent(run, "fwd")
