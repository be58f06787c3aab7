"""Roofline share of the grouped-query flash backward kernel where the
kinds of layer have query heads of their own: the least time the chip could
take for the five products one backward requires of each kind's layers at
its own heads, K/V heads and window, dK and dV written once per K/V head,
over the measured time of the backward calls."""
from benchmarks.layer_metrics import _mixed_gqa_flash

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s_chip"


def read(run):
    return _mixed_gqa_flash.roofline_percent(run, "bwd")
