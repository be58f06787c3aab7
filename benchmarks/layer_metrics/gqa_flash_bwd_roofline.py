"""Roofline share of the grouped-query flash backward kernel: the least
time the chip could take for the five products one backward requires of the
window layers and the full one together, on the pairs each kind's mask
keeps, with dK and dV written once per K/V head, over the measured time of
the backward calls."""
from benchmarks.layer_metrics import _gqa_flash

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s_chip"


def read(run):
    return _gqa_flash.roofline_percent(run, "bwd")
