"""Roofline share of the latent-attention backward pass: the least time the
chip could take for the five products one backward requires, each at the
width it contracts or produces, over the measured time of the backward
kernels."""
from benchmarks.layer_metrics import _mla_flash

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s_chip"


def read(run):
    return _mla_flash.roofline_percent(run, "bwd")
