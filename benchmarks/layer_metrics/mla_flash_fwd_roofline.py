"""Roofline share of the latent-attention forward kernel: the least time the
chip could take for one call at widths 192 / 128 (flops_mla_moe.py) over
its measured time. The forward that remat repeats is a call like any other."""
from benchmarks.layer_metrics import _mla_flash

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s_chip"


def read(run):
    return _mla_flash.roofline_percent(run, "fwd")
