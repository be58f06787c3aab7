"""Roofline share of the routed experts' grouped products: the least time
the chip could take for the three products forward and the six backward of
every sparse layer, on the pairs balanced routing sends to the held experts
(benchmarks/flops_mla_moe.py), over the measured time of the matmul- or
Mosaic-category instructions under the experts stage and of the grouped
products told by shape (`_moe.grouped_products`). What remat repeats is
in the time and not in the requirement."""
from benchmarks.layer_metrics import _moe

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s_chip"


def read(run):
    least, seconds = _moe.experts_least_seconds(run), \
        _moe.experts_product_seconds(run)
    if not (least and seconds):
        return None
    return 100.0 * least * run.trace.steps / seconds
