"""Roofline share of the Gated DeltaNet layers' rule: the least time the
chip could take for the rule of every linear layer, forward and backward,
by the RECURRENCE's required work at the PUBLISHED widths (7 d_k d_v a head
and token forward, 96 x 192 here) and one read of q, k, v, the per-head
log-decay and the write strength and one write of o a pass
(benchmarks/flops_gdn_hybrid.py), over `gated_delta_ms_per_step`'s time.
The stage holds more than the rule (convolutions, norms, gates), what
remat repeats, and the lanes the padding of the heads to 128 and 256
wastes, so the share cannot pass 100 % and a kernel at the native widths
reads higher on the same work."""
from benchmarks.layer_metrics import _gated_delta, gated_delta_ms_per_step

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s_chip"


def read(run):
    least = _gated_delta.rule_least_seconds(run)
    ms = gated_delta_ms_per_step.read(run)
    if not (least and ms):
        return None
    return 100.0 * least / (ms / 1e3)
