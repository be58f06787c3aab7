"""Roofline share of the gated delta rule: the least time the chip could
take for the rule of every linear-attention layer, forward and backward, by
the RECURRENCE's required work (7 d_k d_v a head and token forward) and one
read of q, k, v, the log-decays and the write strength and one write of o a
pass (benchmarks/flops_kda_mla_moe.py), over the measured time of every
instruction under the program's linear-attention stage
(`linear_attn_ms_per_step`'s time, unnamed fusions and all). The stage holds
more than the rule (convolutions, norms, gates) and what remat repeats, so
the share cannot pass 100 %; a chunked form on XLA's products reads a few
percent, and a kernel for the rule is read on the same work."""
from benchmarks.layer_metrics import _linear_attn

LAYER = "kernels"
SOURCE = "device_trace"
UNIT = "%"
BETTER = "higher"
MOVES = "tokens_per_s_chip"


def read(run):
    least, ms = _linear_attn.rule_least_seconds(run), \
        _linear_attn.ms_per_step(run)
    if not (least and ms):
        return None
    return 100.0 * least / (ms / 1e3)
