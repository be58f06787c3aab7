"""What the readers of a Gated DeltaNet hybrid share: the facts its runner
logs of the compiled rule under the program's linear-attention stage's
name (`runners/gdn_hybrid.linear_attention_of`: the layers of each kind,
the heads, the published widths d_k and d_v and the lane widths the
kernels take, the scan's trips, the tokens) and the least time the chip
could take for the layers' rule at the PUBLISHED widths
(benchmarks/flops_gdn_hybrid.py), so that the lanes a padding of the heads
wastes read as a lower share. The stage's device time is `_linear_attn`'s.
From a runner that logs no such facts (the Kimi cell's, whose facts carry
one `head_dim`), everything here reads None."""
from benchmarks import flops_gdn_hybrid
from benchmarks.layer_metrics import _linear_attn


def facts(run):
    """The runner's facts of the rule, where they are a Gated DeltaNet
    hybrid's; None elsewhere."""
    found = _linear_attn.facts(run)
    return found if found and "key_dim" in found else None


def rule_least_seconds(run):
    """The least time a step's rule could take: forward and backward of
    every linear layer on this chip's tokens, each pass at the larger of
    its required FLOPs over the peak and its required bytes over the
    bandwidth (remat's repeat and the padded lanes are not required)."""
    found = facts(run)
    if not found or run.peaks is None:
        return None
    least = 0.0
    for kind in ("fwd", "bwd"):
        flop, byte = flops_gdn_hybrid.rule_pass_cost(
            kind, tokens=found["tokens"], heads=found["heads"],
            key_dim=found["key_dim"], value_dim=found["value_dim"])
        least += flops_gdn_hybrid.least_seconds(flop, byte, run.peaks)[0]
    return least * found["layers"]["linear"]
