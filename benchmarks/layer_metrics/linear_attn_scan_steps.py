"""Trips of the chunked rule's scan over chunks, read from the COMPILED
step's `while` loops under the linear-attention stage that carry the rule's
state (`scan_steps` of the facts the runner logs under the stage's name,
runners/kda_mla_moe.py): sequence /
chunk, 32 at 2,048 tokens and a chunk of 64. A chunk that changed shows
here before it shows in the rate, and a scan that became a loop over tokens
reads the sequence length. None from a runner that logs no such fact."""
from benchmarks.layer_metrics import _linear_attn

LAYER = "model_block"
SOURCE = "program_counter"
UNIT = "trips"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    return (_linear_attn.facts(run) or {}).get("scan_steps")
