"""Trips of the Gated DeltaNet layers' scan over chunks, read from the
COMPILED step's `while` loops under the linear-attention stage that carry
the rule's float32 state at the lane widths the kernels take, [rows, H,
128, 256] (`scan_steps` of the facts `runners/gdn_hybrid.py` logs under
the stage's name): sequence / chunk, 64 at 4,096 tokens and a chunk of 64.
A chunk that changed shows here before it shows in the rate, a scan that
became a loop over tokens reads the sequence length, and a state that is
no longer float32 or no longer of that shape is not found (the runner then
says the run is incorrect). None from a runner that logs no such fact."""
from benchmarks.layer_metrics import _gated_delta

LAYER = "model_block"
SOURCE = "program_counter"
UNIT = "trips"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    return (_gated_delta.facts(run) or {}).get("scan_steps")
