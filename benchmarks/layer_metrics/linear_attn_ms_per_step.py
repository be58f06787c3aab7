"""Device time per step of the linear-attention layers between their
projections and their output projection: every instruction under the
program's linear-attention stage (short convolutions, SiLU, the head norms,
softplus and the log-decays, the chunked delta rule, the gated head norm),
forward, remat and backward, and every fusion the compiler left without a
name of its own whose instructions stand under that stage and no other
(`_linear_attn.unnamed_seconds`: 91 ms of 916 a step on a v5e, which
`stage_table.py`'s row of the stage leaves under no stage). The softmax
layers of the same model stand under `attn_core` and are not here."""
from benchmarks.layer_metrics import _linear_attn

LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    return _linear_attn.ms_per_step(run)
