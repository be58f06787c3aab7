"""Device time per step of everything after the gradients: every
instruction under the program's optimizer stage (`_stages.metrics`), which
in a cell with a held share of the optimizer state (`opt_share`) is the
slicing of each gradient to the held share, AdamW on the held float32
masters and moments, and the write of the held slice into each bfloat16
parameter. None on a program whose vocabulary lacks the stage or a run
without a trace."""
from benchmarks.layer_metrics import _stages

LAYER = "compiled_trainer"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    return (_stages.metrics(run) or {}).get("optimizer_ms_per_step")
