"""Device time per step of the multi-token-prediction module: every
instruction under the program's module stage (its two norms, the projection
of [hidden ; next embedding], its layer, its final norm, its pass through
the shared head and its loss), forward, remat and backward. The stage is
opened outside the stages the module's layer opens itself and
`_stages.place` goes by the first stage of a path, so the module is whole
here and absent from the trunk's stage metrics."""
from benchmarks.layer_metrics import _moe

LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    return _moe.stage_ms_per_step(run, "MTP")
