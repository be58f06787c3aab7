"""Device time per step of a gated softmax layer's output gate: every
instruction under the program's gate stage (imported through
`_stages.vocabulary()`, never spelt here: the gate's product from the
block's normed input, its sigmoid, the multiply), forward, remat and
backward, and every fusion the compiler left without a name of its own
whose instructions stand under that stage and no other
(`_linear_attn.unnamed_seconds`). A multiply that XLA fuses into the output
projection goes with that fusion's stage (PERF.md section 3). None on a
program whose vocabulary lacks the stage."""
from benchmarks.layer_metrics import _linear_attn, _moe

LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"
STAGE = "ATTN_GATE"


def read(run):
    named = _moe.stage_ms_per_step(run, STAGE)
    if named is None:
        return None
    return named + 1e3 * _linear_attn.unnamed_seconds(
        run, _moe.stage_name(STAGE)) / run.trace.steps
