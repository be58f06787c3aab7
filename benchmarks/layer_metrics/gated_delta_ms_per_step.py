"""Device time per step of the Gated DeltaNet layers between their
projections and their output projection: every instruction under the
program's linear-attention stage (the short convolutions, SiLU, the head
norms, softplus and the per-head log-decays, the chunked delta rule with
its padding to whole lanes, the SiLU-gated head norm), forward, remat and
backward, and every fusion the compiler left without a name of its own
whose instructions stand under that stage and no other
(`_linear_attn.ms_per_step`, which the Kimi cell's `linear_attn_ms_per_step`
reads too). The full-attention layers of the same model stand under
`attn_core` and are not here."""
from benchmarks.layer_metrics import _gated_delta, _linear_attn

LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    if _gated_delta.facts(run) is None:
        return None
    return _linear_attn.ms_per_step(run)
