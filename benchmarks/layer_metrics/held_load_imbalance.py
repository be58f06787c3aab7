"""The fullest held expert's (token, expert) pairs over the balanced share
T k / E, on the ring's first batch with the parameters the window starts
from, worst layer: what the routers' counters say of how unevenly the grouped
products are loaded (1 is balanced). `moe_load_imbalance`'s read of a routed
family's `facts["moe"]`, under a name a cell may list that `BENCHMARK.json`
does not yet list it for."""
from benchmarks.layer_metrics.moe_load_imbalance import read  # noqa: F401

LAYER = "model_block"
SOURCE = "program_counter"
UNIT = "ratio"
BETTER = "lower"
MOVES = "tokens_per_s_chip"
