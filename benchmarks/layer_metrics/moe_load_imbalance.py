"""The fullest held expert's (token, expert) pairs over the balanced share
T k / E, on the check batch, worst sparse layer: what the router's counters
say of how unevenly the grouped products are loaded (1 is balanced)."""
LAYER = "model_block"
SOURCE = "program_counter"
UNIT = "ratio"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    moe = run.program.facts.get("moe")
    return moe["fullest_over_balanced"] if moe else None
