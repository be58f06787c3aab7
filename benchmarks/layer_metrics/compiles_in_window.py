"""Backend compilations inside the measured window. Any makes the run
incorrect: a compile stalls the loop for seconds."""
LAYER = "compile_cache"
SOURCE = "program_counter"
UNIT = "compilations"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    return run.compiles_in_window
