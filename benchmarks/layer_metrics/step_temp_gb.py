"""Temporary device memory of the step executable, from the compiler's
`memory_analysis()`: activations, remat buffers, logits. The part of
`hbm_peak_gb` that a remat policy or a fused loss head changes."""
LAYER = "compiled_trainer"
SOURCE = "program_counter"
UNIT = "GB"
BETTER = "lower"
MOVES = "hbm_peak_gb"


def read(run):
    memory = run.program.memory
    return memory["temp"] / 1e9 if memory else None
