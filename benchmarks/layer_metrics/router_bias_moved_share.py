"""Share of the (layer, expert) selection biases that the first step on the
check batch moved from their initial value, over every router of the step
(the trunk's sparse layers and the prediction module's): what the runner
read back from the program's state. Every expert that drew more or fewer
pairs than the mean moves, so it is near 1; 0 means the update was lost.
None from a runner that reads no biases."""
LAYER = "model_block"
SOURCE = "program_counter"
UNIT = "fraction"
BETTER = "higher"
MOVES = "tokens_per_s_chip"


def read(run):
    moe = run.program.facts.get("moe") or {}
    return moe.get("bias_moved_share")
