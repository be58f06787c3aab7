"""Device time per step of attention computed through [B,H,S,S] score
tensors: every instruction that holds an array of one chip's (batch, heads,
seq, seq), whatever fusion it sits in (the score and context einsums with
the softmax XLA fuses into them; forward, remat and backward). What a flash
path would replace; 0 where the step holds no such tensor."""
LAYER = "model_block"
SOURCE = "device_trace"
UNIT = "ms"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    attention = run.program.facts.get("attention")
    if run.trace is None or not attention:
        return None
    scores = "{batch},{heads},{seq},{seq}".format(**attention)
    return 1e3 * run.trace.seconds(lambda op: scores in op.shapes) \
        / run.trace.steps
