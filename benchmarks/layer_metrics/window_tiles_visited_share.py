"""Score tiles the step's attention visits over the tiles the same layers
would visit were they all causal, from the kernel module's `tile_counts` on
the shapes, blocks and window the runner compiled
(`facts["attention"]["tiles"]`): 1.0 = the window is gone; 0.6875 at
4,096 tokens, a 1,024-key window in three layers of four, 512 x 512 tiles."""
LAYER = "kernels"
SOURCE = "program_counter"
UNIT = "fraction"
BETTER = "lower"
MOVES = "tokens_per_s_chip"


def read(run):
    attention = run.program.facts.get("attention") or {}
    tiles = attention.get("tiles")
    if not tiles:
        return None
    visited = sum(count * sum(tiles[kind][:2])
                  for kind, count in attention["layers"].items())
    causal = sum(attention["layers"].values()) * sum(tiles["causal"][:2])
    return visited / causal
