"""Plain float32 references, one file per family, written from the papers.

Each takes the program's parameter tree (per-layer arrays stacked on a
leading layer axis) and one sequence, and returns the summed negative
log-likelihood over the labelled positions and their count. No kernels, no
remat, no batching, no mixed precision; `benchmarks/check.py` runs them under
`jax.default_matmul_precision("highest")`.
"""
