"""The generator of language-model training traffic: batches of token ids
and labels from a seed. (`cells.load_traffic` reads a mix's file and says
what every mix has; a family whose inputs are of another kind brings its
generator with its runner.) What this one reads of a mix:

    batch, seq    rows and tokens per row of one global batch
    labels        "next_token": labels are the ids shifted by one (causal LM)
                  "masked": -100 everywhere except a seeded `mask_share` of
                  each row's positions, which carry a target id (masked LM)

Ids are uniform over the vocabulary: the step does the same arithmetic on any
ids, so the distribution changes no time and no operation fails. The same
seed gives the same bytes.
"""
from __future__ import annotations

import numpy as np

IGNORE = -100           # a label the masked-LM loss skips


def _labelled_per_row(traffic: dict) -> int:
    """Positions of a row that carry a label: the same count in every row,
    so that every step does the same work."""
    if traffic["labels"] == "next_token":
        return traffic["seq"]
    if traffic["labels"] == "masked":
        return max(1, round(traffic["mask_share"] * traffic["seq"]))
    raise ValueError(f"traffic {traffic.get('name')!r}: unknown labels "
                     f"{traffic['labels']!r}")


def _batch(rng, traffic: dict, vocab: int, rows: int):
    seq = traffic["seq"]
    ids = rng.integers(0, vocab, (rows, seq + 1), dtype=np.int32)
    tokens = ids[:, :-1]
    if traffic["labels"] == "next_token":
        return tokens, ids[:, 1:]
    picked = np.argsort(rng.random((rows, seq)),
                        axis=1)[:, :_labelled_per_row(traffic)]
    labels = np.full((rows, seq), IGNORE, np.int32)
    np.put_along_axis(labels, picked,
                      np.take_along_axis(ids[:, 1:], picked, axis=1), axis=1)
    return tokens, labels


def make_ring(traffic: dict, vocab: int, seed: int) -> list:
    """`ring` distinct (tokens, labels) int32 batches of [batch, seq]."""
    rng = np.random.default_rng([seed, 0])
    return [tuple(np.ascontiguousarray(a) for a in
                  _batch(rng, traffic, vocab, traffic["batch"]))
            for _ in range(traffic["ring"])]


def make_check_batch(traffic: dict, vocab: int, seed: int):
    """Two seeded sequences, and the same two tiled to the cell's batch.

    The program steps on the tiled batch (the shapes it was compiled for);
    the float32 reference computes on the two sequences. Mean losses and
    gradients of the two are the same numbers."""
    batch = traffic["batch"]
    if batch % 2:
        raise ValueError(f"batch {batch} cannot be tiled from two sequences")
    rng = np.random.default_rng([seed, 1])
    two = _batch(rng, traffic, vocab, 2)
    tiled = tuple(np.ascontiguousarray(np.tile(a, (batch // 2, 1)))
                  for a in two)
    return two, tiled


def labelled_share(traffic: dict) -> float:
    """Share of positions that carry a label (what the loss head must do)."""
    return _labelled_per_row(traffic) / traffic["seq"]
