"""What decides `correct` besides finite losses and a window without
compilation: the program against its plain float32 reference, and no
silent fallback from the flash kernels where a configuration states them."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.common import adamw_first_update


def reference_losses(ref, params, seqs, config: dict):
    """(loss on the two sequences, loss after one AdamW update or None).

    `params` are the program's initial parameters in float32, `seqs` the two
    check sequences (tokens [2,s], labels [2,s]). One sequence at a time, so
    that float32 activations without remat fit beside the parameters.
    The mean over labelled positions is sum / count over both sequences,
    and the gradient of the mean is the summed gradient / count."""
    tokens, labels = seqs
    nll = functools.partial(ref.nll, config=config)
    with jax.default_matmul_precision("highest"):
        loss_fn = jax.jit(nll)
        if config["reference_check"] == "loss":
            sums = [loss_fn(params, t, l) for t, l in zip(tokens, labels)]
            return _mean(sums), None

        grad_fn = jax.jit(jax.value_and_grad(nll, has_aux=True))
        sums, total = [], None
        for t, l in zip(tokens, labels):
            (nll, count), grads = grad_fn(params, t, l)
            sums.append((nll, count))
            total = grads if total is None else _add(total, grads)
        count = sum(int(c) for _, c in sums)
        update = jax.jit(
            lambda p, g: adamw_first_update(
                p, jax.tree_util.tree_map(lambda x: x / count, g),
                ref.decayed(p), config["optimizer"],
                jnp.dtype(config["dtype"])),
            donate_argnums=(0,))
        loss0 = _mean(sums)
        params = update(params, total)
        return loss0, _mean([loss_fn(params, t, l)
                             for t, l in zip(tokens, labels)])


@jax.jit
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def _mean(sums) -> float:
    return float(sum(float(n) for n, _ in sums)
                 / sum(int(c) for _, c in sums))


def compare_losses(program, reference, tolerance: dict) -> list:
    """Problems found, empty when the program agrees with the reference.
    `program` and `reference` are (loss0, loss1 or None)."""
    problems = []
    p0, p1 = program
    r0, r1 = reference
    if not abs(p0 - r0) <= tolerance["loss"] * abs(r0):
        problems.append(f"step-0 loss {p0} vs reference {r0}: off by more "
                        f"than {tolerance['loss']} relative")
    if r1 is not None:
        drop_p, drop_r = p0 - p1, r0 - r1
        if not (drop_r > 0 and
                abs(drop_p - drop_r) <= tolerance["drop"] * drop_r):
            problems.append(
                f"loss fell by {drop_p} after the first update vs the "
                f"reference's {drop_r}: off by more than "
                f"{tolerance['drop']} relative")
    return problems


def flash_fallback_problems(lowered_text: str, seq: int,
                            batch_heads) -> list:
    """Problems if the lowered step fell back from the flash kernels to
    einsum attention: no Mosaic call, or a [B,H,S,S] score tensor.
    `batch_heads` holds the (batch, heads) pairs to look for: the global
    one, and one chip's, since inside a shard_map the shapes are a chip's.
    Only a configuration that states flash is held to this: how a
    configuration that states nothing computes attention is the program's
    to change, and the reference comparison guards what it computes."""
    problems = []
    if "tpu_custom_call" not in lowered_text:
        problems.append("no tpu_custom_call in the lowered step")
    found = sorted(s for s in (f"{b}x{h}x{seq}x{seq}x"
                               for b, h in batch_heads) if s in lowered_text)
    if found:
        problems.append(f"einsum attention is lowered: tensor<{found[0]}..>")
    return problems


def finite(losses) -> int:
    """How many of the losses read in the window are not finite."""
    return int((~np.isfinite(np.asarray(losses, np.float64))).sum())
