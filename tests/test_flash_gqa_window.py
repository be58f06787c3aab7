"""The flash kernels with fewer K/V heads than query heads and with a
window (ops/pallas/flash_attention.py), in the Pallas interpreter, against
`blocks.attention`'s einsum branch, and the tile counts a window leaves."""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import with_flag

from paddle_tpu.models import blocks

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def _qkv(seq, heads, kv_heads, d, seed):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (1, seq, heads, d), jnp.float32),
            jax.random.normal(keys[1], (1, seq, kv_heads, d), jnp.float32),
            jax.random.normal(keys[2], (1, seq, kv_heads, d), jnp.float32),
            jax.random.normal(keys[3], (1, seq, heads * d), jnp.float32))


def _both_branches(q, k, v, w, window):
    """(value, gradients) of the kernel branch and of the einsum branch."""
    scale = 1.0 / math.sqrt(q.shape[-1])

    def run(flash):
        out, pull = jax.vjp(lambda q, k, v: blocks.attention(
            q, k, v, causal=True, scale=scale, flash=flash, window=window),
            q, k, v)
        return out, pull(w)

    with with_flag("FLAGS_flash_interpret", True):
        assert blocks.use_flash_kernel(True, q.shape[1])
        kernel = run(True)
    return kernel, run(False)


# the window against the sequence: longer than it, equal to it, a quarter of
# it (one 512 x 512 tile that both diagonals cross); then 128-wide tiles
# with the two diagonals in one tile (96) and in tiles of their own (256)
@pytest.mark.parametrize("seq, window", [
    (128, 256), (128, 128), (512, 128), (384, 96), (384, 256), (128, None),
    (384, None)], ids=["shorter", "equal", "four_times", "crossed",
                       "edge_tiles", "causal_128", "causal_384"])
@pytest.mark.parametrize("heads, kv_heads", [(2, 2), (4, 2), (2, 1)],
                         ids=["mha", "two_a_kv_head", "one_kv_head"])
def test_kernels_equal_the_einsum_branch(heads, kv_heads, seq, window):
    """Forward and all three gradients; dK and dV come back with the K/V
    heads' own shape, each the sum over its query heads."""
    q, k, v, w = _qkv(seq, heads, kv_heads, 128, seed=seq + heads)
    (got, got_grads), (want, want_grads) = _both_branches(q, k, v, w, window)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for a, b in zip(got_grads, want_grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


def test_fewer_kv_heads_at_a_narrow_head_take_the_head_major_entry():
    """At head_dim 64 a K/V head's columns are half a lane block, which no
    index map names: `head_group` says None and the head-major kernels
    take the K/V head from the leading axis."""
    assert fa.head_group(4, 64, 64, 256, 256, jnp.float32, 2) is None
    assert fa.head_group(4, 64, 64, 256, 256, jnp.float32) == 2
    assert fa.head_group(4, 128, 128, 256, 256, jnp.float32, 2) == 1
    q, k, v, w = _qkv(256, 4, 2, 64, seed=5)
    (got, got_grads), (want, want_grads) = _both_branches(q, k, v, w, 100)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


def test_a_window_needs_the_causal_mask_and_kv_heads_must_divide():
    q = jnp.zeros((1, 128, 256))
    with pytest.raises(ValueError, match="causal"):
        fa.mha_seq_major(q, q, q, 2, causal=False, window=64)
    with pytest.raises(ValueError, match="divide"):
        fa.mha_seq_major(q, q[..., :128], q[..., :128], 3, kv_heads=2)


def _brute_force(sq, sk, bq, bk, window):
    """(masked, whole, skipped) from every (query, key) pair."""
    i = np.arange(sq)[:, None] + (sk - sq)
    j = np.arange(sk)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    tiles = seen.reshape(sq // bq, bq, sk // bk, bk)
    some, every = tiles.any((1, 3)), tiles.all((1, 3))
    return int((some & ~every).sum()), int(every.sum()), int((~some).sum())


@pytest.mark.parametrize("sq, sk, bq, bk, window", [
    (4096, 4096, 512, 512, 1024),       # the Mellum cell: 21 of 64 visited
    (4096, 4096, 512, 512, None),
    (4096, 4096, 512, 256, 1024),
    (2048, 2048, 512, 512, 300),        # both diagonals in one tile
    (1024, 1024, 128, 128, 128),
    (1024, 1024, 128, 128, 1),          # a query sees itself alone
    (512, 1024, 128, 128, 256),         # sq < sk: offset 512
    (1024, 1024, 256, 128, 4096),       # a window longer than the sequence
])
def test_tile_counts_with_a_window_against_a_brute_force_count(sq, sk, bq, bk,
                                                               window):
    got = fa.tile_counts(sq, sk, bq, bk, True, sk - sq, window)
    masked, whole, skipped = _brute_force(sq, sk, bq, bk, window)
    assert sum(got) == (sq // bq) * (sk // bk)
    # no visible pair is skipped, no tile with a hidden pair runs unmasked;
    # a tile the loops mask may be whole (the bounds are per query block)
    assert got[2] <= skipped and got[1] <= whole
    assert got[0] + got[1] == masked + whole + (skipped - got[2])
    if (sq, bq, bk, window) == (4096, 512, 512, 1024):
        assert got == (14, 7, 43) == (masked, whole, skipped)
    if window is None:
        assert got == (8, 28, 28) == (masked, whole, skipped)


def test_the_backward_visits_the_tiles_the_forward_does():
    """The backward's loop bounds are by query block per key block: taken
    over all the key blocks they name the same tiles as the forward's."""
    for sq, bq, bk, window in ((2048, 512, 512, 1024), (1024, 128, 128, 300),
                               (1024, 128, 256, 128), (2048, 512, 256, 600)):
        nqb, nkb = sq // bq, sq // bk
        fwd = np.zeros((nqb, nkb), int)
        first, lower, full, seen = (np.asarray(n) for n in
                                    fa._visible_key_blocks(
            jnp.arange(nqb), bq, bk, nkb, True, 0, window))
        for i in range(nqb):
            fwd[i, first[i]:lower[i]] = 1
            fwd[i, lower[i]:full[i]] = 2
            fwd[i, full[i]:seen[i]] = 1
        bwd = np.zeros((nqb, nkb), int)
        for kj in range(nkb):
            start = np.clip(kj * bk // bq, 0, nqb)
            whole = np.clip(((kj + 1) * bk - 1 + bq - 1) // bq, start, nqb)
            reach = kj * bk + window
            end = np.clip((reach + bk - 2) // bq + 1, start, nqb)
            whole = np.clip(whole, start, end)
            inside = np.clip((reach - bq) // bq + 1, whole, end)
            bwd[start:whole, kj] = 1
            bwd[whole:inside, kj] = 2
            bwd[inside:end, kj] = 1
        masked, whole_tiles, skipped = _brute_force(sq, sq, bq, bk, window)
        # both visit every tile with a visible pair, and run unmasked only
        # tiles that are whole
        for visited in (fwd, bwd):
            assert (visited > 0).sum() >= masked + whole_tiles
            assert (visited == 2).sum() <= whole_tiles
        assert ((fwd > 0) == (bwd > 0)).all()


def test_vmem_accounting_takes_the_two_head_counts():
    bf16 = jnp.bfloat16
    alone = fa.vmem_footprint(4096, 4096, 128, bf16)
    shared = fa.vmem_footprint(4096, 4096, 128, bf16, rep=8)
    # the forward is the same; the backward adds a K/V head's float32 dK
    # and dV and, to fit them, halves its key block
    assert shared["fwd"] == alone["fwd"]
    assert fa._bwd_block_k(4096, 4096, 128, 128, bf16, 1, 8) == 256
    assert fa._bwd_block_k(4096, 4096, 128, 128, bf16) == 512
    assert alone["bwd"] < shared["bwd"] < fa.SCOPED_VMEM_BYTES
    assert fa.max_seq(128, bf16, True, rep=8) == 4096
    assert fa.max_seq(128, bf16, True) == 6144
    assert fa.max_seq(128, bf16, False, rep=8) == fa.max_seq(128, bf16, False)
    assert fa.head_group(32, 128, 128, 4096, 4096, bf16, 4) == 1
    assert fa.head_group(32, 128, 128, 4608, 4608, bf16, 4) is None
