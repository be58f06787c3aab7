"""The gated delta rule with a decay per key channel, in its chunked form.

One head keeps a state S [d_k, d_v] and, token by token (Kimi Delta
Attention, arXiv:2510.26692; the delta rule of Schlag et al. 2021 with
Yang et al. 2024's gate made a vector):

    S' = Diag(exp g_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T        o_t = S_t^T q_t

`chunk_gated_delta_rule` computes the same o without a loop over tokens.
Inside a chunk of C tokens, with G_i the log-decays summed from the chunk's
first token to token i, the writes u_j = beta_j (v_j - S'_j^T k_j) solve a
unit lower triangular system,

    (I + A) U = beta V - (beta K exp G) S_0,
    A[i, j] = beta_i sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])     (j < i)

so U = U~ - W S_0 with T = (I + A)^-1, U~ = T (beta V), W = T (beta K exp G),
none of which knows the state; and with B[i, j] the same sum over q_i and
k_j for j <= i,

    O = (Q exp G) S_0 + B U
    S_C = Diag(exp G_C) S_0 + (K exp(G_C - G))^T U.

Everything but those last three lines is computed for all the chunks at
once, as batched products (`_chunk_terms`); a `lax.scan` over the S / C
chunks carries S_0 in float32 and does the three.

The decay is per CHANNEL, so A and B are not a product of two matrices
scaled by a row's and a column's scalar: exp(G_i - G_j) has to go inside
the sum over c, and exp(G_i) * exp(-G_j) overflows float32 once a chunk's
summed log-decay passes -88. Here every `exp` is of a difference of summed
logs that is <= 0. Sub-blocks of `sub` tokens on the diagonal are computed
pairwise (the [sub, sub, d_k] tensor of exp(G_i - G_j), j <= i, summed over
c: `_diagonal_blocks`, recomputed in the backward pass and never kept).
Below the diagonal the chunk is halved again and again (`_below`): in each
half-pair the later half's rows meet the earlier half's columns through the
summed log at the later half's FIRST row, r, as exp(G_i - r) * exp(r - G_j),
both factors <= 1 because the sums only fall. T is built on the same
halving: a sub-block's inverse by forward substitution (`_unit_lower
_inverse`, on [sub, sub, batch] arrays so that the batch and not a width of
16 lies along the lanes), and [[P, 0], [R, Q]]^-1 = [[P^-1, 0],
[-Q^-1 R P^-1, Q^-1]] above it, which on the whole chunk is T <- T - T R T
with R the level's blocks of A.

Two ways to compute the chunk terms, one algorithm, chosen by the operands'
shapes alone (`ops/pallas/delta_rule.taken` on `lane_widths`): head widths
that are multiples of 128 lanes, or that become such by a zero padding of
at most a third of the padded width (`lane_widths`: d_k 96 -> 128 and d_v
192 -> 256 for Gated DeltaNet's heads), at the chunk of `CHUNK` = 4 `SUB`
take the Mosaic kernels of `ops/pallas/delta_rule.py`, which read q, k, v
and g where the layer wrote them, `[B, S, H d]`, hold everything above in
VMEM (there T comes from all the levels' blocks at once, in three
dependent products) and write the six terms once, the chunk axis leading;
any other shape (narrow heads, chunks of 8 to 32) takes `_chunk_terms`,
XLA's own passes, which is also what the kernels are tested against. The
scan over chunks is the same `lax.scan` behind both.

Precision, in both: the log-decays, their sums, every exp, T and the state
are float32; the products' operands are rounded to q's dtype (bfloat16 in
the trainer) and accumulate in float32.

The padding is exact and is done inside the checkpointed group, so neither
direction keeps a padded array: zero key channels add nothing to k.k or
q.k, so A, B and the writes are unchanged, the padded rows and columns of S
start at 0 and stay 0 (their k and v are 0), and the output's padded
columns are sliced away. The scan then carries the padded state.

The log-decay is a head AND key channel's, or one a head (Gated DeltaNet,
Yang et al. 2024, arXiv:2412.06464): g [B, S, H] is the same value in every
channel, broadcast over d_k inside the group; equal channels give the
scalar form exactly. Nothing here bounds beta: write strengths up to 2 (I -
beta k k^T with a negative eigenvalue, Grazzi et al. 2024, arXiv:2411.12537)
go through the same algebra, T = (I + A)^-1 being unit lower triangular
whatever A holds.

The backward pass keeps a group's five inputs and computes the rest again:
rows of the batch are taken in groups of at most `GROUP_TOKENS` tokens, one
after another, each under `jax.checkpoint`. Behind the kernels a group's
backward pass runs the forward kernel and the scan again (the scan's
transpose, JAX's, reads the six terms and a state a trip), then the
backward kernel, which forms G, the pairwise exponentials, T, W and U once
more in VMEM from the five inputs and writes the five gradients once: no
float32 intermediate of the chunk terms is an array in either direction.
Where a layer forms the five inputs with the Mosaic kernels of
`ops/pallas/kda_elementwise.py`, `models/delta_layer.linear_attention`
checkpoints those kernels and the rule together, so what the layer's
backward pass keeps is the projections the inputs are formed from; the
inputs are formed once more and held only while the rule's gradient is
taken.
Behind the XLA form the backward pass is JAX's transpose of `_chunk_terms`,
whose float32 intermediates, the size of several q, are what the groups
bound there. One row of 2,048 tokens a group is still the choice behind the
kernels: what a group's backward pass holds is now the six terms and the
states of the scan's transpose, and on a v5e the rule at 8 rows of the Kimi
cell's widths ran 17.5 / 47.0 ms forward / gradient with one row a group
and 18.0 / 48.9 with two, whose step compiles to the same 13.96 GB; four
rows a group compile to 15.34 GB and eight to 17.14 (PERF.md section 6,
PR 38; with the XLA form one row was the fastest of 1, 2, 4 and 8 and the
only one under 15 GB, PR 37).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .pallas import delta_rule

CHUNK = 64      # tokens a trip of the scan over chunks: the family's
SUB = 16        # the pairwise sub-block; a chunk is SUB times a power of two
GROUP_TOKENS = 2048     # rows of the batch taken together: this many tokens
                        # (measured, the docstring's last paragraph)
_HIGHEST = jax.lax.Precision.HIGHEST


def lane_widths(d_k: int, d_v: int):
    """(d_k, d_v) as the kernels would take them: each rounded up to whole
    lane tiles of `delta_rule.LANES` where the zeros are at most a third of
    the rounded width, else as it is (a narrow head is no kernel's)."""
    def rounded(d):
        up = -(-d // delta_rule.LANES) * delta_rule.LANES
        return up if 2 * up <= 3 * d else d

    return rounded(d_k), rounded(d_v)


@jax.checkpoint
def _diagonal_blocks(q, k, G):
    """q, k, G [..., sub, d_k] (float32) -> (sum_c q_i k_j e_ij, sum_c k_i
    k_j e_ij) [..., sub, sub] with e_ij = exp(G_i - G_j) for j <= i and 0
    above the diagonal. Checkpointed: the backward pass forms e again from
    G rather than keep a [..., sub, sub, d_k] tensor."""
    sub = G.shape[-2]
    lower = np.tril(np.ones((sub, sub), bool))[..., None]
    e = jnp.exp(jnp.where(lower, G[..., :, None, :] - G[..., None, :, :],
                          -jnp.inf))
    ke = k[..., None, :, :] * e
    return (q[..., :, None, :] * ke).sum(-1), (k[..., :, None, :] * ke).sum(-1)


def _unit_lower_inverse(a):
    """(I + a)^-1 for a [n, n, batch] strictly lower triangular in its first
    two axes, row by row: t_0 = e_0, t_i = e_i - sum_{j<i} a[i, j] t_j.
    Elementwise over the batch, which is the minor axis."""
    n = a.shape[0]
    eye = np.eye(n, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0][:, None], a.shape[1:])]
    for i in range(1, n):
        rows.append(eye[i][:, None] - (
            a[i, :i, None, :] * jnp.stack(rows)).sum(0))
    return jnp.stack(rows)


def _on_the_diagonal(blocks):
    """[..., n, sub, sub] -> [..., n sub, n sub], the blocks on the
    diagonal and zero elsewhere."""
    n, sub = blocks.shape[-3], blocks.shape[-1]
    placed = blocks[..., :, :, None, :] \
        * np.eye(n, dtype=blocks.dtype)[:, None, :, None]
    return placed.reshape(blocks.shape[:-3] + (n * sub, n * sub))


def _below(q, k, G, size: int, dtype):
    """One level of the halving, on the whole chunk: q, k, G [..., C, d_k]
    -> (sum_c q_i k_j exp(G_i - G_j), the same of k_i k_j) [..., C, C] for
    i in the later half and j in the earlier half of the same run of 2
    `size` tokens, zero elsewhere. Through the summed log at the later
    half's first row, so that both exponents are <= 0; the products'
    operands are rounded to `dtype`."""
    C = G.shape[-2]
    at = np.arange(C)         # masks are constants: numpy's, not traced
    later = ((at // size) % 2 == 1)[:, None]
    # each half's summed log at its first row; a later half's rows take
    # their own, an earlier half's columns the NEXT half's
    first = G.reshape(G.shape[:-2] + (C // size, size, -1))[..., 0, :]
    r = jnp.where(later, jnp.repeat(first, size, -2),
                  jnp.repeat(jnp.roll(first, -1, -2), size, -2))
    rows = jnp.exp(jnp.where(later, G - r, -jnp.inf))
    cols = (k * jnp.exp(jnp.where(later, -jnp.inf, r - G))).astype(dtype)
    same = (at[:, None] // (2 * size)) == (at[None, :] // (2 * size))

    def product(x):
        return jnp.einsum("...ic,...jc->...ij", (x * rows).astype(dtype),
                          cols, preferred_element_type=jnp.float32) * same

    return product(q), product(k)


def _chunk_terms(q, k, v, g, beta, sub: int):
    """q, k [B, H, N, C, d_k], v [B, H, N, C, d_v], g like k (float32),
    beta [B, H, N, C] (float32) -> what the scan over chunks reads, in q's
    dtype but `keep`: W, U~, Q exp G, K exp(G_C - G), keep = exp G_C
    [B, H, N, d_k] and B [B, H, N, C, C] of the module docstring."""
    dtype, f32 = q.dtype, jnp.float32
    lead, C = q.shape[:3], q.shape[3]
    qf, kf = q.astype(f32), k.astype(f32)
    G = jnp.cumsum(g, -2)

    def blocks(x):      # [B, H, N, C, d] -> [B, H, N, C / sub, sub, d]
        return x.reshape(lead + (C // sub, sub) + x.shape[4:])

    qk, kk = _diagonal_blocks(blocks(qf), blocks(kf), blocks(G))
    # the sub-blocks' inverses with the batch along the lanes
    a = blocks(beta)[..., None] * kk * np.tril(
        np.ones((sub, sub), np.float32), -1)
    T = jnp.moveaxis(_unit_lower_inverse(
        jnp.moveaxis(a, (-2, -1), (0, 1)).reshape(sub, sub, -1)).reshape(
            (sub, sub) + a.shape[:-2]), (0, 1), (-2, -1))
    B, T = _on_the_diagonal(qk), _on_the_diagonal(T)
    size = sub
    while size < C:
        below_qk, below_kk = _below(qf, kf, G, size, dtype)
        R = beta[..., None] * below_kk
        B = B + below_qk
        T = T - jnp.einsum("...ij,...jk,...kl->...il", T, R, T,
                           precision=_HIGHEST)
        size *= 2
    B, T = B.astype(dtype), T.astype(dtype)
    last, decayed = G[..., -1:, :], jnp.exp(G)
    W = jnp.einsum("...ij,...jc->...ic", T,
                   (kf * beta[..., None] * decayed).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    U = jnp.einsum("...ij,...jc->...ic", T,
                   (v.astype(f32) * beta[..., None]).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    return (W, U, (qf * decayed).astype(dtype),
            (kf * jnp.exp(last - G)).astype(dtype),
            jnp.exp(last[..., 0, :]), B)


@functools.partial(jax.checkpoint, static_argnums=(5, 6, 7))
def _rule(q, k, v, g, beta, chunk: int, sub: int, kernels: bool):
    """`chunk_gated_delta_rule` on a group of rows, the chunk terms by the
    Mosaic kernels where `kernels`, else by `_chunk_terms`. Checkpointed:
    the backward pass keeps a group's inputs and computes the rest again,
    one group at a time. (Behind the kernels that is one more forward
    kernel: the scan's transpose reads the six terms and a state a trip,
    140 MB a row of 2,048 tokens at 32 heads of 128, and eight rows of
    them kept across a layer's backward pass compiled the Kimi cell's step
    to 16.87 GB against 13.96, sandbox compile, PR 38.)"""
    b, s, h, dk = q.shape
    dv, dtype, f32 = v.shape[-1], q.dtype, jnp.float32
    g, beta = g.astype(f32), beta.astype(f32)
    width = dv
    if kernels:
        dk, dv = lane_widths(dk, dv)
        q, k = (_widened(x, dk) for x in (q, k))
        v = _widened(v, dv)
    if g.ndim == 3:         # one log-decay a head, the same in every channel
        g = jnp.broadcast_to(g[..., None], (b, s, h, dk))
    else:
        g = _widened(g, dk)

    def chunks(x):          # [B, S, H, ...] -> [B, H, N, C, ...]
        x = x.reshape((b, s // chunk, chunk) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    if kernels:     # q, k, v, g as [B, S, H d]; the chunk axis comes leading
        terms = delta_rule.chunk_terms(
            *(x.reshape(b, s, -1) for x in (q, k, v, g)), beta, h)
    else:
        terms = tuple(jnp.moveaxis(x, 2, 0) for x in _chunk_terms(
            chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta), sub))

    def step(state, xs):
        W, U, q_in, k_out, keep, B = xs
        low = state.astype(dtype)
        u = (U - jnp.einsum("bhic,bhcv->bhiv", W, low,
                            preferred_element_type=f32)).astype(dtype)
        o = jnp.einsum("bhic,bhcv->bhiv", q_in, low,
                       preferred_element_type=f32) \
            + jnp.einsum("bhij,bhjv->bhiv", B, u,
                         preferred_element_type=f32)
        state = keep[..., None] * state + jnp.einsum(
            "bhic,bhiv->bhcv", k_out, u, preferred_element_type=f32)
        return state, o.astype(dtype)

    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, dv), f32), terms)
    # [N, B, H, C, d_v] -> [B, S, H, d_v]
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, s, h, dv)
    return o if width == dv else o[..., :width]


def _widened(x, width: int):
    """x [..., d] with zeros appended to `width` channels (as it is where
    it has them)."""
    extra = width - x.shape[-1]
    return x if not extra else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, extra)])


def chunk_gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK):
    """q, k [B, S, H, d_k], v [B, S, H, d_v], g [B, S, H, d_k] the
    log-decays (<= 0) or [B, S, H], one a head, beta [B, S, H] -> o [B, S,
    H, d_v] in q's dtype: the recurrence of the module docstring from S_0 =
    0 in every row of the batch, in chunks of `chunk` tokens. q and k come
    as the rule takes them (normalised, q scaled)."""
    b, s = q.shape[:2]
    sub = min(SUB, chunk)
    if s % chunk:
        raise ValueError(
            f"chunk_gated_delta_rule: the sequence length {s} is not a "
            f"multiple of the chunk {chunk}")
    if chunk % sub or (chunk // sub) & (chunk // sub - 1):
        raise ValueError(
            f"chunk_gated_delta_rule: a chunk is {sub} times a power of "
            f"two, got {chunk}")
    rows = max(n for n in range(1, b + 1)
               if b % n == 0 and (n == 1 or n * s <= GROUP_TOKENS))
    kernels = delta_rule.taken(*lane_widths(q.shape[-1], v.shape[-1]), chunk,
                               sub, q.dtype)
    if rows == b:
        return _rule(q, k, v, g, beta, chunk, sub, kernels)
    groups = jax.lax.map(
        lambda x: _rule(*x, chunk, sub, kernels),
        tuple(x.reshape((b // rows, rows) + x.shape[1:])
              for x in (q, k, v, g, beta)))
    return groups.reshape((b,) + groups.shape[2:])
