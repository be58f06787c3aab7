"""Tensor-parallel semantic ops (mpu/mp_ops.py analog).

vocab_parallel_cross_entropy == the reference's
c_softmax_with_cross_entropy (fleet/layers/mpu/mp_ops.py:77-385): the
softmax-cross-entropy over a vocab-sharded classifier computed WITHOUT
ever materializing the full [B, S, V] logits. Each mp shard projects the
hidden states onto its vocab slice and three cheap collectives (max,
sum-exp, picked-logit) complete the loss — the TPU form uses a
partial-manual shard_map over the mp axis so dp/pp/sp placement stays
with GSPMD.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def vocab_parallel_softmax_cross_entropy(hidden, vocab_weight, labels,
                                         mesh: Mesh, axis: str = "mp"):
    """Per-token loss [B, S] from hidden [B, S, H] (mp-replicated) and a
    vocab-sharded classifier weight [V, H] (dim 0 over ``axis``), raw
    arrays in, under jit. Full logits never exist: each shard holds
    [B, S, V/mp]."""

    def f(h, w, y):
        n = lax.psum(1, axis)
        r = lax.axis_index(axis)
        vshard = w.shape[0]
        logits = jnp.einsum("bsh,vh->bsv", h, w).astype(jnp.float32)
        # global max for a stable softmax; gradient-free (the shift
        # cancels in softmax), and pmax has no autodiff rule anyway
        gmax = lax.pmax(
            lax.stop_gradient(jnp.max(logits, axis=-1)), axis)
        shifted = logits - gmax[..., None]
        sumexp = lax.psum(jnp.sum(jnp.exp(shifted), axis=-1), axis)
        # the label's (shifted) logit lives on exactly one shard
        lo = r * vshard
        is_local = jnp.logical_and(y >= lo, y < lo + vshard)
        idx = jnp.clip(y - lo, 0, vshard - 1)
        picked = jnp.take_along_axis(shifted, idx[..., None],
                                     axis=-1)[..., 0]
        picked = lax.psum(jnp.where(is_local, picked, 0.0), axis)
        return jnp.log(sumexp) - picked

    if mesh is None or axis not in mesh.axis_names \
            or mesh.shape[axis] <= 1:
        logits = jnp.einsum("bsh,vh->bsv", hidden,
                            vocab_weight).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, labels[..., None],
                                    axis=-1)[..., 0]

    # partial-manual over mp only: dp/pp/sp placement stays with GSPMD
    return jax.shard_map(f, mesh=mesh, in_specs=(P(), P(axis, None), P()),
                         out_specs=P(), axis_names={axis},
                         check_vma=False)(hidden, vocab_weight, labels)


# The ParallelCrossEntropy layer lives in mp_layers.py (exported via
# fleet); it delegates to mp_softmax_cross_entropy below for the eager
# multi-process regime and to GSPMD cross_entropy otherwise.

# ===================== eager multi-process collective primitives ========
# The host-driven forms of the reference's mpu collectives
# (fleet/layers/mpu/mp_ops.py:77-385: _c_identity/_c_concat/_c_split/
# _mp_allreduce/_c_lookup_table/_c_softmax_with_cross_entropy), built as
# PyLayers over the ProcessGroup-backed communication API so eager
# tensor-parallel layers work across real processes — the regime GSPMD
# cannot cover (no compiled mesh program spanning host processes).

def _comm():
    from .. import communication as comm
    return comm


def _fresh(t):
    from ..._core.tensor import Tensor
    return Tensor(t._value)


def _make_pylayers():
    from ...autograd import PyLayer

    class CIdentity(PyLayer):
        @staticmethod
        def forward(ctx, x, group):
            ctx.group = group
            return _fresh(x)

        @staticmethod
        def backward(ctx, dy):
            g = _fresh(dy)
            _comm().all_reduce(g, group=ctx.group)
            return g

    class MPAllReduce(PyLayer):
        @staticmethod
        def forward(ctx, x, group):
            out = _fresh(x)
            _comm().all_reduce(out, group=group)
            return out

        @staticmethod
        def backward(ctx, dy):
            return _fresh(dy)

    class CConcat(PyLayer):
        """fwd all-gather along the last dim / bwd local split."""

        @staticmethod
        def forward(ctx, x, group, rank, nranks):
            ctx.rank, ctx.nranks = rank, nranks
            parts = []
            _comm().all_gather(parts, x, group=group)
            vals = [p._value for p in parts]
            from ..._core.tensor import Tensor
            return Tensor(jnp.concatenate(vals, axis=-1))

        @staticmethod
        def backward(ctx, dy):
            from ..._core.tensor import Tensor
            per = dy.shape[-1] // ctx.nranks
            lo = ctx.rank * per
            return Tensor(
                lax.slice_in_dim(dy._value, lo, lo + per, axis=-1))

    class CSplit(PyLayer):
        """fwd take own chunk of the last dim / bwd all-gather."""

        @staticmethod
        def forward(ctx, x, group, rank, nranks):
            ctx.group, ctx.rank, ctx.nranks = group, rank, nranks
            from ..._core.tensor import Tensor
            per = x.shape[-1] // nranks
            lo = rank * per
            return Tensor(
                lax.slice_in_dim(x._value, lo, lo + per, axis=-1))

        @staticmethod
        def backward(ctx, dy):
            parts = []
            _comm().all_gather(parts, dy, group=ctx.group)
            from ..._core.tensor import Tensor
            return Tensor(jnp.concatenate(
                [p._value for p in parts], axis=-1))

    return CIdentity, MPAllReduce, CConcat, CSplit


_PYLAYERS = None


def _pylayers():
    global _PYLAYERS
    if _PYLAYERS is None:
        _PYLAYERS = _make_pylayers()
    return _PYLAYERS


def mp_identity(x, group):
    """Copy whose backward all-reduces over the mp group (_c_identity)."""
    return _pylayers()[0].apply(x, group)


def mp_allreduce(x, group):
    """All-reduce whose backward is identity (_mp_allreduce_sum)."""
    return _pylayers()[1].apply(x, group)


def mp_concat(x, group, rank, nranks):
    """All-gather + concat on the feature dim (_c_concat)."""
    return _pylayers()[2].apply(x, group, rank, nranks)


def mp_split(x, group, rank, nranks):
    """Keep this rank's chunk of the feature dim (_c_split)."""
    return _pylayers()[3].apply(x, group, rank, nranks)


def mp_lookup_table(weight_local, ids, vocab_start, group):
    """Vocab-sharded embedding lookup (_c_lookup_table): out-of-range ids
    hit row 0 locally, get masked to zero, and the cross-shard sum
    restores the full gather. Differentiable through the local gather."""
    from ...nn import functional as F
    per = weight_local.shape[0]
    idv = ids._value
    in_range = (idv >= vocab_start) & (idv < vocab_start + per)
    from ..._core.tensor import Tensor
    local_ids = Tensor(jnp.where(in_range, idv - vocab_start, 0))
    emb = F.embedding(local_ids, weight_local)
    mask = Tensor(in_range.astype(emb._value.dtype)[..., None])
    return mp_allreduce(emb * mask, group)


def mp_softmax_cross_entropy(logits_local, label, vocab_start, group,
                             ignore_index=-100):
    """Eager multi-process c_softmax_with_cross_entropy (mp_ops.py:385):
    per-token loss from vocab-sharded logits [.., V/mp] without ever
    forming the full logits on one rank. The global max is a detached
    stability shift; the exp-sum and picked-logit ride differentiable
    all-reduces."""
    from ..._core.tensor import Tensor
    from ...ops import reduction  # noqa: F401  (registers max/sum)
    comm = _comm()

    if label.ndim == logits_local.ndim:
        # paddle convention: labels may carry a trailing unit dim
        label = Tensor(label._value[..., 0])
    per = logits_local.shape[-1]
    # detached global max for numerics (non-differentiable by design)
    local_max = Tensor(jnp.max(logits_local._value, axis=-1,
                               keepdims=True))
    comm.all_reduce(local_max, op=comm.ReduceOp.MAX, group=group)
    shifted = logits_local - local_max  # broadcasts; max detached

    sum_exp = shifted.exp().sum(axis=-1, keepdim=True)
    sum_exp = mp_allreduce(sum_exp, group)
    log_den = sum_exp.log()

    idv = label._value
    in_range = (idv >= vocab_start) & (idv < vocab_start + per)
    local_lab = jnp.where(in_range, idv - vocab_start, 0)
    onehot = jax.nn.one_hot(local_lab, per, dtype=shifted._value.dtype) \
        * in_range[..., None].astype(shifted._value.dtype)
    picked = (shifted * Tensor(onehot)).sum(axis=-1, keepdim=True)
    picked = mp_allreduce(picked, group)

    loss = (log_den - picked).squeeze(-1)
    # mask ignored tokens for ANY ignore_index value (the default -100
    # is an active sentinel, matching F.cross_entropy's semantics)
    keep = Tensor((idv != ignore_index).astype(loss._value.dtype))
    return loss * keep
