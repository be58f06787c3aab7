"""Compiled pipeline parallelism: stages on a 'pp' mesh axis.

The reference's pipeline runtime is host-driven micro-batch P2P
(meta_parallel/pipeline_parallel.py:242: 1F1B forward_backward_pipeline:684;
p2p shape handshake pp_utils/p2p_communication.py:52). The TPU-native
compiled form (SURVEY §7 "PP across a pod") keeps the whole schedule inside
ONE XLA program: layer-stacked params are sharded over the 'pp' axis, and
micro-batch activations stream between stages with ``ppermute`` over ICI
inside a ``lax.scan``. jax 0.9 partial-manual ``shard_map``
(axis_names={'pp'}) leaves the other mesh axes (dp, mp, sharding) to GSPMD,
so compiled PP composes with TP/DP/ZeRO without hand-written collectives.

Schedule realized is GPipe/FThenB numerics (micro-batches are independent,
so 1F1B reordering does not change results — it is a memory optimization
that XLA's remat + buffer donation subsumes here); the scan runs
T = M + n - 1 ticks with the usual (n-1)/T bubble.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


# ------------------------------------------------- the collective order
# THE permutation lists and tick counts the compiled lowerings below
# are built from. Exported so the sanitizer's pipeline_schedule checker
# (analysis/distributed_checks.check_compiled_pipeline) validates the
# REAL collective-permute order of the shipping lowering, not a
# hand-modeled copy of it.

def stream_permutation(n: int):
    """Activation ring of the streamed-scan pipeline: stage i hands its
    output to stage i+1 every tick (one ``ppermute`` per tick)."""
    return [(i, (i + 1) % n) for i in range(n)]


def stream_tick_count(num_micro: int, n: int) -> int:
    return num_micro + n - 1


def fb_permutations(n: int):
    """The 1F1B train step's per-tick pair: activations flow down the
    ring, cotangents flow up it."""
    down = [(i, (i + 1) % n) for i in range(n)]
    up = [((i + 1) % n, i) for i in range(n)]
    return down, up


def fb_tick_count(num_micro: int, n: int) -> int:
    return num_micro + 2 * (n - 1)


def spmd_pipeline(stage_fn: Callable, x_mb, axis_name: str = "pp"):
    """Stream micro-batches through pipeline stages. Call inside a manual
    shard_map context over ``axis_name``.

    stage_fn: activation [mb, ...] -> activation [mb, ...] for THIS stage's
        layer slice (closure over stage-local params).
    x_mb: [M, mb, ...] all micro-batches (replicated over the pp axis).
    Returns [M, mb, ...] trunk outputs, replicated over pp.
    """
    n = jax.lax.psum(1, axis_name)
    rank = jax.lax.axis_index(axis_name)
    m = x_mb.shape[0]
    t_total = stream_tick_count(m, n)
    perm = stream_permutation(n)

    state0 = jnp.zeros_like(x_mb[0])
    outputs0 = jnp.zeros_like(x_mb)

    def tick(carry, t):
        state, outputs = carry
        inp = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, m - 1), 0, keepdims=False)
        cur = jnp.where(rank == 0, inp, state)
        # bubble ticks (t outside [rank, rank+m)) skip the stage compute:
        # lax.cond lowers to an HLO conditional, so idle ranks run the
        # identity branch instead of burning stage FLOPs on garbage
        valid = jnp.logical_and(t >= rank, t < rank + m)
        out = jax.lax.cond(valid, stage_fn, lambda a: a, cur)
        widx = jnp.clip(t - (n - 1), 0, m - 1)
        prev = jax.lax.dynamic_index_in_dim(outputs, widx, 0,
                                            keepdims=False)
        is_ready = jnp.logical_and(rank == n - 1, t >= n - 1)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs, jnp.where(is_ready, out, prev), widx, 0)
        state = jax.lax.ppermute(out, axis_name, perm)
        return (state, outputs), None

    (_, outputs), _ = jax.lax.scan(tick, (state0, outputs0),
                                   jnp.arange(t_total))
    # broadcast the last stage's outputs to every pp rank
    outputs = jax.lax.psum(jnp.where(rank == n - 1, outputs, 0.0),
                           axis_name)
    return outputs


def pipelined_trunk(block_fn: Callable, mesh: Mesh, num_microbatches: int,
                    axis_name: str = "pp", remat: bool = True):
    """Wrap a layer-scanned transformer trunk into the compiled pipeline.

    block_fn(x, blk) -> x applies ONE block with params blk (leaves
    [*per-layer shapes]). Returns trunk(params_blocks, x) where
    params_blocks leaves are [L, ...] sharded P('pp', ...) and
    x is [B, S, H]; result is [B, S, H].
    """

    def stage(blocks_local, a):
        fn = jax.checkpoint(block_fn) if remat else block_fn

        def body(carry, blk):
            return fn(carry, blk), None

        out, _ = jax.lax.scan(body, a, blocks_local)
        return out

    def trunk(blocks, x):
        b = x.shape[0]
        if b % num_microbatches:
            raise ValueError(
                f"batch {b} not divisible by micro-batches "
                f"{num_microbatches}")
        mb = b // num_microbatches
        x_mb = x.reshape(num_microbatches, mb, *x.shape[1:])

        blocks_spec = jax.tree_util.tree_map(
            lambda leaf: P(axis_name), blocks)

        # partial-manual over ONLY the pp axis: the other mesh axes (dp,
        # mp) stay with GSPMD
        inner = jax.shard_map(
            lambda bl, xm: spmd_pipeline(
                functools.partial(stage, bl), xm, axis_name),
            mesh=mesh,
            in_specs=(blocks_spec, P()),
            out_specs=P(),
            axis_names={axis_name}, check_vma=False)
        y_mb = inner(blocks, x_mb)
        return y_mb.reshape(b, *x.shape[1:])

    return trunk


# --------------------------------------------------------------- schedules

class PipelineSchedule:
    """Schedule descriptor (passes/pipeline_scheduler_pass analog). In the
    compiled runtime all schedules share GPipe/FThenB numerics; the choice
    records intent and tunes micro-batch count / remat policy."""

    name = "FThenB"

    def __init__(self, num_microbatches: Optional[int] = None,
                 remat: bool = True):
        self.num_microbatches = num_microbatches
        self.remat = remat


class FThenB(PipelineSchedule):
    name = "FThenB"


class OneFOneB(PipelineSchedule):
    """1F1B (pipeline_parallel.py:684): identical numerics to FThenB. The
    compiled path gets its memory control from remat + donation; the
    host-driven multi-process runtime (pipeline.DistPipelineRuntime)
    implements the real 1F1B stash cap (peak in-flight activations
    num_stages instead of num_microbatches)."""
    name = "1F1B"


class VPP(PipelineSchedule):
    """Interleaved virtual-pipeline (PipelineParallelWithInterleave:1308).
    Compiled form runs v rounds of the ring; round-1 falls back to FThenB
    numerics with v*num_stages micro-batches."""
    name = "VPP"

    def __init__(self, num_microbatches=None, remat=True,
                 virtual_pp_degree: int = 2):
        super().__init__(num_microbatches, remat)
        self.virtual_pp_degree = virtual_pp_degree


class ZeroBubble(PipelineSchedule):
    """ZeroBubble (pipeline_zero_bubble.py:62): splits weight-grad from
    activation-grad to fill the bubble; XLA's scheduler already overlaps
    the two inside the compiled backward scan."""
    name = "ZeroBubble"


# ------------------------------------------------ memory-true 1F1B

def pipeline_1f1b_train_step(stage_fn: Callable, loss_fn: Callable,
                             mesh: Mesh, num_microbatches: int,
                             axis_name: str = "pp"):
    """Compiled 1F1B whose ACTIVATION RESIDENCY follows the 1F1B bound.

    The streamed-scan pipeline above has GPipe residency: jax.grad
    through the scan saves every tick's boundary activations, so saved
    bytes grow with num_microbatches. This builder hand-schedules
    forward AND backward inside ONE XLA program instead:

    - per tick, a rank runs F for micro fi = t - rank and B for micro
      bi = t - 2(n-1) + rank (the classic interleave; the last stage
      backpropagates a micro the same tick it forwards it);
    - F runs jax.vjp and stores the pullback's RESIDUAL LEAVES in a
      rotating stash of depth 2n (in-flight micros per rank < 2n), so
      stash memory scales with num_STAGES — never with micro-batches;
    - leaves that are just references to the stage parameters are
      detected during an abstract trace (they alias the param tracers)
      and re-supplied from the live params at B time instead of being
      stashed, the same dedup the reference gets from TensorWrapper
      holding weights by reference;
    - activations flow down / cotangents flow up with one ppermute
      pair per tick over ICI.

    stage_fn(params_local, a) -> a;  loss_fn(y, label) -> scalar.
    Returns train(params_blocks, x, labels) -> (loss, grads) with
    params_blocks leaves [n, ...] sharded over the pp axis. Bubble
    ticks burn idle-branch FLOPs (masked, not skipped); the memory
    bound, not the bubble, is what this path is for. The tick loop is a
    lax.fori_loop, so program size and compile time are constant in
    num_microbatches.
    """
    n = mesh.shape[axis_name]
    S = 2 * n                    # stash depth >= peak in-flight
    M = num_microbatches

    def inner(params, x_mb, labels_mb):
        rank = jax.lax.axis_index(axis_name)
        # blocks arrive [1, ...] per device (their pp shard): drop the
        # stage axis so stage_fn sees per-stage shapes
        params = jax.tree_util.tree_map(lambda l: l[0], params)
        mb_shape = x_mb.shape[1:]

        # ---- abstract pullback structure (static across ticks)
        holder = {}

        def probe(p, a):
            out, pull = jax.vjp(stage_fn, p, a)
            leaves, treedef = jax.tree_util.tree_flatten(pull)
            p_leaves = jax.tree_util.tree_leaves(p)
            p_ids = {id(x) for x in p_leaves}
            holder["treedef"] = treedef
            holder["is_param"] = [id(x) in p_ids for x in leaves]
            # map param-aliasing leaves to their index in p_leaves
            idx_of = {id(x): i for i, x in enumerate(p_leaves)}
            holder["param_idx"] = [idx_of.get(id(x), -1) for x in leaves]
            return out, leaves

        _, leaf_avals = jax.eval_shape(
            probe, params, jax.ShapeDtypeStruct(mb_shape, x_mb.dtype))
        treedef = holder["treedef"]
        is_param = holder["is_param"]
        param_idx = holder["param_idx"]

        stash = [jnp.zeros((S,) + av.shape, av.dtype)
                 for av, isp in zip(leaf_avals, is_param) if not isp]
        grads = jax.tree_util.tree_map(jnp.zeros_like, params)
        recv_fwd = jnp.zeros(mb_shape, x_mb.dtype)
        recv_bwd = jnp.zeros(mb_shape, x_mb.dtype)
        loss_acc = jnp.zeros((), jnp.float32)

        down, up = fb_permutations(n)
        T = fb_tick_count(M, n)
        p_leaves_live = jax.tree_util.tree_leaves(params)

        def tick(t, carry):
            # ONE tick body traced once: program size and compile time
            # stay constant in num_microbatches (lax.fori_loop), unlike
            # an unrolled python loop
            stash, grads, recv_fwd, recv_bwd, loss_acc = carry
            fi = t - rank                       # traced (rank-dependent)
            bi = t - 2 * (n - 1) + rank
            f_on = jnp.logical_and(fi >= 0, fi < M)
            b_on = jnp.logical_and(bi >= 0, bi < M)

            # ---------------- F phase
            x_self = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(fi, 0, M - 1), 0, keepdims=False)
            a_in = jnp.where(rank == 0, x_self, recv_fwd)
            out, pull = jax.vjp(stage_fn, params, a_in)
            leaves = jax.tree_util.tree_flatten(pull)[0]
            # stash non-param residual leaves at slot fi % S
            slot = jnp.clip(fi, 0, M - 1) % S
            si = 0
            new_stash = []
            for leaf, isp in zip(leaves, is_param):
                if isp:
                    continue
                cur = stash[si]
                upd = jax.lax.dynamic_update_index_in_dim(
                    cur, leaf.astype(cur.dtype), slot, 0)
                new_stash.append(jnp.where(f_on, upd, cur))
                si += 1
            stash = new_stash

            # last rank: loss + cotangent for the SAME micro this tick
            lbl = jax.lax.dynamic_index_in_dim(
                labels_mb, jnp.clip(fi, 0, M - 1), 0, keepdims=False)
            mloss, dy = jax.value_and_grad(loss_fn)(out, lbl)
            is_last = rank == n - 1
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(f_on, is_last), mloss / M, 0.0)

            # ---------------- B phase
            bslot = jnp.clip(bi, 0, M - 1) % S
            si = 0
            b_leaves = []
            for isp, pidx in zip(is_param, param_idx):
                if isp:
                    b_leaves.append(p_leaves_live[pidx])
                else:
                    b_leaves.append(jax.lax.dynamic_index_in_dim(
                        stash[si], bslot, 0, keepdims=False))
                    si += 1
            pull_b = jax.tree_util.tree_unflatten(treedef, b_leaves)
            g_in = jnp.where(is_last, dy / M, recv_bwd)
            dparams, dx = pull_b(g_in)
            grads = jax.tree_util.tree_map(
                lambda acc, d: acc + jnp.where(b_on, d, 0.0).astype(
                    acc.dtype),
                grads, dparams)

            # ---------------- comm for next tick
            send_f = jnp.where(f_on, out, jnp.zeros_like(out))
            recv_fwd = jax.lax.ppermute(send_f, axis_name, down)
            send_b = jnp.where(b_on, dx, jnp.zeros_like(dx))
            recv_bwd = jax.lax.ppermute(send_b, axis_name, up)
            return (stash, grads, recv_fwd, recv_bwd, loss_acc)

        carry = (stash, grads, recv_fwd, recv_bwd, loss_acc)
        stash, grads, recv_fwd, recv_bwd, loss_acc = jax.lax.fori_loop(
            0, T, tick, carry)

        loss = jax.lax.psum(loss_acc, axis_name)
        # re-add the stage axis so the P(pp) out-spec reassembles [n, ...]
        grads = jax.tree_util.tree_map(lambda g: g[None], grads)
        return loss, grads

    def train(params_blocks, x, labels):
        b = x.shape[0]
        if b % M:
            raise ValueError(f"batch {b} % micro-batches {M} != 0")
        mb = b // M
        x_mb = x.reshape(M, mb, *x.shape[1:])
        l_mb = labels.reshape(M, mb, *labels.shape[1:])
        blocks_spec = jax.tree_util.tree_map(
            lambda _: P(axis_name), params_blocks)
        sm = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(blocks_spec, P(), P()),
            out_specs=(P(), blocks_spec),
            axis_names={axis_name}, check_vma=False)
        loss, grads = sm(params_blocks, x_mb, l_mb)
        return loss, grads

    return train
