"""Mixture-of-Experts functional core (TPU-native). Two paths:

**Capacity path** (`top1_gating`, `top2_gating`, `moe_dispatch`,
`moe_combine`, `moe_ffn`): the GShard/Switch dense-dispatch formulation for
the eager `MoELayer` (incubate/distributed/models/moe) and the registered
ops. The reference's MoE stack (moe_layer.py:261, gates under moe/gate/,
all-to-all dispatch via global_scatter/global_gather, fused kernel
incubate/nn/functional/fused_moe.py) is CUDA-centric: ragged token dispatch
with index scatter/gather. Here: fixed expert capacity C, one-hot
dispatch/combine tensors [S, E, C] and einsum dispatch, so everything is
static-shaped and lands on the MXU; tokens over capacity are dropped; under
GSPMD an 'ep'-sharded expert dim lowers the dispatch einsums to the same
all-to-all the reference issues by hand. Top-1 and top-2 only.

**Dropless path** (`sigmoid_topk_route`, `softmax_topk_route`,
`held_experts_ffn`): what the compiled trainer's sparse families use
(models/mla_moe.py: sigmoid scores with a selection bias; models/llama.py:
softmax probabilities, renormalised, with a balance term). Any k, no
capacity, no dropped pair, no [S, E, C] tensor: the (token, expert) pairs
are sorted by expert and run through grouped products
(`ops/pallas/grouped_matmul.grouped_dot`: the repo's own Mosaic kernels,
forward and both gradients, which visit only the row tiles that hold a
group's rows). The layer is told
which experts it holds, as expert parallelism tells it: it routes over all
E and computes the part of the result its own experts give.

Shapes: tokens x [S, M] (leading group/batch dims folded by callers),
logits [S, E], dispatch/combine [S, E, C], expert weights stacked [E, ...].
Everything is differentiable jnp; usable eagerly (registered ops) and under
jit/pjit.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .pallas.grouped_matmul import grouped_dot


# ------------------------------------------------------------------ gating

def _capacity(s: int, e: int, k: int, capacity_factor: float,
              capacity: Optional[int]) -> int:
    if capacity is not None:
        return max(int(capacity), 1)
    return max(int(s * k * capacity_factor / e + 0.999999), 1)


def top2_gating(logits, capacity_factor: float = None,
                capacity: Optional[int] = None):
    """GShard top-2 gating (moe/gate/gshard_gate.py analog).

    logits [S, E] -> (combine [S, E, C], dispatch bool [S, E, C], aux_loss).
    aux_loss is the GShard load-balance loss: E * mean(me * ce).
    """
    if capacity_factor is None:
        from .._core.flags import flag_value
        capacity_factor = flag_value("FLAGS_moe_capacity_factor")
    s, e = logits.shape
    c = _capacity(s, e, 2, capacity_factor, capacity)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [S,E]

    g1_idx = jnp.argmax(probs, axis=-1)                          # [S]
    mask1 = jax.nn.one_hot(g1_idx, e, dtype=probs.dtype)         # [S,E]
    probs2 = probs * (1.0 - mask1)
    g2_idx = jnp.argmax(probs2, axis=-1)
    mask2 = jax.nn.one_hot(g2_idx, e, dtype=probs.dtype)

    # load-balance aux loss over the top-1 assignment
    me = jnp.mean(probs, axis=0)                                 # [E]
    ce = jnp.mean(mask1, axis=0)
    aux_loss = jnp.sum(me * ce) * e

    # positions within each expert's buffer (top-1 tokens first)
    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - mask1             # [S,E]
    mask1 = mask1 * (pos1 < c)
    pos2 = (jnp.cumsum(mask2, axis=0) - mask2
            + jnp.sum(mask1, axis=0, keepdims=True))
    mask2 = mask2 * (pos2 < c)
    pos2 = pos2 * mask2

    g1 = jnp.sum(probs * mask1, axis=-1)                         # [S]
    g2 = jnp.sum(probs * mask2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    loc1 = jnp.sum(pos1 * mask1, axis=-1).astype(jnp.int32)      # [S]
    loc2 = jnp.sum(pos2, axis=-1).astype(jnp.int32)
    oh_c1 = jax.nn.one_hot(loc1, c, dtype=probs.dtype)           # [S,C]
    oh_c2 = jax.nn.one_hot(loc2, c, dtype=probs.dtype)
    combine = (g1[:, None, None] * mask1[:, :, None] * oh_c1[:, None, :]
               + g2[:, None, None] * mask2[:, :, None] * oh_c2[:, None, :])
    dispatch = combine > 0.0
    return combine, dispatch, aux_loss


def top1_gating(logits, capacity_factor: float = 1.25,
                capacity: Optional[int] = None, jitter_eps: float = 0.0,
                rng=None):
    """Switch-Transformer top-1 gating (moe/gate/switch_gate.py analog)."""
    s, e = logits.shape
    c = _capacity(s, e, 1, capacity_factor, capacity)
    if jitter_eps > 0.0 and rng is not None:
        noise = jax.random.uniform(rng, logits.shape, jnp.float32,
                                   1.0 - jitter_eps, 1.0 + jitter_eps)
        logits = logits * noise
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    mask = jax.nn.one_hot(idx, e, dtype=probs.dtype)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(mask, axis=0)
    aux_loss = jnp.sum(me * ce) * e
    pos = jnp.cumsum(mask, axis=0) * mask - mask
    mask = mask * (pos < c)
    gate = jnp.sum(probs * mask, axis=-1)
    loc = jnp.sum(pos * mask, axis=-1).astype(jnp.int32)
    oh_c = jax.nn.one_hot(loc, c, dtype=probs.dtype)
    combine = gate[:, None, None] * mask[:, :, None] * oh_c[:, None, :]
    dispatch = combine > 0.0
    return combine, dispatch, aux_loss


# ------------------------------------------------------------ dispatch/ffn

def moe_dispatch(x, dispatch):
    """x [S, M], dispatch [S, E, C] -> expert inputs [E, C, M] (einsum =
    the TPU-native global_scatter)."""
    return jnp.einsum("sec,sm->ecm", dispatch.astype(x.dtype), x)


def moe_combine(expert_out, combine):
    """expert_out [E, C, M], combine [S, E, C] -> [S, M] (global_gather)."""
    return jnp.einsum("sec,ecm->sm", combine.astype(expert_out.dtype),
                      expert_out)


def moe_ffn(x, gate_w, w0, b0, w1, b1, *, k: int = 2,
            capacity_factor: float = 1.25, capacity: Optional[int] = None,
            activation: str = "gelu"):
    """Full MoE FFN block: gating + dispatch + grouped expert MLP + combine.

    x [S, M]; gate_w [M, E]; stacked expert weights w0 [E, M, H],
    b0 [E, H], w1 [E, H, M], b1 [E, M]. Returns (out [S, M], aux_loss).
    The grouped matmuls keep E as a batched einsum dim — one large MXU op;
    sharding w0/w1 on E over the 'ep' mesh axis makes GSPMD insert the
    dispatch all-to-alls.
    """
    logits = x @ gate_w.astype(x.dtype)
    if k == 1:
        combine, dispatch, aux = top1_gating(logits, capacity_factor,
                                             capacity)
    else:
        combine, dispatch, aux = top2_gating(logits, capacity_factor,
                                             capacity)
    xe = moe_dispatch(x, dispatch)                    # [E, C, M]
    h = jnp.einsum("ecm,emh->ech", xe, w0.astype(x.dtype)) \
        + b0[:, None, :].astype(x.dtype)
    act = getattr(jax.nn, activation)
    h = act(h)
    ye = jnp.einsum("ech,ehm->ecm", h, w1.astype(x.dtype)) \
        + b1[:, None, :].astype(x.dtype)
    out = moe_combine(ye, combine.astype(x.dtype))
    return out, aux.astype(jnp.float32)


# ----------------------------------------------------------- dropless path

def sigmoid_topk_route(x, w_r, b, k: int, scale: float):
    """Sigmoid top-k routing with a selection bias (DeepSeek-V3's
    `noaux_tc` with one group), in float32.

    x [T, M], w_r [M, E], b [E] -> (ids [T, k] int32, weights [T, k]
    float32). Scores s = sigmoid(x w_r); the k experts with the largest
    s + b are chosen (b steers the choice only: its load-driven update is a
    training recipe, and no gradient reaches it); the weights are the
    chosen s, normalised to sum 1, times `scale`. The product is a true
    float32 one (HIGHEST: a TPU's default rounds float32 operands to
    bfloat16, and a near-tie between two experts flips on that)."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               w_r.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, ids = jax.lax.top_k(s + b.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
    return ids.astype(jnp.int32), w


def softmax_topk_route(x, w_r, k: int):
    """Softmax top-k routing with renormalised weights (`norm_topk_prob`),
    in float32: no bias, no scaling.

    x [T, M], w_r [M, E] -> (ids [T, k] int32, weights [T, k] float32,
    probs [T, E] float32). p = softmax(x w_r) over ALL the experts; the k
    largest are chosen; the weights are the chosen p over their sum.
    `probs` is what a balance term reads (`balance_term`). The product is
    a true float32 one, HIGHEST, for `sigmoid_topk_route`'s reason."""
    probs = jax.nn.softmax(jnp.dot(x.astype(jnp.float32),
                                   w_r.astype(jnp.float32),
                                   precision=jax.lax.Precision.HIGHEST), -1)
    w, ids = jax.lax.top_k(probs, k)
    return ids.astype(jnp.int32), w / w.sum(-1, keepdims=True), probs


def pairs_drawn(ids, num_experts: int):
    """Choices [T, k] -> the (token, expert) pairs each of ALL the experts
    drew, int32 [num_experts]."""
    return (ids[..., None] == jnp.arange(num_experts)).sum((0, 1)).astype(
        jnp.int32)


def balance_term(ids, probs):
    """The auxiliary load-balance term of a softmax top-k router (Switch
    Transformer's, at k choices a token): E * sum_e F_e P_e with F_e the
    share of the T tokens that chose expert e (sums to k; a count, so no
    gradient) and P_e the mean over tokens of p_e. k at perfect balance.
    ids [T, k], probs [T, E] -> (float32 scalar, the pairs drawn [E])."""
    t, e = probs.shape
    drawn = pairs_drawn(ids, e)
    share = jax.lax.stop_gradient(drawn.astype(jnp.float32) / t)
    return e * jnp.sum(share * probs.mean(0)), drawn


def chunk_count(held: int, num_experts: int, pairs: int) -> int:
    """Chunks the sorted pairs are run in: as many as leave balanced
    routing's held pairs (pairs * held / num_experts) half of the first
    chunk, num_experts / (2 held): 4 where an eighth of the experts is
    held, 2 at a quarter, 1 from a half on (and where the pairs do not cut
    into chunks of whole sublanes). At a quarter held, 4 chunks would end
    the balanced load ON the first chunk's edge, and every seed a little
    over it would run a second chunk's gather and elementwise pass for a
    handful of pairs."""
    chunks = max(num_experts // (2 * held), 1)
    return chunks if pairs % (chunks * 8) == 0 else 1


def _gather_sum(rows, pos, live, weights=None):
    """sum_j [live[:, j]] weights[:, j] * rows[pos[:, j]] in float32, [T, M]:
    k gathers of T rows each, so no [T, k, M] array is ever formed."""
    out = 0
    for j in range(pos.shape[1]):
        mine = jnp.where(live[:, j, None], rows[pos[:, j]], 0).astype(
            jnp.float32)
        out = out + (mine if weights is None else mine * weights[:, j, None])
    return out


@jax.custom_vjp
def _dispatch(x, token, pos, live):
    """x [T, M] -> a chunk's rows x[token], [C, M]. `pos` [T, k] is where
    each of a token's k pairs sits in the chunk and `live` whether it sits
    there at all (in this chunk, and a pair of a held expert): the
    backward sums a token's live rows, a gather too, so no scatter runs in
    either direction. Rows that are not live belong to pairs of experts
    held elsewhere; the grouped products never touch them, so what comes
    back for them is not a gradient and is left out."""
    return x[token]


def _dispatch_fwd(x, token, pos, live):
    return x[token], (pos, live)


def _dispatch_bwd(res, d_rows):
    pos, live = res
    return (_gather_sum(d_rows, pos, live).astype(d_rows.dtype), None, None,
            None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, weights, pair, pos, live):
    """A chunk's rows [C, M] (row r is pair `pair[r]` = token * k + j),
    weights [T, k] -> float32 [T, M]: each token gathers its live pairs'
    rows and sums them by weight."""
    return _gather_sum(rows, pos, live, weights)


def _combine_fwd(rows, weights, pair, pos, live):
    return _gather_sum(rows, pos, live, weights), (rows, weights, pair, pos,
                                                   live)


def _combine_bwd(res, d_out):
    """In sorted space: a row's gradient is its token's d_out times the
    pair's weight, and the weight's gradient the row's product with that
    d_out. (A row that is not live was zeroed before it came here, and
    that `where` stops what this returns for it.)"""
    rows, weights, pair, pos, live = res
    mine = d_out[pair // weights.shape[1]]                      # [C, M]
    d_rows = (mine * weights.reshape(-1)[pair][:, None]).astype(rows.dtype)
    d_sorted = (mine * rows.astype(jnp.float32)).sum(-1)        # [C]
    d_weights = jnp.where(live, d_sorted[pos], 0).astype(weights.dtype)
    return d_rows, d_weights, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _chunk(x, weights, experts, order, where, starts, ends, lo, rows):
    """The chunk of `rows` sorted pairs from `lo` through the held experts:
    float32 [T, M], the weighted sum of its live rows' outputs at their
    tokens. The grouped products leave the rows past the last group
    unwritten (`grouped_matmul`): everything here that reads such a row
    selects it away (`valid`, `live`), and `silu(gate) * up` of one feeds
    only a row the down product skips."""
    pair = jax.lax.dynamic_slice(order, (lo,), (rows,))
    pos = where - lo
    live = (pos >= 0) & (pos < rows) & (where < ends[-1])
    pos = jnp.clip(pos, 0, rows - 1)
    sizes = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
    mine = _dispatch(x, pair // weights.shape[1], pos, live)    # [C, M]
    gate, up = grouped_dot(mine, (experts["gate_w"], experts["up_w"]), sizes)
    out = grouped_dot(jax.nn.silu(gate) * up, experts["down_w"], sizes)
    valid = jnp.arange(rows) < sizes.sum()
    return _combine(jnp.where(valid[:, None], out, 0), weights, pair, pos,
                    live)


def _chunk_starts(order, chunks: int):
    rows = order.shape[0] // chunks
    return jnp.arange(chunks, dtype=jnp.int32) * rows, rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _chunks(chunks, x, weights, experts, order, where, starts, ends):
    """Every chunk that holds a held pair, summed: float32 [T, M]. The
    backward is written out (one chunk at a time, recomputed in the branch
    that runs it): differentiating the `cond` would hand each branch's
    operands, the expert matrices among them, back once a chunk."""
    los, rows = _chunk_starts(order, chunks)

    def body(out, lo):
        return jax.lax.cond(
            lo < ends[-1],
            lambda out: out + _chunk(x, weights, experts, order, where,
                                     starts, ends, lo, rows),
            lambda out: out, out), None

    return jax.lax.scan(body, jnp.zeros(x.shape, jnp.float32), los)[0]


def _chunks_fwd(chunks, *args):
    return _chunks(chunks, *args), args


def _chunks_bwd(chunks, res, d_out):
    x, weights, experts, order, where, starts, ends = res
    los, rows = _chunk_starts(order, chunks)
    add = functools.partial(jax.tree_util.tree_map, jnp.add)

    def body(grads, lo):
        def active(grads):
            _, vjp = jax.vjp(
                lambda x, weights, experts: _chunk(
                    x, weights, experts, order, where, starts, ends, lo,
                    rows), x, weights, experts)
            return add(grads, vjp(d_out))

        return jax.lax.cond(lo < ends[-1], active, lambda g: g, grads), None

    grads, _ = jax.lax.scan(
        body, jax.tree_util.tree_map(jnp.zeros_like, (x, weights, experts)),
        los)
    return grads + (None, None, None, None)


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def held_experts_ffn(x, ids, weights, experts, held, num_experts=None):
    """The part of a routed-experts layer that the experts held here give.

    x [T, M]; ids, weights [T, k] from a router over ALL `num_experts`
    experts (None: the held ones are all there are); `experts` the SwiGLU
    matrices of the held ones, stacked: gate_w, up_w [n, M, F], down_w
    [n, F, M]; `held` = (first, count): experts first .. first + count - 1
    are here. Returns sum over the pairs that chose a
    held expert of weight * down(silu(gate x) * up x), [T, M]; what the
    experts held elsewhere would add is left out (expert parallelism adds
    it in its exchange, which this function does not stand in for).

    Dropless with static shapes, and no capacity. The T*k pairs are sorted
    so that the pairs of held experts come first, grouped by expert, and
    the rest last; the sorted order is cut into `chunk_count` chunks of
    equal rows (4 where an eighth of the experts is held, 2 at a quarter),
    and a chunk runs (gather, three grouped products over its part of each
    expert's group, weighted combine) only if a held pair lies in it.
    Balanced routing fills half of the first chunk and the others cost a
    branch not taken; a router that sends every token to held experts
    fills all of them, and no pair is dropped either way. Buffers have a
    chunk's rows. Within a running chunk a pair that chose no held expert
    costs no product (`grouped_dot`'s kernels visit the row tiles of the
    held pairs and no other, in all nine products of a pass, and leave the
    rows past them unwritten: `_chunk` selects those away) but does cost
    its row of the gather and the elementwise pass. Gradients reach x, the
    weights and the expert matrices; ids are integers."""
    first, count = held
    t, k = ids.shape
    local = ids.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)    # [T*k]
    where = jnp.argsort(order).astype(jnp.int32).reshape(t, k)
    ends = jnp.cumsum((key[:, None] == jnp.arange(count)).sum(0)).astype(
        jnp.int32)                              # each held group's end
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), ends[:-1]])
    chunks = chunk_count(count, num_experts or count, t * k)
    return _chunks(chunks, x, weights.astype(jnp.float32), experts, order,
                   where, starts, ends).astype(x.dtype)


# -------------------------------------------------- eager op registration

def _register():
    from .._core.op_registry import register_op

    register_op("moe_gate_top2", top2_gating, multi_output=True)
    register_op("moe_gate_top1",
                lambda logits, capacity_factor=1.25, capacity=None:
                top1_gating(logits, capacity_factor, capacity),
                multi_output=True)
    register_op("moe_dispatch", moe_dispatch)
    register_op("moe_combine", moe_combine)
    register_op("fused_moe", moe_ffn, multi_output=True)


_register()
