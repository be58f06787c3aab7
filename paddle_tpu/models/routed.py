"""The routed layer of both sparse families (llama.py, mla_moe.py), each
decision written once: the routed feed-forward, the router's facts, the
selection biases' move and the one-chip sparse train step.

Nothing here asks which family it serves. A layer's parameters say which
router it has (a selection bias `router_b`: the sigmoid one) and whether a
shared expert stands beside it; a model's say whether a prediction module
("mtp") holds a router after the trunk's, whose routers sit in the groups
of `GROUPS`, each a stacked tree or a list of trees, one a position of a
period (`blocks.scan_segments`).
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..ops import moe
from . import stages
from .blocks import in_layer_order, rms_norm, swiglu
from .trainer import build_adamw_train_step


# ------------------------------------------------------ the feed-forward

def routed_part(flat, blk, k: int, scale: float, held: Tuple[int, int],
                experts: int):
    """The `held` (first, count) routed experts' part on flat [T, h],
    already normed -> (that [T, h], the router's record): a sigmoid
    router's choices [T, k] (`moe.sigmoid_topk_route`, weights times
    `scale`), or a softmax router's {"balance": the layer's E * sum_e F_e
    P_e, "pairs": the pairs each of ALL the `experts` drew [E]}."""
    with jax.named_scope(stages.ROUTER):
        if "router_b" in blk:
            ids, weights = moe.sigmoid_topk_route(
                flat, blk["router_w"], blk["router_b"], k, scale)
            record = ids
        else:
            ids, weights, probs = moe.softmax_topk_route(
                flat, blk["router_w"], k)
            balance, drawn = moe.balance_term(ids, probs)
            record = {"balance": balance, "pairs": drawn}
    with jax.named_scope(stages.EXPERTS):
        return moe.held_experts_ffn(flat, ids, weights, blk["experts"], held,
                                    experts), record


def feed_forward(x, blk, eps: float, **routing):
    """The routed feed-forward on x [B, S, h] -> (its output, the router's
    record): under MLP the norm and the ungated shared expert where the
    layer has one, then `routed_part(**routing)`, and under MLP the sum."""
    with jax.named_scope(stages.MLP):
        y = rms_norm(x, blk["ln2_g"], eps)
        shared = swiglu(y, blk["shared_gate_w"], blk["shared_up_w"],
                        blk["shared_down_w"]) if "shared_gate_w" in blk \
            else None
    b, s, h = y.shape
    out, record = routed_part(y.reshape(b * s, h), blk, **routing)
    with jax.named_scope(stages.MLP):
        out = out.reshape(b, s, h)
        return out if shared is None else shared + out, record


# ---------------------------------------------------- the router's facts

# the groups of a trunk's parameters that can hold routers, in layer order
GROUPS = ("blocks", "sparse", "tail")


def router_biases(params):
    """Every sigmoid router's selection bias, [routers, E]: the trunk's in
    layer order (`blocks.in_layer_order`), then a prediction module's (the
    order of the choices)."""
    found = [in_layer_order(layers["router_b"] if isinstance(layers, dict)
                            else [layer["router_b"] for layer in layers])
             for layers in (params[g] for g in GROUPS if g in params)]
    if "mtp" in params:
        found.append(params["mtp"]["layer"]["router_b"])
    return jnp.concatenate(found)


def facts(params, ids, experts: int) -> dict:
    """What the sigmoid routers did on a batch, from their choices ids
    [routers, T, k]: `pairs`, those each of ALL the `experts` drew, int32,
    and the selection `biases` they chose by, both [routers, E]."""
    return {"pairs": moe.pairs_drawn(ids, experts),
            "biases": router_biases(params)}


# ---------------------------------------------------------- the bias move

def move_router_biases(master, ids, experts: int, rate: float):
    """The trainer's `state_update` of sigmoid routers: `master` with every
    selection bias (`router_biases`' rows) moved by the load of the step
    just run (`moe.moved_biases`: the pairs that the choices ids [routers,
    T, k] drew over ALL the `experts`, on this chip's tokens), each row put
    back where `router_biases` took it."""
    drawn = moe.pairs_drawn(ids, experts).astype(jnp.float32)
    biases = moe.moved_biases(router_biases(master), drawn, rate)
    at = 0
    for group in (g for g in GROUPS if g in master):
        layers = master[group]
        if isinstance(layers, dict):
            count = layers["router_b"].shape[0]
            moved = dict(layers, router_b=biases[at:at + count])
        else:       # a list, a position of the period each: interleaved
            periods, count = layers[0]["router_b"].shape[0], len(layers)
            mine = biases[at:at + periods * count].reshape(
                (periods, count) + biases.shape[1:])
            moved = [dict(layer, router_b=mine[:, i])
                     for i, layer in enumerate(layers)]
            count *= periods
        master, at = dict(master, **{group: moved}), at + count
    if "mtp" in master:
        layer = dict(master["mtp"]["layer"], router_b=biases[at:])
        master = dict(master, mtp=dict(master["mtp"], layer=layer))
    return master


def move_biases_only(state, ids, experts: int, rate: float):
    """The train state after the biases' move of one step WITHOUT the step:
    `move_router_biases` on master and parameters alike, ids the choices
    of a forward pass on a batch; no gradient, no AdamW, the step count as
    it was. What a deployment's thousands of steps do to the load (the rule
    balances it, the weights hardly moving meanwhile) a benchmark can
    reach in a few hundred of these before it times real steps."""
    return dict(state, **{name: move_router_biases(state[name], ids, experts,
                                                   rate)
                          for name in ("params", "master")})


# ------------------------------------------------------- the one-chip step

def build_train_step(loss: Callable, init_params: Callable, specs, wd_mask,
                     mesh: Optional[Mesh], *, share: bool, experts: int,
                     rate: float, **adamw):
    """(init_fn, step) through `trainer.build_adamw_train_step`, ONE
    compiled program a step. `share`: a routed feed-forward or layers of
    two kinds, run as one chip's share without its exchange, which a mesh
    of several chips would need (an `ep` axis). Where the parameters hold
    selection biases and `rate` is not 0, `move_router_biases` is the
    step's `state_update`, fed the choices `loss(..., want_ids=True)`
    gives."""
    if share and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            "a routed feed-forward or mixed layer kinds run as one chip's "
            "share; experts over an 'ep' mesh axis are not implemented")
    moves = bool(rate) and any(
        getattr(path[-1], "key", None) == "router_b" for path, _ in
        jax.tree_util.tree_leaves_with_path(
            jax.eval_shape(lambda: init_params(0))))
    return build_adamw_train_step(
        functools.partial(loss, want_ids=moves), init_params, specs, wd_mask,
        mesh=mesh, state_update=functools.partial(
            move_router_biases, experts=experts, rate=rate) if moves
        else None, **adamw)
