"""Generic compiled trainer: one XLA program = fwd + bwd + fused AdamW.

Shared by the model families (gpt/llama/bert/mla_moe): takes a pure loss fn,
a param-init fn, GSPMD param specs and a weight-decay mask, and returns
(init_fn, step_fn) with dp/mp/pp/ZeRO-1 shardings and buffer donation —
the TPU-native analog of the reference's fused optimizer + DistributedStrategy
plumbing (HybridParallelOptimizer, dygraph_sharding_optimizer.py)."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import stages


def filter_specs_for_mesh(specs, mesh: Optional[Mesh]):
    """Drop references to axes the mesh doesn't have."""
    if mesh is None:
        return specs

    def _filter(sp: P):
        return P(*(e if e in mesh.axis_names else None for e in sp))

    return jax.tree_util.tree_map(_filter, specs,
                                  is_leaf=lambda x: isinstance(x, P))


def zero1_opt_specs(specs, param_shapes, mesh: Optional[Mesh],
                    axis: str = "dp"):
    """ZeRO-1: shard optimizer state over the dp axis on the first
    unsharded, divisible dim (sharding-stage-1; each dp rank keeps 1/dp
    of master/m/v and XLA all-gathers the updated master where needed)."""
    if mesh is None or axis not in mesh.axis_names:
        return specs
    size = mesh.shape[axis]

    def _one(sp: P, shape):
        entries = list(sp) + [None] * (len(shape) - len(sp))
        for i, (e, dim) in enumerate(zip(entries, shape)):
            if e is None and dim % size == 0 and dim >= size:
                entries[i] = axis
                return P(*entries)
        return sp

    return jax.tree_util.tree_map(
        lambda sp, sh: _one(sp, sh.shape), specs, param_shapes,
        is_leaf=lambda x: isinstance(x, P))


def build_adamw_train_step(
        loss_fn: Callable,            # (params, tokens, labels) -> loss,
        #                               or (loss, aux) with `state_update`
        init_params_fn: Callable,     # (seed) -> params pytree
        specs,                        # PartitionSpec tree (or None)
        wd_mask,                      # bool tree matching params
        mesh: Optional[Mesh] = None,
        lr: float = 3e-4, wd: float = 0.1, b1: float = 0.9,
        b2: float = 0.95, eps: float = 1e-8,
        state_update: Optional[Callable] = None):
    """Returns (init_fn, step_fn); step(state, tokens, labels) -> (state,
    loss). On a mesh the batch is sharded over `dp` and so is the optimizer
    state (ZeRO-1).

    `state_update(master, aux) -> master` is for what a step moves by
    another rule than AdamW's: `loss_fn` then returns (loss, aux), and the
    function is handed the float32 master weights as AdamW left them and
    returns them with the leaves it replaces (the rest as they came, which
    compiles to nothing). It runs inside the same program, before the cast
    that makes the parameters, so master and parameter move alike; no
    gradient, moment or weight decay has a say in what it writes. Without
    it the step is what it was, instruction for instruction."""
    specs = filter_specs_for_mesh(specs, mesh)
    param_shapes = jax.eval_shape(lambda: init_params_fn(0))
    opt_specs = zero1_opt_specs(specs, param_shapes, mesh)

    def to_sharding(tree):
        if mesh is None:
            return None
        return jax.tree_util.tree_map(
            lambda sp: NamedSharding(mesh, sp), tree,
            is_leaf=lambda x: isinstance(x, P))

    def init_fn(seed=0):
        params = init_params_fn(seed)
        master = jax.tree_util.tree_map(
            lambda p: jnp.array(p, dtype=jnp.float32, copy=True), params)
        m = jax.tree_util.tree_map(jnp.zeros_like, master)
        v = jax.tree_util.tree_map(jnp.zeros_like, master)
        state = {"params": params, "master": master, "m": m, "v": v,
                 "step": jnp.zeros((), jnp.int32)}
        if mesh is not None:
            state = jax.device_put(state, _state_shardings())
        return state

    def _state_shardings():
        return {"params": to_sharding(specs),
                "master": to_sharding(opt_specs),
                "m": to_sharding(opt_specs), "v": to_sharding(opt_specs),
                "step": NamedSharding(mesh, P())}

    def step_fn(state, tokens, labels):
        loss, grads = jax.value_and_grad(
            loss_fn, has_aux=state_update is not None)(
                state["params"], tokens, labels)
        if state_update is not None:
            loss, aux = loss
        with jax.named_scope(stages.OPTIMIZER):
            step = state["step"] + 1
            t = step.astype(jnp.float32)

            def upd(p_master, g, m, v, use_wd):
                g = g.astype(jnp.float32)
                m2 = b1 * m + (1 - b1) * g
                v2 = b2 * v + (1 - b2) * g * g
                mhat = m2 / (1 - b1 ** t)
                vhat = v2 / (1 - b2 ** t)
                decay = wd * p_master if use_wd else 0.0
                new_master = p_master - lr * (
                    mhat / (jnp.sqrt(vhat) + eps) + decay)
                return new_master, m2, v2

            flat_master, tree = jax.tree_util.tree_flatten(state["master"])
            outs = [upd(pm, g, m, v, w) for pm, g, m, v, w in zip(
                flat_master, jax.tree_util.tree_leaves(grads),
                jax.tree_util.tree_leaves(state["m"]),
                jax.tree_util.tree_leaves(state["v"]),
                jax.tree_util.tree_leaves(wd_mask))]
            new_master = jax.tree_util.tree_unflatten(
                tree, [o[0] for o in outs])
            new_m = jax.tree_util.tree_unflatten(tree, [o[1] for o in outs])
            new_v = jax.tree_util.tree_unflatten(tree, [o[2] for o in outs])
            if state_update is not None:
                new_master = state_update(new_master, aux)
            new_params = jax.tree_util.tree_map(
                lambda pm, p: pm.astype(p.dtype), new_master, state["params"])
            return {"params": new_params, "master": new_master, "m": new_m,
                    "v": new_v, "step": step}, loss

    if mesh is not None:
        batch = NamedSharding(
            mesh, P("dp" if "dp" in mesh.axis_names else None, None))
        st_sh = _state_shardings()
        jstep = jax.jit(
            step_fn,
            in_shardings=(st_sh, batch, batch),
            out_shardings=(st_sh, NamedSharding(mesh, P())),
            donate_argnums=(0,))
    else:
        jstep = jax.jit(step_fn, donate_argnums=(0,))
    return init_fn, jstep
