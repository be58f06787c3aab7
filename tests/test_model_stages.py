"""The seven named scopes of the compiled train step (models/stages.py):
every instruction of the real `build_train_step` of each family says which
stage it belongs to, in every direction the family has, and the scopes
change nothing but metadata."""
import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.layer_metrics import _stages
from paddle_tpu.models import bert, gpt, llama, stages

BATCH, SEQ = 4, 32
CASES = [(family, remat) for family in ("gpt", "bert", "llama")
         for remat in (True, False)] + [("gpt-dp2mp2", True)]
IDS = [f"{family}-{'remat' if remat else 'plain'}" for family, remat in CASES]

# What stands under no scope, with JAX's `jit(step_fn)/jvp()/` or
# `jit(step_fn)/transpose(jvp())/` taken off: the scan over the layers and
# the checkpoint around a block, nothing of a model's own.
SCAN_PLUMBING = re.compile(
    r"^(?:broadcast_in_dim"                     # the stacked gradients' zeros
    r"|while(?:/cond/lt"                        # the trip count
    r"|/body/(?:add|sub|lt|select_n"            # the counter, its wrap-around
    r"|dynamic_slice|squeeze"                   # this layer's parameters
    r"|dynamic_update_slice|broadcast_in_dim"   # stacking what a layer gives
    r"|closed_call(?:/(?:remat2|checkpoint))?))?)$")     # the calls


def _build(family, remat):
    """The family's real train step at a tiny size, lowered on shapes."""
    if family == "gpt-dp2mp2":
        # the host mesh and layout `--cpu-dry-run` of the four-chip cell
        # builds (benchmarks/run.py)
        from benchmarks.cells import load_cell
        from benchmarks.runners import _trainer
        cell = load_cell("gpt3xl-dp2mp2-s2048", tiny=True)
        return _trainer.lower_step(cell, jax.devices()[:cell.chips])[0]
    if family == "gpt":
        init_fn, step = gpt.build_train_step(
            gpt.GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                          num_heads=4, max_position_embeddings=64,
                          dtype="bfloat16"), remat=remat)
    elif family == "bert":
        init_fn, step = bert.build_train_step(
            bert.BertConfig(vocab_size=256, hidden_size=64, num_layers=2,
                            num_heads=4, intermediate_size=128,
                            max_position_embeddings=64), remat=remat)
    else:
        init_fn, step = llama.build_train_step(
            llama.LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                              num_heads=4, num_kv_heads=2,
                              intermediate_size=128), remat=remat)
    state = jax.eval_shape(lambda: init_fn(0))
    tokens = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    return step.trace(state, tokens, tokens).lower()


@functools.lru_cache(maxsize=None)
def _compiled_text(family, remat):
    return _build(family, remat).compile().as_text()


def _paths(hlo_text):
    """Every jaxpr path of the step in the module's metadata. XLA names a
    reducer's own scalar instructions by the primitive alone (`reduce_sum`,
    `jit(take_along_axis)/scatter-add`): no path of the step's."""
    return {path for op_name in _stages.op_names(hlo_text).values()
            for path in op_name.split(";") if path.startswith("jit(step_fn)/")}


@pytest.mark.parametrize("family, remat", CASES, ids=IDS)
def test_every_stage_in_every_direction(family, remat):
    placed = {path: _stages.place(path, stages)
              for path in _paths(_compiled_text(family, remat))}
    through_block = ("forward", "remat", "backward") if remat \
        else ("forward", "backward")
    want = {(s, d) for s in stages.BLOCK for d in through_block} | {
        (s, d) for s in (stages.EMBED, stages.LOSS_HEAD)
        for d in ("forward", "backward")} | {(stages.OPTIMIZER, "update")}
    assert {found for found in placed.values() if found[0]} == want
    # the update is no part of the differentiated function
    for path, (stage, _) in placed.items():
        if stage == stages.OPTIMIZER:
            assert "jvp(" not in path and "rematted" not in path, path
    # what is under no scope is the scan's own plumbing
    unscoped = {re.sub(r"^jit\(step_fn\)/(?:transpose\(jvp\(\)\)|jvp\(\))/",
                       "", path)
                for path, (stage, _) in placed.items() if stage is None}
    assert [p for p in sorted(unscoped) if not SCAN_PLUMBING.match(p)] == []


@pytest.mark.parametrize("family, remat", CASES, ids=IDS)
def test_the_scopes_change_no_computation(family, remat, monkeypatch):
    """The compiled module with its metadata taken out (each instruction's
    own, and the tables of files and stack frames it points into) is the
    same text with the scopes patched away."""
    def computation(text):
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        return re.sub(r"\n\nFileNames\n.*?\n\n\n", "\n\n", text, count=1,
                      flags=re.DOTALL)

    scoped = _compiled_text(family, remat)
    assert f"/{stages.ATTN_CORE}/" in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _build(family, remat).compile().as_text()
    assert f"/{stages.ATTN_CORE}/" not in bare
    assert computation(bare) == computation(scoped)
