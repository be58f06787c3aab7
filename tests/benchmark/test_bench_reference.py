"""The plain float32 references against the compiled trainer at a tiny
size on the CPU: step-0 loss and the loss after the first AdamW update."""
import jax
import jax.numpy as jnp
import pytest

from benchmarks import check, generator
from benchmarks.cells import load_cell


def _both(cell_name, dtype, seed=3):
    cell = load_cell(cell_name, tiny=True)
    config = dict(cell.config, dtype=dtype,
                  reference_check="loss_and_update")
    init_fn, step, init_params = cell.runner.build(config, None, {})
    two, tiled = generator.make_check_batch(
        cell.traffic, config["vocab_size"], seed)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    init_params(seed))
    reference = check.reference_losses(cell.reference, params, two, config)
    state = init_fn(seed)
    state, loss0 = step(state, *tiled)
    state, loss1 = step(state, *tiled)
    return (float(loss0), float(loss1)), reference, config


@pytest.mark.parametrize("cell_name", ["gpt2m-pretrain-s1024",
                                       "bertl-mlm-s512"])
def test_trainer_agrees_with_reference_in_float32(cell_name):
    program, reference, _ = _both(cell_name, "float32")
    # same arithmetic in another order: float32 rounding only
    assert program[0] == pytest.approx(reference[0], rel=1e-5)
    drop_p, drop_r = program[0] - program[1], reference[0] - reference[1]
    assert drop_r > 0.01                    # the update did something
    assert drop_p == pytest.approx(drop_r, rel=1e-3)


@pytest.mark.parametrize("cell_name", ["gpt2m-pretrain-s1024",
                                       "bertl-mlm-s512"])
def test_bfloat16_trainer_stays_near_the_float32_reference(cell_name):
    """At h64 the fall of the loss is a few hundredths, so bfloat16 rounding
    is a larger share of it than at the real widths, where the files'
    tolerances (5e-4 and 0.5 %) are checked in every run on the chip."""
    program, reference, _ = _both(cell_name, "bfloat16")
    assert check.compare_losses(program, reference,
                                {"loss": 1e-3, "drop": 0.05}) == []


def test_compare_losses_catches_a_wrong_gradient():
    tol = {"loss": 5e-4, "drop": 0.02}
    ref = (10.8, 10.7)
    assert check.compare_losses((10.801, 10.7005), ref, tol) == []
    assert "step-0 loss" in check.compare_losses((10.9, 10.8), ref, tol)[0]
    # the loss falls 10 % less than it should: some gradient is wrong
    assert "fell by" in check.compare_losses((10.8, 10.71), ref, tol)[0]
    # the forward-only check of a model too large for the update
    assert check.compare_losses((10.8, 10.0), (10.8, None), tol) == []
    assert check.finite([1.0, float("nan"), float("inf")]) == 2


@pytest.mark.parametrize("text, n_problems", [
    ("x tpu_custom_call y tensor<16x16x64xbf16>", 0),
    ("tensor<16x16x1024x1024xf32>", 2),
    ("tpu_custom_call tensor<8x8x1024x1024xf32>", 1),   # one chip's shard
])
def test_a_flash_fallback_is_read_off_the_lowered_step(text, n_problems):
    got = check.flash_fallback_problems(text, 1024, {(16, 16), (8, 8)})
    assert len(got) == n_problems, got


def test_only_a_configuration_that_states_flash_is_held_to_it():
    """bert states no attention path: how it computes attention is the
    program's to change (ROADMAP A2), and the reference guards the result."""
    import types

    from benchmarks.runners import _trainer
    einsum_only = types.SimpleNamespace(
        as_text=lambda: "tensor<32x16x512x512xf32>")
    assert _trainer.flash_problems(load_cell("bertl-mlm-s512"),
                                   einsum_only) == []
    assert "attention" not in load_cell("bertl-mlm-s128").config
    gpt = load_cell("gpt2m-pretrain-s1024")
    assert len(_trainer.flash_problems(gpt, types.SimpleNamespace(
        as_text=lambda: "tensor<16x16x1024x1024xf32>"))) == 2
