"""The Olmo-Hybrid-7B cell: the plain reference of Gated DeltaNet and
full-attention layers in the post-norm block against the program at a
tiny size (the loss and every gradient; the loss, its fall after one
update of the held share of a ZeRO-1 optimizer state and the rule alone,
as the runner compares them, and the bfloat16 references that the checks
refuse), the configuration's cut against the catalog, the FLOP count by
hand, what the runner reads from a compiled step, and the four new readers
on a hand-made trace."""
import functools
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_gdn_hybrid, peaks
from benchmarks import trace_reduce as tr
from benchmarks.cells import load_cell
from benchmarks.layer_metrics import (Run, _gated_delta,
                                      gated_delta_ms_per_step,
                                      gated_delta_roofline,
                                      gated_delta_scan_steps,
                                      optimizer_ms_per_step)
from benchmarks.reference import gdn_hybrid as ref
from benchmarks.runners import gdn_hybrid as runner
from paddle_tpu.models import llama, stages
from paddle_tpu.ops import linear_attention

CELL = "olmohy-zero8share-pretrain-s4096"
SEQ = 64


@pytest.fixture(scope="module")
def tiny():
    cell = load_cell(CELL, tiny=True)
    config = cell.config
    c = runner.program_config(config)
    master = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), llama.init_llama_params(c, 2))
    ids = np.random.default_rng(4).integers(
        0, config["vocab_size"], (2, SEQ + 1), dtype=np.int32)
    return cell, c, master, (ids[:, :-1], ids[:, 1:])


# ------------------------------------------- the program against the reference

def test_the_loss_and_every_gradient_are_the_references(tiny):
    """float32, tiny widths, one sequence: the program's mean cross-entropy
    and its gradient to every parameter against the reference's."""
    cell, c, master, (tokens, labels) = tiny
    config = cell.config

    def mean(params):
        total, count = ref.nll(params, tokens[0], labels[0], config)
        return total / count

    with jax.default_matmul_precision("highest"):
        got, got_g = jax.jit(jax.value_and_grad(lambda p: llama.llama_loss(
            p, tokens[:1], labels[:1], c)))(master)
        want, want_g = jax.jit(jax.value_and_grad(mean))(master)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    found = jax.tree_util.tree_leaves_with_path(got_g)
    # the embedding, the head, the final norm; 18 in a linear layer, 11 in
    # the full one
    assert len(found) == len(jax.tree_util.tree_leaves(want_g)) == 68
    for (path, g), w in zip(found, jax.tree_util.tree_leaves(want_g)):
        scale = float(jnp.abs(w).max())
        assert scale > 0, path
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5 * scale,
                                   err_msg=jax.tree_util.keystr(path))


def test_the_step_against_the_reference_and_the_references_it_refuses(tiny):
    """The runner's comparison at the tiny size, float32: the step-0 loss,
    its fall after one update of the held share, the rule alone forward and
    backward, and a first step that moved the held share and nothing else.
    The reference in bfloat16 throughout is refused by the fall, and the
    one whose state and log-decays are bfloat16 by `rule` and `rule_back`,
    each alone."""
    cell, c, master, seqs = tiny
    config = cell.config
    cotangent = runner.rule_cotangent(2, cell)[:, :SEQ]
    copy = functools.partial(jax.tree_util.tree_map, jnp.copy)
    with jax.default_matmul_precision("highest"):
        rule = ref.check_rule(master, seqs[0], cotangent, config)
        rule_lower = ref.check_rule(master, seqs[0], cotangent, config,
                                    jnp.bfloat16)
        want = ref.check_step(copy(master), seqs, config)
        lower = ref.check_step(copy(master), seqs, config,
                               precision="default", dtype=jnp.bfloat16)
        opt = config["optimizer"]
        init_fn, step = llama.build_train_step(
            c, lr=opt["lr"], wd=opt["wd"], b1=opt["b1"], b2=opt["b2"],
            opt_share=tuple(config["deployment"]["opt_share"]))
        state = init_fn(2)
        offs = runner.rule_offs(state["params"], seqs[0], cotangent, c, rule)
        before = jax.device_get(state["params"])
        state, loss0 = step(state, *seqs)
        moved = runner.share_moved(before, state["params"], config)
        state, loss1 = step(state, *seqs)
    got = {"loss0": float(loss0), "loss1": float(loss1), "rule_off": offs[0],
           "rule_back_off": offs[1], "moved": moved}
    limits = {"loss": 2e-5, "drop": 5e-3, "rule": 1e-4, "rule_back": 1e-4}
    assert runner.compare(got, want, limits) == []
    assert moved == {"held": 1.0, "unheld": 0.0}
    assert max(offs) < 2e-5
    # the reference in bfloat16 throughout: its update hardly survives
    refused = runner.compare(got, lower, limits)
    assert any("fell by" in problem for problem in refused)
    # the state and log-decays in bfloat16: the rule's limits, each alone
    low = [runner.off(x, y) for x, y in zip(rule_lower, rule)]
    assert 1e-3 < min(low)
    for key, value, what in (("rule_off", low[0], "chunked rule is"),
                             ("rule_back_off", low[1], "rule's gradient")):
        refused = runner.compare(dict(got, **{key: value}), want, limits)
        assert len(refused) == 1 and what in refused[0]
    # a step that moved nothing, or moved what this chip does not hold
    for bad, what in (({"held": 0.0, "unheld": 0.0}, "no held"),
                      ({"held": 1.0, "unheld": 0.25}, "outside")):
        refused = runner.compare(dict(got, moved=bad), want, limits)
        assert len(refused) == 1 and what in refused[0]


def test_the_reference_holds_the_slices_the_program_holds(tiny):
    """The reference's own slicing (the first dimension 8 divides, a weight
    none divides whole) gives the masters the program's state holds."""
    cell, c, master, _ = tiny
    state = llama.build_train_step(
        c, opt_share=tuple(cell.config["deployment"]["opt_share"]))[0](2)
    held = ref.held(master, cell.config)
    for a, b in zip(jax.tree_util.tree_leaves(state["master"]),
                    jax.tree_util.tree_leaves(held)):
        np.testing.assert_array_equal(a, b)
    shapes = {jax.tree_util.keystr(p): a.shape for p, a in
              jax.tree_util.tree_leaves_with_path(state["master"])}
    assert shapes["['wte']"] == (64, 64)
    assert shapes["['sparse'][0]['decay_log']"] == (1, 2)     # whole
    assert shapes["['sparse'][0]['q_conv_w']"] == (1, 4, 6)


# ---------------------------------------------------------- the configuration

def test_the_configuration_is_the_catalogs_cut_as_stated():
    config = load_cell(CELL).config
    # MODEL_CATALOG: a JSON-lines file of published model configurations,
    # one {"name", "source_url", "config"} object per line.
    catalog = os.environ.get("MODEL_CATALOG", "")
    if catalog and os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert config["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items()
                         if config.get(k, "absent") != v)
        assert differs == sorted(config["reduced"])
        assert {k: row["config"][k] for k in differs} == config["published"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    widths = ("hidden_size", "intermediate_size", "num_attention_heads",
              "num_key_value_heads", "linear_num_key_heads",
              "linear_num_value_heads", "linear_key_head_dim",
              "linear_value_head_dim", "linear_conv_kernel_dim")
    assert [config[k] for k in widths] == [3840, 11008, 30, 30, 30, 30, 96,
                                           192, 4]
    assert config["layer_types"] == ["linear_attention"] * 3 \
        + ["full_attention"]
    assert config["published"]["layer_types"] == config["layer_types"] * 8
    assert config["published"]["vocab_size"] == 8 * config["vocab_size"]
    assert config["deployment"]["opt_share"] == [0, 8]
    assert config["deployment"]["chips"] == 64
    assert {"block", "gdn_form", "gdn_output", "rope", "qk_norm", "init",
            "dtype", "padding", "optimizer"} <= set(config["assumed"])
    limits = config["tolerance"]
    assert (limits["loss"], limits["drop"], limits["rule"],
            limits["rule_back"]) == (2e-4, 2.4e-2, 1.5e-2, 1.8e-2)
    assert all(len(limits[f"{name}_why"]) > 200
               for name in ("loss", "drop", "rule", "rule_back"))
    assert config["memory"]["step_total_bytes"] == 9_294_018_560
    c = runner.program_config(config)
    assert c.post_norm and c.qk_norm and c.rope_theta is None
    assert c.linear_allow_neg_eigval and c.head_dim == 128
    counts = llama.count_params(c)
    assert counts["total"] == 928_862_196
    assert counts["layers"] == 3 * 215_570_172 + 185_809_920
    assert counts["embedding_and_head"] == 2 * 12544 * 3840
    traffic = load_cell(CELL).traffic
    assert (traffic["batch"], traffic["seq"]) == (4, 4096)


def test_the_runner_and_the_program_pad_the_heads_alike():
    """The widths the runner looks for the state at are the ones the
    program pads the heads to (each decides by its own rule)."""
    for dk in range(8, 400, 7):
        for dv in (dk, 2 * dk, 40, 192):
            assert runner.lane_widths(dk, dv) \
                == linear_attention.lane_widths(dk, dv)
    assert runner.lane_widths(96, 192) == (128, 256)


# ------------------------------------------------------- required operations

SHAPES = dict(hidden=3840, ffn=11008, linear_layers=3, linear_heads=30,
              key_dim=96, value_dim=192, taps=4, full_layers=1, vocab=12544,
              seq=4096)


def test_required_flops_by_hand():
    linear = 2 * (2 * 3840 * 2880 + 2 * 3840 * 5760 + 2 * 3840 * 30
                  + 5760 * 3840) + 2 * 4 * (2 * 2880 + 5760)
    assert linear == 177_408_000 + 92_160
    assert flops_gdn_hybrid.linear_projection_flops(
        hidden=3840, heads=30, key_dim=96, value_dim=192, taps=4) == linear
    rule = 7 * 96 * 192 * 30
    assert flops_gdn_hybrid.rule_flops(heads=30, key_dim=96,
                                       value_dim=192) == rule == 3_870_720
    full = 8 * 3840 * 3840 + 2 * 4096 * 3840
    mlp = 6 * 3840 * 11008
    forward = 3 * (linear + rule) + full + 4 * mlp + 2 * 3840 * 12544
    assert flops_gdn_hybrid.train_flops_per_token(**SHAPES) \
        == pytest.approx(3 * forward)
    assert 3 * forward == pytest.approx(5.4131e9, rel=1e-4)
    cell = load_cell(CELL)
    assert runner.shapes(cell) == SHAPES
    assert runner.flops_per_token(cell) == pytest.approx(3 * forward)


def test_the_rules_required_work_is_the_recurrences_at_the_published_widths():
    """7 d_k d_v a head and token forward and twice that backward; bytes one
    read of q, k (96), v (192), one log-decay and one write strength a
    head, and one write of o (192): the padding to 128 / 256 is no part of
    it."""
    flop, byte = flops_gdn_hybrid.rule_pass_cost(
        "fwd", tokens=16384, heads=30, key_dim=96, value_dim=192)
    assert flop == 16384 * 30 * 7 * 96 * 192
    assert byte == 16384 * 30 * (2 * (96 + 192) * 2 + 8)
    assert flops_gdn_hybrid.rule_pass_cost(
        "bwd", tokens=16384, heads=30, key_dim=96, value_dim=192) \
        == (2 * flop, 2 * byte)
    v5e = peaks.peaks_of("TPU v5 lite")
    # memory-bound by requirement: 111 FLOP a byte under the chip's 240
    assert flops_gdn_hybrid.least_seconds(flop, byte, v5e)[1] == "memory"


# ------------------------------------------------ what the compiled step says

def _loop(name, carried, condition, path):
    return (f"  %{name} = ({carried}) while(%tuple), condition=%{condition},"
            f' body=%body, metadata={{op_name="{path}"}}')


def _compiled(bound=64, state="f32[1,30,128,256]{3,2,1,0}"):
    """A compiled step's text with two loops of the rule, the scan of
    another width and another stage's loop."""
    carried = f"u32[], {state}, bf16[64,1,30,64,256]{{4,3,2,1,0}}"
    path = f"jit(step_fn)/jvp()/while/body/{stages.LINEAR_ATTN}/while"
    return "\n".join([
        "%cond.1 (arg: (u32[])) -> pred[] {",
        f"  %constant.2 = u32[] constant({bound})",
        "  ROOT %lt = pred[] compare(%a, %constant.2), direction=LT", "}",
        "%cond.2 (arg: (u32[])) -> pred[] {",
        "  %constant.3 = u32[] constant(5)",
        "  ROOT %lt = pred[] compare(%a, %constant.3), direction=LT", "}",
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        _loop("while.1", carried, "cond.1", path),
        _loop("while.2", carried, "cond.1",
              path.replace("jvp()", "transpose(jvp())")),
        _loop("while.3", "u32[], f32[1,30,96,192]{3,2,1,0}", "cond.2",
              path),
        _loop("while.4", carried, "cond.2",
              f"jit(step_fn)/jvp()/{stages.ATTN_CORE}/while"), "}"])


def test_the_scans_trips_are_read_from_the_compiled_loops():
    cell = load_cell(CELL)
    assert runner.state_loops(_compiled(), stages.LINEAR_ATTN, 30, 128, 256) \
        == [(64, 1), (64, 1)]
    assert runner.linear_attention_of(cell, _compiled()) == {
        "layers": {"linear": 3, "full": 1}, "scan_steps": 64, "chunk": 64,
        "heads": 30, "key_dim": 96, "value_dim": 192,
        "lane_widths": [128, 256], "tokens": 16384,
        "state_bytes": 30 * 128 * 256 * 4}
    # a state no longer float32, or no longer padded, is not found
    for state in ("bf16[1,30,128,256]{3,2,1,0}", "f32[1,30,96,192]{3,2,1,0}"):
        assert runner.linear_attention_of(
            cell, _compiled(state=state))["scan_steps"] is None
    assert runner.linear_attention_of(cell, _compiled(4096))["chunk"] == 1


def test_the_tiny_steps_own_compiled_loops(tiny):
    """The tiny program compiled here: its loops under the stage that carry
    a [rows, heads, d_k, d_v] float32 state (narrow heads: no padding) run
    sequence / chunk trips."""
    cell, c, _, _ = tiny
    init_fn, step, _ = runner.build(cell.config, None, {})
    state = jax.eval_shape(lambda: init_fn(0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = step.trace(state, tokens, tokens).lower().compile().as_text()
    assert set(runner.state_loops(text, stages.LINEAR_ATTN, 2, 24, 40)) \
        == {(128 // linear_attention.CHUNK, 2)}


# ----------------------------------------------------- readers, a hand trace

US = 1e-6


def _hand_hlo():
    fwd, bwd = "jit(step_fn)/jvp()/", "jit(step_fn)/transpose(jvp())/"
    scan = "while/body/closed_call/"

    def op(name, kind, path):
        return (f'  %{name} = f32[8,8]{{1,0}} {kind}(%p, %p), '
                f'metadata={{op_name="{path}"}}')

    return "\n".join([
        "HloModule jit_step_fn, is_scheduled=true", "",
        "ENTRY %main (p: f32[8,8]) -> f32[8,8] {",
        "  %p = f32[8,8]{1,0} parameter(0)",
        op("rule.1", "dot", fwd + scan + stages.LINEAR_ATTN
           + "/while/body/dot_general"),
        op("rule.2", "dot", bwd + scan + stages.LINEAR_ATTN
           + "/while/body/dot_general"),
        op("attn.3", "dot", fwd + scan + stages.ATTN_CORE + "/dot_general"),
        op("adam.4", "multiply", "jit(step_fn)/" + stages.OPTIMIZER + "/mul"),
        op("adam.5", "add", "jit(step_fn)/" + stages.OPTIMIZER + "/add"),
        "  ROOT %out = f32[8,8]{1,0} copy(%p)", "}", ""])


OP_US = {"rule.1": 60_000, "rule.2": 140_000, "attn.3": 9_000,
         "adam.4": 7_000, "adam.5": 3_000}
FACTS = {stages.LINEAR_ATTN: {
    "layers": {"linear": 3, "full": 1}, "scan_steps": 64, "chunk": 64,
    "heads": 30, "key_dim": 96, "value_dim": 192, "lane_widths": [128, 256],
    "tokens": 16384, "state_bytes": 3932160}}


@pytest.fixture()
def hand_run():
    hlo_text = _hand_hlo()
    op_s = {name: us * US for name, us in OP_US.items()}
    busy = sum(op_s.values())
    summary = tr.Summary(
        steps=2, chips=1, window_s=busy, busy_s=busy, category_s={},
        ops=tr.parse_hlo(hlo_text), op_s=op_s,
        op_calls={name: 2 for name in op_s}, collective_s=0.0,
        collective_exposed_s=0.0, device_ops=[], idle_gaps=[])
    program = types.SimpleNamespace(hlo_text=lambda: hlo_text, facts=FACTS,
                                    memory=None)
    return Run(None, program, peaks.peaks_of("TPU v5 lite"), 0, 0, 0, [],
               summary)


def test_the_new_readers_on_the_hand_trace(hand_run):
    ms = (60_000 + 140_000) * 1e3 * US / 2
    assert gated_delta_ms_per_step.read(hand_run) == pytest.approx(ms)
    # memory-bound by requirement: three passes' bytes over the bandwidth,
    # at the published widths
    byte = 16384 * 30 * (2 * (96 + 192) * 2 + 8)
    least = 3 * 3 * byte / 819e9
    assert _gated_delta.rule_least_seconds(hand_run) == pytest.approx(least)
    assert gated_delta_roofline.read(hand_run) \
        == pytest.approx(100 * least / (ms / 1e3))
    assert 0 < gated_delta_roofline.read(hand_run) < 100
    assert gated_delta_scan_steps.read(hand_run) == 64
    assert optimizer_ms_per_step.read(hand_run) \
        == pytest.approx((7_000 + 3_000) * 1e3 * US / 2)


def test_a_reader_whose_span_or_fact_is_gone_reads_none(hand_run):
    # the Kimi cell's facts carry one head width: not this layer's
    hand_run.program.facts = {stages.LINEAR_ATTN: {
        "layers": {"kda": 4, "mla": 1}, "scan_steps": 32, "heads": 32,
        "head_dim": 128, "tokens": 16384}}
    assert gated_delta_ms_per_step.read(hand_run) is None
    assert gated_delta_roofline.read(hand_run) is None
    assert gated_delta_scan_steps.read(hand_run) is None
    hand_run.program.facts = {}
    assert gated_delta_scan_steps.read(hand_run) is None
    hand_run.program.facts = FACTS
    hand_run.trace = None
    assert gated_delta_ms_per_step.read(hand_run) is None
    assert optimizer_ms_per_step.read(hand_run) is None
    assert gated_delta_scan_steps.read(hand_run) == 64


def test_the_cell_lists_the_six_common_readers_and_its_four():
    cell = load_cell(CELL)
    assert list(cell.layer_metrics) == [
        "compile_cache_hit_share", "compiles_in_window", "step_ms_p50",
        "step_temp_gb", "matmul_share", "remat_share",
        "gated_delta_ms_per_step", "gated_delta_roofline",
        "gated_delta_scan_steps", "optimizer_ms_per_step"]
    assert cell.config["attention"] == "flash" and cell.chips == 1
