"""What ISSUE 37 adds to the benchmark: the plain reference with
linear-attention and latent layers against the program at a tiny size (loss,
its fall after one update, the biases' move), the published 27-layer pattern
as the program parses it, the guide's share test (the shares' parts add up to
the uncut layer), the FLOP count against figures worked out by hand, the
configuration's cut against the catalog, what the runner reads from a
compiled step, and the three new readers on a hand-made trace."""
import dataclasses
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_kda_mla_moe, peaks
from benchmarks import trace_reduce as tr
from benchmarks.cells import load_cell
from benchmarks.layer_metrics import (Run, _linear_attn, _stages,
                                      linear_attn_ms_per_step,
                                      linear_attn_roofline,
                                      linear_attn_scan_steps)
from benchmarks.reference import kda_mla_moe as ref
from benchmarks.runners import kda_mla_moe as runner
from paddle_tpu.models import mla_moe, stages
from paddle_tpu.ops import linear_attention

CELL = "kimil-ep32share-pretrain-s2048"
SEQ = 64


@pytest.fixture(scope="module")
def tiny():
    config = load_cell(CELL, tiny=True).config
    c = runner.program_config(config)
    master = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), mla_moe.init_mla_moe_params(c, 2))
    ids = np.random.default_rng(4).integers(
        0, config["vocab_size"], (2, SEQ + 1), dtype=np.int32)
    return config, c, master, (ids[:, :-1], ids[:, 1:])


# ------------------------------------------- the program against the reference

def test_the_family_against_the_reference_on_loss_and_its_fall(tiny):
    """float32, tiny widths: step-0 loss, the fall after one AdamW update
    and one move of the biases, and the biases themselves."""
    config, c, master, seqs = tiny
    cell = load_cell(CELL, tiny=True)
    cotangent = runner.rule_cotangent(2, cell)[:, :SEQ]
    with jax.default_matmul_precision("highest"):
        rule = ref.check_rule(master, seqs[0], cotangent, config)
        rule_lower = ref.check_rule(master, seqs[0], cotangent, config,
                                    jnp.bfloat16)
        want = ref.check_step(jax.tree_util.tree_map(jnp.copy, master), seqs,
                              config)
        opt = config["optimizer"]
        init_fn, step = mla_moe.build_train_step(
            c, lr=opt["lr"], wd=opt["wd"], b1=opt["b1"], b2=opt["b2"])
        state = init_fn(2)
        offs = runner.rule_offs(state["params"], seqs[0], cotangent, c, rule)
        before = mla_moe._router_biases(state["master"])
        state, loss0 = step(state, *seqs)
        after = mla_moe._router_biases(state["master"])
        state, loss1 = step(state, *seqs)
    got = {"loss0": float(loss0), "loss1": float(loss1), "rule_off": offs[0],
           "rule_back_off": offs[1],
           "bias_moved_share": float((after != before).mean())}
    limits = {"loss": 2e-5, "drop": 5e-3, "rule": 1e-4, "rule_back": 1e-4}
    assert runner.compare(got, want, limits) == []
    # the rule alone, from operands the reference made itself: the float32
    # program is the recurrence to rounding, forward and backward, and a
    # reference whose state and log-decays are bfloat16 is refused by both
    lower = [runner.off(x, y) for x, y in zip(rule_lower, rule)]
    assert max(offs) < 2e-5 < 1e-3 < min(lower)
    for key, low, what in (("rule_off", lower[0], "chunked rule is"),
                           ("rule_back_off", lower[1], "rule's gradient")):
        refused = runner.compare(dict(got, **{key: low}), want, limits)
        assert len(refused) == 1 and what in refused[0]
    assert len(runner.compare(dict(got, bias_moved_share=0.0), want,
                              limits)) == 1
    np.testing.assert_array_equal(after, want["biases1"])
    assert want["bias_moved_share"] > 0.5
    assert want["pairs0"].shape == (4, config["published"]["num_experts"])
    assert int(want["pairs0"].sum()) == 4 * 2 * SEQ \
        * config["num_experts_per_token"]


@pytest.mark.parametrize("kind", ["linear", "full"])
def test_one_layer_of_each_kind_against_the_reference(tiny, kind):
    """The attention half alone, one row: the program's block functions
    against the reference's, on the parameters of a layer of that kind."""
    config, c, master, _ = tiny
    position = {"linear": 0, "full": 2}[kind]
    blk = jax.tree_util.tree_map(lambda a: a[0], master["sparse"][position])
    y = jax.random.normal(jax.random.PRNGKey(3), (1, SEQ, c.hidden_size))
    normed = ref.rms_norm(y[0], blk["ln1_g"], config["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        if kind == "linear":
            got, _ = mla_moe._linear_attention(y, blk, c)
            want = ref.linear_attention(normed, blk, config)
        else:
            got, _ = mla_moe._attention(y, blk, c)
            want = ref.latent_attention(normed, blk, config)
    assert float(jnp.abs(want).max()) > 1e-4
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=1e-6)


def test_the_reference_recurrence_by_hand():
    """`delta_rule` against the three lines of the recurrence in numpy,
    float64, a loop over tokens and heads."""
    rng = np.random.default_rng(0)
    s, h, d = 24, 2, 4
    q, k, v = (rng.normal(size=(s, h, d)) for _ in range(3))
    g = -rng.random((s, h, d))
    beta = rng.random((s, h))
    want = np.zeros((s, h, d))
    for head in range(h):
        S = np.zeros((d, d))
        for t in range(s):
            S = np.exp(g[t, head])[:, None] * S
            S = S + beta[t, head] * np.outer(
                k[t, head], v[t, head] - S.T @ k[t, head])
            want[t, head] = S.T @ q[t, head]
    got = ref.delta_rule(*(jnp.asarray(x, jnp.float32)
                           for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------ the published pattern

def test_the_published_pattern_is_a_dense_layer_six_periods_and_a_tail():
    config = load_cell(CELL, tiny=True).config
    published = dict(config, num_hidden_layers=27,
                     linear_attn_config=config["published"][
                         "linear_attn_config"])
    kinds = runner.layer_types(published)
    assert (kinds.count("linear"), kinds.count("full")) == (20, 7)
    assert [n + 1 for n, kind in enumerate(kinds) if kind == "full"] \
        == [4, 8, 12, 16, 20, 24, 27]
    c = runner.program_config(published)
    L, F = mla_moe.LINEAR, mla_moe.FULL
    assert c.segments == [("dense", (L,), False, 1),
                          ("sparse", (L, L, F, L), True, 6),
                          ("tail", (L, F), True, 1)]
    shapes = jax.eval_shape(lambda: mla_moe.init_mla_moe_params(c, 0))
    assert [len(shapes[g]) for g in ("dense", "sparse", "tail")] == [1, 4, 2]
    assert shapes["sparse"][2]["kv_a_w"].shape[0] == 6
    assert "kv_a_w" not in shapes["sparse"][0]
    found = ref.layers_in_order(mla_moe.init_mla_moe_params(c, 0), published)
    assert [(n, linear, sparse) for n, _, linear, sparse in found] == [
        (n + 1, kind == "linear", n > 0) for n, kind in enumerate(kinds)]
    # the cut: one whole period and no tail
    cut = runner.program_config(config)
    assert cut.segments == [("dense", (L,), False, 1),
                            ("sparse", (L, L, F, L), True, 1)]


def test_a_layer_named_twice_or_not_at_all_is_refused():
    config = load_cell(CELL, tiny=True).config
    spec = dict(config["linear_attn_config"], full_attn_layers=[4, 5])
    with pytest.raises(ValueError, match="name each of the 5 layers once"):
        runner.layer_types(dict(config, linear_attn_config=spec))
    with pytest.raises(ValueError, match="layer_types names"):
        dataclasses.replace(runner.program_config(config),
                            layer_types=("full",) * 4)


# ------------------------------------------------------------ the share test

def test_the_shares_parts_add_up_to_the_uncut_layer(tiny):
    """Model-configs guide, section 4: at a small size, the held experts'
    parts of all the shares (4 shares of 4 experts of 16 here, 32 of 8 of
    256 in the cell), with what every chip computes alike (attention, the
    shared expert, the residual) counted once, add up to what the uncut
    reference gives for the whole layer."""
    config, c, master, _ = tiny
    eps, total = config["rms_norm_eps"], config["published"]["num_experts"]
    held = config["num_experts"]
    key = jax.random.PRNGKey(5)
    layer = jax.tree_util.tree_map(lambda a: a[0], master["sparse"][0])
    experts = {n: 0.05 * jax.random.normal(
        jax.random.fold_in(key, i), (total,) + a.shape[1:])
        for i, (n, a) in enumerate(sorted(layer["experts"].items()))}
    x = jax.random.normal(key, (SEQ, c.hidden_size))

    def whole_layer(deployment, count, experts):
        cfg = dict(config, num_experts=count, deployment=deployment)
        return ref.layer(True, True, cfg, jnp.float32)(
            x, dict(layer, experts=experts))[0]

    with jax.default_matmul_precision("highest"):
        uncut = whole_layer({"experts_first": 0}, total, experts)
        # what every share computes alike: attention, shared expert, x
        y = ref.rms_norm(x, layer["ln1_g"], eps)
        alike = x + ref.linear_attention(y, layer, config)
        normed = ref.rms_norm(alike, layer["ln2_g"], eps)
        alike = alike + ref.swiglu(normed, layer["shared_gate_w"],
                                   layer["shared_up_w"],
                                   layer["shared_down_w"])
        parts = []
        for first in range(0, total, held):
            mine = {n: a[first:first + held] for n, a in experts.items()}
            share = whole_layer({"experts_first": first}, held, mine)
            parts.append(share - alike)         # its held experts' part
            # and the program's share is that share
            got, _ = mla_moe._block(
                x[None], dict(layer, experts=mine),
                dataclasses.replace(c, experts_held=(first, held)),
                sparse=True, want_ids=False, kind=mla_moe.LINEAR)
            np.testing.assert_allclose(got[0], share, rtol=2e-4, atol=2e-5)
    assert len(parts) == total // held == 4
    assert all(float(jnp.abs(p).max()) > 1e-4 for p in parts)
    np.testing.assert_allclose(alike + sum(parts), uncut, rtol=1e-4,
                               atol=1e-5)


# ------------------------------------------------------- required operations

SHAPES = dict(hidden=2304, linear_layers=4, linear_heads=32,
              linear_head_dim=128, taps=4, full_layers=1, heads=32,
              kv_rank=512, nope=128, rope=64, v_dim=128, dense_ffn=9216,
              expert_ffn=1024, shared=1, dense_layers=1, sparse_layers=4,
              router_outputs=256, held=8, k=8, vocab=20480, seq=2048)


def test_required_flops_by_hand():
    linear = 2 * (3 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096)
                  + 2304 * 32 + 4096 * 2304) + 2 * 4 * 3 * 4096
    assert linear == 78_921_728 + 98_304
    assert flops_kda_mla_moe.linear_projection_flops(
        hidden=2304, heads=32, head_dim=128, taps=4) == linear
    assert flops_kda_mla_moe.rule_flops(heads=32, head_dim=128) \
        == 7 * 128 * 128 * 32 == 3_670_016
    full = 2 * (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256
                + 32 * 128 * 2304)
    assert full == 2 * 29_114_368
    scores = 2048 * 32 * (192 + 128)
    sparse = 6 * 2304 * 1024 + 2 * 2304 * 256 + 6 * 2304 * 1024 * 8 * 8 / 256
    forward = 4 * (linear + 3_670_016) + full + scores + 6 * 2304 * 9216 \
        + 4 * sparse + 2 * 2304 * 20480
    assert flops_kda_mla_moe.train_flops_per_token(**SHAPES) \
        == pytest.approx(3 * forward)
    assert 3 * forward == pytest.approx(2.1218e9, rel=1e-3)
    cell = load_cell(CELL)
    assert runner.shapes(cell) == SHAPES
    assert runner.flops_per_token(cell) == pytest.approx(3 * forward)


def test_the_rules_required_work_is_the_recurrences():
    """7 d d a head and token forward and twice that backward, whatever
    computes it; bytes one read of q, k, v, g, beta and one write of o."""
    flop, byte = flops_kda_mla_moe.rule_pass_cost(
        "fwd", tokens=8192, heads=32, head_dim=128)
    assert flop == 8192 * 32 * 7 * 128 * 128
    assert byte == 8192 * 32 * (4 * 128 * 2 + 128 * 4 + 4)
    back = flops_kda_mla_moe.rule_pass_cost(
        "bwd", tokens=8192, heads=32, head_dim=128)
    assert back == (2 * flop, 2 * byte)
    v5e = peaks.peaks_of("TPU v5 lite")
    # memory-bound by requirement: 75 FLOP a byte under the chip's 240
    assert flops_kda_mla_moe.least_seconds(flop, byte, v5e)[1] == "memory"


# ----------------------------------------------------------- the configuration

def test_the_configuration_is_the_catalogs_cut_as_stated():
    config = load_cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert config["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items()
                         if config.get(k, "absent") != v)
        assert differs == sorted(config["reduced"])
        assert {k: row["config"][k] for k in differs} == config["published"]
    assert config["reduced"] == ["num_hidden_layers", "linear_attn_config",
                                 "num_experts", "vocab_size"]
    widths = ("hidden_size", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "intermediate_size",
              "moe_intermediate_size", "num_attention_heads",
              "num_experts_per_token", "routed_scaling_factor")
    assert [config[k] for k in widths] == [2304, 512, 128, 64, 128, 9216,
                                           1024, 32, 8, 2.446]
    spec, whole = (config["linear_attn_config"],
                   config["published"]["linear_attn_config"])
    assert {k: spec[k] for k in ("num_heads", "head_dim",
                                 "short_conv_kernel_size")} \
        == {k: whole[k] for k in ("num_heads", "head_dim",
                                  "short_conv_kernel_size")} \
        == {"num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4}
    assert (spec["kda_layers"], spec["full_attn_layers"]) \
        == ([1, 2, 3, 5], [4])
    assert config["deployment"]["chips_sharing_a_layer"] == 32
    assert "one chip of 32 that share each layer" \
        in config["deployment"]["how"]
    assert config["published"]["num_experts"] \
        == 32 * config["num_experts"] == 256
    assert config["published"]["vocab_size"] == 8 * config["vocab_size"]
    assert {"kda_form", "kda_ranks", "qk_norm_eps", "kda_init", "kda_dtype",
            "mla_form", "router", "optimizer",
            "balance_steps"} <= set(config["assumed"])
    limits = config["tolerance"]
    assert (limits["loss"], limits["drop"], limits["rule"],
            limits["rule_back"]) == (2e-4, 8e-3, 1e-2, 1.3e-2)
    assert all(len(limits[f"{name}_why"]) > 200
               for name in ("loss", "drop", "rule", "rule_back"))
    c = runner.program_config(config)
    assert c.q_lora_rank is None and c.mla_use_nope and c.hc_mult is None
    assert c.held == (0, 8) and c.n_routed_experts == 256
    counts = mla_moe.count_params(c)
    assert counts["total"] == 602_434_432
    assert counts["routed_experts"] == 4 * 8 * 7_077_888
    traffic = load_cell(CELL).traffic
    assert (traffic["batch"], traffic["seq"]) == (8, 2048)


# ------------------------------------------------ what the compiled step says

def _loop(name, carried, condition, path):
    return (f"  %{name} = ({carried}) while(%tuple), condition=%{condition},"
            f' body=%body, metadata={{op_name="{path}"}}')


def _compiled(bound=32, second="cond.1"):
    """A compiled step's text with two loops of the rule (the second's
    condition is `second`), the layers' scan and another stage's loop."""
    state = ("u32[], f32[1,32,128,128]{3,2,1,0}, "
             "bf16[32,1,32,64,128]{4,3,2,1,0}")
    path = f"jit(step_fn)/jvp()/while/body/{stages.LINEAR_ATTN}/while"
    return "\n".join([
        "%cond.1 (arg: (u32[])) -> pred[] {",
        "  %constant.1 = u32[] constant(0)",
        f"  %constant.2 = u32[] constant({bound})",
        "  ROOT %lt = pred[] compare(%a, %constant.2), direction=LT", "}",
        "%cond.2 (arg: (u32[])) -> pred[] {",
        "  %constant.3 = u32[] constant(5)",
        "  ROOT %lt = pred[] compare(%a, %constant.3), direction=LT", "}",
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        _loop("while.1", state, "cond.1", path),
        _loop("while.2", state, second,
              path.replace("jvp()", "transpose(jvp())")),
        # the layers' scan carries no state of the rule's
        _loop("while.3", "u32[], bf16[4,2048,2304]{2,1,0}", "cond.2",
              "jit(step_fn)/jvp()/while"),
        # another stage's loop with such a tensor is not the rule's
        _loop("while.4", state, "cond.2",
              f"jit(step_fn)/jvp()/{stages.EXPERTS}/while"), "}"])


def test_the_scans_trips_are_read_from_the_compiled_loops():
    assert runner.state_loops(_compiled(), stages.LINEAR_ATTN, 32, 128) \
        == [(32, 1), (32, 1)]
    cell = load_cell(CELL)
    assert runner.linear_attention_of(cell, _compiled()) == {
        "layers": {"kda": 4, "mla": 1}, "scan_steps": 32, "chunk": 64,
        "heads": 32, "head_dim": 128, "tokens": 16384,
        "state_bytes": 1 * 32 * 128 * 128 * 4}
    # a loop over tokens is no chunked scan; loops that disagree, or none,
    # read nothing
    assert runner.linear_attention_of(cell, _compiled(2048))["chunk"] == 1
    assert runner.linear_attention_of(
        cell, _compiled(second="cond.2"))["scan_steps"] is None
    assert runner.linear_attention_of(cell, "")["scan_steps"] is None


def test_the_tiny_steps_own_compiled_loops(tiny):
    """The tiny program compiled here: its loops under the stage that carry
    a [rows, heads, d, d] float32 state run sequence / chunk trips."""
    config, c, _, _ = tiny
    _, step = mla_moe.build_train_step(c)
    state = jax.eval_shape(lambda: mla_moe.build_train_step(c)[0](0))
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
    text = step.trace(state, tokens, tokens).lower().compile().as_text()
    spec = config["linear_attn_config"]
    loops = set(runner.state_loops(text, stages.LINEAR_ATTN,
                                   spec["num_heads"], spec["head_dim"]))
    assert loops == {(128 // linear_attention.CHUNK, 2)}
    assert "linear_chunk" not in config         # the op's constant, no knob


# ----------------------------------------------------- readers, a hand trace

US = 1e-6


def _hand_hlo():
    fwd, bwd = "jit(step_fn)/jvp()/", "jit(step_fn)/transpose(jvp())/"
    scan = "while/body/closed_call/"

    def op(name, kind, path):
        return (f'  %{name} = f32[8,8]{{1,0}} {kind}(%p, %p), '
                f'metadata={{op_name="{path}"}}')

    def fused(name, *paths):
        return [f"%{name} (q: f32[8,8]) -> f32[8,8] {{",
                "  %q = f32[8,8]{1,0} parameter(0)",
                *(op(f"{name}.{i}", "multiply", path).replace("%p", "%q")
                  for i, path in enumerate(paths)), "}", ""]

    def unnamed(name, called):
        return (f"  %{name} = f32[8,8]{{1,0}} fusion(%p), kind=kLoop, "
                f"calls=%{called}")

    chain = fwd + scan + stages.LINEAR_ATTN + "/mul"
    lines = [
        "HloModule jit_step_fn, is_scheduled=true", "",
        # fusions whose own instruction the compiler left without a name
        *fused("chain", chain, chain.replace("/mul", "/exp")),
        *fused("mixed", chain, fwd + scan + stages.ROUTER + "/mul"),
        *fused("bare"),
        "ENTRY %main (p: f32[8,8]) -> f32[8,8] {",
        "  %p = f32[8,8]{1,0} parameter(0)",
        op("rule.1", "dot", fwd + scan + stages.LINEAR_ATTN
           + "/while/body/dot_general"),
        op("conv.2", "multiply", fwd + scan + stages.LINEAR_ATTN + "/mul"),
        op("rule.3", "dot", bwd + scan + "checkpoint/rematted_computation/"
           + stages.LINEAR_ATTN + "/while/body/dot_general"),
        op("rule.4", "dot", bwd + scan + "checkpoint/" + stages.LINEAR_ATTN
           + "/while/body/dot_general"),
        # the latent layer's kernel and the projections are other stages'
        op("attn.5", "dot", fwd + scan + stages.ATTN_CORE + "/dot_general"),
        op("qkv.6", "dot", fwd + scan + stages.ATTN_QKV + "/dot_general"),
        unnamed("fusion.7", "chain"), unnamed("fusion.8", "mixed"),
        unnamed("fusion.9", "bare"),
        "  ROOT %out = f32[8,8]{1,0} copy(%p)", "}", ""]
    return "\n".join(lines)


OP_US = {"rule.1": 40_000, "conv.2": 10_000, "rule.3": 50_000,
         "rule.4": 100_000, "attn.5": 7_000, "qkv.6": 3_000,
         "fusion.7": 30_000, "fusion.8": 2_000, "fusion.9": 1_000}
FACTS = {stages.LINEAR_ATTN: {"layers": {"kda": 4, "mla": 1}, "scan_steps": 32,
                         "chunk": 64, "heads": 32, "head_dim": 128,
                         "tokens": 16384, "state_bytes": 2097152}}


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
    where = _stages.placed(hand_run)
    assert where["rule.1"] == (stages.LINEAR_ATTN, "forward")
    assert where["rule.3"] == (stages.LINEAR_ATTN, "remat")
    assert where["rule.4"] == (stages.LINEAR_ATTN, "backward")
    # a fusion without a name of its own is the stage's by what it calls,
    # when all that is named in there is the stage's
    assert where["fusion.7"] == where["fusion.8"] == (None, "forward")
    assert _linear_attn.stages_inside(_hand_hlo()) == {
        "fusion.7": {stages.LINEAR_ATTN},
        "fusion.8": {stages.LINEAR_ATTN, stages.ROUTER}, "fusion.9": set()}
    ms = (40_000 + 10_000 + 50_000 + 100_000 + 30_000) * 1e3 * US / 2
    assert linear_attn_ms_per_step.read(hand_run) == pytest.approx(ms)
    # memory-bound by requirement: three passes' bytes over the bandwidth
    byte = 16384 * 32 * (4 * 128 * 2 + 128 * 4 + 4)
    least = 4 * 3 * byte / 819e9
    assert linear_attn_roofline.read(hand_run) \
        == pytest.approx(100 * least / (ms / 1e3))
    assert 0 < linear_attn_roofline.read(hand_run) < 100
    assert linear_attn_scan_steps.read(hand_run) == 32


def test_a_reader_whose_span_or_fact_is_gone_reads_none(hand_run,
                                                        monkeypatch):
    text = _hand_hlo()
    lost = text.replace(stages.LINEAR_ATTN, "gone")
    hand_run.program.hlo_text = lambda: lost
    assert linear_attn_ms_per_step.read(hand_run) is None
    assert linear_attn_roofline.read(hand_run) is None
    hand_run.program.hlo_text = lambda: text
    hand_run._stages_placed = None
    # the parent's vocabulary has no such stage, and its runners no such fact
    eleven = types.SimpleNamespace(
        ALL=stages.ALL[:11], OPTIMIZER=stages.OPTIMIZER)
    monkeypatch.setattr(_stages, "vocabulary", lambda: eleven)
    assert linear_attn_ms_per_step.read(hand_run) is None
    assert linear_attn_roofline.read(hand_run) is None
    monkeypatch.setattr(_stages, "vocabulary", lambda: None)
    assert linear_attn_ms_per_step.read(hand_run) is None
    monkeypatch.undo()
    hand_run.program.facts = {}
    assert linear_attn_roofline.read(hand_run) is None
    assert linear_attn_scan_steps.read(hand_run) is None
    hand_run.trace = None
    hand_run.program.facts = FACTS
    assert linear_attn_ms_per_step.read(hand_run) is None
    assert linear_attn_scan_steps.read(hand_run) == 32


def test_the_cell_lists_the_six_common_readers_and_its_three():
    cell = load_cell(CELL)
    assert list(cell.layer_metrics) == [
        "compile_cache_hit_share", "compiles_in_window", "step_ms_p50",
        "step_temp_gb", "matmul_share", "remat_share",
        "linear_attn_ms_per_step", "linear_attn_roofline",
        "linear_attn_scan_steps"]
    assert cell.config["attention"] == "flash"
