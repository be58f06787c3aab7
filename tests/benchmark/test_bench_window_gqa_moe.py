"""What ISSUE 32 adds to the benchmark: the FLOP and byte counts against
figures worked out by hand, the plain reference's pieces against equations
written out here in numpy, the runner's comparison and lowered-step checks
on made-up figures, the configuration's cut against the catalog, and the
six new readers on a hand-made trace."""
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops_window_gqa_moe as flops
from benchmarks import peaks
from benchmarks import trace_reduce as tr
from benchmarks.cells import load_cell
from benchmarks.layer_metrics import (Run, _gqa_flash, gqa_flash_bwd_roofline,
                                      gqa_flash_fwd_roofline,
                                      gqa_flash_ms_per_step,
                                      held_load_imbalance,
                                      sparse_ffn_ms_per_step,
                                      window_tiles_visited_share)
from benchmarks.reference import window_gqa_moe as ref
from benchmarks.runners import window_gqa_moe as runner

CELL = "mellum2-ep4share-pretrain-s4096"
WINDOW, FULL = "sliding_attention", "full_attention"


# ------------------------------------------------------ FLOPs and bytes

@pytest.mark.parametrize("seq, window", [(4096, 1024), (4096, None),
                                         (128, 32), (64, 64), (64, 100),
                                         (7, 1)])
def test_visible_pairs_against_a_count(seq, window):
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    assert flops.visible_pairs(seq, window) == int(seen.sum())


def test_required_flops_of_the_cell_by_hand():
    """ISSUE 32's figures: a layer's products, the held experts at the
    balanced share, each kind's attention on its own pairs, the head."""
    cell = load_cell(CELL)
    attn = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    assert attn == 21_233_664
    expert = 3 * 2304 * 896
    assert expert == 6_193_152
    assert flops.visible_pairs(4096, 1024) == 3_670_528
    assert flops.visible_pairs(4096) == 8_390_656
    window = 4 * 4096 * 3_670_528 / 4096
    full = 4 * 4096 * 8_390_656 / 4096
    assert (round(window / 1e6, 2), round(full / 1e6, 2)) == (14.68, 33.56)
    forward = 4 * 2 * (attn + 2304 * 64) + 4 * 2 * (8 * 16 / 64) * expert \
        + 3 * window + full + 2 * 2304 * 24576
    assert round(forward / 1e6, 1) == 461.0
    got = runner.flops_per_token(cell)
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert round(got / 1e9, 3) == 1.383
    assert round(got * 16384 / 1e12, 2) == 22.66
    assert flops.pairs_per_token(k=8, held=16, router_outputs=64) == 2.0


def test_kernel_cost_reads_k_and_v_once_a_kv_head():
    cost = dict(batch=4, heads=32, kv_heads=4, seq=4096, head_dim=128)
    flop, byte = flops.gqa_flash_pass_cost("fwd", **cost)
    assert flop == 2 * 2 * 4 * 32 * 8_390_656 * 128
    wide, narrow = 4 * 4096 * 32 * 128 * 2, 4 * 4096 * 4 * 128 * 2
    assert byte == 2 * wide + 2 * narrow + 4 * 32 * 4096 * 4
    flop_w, byte_w = flops.gqa_flash_pass_cost("fwd", window=1024, **cost)
    assert byte_w == byte
    assert flop_w / flop == pytest.approx(3_670_528 / 8_390_656)
    flop_b, byte_b = flops.gqa_flash_pass_cost("bwd", **cost)
    assert flop_b == 2.5 * flop
    assert byte_b == 4 * wide + 4 * narrow + 4 * 32 * 4096 * 4
    # with as many K/V heads as query heads it is `flops.flash_pass_cost`,
    # but for the diagonal's own pairs, which that one counts half of
    from benchmarks import flops as plain
    got = flops.gqa_flash_pass_cost("bwd", batch=2, heads=8, kv_heads=8,
                                    seq=512, head_dim=64)
    want = plain.flash_pass_cost("bwd", bh=16, seq=512, head_dim=64,
                                 causal=True)
    assert got == (want[0] * 513 / 512, want[1])


# --------------------------- the reference's pieces against numpy, by hand

@pytest.fixture(scope="module")
def tiny():
    return load_cell(CELL, tiny=True).config


def test_reference_attention_by_hand(tiny):
    """One window layer and one full one: rope by each kind's table,
    query head h on K/V head h // 2, the mask from i and j, in float64."""
    rng = np.random.default_rng(0)
    s, h, heads, kv, d = 48, 64, 4, 2, 16
    x = rng.normal(size=(s, h)).astype(np.float32)
    p = {k: (rng.normal(size=shape) * 0.2).astype(np.float32)
         for k, shape in (("q_w", (h, heads * d)), ("k_w", (h, kv * d)),
                          ("v_w", (h, kv * d)))}

    def by_hand(kind, window, of):
        freqs, factor = (np.asarray(a, np.float64) for a in ref.inv_freq(
            d, tiny["rope_parameters"][kind]))
        angle = np.arange(s)[:, None] * freqs[None, :]
        cos, sin = np.cos(angle) * factor, np.sin(angle) * factor

        def rot(a):                                         # [s, n, d]
            lo, hi = a[..., :d // 2], a[..., d // 2:]
            c, sn = cos[:, None], sin[:, None]
            return np.concatenate([lo * c - hi * sn, hi * c + lo * sn], -1)

        x64 = x.astype(np.float64)
        q = rot((x64 @ p["q_w"]).reshape(s, heads, d))
        k = rot((x64 @ p["k_w"]).reshape(s, kv, d))
        v = (x64 @ p["v_w"]).reshape(s, kv, d)
        out = np.zeros((s, heads, d))
        for head in range(heads):
            g = of(head)
            for i in range(s):
                first = 0 if window is None else max(0, i - window + 1)
                a = k[first:i + 1, g] @ q[i, head] / np.sqrt(d)
                w = np.exp(a - a.max())
                out[i, head] = (w / w.sum()) @ v[first:i + 1, g]
        return out.reshape(s, heads * d)

    with jax.default_matmul_precision("highest"):
        for kind, window in ((WINDOW, tiny["sliding_window"]), (FULL, None)):
            got = ref.attention(jnp.asarray(x), p, kind, tiny)
            np.testing.assert_allclose(
                got, by_hand(kind, window, lambda h: h // 2), rtol=2e-4,
                atol=2e-5)
        # the two wrong models the limits must refuse
        got = ref.attention(jnp.asarray(x), p, WINDOW, tiny, window=None)
        np.testing.assert_allclose(got, by_hand(WINDOW, None,
                                                lambda h: h // 2),
                                   rtol=2e-4, atol=2e-5)
        got = ref.attention(jnp.asarray(x), p, FULL, tiny, group_of="modulo")
        np.testing.assert_allclose(got, by_hand(FULL, None, lambda h: h % 2),
                                   rtol=2e-4, atol=2e-5)


def test_reference_queries_in_blocks_change_nothing(tiny, monkeypatch):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    p = {k: jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
         for k, shape in (("q_w", (64, 64)), ("k_w", (64, 32)),
                          ("v_w", (64, 32)))}
    whole = ref.attention(x, p, WINDOW, tiny)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    np.testing.assert_allclose(ref.attention(x, p, WINDOW, tiny), whole,
                               rtol=1e-5, atol=1e-6)


def test_reference_router_and_held_experts_by_hand(tiny):
    rng = np.random.default_rng(2)
    s, h, f, e, k, held = 24, 64, 32, 16, 8, 4
    x = rng.normal(size=(s, h))
    p = {"router_w": rng.normal(size=(h, e)) * 0.3,
         "experts": {"gate_w": rng.normal(size=(held, h, f)) * 0.2,
                     "up_w": rng.normal(size=(held, h, f)) * 0.2,
                     "down_w": rng.normal(size=(held, f, h)) * 0.2}}
    z = x @ p["router_w"]
    prob = np.exp(z - z.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    want, drawn = np.zeros((s, h)), np.zeros(e, int)
    for t in range(s):
        chosen = np.argsort(-prob[t])[:k]
        drawn[chosen] += 1
        for expert in chosen:
            if expert < held:
                a = x[t] @ p["experts"]["gate_w"][expert]
                y = (a / (1 + np.exp(-a))
                     * (x[t] @ p["experts"]["up_w"][expert])) \
                    @ p["experts"]["down_w"][expert]
                want[t] += prob[t, expert] / prob[t, chosen].sum() * y
    f32 = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), p)
    with jax.default_matmul_precision("highest"):
        got, got_drawn, p_sum = ref.routed_ffn(jnp.asarray(x, jnp.float32),
                                               f32, tiny)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(got_drawn, drawn)
    np.testing.assert_allclose(p_sum, prob.sum(0), rtol=1e-4)
    # the balance term of this one sequence, as the issue writes it
    share = drawn / s
    assert share.sum() == pytest.approx(k)
    np.testing.assert_allclose(
        ref.balance_of(jnp.asarray(share[None]), p_sum[None] / s),
        e * (share * prob.mean(0)).sum(), rtol=1e-4)


def test_a_batchs_balance_term_is_not_the_mean_of_its_sequences(tiny):
    """F and P are both means over the batch: `batch_loss` counts F over
    all the sequences first; the parts of the sequences then add up."""
    from paddle_tpu.models.llama import init_llama_params
    cell = load_cell(CELL, tiny=True)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        init_llama_params(runner.program_config(tiny), 5))
    # routers far from balance, so that the two sequences' loads differ
    params["blocks"]["router_w"] = params["blocks"]["router_w"] * 40.0
    ids = np.random.default_rng(3).integers(0, tiny["vocab_size"], (2, 65),
                                            dtype=np.int32)
    seqs = (ids[:, :-1], ids[:, 1:])
    grad_fn = ref._grad_fn(tiny, jnp.dtype("float32"))
    loss, lm, balance, drawn, _ = ref.batch_loss(grad_fn, params, seqs, tiny)
    assert loss == pytest.approx(lm + tiny["router_aux_loss_coef"] * balance)
    parts = [ref.parts(params, t, l, tiny) for t, l in zip(*seqs)]
    share = sum(p[1] for p in parts) / 128
    p_mean = sum(p[2] for p in parts) / 128
    assert balance == pytest.approx(float(ref.balance_of(share, p_mean)),
                                    rel=1e-5)
    alone = np.mean([float(ref.balance_of(p[1] / 64, p[2] / 64))
                     for p in parts])
    assert abs(alone - balance) > 1e-3 * balance
    np.testing.assert_array_equal(drawn, sum(p[1] for p in parts))
    assert lm == pytest.approx(sum(float(p[0][0]) for p in parts) / 128,
                               rel=1e-5)
    del cell


def test_nll_is_the_form_the_harness_takes(tiny):
    from paddle_tpu.models.llama import init_llama_params
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        init_llama_params(runner.program_config(tiny), 5))
    ids = np.random.default_rng(3).integers(0, tiny["vocab_size"], 65,
                                            dtype=np.int32)
    (total, count), grads = jax.value_and_grad(
        lambda p: ref.nll(p, ids[:-1], ids[1:], tiny), has_aux=True)(params)
    assert int(count) == 64 and np.isfinite(float(total))
    assert float(total) / 64 > np.log(tiny["vocab_size"]) - 0.5
    assert all(bool(jnp.isfinite(g).all())
               for g in jax.tree_util.tree_leaves(grads))
    masters = ref.as_the_forward_sees(params, dict(tiny, dtype="bfloat16"))
    assert (masters["blocks"]["router_w"]
            == params["blocks"]["router_w"]).all()
    assert (masters["wte"] == params["wte"].astype(jnp.bfloat16).astype(
        jnp.float32)).all()


# ------------------------------------------------- the runner's own checks

def test_compare_holds_each_term_to_its_own_limit():
    tolerance = {"loss": 1e-4, "balance": 1e-3, "drop": 1e-2}
    reference = {"loss0": 10.0, "loss1": 9.5, "lm0": 9.992, "balance0": 8.0}
    good = dict(reference, loss0=10.0005, loss1=9.5005, lm0=9.9925,
                balance0=8.004)
    assert runner.compare(good, reference, tolerance) == []
    # a balance term that is gone hides in the scalar: 0.008 of 10
    lost = dict(good, balance0=0.0)
    assert len(runner.compare(lost, reference, tolerance)) == 1
    assert "balance0" in runner.compare(lost, reference, tolerance)[0]
    assert "lm0" in runner.compare(dict(good, lm0=9.99), reference,
                                   tolerance)[0]
    assert "fell" in runner.compare(dict(good, loss1=9.52), reference,
                                    tolerance)[0]


def test_lowered_problems_tell_a_score_tensor_from_q():
    """At 4,096 tokens q is [4, 4096, 4096] too: only a tensor of rank 4 or
    more that ends in [S, S] is a score tensor; and K or V repeated to the
    query heads shows as [B, S, kv, rep, d]."""
    cell = load_cell(CELL)
    flash = ("stablehlo.custom_call @tpu_custom_call(%1) : "
             "tensor<4x4096x4096xbf16>")

    def problems(text):
        return runner.lowered_problems(
            cell, types.SimpleNamespace(as_text=lambda: text))

    assert problems(flash) == []
    assert problems("tensor<4x4096x512xbf16>") != []        # no kernel
    grouped = flash + " tensor<4x4x8x4096x4096xf32>"
    assert any("4096, 4096" in p for p in problems(grouped))
    plain = flash + " tensor<4x32x4096x4096xf32>"
    assert len(problems(plain)) == 2        # `_trainer`'s check and ours
    repeated = flash + " tensor<4x4096x4x8x128xbf16>"
    assert any("repeated" in p for p in problems(repeated))


def test_pairs_off_and_routing_by_hand():
    drawn = np.array([[6, 2, 0, 0], [2, 2, 2, 2]])
    reference = np.array([[4, 4, 0, 0], [1, 1, 1, 1]])
    assert runner.pairs_off(drawn, reference) == pytest.approx(0.125)
    config = {"deployment": {"experts_first": 1}, "num_experts": 2,
              "num_experts_per_tok": 2}
    found = runner.routing(drawn, config, tokens=4)
    assert found["held_share"] == pytest.approx((2 / 8 + 4 / 8) / 2)
    assert found["fullest_over_balanced"] == pytest.approx(2 / 2)
    assert found["emptiest_over_balanced"] == 0.0
    assert found["dropped_pairs"] == 0


# ------------------------------------------------------- the configuration

def test_the_configuration_states_its_cut():
    """Every width is the source's; `reduced` is the five keys of depth,
    experts held and vocabulary; the published counts and the deployment
    stand beside the held ones."""
    config = load_cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert config["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items()
                         if config.get(k, "absent") != v)
        assert differs == sorted(config["reduced"])
        assert config["published"] == {
            k: row["config"][k] for k in ("num_hidden_layers", "num_experts",
                                          "vocab_size")}
        period = row["config"]["layer_types"][:4]
        assert row["config"]["layer_types"] == period * 7
        assert config["layer_types"] == period
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "mlp_layer_types", "num_experts",
                                 "vocab_size"]
    widths = ("hidden_size", "head_dim", "num_attention_heads",
              "num_key_value_heads", "intermediate_size",
              "moe_intermediate_size", "num_experts_per_tok",
              "sliding_window")
    assert [config[k] for k in widths] == [2304, 128, 32, 4, 7168, 896, 8,
                                           1024]
    assert config["deployment"]["chips_sharing_a_layer"] == 4
    assert config["published"]["num_experts"] \
        == 4 * config["num_experts"] == 64
    assert config["published"]["vocab_size"] == 4 * config["vocab_size"]
    assert config["attention"] == "flash"
    assert {"router_aux_loss_coef", "qk_norm", "initializer_range",
            "router_dtype", "optimizer", "balance_steps"} \
        <= set(config["assumed"])
    assert config["optimizer"]["lr"] == 1e-6
    assert (config["balance_steps"], config["balance_rate"]) == (120, 2e-4)
    assert "to be set" not in json.dumps(config["tolerance"]).lower()
    assert {"loss", "balance", "drop"} < set(config["tolerance"])
    c = runner.program_config(config)
    assert (c.num_heads, c.kv_heads, c.head_dim) == (32, 4, 128)
    assert c.num_heads * c.head_dim == 4096 != c.hidden_size
    assert c.held == (0, 16) and c.num_experts == 64
    assert c.period == (WINDOW,) * 3 + (FULL,)
    assert c.router_aux_loss_coef == 0.001
    from paddle_tpu.models.llama import count_params
    assert count_params(c)["total"] == 595_153_152
    traffic = load_cell(CELL).traffic
    assert traffic["batch"] * traffic["seq"] == 16384
    tiny = load_cell(CELL, tiny=True)
    assert tiny.config["sliding_window"] < tiny.traffic["seq"]
    assert tiny.config["num_key_value_heads"] \
        < tiny.config["num_attention_heads"]


def test_the_cell_visits_21_of_64_tiles_in_a_window_layer():
    cell = load_cell(CELL)
    tiles = runner.tiles(cell)
    assert tiles["blocks"] == [512, 512]
    assert tiles[WINDOW] == [14, 7, 43] and tiles[FULL] == [8, 28, 28]
    assert tiles["causal"] == tiles[FULL]
    attention = runner.attention(cell)
    assert attention["layers"] == {FULL: 1, WINDOW: 3}
    assert (attention["heads"], attention["kv_heads"],
            attention["window"]) == (32, 4, 1024)


# ----------------------------------------------------- readers, a hand trace

US = 1e-6
Q, KV, OUT = (4, 4096, 4096), (4, 4096, 512), (4, 4096, 4096)
ROWS = (4, 32, 8, 1, 512)


def _mosaic(operands, results):
    return tr.Op("custom-call", "mosaic", "mosaic: hand", frozenset(), False,
                 tuple(operands), tuple(results))


@pytest.fixture()
def hand_run():
    cell = load_cell(CELL)
    ops = {
        "fwd.window": _mosaic([Q, KV, KV], [OUT, ROWS]),
        "fwd.full": _mosaic([Q, KV, KV], [OUT, ROWS]),
        "bwd.window": _mosaic([Q, KV, KV, OUT, ROWS, ROWS], [Q, KV, KV]),
        "bwd.full": _mosaic([Q, KV, KV, OUT, ROWS, ROWS], [Q, KV, KV]),
        # XLA's grouped matmul: a stack of the held experts' matrices
        "grouped": _mosaic([(65536, 2304), (16, 2304, 896), (16,)],
                           [(65536, 896)]),
        # not ours: as many K/V heads as query heads
        "other": _mosaic([Q, Q, Q], [OUT, ROWS]),
    }
    op_us = {"fwd.window": 3 * 2 * 3000, "fwd.full": 2 * 6000,
             "bwd.window": 3 * 8000, "bwd.full": 15000, "grouped": 900,
             "other": 5000}
    calls = {"fwd.window": 6, "fwd.full": 2, "bwd.window": 3, "bwd.full": 1,
             "grouped": 4, "other": 1}
    op_s = {name: us * US for name, us in op_us.items()}
    summary = tr.Summary(
        steps=1, chips=1, window_s=sum(op_s.values()),
        busy_s=sum(op_s.values()), category_s={}, ops=ops, op_s=op_s,
        op_calls=calls, collective_s=0.0, collective_exposed_s=0.0,
        device_ops=[], idle_gaps=[])
    facts = {"attention": dict(runner.attention(cell),
                               tiles=runner.tiles(cell)),
             "moe": {"fullest_over_balanced": 1.125, "tokens": 16384,
                     "layers": 4, "shapes": runner.shapes(cell)}}
    program = types.SimpleNamespace(hlo_text=lambda: "", facts=facts,
                                    memory=None)
    return Run(cell, program, peaks.peaks_of("TPU v5 lite"), 0, 0, 0, [],
               summary)


def test_the_kernel_readers_on_the_hand_trace(hand_run):
    found = _gqa_flash.passes(hand_run)
    assert found["fwd"] == pytest.approx((30000 * US, 8))
    assert found["bwd"] == pytest.approx((39000 * US, 4))
    assert gqa_flash_ms_per_step.read(hand_run) == pytest.approx(69.0)
    v5e = hand_run.peaks
    cost = dict(batch=4, heads=32, kv_heads=4, seq=4096, head_dim=128)
    for reader, kind, us in ((gqa_flash_fwd_roofline, "fwd", 30000 / 2),
                             (gqa_flash_bwd_roofline, "bwd", 39000)):
        least = sum(n * flops.least_seconds(*flops.gqa_flash_pass_cost(
            kind, window=w, **cost), v5e)[0]
            for n, w in ((3, 1024), (1, None)))
        assert reader.read(hand_run) == pytest.approx(
            100 * least / (us * US))
        assert 0 < reader.read(hand_run) < 100
    # a kernel that lost its window takes the full layer's time in all four
    before = gqa_flash_fwd_roofline.read(hand_run)
    hand_run.trace.op_s["fwd.window"] = 3 * 2 * 6000 * US
    assert gqa_flash_fwd_roofline.read(hand_run) == pytest.approx(
        before * 30000 / 48000)


def test_the_counter_readers_on_the_hand_trace(hand_run):
    assert window_tiles_visited_share.read(hand_run) == 0.6875
    assert held_load_imbalance.read(hand_run) == 1.125
    facts = hand_run.program.facts
    facts["attention"]["tiles"][WINDOW] = facts["attention"]["tiles"][FULL]
    assert window_tiles_visited_share.read(hand_run) == 1.0


def test_on_a_program_without_the_mechanisms_the_readers_read_nothing(
        hand_run):
    """The parent's facts: an attention without `kv_heads` or tiles, no
    routed feed-forward; and a run without a trace."""
    facts = hand_run.program.facts
    hand_run.program.facts = {"attention": {
        k: v for k, v in facts["attention"].items()
        if k not in ("kv_heads", "tiles", "layers", "window")}}
    for reader in (gqa_flash_ms_per_step, gqa_flash_fwd_roofline,
                   gqa_flash_bwd_roofline, window_tiles_visited_share,
                   sparse_ffn_ms_per_step, held_load_imbalance):
        assert reader.read(hand_run) is None, reader.__name__
    hand_run.program.facts = facts
    hand_run.trace = None
    for reader in (gqa_flash_ms_per_step, gqa_flash_fwd_roofline,
                   gqa_flash_bwd_roofline, sparse_ffn_ms_per_step):
        assert reader.read(hand_run) is None, reader.__name__
    assert window_tiles_visited_share.read(hand_run) == 0.6875


def test_sparse_ffn_is_the_two_stages_and_the_grouped_products():
    """On a step lowered at the tiny size: instructions under the router's
    and the experts' stages, every direction, are the reader's; it reads
    None where the stages are not (a program from before them)."""
    from benchmarks.layer_metrics import _moe, _stages
    from benchmarks.runners import _trainer
    from paddle_tpu.models import stages
    cell = load_cell(CELL, tiny=True)
    lowered, _, _ = _trainer.lower_step(cell, jax.devices()[:1])
    text = lowered.compile().as_text()
    names = _stages.op_names(text)
    under = {stage: [n for n, path in names.items() if stage in path]
             for stage in (stages.ROUTER, stages.EXPERTS)}
    assert under[stages.ROUTER] and under[stages.EXPERTS]
    ops = tr.parse_hlo(text)
    timed = [n for stage in (stages.ROUTER, stages.EXPERTS)
             for n in [n for n in under[stage] if n in ops
                       and ops[n].category != "container"][:3]]
    op_s = {n: 1e-3 for n in timed}
    summary = tr.Summary(
        steps=2, chips=1, window_s=1.0, busy_s=1.0, category_s={}, ops=ops,
        op_s=op_s, op_calls={n: 2 for n in op_s}, collective_s=0.0,
        collective_exposed_s=0.0, device_ops=[], idle_gaps=[])
    program = types.SimpleNamespace(
        hlo_text=lambda: text, memory=None,
        facts={"moe": {"shapes": runner.shapes(cell)}})
    run = Run(cell, program, None, 0, 0, 0, [], summary)
    placed = _stages.placed(run)
    assert {placed[n][0] for n in timed} <= {stages.ROUTER, stages.EXPERTS}
    assert sparse_ffn_ms_per_step.read(run) == pytest.approx(
        1e3 * len(timed) * 1e-3 / 2)
    assert _moe.stage_ms_per_step(run, "ROUTER") == pytest.approx(1.5)
    summary.op_s = {n: 1e-3 for n in timed[:3]}     # the experts' stage gone
    run._stages_placed = None
    assert sparse_ffn_ms_per_step.read(run) is None
