"""What ISSUE 26 adds to the benchmark: the plain reference of the
latent-attention sparse-expert family against a single-token case written
out by hand in numpy, its FLOP and byte counts against figures worked out
here, and its eight readers on a hand-made trace."""
import types

import jax
import numpy as np
import pytest

from benchmarks import flops_mla_moe, peaks
from benchmarks import trace_reduce as tr
from benchmarks.cells import load_cell
from benchmarks.layer_metrics import (Run, _mla_flash, _moe, _stages,
                                      mla_flash_bwd_roofline,
                                      mla_flash_fwd_roofline,
                                      mla_flash_ms_per_step,
                                      moe_experts_ms_per_step,
                                      moe_experts_roofline,
                                      moe_load_imbalance,
                                      moe_router_ms_per_step,
                                      residual_mix_ms_per_step)
from benchmarks.reference import mla_moe as ref
from benchmarks.runners import mla_moe as runner
from paddle_tpu.models import stages

CELL = "xing4-ep8share-pretrain-s2048"


# ------------------------------------------- the reference, one token by hand

def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _rms(x, gain, eps):
    return x / np.sqrt((x * x).mean() + eps) * gain


def _swiglu(x, gate, up, down):
    a = x @ gate
    return (a * _sigmoid(a) * (x @ up)) @ down


def _hand_nll(params, token, label, config):
    """One token through every layer, each equation spelt out. With one
    position the causal softmax is over one key, so attention gives that
    key's value and RoPE at position 0 turns nothing."""
    n, eps = config["hc_mult"], config["rms_norm_eps"]
    dn, dv = config["qk_nope_head_dim"], config["v_head_dim"]
    rank, k = config["kv_lora_rank"], config["num_experts_per_tok"]
    first, held = (config["deployment"]["experts_first"],
                   config["n_routed_experts"])

    def layer(tree, i):
        return jax.tree_util.tree_map(lambda a: np.asarray(a[i], np.float64),
                                      tree)

    def mix(X, hc, fn):
        u = _rms(X.reshape(-1), hc["norm_g"], config["hc_eps"])
        proj = u @ hc["phi"]
        pre = _sigmoid(hc["alpha"][0] * proj[:n] + hc["b_pre"])
        post = 2 * _sigmoid(hc["alpha"][1] * proj[n:2 * n] + hc["b_post"])
        R = np.exp(np.clip(
            hc["alpha"][2] * proj[2 * n:].reshape(n, n) + hc["b_res"],
            config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]))
        for _ in range(config["hc_sinkhorn_iters"]):
            for i in range(n):
                R[i] = R[i] / (R[i].sum() + config["hc_eps"])
            for j in range(n):
                R[:, j] = R[:, j] / (R[:, j].sum() + config["hc_eps"])
        y = fn(sum(pre[i] * X[i] for i in range(n)))
        return np.stack([sum(R[i, j] * X[j] for j in range(n)) + post[i] * y
                         for i in range(n)])

    def attention(x, p):
        x = _rms(x, p["ln1_g"], eps)
        c_kv = _rms((x @ p["kv_a_w"])[:rank], p["kv_a_ln"], eps)
        value = (c_kv @ p["kv_b_w"]).reshape(-1, dn + dv)[:, dn:]
        return value.reshape(-1) @ p["o_w"]

    def dense(x, p):
        return _swiglu(_rms(x, p["ln2_g"], eps), p["gate_w"], p["up_w"],
                       p["down_w"])

    def sparse(x, p):
        x = _rms(x, p["ln2_g"], eps)
        score = _sigmoid(x @ p["router_w"])
        chosen = np.argsort(-(score + p["router_b"]))[:k]
        weight = score[chosen] / (score[chosen].sum() + 1e-20) \
            * config["routed_scaling_factor"]
        out = _swiglu(x, p["shared_gate_w"], p["shared_up_w"],
                      p["shared_down_w"])
        for e, w in zip(chosen, weight):
            if first <= e < first + held:
                mine = {key: a[e - first] for key, a in p["experts"].items()}
                out = out + w * _swiglu(x, mine["gate_w"], mine["up_w"],
                                        mine["down_w"])
        return out

    x = np.asarray(params["wte"][token], np.float64)
    X = np.stack([x] * n)
    for group, ffn in (("dense", dense), ("sparse", sparse)):
        for i in range(params[group]["ln1_g"].shape[0]):
            p = layer(params[group], i)
            X = mix(X, p["hc_attn"], lambda y: attention(y, p))
            X = mix(X, p["hc_ffn"], lambda y: ffn(y, p))
    x = _rms(X.sum(0), np.asarray(params["lnf_g"], np.float64), eps)
    logits = np.asarray(params["lm_head"], np.float64) @ x
    logits = logits - logits.max()
    return float(-(logits[label] - np.log(np.exp(logits).sum())))


@pytest.mark.parametrize("seed, token, label", [(0, 5, 17), (1, 300, 2)])
def test_reference_against_a_single_token_by_hand(seed, token, label):
    from paddle_tpu.models.mla_moe import init_mla_moe_params
    config = load_cell(CELL, tiny=True).config
    params = init_mla_moe_params(runner.program_config(config), seed)
    # away from the start, where every gain is 1 and every bias 0
    params = jax.tree_util.tree_map(
        lambda a: a * 3.0 if a.ndim > 2 else a + 0.1, params)
    with jax.default_matmul_precision("highest"):
        nll, count = ref.nll(params, np.asarray([token], np.int32),
                             np.asarray([label], np.int32), config)
    assert int(count) == 1
    assert float(nll) == pytest.approx(
        _hand_nll(params, token, label, config), rel=2e-5)


def test_reference_leaves_out_what_the_absent_experts_would_add():
    """The same parameters with the held range moved: another partial sum,
    unless no token chose any held expert."""
    from paddle_tpu.models.mla_moe import init_mla_moe_params
    config = load_cell(CELL, tiny=True).config
    params = init_mla_moe_params(runner.program_config(config), 2)
    tokens = np.arange(1, 33, dtype=np.int32)
    here, _ = ref.nll(params, tokens, tokens, config)
    moved = dict(config, deployment=dict(config["deployment"],
                                         experts_first=8))
    there, _ = ref.nll(params, tokens, tokens, moved)
    assert abs(float(here) - float(there)) > 1e-6


# ------------------------------------------------------------------ counts

def test_required_flops_of_the_cell_by_hand():
    cell = load_cell(CELL)
    s = runner.shapes(cell)
    attention = 2 * (3584 * 768 + 768 * 4 * 192 + 3584 * 576
                     + 512 * 4 * 256 + 4 * 128 * 3584)
    assert flops_mla_moe.attention_projection_flops(
        hidden=3584, heads=4, q_rank=768, kv_rank=512, nope=128, rope=64,
        v_dim=128) == attention == 15_532_032
    mixing = 2 * 14336 * 24 + 2 * 4 * 3584 + 2 * 16 * 3584 + 2 * 4 * 3584
    assert flops_mla_moe.stream_mix_flops(hidden=3584, streams=4) == mixing
    assert flops_mla_moe.pairs_per_token(k=4, held=8, router_outputs=64) \
        == 0.5
    layer = attention + 2048 * 4 * 320 + 2 * mixing
    forward = 5 * layer + 6 * 3584 * 9216 + 4 * (
        6 * 3584 * 1024 + 2 * 3584 * 64 + 0.5 * 6 * 3584 * 1024) \
        + 2 * 3584 * 16384
    assert runner.flops_per_token(cell) == 3 * forward
    assert runner.flops_per_token(cell) == pytest.approx(1.647e9, rel=1e-3)
    assert s["sparse_layers"] == 4 and s["held"] == 8 and s["seq"] == 2048


def test_kernel_costs_by_hand():
    # one layer's routed experts on 4096 pairs: 3 products forward, 6 back
    flop, byte = flops_mla_moe.grouped_pass_cost(
        "fwd", pairs=4096, held=8, hidden=3584, width=1024)
    assert flop == 3 * 2 * 4096 * 3584 * 1024
    assert byte == 2 * (3 * 8 * 3584 * 1024 + 2 * 4096 * 3584
                        + 3 * 4096 * 1024)
    flop_b, byte_b = flops_mla_moe.grouped_pass_cost(
        "bwd", pairs=4096, held=8, hidden=3584, width=1024)
    assert flop_b == 2 * flop and byte_b > byte
    # 16 heads-times-rows of 2048 at 192 / 128, causal
    flop, byte = flops_mla_moe.mla_flash_pass_cost(
        "fwd", bh=16, seq=2048, d_qk=192, d_v=128, causal=True)
    assert flop == 16 * 2048 * 2048 * (192 + 128)
    assert byte == 2 * 16 * 2048 * (2 * 192 + 2 * 128) + 16 * 2048 * 4
    flop, _ = flops_mla_moe.mla_flash_pass_cost(
        "bwd", bh=16, seq=2048, d_qk=192, d_v=128, causal=True)
    assert flop == 16 * 2048 * 2048 * (3 * 192 + 2 * 128)
    # at one width it is flops.py's count
    from benchmarks import flops
    assert flops_mla_moe.mla_flash_pass_cost(
        "bwd", bh=8, seq=512, d_qk=64, d_v=64, causal=False) \
        == flops.flash_pass_cost("bwd", bh=8, seq=512, head_dim=64,
                                 causal=False)


# ----------------------------------------------------- readers, a hand trace

US = 1e-6
# batch 1, 2 held heads, 16 positions, widths 6 and 4; 3 routed experts held
Q, V = "bf16[2,16,6]{2,1,0}", "bf16[2,16,4]{2,1,0}"
ROW = "f32[2,1,1,16]{3,2,1,0}"


def _hand_hlo():
    fwd, bwd = ("jit(step_fn)/jvp()/while/body/closed_call/",
                "jit(step_fn)/transpose(jvp())/while/body/closed_call/"
                "checkpoint/")
    remat = bwd + "rematted_computation/"

    def call(name, results, operands, path):
        return (f'  %{name} = {results} custom-call(%p), '
                f'custom_call_target="tpu_custom_call", '
                f'operand_layout_constraints={{{", ".join(operands)}}}, '
                f'metadata={{op_name="{path}"}}')

    rows, matrix = "bf16[64,8]{1,0}", "bf16[3,8,5]{2,1,0}"
    lines = [
        "HloModule jit_step_fn, is_scheduled=true", "",
        "ENTRY %main (p: bf16[8,8]) -> bf16[8,8] {",
        "  %p = bf16[8,8]{1,0} parameter(0)",
        f'  %dot.1 = f32[16,9]{{1,0}} dot(%p, %p), metadata={{op_name="{fwd}'
        f'{stages.ROUTER}/dot_general"}}',
        f'  %sort.2 = s32[64]{{0}} sort(%p), metadata={{op_name="{fwd}'
        f'{stages.EXPERTS}/sort"}}',
        call("gmm.3", "bf16[64,5]{1,0}", [rows, matrix, "s32[3]{0}"],
             fwd + stages.EXPERTS + "/ragged_dot"),
        call("gmm.4", "bf16[64,5]{1,0}", [rows, matrix, "s32[3]{0}"],
             remat + stages.EXPERTS + "/ragged_dot"),
        # as XLA names its own grouped-matmul kernel: no stage of ours
        call("gmm.5", "bf16[3,8,5]{2,1,0}", [rows, rows, "s32[3]{0}"],
             "ragged-dot-none"),
        f'  %fusion.6 = f32[4,4,16]{{2,1,0}} divide(%p, %p), metadata='
        f'{{op_name="{fwd}{stages.RESIDUAL_MIX}/div"}}',
        f'  %fusion.7 = f32[4,4,16]{{2,1,0}} multiply(%p, %p), metadata='
        f'{{op_name="{bwd}{stages.RESIDUAL_MIX}/mul"}}',
        call("attn.8", f"({V}, f32[2,16,1]{{2,1,0}})", [Q, Q, V],
             fwd + stages.ATTN_CORE + "/pallas_call"),
        call("attn.9", f"({V}, f32[2,16,1]{{2,1,0}})", [Q, Q, V],
             remat + stages.ATTN_CORE + "/pallas_call"),
        call("attn.10", f"({Q}, {Q}, {V})", [Q, Q, V, V, ROW, ROW],
             bwd + stages.ATTN_CORE + "/pallas_call"),
        "  ROOT %out = bf16[8,8]{1,0} copy(%p)", "}", ""]
    return "\n".join(lines)


# self seconds in a window of 2 steps, each instruction run once a step
OP_US = {"dot.1": 8, "sort.2": 6, "gmm.3": 20, "gmm.4": 20, "gmm.5": 40,
         "fusion.6": 30, "fusion.7": 50, "attn.8": 10, "attn.9": 10,
         "attn.10": 25}
FACTS = {
    "attention": {"batch": 1, "heads": 2, "seq": 16, "head_dim": 6,
                  "v_head_dim": 4, "causal": True},
    "moe": {"tokens": 16, "pairs": 64, "layers": 1,
            "fullest_over_balanced": 1.75,
            "shapes": {"k": 4, "held": 3, "router_outputs": 12, "hidden": 8,
                       "expert_ffn": 5}}}


@pytest.fixture()
def hand_run():
    hlo_text = _hand_hlo()
    ops = tr.parse_hlo(hlo_text)
    assert ops["gmm.3"].category == ops["attn.10"].category == "mosaic"
    assert ops["dot.1"].category == "matmul"
    op_s = {name: us * US for name, us in OP_US.items()}
    busy = sum(op_s.values())
    summary = tr.Summary(
        steps=2, chips=1, window_s=busy, busy_s=busy, category_s={}, ops=ops,
        op_s=op_s, op_calls={name: 2 for name in op_s}, collective_s=0.0,
        collective_exposed_s=0.0, device_ops=[], idle_gaps=[])
    program = types.SimpleNamespace(hlo_text=lambda: hlo_text, facts=FACTS,
                                    memory=None)
    return Run(None, program, peaks.peaks_of("TPU v5 lite"), 0, 0, 0, [],
               summary)


def test_stage_readers_on_the_hand_trace(hand_run):
    per_step = 1e3 * US / 2
    assert moe_router_ms_per_step.read(hand_run) \
        == pytest.approx(8 * per_step)
    # 46 under the stage; gmm.5 (40) under none, found by its result's shape
    assert _moe.stage_ms_per_step(hand_run, "EXPERTS") \
        == pytest.approx(46 * per_step)
    assert _moe.grouped_products(hand_run) == {"gmm.3", "gmm.4", "gmm.5"}
    assert moe_experts_ms_per_step.read(hand_run) \
        == pytest.approx((6 + 20 + 20 + 40) * per_step)
    assert residual_mix_ms_per_step.read(hand_run) \
        == pytest.approx((30 + 50) * per_step)
    assert moe_load_imbalance.read(hand_run) == 1.75


def test_experts_roofline_on_the_hand_trace(hand_run):
    """The sort is no product; forward, remat and backward calls are 80 us
    in the window; required is one forward and one backward a step on
    16 * 4 * 3 / 12 = 16 pairs."""
    assert _moe.experts_product_seconds(hand_run) == pytest.approx(80 * US)
    v5e = hand_run.peaks
    least = sum(flops_mla_moe.least_seconds(
        *flops_mla_moe.grouped_pass_cost(kind, pairs=16, held=3, hidden=8,
                                         width=5), v5e)[0]
        for kind in ("fwd", "bwd"))
    assert _moe.experts_least_seconds(hand_run) == pytest.approx(least)
    assert moe_experts_roofline.read(hand_run) \
        == pytest.approx(100 * least * 2 / (80 * US))


def test_mla_flash_readers_on_the_hand_trace(hand_run):
    """Told by shape: q and k of 2*16*6 elements, v of 2*16*4; the grouped
    products have neither. Two forward calls a step (one is remat's), one
    backward pass (three results)."""
    found = _mla_flash.passes(hand_run)
    assert found["fwd"] == (pytest.approx(20 * US), 4)
    assert found["bwd"] == (pytest.approx(25 * US), 2)
    assert mla_flash_ms_per_step.read(hand_run) \
        == pytest.approx(45 * 1e3 * US / 2)
    v5e = hand_run.peaks
    for reader, kind, seconds, n in (
            (mla_flash_fwd_roofline, "fwd", 20 * US, 4),
            (mla_flash_bwd_roofline, "bwd", 25 * US, 2)):
        least, _ = flops_mla_moe.least_seconds(
            *flops_mla_moe.mla_flash_pass_cost(
                kind, bh=2, seq=16, d_qk=6, d_v=4, causal=True), v5e)
        assert reader.read(hand_run) == pytest.approx(
            100 * least * n / seconds)


def test_a_padded_value_is_still_found_and_held_to_the_published_widths(
        hand_run):
    """Where a program pads v to the query's width, the calls have three
    operands of q's size: found, and the requirement stays at 6 / 4."""
    hlo_text = hand_run.program.hlo_text().replace(V, Q)
    hand_run.trace.ops = tr.parse_hlo(hlo_text)
    hand_run.program.hlo_text = lambda: hlo_text
    assert _mla_flash.passes(hand_run)["fwd"] == (pytest.approx(20 * US), 4)
    assert _mla_flash.passes(hand_run)["bwd"] == (pytest.approx(25 * US), 2)


def test_on_a_program_without_the_stages_the_readers_read_nothing(
        hand_run, monkeypatch):
    """The parent commit's `models/stages.py` has seven names: a reader of a
    stage it lacks returns None and does not raise (and the cell's other
    new readers find no facts there)."""
    seven = types.SimpleNamespace(ALL=stages.ALL[:7],
                                  OPTIMIZER=stages.OPTIMIZER)
    monkeypatch.setattr(_stages, "vocabulary", lambda: seven)
    for reader in (moe_router_ms_per_step, moe_experts_ms_per_step,
                   residual_mix_ms_per_step, moe_experts_roofline):
        assert reader.read(hand_run) is None
    monkeypatch.setattr(_stages, "vocabulary", lambda: None)
    assert moe_experts_ms_per_step.read(hand_run) is None
    hand_run.program.facts = {"attention": {
        "batch": 1, "heads": 2, "seq": 16, "head_dim": 6, "causal": True}}
    for reader in (mla_flash_ms_per_step, mla_flash_fwd_roofline,
                   mla_flash_bwd_roofline, moe_load_imbalance,
                   moe_experts_roofline):
        assert reader.read(hand_run) is None


def test_without_a_trace_the_trace_readers_read_nothing(hand_run):
    hand_run.trace = None
    for reader in (moe_router_ms_per_step, moe_experts_ms_per_step,
                   residual_mix_ms_per_step, moe_experts_roofline,
                   mla_flash_ms_per_step, mla_flash_fwd_roofline,
                   mla_flash_bwd_roofline):
        assert reader.read(hand_run) is None
    assert moe_load_imbalance.read(hand_run) == 1.75


def test_the_configuration_states_its_cut():
    """Every width is the source's; `reduced` is the seven counts; the
    published counts and the deployment stand beside the held ones."""
    import json
    import os
    config = load_cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Xing4.0-29B-A4B")
        assert config["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items()
                         if config.get(k) != v)
        assert differs == sorted(config["reduced"])
        assert {k: row["config"][k] for k in differs} == config["published"]
    assert len(config["reduced"]) == 7
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    assert config["published"]["n_routed_experts"] \
        == 8 * config["n_routed_experts"]
    assert config["published"]["num_attention_heads"] \
        == 8 * config["num_attention_heads"]
    assert config["published"]["vocab_size"] == 8 * config["vocab_size"]
    assert "TO BE SET" not in json.dumps(config["tolerance"])
