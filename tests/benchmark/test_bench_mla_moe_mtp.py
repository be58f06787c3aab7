"""What ISSUE 30 adds to the benchmark: the plain reference with a
prediction module and a moving bias against equations written out here in
numpy, the FLOP count against figures worked out by hand, the runner's
comparison on made-up figures, the configuration's cut against the catalog,
and the four new readers on a hand-made trace."""
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check, flops_mla_moe_mtp, peaks
from benchmarks import trace_reduce as tr
from benchmarks.cells import load_cell
from benchmarks.layer_metrics import (Run, _stages, attention_ms_per_step,
                                      loss_head_ms_per_step, mtp_ms_per_step,
                                      router_bias_moved_share)
from benchmarks.reference import mla_moe_mtp as ref
from benchmarks.runners import mla_moe_mtp as runner
from paddle_tpu.models import stages

CELL = "glm47f-ep8share-pretrain-s2048"
SEQ = 24


@pytest.fixture(scope="module")
def tiny():
    from paddle_tpu.models.mla_moe import init_mla_moe_params
    config = load_cell(CELL, tiny=True).config
    params = init_mla_moe_params(runner.program_config(config), 2)
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        a + 0.05 * jax.random.normal(k, a.shape, a.dtype)
        for a, k in zip(leaves, keys)])
    ids = np.random.default_rng(4).integers(0, config["vocab_size"],
                                            SEQ + 1, dtype=np.int32)
    return config, params, ids[:-1], ids[1:]


# --------------------------------- the reference against numpy, by hand

def _np(tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), tree)


def _rms(x, gain, eps):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * gain


def _silu_glu(x, gate, up, down):
    a = x @ gate
    return (a / (1 + np.exp(-a)) * (x @ up)) @ down


def _log_softmax(logits):
    logits = logits - logits.max(-1, keepdims=True)
    return logits - np.log(np.exp(logits).sum(-1, keepdims=True))


def _hand_layer(x, p, config, sparse):
    """One pre-norm layer on x [s, h], every equation spelt out."""
    s = x.shape[0]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    rank, eps = config["kv_lora_rank"], config["rms_norm_eps"]
    y = _rms(x, p["ln1_g"], eps)
    q = (_rms(y @ p["q_a_w"], p["q_a_ln"], eps) @ p["q_b_w"]).reshape(
        s, -1, dn + dr)
    heads = q.shape[1]
    kv_a = y @ p["kv_a_w"]
    kv = (_rms(kv_a[:, :rank], p["kv_a_ln"], eps) @ p["kv_b_w"]).reshape(
        s, heads, dn + dv)
    angle = np.arange(s)[:, None] * config["rope_theta"] ** (
        -2.0 * np.arange(dr // 2) / dr)[None, :]

    def turn(v):            # [s, ..., dr], halves paired
        a, b = v[..., :dr // 2], v[..., dr // 2:]
        cos = np.cos(angle).reshape((s,) + (1,) * (v.ndim - 2) + (-1,))
        sin = np.sin(angle).reshape(cos.shape)
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    k_r = turn(kv_a[:, rank:])
    out = np.zeros((s, heads, dv))
    for h in range(heads):
        qh = np.concatenate([q[:, h, :dn], turn(q[:, h, dn:])], -1)
        kh = np.concatenate([kv[:, h, :dn], k_r], -1)
        for i in range(s):
            score = qh[i] @ kh[:i + 1].T / np.sqrt(dn + dr)
            prob = np.exp(score - score.max())
            out[i, h] = prob / prob.sum() @ kv[:i + 1, h, dn:]
    x = x + out.reshape(s, -1) @ p["o_w"]
    y = _rms(x, p["ln2_g"], eps)
    if not sparse:
        return x + _silu_glu(y, p["gate_w"], p["up_w"], p["down_w"]), None
    score = 1 / (1 + np.exp(-(y @ p["router_w"])))
    k = config["num_experts_per_tok"]
    chosen = np.argsort(-(score + p["router_b"]), -1, kind="stable")[:, :k]
    first, held = (config["deployment"]["experts_first"],
                   config["n_routed_experts"])
    ffn = _silu_glu(y, p["shared_gate_w"], p["shared_up_w"],
                    p["shared_down_w"])
    for i in range(s):
        picked = score[i, chosen[i]]
        for e, g in zip(chosen[i], config["routed_scaling_factor"] * picked
                        / picked.sum()):
            if first <= e < first + held:
                mine = {n: a[e - first] for n, a in p["experts"].items()}
                ffn[i] += g * _silu_glu(y[i], mine["gate_w"], mine["up_w"],
                                        mine["down_w"])
    drawn = np.bincount(chosen.ravel(), minlength=score.shape[1])
    return x + ffn, drawn


def _hand_parts(params, tokens, labels, config):
    p, eps = _np(params), config["rms_norm_eps"]
    x, drawn = p["wte"][tokens], []
    for group, sparse in (("dense", False), ("sparse", True)):
        for i in range(p[group]["ln1_g"].shape[0]):
            x, d = _hand_layer(x, jax.tree_util.tree_map(
                lambda a: a[i], p[group]), config, sparse)
            drawn += [d] if sparse else []
    main = -_log_softmax(_rms(x, p["lnf_g"], eps) @ p["lm_head"].T)[
        np.arange(len(labels)), labels].sum()
    mtp = p["mtp"]
    both = np.concatenate([_rms(x, mtp["hnorm_g"], eps),
                           _rms(p["wte"][labels], mtp["enorm_g"], eps)], -1)
    y, d = _hand_layer(both @ mtp["eh_w"], jax.tree_util.tree_map(
        lambda a: a[0], mtp["layer"]), config, True)
    logp = _log_softmax(_rms(y, mtp["lnf_g"], eps) @ p["lm_head"].T)
    # position i predicts token i + 2, which is label i + 1
    module = -logp[np.arange(len(labels) - 1), labels[1:]].sum()
    return main, module, np.stack(drawn + [d])


def test_reference_against_the_equations_by_hand(tiny):
    config, params, tokens, labels = tiny
    (main, count), (module, fewer), drawn = ref.parts(params, tokens, labels,
                                                      config)
    want_main, want_module, want_drawn = _hand_parts(params, tokens, labels,
                                                     config)
    assert (int(count), int(fewer)) == (SEQ, SEQ - 1)
    assert float(main) == pytest.approx(want_main, rel=2e-5)
    assert float(module) == pytest.approx(want_module, rel=2e-5)
    np.testing.assert_array_equal(drawn, want_drawn)
    assert drawn.shape == (3, 16) and (drawn.sum(1) == SEQ * 4).all()


def test_nll_is_the_form_the_harness_takes(tiny):
    """sum / count is L_main + lambda L_mtp, over one sequence or two of
    one length: what `check.reference_losses` and `aot_check.py` assume."""
    config, params, tokens, labels = tiny
    (main, count), (module, fewer), _ = ref.parts(params, tokens, labels,
                                                  config)
    value, n = ref.nll(params, tokens, labels, config)
    assert float(value) / int(n) == pytest.approx(
        float(main) / SEQ + 0.3 * float(module) / (SEQ - 1), rel=1e-6)
    two = (np.stack([tokens, labels]), np.stack([labels, tokens]))
    loss0, _ = check.reference_losses(
        ref, params, two, dict(config, reference_check="loss"))
    found = [ref.parts(params, t, l, config) for t, l in zip(*two)]
    assert loss0 == pytest.approx(
        sum(float(f[0][0]) for f in found) / (2 * SEQ)
        + 0.3 * sum(float(f[1][0]) for f in found) / (2 * (SEQ - 1)),
        rel=1e-6)


def test_reference_leaves_out_what_the_absent_experts_would_add(tiny):
    config, params, tokens, labels = tiny
    x = jax.random.normal(jax.random.PRNGKey(0), (SEQ, config["hidden_size"]))
    blk = jax.tree_util.tree_map(lambda a: a[0], params["sparse"])
    held, drawn = ref.sparse_ffn(x, blk, config)
    none, _ = ref.sparse_ffn(x, blk, dict(config, n_routed_experts=0))
    shared = ref.swiglu(x, blk["shared_gate_w"], blk["shared_up_w"],
                        blk["shared_down_w"])
    np.testing.assert_allclose(none, shared, rtol=1e-6)
    assert float(jnp.abs(held - none).max()) > 1e-4
    # the router chose over all 16, of which 4 are held
    assert int(drawn.sum()) == SEQ * 4 and int(drawn[4:].sum()) > 0


def test_check_step_moves_the_biases_before_the_second_loss(tiny):
    config, params, tokens, labels = tiny
    two = (np.stack([tokens, labels]), np.stack([labels, tokens]))
    def fresh():           # `check_step` donates what it is given
        return jax.tree_util.tree_map(
            lambda a: jnp.array(a, jnp.float32, copy=True), params)

    moving = ref.check_step(fresh(), two, config)
    held = ref.check_step(fresh(), two,
                          dict(config, router_bias_update_rate=0.0))
    assert moving["loss0"] == held["loss0"]
    assert moving["loss0"] == pytest.approx(
        moving["main0"] + 0.3 * moving["mtp0"])
    assert moving["loss1"] < moving["loss0"]
    assert moving["bias_moved_share"] > 0.5 and held["bias_moved_share"] == 0
    gamma = np.float32(config["router_bias_update_rate"])
    start = np.asarray(ref.router_biases(params))
    assert np.abs(np.abs(moving["biases1"] - start)
                  - gamma * (moving["biases1"] != start)).max() < 1e-7


def test_the_update_is_rounded_on_its_bits():
    """A float32 -> bfloat16 -> float32 round trip inside one program is a
    pair of converts XLA's TPU pipeline may drop; the reference rounds on
    the bits instead, to the same values, and its program holds no
    bfloat16 at all."""
    x = np.random.default_rng(0).normal(size=50000).astype(np.float32) * 0.02
    x = jnp.asarray(np.concatenate([x, np.float32(
        [0.0, -0.0, 1.0, 1.0003, 1.00390625, 1.01171875, -1.00390625])]))
    got = ref.round_through(x, jnp.dtype("bfloat16"))
    np.testing.assert_array_equal(
        got, x.astype(jnp.bfloat16).astype(jnp.float32))
    assert float(got[-4]) == 1.0            # a gain's first update is lost
    assert ref.round_through(x, jnp.dtype("float32")) is x
    text = jax.jit(lambda a: ref.round_through(
        a * 1.5, jnp.dtype("bfloat16"))).lower(x).as_text()
    assert "bf16" not in text
    config = {"dtype": "bfloat16"}
    master = {"a_w": jnp.full((4,), 0.0197, jnp.float32),
              "ln_g": jnp.full((4,), 1.0003, jnp.float32),
              "router_w": jnp.full((4,), 0.0197, jnp.float32)}
    seen = ref.as_the_forward_sees(master, config)
    assert float(seen["router_w"][0]) == float(master["router_w"][0])
    assert float(seen["a_w"][0]) == float(
        jnp.float32(0.0197).astype(jnp.bfloat16)) != float(master["a_w"][0])
    assert float(seen["ln_g"][0]) == 1.0
    same = ref.as_the_forward_sees(master, {"dtype": "float32"})
    assert all(same[k] is master[k] for k in master)


def test_pairs_off_by_hand():
    """Half the distance between the distributions of a router's pairs,
    the mean over the routers; the batches may be multiples of another."""
    got = runner.pairs_off([[3, 1], [2, 2]], [[2, 2], [2, 2]])
    assert got == pytest.approx((0.25 + 0.0) / 2)
    assert runner.pairs_off([[12, 4], [8, 8]], [[2, 2], [2, 2]]) \
        == pytest.approx(got)
    assert runner.pairs_off([[5, 0]], [[0, 5]]) == 1.0


# ------------------------------------------------------ the count, by hand

def test_required_flops_of_the_cell_by_hand():
    """ISSUE 30's figures: projections 43.5 M and attention products 21.0 M
    a layer x 6, dense MLP 125.8 M, sparse FFN 28.6 M x 5, two head passes
    158.6 M, W_eh 16.8 M: 831 M forward, 2.49 G a token."""
    cell = load_cell(CELL)
    projections = 2 * (2048 * 768 + 768 * 20 * 256 + 2048 * 576
                       + 512 * 20 * 448 + 20 * 256 * 2048)
    products = 2048 * 20 * (256 + 256)
    sparse = 6 * 2048 * 1536 + 2 * 2048 * 64 + 6 * 2048 * 1536 * 4 * 8 / 64
    forward = 6 * (projections + products) + 6 * 2048 * 10240 + 5 * sparse \
        + 2 * 2 * 2048 * 19360 + 2 * 4096 * 2048
    assert round(projections / 1e6, 1) == 43.5
    assert round(products / 1e6, 1) == 21.0
    assert round(sparse / 1e6, 1) == 28.6
    assert round(forward / 1e6) == 831
    assert runner.flops_per_token(cell) == pytest.approx(3 * forward)
    # the module is over a fifth of it (22.8 %): its layer, its projection,
    # its head pass
    without = dict(runner.shapes(cell), mtp_layers=0)
    module = 1 - flops_mla_moe_mtp.train_flops_per_token(**without) \
        / runner.flops_per_token(cell)
    assert 0.22 < module < 0.24


# -------------------------------------------- the runner's own comparison

def test_compare_catches_a_module_that_hides_in_the_sum():
    tol = {"loss": 4e-4, "drop": 5e-4}
    reference = {"loss0": 12.84, "main0": 9.88, "mtp0": 9.8667,
                 "loss1": 2.84, "bias_moved_share": 0.97}
    good = dict(reference, bias_moved_share=0.96)
    assert runner.compare(good, reference, tol) == []
    # the sum is right and the parts are not
    swapped = dict(good, main0=9.88 + 0.03, mtp0=9.8667 - 0.1)
    found = runner.compare(swapped, reference, tol)
    assert len(found) == 2 and "main0" in found[0] and "mtp0" in found[1]
    assert "step-0 loss" in runner.compare(
        dict(good, loss0=9.88), reference, tol)[0]
    assert "fell by" in runner.compare(
        dict(good, loss1=2.85), reference, tol)[0]
    assert "no selection bias moved" in runner.compare(
        dict(good, bias_moved_share=0.0), reference, tol)[0]


# ------------------------------------------------------- the configuration

def test_the_configuration_states_its_cut():
    """Every width is the source's; `reduced` is the three counts; the
    published counts and the deployment stand beside the held ones."""
    config = load_cell(CELL).config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "GLM-4.7-Flash")
        assert config["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items()
                         if config.get(k, "absent") != v)
        assert differs == sorted(config["reduced"])
        assert {k: row["config"][k] for k in differs} == config["published"]
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    widths = ("hidden_size", "q_lora_rank", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_experts_per_tok",
              "routed_scaling_factor")
    assert [config[k] for k in widths] == [2048, 768, 512, 192, 64, 256,
                                           10240, 1536, 20, 4, 1.8]
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    assert config["published"]["n_routed_experts"] \
        == 8 * config["n_routed_experts"] == 64
    assert config["published"]["vocab_size"] == 8 * config["vocab_size"]
    assert config["num_nextn_predict_layers"] == 1
    assert {"router_bias_update_rate", "mtp_loss_weight", "mtp_form",
            "balance_steps", "optimizer"} <= set(config["assumed"])
    assert config["balance_steps"] == 120
    assert config["optimizer"]["lr"] == 1e-5
    assert "TO BE SET" not in json.dumps(config["tolerance"])
    c = runner.program_config(config)
    assert c.hc_mult is None and c.heads == 20 and c.held == (0, 8)
    assert (c.router_bias_update_rate, c.mtp_loss_weight) == (0.001, 0.3)
    traffic = load_cell(CELL).traffic
    assert traffic["batch"] * traffic["seq"] == 16384


# ----------------------------------------------------- readers, a hand trace

US = 1e-6


def _hand_hlo():
    fwd, bwd = "jit(step_fn)/jvp()/", "jit(step_fn)/transpose(jvp())/"
    scan = "while/body/closed_call/"
    mtp_f = f"jit(step_fn)/jvp({stages.MTP})/"
    mtp_b = f"jit(step_fn)/transpose(jvp({stages.MTP}))/"

    def op(name, kind, path):
        return (f'  %{name} = f32[8,8]{{1,0}} {kind}(%p, %p), '
                f'metadata={{op_name="{path}"}}')

    lines = [
        "HloModule jit_step_fn, is_scheduled=true", "",
        "ENTRY %main (p: f32[8,8]) -> f32[8,8] {",
        "  %p = f32[8,8]{1,0} parameter(0)",
        op("attn.1", "dot", fwd + scan + stages.ATTN_CORE + "/dot_general"),
        op("attn.2", "dot", bwd + scan + "checkpoint/" + stages.ATTN_CORE
           + "/dot_general"),
        op("head.3", "dot", f"jit(step_fn)/jvp({stages.LOSS_HEAD})/"
           "dot_general"),
        op("head.4", "multiply", "jit(step_fn)/transpose(jvp("
           f"{stages.LOSS_HEAD}))/mul"),
        # the module: its own attention and head pass stand under its stage
        op("mtp.5", "dot", mtp_f + "dot_general"),
        op("mtp.6", "dot", mtp_f + scan + stages.ATTN_CORE + "/dot_general"),
        op("mtp.7", "dot", mtp_b + scan + "checkpoint/rematted_computation/"
           + stages.EXPERTS + "/ragged_dot"),
        op("mtp.8", "subtract", mtp_b + "jit(log_softmax)/sub"),
        op("bias.9", "sign", f"jit(step_fn)/{stages.OPTIMIZER}/sign"),
        "  ROOT %out = f32[8,8]{1,0} copy(%p)", "}", ""]
    return "\n".join(lines)


OP_US = {"attn.1": 10, "attn.2": 30, "head.3": 7, "head.4": 9, "mtp.5": 2,
         "mtp.6": 5, "mtp.7": 11, "mtp.8": 3, "bias.9": 1}
FACTS = {"moe": {"bias_moved_share": 0.9625}}


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
    per_step = 1e3 * US / 2
    where = _stages.placed(hand_run)
    assert where["mtp.6"] == (stages.MTP, "forward")
    assert where["mtp.7"] == (stages.MTP, "remat")
    assert where["mtp.8"] == (stages.MTP, "backward")
    assert mtp_ms_per_step.read(hand_run) \
        == pytest.approx((2 + 5 + 11 + 3) * per_step)
    # the trunk's alone: the module's attention and head pass are its own
    assert attention_ms_per_step.read(hand_run) \
        == pytest.approx((10 + 30) * per_step)
    assert loss_head_ms_per_step.read(hand_run) \
        == pytest.approx((7 + 9) * per_step)
    assert router_bias_moved_share.read(hand_run) == 0.9625


def test_a_reader_whose_span_is_gone_reads_none(hand_run, monkeypatch):
    text = _hand_hlo()
    for reader, scope in ((mtp_ms_per_step, f"({stages.MTP})"),
                          (attention_ms_per_step,
                           f"closed_call/{stages.ATTN_CORE}"),
                          (loss_head_ms_per_step, f"({stages.LOSS_HEAD})")):
        lost = text.replace(scope, "(gone)" if scope[0] == "("
                            else "closed_call/gone")
        if reader is attention_ms_per_step:
            lost = lost.replace(f"checkpoint/{stages.ATTN_CORE}",
                                "checkpoint/gone")
        hand_run.program.hlo_text = lambda lost=lost: lost
        hand_run._stages_placed = None
        assert reader.read(hand_run) is None, reader.__name__
    hand_run.program.hlo_text = lambda: text
    hand_run._stages_placed = None
    # the parent's vocabulary has no such stage, and no such fact
    ten = types.SimpleNamespace(
        ALL=stages.ALL[:10], OPTIMIZER=stages.OPTIMIZER,
        ATTN_CORE=stages.ATTN_CORE, LOSS_HEAD=stages.LOSS_HEAD,
        ATTN_QKV=stages.ATTN_QKV, ATTN_OUT=stages.ATTN_OUT, MLP=stages.MLP)
    monkeypatch.setattr(_stages, "vocabulary", lambda: ten)
    assert mtp_ms_per_step.read(hand_run) is None
    monkeypatch.setattr(_stages, "vocabulary", lambda: None)
    for reader in (mtp_ms_per_step, attention_ms_per_step,
                   loss_head_ms_per_step):
        assert reader.read(hand_run) is None
    hand_run.program.facts = {"moe": {"tokens": 16}}
    assert router_bias_moved_share.read(hand_run) is None
    hand_run.program.facts = {}
    assert router_bias_moved_share.read(hand_run) is None


def test_without_a_trace_the_trace_readers_read_nothing(hand_run):
    hand_run.trace = None
    for reader in (mtp_ms_per_step, attention_ms_per_step,
                   loss_head_ms_per_step):
        assert reader.read(hand_run) is None
    assert router_bias_moved_share.read(hand_run) == 0.9625
