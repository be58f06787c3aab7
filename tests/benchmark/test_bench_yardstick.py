"""The benchmark's own arithmetic against figures worked out by hand:
required FLOPs, flash costs, the peaks table, the traffic generator."""
import numpy as np
import pytest

from benchmarks import cells, flops, generator, peaks


@pytest.mark.parametrize("kwargs, gflop", [
    # gpt2-medium at s1024: 6*(12*24*1024^2 + 50304*1024) + 6*24*1024*1024
    (dict(layers=24, hidden=1024, ffn=4096, vocab=50304, seq=1024), 2.272),
    # gpt3-1.3b at s2048
    (dict(layers=24, hidden=2048, ffn=8192, vocab=50304, seq=2048), 8.470),
])
def test_gpt_required_flops(kwargs, gflop):
    got = flops.gpt_train_flops_per_token(**kwargs) / 1e9
    assert got == pytest.approx(gflop, rel=5e-4)


@pytest.mark.parametrize("seq, gflop", [(512, 1.997), (128, 1.884)])
def test_bert_large_required_flops(seq, gflop):
    # 6*(12*24*1024^2 + 1024^2) + 0.15*6*30522*1024 + 12*24*s*1024
    got = flops.bert_mlm_train_flops_per_token(
        layers=24, hidden=1024, ffn=4096, vocab=30522, seq=seq,
        labelled_share=0.15) / 1e9
    assert got == pytest.approx(gflop, rel=5e-4)


def test_gpt2_medium_exact_by_hand():
    assert flops.gpt_train_flops_per_token(
        layers=24, hidden=1024, ffn=4096, vocab=50304, seq=1024) \
        == 6 * (301_989_888 + 51_511_296) + 150_994_944


@pytest.mark.parametrize("kind, products, arrays, rows", [
    ("fwd", 2, 4, 1),       # S, O | q k v -> o, lse
    ("bwd", 5, 8, 1),       # S, dP, dV, dK, dQ | q k v o do lse -> dq dk dv
])
def test_flash_pass_cost(kind, products, arrays, rows):
    bh, s, d = 256, 1024, 64
    flop, byte = flops.flash_pass_cost(kind, bh=bh, seq=s, head_dim=d,
                                       causal=True)
    assert flop == products * bh * s * s * d        # half of 2*s*s*d each
    assert byte == arrays * bh * s * d * 2 + rows * bh * s * 4
    full, _ = flops.flash_pass_cost(kind, bh=bh, seq=s, head_dim=d,
                                    causal=False)
    assert full == 2 * flop
    # at the cells' shapes both are compute-bound on a v5e
    assert flops.least_seconds(flop, byte, peaks.peaks_of("TPU v5 lite"))[1] \
        == "compute"


def test_roofline_says_which_bound():
    v5e = peaks.peaks_of("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bytes_s) == (197e12, 819e9)
    assert flops.least_seconds(197e12, 1.0, v5e) == (1.0, "compute")
    assert flops.least_seconds(1.0, 819e9, v5e) == (1.0, "memory")


def test_unknown_device_is_an_error_not_a_default():
    with pytest.raises(LookupError, match="TPU v5"):
        peaks.peaks_of("TPU v5")        # a v5p must not answer as a v5e
    with pytest.raises(LookupError):
        peaks.peaks_of("cpu")


@pytest.mark.parametrize("name", ["pretrain-b16-s1024", "mlm-b32-s512",
                                  "mlm-b128-s128", "pretrain-b16-s2048"])
def test_traffic_is_seeded_and_fixed_work(name):
    traffic = cells.load_traffic(name)
    a = generator.make_ring(traffic, 30522, seed=7)
    b = generator.make_ring(traffic, 30522, seed=7)
    c = generator.make_ring(traffic, 30522, seed=8)
    assert len(a) == traffic["ring"] == 8
    assert traffic["batch"] * traffic["seq"] == 16384 * (
        2 if name.endswith("s2048") else 1)
    for (ta, la), (tb, lb) in zip(a, b):
        assert ta.dtype == la.dtype == np.int32
        assert ta.shape == la.shape == (traffic["batch"], traffic["seq"])
        assert (ta == tb).all() and (la == lb).all()
        assert 0 <= ta.min() and ta.max() < 30522
    assert not (a[0][0] == c[0][0]).all()
    assert not (a[0][0] == a[1][0]).all()           # the ring is distinct
    tokens, labels = a[0]
    if traffic["labels"] == "next_token":
        assert (labels[:, :-1] == tokens[:, 1:]).all()
        assert generator.labelled_share(traffic) == 1.0
    else:
        k = round(0.15 * traffic["seq"])
        assert ((labels >= 0).sum(1) == k).all()    # the same in every row
        assert set(np.unique(labels[labels < 0])) == {generator.IGNORE}
        assert generator.labelled_share(traffic) == k / traffic["seq"]


def test_check_batch_is_two_sequences_tiled():
    traffic = cells.load_traffic("mlm-b32-s512")
    (tok2, lab2), (tok, lab) = generator.make_check_batch(traffic, 30522, 3)
    assert tok2.shape == (2, 512) and tok.shape == (32, 512)
    assert (tok[0::2] == tok2[0]).all() and (tok[1::2] == tok2[1]).all()
    assert (lab[0::2] == lab2[0]).all() and (lab[1::2] == lab2[1]).all()


def test_bad_traffic_file_is_refused(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "bad.json").write_text(
        '{"batch": 2, "seq": 8, "ring": 0, "sync_every": 1, '
        '"trace_steps": 1, "labels": "next_token"}')
    monkeypatch.setattr(cells, "HERE", str(tmp_path))
    with pytest.raises(ValueError, match="ring"):
        cells.load_traffic("bad")
    with pytest.raises(ValueError, match="unknown labels"):
        generator.make_ring({"name": "odd", "batch": 2, "seq": 8, "ring": 1,
                             "labels": "shuffled"}, 100, seed=0)
