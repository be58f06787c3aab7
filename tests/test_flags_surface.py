"""Runtime flag surface (reference paddle/common/flags.cc, ~187 flags).

Asserts the registry size and spot-checks that flags are LIVE — read at
their use site, not dead registry entries (the VERDICT r4 'no dead
flags' requirement).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu._core.flags import _REGISTRY, flag_value, set_flags


from conftest import with_flag as _with_flag  # noqa: E402


def test_flag_surface_size_and_help():
    assert len(_REGISTRY) >= 60, len(_REGISTRY)
    undocumented = [n for n, f in _REGISTRY.items() if not f.help]
    assert not undocumented, undocumented


def test_sot_cache_entries_flag_live():
    from paddle_tpu.jit.sot import symbolic_translate

    def fn(x, k):
        return (x * k).sum()

    sfn = symbolic_translate(fn)
    x = paddle.to_tensor(np.ones((2, 2), "float32"))
    with _with_flag("FLAGS_sot_cache_entries", 2):
        for k in range(5):
            sfn(x, k)
        assert len(sfn._entries) <= 2


def test_check_nan_inf_level_warns_instead_of_raising():
    import warnings
    with _with_flag("FLAGS_check_nan_inf", True):
        bad = paddle.to_tensor(np.array([1.0, np.inf], "float32"))
        with pytest.raises(FloatingPointError):
            _ = bad * 2.0
        with _with_flag("FLAGS_check_nan_inf_level", 1):
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                out = bad * 2.0
            assert any("NaN/Inf" in str(x.message) for x in w)
            assert np.isinf(out.numpy()).any()


def test_lazy_enable_kill_switch():
    from paddle_tpu._core import lazy
    x = paddle.to_tensor(np.ones((2,), "float32"))
    with _with_flag("FLAGS_lazy_enable", False):
        with lazy.lazy_guard() as ctx:
            y = x + 1.0
            assert not getattr(y._payload, "_is_lazy_ref", False)
        assert ctx.ops_recorded == 0
    np.testing.assert_allclose(y.numpy(), [2.0, 2.0])


def test_lazy_enable_toggle_mid_guard_takes_effect():
    """Flipping FLAGS_lazy_enable with a guard already open must take
    effect on the NEXT dispatch (no stale context, no stale cache hit):
    ops before the flip stay lazy, ops after run eagerly, and both
    produce correct values."""
    from paddle_tpu._core import lazy
    x = paddle.to_tensor(np.ones((2,), "float32"))
    with lazy.lazy_guard() as ctx:
        y = x + 1.0
        assert getattr(y._payload, "_is_lazy_ref", False)
        set_flags({"FLAGS_lazy_enable": False})
        try:
            z = x * 3.0
            assert not getattr(z._payload, "_is_lazy_ref", False), \
                "kill-switch must take effect mid-guard"
        finally:
            set_flags({"FLAGS_lazy_enable": True})
        w = x * 5.0
        assert getattr(w._payload, "_is_lazy_ref", False)
    np.testing.assert_allclose(y.numpy(), [2.0, 2.0])
    np.testing.assert_allclose(z.numpy(), [3.0, 3.0])
    np.testing.assert_allclose(w.numpy(), [5.0, 5.0])
    assert ctx.ops_recorded >= 2


def test_lazy_max_segment_ops_live_on_open_context():
    """FLAGS_lazy_max_segment_ops is read live: lowering it mid-session
    caps the ALREADY-OPEN context's next record."""
    from paddle_tpu._core import lazy
    x = paddle.to_tensor(np.ones((2,), "float32"))
    old = flag_value("FLAGS_lazy_max_segment_ops")
    with lazy.lazy_guard() as ctx:
        y = x + 1.0
        assert ctx.segments_run == 0
        set_flags({"FLAGS_lazy_max_segment_ops": 2})
        try:
            y = y + 1.0   # hits the lowered cap -> forced flush
            assert ctx.segments_run == 1
            assert "segment_cap" in ctx.breaks
        finally:
            set_flags({"FLAGS_lazy_max_segment_ops": old})
    np.testing.assert_allclose(y.numpy(), [3.0, 3.0])


def test_eager_fusion_flag_toggle_flushes_ambient():
    """Turning FLAGS_eager_fusion off lands pending ambient work and
    restores strict per-op dispatch; turning it back on resumes fusion."""
    from paddle_tpu._core import lazy
    assert lazy.eager_fusion_enabled()
    x = paddle.to_tensor(np.ones((2,), "float32"))
    y = x + 1.0                            # ambient: lazy
    assert getattr(y._payload, "_is_lazy_ref", False)
    lazy.enable_eager_fusion(False)
    try:
        assert not getattr(y._payload, "_is_lazy_ref", False), \
            "disable must flush pending ambient ops"
        z = x * 2.0                        # strict per-op dispatch
        assert not getattr(z._payload, "_is_lazy_ref", False)
    finally:
        lazy.enable_eager_fusion(True)
    w = x * 4.0
    assert getattr(w._payload, "_is_lazy_ref", False)
    np.testing.assert_allclose(y.numpy(), [2.0, 2.0])
    np.testing.assert_allclose(w.numpy(), [4.0, 4.0])


def test_executable_cache_capacity_flag_lru():
    """FLAGS_executable_cache_capacity bounds every compiled-runner
    cache with LRU eviction, read live at insertion time."""
    from paddle_tpu._core import lazy
    lazy.clear_segment_cache()
    with _with_flag("FLAGS_executable_cache_capacity", 2):
        for k in range(1, 5):   # 4 distinct signatures
            x = paddle.to_tensor(np.ones((k, 2), "float32"))
            with lazy.lazy_guard():
                y = x + 1.0
            np.testing.assert_allclose(y.numpy(), np.full((k, 2), 2.0))
        assert len(lazy._SEG_CACHE) <= 2, "LRU cap not enforced"
    # re-running an evicted signature recompiles and still works
    x = paddle.to_tensor(np.ones((1, 2), "float32"))
    with lazy.lazy_guard():
        y = x + 1.0
    np.testing.assert_allclose(y.numpy(), np.full((1, 2), 2.0))


def test_pipeline_max_inflight_cap():
    from paddle_tpu.distributed.pipeline import _HostPipeBase

    class _PG:
        rank = 0
        size = 2

    class _G:
        pg = _PG()

    base = _HostPipeBase(_G(), None, 4)
    base._stash = {0: (paddle.to_tensor([1.0]),),
                   1: (paddle.to_tensor([1.0]),)}
    with _with_flag("FLAGS_pipeline_max_inflight", 1):
        with pytest.raises(RuntimeError):
            base._track()


def test_moe_capacity_factor_flag():
    import jax.numpy as jnp
    from paddle_tpu.ops.moe import _capacity
    with _with_flag("FLAGS_moe_capacity_factor", 2.0):
        from paddle_tpu.ops.moe import top2_gating
        logits = jnp.zeros((8, 4), jnp.float32)
        combine, dispatch, aux = top2_gating(logits)
        # capacity = ceil(8 * 2 * 2.0 / 4) = 8
        assert combine.shape[-1] == _capacity(8, 4, 2, 2.0, None)


def test_sparse_validate_indices_flag():
    import paddle_tpu.sparse as sparse
    with _with_flag("FLAGS_sparse_validate_indices", True):
        with pytest.raises(ValueError):
            sparse.sparse_coo_tensor([[0, 5], [0, 1]], [1.0, 2.0],
                                     shape=[2, 2])
    # off: constructs without bounds check (legacy behavior)
    sparse.sparse_coo_tensor([[0, 1], [0, 1]], [1.0, 2.0], shape=[2, 2])


def test_static_checks_flag_live():
    """FLAGS_static_checks is read live at flush: 'error' refuses to
    launch a seeded-violation segment, 'off' skips the checkers (and
    captures no provenance on the recorded ops)."""
    from paddle_tpu._core import lazy
    from paddle_tpu.analysis import StaticCheckError

    x = paddle.to_tensor(np.ones((2,), "float32"))
    with _with_flag("FLAGS_static_checks", "error"):
        with lazy.lazy_guard() as ctx:
            y = x + 1.0
            x._inplace_version += 1      # seeded unnotified mutation
            with pytest.raises(StaticCheckError):
                ctx.flush()
    x._inplace_version = 0
    with _with_flag("FLAGS_static_checks", "off"):
        with lazy.lazy_guard() as ctx:
            y = x + 1.0
            assert ctx.pending[-1].src is None, \
                "off mode must not pay for provenance capture"
            x._inplace_version += 1
        np.testing.assert_allclose(y.numpy(), [2.0, 2.0])
    x._inplace_version = 0


def test_static_checks_fix_spelling_live():
    """'fix' (and its synonyms) is a first-class FLAGS_static_checks
    level: the flush repairs the mechanical classes in place instead of
    warning, and clean programs are never rewritten."""
    from paddle_tpu._core import lazy
    from paddle_tpu.analysis.hooks import check_mode, fixes_applied

    for spelling in ("fix", "autofix", "repair"):
        with _with_flag("FLAGS_static_checks", spelling):
            assert check_mode() == "fix"

    x = paddle.to_tensor(np.ones((2,), "float32"))
    with _with_flag("FLAGS_static_checks", "fix"):
        before = fixes_applied()
        with lazy.lazy_guard() as ctx:
            y = x + 1.0
            x._inplace_version += 1      # seeded unnotified mutation
            ctx.flush()                   # repaired, not raised
        assert fixes_applied() == before + 1
        np.testing.assert_allclose(y.numpy(), [2.0, 2.0])
        # clean program: the rewrite counter must stay frozen
        before = fixes_applied()
        with lazy.lazy_guard() as ctx:
            z = x * 2.0
            ctx.flush()
        assert fixes_applied() == before
    x._inplace_version = 0


def test_ir_pass_disable_flag():
    from paddle_tpu.ir.pass_base import Pass, PassManager

    ran = []

    class P(Pass):
        def __init__(self, name):
            self.name = name

        def run(self, ws, protected):
            ran.append(self.name)
            return False

    pm = PassManager([P("a"), P("b")])
    with _with_flag("FLAGS_ir_pass_disable", "a"):
        pm.run(None)
    assert ran == ["b"]


def test_dy2static_cache_limit_evicts():
    net_calls = []

    @paddle.jit.to_static
    def fn(x, k):
        return x * k

    x = paddle.to_tensor(np.ones((2,), "float32"))
    with _with_flag("FLAGS_dy2static_cache_limit", 2):
        for k in range(4):
            fn(x, k)
        assert len(fn._fwd_cache) <= 2


def test_amp_scaler_flag_defaults():
    with _with_flag("FLAGS_amp_init_loss_scaling", 128.0):
        sc = paddle.amp.GradScaler()
        assert float(sc._scale) == 128.0


def test_zb_extra_delay_flag():
    from paddle_tpu.distributed.pipeline import _zero_bubble_schedule
    base = _zero_bubble_schedule(0, 2, 4)
    with _with_flag("FLAGS_zb_w_extra_delay", 1):
        delayed = _zero_bubble_schedule(0, 2, 4)
    # more deferral: the first W appears no earlier than before
    assert delayed.index(("W", 0)) >= base.index(("W", 0))


def test_ckpt_strict_load_flag(tmp_path):
    import pickle
    d = tmp_path / "ckpt"
    d.mkdir()
    with open(d / "data_rank0.pkl", "wb") as f:
        pickle.dump({"a": np.ones(2, "float32")}, f)
    from paddle_tpu.distributed.checkpoint import load_state_dict
    sd = {"a": paddle.to_tensor(np.zeros(2, "float32")),
          "b": paddle.to_tensor(np.zeros(2, "float32"))}
    with pytest.raises(KeyError):
        load_state_dict(sd, str(d))
    with _with_flag("FLAGS_ckpt_strict_load", False):
        load_state_dict(sd, str(d))
        np.testing.assert_allclose(sd["a"].numpy(), np.ones(2))


def test_host_alloc_chunk_flag_consumer():
    """host_pool() builds the native host pool with the flagged chunk
    size (csrc/allocator.cc)."""
    from paddle_tpu._core import native
    try:
        lib = native.get_lib()
    except Exception:
        pytest.skip("native lib unavailable")
    native._HOST_POOL = None
    with _with_flag("FLAGS_host_alloc_chunk_kb", 64):
        h = native.host_pool()
        assert h
        p = lib.pt_alloc_malloc(h, 1024)
        assert p
        assert lib.pt_alloc_free(h, p) == 0
    native._HOST_POOL = None
