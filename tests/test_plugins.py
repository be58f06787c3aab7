"""Custom-device plugin ABI + custom-op extension tests.

Mirrors the reference's fake-device contract suite
(test/custom_runtime/test_custom_cpu_plugin.py over
phi/backends/custom/fake_cpu_device.h) and the custom-op tests
(test/custom_op/) — ours drive csrc/device_ext.h through the in-tree
libpt_fake_device plugin and JIT-compile a real C++ op."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.utils import (
    get_all_custom_device_type,
    load_custom_device_lib,
    run_check,
)
from paddle_tpu.utils.cpp_extension import compile_and_load_op

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAKE_SO = os.path.join(REPO, "csrc", "build", "libpt_fake_device.so")


@pytest.fixture(scope="module")
def fake_dev():
    from paddle_tpu._core import native
    native.get_lib()  # triggers build of both .so files
    return load_custom_device_lib(FAKE_SO)


class TestDevicePlugin:
    def test_load_and_enumerate(self, fake_dev):
        assert fake_dev.device_type == "fake_cpu"
        assert fake_dev.device_count() == 2
        assert "fake_cpu" in get_all_custom_device_type()

    def test_memcpy_round_trip(self, fake_dev):
        arr = np.random.RandomState(0).randn(64, 3).astype(np.float32)
        out = fake_dev.round_trip(arr, device=1)
        np.testing.assert_array_equal(out, arr)

    def test_mem_stats(self, fake_dev):
        s0 = fake_dev.memory_stats(0)
        assert s0["total"] > 0 and s0["free"] <= s0["total"]

    def test_stream_event_contract(self, fake_dev):
        assert fake_dev.stream_check(0)

    def test_ccl_hook(self, fake_dev):
        arr = np.arange(6, dtype=np.float32)
        out = fake_dev.ccl_all_reduce(arr)   # world-of-one: identity
        np.testing.assert_array_equal(out, arr)

    def test_bad_plugin_path_raises(self):
        with pytest.raises(RuntimeError):
            load_custom_device_lib("/nonexistent/libnope.so")

    def test_reload_same_type_is_idempotent(self, fake_dev):
        again = load_custom_device_lib(FAKE_SO)
        assert again.device_type == "fake_cpu"
        assert again.device_count() == 2


_SCALE_SHIFT_SRC = r"""
#include <stdint.h>
// custom op: out = 2*x + y  (elementwise, float32 host buffers)
extern "C" int pt_op_scale_shift(const void** ins, const int64_t* sizes,
                                 int n_in, void* out, int64_t out_size) {
  if (n_in != 2 || sizes[0] != out_size || sizes[1] != out_size) return 1;
  const float* x = (const float*)ins[0];
  const float* y = (const float*)ins[1];
  float* o = (float*)out;
  for (int64_t i = 0; i < out_size; ++i) o[i] = 2.0f * x[i] + y[i];
  return 0;
}
"""


class TestCustomOp:
    @pytest.fixture(scope="class")
    def scale_shift(self):
        return compile_and_load_op(_SCALE_SHIFT_SRC, "scale_shift")

    def test_eager(self, scale_shift):
        x = paddle.to_tensor(np.ones((3, 4), np.float32))
        y = paddle.to_tensor(np.full((3, 4), 5.0, np.float32))
        out = scale_shift(x, y)
        np.testing.assert_allclose(out.numpy(),
                                   np.full((3, 4), 7.0, np.float32))

    def test_under_jit(self, scale_shift):
        import paddle_tpu.nn as nn

        class Net(nn.Layer):
            def forward(self, x, y):
                return scale_shift(x, y) + 1.0

        net = paddle.jit.to_static(Net())
        x = paddle.to_tensor(np.zeros((2, 2), np.float32))
        y = paddle.to_tensor(np.ones((2, 2), np.float32))
        out = net(x, y)
        np.testing.assert_allclose(out.numpy(),
                                   np.full((2, 2), 2.0, np.float32))

    def test_bad_source_raises(self):
        with pytest.raises(RuntimeError):
            compile_and_load_op("this is not C++", "broken_op")


def test_run_check(capsys):
    assert run_check()
    out = capsys.readouterr().out
    assert "installed successfully" in out
