"""Out-of-tree C++ extension points.

Two seams, mirroring the reference:

- Custom DEVICE plugins (paddle/phi/backends/device_ext.h:96 +
  DeviceManager::LoadCustomRuntimeLib, device_manager.h:298): a vendor
  .so exporting PT_InitDevicePlugin is dlopened and driven through the
  C fn-pointer table in csrc/device_ext.h. `CustomDevice` exposes the
  memory/stream/collective contract to Python.
- Custom OPS (paddle/extension.h + fluid/framework/custom_operator.cc +
  paddle.utils.cpp_extension JIT build): a .so exporting pt_op_<name>
  host-buffer kernels is registered into the op registry; under jit the
  op runs through jax.pure_callback, eagerly it is the same path — the
  TPU-native equivalent of a CPU custom kernel (device custom kernels
  are Pallas functions registered directly, no C ABI needed).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import tempfile
from typing import Callable, List, Optional, Sequence

import numpy as np

from .._core import native

_loaded_device_types: List[str] = []


class CustomDevice:
    """Handle to one loaded plugin device type (CustomDevice adapter,
    custom_device.cc:42 analog)."""

    def __init__(self, dev_type: str):
        self.device_type = dev_type
        self._lib = native.get_lib()

    def device_count(self) -> int:
        return self._lib.pt_plugin_device_count(self.device_type.encode())

    def memory_stats(self, device: int = 0):
        total = ctypes.c_uint64()
        free = ctypes.c_uint64()
        rc = self._lib.pt_plugin_mem_stats(
            self.device_type.encode(), device,
            ctypes.byref(total), ctypes.byref(free))
        if rc != 0:
            raise RuntimeError(native.last_error() or "mem_stats failed")
        return {"total": total.value, "free": free.value}

    def stream_check(self, device: int = 0) -> bool:
        """Create stream -> record+sync event -> destroy (the contract
        smoke the reference's fake-device tests drive)."""
        return self._lib.pt_plugin_stream_check(
            self.device_type.encode(), device) == 0

    def round_trip(self, arr: np.ndarray, device: int = 0) -> np.ndarray:
        """h2d then d2h through plugin memory: the memcpy contract."""
        arr = np.ascontiguousarray(arr)
        dev = self.device_type.encode()
        ptr = self._lib.pt_plugin_malloc(dev, device, arr.nbytes)
        if not ptr:
            raise RuntimeError("plugin malloc failed")
        try:
            src = arr.ctypes.data_as(ctypes.c_void_p)
            rc = self._lib.pt_plugin_memcpy(dev, device, ptr, src,
                                            arr.nbytes, 0)  # h2d
            out = np.empty_like(arr)
            rc |= self._lib.pt_plugin_memcpy(
                dev, device, out.ctypes.data_as(ctypes.c_void_p),
                ptr, arr.nbytes, 1)  # d2h
            if rc != 0:
                raise RuntimeError("plugin memcpy failed")
            return out
        finally:
            self._lib.pt_plugin_free(dev, device, ptr)

    def ccl_all_reduce(self, arr: np.ndarray, device: int = 0,
                       op: str = "sum") -> np.ndarray:
        """Route through the plugin's xccl hook (device_ext.h:557
        analog); identity for single-member fabrics."""
        arr = np.ascontiguousarray(arr).copy()
        codes = {"float32": 0, "float64": 1, "int32": 2, "int64": 3}
        ops = {"sum": 0, "max": 1, "min": 2, "prod": 3}
        rc = self._lib.pt_plugin_ccl_all_reduce(
            self.device_type.encode(), device,
            arr.ctypes.data_as(ctypes.c_void_p), arr.size,
            codes[arr.dtype.name], ops[op])
        if rc != 0:
            raise RuntimeError("plugin ccl_all_reduce failed")
        return arr


def load_custom_device_lib(path: str) -> CustomDevice:
    """dlopen a device plugin .so (LoadCustomRuntimeLib analog)."""
    lib = native.get_lib()
    name = lib.pt_plugin_load(os.fspath(path).encode())
    if not name:
        raise RuntimeError(
            f"failed to load device plugin {path}: {native.last_error()}")
    dev_type = name.decode()
    if dev_type not in _loaded_device_types:
        _loaded_device_types.append(dev_type)
    return CustomDevice(dev_type)


def get_all_custom_device_type() -> List[str]:
    return list(_loaded_device_types)


# ------------------------------------------------------------ custom ops

def load_op_library(path: str, op_name: str,
                    out_shape_fn: Optional[Callable] = None):
    """Load pt_op_<op_name> from a .so and register it as a framework op.

    The C kernel computes on float32 host buffers; output shape defaults
    to the first input's (elementwise contract) unless out_shape_fn is
    given. Works eagerly and under jit via jax.pure_callback — the role
    of the reference's custom-op registration (custom_operator.cc) with
    the CPU kernel path; TPU-resident custom kernels are Pallas functions
    registered with register_op directly.
    """
    import jax
    import jax.numpy as jnp

    lib = native.get_lib()
    rc = lib.pt_custom_op_load(os.fspath(path).encode(), op_name.encode())
    if rc != 0:
        raise RuntimeError(
            f"failed to load op {op_name}: {native.last_error()}")

    def host_call(*arrays):
        arrays = [np.ascontiguousarray(np.asarray(a, np.float32))
                  for a in arrays]
        n = len(arrays)
        ins = (ctypes.c_void_p * n)(
            *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrays])
        sizes = (ctypes.c_int64 * n)(*[a.size for a in arrays])
        out_shape = (out_shape_fn(*[a.shape for a in arrays])
                     if out_shape_fn else arrays[0].shape)
        out = np.empty(out_shape, np.float32)
        if lib.pt_custom_op_call(op_name.encode(), ins, sizes, n,
                                 out.ctypes.data_as(ctypes.c_void_p),
                                 out.size) != 0:
            raise RuntimeError(f"custom op {op_name} failed: "
                               f"{native.last_error()}")
        return out

    def op_fn(*xs):
        shape = (out_shape_fn(*[x.shape for x in xs]) if out_shape_fn
                 else xs[0].shape)
        return jax.pure_callback(
            host_call, jax.ShapeDtypeStruct(tuple(shape), jnp.float32),
            *xs)

    from .._core.op_registry import register_op
    register_op(op_name, op_fn, custom=True)

    from .._core.executor import apply

    def user_fn(*tensors):
        return apply(op_name, *tensors)

    return user_fn


def compile_and_load_op(source: str, op_name: str,
                        out_shape_fn: Optional[Callable] = None,
                        extra_cflags: Sequence[str] = ()):
    """JIT-build a custom-op .so from C++ source text and register it
    (paddle.utils.cpp_extension.load analog, g++ instead of nvcc)."""
    workdir = tempfile.mkdtemp(prefix=f"pt_op_{op_name}_")
    src = os.path.join(workdir, f"{op_name}.cc")
    so = os.path.join(workdir, f"lib{op_name}.so")
    with open(src, "w") as f:
        f.write(source)
    cmd = ["g++", "-std=c++17", "-O2", "-fPIC", "-shared",
           *extra_cflags, src, "-o", so]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"custom op build failed:\n{proc.stderr}")
    return load_op_library(so, op_name, out_shape_fn=out_shape_fn)
