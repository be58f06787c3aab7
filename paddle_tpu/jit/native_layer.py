"""Python facade over the C++ jit layer container (csrc/jit_layer.cc —
fluid/jit/layer.h analog): the saved artifact is owned natively
(memory-mapped params, validated offsets), Python gets zero-copy views
and the serialized StableHLO program, and execution goes back through
jax.export deserialization onto PJRT."""
from __future__ import annotations

import ctypes
from typing import Dict, List

import numpy as np

from .._core import native


class NativeJitLayer:
    def __init__(self, path_prefix: str):
        self._lib = native.bind_jit(native.get_lib())
        self._h = self._lib.pt_jit_open(path_prefix.encode())
        if not self._h:
            raise RuntimeError(
                f"jit container open failed: {native.last_error()}")

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            try:
                self._lib.pt_jit_close(h)
            except Exception:
                pass

    # ------------------------------------------------------------ params
    def num_params(self) -> int:
        return self._lib.pt_jit_num_params(self._h)

    def param_names(self) -> List[str]:
        return [self._lib.pt_jit_param_name(self._h, i).decode()
                for i in range(self.num_params())]

    def param(self, i: int) -> np.ndarray:
        """Zero-copy read-only view into the mmapped file."""
        dtype = self._lib.pt_jit_param_dtype(self._h, i).decode()
        dims = (ctypes.c_int64 * 16)()
        nd = self._lib.pt_jit_param_shape(self._h, i, dims, 16)
        shape = tuple(dims[d] for d in range(nd))
        size = ctypes.c_uint64()
        ptr = self._lib.pt_jit_param_data(self._h, i,
                                          ctypes.byref(size))
        if not ptr:
            raise RuntimeError("jit param_data failed")
        buf = (ctypes.c_char * size.value).from_address(ptr)
        np_dt = _np_dtype(dtype)
        arr = np.frombuffer(buf, dtype=np_dt).reshape(shape)
        arr.flags.writeable = False
        return arr

    def state_dict(self) -> Dict[str, np.ndarray]:
        return {self._lib.pt_jit_param_name(self._h, i).decode():
                self.param(i) for i in range(self.num_params())}

    # ----------------------------------------------------------- program
    def program_bytes(self) -> bytes:
        size = ctypes.c_uint64()
        ptr = self._lib.pt_jit_program(self._h, ctypes.byref(size))
        if size.value == 0:
            return b""
        return ctypes.string_at(ptr, size.value)


def _np_dtype(name: str):
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))
