"""Native prefetching token loader for LLM pretraining.

Python surface over csrc/data_feed.cc (the reference's C++ DataFeed role,
paddle/fluid/framework/data_feed.h): a C++ worker thread mmap-reads a flat
int32 token file and keeps a prefetch ring of [batch, seq_len+1] windows;
next() returns (tokens [B,S], labels [B,S]) ready for the train step, so
input never blocks the TPU step loop."""
from __future__ import annotations

import ctypes

import numpy as np

from .._core import native


class NativeTokenLoader:
    def __init__(self, path: str, seq_len: int, batch_size: int,
                 shuffle: bool = True, seed: int = 0,
                 prefetch_depth: int = 4):
        self._lib = native.get_lib()
        self._h = self._lib.pt_feed_create(
            str(path).encode(), seq_len, batch_size, 1 if shuffle else 0,
            seed, prefetch_depth)
        if not self._h:
            raise RuntimeError(
                f"NativeTokenLoader failed: {native.last_error()}")
        self.seq_len = seq_len
        self.batch_size = batch_size
        self._buf = np.empty((batch_size, seq_len + 1), np.int32)

    @property
    def num_windows(self) -> int:
        return int(self._lib.pt_feed_num_windows(self._h))

    def next(self):
        """Blocking: returns (tokens [B, S], labels [B, S]) int32."""
        if self._lib.pt_feed_next(
                self._h, self._buf.ctypes.data_as(ctypes.c_void_p)) != 0:
            raise StopIteration
        window = self._buf
        return window[:, :-1].copy(), window[:, 1:].copy()

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def close(self):
        if getattr(self, "_h", None):
            self._lib.pt_feed_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
