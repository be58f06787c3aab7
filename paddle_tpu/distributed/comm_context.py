"""Python facade over the native socket collective engine.

Analog of the reference's CommContextManager + per-ring comm contexts
(phi/core/distributed/comm_context_manager.h:43): endpoints are exchanged
through the TCPStore (the same role the store plays for NCCL unique-ids),
then a full TCP mesh is established in csrc/comm_context.cc and ring
collectives run natively. dtypes outside the native set (bf16/f16) are
upcast for reductions and restored after — byte-oriented ops (broadcast,
all_gather, send/recv) are dtype-agnostic.
"""
from __future__ import annotations

import ctypes
import os
import socket
from typing import Optional, Sequence

import numpy as np

from .._core import native

_DTYPE_CODE = {"float32": 0, "float64": 1, "int32": 2, "int64": 3,
               "uint8": 4}
_OP_CODE = {"sum": 0, "max": 1, "min": 2, "prod": 3, "avg": 0}


# the last value THIS module wrote to the env; any other value found
# there was pinned by the operator and wins over the flag
_LAST_EXPORTED_POLL_LIMIT = None


def _export_poll_limit():
    """The native engine reads its stall bound from the env at first
    transfer. Re-export the flag on EVERY engine construction so
    set_flags calls made at any point before building an engine take
    effect; a PT_COMM_IDLE_POLL_LIMIT value the operator set themselves
    (detected as: present and not what we last exported) wins."""
    global _LAST_EXPORTED_POLL_LIMIT
    from .._core.flags import flag_value
    cur = os.environ.get("PT_COMM_IDLE_POLL_LIMIT")
    if cur is not None and cur != _LAST_EXPORTED_POLL_LIMIT:
        return
    val = str(flag_value("FLAGS_comm_idle_poll_limit"))
    os.environ["PT_COMM_IDLE_POLL_LIMIT"] = val
    _LAST_EXPORTED_POLL_LIMIT = val


def _advertised_host() -> str:
    return os.environ.get("PADDLE_LOCAL_IP",
                          os.environ.get("POD_IP", "127.0.0.1"))


class CommContext:
    """One mesh of sockets for one (group, instance)."""

    def __init__(self, store, rank: int, world: int, key: str):
        _export_poll_limit()
        self._lib = native.get_lib()
        self._h = self._lib.ptcc_create(rank, world)
        if not self._h:
            raise RuntimeError(f"ptcc_create: {native.last_error()}")
        self.rank = rank
        self.world = world
        port = self._lib.ptcc_listen_port(self._h)
        ep = f"{_advertised_host()}:{port}".encode()
        store.set(f"{key}/ep/{rank}", ep)
        eps = [store.get(f"{key}/ep/{r}").decode()
               for r in range(world)]
        rc = self._lib.ptcc_connect(self._h, ",".join(eps).encode())
        if rc != 0:
            raise RuntimeError(f"ptcc_connect: {native.last_error()}")

    @classmethod
    def create_negotiated(cls, store, rank: int, world: int,
                          key: str) -> Optional["CommContext"]:
        """Collective transport selection: every rank publishes whether it
        CAN run the native engine (lib loads + listener opens) before
        anyone blocks in connect/accept. Native is used only when ALL
        ranks can — a per-rank silent fallback would leave peers hanging
        in accept and mismatch collective protocols."""
        from .._core.flags import flag_value
        ok = bool(flag_value("FLAGS_pg_native_transport"))
        try:
            if ok:
                lib = native.get_lib()
                probe = lib.ptcc_create(rank, world)
                if not probe:
                    ok = False
                else:
                    lib.ptcc_destroy(probe)
        except Exception:
            ok = False
        store.set(f"{key}/cap/{rank}", b"1" if ok else b"0")
        caps = [store.get(f"{key}/cap/{r}") for r in range(world)]
        if any(c != b"1" for c in caps):
            return None
        return cls(store, rank, world, key)

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h:
            try:
                self._lib.ptcc_destroy(h)
            except Exception:
                pass

    # ------------------------------------------------------------ helpers
    def _reduce_view(self, arr: np.ndarray):
        """(contiguous buffer, dtype code, restore_fn) for reductions."""
        arr = np.ascontiguousarray(arr)
        name = arr.dtype.name
        if name in _DTYPE_CODE:
            return arr.copy(), _DTYPE_CODE[name], lambda a: a
        # bf16/f16/ints outside the set: reduce in f32/f64
        up = arr.astype(np.float32 if arr.dtype.itemsize <= 2
                        else np.float64)
        orig = arr.dtype
        return up, _DTYPE_CODE[up.dtype.name], lambda a: a.astype(orig)

    @staticmethod
    def _ptr(a: np.ndarray):
        return a.ctypes.data_as(ctypes.c_void_p)

    def _check(self, rc: int, what: str):
        if rc != 0:
            raise RuntimeError(f"{what}: {native.last_error()}")

    # --------------------------------------------------------- collectives
    def all_reduce(self, arr: np.ndarray, op: str = "sum") -> np.ndarray:
        buf, code, restore = self._reduce_view(arr)
        self._check(self._lib.ptcc_all_reduce(
            self._h, self._ptr(buf), buf.size, code, _OP_CODE[op]),
            "all_reduce")
        if op == "avg":
            buf = buf / self.world
        out = restore(buf)
        return np.asarray(out, dtype=arr.dtype).reshape(arr.shape)

    def reduce_scatter(self, arr: np.ndarray, op: str = "sum"):
        """arr: concatenation of world equal parts along axis 0; returns
        this rank's reduced part."""
        buf, code, restore = self._reduce_view(arr)
        per = buf.size // self.world
        out = np.empty(per, buf.dtype)
        self._check(self._lib.ptcc_reduce_scatter(
            self._h, self._ptr(buf), self._ptr(out), per, code,
            _OP_CODE[op]), "reduce_scatter")
        if op == "avg":
            out = out / self.world
        part_shape = (arr.shape[0] // self.world,) + arr.shape[1:]
        return np.asarray(restore(out),
                          dtype=arr.dtype).reshape(part_shape)

    def all_gather_bytes(self, data: bytes) -> list:
        """Equal-size byte blobs, rank-major."""
        n = len(data)
        inb = np.frombuffer(data, np.uint8)
        out = np.empty(n * self.world, np.uint8)
        self._check(self._lib.ptcc_all_gather(
            self._h, self._ptr(np.ascontiguousarray(inb)),
            self._ptr(out), n), "all_gather")
        raw = out.tobytes()
        return [raw[i * n:(i + 1) * n] for i in range(self.world)]

    def all_gather(self, arr: np.ndarray) -> list:
        arr = np.ascontiguousarray(arr)
        blobs = self.all_gather_bytes(arr.tobytes())
        return [np.frombuffer(b, arr.dtype).reshape(arr.shape).copy()
                for b in blobs]

    def broadcast_bytes(self, data: Optional[bytes], root: int,
                        nbytes: int) -> bytes:
        buf = np.frombuffer(data, np.uint8).copy() if data is not None \
            else np.empty(nbytes, np.uint8)
        self._check(self._lib.ptcc_broadcast(
            self._h, self._ptr(buf), nbytes, root), "broadcast")
        return buf.tobytes()

    def send(self, arr: np.ndarray, dst: int):
        arr = np.ascontiguousarray(arr)
        self._check(self._lib.ptcc_send(
            self._h, self._ptr(arr), arr.nbytes, dst), "send")

    def recv_into(self, arr: np.ndarray, src: int) -> np.ndarray:
        self._check(self._lib.ptcc_recv(
            self._h, self._ptr(arr), arr.nbytes, src), "recv")
        return arr

    def barrier(self):
        self._check(self._lib.ptcc_barrier(self._h), "barrier")
