"""TCPStore rendezvous (native-backed).

Python surface of the reference's TCPStore
(phi/core/distributed/store/tcp_store.h:121; Python handle created at
parallel.py:1134 core.create_or_get_global_tcp_store). Rank 0 hosts the
C++ server (csrc/tcp_store.cc); every rank connects a C++ client. Used for
multi-host bring-up: exchanging coordinator addresses before
jax.distributed.initialize, barrier-by-key, elastic membership."""
from __future__ import annotations

import logging
import os
from typing import Optional

from .._core import native
from .resilience import faults as _faults
from .resilience import retry as _retry

_log = logging.getLogger("paddle_tpu.distributed")

# typed transient failure for set/get/wait (lives in resilience.retry —
# this module imports retry, not the reverse — and is re-exported here
# because it is the store's error)
StoreOpError = _retry.StoreOpError


class TCPStore:

    # barrier round numbers wrap here: a round's keys are deleted when
    # the last rank leaves, so reuse after 2^16 rounds is safe — and
    # the counter no longer grows without bound across a long job's
    # repeated barriers on the same key (all ranks wrap identically,
    # so the key namespaces still agree)
    _BARRIER_ROUND_WRAP = 1 << 16

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 is_master: bool = False, world_size: int = 1,
                 timeout: float = None):
        if timeout is None:
            from .._core.flags import flag_value
            timeout = float(flag_value("FLAGS_tcp_store_timeout_s"))
        self._lib = native.get_lib()
        self._server = None
        self._timeout_ms = int(timeout * 1000)
        self._barrier_rounds = {}
        if is_master:
            self._server = self._lib.pt_store_server_start(port)
            if not self._server:
                raise RuntimeError(
                    f"TCPStore server failed: {native.last_error()}")
            port = self._lib.pt_store_server_port(self._server)
        self.host = host
        self.port = port
        self.world_size = world_size
        self._client = self._lib.pt_store_client_connect(
            host.encode(), port, self._timeout_ms)
        if not self._client:
            self._close_server()
            raise RuntimeError(
                f"TCPStore connect failed: {native.last_error()}")

    # ------------------------------------------------------------- KV API
    # Each op is one retryable attempt wrapped by the store RetryPolicy
    # (resilience/retry.py): transient failures — injected via the
    # store::* fault sites or OS-level — back off and re-attempt; a
    # first-attempt success pays one try/except and zero registry work.
    def set(self, key: str, value) -> None:
        data = value.encode() if isinstance(value, str) else bytes(value)
        _retry.store_policy().run(self._set_once, key, data,
                                  what=f"store::set({key})")

    def _set_once(self, key: str, data: bytes) -> None:
        if _faults.ACTIVE:
            _faults.inject("store::set")
        if self._lib.pt_store_set(self._client, key.encode(), data,
                                  len(data)) != 0:
            raise StoreOpError(f"TCPStore.set failed: "
                               f"{native.last_error()}")

    def get(self, key: str) -> bytes:
        return _retry.store_policy().run(self._get_once, key,
                                         what=f"store::get({key})")

    def try_get(self, key: str, timeout: float = 0.25):
        """Liveness-probe get: ONE attempt with its own short deadline,
        None when the key is missing or slow — never retried and never
        the store-wide timeout. `get` waits for a key that SHOULD
        appear (rendezvous); this asks whether a key is there NOW
        (heartbeat scans, membership polls) — using `get` for that
        blocks the watcher for the full store timeout per missing
        node. Deliberately NOT a `store::get` fault site: probe
        callers treat this as never-raising, and a probe consuming
        the site's occurrence counts would desync @occ drills aimed
        at real rendezvous gets (membership drills have their own
        member:: sites)."""
        out = self._sized_read(key, max(int(timeout * 1000), 1))
        return None if isinstance(out, int) else out

    def _sized_read(self, key: str, ms: int):
        """The native get's size-then-read, raced against concurrent
        rewrites: if the value grows between the two calls, the native
        side skips the copy (buf too small) but still returns the NEW
        length — returning the zero-filled buffer would hand the
        caller garbage (a heartbeat scan would adopt a '\\x00...' node
        id; a rendezvous consumer would json-parse NULs). Re-size and
        retry; returns the bytes, or the last failing native rc (int
        < 0) / -1 for a key that would not hold still."""
        import ctypes
        n = self._lib.pt_store_get(self._client, key.encode(), None, 0,
                                   ms)
        if n < 0:
            return int(n)
        for _ in range(3):
            buf = ctypes.create_string_buffer(int(n))
            n2 = self._lib.pt_store_get(self._client, key.encode(), buf,
                                        n, ms)
            if n2 < 0:
                return int(n2)
            if n2 <= n:
                return buf.raw[:n2]
            n = n2
        return -1

    def _get_once(self, key: str) -> bytes:
        if _faults.ACTIVE:
            _faults.inject("store::get")
        out = self._sized_read(key, self._timeout_ms)
        if isinstance(out, int):
            reason = native.last_error() \
                or "value kept changing size under the read"
            raise StoreOpError(f"TCPStore.get('{key}') failed: {reason}")
        return out

    def add(self, key: str, amount: int = 1) -> int:
        # NOT retried: add is not idempotent — a retry after an applied-
        # but-unacked increment would double-count, and rendezvous
        # counters are exactly where that corrupts the job. The fault
        # site still fires so tests can target it.
        if _faults.ACTIVE:
            _faults.inject("store::add")
        r = self._lib.pt_store_add(self._client, key.encode(), amount)
        if r < 0 and native.last_error():
            raise RuntimeError(f"TCPStore.add failed: "
                               f"{native.last_error()}")
        return int(r)

    def wait(self, key: str, timeout: Optional[float] = None) -> None:
        _retry.store_policy().run(self._wait_once, key, timeout,
                                  what=f"store::wait({key})")

    def _wait_once(self, key: str, timeout: Optional[float]) -> None:
        if _faults.ACTIVE:
            _faults.inject("store::wait")
        ms = int((timeout or self._timeout_ms / 1000) * 1000)
        if self._lib.pt_store_wait(self._client, key.encode(), ms) != 0:
            raise StoreOpError(f"TCPStore.wait('{key}') timed out")

    def delete(self, key: str) -> None:
        if self._lib.pt_store_del(self._client, key.encode()) != 0:
            raise RuntimeError(f"TCPStore.delete failed: "
                               f"{native.last_error()}")

    def barrier(self, key: str = "barrier", timeout: Optional[float] = None):
        """All world_size ranks arrive, then proceed (barrier-by-key, the
        reference's store-barrier pattern).

        Reusable: every use of a key gets a fresh round number (all ranks
        call barrier the same number of times, so local counters agree),
        and the last rank out deletes the round's keys."""
        rnd = self._barrier_rounds.get(key, 0)
        self._barrier_rounds[key] = (rnd + 1) % self._BARRIER_ROUND_WRAP
        base = f"__bar/{key}/{rnd}"
        arrived = self.add(f"{base}/count", 1)
        if arrived >= self.world_size:
            self.set(f"{base}/done", b"1")
        self.wait(f"{base}/done", timeout)
        left = self.add(f"{base}/left", 1)
        if left >= self.world_size:
            for suffix in ("count", "done", "left"):
                self.delete(f"{base}/{suffix}")

    # ---------------------------------------------------------- lifecycle
    def _close_server(self):
        if self._server:
            self._lib.pt_store_server_stop(self._server)
            self._server = None

    def close(self):
        if getattr(self, "_client", None):
            self._lib.pt_store_client_close(self._client)
            self._client = None
        self._close_server()

    def __del__(self):
        # narrow handling with a logged reason (the xplane-fallback
        # convention): interpreter teardown can null out the ctypes lib
        # or module globals (AttributeError/TypeError), and a peer gone
        # first surfaces as OSError/RuntimeError from the native close —
        # anything else is a real bug and should not be swallowed
        try:
            self.close()
        except (OSError, RuntimeError, AttributeError, TypeError) as e:
            try:
                _log.debug("TCPStore close during __del__ skipped: %r", e)
            except Exception:
                pass   # logging itself can be torn down at exit


def create_or_get_global_tcp_store() -> TCPStore:
    """parallel.py:1134 analog: build the job-wide store from the standard
    env (MASTER_ADDR/MASTER_PORT or PADDLE_MASTER, PADDLE_TRAINER_ID)."""
    global _global_store
    if _global_store is not None:
        return _global_store
    master = os.environ.get("PADDLE_MASTER") or "{}:{}".format(
        os.environ.get("MASTER_ADDR", "127.0.0.1"),
        os.environ.get("MASTER_PORT", "6170"))
    host, port = master.rsplit(":", 1)
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    _global_store = TCPStore(host, int(port), is_master=(rank == 0),
                             world_size=world)
    return _global_store


_global_store: Optional[TCPStore] = None
