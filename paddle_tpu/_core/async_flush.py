"""Async dispatch pipeline: the off-thread segment-flush executor.

The PR-3 span budget puts the residual steady-state overhead squarely
on the host: with the accelerator holding at <=2 XLA executions per
step, the Python thread still serializes eager RECORDING of step N+1
behind the flush (cache lookup + compile + dispatch) of step N's
segments. This module breaks that serialization the way the reference
gets it free from CUDA-stream asynchrony (and 2011.03641 argues is the
whole game at this regime): `CaptureContext.flush` seals the trace and
hands it to a single-worker executor; the recording thread immediately
resumes, with every live output bound to a `PendingValue` placeholder
that materializes through the existing LazyRef machinery.

Contracts:

- **ordering**: one worker, FIFO queue — segments execute in exactly
  the order they were sealed, so eager ordering (and donation
  reasoning, which is decided at seal time on the recording thread) is
  preserved.
- **sync points**: reading a pending value (`Tensor._value`,
  `.numpy()`, `float()`), `backward()` through a segment whose inputs
  are pending, and `drain()` all block until the in-flight work lands.
- **errors**: a worker failure is latched into every PendingValue of
  the failed job *and* into the executor. Framework exceptions
  (injected faults, StaticCheckError, EnforceNotMet) re-raise with
  their original type at the next sync point — rollback and sanitizer
  contracts see the same exception class as the synchronous path —
  while anything else is wrapped in EnforceNotMet with the flight
  recorder's post-mortem already dumped from the worker.
- **shutdown**: an atexit hook drains and retires the worker; a
  process must not exit with a leaked flush thread
  (tests/test_async_flush.py asserts this).
"""
from __future__ import annotations

import atexit
import queue
import threading
from typing import Any, Callable, List, Optional

_WORKER_NAME = "paddle_tpu-flush-worker"


class PendingValue:
    """Placeholder payload for one output of an in-flight flushed
    segment. Carries the recorded aval so metadata reads (shape/dtype/
    signature building) never block; `resolve()` blocks until the
    worker lands the concrete jax array (or re-raises its error)."""

    _is_pending_value = True
    __slots__ = ("aval", "_event", "_value", "_error", "__weakref__")

    def __init__(self, aval):
        self.aval = aval
        self._event = threading.Event()
        self._value = None
        self._error = None

    # metadata mirrors a jax array so _aval_of/_in_signature/
    # _segment_needs_grad read pending inputs without materializing
    @property
    def shape(self):
        return self.aval.shape

    @property
    def dtype(self):
        return self.aval.dtype

    @property
    def weak_type(self):
        return getattr(self.aval, "weak_type", False)

    @property
    def ndim(self):
        return len(self.aval.shape)

    def done(self) -> bool:
        return self._event.is_set()

    def _fill(self, value):
        self._value = value
        self._event.set()

    def _fail(self, error: BaseException):
        self._error = error
        self._event.set()

    def resolve(self):
        self._event.wait()
        if self._error is not None:
            raise _surface_error(self._error)
        return self._value


def resolve_value(v):
    """Concrete payload for `v` (blocking if pending)."""
    if getattr(v, "_is_pending_value", False):
        return v.resolve()
    return v


def resolve_pending(vals) -> list:
    """Resolve every PendingValue in a payload list — the boundary any
    consumer (segment runner, vjp, replay) crosses before handing
    values to jax."""
    return [v.resolve() if getattr(v, "_is_pending_value", False) else v
            for v in vals]


def _surface_error(err: BaseException) -> BaseException:
    """The exception a sync point raises for a worker failure. Typed
    framework errors keep their class (rollback retry-ability, fault
    drills, and sanitizer handling must behave exactly like the
    synchronous path); anything else becomes EnforceNotMet so user
    code gets the framework's error surface, with the original chained
    as __cause__."""
    from ..base.core import EnforceNotMet
    from ..distributed.resilience.faults import FaultError
    try:
        from ..analysis.diagnostics import StaticCheckError
    except Exception:                                # pragma: no cover
        StaticCheckError = ()
    if isinstance(err, (EnforceNotMet, FaultError, StaticCheckError,
                        FloatingPointError)):
        return err
    wrapped = EnforceNotMet(
        f"async segment flush failed off-thread: "
        f"{type(err).__name__}: {err}",
        context="the failure happened on the flush worker; this "
                "re-raise is the next sync point. Set "
                "FLAGS_async_flush=false to fail at the flush site.")
    wrapped.__cause__ = err
    return wrapped


# run-ahead bound: a recording thread with no sync point could
# otherwise seal segments faster than the worker executes them, each
# queued job pinning its trace + input buffers — memory would grow
# linearly with run-ahead where the sync path's stays flat. Classic
# pipeline depth; submit blocks (on the condition, never on the queue)
# once this many jobs are in flight.
_MAX_INFLIGHT = 4


class FlushExecutor:
    """Single-worker FIFO executor for sealed segment flushes."""

    def __init__(self, max_inflight: int = _MAX_INFLIGHT):
        self._max_inflight = max(int(max_inflight), 1)
        self._q: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._inflight = 0          # submitted, not yet finished
        self._idle = threading.Condition(self._lock)
        self._latched: List[BaseException] = []
        self._stopped = False

    # ------------------------------------------------------------ worker
    def _ensure_worker_locked(self):
        """Start the worker if needed. Caller holds self._lock — the
        check-and-start must be atomic or two threads' first concurrent
        submits would each start a worker, breaking FIFO ordering and
        leaking the orphan past shutdown."""
        if self._thread is not None and self._thread.is_alive():
            return
        self._thread = threading.Thread(
            target=self._loop, name=_WORKER_NAME, daemon=True)
        self._thread.start()

    def _loop(self):
        while True:
            job = self._q.get()
            if job is None:
                return
            fn, on_error = job
            try:
                fn()
            except BaseException as e:   # latched, surfaced at sync
                with self._lock:
                    self._latched.append(e)
                from ..observability import _state as _OBS
                if _OBS.DIST:
                    # a latched worker error is a postmortem trigger on
                    # the distributed plane too: publish this rank's
                    # ring now — by the time the error re-raises at the
                    # sync point the ring may have wrapped past the
                    # failing flush. Never raises.
                    from ..observability import distributed as _dtel
                    _dtel.trigger_postmortem(
                        f"async_flush worker error: {e!r}")
                if on_error is not None:
                    try:
                        on_error(e)
                    except Exception:    # pragma: no cover
                        pass
            finally:
                with self._idle:
                    self._inflight -= 1
                    self._idle.notify_all()

    # --------------------------------------------------------- interface
    def submit(self, fn: Callable[[], Any],
               on_error: Optional[Callable] = None):
        """Queue one sealed-segment job. `on_error(exc)` runs on the
        worker after a failure (fills the job's PendingValues). The
        whole stopped-check + enqueue is one locked section: a job
        slipping in behind shutdown's sentinel would never run, leaving
        its PendingValues blocked forever. Backpressure waits on the
        condition (which releases the lock), NEVER on a bounded queue —
        a blocking put under the lock would deadlock against the
        worker's completion decrement."""
        with self._idle:
            while not self._stopped \
                    and self._inflight >= self._max_inflight:
                self._idle.wait()
            if self._stopped:
                raise RuntimeError("flush executor is shut down")
            self._inflight += 1
            self._ensure_worker_locked()
            self._q.put((fn, on_error))

    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    def drain(self, raise_latched: bool = True):
        """Block until every submitted job finished. With
        `raise_latched`, re-raise the first worker error latched since
        the last drain (rollback's detection point); otherwise the
        errors are discarded — the aborted step's pending outputs still
        carry them individually."""
        with self._idle:
            while self._inflight:
                self._idle.wait()
            errs, self._latched = self._latched, []
        if raise_latched and errs:
            raise _surface_error(errs[0])

    def shutdown(self, timeout: float = 5.0):
        """Drain, stop the worker thread, and join it. Errors latched
        by unread jobs are discarded (process is exiting)."""
        with self._idle:
            if self._stopped:
                return
            self._stopped = True
            t = self._thread
            self._idle.notify_all()   # wake submitters blocked on
            #                           backpressure so they raise
        if t is not None and t.is_alive():
            self._q.put(None)
            t.join(timeout)
        with self._lock:
            self._thread = None
            self._latched = []

    def worker_alive(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()


_EXECUTOR: Optional[FlushExecutor] = None
_EXEC_LOCK = threading.Lock()


def get_executor() -> FlushExecutor:
    global _EXECUTOR
    if _EXECUTOR is None:
        with _EXEC_LOCK:
            if _EXECUTOR is None:
                _EXECUTOR = FlushExecutor()
                atexit.register(shutdown)
    return _EXECUTOR


def drain(raise_latched: bool = True):
    """Drain the pipeline if it ever started (cheap no-op otherwise).
    THE sync primitive rollback/quiesce/checkpoint paths call before
    touching live state."""
    ex = _EXECUTOR
    if ex is not None:
        ex.drain(raise_latched=raise_latched)


def shutdown():
    global _EXECUTOR
    ex = _EXECUTOR
    if ex is not None:
        ex.shutdown()
        _EXECUTOR = None
