"""Structured runtime spans.

A span is one timed region of the fused runtime (a segment flush, an
XLA compile, a collective) that fans out to every enabled consumer:

- metrics:  duration observed into a histogram (`hist` name, so e.g.
  every `segment::flush[<reason>]` variant feeds ONE `segment.flush_us`
  histogram instead of fragmenting per reason);
- trace:    an event appended to the profiler's host-event buffer, so
  the chrome-trace export shows the span on the recording thread's
  lane, nested under/over other host events by time;
- flight:   a ring-buffer entry for post-mortem dumps.

Callers pre-gate on `_state.ACTIVE` — constructing a span when
everything is off never happens on a hot path.

Two clocks. A live span times itself on `perf_counter_ns` (monotonic, of
arbitrary origin: the consumers' clock) and stamps `start_ns` on the epoch
clock beside it, which is the clock of JAX's own monitoring events and the
one a device trace can be laid over (a profile counts from its own start;
one span stamped on both clocks says how far apart they are). A
span that something else timed and that has already ended (JAX's trace,
lowering and compile events: `programs.py`) comes in through `record`,
on the epoch clock. A span's self time is its duration less what the
spans it adopted cover.
"""
from __future__ import annotations

import time

from . import _state, metrics


class Span:
    __slots__ = ("name", "hist", "args", "_t0", "start_ns", "dur_us",
                 "parent", "children_us")

    def __init__(self, name: str, hist=None, args=None):
        self.name = name
        self.hist = hist
        self.args = args
        self._t0 = None
        self.start_ns = None        # epoch clock
        self.dur_us = None          # set when the span has ended
        self.parent = None
        self.children_us = 0.0      # what adopted spans cover of it

    @property
    def end_ns(self) -> int:
        return self.start_ns + int(self.dur_us * 1000.0)

    @property
    def self_us(self) -> float:
        return self.dur_us - self.children_us

    def adopt(self, child: "Span"):
        """`child` ran inside this span, on its thread."""
        child.parent = self
        self.children_us += child.dur_us

    def begin(self):
        self._t0 = time.perf_counter_ns()
        self.start_ns = time.time_ns()
        if _state.GOODPUT:
            # the attribution ledger's state transition: entering a
            # mapped span (execute/compile/comm/io/ckpt/...) switches
            # the wall-clock bucket the goodput plane accrues into
            from . import goodput
            goodput.on_span_begin(self.name, self._t0)
        return self

    def end(self, error=None):
        if self._t0 is None:
            return
        t0, self._t0 = self._t0, None
        now_ns = time.perf_counter_ns()
        self.dur_us = dur_us = (now_ns - t0) / 1000.0
        if _state.GOODPUT:
            from . import goodput
            goodput.on_span_end(self.name, now_ns, dur_us)
        self._fan_out(t0, dur_us, error)

    def _fan_out(self, t0, dur_us, error=None):
        """To every consumer that is on but the goodput ledger, whose
        state machine takes a span's two ends as they happen. `t0` is the
        start on the consumers' clock, `perf_counter_ns`."""
        if _state.METRICS and self.hist is not None:
            metrics.observe(self.hist, dur_us)
        if _state.TRACE:
            from ..profiler import _add_span_event
            _add_span_event(self.name, t0 / 1000.0, dur_us,
                            dict(self.args or (), epoch_ns=self.start_ns))
        if _state.FLIGHT:
            from . import flight
            detail = dict(self.args) if self.args else {}
            detail["dur_us"] = round(dur_us, 1)
            if error is not None:
                detail["error"] = repr(error)
            flight.note("span", self.name, **detail)
        if _state.DIST:
            from . import distributed
            distributed.note_span(
                self.name, t0, dur_us,
                (self.args or {}).get("bytes", 0))

    def __enter__(self):
        return self.begin()

    def __exit__(self, et, ev, tb):
        self.end(error=ev)
        return False


def span(name: str, hist: str = None, **args) -> Span:
    return Span(name, hist, args or None)


def record(name: str, start_epoch_ns: int, dur_us: float, parent: Span = None,
           **args) -> Span:
    """A span that has already ended, timed by someone else on the epoch
    clock: the same record and the same consumers as a live one's (all but
    the goodput ledger). `parent` adopts it."""
    s = Span(name, None, args or None)
    s.start_ns, s.dur_us = int(start_epoch_ns), float(dur_us)
    if parent is not None:
        parent.adopt(s)
    if _state.ACTIVE:
        # its start on the consumers' clock, by the clocks' distance now
        s._fan_out(time.perf_counter_ns() - (time.time_ns() - s.start_ns),
                   s.dur_us)
    return s


class _NullSpan:
    """Shared no-op stand-in (stateless, safe to reuse) so call sites
    can write `with maybe_span(...)` without a branch."""

    def begin(self):
        return self

    def end(self, error=None):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()
