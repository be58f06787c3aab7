"""Lazy op-capture engine: the eager fusion window + SOT graph builder.

Two reference roles land here, rebuilt the XLA way:

- the *fusion buffer / lazy trace window* the reference gets from CUDA
  stream asynchrony (per-op kernels queue on a stream; the host runs
  ahead): under `lazy_guard()` eager ops are RECORDED instead of
  dispatched one executable at a time, and a whole pending segment runs
  as ONE jitted XLA program the first time any concrete value is needed.
  This removes per-op dispatch latency and lets XLA fuse across op
  boundaries (SURVEY §7 hard part #1).
- the *FunctionGraph* under SOT-style bytecode capture
  (python/paddle/jit/sot/symbolic/symbolic_context.py role): jit/sot's
  OpcodeExecutor runs user bytecode under this context; every framework
  op joins the graph, and any graph break (print, .numpy(), a
  data-dependent branch) is just a flush — the remaining trace resumes
  into a new segment automatically.

Materialization triggers: reading `Tensor._value` (property), exiting
the guard, `backward()`, or the segment hitting
FLAGS_lazy_max_segment_ops. Shape/dtype/ndim metadata reads answer from
the recorded aval WITHOUT materializing.

Compiled segments are cached by a structural signature (op names, attrs,
wiring, input avals), so steady-state replays cost one cache lookup and
one XLA execution per segment.
"""
from __future__ import annotations

import contextlib
import functools
import warnings
import weakref
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dispatch
from . import flags as _flags
from . import persist as _persist
from ..observability import _state as _OBS
from .async_flush import resolve_pending
from .cache import ExecCache
from .op_registry import OpDef

# Compiled-runner caches (LRU-bounded by FLAGS_executable_cache_capacity):
#   _SEG_CACHE   (signature, donate_mask) -> jitted segment runner
#   _FUSED_CACHE (signature, grad_in, root) -> jitted fwd+vjp step runner
# The stat names feed cache.<name>.{hit,miss} observability counters;
# cache.fused_step is THE steady-state step-cache hit-rate signal.
_SEG_CACHE: Dict[Tuple, Any] = ExecCache(stat="segment")
_FUSED_CACHE: Dict[Tuple, Any] = ExecCache(stat="fused_step")
# out-aval cache for record-time shape inference: LRU-bounded like the
# executable caches (shape-polymorphic workloads mint unbounded keys).
# No ExecCache stat: it is not an executable cache, so its hit/miss
# counters live under record.aval_cache.* (counted in _out_avals) and
# stay OUT of the derived cache_hit_rate headline.
_AVAL_CACHE: Dict[Tuple, Tuple] = ExecCache()

# Mesh epoch: a salt baked into every segment/step-cache signature.
# Elastic re-planning (resilience/adaptive.py) bumps it after moving
# live state onto a new device mesh, so the first post-replan step
# compiles exactly ONE fresh executable against the new layout instead
# of silently hitting a runner whose donation bookkeeping and sharding
# assumptions were fixed on the old mesh; every later step hits the
# re-keyed entry (recompile-exactly-once, asserted in
# tests/test_resilience.py via the compiles.fused_step counter).
MESH_EPOCH = 0

# Ambient SPMD mesh (distributed/spmd.py activates/clears this; lazy
# NEVER imports distributed). While set, cache signatures gain a
# sharding component — (mesh shape+axes, per-input PartitionSpec) —
# and the compile sites lower with GSPMD in_shardings so collectives
# live inside the executable. None = the zero-cost single-device path:
# one module-attr read per flush, zero extra key bytes.
SPMD = None

# sharding-component builds (diagnostics + tests/test_spmd_step.py's
# off-freeze assert: a no-mesh run must never touch the sharding key path)
SHARD_SIG_BUILDS = 0

# Perf-lint flush observer (analysis/perf_checks.py installs
# hooks.on_perf_flush here while a PerfRecorder is active): every seal
# of the fusion window — flush, per-op replay, fused backward — reports
# (ctx, reason, pending) so the static perf analyzer can attribute
# fusion-window breaks and host syncs to the recorded ops' source
# lines. None = one module-attr read per flush, zero work.
PERF_OBSERVER = None

# Forced src capture for perf traces (nesting counter): _PendingOp.src
# is normally captured only under FLAGS_static_checks, but perf
# diagnostics must point at Python source even with the sanitizer off —
# the analysis CLI and check_perf bump this around their own traces.
PERF_SRC = 0

# True when some executable was cached WITHOUT cost_analysis capture
# (compiled while FLAGS_compute_telemetry was off). Entering the
# compute plane bumps MESH_EPOCH only while this is set — so a
# monitoring loop that flips the plane on/off around each budget
# sample (budget.collect) does not invalidate every compiled-program
# cache in the process on every sample once the warm entries already
# carry their analyses.
COST_STALE = False


def mark_cost_stale():
    global COST_STALE
    COST_STALE = True


# ---- trace-stable record fast path (FLAGS_record_fast_path).
# A steady-state train step records the same op sequence every
# iteration — the signature memo proves it at seal time. While proven,
# the context retains the sealed segment's op SKELETON and replays it
# against the incoming (op, attrs, input-wiring) stream
# position-for-position: matching ops skip jax.eval_shape / aval-cache
# key construction / attrs copying / sig-entry interning entirely and
# reuse the skeleton's cached out-avals + interned entries, re-binding
# only external input payloads. Any mismatch falls back to the full
# record path for the rest of the segment. FAST_OPS counts replayed
# ops process-wide (tests/test_record_fastpath.py); _FAST_GEN is the
# skeleton generation — bumping it (mesh-epoch bump / replan, relevant
# set_flags) invalidates every armed skeleton at its next fast record.
FAST_OPS = 0
_FAST_PATH = True
_FAST_GEN = 0
# C mirror of _FAST_GEN + the whole-step driver's arm cell — declared
# BEFORE the flag watchers below fire (they invalidate at import); the
# driver itself is documented at _DriveState further down
_FAST_GEN_CELL: list = [0]
_DRIVE_CELL: list = [None]
_DRIVE_OK = False


def invalidate_skeletons(_value=None) -> int:
    """Bump the skeleton generation: every context drops its armed
    record skeleton on the next fast-record attempt (re-armed at the
    next memo-proven seal). The C mirror cell retires any in-flight
    whole-step drive at its very next op for the same events."""
    global _FAST_GEN
    _FAST_GEN += 1
    _FAST_GEN_CELL[0] = _FAST_GEN
    return _FAST_GEN


def _sync_fast_path_gate(value):
    global _FAST_PATH
    _FAST_PATH = bool(value)
    invalidate_skeletons()


_flags.watch_flag("FLAGS_record_fast_path", _sync_fast_path_gate)
# sanitizer / provenance / segment-shape mode changes invalidate armed
# skeletons (the fast path re-proves the stream under the new mode)
_flags.watch_flag("FLAGS_static_checks", invalidate_skeletons)
_flags.watch_flag("FLAGS_compute_telemetry", invalidate_skeletons)
_flags.watch_flag("FLAGS_lazy_max_segment_ops", invalidate_skeletons)

# ---- whole-step replay promotion (FLAGS_step_replay_after). A shape
# whose skeleton fully replays N consecutive sealed iterations gets a
# STEP PLAN: the seal skips signature reconstruction entirely and runs
# the cached executable under a ``segment::replay_step`` span (goodput
# prices it as productive execute). Any structural drift, mechanical
# invalidation (mesh epoch, watched flags, note_inplace, grad-mode
# flip — they all break the per-op replay that feeds the plan) or a
# live-set change demotes that shape to per-op skeleton replay and
# re-arms the streak. REPLAY_STEPS counts driven seals process-wide
# (tests/test_step_replay.py and the off-freeze assertions read it).
REPLAY_STEPS = 0
_STEP_REPLAY_AFTER = 3


def _sync_step_replay_gate(value):
    global _STEP_REPLAY_AFTER
    _STEP_REPLAY_AFTER = int(value or 0)
    invalidate_skeletons()


_flags.watch_flag("FLAGS_step_replay_after", _sync_step_replay_gate)

# ---- the whole-step NATIVE driver (zero-python steady state). Once a
# shape's skeleton carries a promoted step plan, the executor gate arms
# a _DriveState in _DRIVE_CELL after the segment's FIRST fast record:
# from then on apply() hands each dispatch to ONE C call
# (eager_core.drive_record) that coerces operands, validates against
# the plan cursor and mints the outputs — no python-level gate, scalar
# cache probe, context lookup or per-op counter write. The C side holds
# the two mutable cells below (registered once via bind_drive):
# _FAST_GEN_CELL mirrors _FAST_GEN, so every mechanical invalidation
# event (mesh epoch, watched flags, step-replay flag) retires an
# in-flight drive at its next op, and _DRIVE_CELL[0] is the armed
# state (None = disarmed). The driver retires ITSELF on plan
# completion, segment cap and any mismatch; _drive_reconcile writes
# the driven cursor + batched counters back at every python re-entry
# point that reads them (flush, segment reset, note_inplace,
# interceptor installs via executor._sync_apply_fast). When the C
# library is unavailable (_DRIVE_OK stays False) the bit-exact pure
# python driver is the per-op skeleton replay + the _step_plan_sig
# seal — same admissions, same demotions, just not one-call-per-op.


class _DriveState:
    """Flat per-segment view of everything drive_record touches per op,
    one resolved slot offset away: the plan's ctups + sealed in-sig,
    the context's CURRENT segment lists (the same objects the context
    attributes name — the driver appends to them in place), the armed
    generation, the owning thread and the replay cursor. `n_driven`
    batches the per-op counters until retire/reconcile."""

    __slots__ = ("ctx", "ctups", "in_sig", "in_ids", "in_tensors",
                 "in_vals", "in_meta", "in_pins", "pending", "sig_ops",
                 "pinned", "pos", "gen", "cap", "n_driven", "tid",
                 "sc_k", "sc_v")


def _arm_drive(ctx, sk):
    """Publish a drive for the rest of the current segment (called by
    the executor gate right after a successful fast record of a
    plan-carrying skeleton)."""
    if not _DRIVE_OK:
        return
    d = _DriveState()
    d.ctx = ctx
    d.ctups = sk.ctups
    d.in_sig = sk.in_sig
    d.in_ids = ctx._in_ids
    d.in_tensors = ctx._in_tensors
    d.in_vals = ctx._in_vals
    d.in_meta = ctx._in_meta
    d.in_pins = ctx._in_pins
    d.pending = ctx.pending
    d.sig_ops = ctx._sig_ops
    d.pinned = ctx.on_flush is not None
    d.pos = ctx._skel_pos
    d.gen = sk.gen
    cap = ctx._max_override
    d.cap = _MAX_SEG_OPS if cap is None else cap
    d.n_driven = 0
    d.tid = _threading.get_ident()
    # per-drive scalar memo: scalar-OBJECT identity -> wrapper tensor
    # (literals from co_consts keep identity across iterations, so the
    # drive's steady state skips the key-tuple hash probe per operand;
    # the memo lives exactly as long as the drive, so it can never
    # disagree with the in_ids registrations made through it)
    d.sc_k = []
    d.sc_v = []
    _DRIVE_CELL[0] = d


def _drive_reconcile(ctx):
    """Write an armed drive's cursor and batched counters back to its
    context and disarm. Idempotent with the C driver's own retire (the
    cell is cleared first, counters are zeroed on read) — called at
    every python re-entry point that reads _skel_pos/_fast_ops or
    rebinds the segment lists."""
    global FAST_OPS
    d = _DRIVE_CELL[0]
    if d is None or d.ctx is not ctx:
        return
    _DRIVE_CELL[0] = None
    ctx._skel_pos = d.pos
    n = d.n_driven
    if n:
        d.n_driven = 0
        ctx._fast_ops += n
        ctx.ops_recorded += n
        FAST_OPS += n


def _drive_disarm():
    """Retire any armed drive through its context — interceptor
    installs and per-op modes change what apply() must do per op, so
    the plan's whole-step equivalence no longer holds."""
    d = _DRIVE_CELL[0]
    if d is not None:
        _drive_reconcile(d.ctx)


def bump_mesh_epoch() -> int:
    """Invalidate the compiled-segment and fused-step cache keys (the
    old entries age out of the LRU; nothing is recompiled until the
    next flush). Armed record skeletons are invalidated too — a replan
    must re-prove the op stream on the new mesh."""
    global MESH_EPOCH
    MESH_EPOCH += 1
    invalidate_skeletons()
    return MESH_EPOCH


# ---- hot-path flag gates. current_context()/max_ops used to pay ~4
# registry lookups per RECORDED OP; the watcher pattern
# (STATIC_CHECKS_ACTIVE) caches each flag into a module attribute that
# set_flags keeps coherent, so mid-session flips still take effect
# immediately (test_flags_surface contract) at one attribute read.
_LAZY_ENABLE = True
_EAGER_FUSION = True
_MAX_SEG_OPS = 256
_DONATE_INPUTS = True


def _mk_gate(name):
    def _set(v, _n=name):
        globals()[_n] = v
    return _set


_flags.watch_flag("FLAGS_lazy_enable", _mk_gate("_LAZY_ENABLE"))
_flags.watch_flag("FLAGS_eager_fusion", _mk_gate("_EAGER_FUSION"))
_flags.watch_flag("FLAGS_lazy_max_segment_ops", _mk_gate("_MAX_SEG_OPS"))
_flags.watch_flag("FLAGS_lazy_donate_inputs", _mk_gate("_DONATE_INPUTS"))

# flush reasons eligible for the async pipeline: seals where the
# recording thread genuinely runs ahead. A cap mid-record always
# qualifies; a guard EXIT does too — the code after the `with` block
# (or after a SOT-captured call returns) continues on pending values
# and only blocks at a real read. Materialize reads block on the
# result anyway — going async there only adds a thread hop to the
# critical path — and guard_error stays synchronous (unwind path).
_ASYNC_REASONS = frozenset(("segment_cap", "guard_exit"))

# set the first time a segment is flushed asynchronously; gates the
# resolve-scan at consumption points so the sync-only path never pays
# even the per-value getattr walk
_ASYNC_SEEN = False


class _CachedKey:
    """Executable-cache key wrapper with a precomputed hash.

    The steady-state signature memo returns the SAME _CachedKey object
    every step, so the per-step cache lookup costs one cached-int hash
    and one identity compare instead of re-hashing a structure that
    grows with the op count. Subscripting delegates to the wrapped
    tuple (register_segment_grad slices sig[1]/sig[2]/sig[4]
    positionally)."""

    __slots__ = ("sig", "_h")

    def __init__(self, sig):
        self.sig = sig
        self._h = hash(sig)

    def __hash__(self):
        return self._h

    def __eq__(self, other):
        if self is other:
            return True
        if isinstance(other, _CachedKey):
            return self._h == other._h and self.sig == other.sig
        return NotImplemented

    def __getitem__(self, i):
        return self.sig[i]

    def __repr__(self):
        return f"_CachedKey({self.sig!r})"


# Hot-import bindings: record()/_lazy_tensor() run per recorded op, and
# a function-local `from .tensor import Tensor` costs an importlib
# round-trip per call (~190 of them per 32-op chain step in the
# profile).
# Bound once on first use — module top-level import would be cyclic
# during package init (tensor -> autograd -> dispatch while lazy loads).
_TENSOR_CLS = None
_AUTOGRAD_META = None
_IS_GRAD_ENABLED = None


def _bind_hot_imports():
    global _TENSOR_CLS, _AUTOGRAD_META, _IS_GRAD_ENABLED
    from .autograd import AutogradMeta, is_grad_enabled
    from .tensor import Tensor
    _TENSOR_CLS = Tensor
    _AUTOGRAD_META = AutogradMeta
    _IS_GRAD_ENABLED = is_grad_enabled
    return Tensor


# flush reasons that BREAK the fusion window mid-step (vs. the natural
# whole-step seals: materialize, backward_fused, grad_targets, guard
# exits). Each break forfeits the step cache and the optimizer's
# donation fast path for that window — the BUDGET_r06 eager-GPT finding
# (4 record_fallback breaks/step) promoted to a first-class counter.
_WINDOW_BREAK_REASONS = frozenset(
    ("record_fallback", "segment_cap", "ambient_disable", "guard_error"))


def _obs_flush_span(reason: str, n_ops: int, n_inputs: int, n_live: int,
                    n_donate: int, n_fast: int = 0):
    """Counters + the begun flush span. Callers gate on _OBS.ACTIVE —
    this never runs when observability, tracing, and the flight
    recorder are all off."""
    if _OBS.METRICS:
        from ..observability import metrics
        metrics.inc("segment.flushes")
        # record_fallback:<op> collapses to one reason bucket
        head = reason.split(":", 1)[0]
        metrics.inc("segment.flush_reason." + head)
        if head in _WINDOW_BREAK_REASONS:
            metrics.inc("fusion.window_breaks")
            metrics.inc("fusion.window_breaks." + head)
        metrics.inc("segment.ops", n_ops)
        if n_fast:
            # skeleton-replayed records of this segment (counted at
            # seal so the fast path pays zero per-op registry work);
            # budget surfaces record.* next to the segment counters
            metrics.inc("record.fast_ops", n_fast)
        if n_donate:
            metrics.inc("segment.donated_inputs", n_donate)
    from ..observability.spans import span
    return span(f"segment::flush[{reason}]", hist="segment.flush_us",
                reason=reason, ops=n_ops, inputs=n_inputs,
                live=n_live, donated=n_donate).begin()


def _obs_exec_span(compiled: bool, n_ops: int, driven: bool = False):
    """The compile-vs-cached-execute split under a flush span (compile
    counters are bumped at the call sites, which know WHICH cache
    missed: compiles.segment vs compiles.fused_step). A promoted
    whole-step seal takes its own ``segment::replay_step`` name —
    goodput prices it in the execute bucket, and the distinct histogram
    is the step-driver's latency meter."""
    from ..observability.spans import span
    if driven and not compiled:
        return span("segment::replay_step",
                    hist="segment.replay_step_us", ops=n_ops).begin()
    return span("segment::compile" if compiled else "segment::execute",
                hist=("segment.compile_us" if compiled
                      else "segment.execute_us"), ops=n_ops).begin()


def _obs_flush_failed(reason: str, err: BaseException):
    """Failed flush: the flight recorder's post-mortem trigger."""
    if _OBS.FLIGHT:
        from ..observability import flight
        flight.on_error("flush_failed", f"reason={reason}: {err!r}")


def _nan_scan_segment(pending, live, out_vals, kind, in_vals=(),
                      extra=None):
    """FLAGS_check_nan_inf sweep over a flushed/replayed segment's live
    outputs, blaming the producing op WITH its record-time file:line
    provenance (_PendingOp.src, captured while checks are on) — a
    postmortem must name where the tripping value was recorded, not
    just which kernel emitted it. On a trip, the numerics plane's NaN
    forensics re-runs the range propagation over the offending program
    and attaches the ranked suspect ops to the flight dump before the
    FloatingPointError continues up. `extra` is an optional
    (label, values) pair swept after the live outputs (the fused
    backward's gradient bundle)."""
    try:
        for (j, _s), val in zip(live, out_vals):
            p = pending[j]
            src = getattr(p, "src", None)
            dispatch._check_nan_inf(
                f"{p.op.name} ({kind}" + (f" @ {src})" if src else ")"),
                (val,))
        if extra is not None:
            dispatch._check_nan_inf(extra[0], tuple(extra[1]))
    except FloatingPointError:
        from ..analysis import hooks as _ahooks
        _ahooks.on_nan_trip(None, pending, list(in_vals), kind)
        raise


def _oom_convert(e: BaseException, where: str, mem_info=None):
    """RESOURCE_EXHAUSTED at an execute site becomes the typed
    ``base.core.ResourceExhaustedError`` carrying the memory
    postmortem (top live buffers with provenance, failing executable's
    memory analysis, watermark). Anything else passes through at the
    cost of one substring check — this only runs on the error path."""
    if "RESOURCE_EXHAUSTED" not in str(e):
        return e
    from ..observability import memory as _memtel
    return _memtel.on_oom(e, where, mem_info)


def _inject_exec_oom():
    """``exec::oom`` drill site: a synthetic RESOURCE_EXHAUSTED at the
    execute boundary (resilience/faults.py kind ``oom``), fired at all
    three execute sites so the OOM postmortem path — including the
    async worker's typed re-raise at the sync point — is testable
    without exhausting real device memory. Callers pre-gate on
    ``_flags.FAULT_INJECT_ACTIVE``."""
    from ..distributed.resilience import faults as _faults
    _faults.inject("exec::oom")


def _spmd_jit(fn, donate, run_vals, spmd):
    """jit with explicit GSPMD input layouts when an ambient mesh is
    active: every input's committed on-mesh sharding (replicated for
    the rest) becomes an ``in_shardings`` entry, so the ONE compiled
    program is partitioned over the dp×mp mesh and its collectives
    (gradient all-reduce, TP exchanges) are emitted by the compiler
    instead of driven from the host. Tracer inputs fall back to plain
    jit (spmd.in_shardings returns None)."""
    if spmd is not None:
        shardings = spmd.in_shardings(run_vals)
        if shardings is not None:
            if _OBS.METRICS:
                from ..observability import metrics
                metrics.inc("compiles.spmd")
            return jax.jit(fn, donate_argnums=donate,
                           in_shardings=shardings)
    return jax.jit(fn, donate_argnums=donate)


def _note_compiled_comm(cache, key, spmd, in_vals, out_vals, site,
                        gather_only=False):
    """Observability parity for collectives compiled INTO a program:
    estimate their payload from the in/out sharding specs (computed
    once per compile, cached on the ExecCache entry like the memory
    analysis) and count them per execution as
    ``comm.bytes.compiled.<site>`` — so moving collectives off the
    host does not blind the PR-8 comm-overlap report. Callers gate on
    ``_OBS.METRICS and SPMD``."""
    est = cache.comm_info(key)
    if est is None:
        est = spmd.estimate_bytes(in_vals, out_vals,
                                  gather_only=gather_only)
        cache.note_comm(key, est)
    if est:
        from ..observability import metrics
        metrics.inc("comm.bytes.compiled." + site, est)


def _mesh_devices(spmd) -> int:
    """Pricing basis for the compute plane's per-chip cost analysis:
    the ambient mesh's device count (1 without a mesh)."""
    if spmd is None:
        return 1
    n = 1
    for s in spmd.shape:
        n *= int(s)
    return n


def _compile_segment_runner(pending, live, donate, run_vals, sig,
                            spmd=None):
    """Build one segment's cached runner. With the memory or compute
    telemetry plane on (and concrete inputs), compile through the jax
    AOT path so the executable's ``memory_analysis()`` /
    ``cost_analysis()`` land on the ExecCache entry exactly once per
    compile; otherwise the plain jit wrapper. Both are interchangeable
    callables — the cache key already pins the input signature, so an
    AOT-compiled entry only ever sees matching arguments. `spmd` is
    the ambient mesh the caller keyed the segment against (the async
    worker passes its seal-time capture)."""
    jitted = _spmd_jit(_build_segment_fn(pending, live), donate,
                       run_vals, spmd)
    if not _OBS.COMPUTE:
        mark_cost_stale()
    if (_OBS.MEM or _OBS.COMPUTE or _persist.ACTIVE) and not any(
            isinstance(v, jax.core.Tracer) for v in run_vals):
        from ..observability import memory as _memtel
        with _quiet_donation_compile():
            return _memtel.aot_compile(jitted, run_vals, stat="segment",
                                       cache=_SEG_CACHE,
                                       key=(sig, donate),
                                       n_devices=_mesh_devices(spmd))
    return jitted


def _spmd_for_compile(in_vals):
    """The ambient mesh a program should be PINNED against, or None.
    A segment whose key-time inputs include unresolved PendingValues
    compiles without in_shardings: their layout is unknowable at seal
    time, the key carries the "?" sentinel for them, and an unpinned
    jit re-specializes per input layout internally — so one cache
    entry stays correct for every layout the producer hands it."""
    spmd = SPMD
    if spmd is None:
        return None
    if _ASYNC_SEEN and any(getattr(v, "_is_pending_value", False)
                           for v in in_vals):
        return None
    return spmd


def _compile_fused_runner(pending, live, grad_in, root_k, run_vals, key,
                          spmd=None):
    """Fused fwd+vjp step runner, AOT-compiled for its memory / cost
    analysis when a telemetry plane is on (the steady-state step cache
    can then report its compiled footprint and price its FLOPs on
    every later hit)."""
    jitted = _spmd_jit(_build_fused_fn(pending, live, grad_in, root_k),
                       (), run_vals, spmd)
    if not _OBS.COMPUTE:
        mark_cost_stale()
    if (_OBS.MEM or _OBS.COMPUTE or _persist.ACTIVE) and not any(
            isinstance(v, jax.core.Tracer) for v in run_vals):
        from ..observability import memory as _memtel
        with _quiet_donation_compile():
            return _memtel.aot_compile(jitted, run_vals,
                                       stat="fused_step",
                                       cache=_FUSED_CACHE, key=key,
                                       n_devices=_mesh_devices(spmd))
    return jitted


def _persist_sig(sig) -> Tuple:
    """Disk identity of a segment signature: the raw key with its
    MESH_EPOCH component (position 4) zeroed. The epoch salt exists to
    re-key IN-MEMORY entries across elastic re-plans, but every
    structural consequence of a re-plan already lives in the signature
    (shard_sig / input avals / op stream), so two processes — or two
    re-plan cycles landing on the same layout — share one disk entry."""
    raw = sig.sig if isinstance(sig, _CachedKey) else tuple(sig)
    return raw[:4] + (0,) + raw[5:]


def _jit_factory(build_fn, donate, run_vals, spmd):
    """Deferred jit construction for a disk-loaded runner's tracer
    fallback. The in_shardings are resolved NOW (cheap metadata) so
    the retained closure never pins the input BUFFERS — a pinned param
    buffer would defeat the refcount-proof donation checks (lazy's
    _donatable_inputs, the optimizer's _pick_update) for as long as
    the runner lives."""
    shardings = None
    if spmd is not None:
        shardings = spmd.in_shardings(run_vals)

    def factory():
        if shardings is not None:
            return jax.jit(build_fn(), donate_argnums=donate,
                           in_shardings=shardings)
        return jax.jit(build_fn(), donate_argnums=donate)

    return factory


def _disk_runner(kind, norm_key, jit_factory, cache=None, key=None,
                 stat="segment"):
    """Consult the persistent executable cache after an in-memory miss
    and BEFORE ``lower().compile()``. A verified hit rehydrates into a
    runner (telemetry sidecars re-noted so warm loads keep their
    meters) — the caller then takes the cached-execute span and bumps
    no ``compiles.*`` counter. Callers pre-gate on ``_persist.ACTIVE``."""
    payload = _persist.load(kind, norm_key)
    if payload is None:
        return None
    runner = _persist.make_runner(payload, jit_factory)
    if runner is None:
        return None
    _persist.renote(payload, stat, cache, key)
    return runner


def _disk_store(kind, norm_key, runner, cache=None, key=None):
    """Persist a freshly-compiled runner's executable + sidecars. Only
    AOT-compiled runners carry the raw Compiled (`aot_executable`);
    with persistence active the compile helpers always take the AOT
    path for concrete inputs, so a plain-jit runner here means tracer
    inputs — not persistable, skip silently."""
    if getattr(runner, "persisted", False):
        return
    compiled = getattr(runner, "aot_executable", None)
    if compiled is not None:
        _persist.store(kind, norm_key, compiled,
                       _persist.sidecars(runner, cache, key))


def _note_donated_inputs(in_vals, donate):
    """Donation savings accounting: bytes of the input buffers this
    executed program consumed in place (gated on _OBS.MEM by callers)."""
    from ..observability import memory as _memtel
    _memtel.note_donated(sum(getattr(in_vals[i], "nbytes", 0)
                             for i in donate))


@contextlib.contextmanager
def _quiet_donation_compile():
    """Backends without buffer donation (CPU) warn at compile time and
    silently copy instead; donation is a best-effort optimization here,
    not a contract. Scoped around OUR compile-triggering first calls so
    the suppression never leaks into user code, where the same warning
    may be the only signal that their own donate_argnums degraded."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def _live_aliases(ref):
    """Tensors still ALIASING this pending output. Payload identity is
    the correctness-bearing invariant: a tensor overwritten in place
    mid-segment no longer keeps its old pending output alive — and must
    not be clobbered when the segment's results are bound."""
    return [t for t in (r() for r in ref.trefs)
            if t is not None and t._payload is ref]


class LazyRef:
    """Placeholder payload for one output of one pending op."""

    _is_lazy_ref = True
    __slots__ = ("ctx", "op_idx", "slot", "aval", "requires_grad",
                 "trefs", "__weakref__")

    def __init__(self, ctx, op_idx, slot, aval, requires_grad):
        self.ctx = ctx
        self.op_idx = op_idx
        self.slot = slot
        self.aval = aval              # jax.ShapeDtypeStruct
        self.requires_grad = requires_grad
        self.trefs: List = []         # weakrefs to Tensors aliasing this

    def add_tref(self, tensor):
        self.trefs.append(weakref.ref(tensor))

    def materialize(self):
        self.ctx.flush()


class _PendingOp:
    __slots__ = ("op", "attrs", "wiring", "out_refs", "n_outs", "src")

    def __init__(self, op, attrs, wiring, out_refs, src=None):
        self.op = op
        self.attrs = attrs
        self.wiring = wiring          # per input: ("in", i) | ("op", j, s) | None
        self.out_refs = out_refs      # list[LazyRef]
        self.n_outs = len(out_refs)
        # "file:line" of the recording user frame — captured only under
        # FLAGS_static_checks so diagnostics can point at Python source;
        # deliberately NOT part of the segment signature
        self.src = src


# the view-op family the sanitizer's alias graph tracks (reference
# semantics alias storage). THE authoritative set — it lives here so
# the record hot path gates on it without importing analysis;
# analysis.alias_graph re-exports it as VIEW_OP_NAMES
_VIEW_OP_NAMES = frozenset((
    "reshape", "squeeze", "unsqueeze", "flatten_", "transpose",
    "view_slice", "view_dtype", "strided_slice_", "diagonal_", "split_",
))

# str(np.dtype) costs ~10us a call and the dispatch hot path needs it
# for every input of every signature — memoized per dtype object
_DTYPE_STR: Dict[Any, str] = {}


def _dstr(dt) -> str:
    s = _DTYPE_STR.get(dt)
    if s is None:
        s = _DTYPE_STR[dt] = str(dt)
    return s


# jnp.issubdtype(dt, inexact) walks the numpy type lattice (~1-2us);
# the record hot path asks it per output — memoized per dtype object
_INEXACT_DT: Dict[Any, bool] = {}


def _is_inexact(dt) -> bool:
    r = _INEXACT_DT.get(dt)
    if r is None:
        r = _INEXACT_DT[dt] = bool(jnp.issubdtype(dt, jnp.inexact))
    return r


# Native record core (csrc/eager_core.cc): interned shape/dtype atoms,
# the aval-cache key build + lookup and the sig-entry intern in C.
# Resolved once through dispatch's extension loader; None = the pure
# python path (which must stand alone — the library is best-effort).
# Bench row 17 and the fallback tests force either prong by setting
# _NC/_NC_TRIED directly.
_NC = None
_NC_TRIED = False


def _native_core():
    global _NC, _NC_TRIED, _DRIVE_OK
    _NC_TRIED = True
    ec = dispatch._eager_core()
    if ec is not None and hasattr(ec, "aval_cache_get"):
        if hasattr(ec, "bind_types"):
            from .autograd import AutogradMeta
            from .tensor import Tensor
            ec.bind_types(LazyRef, Tensor, AutogradMeta, _PendingOp,
                          jax.core.Tracer)
        if hasattr(ec, "bind_drive"):
            # whole-step driver registration: the C side keeps direct
            # handles to the op registry, the live scalar-wrapper cache
            # (read per op — can never go stale), the two mutable cells
            # and this module (retire writes FAST_OPS). Refuses (False)
            # when any _DriveState slot offset fails to resolve; the
            # driver then stays off and replay runs per-op.
            try:
                import sys
                from . import executor as _executor
                _DRIVE_OK = bool(ec.bind_drive(
                    _DriveState, _executor._OPS,
                    _executor._SCALAR_TENSORS, _FAST_GEN_CELL,
                    _DRIVE_CELL, sys.modules[__name__]))
                if _DRIVE_OK:
                    _executor._NC_DRIVE = ec.drive_record
            except Exception:
                _DRIVE_OK = False
        _NC = ec
    return _NC


# per-op signature entries interned by content: steady-state memo
# validation compares tuples of IDENTICAL entry objects, so the
# per-step check is n pointer compares (exact, not sampled). Past
# 65536 entries the pool is CLEARED — identity compares degrade to
# tuple equality until repopulation, never correctness (pinned in
# tests/test_record_fastpath.py). The native core keeps its own pool
# with the same overflow rule.
_SIG_ENTRY_INTERN: Dict[Tuple, Tuple] = {}


def _intern_sig_entry(entry: Tuple) -> Tuple:
    nc = _NC if _NC_TRIED else _native_core()
    if nc is not None:
        return nc.sig_entry(entry)
    e = _SIG_ENTRY_INTERN.setdefault(entry, entry)
    if len(_SIG_ENTRY_INTERN) > 65536:
        _SIG_ENTRY_INTERN.clear()
    return e


def _aval_of(x):
    # weak_type MUST survive: python scalars are weak (x64 mode makes
    # them f64-weak) and weak+f32 promotes to f32, not f64
    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                weak_type=getattr(x, "weak_type", False))


def _out_avals(op: OpDef, attrs, in_avals, akey=None):
    if akey is None:
        akey = dispatch.attrs_key(attrs)
    backend = jax.default_backend()
    nc = _NC if _NC_TRIED else _native_core()
    if nc is not None:
        # C builds the (op, backend, attrs, per-aval atom) key in one
        # pass over interned shape/dtype atoms and probes the C-side
        # cache; the python dict below is the standalone fallback
        hit = nc.aval_cache_get(op.name, backend, akey, in_avals)
        key = None
    else:
        key = (op.name, backend, akey,
               tuple((tuple(a.shape), _dstr(a.dtype), a.weak_type)
                     if a is not None else None for a in in_avals))
        hit = _AVAL_CACHE.get(key)
    if _OBS.METRICS:
        # record.aval_cache.*, NOT cache.*: the derived cache_hit_rate
        # headline sums executable caches only
        from ..observability import metrics
        metrics.inc("record.aval_cache.hit" if hit is not None
                    else "record.aval_cache.miss")
    if hit is not None:
        return hit
    fn = functools.partial(op.kernel_for(backend), **attrs)
    out = jax.eval_shape(fn, *in_avals)
    outs = out if op.multi_output else (out,)
    hit = tuple(jax.tree_util.tree_leaves(outs))
    if len(hit) != len(outs):
        # nested outputs: treat as un-capturable
        raise TypeError(f"op {op.name} has nested outputs")
    if nc is not None:
        # the native pool honors the same capacity flag (clear-on-
        # overflow rather than LRU; inserts are compile-path cold)
        nc.aval_cache_put(op.name, backend, akey, in_avals, hit,
                          int(_flags.flag_value(
                              "FLAGS_executable_cache_capacity")))
    else:
        _AVAL_CACHE[key] = hit
    return hit


def _fast_attr_safe(v) -> bool:
    """True when an attr value is cheap AND safe to compare by dict
    equality on the fast path (primitives and tuples thereof — the
    same class the attrs-key intern treats as canonical). ndarrays /
    lists / exotic values take the interned-key comparison instead."""
    if v is None or type(v) in (bool, int, float, str, bytes):
        return True
    if type(v) is tuple:
        return all(_fast_attr_safe(x) for x in v)
    return False


class _SkelOp:
    """One retained op of a sealed segment's skeleton: everything the
    fast path needs to admit a position-matching record without
    re-deriving it (cached out-avals, interned sig entry, shared attrs
    dict, grad flags)."""

    __slots__ = ("op", "akey", "attrs", "fast_attrs", "wiring",
                 "out_avals", "out_req", "req", "has_inexact", "entry",
                 "n_outs", "ctup")


class _Skeleton:
    """One sealed segment shape's op skeleton (armed only once the
    signature memo proved the stream repeats). `in_sig` is the sealed
    segment's external-input aval signature — the fast path validates
    each fresh registration against it, so reused out-avals can never
    desync from what the inputs imply. `streak` counts consecutive
    fully-replayed seals; at FLAGS_step_replay_after it promotes to a
    whole-step `plan` — (live tuple, _CachedKey, ambient mesh) — that
    lets the seal skip signature reconstruction entirely (the driven
    ``segment::replay_step`` path)."""

    __slots__ = ("ops", "ctups", "in_sig", "gen", "streak", "plan")


class CaptureContext:
    """One lazy trace. Ops recorded since the last flush form the current
    segment; flush() compiles + runs it as one XLA executable."""

    def __init__(self, max_segment_ops: Optional[int] = None):
        self.pending: List[_PendingOp] = []
        # graph inputs of the CURRENT segment: id(tensor) -> index
        self._in_ids: Dict[int, int] = {}
        # WEAK refs to the input tensors: a tensor dying mid-segment must
        # not be pinned by the trace (only its payload snapshot in
        # _in_vals is needed to execute, and a dead input is a donation
        # candidate). _in_pins strong-pins them only under an on_flush
        # observer (SOT capture rebinds inputs at entry-build time).
        self._in_tensors: List = []
        self._in_pins: List = []
        self._in_vals: List = []
        # record-time autograd snapshot per input: (requires_grad,
        # AutogradMeta, inplace_version). The meta OBJECT is strongly
        # held: an intermediate that dies before the flush (a local of a
        # returned-from function) must still chain gradients through its
        # grad_node — only the tensor wrapper is gone, not the graph.
        self._in_meta: List = []
        # incremental structural signature: one entry appended per
        # recorded op, so flush never re-walks the whole pending list
        self._sig_ops: List[Tuple] = []
        self._max_override = max_segment_ops
        # steady-state signature memos, one per SEGMENT SHAPE (keyed by
        # the first interned sig entry — a real train step seals
        # several distinct segment shapes per iteration, e.g. the
        # fwd+bwd window and an optimizer tail, and a single slot would
        # thrash between them): (ops_key, in_sig, live, epoch, backend,
        # shard_sig) -> the _CachedKey handed out at that shape's last
        # seal. Validated by EXACT comparison over interned entries
        # (identity-fast) + the mesh epoch + the ambient-mesh sharding
        # component (None without a mesh), so a replan, a mesh switch
        # or any structural drift rebuilds. _sig_memo aliases the most
        # recent memo (tests read its _CachedKey).
        self._sig_memos: Dict[Any, Tuple] = {}
        self._sig_memo: Optional[Tuple] = None
        # (op_name, repr(error)) of the last record() failure — the
        # executor stashes it on the record_fallback path so the perf
        # analyzer can say WHY an op broke the window
        self._last_record_error = None
        # trace-stable record fast path: the BANK of retained skeletons
        # — one per memo-proven segment shape, bucketed by the shape's
        # first OpDef (the first record of a segment selects MRU-first)
        # and keyed inside the bucket by (length, last entry) like
        # _sig_memos, so two shapes sharing a leading op both keep
        # valid skeletons (mid-stream divergence switches candidates,
        # see _switch_skel) — plus the currently-selected skeleton, the
        # replay cursor into it, whether the CURRENT segment is still
        # matching, and how many of its ops were fast-replayed
        self._skels: Dict[Any, Dict[Tuple, _Skeleton]] = {}
        self._skeleton: Optional[_Skeleton] = None
        self._skel_pos = 0
        self._skel_live = False
        self._fast_ops = 0
        # stats for tests / profiling
        self.segments_run = 0
        self.ops_recorded = 0
        self.breaks: List[str] = []

    @property
    def max_ops(self) -> int:
        """Segment cap, read live (via the watcher-kept gate) so
        set_flags mid-session takes effect on already-open (incl.
        ambient) contexts."""
        if self._max_override is not None:
            return self._max_override
        return _MAX_SEG_OPS

    # ---------------------------------------------------------- recording
    def _input_index(self, tensor) -> int:
        idx = self._in_ids.get(id(tensor))
        # validate against id() reuse: the map entry is only good if the
        # weakref at that slot still points at THIS tensor
        if idx is not None and self._in_tensors[idx]() is tensor:
            return idx
        idx = len(self._in_vals)
        self._in_ids[id(tensor)] = idx
        self._in_tensors.append(weakref.ref(tensor))
        if self.on_flush is not None:
            self._in_pins.append(tensor)
        self._in_vals.append(tensor._payload)
        self._in_meta.append((not tensor.stop_gradient,
                              tensor._autograd_meta,
                              tensor._inplace_version))
        return idx

    def note_inplace(self, tensor):
        """`tensor`'s payload is being overwritten in place. Ops already
        recorded keep the registered snapshot (eager ordering); future
        records must re-register the fresh payload, so the id mapping is
        evicted. The orphaned snapshot becomes a donation candidate at
        flush (its backing tensor no longer aliases it). A mid-segment
        swap also drops the record skeleton: the input stream is being
        re-keyed under the replay's feet, so the fast path re-proves the
        stream at the next sealed steady-state segment instead of
        replaying across the mutation. (Between segments — the fused
        optimizer write-back — there is nothing recorded and the
        skeleton survives.)"""
        if _DRIVE_CELL[0] is not None:
            _drive_reconcile(self)
        self._in_ids.pop(id(tensor), None)
        if self.pending:
            sk = self._skeleton
            self._skeleton = None
            self._skel_live = False
            if sk is not None:
                # evict the banked entry of the shape being replayed
                for op in list(self._skels):
                    bucket = self._skels[op]
                    for k in [k for k, v in bucket.items() if v is sk]:
                        del bucket[k]
                    if not bucket:
                        del self._skels[op]

    def _select_skel(self, op: OpDef):
        """First record of a segment: select the most-recently-used
        banked skeleton whose sealed shape starts with `op` (stale
        generations evict; a mid-stream divergence from the MRU pick
        switches to a sibling shape, see _switch_skel). None = no
        candidate; this segment records through the full path."""
        bucket = self._skels.get(op)
        while bucket:
            k = next(reversed(bucket))
            sk = bucket[k]
            if sk.gen == _FAST_GEN:
                self._skeleton = sk
                return sk
            del bucket[k]
        if bucket is not None:
            del self._skels[op]
        self._skel_live = False
        return None

    def _switch_skel(self, op: OpDef):
        """Mid-stream candidate switch: the selected skeleton just
        mismatched at the replay cursor, but a SIBLING shape (same
        leading OpDef, different (length, last-entry) bucket key) may
        continue the stream — the satellite fix for two segment shapes
        sharing their first op. A candidate is valid only when its
        already-replayed prefix is exactly what this segment recorded:
        identical interned entries (compared by ``==`` — the intern
        pool may have been cleared), out-avals and grad flags, `op` at
        the cursor, and an in-signature prefix covering every external
        input registered so far. Returns the switched skeleton (made
        MRU) or None — nothing was mutated by the failed match, so the
        caller can simply retry the fast record against it."""
        sk = self._skeleton
        pos = self._skel_pos
        if sk is None or not sk.ops:
            return None
        bucket = self._skels.get(sk.ops[0].op)
        if not bucket:
            return None
        n_reg = len(self._in_vals)
        for k in list(reversed(bucket)):
            c = bucket[k]
            if c is sk or c.gen != _FAST_GEN or pos >= len(c.ops):
                continue
            if c.ops[pos].op is not op:
                continue
            if c.in_sig[:n_reg] != sk.in_sig[:n_reg]:
                continue
            ok = True
            for i in range(pos):
                a, b = c.ops[i], sk.ops[i]
                if a.entry != b.entry or a.out_avals != b.out_avals \
                        or a.out_req != b.out_req:
                    ok = False
                    break
            if not ok:
                continue
            del bucket[k]           # MRU refresh
            bucket[k] = c
            self._skeleton = c
            return c
        return None

    def _record_fast(self, op: OpDef, ts, attrs):
        """Trace-stable skeleton replay: admit this record by matching
        the armed skeleton position-for-position instead of re-deriving
        avals/keys. Returns the out-tensor tuple, or None on ANY
        mismatch — nothing was mutated then, and the caller falls back
        to the full record path (this segment stops replaying; the
        skeleton re-arms or rebuilds at the next memo-proven seal).

        Validation per op: same OpDef, equal attrs (dict equality for
        primitive attrs, interned-key equality otherwise), identical
        input wiring — op-ref inputs must point at the same (op, slot),
        external inputs must land on the same input index with the aval
        the sealed segment's in-signature recorded — and the same grad
        intent. Only then are the skeleton's cached out-avals, interned
        sig entry and shared attrs dict reused; external payloads are
        re-bound through the normal registration machinery."""
        global FAST_OPS
        sk = self._skeleton
        if sk is None:
            sk = self._select_skel(op)
        pos = self._skel_pos
        if sk is None or sk.gen != _FAST_GEN or pos >= len(sk.ops) \
                or _flags.STATIC_CHECKS_ACTIVE:
            self._skel_live = False
            if sk is not None and sk.gen != _FAST_GEN:
                self._skeleton = None
            return None
        s = sk.ops[pos]
        if s.op is not op or len(ts) != len(s.wiring):
            self._skel_live = False
            return None
        if s.fast_attrs:
            try:
                if attrs != s.attrs:
                    self._skel_live = False
                    return None
            except ValueError:
                # an ndarray attr value arrived where the armed shape
                # held primitives: dict inequality is ambiguous there —
                # a plain mismatch, NOT an uncapturable op (the full
                # path digests ndarray attrs via _hashable)
                self._skel_live = False
                return None
        elif dispatch.attrs_key(attrs) != s.akey:
            self._skel_live = False
            return None
        in_ids = self._in_ids
        in_tensors = self._in_tensors
        n_in = len(self._in_vals)
        in_sig = sk.in_sig
        new_ext = None      # fresh external registrations, commit later
        new_ids = None
        req = False
        for t, w in zip(ts, s.wiring):
            if t is None:
                if w is not None:
                    self._skel_live = False
                    return None
                continue
            p = t._payload
            if getattr(p, "_is_lazy_ref", False):
                if p.ctx is self and p.op_idx is not None:
                    if w is None or w[0] != "op" or w[1] != p.op_idx \
                            or w[2] != p.slot:
                        self._skel_live = False
                        return None
                    req = req or p.requires_grad
                    continue
                # foreign-context lazy value: the slow path materializes
                self._skel_live = False
                return None
            if w is None or w[0] != "in":
                self._skel_live = False
                return None
            idx = in_ids.get(id(t))
            if idx is not None and in_tensors[idx]() is not t:
                idx = None
            if idx is None and new_ids is not None:
                idx = new_ids.get(id(t))
            if idx is None:
                idx = n_in if new_ext is None else n_in + len(new_ext)
                if idx >= len(in_sig):
                    self._skel_live = False
                    return None
                isig = in_sig[idx]
                if tuple(p.shape) != isig[0] \
                        or _dstr(p.dtype) != isig[1] \
                        or bool(getattr(p, "weak_type", False)) != isig[2]:
                    self._skel_live = False
                    return None
                if new_ext is None:
                    new_ext = [t]
                    new_ids = {id(t): idx}
                else:
                    new_ext.append(t)
                    new_ids[id(t)] = idx
            if w[1] != idx:
                self._skel_live = False
                return None
            req = req or not t._stop_gradient
        if s.has_inexact and (req and _IS_GRAD_ENABLED()) != s.req:
            # grad intent flipped (no_grad scope, stop_gradient toggle):
            # the skeleton's out flags no longer apply
            self._skel_live = False
            return None
        # ---- commit (nothing above mutated the context)
        if new_ext is not None:
            for t in new_ext:
                self._input_index(t)
        src = None
        if PERF_SRC or _OBS.COMPUTE or _flags.NAN_CHECK_ACTIVE:
            # provenance demanded (perf trace / compute plane / armed
            # NaN scan): the fast path still skips aval work but
            # captures the source line per op — diagnostics and
            # named_scope provenance must not degrade under replay
            from ..analysis.hooks import call_site
            src = call_site()
        op_idx = len(self.pending)
        out_refs = []
        outs = []
        for slot in range(s.n_outs):
            rg = s.out_req[slot]
            ref = LazyRef.__new__(LazyRef)
            ref.ctx = self
            ref.op_idx = op_idx
            ref.slot = slot
            ref.aval = s.out_avals[slot]
            ref.requires_grad = rg
            ref.trefs = []
            out_refs.append(ref)
            outs.append(_lazy_tensor(ref, stop_gradient=not rg))
        pop = _PendingOp.__new__(_PendingOp)
        pop.op = op
        pop.attrs = s.attrs
        pop.wiring = s.wiring
        pop.out_refs = out_refs
        pop.n_outs = s.n_outs
        pop.src = src
        self.pending.append(pop)
        self._sig_ops.append(s.entry)
        self._skel_pos = pos + 1
        self.ops_recorded += 1
        self._fast_ops += 1
        FAST_OPS += 1
        return tuple(outs)

    def _build_skeleton(self, in_sig):
        """Retain the just-sealed segment as the replay skeleton (only
        called once the signature memo proved the stream repeats)."""
        ops = []
        for pop, entry in zip(self.pending, self._sig_ops):
            s = _SkelOp()
            s.op = pop.op
            s.akey = entry[1]
            s.attrs = pop.attrs
            s.fast_attrs = all(_fast_attr_safe(v)
                               for v in pop.attrs.values())
            s.wiring = pop.wiring
            s.out_avals = tuple(r.aval for r in pop.out_refs)
            s.out_req = tuple(r.requires_grad for r in pop.out_refs)
            s.req = any(s.out_req)
            s.has_inexact = any(_is_inexact(a.dtype) for a in s.out_avals)
            s.entry = entry
            s.n_outs = pop.n_outs
            # flat tuple for the native matcher: one PyTuple_GET_ITEM
            # per field instead of a slot GetAttr each (multi_output is
            # canonical True/False so C judges it by identity)
            s.ctup = (s.op, s.akey, s.attrs, s.fast_attrs, s.wiring,
                      s.out_avals, s.out_req, s.req, s.has_inexact,
                      s.entry, s.n_outs, True if s.op.multi_output
                      else False)
            ops.append(s)
        sk = _Skeleton()
        sk.ops = ops
        sk.ctups = [s.ctup for s in ops]
        sk.in_sig = in_sig
        sk.gen = _FAST_GEN
        sk.streak = 0
        sk.plan = None
        self._skeleton = sk
        op0 = self.pending[0].op
        bucket = self._skels.get(op0)
        if bucket is None:
            if len(self._skels) > 8:
                self._skels.clear()
            bucket = self._skels[op0] = {}
        # bucket key = (length, last entry), the _sig_memos scheme:
        # same-leading-op shapes coexist instead of thrashing one slot
        bkey = (len(ops), self._sig_ops[-1])
        bucket.pop(bkey, None)
        if len(bucket) > 4:
            bucket.clear()
        bucket[bkey] = sk

    def record(self, op: OpDef, ts, attrs):
        """Record one op application; returns out Tensors (lazy).

        The NATIVE skeleton matcher is entered one level up, in
        executor.apply (the only production caller) — record() itself
        runs the python matcher, which self-gates on the sanitizer /
        provenance modes and stands alone without the C library. The
        two gates are contract twins: a new mode that must bypass the
        replay belongs in _record_fast AND in apply's native gate."""
        if self._skel_live:
            outs = self._record_fast(op, ts, attrs)
            if outs is None and self._skeleton is not None \
                    and self._switch_skel(op) is not None:
                # sibling shape continues the stream: retry once (the
                # failed match mutated nothing)
                self._skel_live = True
                outs = self._record_fast(op, ts, attrs)
            if outs is not None:
                return outs
        is_grad_enabled = _IS_GRAD_ENABLED
        if is_grad_enabled is None:
            _bind_hot_imports()
            is_grad_enabled = _IS_GRAD_ENABLED

        # pass 1: resolve avals WITHOUT mutating the input record, so a
        # failing aval inference (un-capturable op) leaves no ghost
        # inputs behind for the record-fallback path to drag along
        resolved = []
        in_avals = []
        req = False
        for t in ts:
            if t is None:
                resolved.append(None)
                in_avals.append(None)
                continue
            p = t._payload
            if getattr(p, "_is_lazy_ref", False):
                if p.ctx is self and p.op_idx is not None:
                    resolved.append(("op", p.op_idx, p.slot))
                    in_avals.append(p.aval)
                    req = req or p.requires_grad
                    continue
                # lazy value from another context: materialize it
                p.materialize()
                p = t._payload
            resolved.append(("ext", t))
            in_avals.append(_aval_of(p))
            req = req or (not t.stop_gradient)

        akey = dispatch.attrs_key(attrs)
        out_avals = _out_avals(op, attrs, in_avals, akey)

        # pass 2 (cannot fail): register external inputs + build wiring
        wiring = []
        for r in resolved:
            if r is None:
                wiring.append(None)
            elif r[0] == "ext":
                wiring.append(("in", self._input_index(r[1])))
            else:
                wiring.append(r)
        wiring = tuple(wiring)
        req = req and is_grad_enabled()
        op_idx = len(self.pending)
        out_refs = []
        outs = []
        for s, aval in enumerate(out_avals):
            inexact = _is_inexact(aval.dtype)
            ref = LazyRef(self, op_idx, s, aval, req and inexact)
            t = _lazy_tensor(ref, stop_gradient=not (req and inexact))
            out_refs.append(ref)
            outs.append(t)
        src = None
        if _flags.STATIC_CHECKS_ACTIVE:
            from ..analysis.hooks import call_site
            src = call_site()
            if op.name in _VIEW_OP_NAMES:
                # cross-segment alias graph: reference view semantics
                # share storage with the base, so the sanitizer tracks
                # view->base edges process-wide (paddle_tpu.analysis.
                # alias_graph) to catch a later donation/in-place
                # mutation of the base while the view lives on. EVERY
                # output aliases the base (split_ returns N views)
                base = next((t for t in ts if t is not None), None)
                if base is not None:
                    from ..analysis import alias_graph as _ag
                    for _out in outs:
                        _ag.note_view(_out, base, op.name, src)
        elif PERF_SRC or _OBS.COMPUTE or _flags.NAN_CHECK_ACTIVE:
            # perf tracing, the compute telemetry plane AND an armed
            # NaN scan force provenance capture even with the sanitizer
            # off (no alias-graph work — that is the correctness
            # sanitizer's job): perf diagnostics need the line, the
            # compute plane bakes it into each op's named_scope so
            # device profiles group by paddle source, and a NaN trip
            # must name the producing op's file:line in its message
            from ..analysis.hooks import call_site
            src = call_site()
        self.pending.append(_PendingOp(op, dict(attrs), wiring, out_refs,
                                       src))
        entry = _intern_sig_entry((op.name, akey, wiring, len(out_refs)))
        self._sig_ops.append(entry)
        self.ops_recorded += 1
        return tuple(outs)

    def maybe_cap_flush(self):
        """Called by the executor AFTER a successful record, outside its
        record-fallback handler, so a failing segment execution surfaces
        instead of being swallowed as an 'uncapturable op'. Reads the
        cap inline (not via the max_ops property) — this runs once per
        recorded op."""
        cap = self._max_override
        if cap is None:
            cap = _MAX_SEG_OPS
        if len(self.pending) >= cap:
            self.flush("segment_cap")

    def _reset_segment(self):
        if _DRIVE_CELL[0] is not None:
            _drive_reconcile(self)
        self.pending = []
        self._in_ids = {}
        self._in_tensors = []
        self._in_pins = []
        self._in_vals = []
        self._in_meta = []
        self._sig_ops = []
        self._skel_pos = 0
        self._skeleton = None            # selected by the next segment's
        self._skel_live = bool(self._skels)   # first record
        self._fast_ops = 0

    def _live_outputs(self, pending):
        """Lazy refs some Tensor still aliases (see _live_aliases)."""
        live: List[Tuple[int, int]] = []
        live_refs: List[LazyRef] = []
        for j, pop in enumerate(pending):
            for ref in pop.out_refs:
                if _live_aliases(ref):
                    live.append((j, ref.slot))
                    live_refs.append(ref)
        return live, live_refs

    def _signature(self, in_vals, live) -> "_CachedKey":
        # MESH_EPOCH rides after the structural halves:
        # register_segment_grad slices the ops/inputs halves
        # positionally (sig[1]/sig[2]), so the SPMD sharding component
        # — (mesh shape+axes, per-input PartitionSpec) — is appended at
        # the very END and ONLY when a mesh is ambient: a no-mesh
        # session's key stays the 5-tuple it always was (zero extra key
        # bytes) while the same dygraph code under two meshes (or two
        # input layouts) keys two distinct executables. The memo hands
        # back last step's _CachedKey when nothing structural changed —
        # entries are interned, so the comparison is n identity checks,
        # and downstream cache lookups hash a cached int instead of
        # re-walking the whole structure every step.
        ops_key = tuple(self._sig_ops)
        sk = self._skeleton
        if sk is not None and self._skel_live \
                and self._skel_pos == len(sk.ops) \
                and len(in_vals) == len(sk.in_sig):
            # fully skeleton-replayed segment: every external
            # registration was validated against the sealed in-sig, so
            # the tuple is identical by construction — reuse the object
            # (the memo compare below becomes an identity check)
            in_sig = sk.in_sig
        else:
            in_sig = _in_signature(in_vals)
        live_t = tuple(live)
        backend = jax.default_backend()
        spmd = SPMD
        shard_sig = None
        if spmd is not None:
            global SHARD_SIG_BUILDS
            SHARD_SIG_BUILDS += 1
            shard_sig = (spmd.key,
                         tuple(spmd.spec_of(v) for v in in_vals))
        # per-shape memo bucket: first entry + length + last entry
        # disambiguates shapes that share a leading op (entries are
        # interned, so the tuple hashes cheaply). NOTE the skeleton
        # BANK below is still keyed by the first OpDef alone — it must
        # select before anything else is known — so two shapes sharing
        # their first (op, attrs, wiring) entry alternate the bank slot
        # and replay stays off for them (documented limitation; the
        # memo/_CachedKey reuse still works per shape).
        key0 = (self._sig_ops[0], len(self._sig_ops), self._sig_ops[-1])
        memo = self._sig_memos.get(key0)
        if memo is not None and memo[3] == MESH_EPOCH \
                and memo[4] == backend and memo[5] == shard_sig \
                and memo[0] == ops_key \
                and memo[1] == in_sig and memo[2] == live_t:
            # the memo just proved this segment shape repeats: arm (or
            # refresh) its record skeleton — unless the current
            # segment fully replayed it, in which case it is exact
            if _FAST_PATH and not _flags.STATIC_CHECKS_ACTIVE and (
                    sk is None or sk.gen != _FAST_GEN
                    or not (self._skel_live
                            and self._skel_pos == len(sk.ops))):
                self._build_skeleton(memo[1])
            elif sk is not None and sk.gen == _FAST_GEN \
                    and self._skel_live \
                    and self._skel_pos == len(sk.ops):
                # a full clean replay of the armed skeleton just
                # re-proved: advance the whole-step promotion streak,
                # and at the threshold seal the STEP PLAN — live set +
                # _CachedKey + ambient mesh — so later seals of this
                # shape skip signature reconstruction entirely
                sk.streak += 1
                if sk.plan is None and _STEP_REPLAY_AFTER \
                        and sk.streak >= _STEP_REPLAY_AFTER:
                    sk.plan = (memo[2], memo[6], SPMD)
            self._sig_memo = memo
            return memo[6]
        # structural drift for THIS shape: drop its banked skeleton
        # and re-prove before replaying it again — bucket keys carry
        # (length, last entry), so a different shape that merely shares
        # the leading op keeps its valid skeleton
        bucket = self._skels.get(self.pending[0].op)
        if bucket is not None:
            bucket.pop((len(self._sig_ops), self._sig_ops[-1]), None)
            if not bucket:
                del self._skels[self.pending[0].op]
        self._skeleton = None
        base = (backend, ops_key, in_sig, live_t, MESH_EPOCH)
        key = _CachedKey(base if shard_sig is None
                         else base + (shard_sig,))
        if len(self._sig_memos) > 8:
            self._sig_memos.clear()
        memo = (ops_key, in_sig, live_t, MESH_EPOCH, backend,
                shard_sig, key)
        self._sig_memos[key0] = memo
        self._sig_memo = memo
        return key

    def _step_plan_sig(self, live):
        """Whole-step replay admission at seal time. Returns
        ``(sig, True)`` when the current segment fully replayed a
        promoted skeleton and the live set matches its sealed plan —
        the seal then skips _signature() entirely and the execution
        runs under ``segment::replay_step``. Returns ``(None, False)``
        otherwise; a live-set or mesh mismatch against an armed plan
        additionally DEMOTES the shape (streak reset, plan dropped) so
        it re-proves through the normal path before re-promoting.
        The mechanical invalidation events (mesh epoch, watched flags,
        note_inplace, grad-mode flip) never reach this check: they all
        break the per-op replay first, so `_skel_live` is already
        False."""
        sk = self._skeleton
        if sk is None or sk.plan is None or not self._skel_live \
                or self._skel_pos != len(sk.ops) \
                or len(self._in_vals) != len(sk.in_sig):
            return None, False
        plan_live, plan_key, plan_spmd = sk.plan
        if sk.gen != _FAST_GEN or tuple(live) != plan_live \
                or SPMD is not plan_spmd:
            sk.streak = 0
            sk.plan = None
            return None, False
        global REPLAY_STEPS
        REPLAY_STEPS += 1
        if _OBS.METRICS:
            from ..observability import metrics
            metrics.inc("segment.replay_steps")
        self._sig_memo = self._sig_memos.get(
            (self._sig_ops[0], len(self._sig_ops), self._sig_ops[-1]))
        return plan_key, True

    # ------------------------------------------------------------- flush
    def flush(self, reason: str = "materialize"):
        if _DRIVE_CELL[0] is not None:
            # an armed whole-step drive lags the context's cursor and
            # counters (they are written back in batch): reconcile
            # BEFORE anything below reads _skel_pos/_fast_ops
            _drive_reconcile(self)
        if not self.pending:
            # nothing recorded, but clear any input registrations a
            # partially-failed record may have left behind
            self._reset_segment()
            return
        if PERF_OBSERVER is not None:
            PERF_OBSERVER(self, reason, self.pending)
        pending = self.pending
        in_vals = self._in_vals
        in_meta = self._in_meta
        in_tensors = [r() for r in self._in_tensors]  # None = died

        live, live_refs = self._live_outputs(pending)
        sig, driven = self._step_plan_sig(live)
        if sig is None:
            sig = self._signature(in_vals, live)

        # donation: an input whose backing tensor died or was overwritten
        # is dead the moment this program runs — let XLA reuse its buffer
        # for an output (the in-place param.copy_ pattern) instead of
        # copying. Never donate when the segment registers a grad node:
        # saved inputs are the backward residuals. The all-inputs-alive
        # step (the common case) pays ONE identity scan here instead of
        # the set/dict builds + per-buffer refcount probes of the full
        # candidate search.
        donate: Tuple[int, ...] = ()
        from . import flags
        if _DONATE_INPUTS and any(
                t is None or t._payload is not in_vals[i]
                for i, t in enumerate(in_tensors)) and not \
                _segment_needs_grad(in_tensors, in_vals, live_refs, in_meta):
            donate = _donatable_inputs(in_tensors, in_vals, live_refs)

        # async dispatch pipeline: a cap- or guard-exit-sealed segment
        # hands off to the single-worker flush executor so
        # compile+execute leave the recording thread; live outputs
        # become PendingValues that materialize at the next sync
        # point. SOT capture (on_flush observer) rides along: its
        # note_flush accepts pending out tensors (the entry builder
        # reads only avals/identity, never concrete values).
        if _flags.ASYNC_FLUSH_ACTIVE and reason in _ASYNC_REASONS:
            self._flush_async(reason, pending, in_vals, in_meta,
                              in_tensors, live, live_refs, sig, donate,
                              driven)
            return

        # program sanitizer (paddle_tpu.analysis): one cached-gate read
        # when off; in warn/error mode the segment checkers run over the
        # program about to execute (donation safety, in-place races,
        # tracer leaks, shape/dtype drift, cross-segment donation, view
        # aliases). 'error' stops a corrupting launch — drop the trace
        # like a failed compile would. 'fix' repairs the mechanical
        # classes in place and hands back the rewritten (pending,
        # donate) pair; a pruned op list invalidates the incremental
        # live/signature state, so both are recomputed before dispatch.
        _checks_on = False
        if _flags.STATIC_CHECKS_ACTIVE:
            from ..analysis import hooks as _sanitizer
            try:
                _mode = _sanitizer.check_mode()   # full normalization
                if _mode != "off":
                    _checks_on = True
                    _fixed = _sanitizer.on_segment_flush(
                        self, pending, in_vals, in_meta, in_tensors,
                        live, live_refs, donate, _mode, fixable=True,
                        reason=reason)
                    if _fixed is not None:
                        new_pending, donate = _fixed
                        if new_pending is not pending:
                            pending = new_pending
                            live, live_refs = self._live_outputs(pending)
                            sig = self._signature(in_vals, live)
            except Exception:
                self._reset_segment()
                raise

        fspan = _obs_flush_span(reason, len(pending), len(in_vals),
                                len(live), len(donate), self._fast_ops) \
            if _OBS.ACTIVE else None
        dispatch.bump_exec()
        xspan = None
        try:
            # inputs produced by a still-in-flight async flush resolve
            # here (the pipeline's data-dependency sync)
            run_vals = resolve_pending(in_vals) if _ASYNC_SEEN else in_vals
            if _flags.FAULT_INJECT_ACTIVE:
                _inject_exec_oom()
            runner = _SEG_CACHE.get((sig, donate))
            if runner is None and _persist.ACTIVE:
                # disk consult between the in-memory miss and
                # lower().compile(): a verified hit takes the cached-
                # execute span below and bumps no compiles.* counter
                runner = _disk_runner(
                    "segment", (_persist_sig(sig), donate),
                    _jit_factory(
                        lambda: _build_segment_fn(pending, live),
                        donate, run_vals, _spmd_for_compile(in_vals)),
                    cache=_SEG_CACHE, key=(sig, donate))
                if runner is not None:
                    _SEG_CACHE[(sig, donate)] = runner
            # async dispatch: out_vals are in-flight futures — the host
            # returns to tracing the next ops while the device executes;
            # sync happens only at explicit .numpy()/float() reads
            if runner is None:
                if _flags.FAULT_INJECT_ACTIVE:
                    # segment::compile fault site (transient compile
                    # failure): raises inside this try so cleanup is
                    # exactly a real failed compile — trace dropped,
                    # spans closed, flight post-mortem
                    from ..distributed.resilience import faults as _faults
                    _faults.inject("segment::compile")
                if fspan is not None:
                    xspan = _obs_exec_span(True, len(pending))
                if _OBS.METRICS:
                    from ..observability import metrics
                    metrics.inc("compiles.segment")
                runner = _compile_segment_runner(
                    pending, live, donate, run_vals, sig,
                    _spmd_for_compile(in_vals))
                _SEG_CACHE[(sig, donate)] = runner
                if _persist.ACTIVE:
                    _disk_store("segment", (_persist_sig(sig), donate),
                                runner, _SEG_CACHE, (sig, donate))
                with _quiet_donation_compile():   # first call compiles
                    out_vals = runner(*run_vals)
            else:
                if fspan is not None:
                    xspan = _obs_exec_span(False, len(pending), driven)
                out_vals = runner(*run_vals)
            if xspan is not None:
                xspan.end()
        except Exception as e:
            # a failed compile/run must not pin input tensors or poison
            # later records: drop the trace and surface the error (the
            # un-materialized outputs re-raise on read). Spans end
            # BEFORE the flight dump so the report contains the failing
            # flush/compile entry, not just the error note.
            self._reset_segment()
            if xspan is not None:
                xspan.end(error=e)
            if fspan is not None:
                fspan.end(error=e)
            _obs_flush_failed(reason, e)
            oe = _oom_convert(e, f"segment::flush[{reason}]",
                              _SEG_CACHE.memory_info((sig, donate)))
            if oe is not e:
                raise oe from e
            raise
        if _checks_on and donate:
            # cross-segment ledger (sanitizer dataflow): recorded only
            # AFTER the executable ran — a failed compile/run donated
            # nothing, and a phantom entry would turn a valid later
            # program into a false cross_segment_donation error
            from ..analysis.dataflow import note_segment_donation
            note_segment_donation(in_vals, donate, reason, pending)
        if SPMD is not None and _OBS.METRICS:
            _note_compiled_comm(_SEG_CACHE, (sig, donate), SPMD,
                                run_vals, out_vals, "segment")
        if _OBS.COMPUTE:
            # FLOP accounting: price this execution from the cost
            # analysis the compile cached on the entry (zero work when
            # the entry predates the plane)
            from ..observability import compute as _comptel
            _comptel.count_cached(_SEG_CACHE, (sig, donate), "segment")
        if _OBS.MEM and donate:
            _note_donated_inputs(in_vals, donate)
        self._reset_segment()
        self.breaks.append(reason)
        self.segments_run += 1

        try:
            # bind concrete values into every aliasing Tensor; the grad
            # node attaches to a grad-REQUIRING alias — a detach()ed
            # alias must never have its stop_gradient flipped back
            out_tensors = []
            for ref, val in zip(live_refs, out_vals):
                ts = _live_aliases(ref)
                for t in ts:
                    t._payload = val
                grad_ts = [t for t in ts if not t.stop_gradient]
                out_tensors.append(grad_ts[0] if grad_ts
                                   else (ts[0] if ts else None))

            if _OBS.MEM:
                # live-buffer census: segment outputs are born here,
                # provenance = segment signature + producing op (+ the
                # mesh descriptor when the step ran sharded, so an OOM
                # postmortem names which mesh config filled the device)
                from ..observability import memory as _memtel
                _memtel.note_segment_outputs(
                    pending, live, out_vals, sig,
                    mesh=SPMD.desc if SPMD is not None else None)

            # FLAGS_check_nan_inf covers fused-segment outputs too (the
            # per-op eager scan in dispatch.py never sees ops that were
            # recorded before the flag flipped on, nor replayed
            # segments): scan every live output, blaming its producer
            if flags.flag_value("FLAGS_check_nan_inf"):
                _nan_scan_segment(pending, live, out_vals,
                                  "lazy segment output", in_vals)

            self._register_grad(pending, live, live_refs, out_tensors,
                                in_tensors, in_vals, sig, in_meta)

            if self.on_flush is not None:
                self.on_flush(self, reason, pending, live, live_refs,
                              in_tensors, in_vals, sig, out_tensors)
        except Exception as e:
            # a post-execute failure (NaN trip, grad wiring, observer)
            # still closes the flush span and triggers the flight
            # post-mortem — this is exactly the event it exists for
            if fspan is not None:
                fspan.end(error=e)
            _obs_flush_failed(reason, e)
            raise
        if fspan is not None:
            fspan.end()

    def _flush_async(self, reason, pending, in_vals, in_meta, in_tensors,
                     live, live_refs, sig, donate, driven=False):
        """Seal the segment and hand it to the flush executor.

        Caller-thread work is exactly what MUST happen at eager order:
        donation decision (already made — refcount semantics are
        caller-relative), output binding (every live alias gets a
        PendingValue payload), and grad wiring (the graph exists the
        moment eager code moves on). The sanitizer sweep, cache lookup,
        compile, execute, ledger note, and NaN scan all run on the
        worker; failures fail every PendingValue and latch on the
        executor, re-raising at the next sync point (the flight
        post-mortem fires on the worker, so the report carries the
        failing flush)."""
        global _ASYNC_SEEN
        from .async_flush import PendingValue, get_executor

        # mode resolved NOW (a typo'd FLAGS_static_checks raises at the
        # flush site, not from a worker); the sweep itself runs off-thread
        mode = None
        if _flags.STATIC_CHECKS_ACTIVE:
            from ..analysis import hooks as _sanitizer
            mode = _sanitizer.check_mode()
            if mode == "off":
                mode = None
        in_ids = dict(self._in_ids)
        fault_active = _flags.FAULT_INJECT_ACTIVE
        # ambient mesh captured at SEAL time: the signature above was
        # built against it, and the worker must compile/account against
        # the same state even if the recording thread exits the mesh.
        # spmd_pin is None when any sealed input is still pending —
        # such programs compile unpinned (see _spmd_for_compile)
        spmd = SPMD
        spmd_pin = _spmd_for_compile(in_vals)
        fast_n = self._fast_ops
        from . import flags
        nan_check = flags.flag_value("FLAGS_check_nan_inf")

        pvs = [PendingValue(ref.aval) for ref in live_refs]
        out_tensors = []
        for ref, pv in zip(live_refs, pvs):
            ts = _live_aliases(ref)
            for t in ts:
                t._payload = pv
            grad_ts = [t for t in ts if not t.stop_gradient]
            out_tensors.append(grad_ts[0] if grad_ts
                               else (ts[0] if ts else None))
        if _OBS.METRICS:
            from ..observability import metrics
            metrics.inc("segment.async_flushes")

        def job(pending=pending, live=live, live_refs=live_refs,
                sig=sig, donate=donate):
            pvmap = {id(r): pv for r, pv in zip(live_refs, pvs)}
            fspan = xspan = None
            try:
                if mode is not None:
                    from ..analysis import hooks as _sanitizer
                    # fixable=False: fix-mode REPAIRS stay on the
                    # synchronous path — the fixer rewrites context
                    # state that now belongs to the NEXT recording
                    # segment, and the sealed outputs are already bound
                    # to PendingValues. Warn/error semantics (incl. the
                    # deferred StaticCheckError) are identical; ctx is
                    # withheld so nothing can touch live state.
                    _sanitizer.on_segment_flush(
                        None, pending, in_vals, in_meta, in_tensors,
                        live, live_refs, donate, mode, fixable=False,
                        reason=reason, in_ids=in_ids)
                fspan = _obs_flush_span(reason, len(pending),
                                        len(in_vals), len(live),
                                        len(donate), fast_n) \
                    if _OBS.ACTIVE else None
                run_vals = resolve_pending(in_vals)
                dispatch.bump_exec()
                if fault_active:
                    _inject_exec_oom()
                runner = _SEG_CACHE.get((sig, donate))
                if runner is None and _persist.ACTIVE:
                    runner = _disk_runner(
                        "segment", (_persist_sig(sig), donate),
                        _jit_factory(
                            lambda: _build_segment_fn(pending, live),
                            donate, run_vals, spmd_pin),
                        cache=_SEG_CACHE, key=(sig, donate))
                    if runner is not None:
                        _SEG_CACHE[(sig, donate)] = runner
                if runner is None:
                    if fault_active:
                        from ..distributed.resilience import faults \
                            as _faults
                        _faults.inject("segment::compile")
                    if fspan is not None:
                        xspan = _obs_exec_span(True, len(pending))
                    if _OBS.METRICS:
                        from ..observability import metrics
                        metrics.inc("compiles.segment")
                    runner = _compile_segment_runner(pending, live,
                                                     donate, run_vals,
                                                     sig, spmd_pin)
                    _SEG_CACHE[(sig, donate)] = runner
                    if _persist.ACTIVE:
                        _disk_store("segment",
                                    (_persist_sig(sig), donate),
                                    runner, _SEG_CACHE, (sig, donate))
                    with _quiet_donation_compile():
                        out_vals = runner(*run_vals)
                else:
                    if fspan is not None:
                        xspan = _obs_exec_span(False, len(pending),
                                               driven)
                    out_vals = runner(*run_vals)
                if xspan is not None:
                    xspan.end()
                    xspan = None
                if mode is not None and donate:
                    from ..analysis.dataflow import note_segment_donation
                    note_segment_donation(in_vals, donate, reason,
                                          pending)
                if spmd is not None and _OBS.METRICS:
                    _note_compiled_comm(_SEG_CACHE, (sig, donate), spmd,
                                        run_vals, out_vals, "segment")
                if _OBS.COMPUTE:
                    from ..observability import compute as _comptel
                    _comptel.count_cached(_SEG_CACHE, (sig, donate),
                                          "segment")
                if _OBS.MEM:
                    if donate:
                        _note_donated_inputs(in_vals, donate)
                    from ..observability import memory as _memtel
                    _memtel.note_segment_outputs(
                        pending, live, out_vals, sig,
                        mesh=spmd.desc if spmd is not None else None)
                if nan_check:
                    _nan_scan_segment(pending, live, out_vals,
                                      "lazy segment output", in_vals)
                for ref, val in zip(live_refs, out_vals):
                    pv = pvmap.pop(id(ref), None)
                    if pv is not None:
                        pv._fill(val)
                for pv in pvmap.values():   # fixer dropped a live slot
                    pv._fail(RuntimeError(
                        "async flush lost a live output"))
                if fspan is not None:
                    fspan.end()
            except BaseException as e:
                # RESOURCE_EXHAUSTED converts to the typed postmortem
                # HERE, on the worker: the PendingValues and the
                # executor latch carry the typed error, so the sync
                # point re-raises exactly what the sync path would
                oe = _oom_convert(e, "segment::flush[async]",
                                  _SEG_CACHE.memory_info((sig, donate)))
                for pv in pvs:
                    if not pv.done():
                        pv._fail(oe)
                if xspan is not None:
                    xspan.end(error=oe)
                if fspan is not None:
                    fspan.end(error=oe)
                _obs_flush_failed(reason, oe)
                if oe is not e:
                    raise oe from e
                raise

        get_executor().submit(job)
        _ASYNC_SEEN = True
        self._reset_segment()
        self.breaks.append(reason)
        self.segments_run += 1
        self._register_grad(pending, live, live_refs, out_tensors,
                            in_tensors, in_vals, sig, in_meta)
        if self.on_flush is not None:
            # SOT capture observer: the sealed segment's out tensors
            # carry PENDING payloads (they materialize at the first
            # read) — the guarded-entry builder reads only avals and
            # payload identity, so guard-exit seals ride the pipeline
            self.on_flush(self, reason, pending, live, live_refs,
                          in_tensors, in_vals, sig, out_tensors)

    on_flush = None  # observer hook (jit/sot records segment structure)

    def flush_per_op(self, reason: str = "grad_targets"):
        """Land the pending trace as per-op eager dispatches — one
        GradNode per op instead of one fused segment node.

        paddle.grad(outputs, inputs) needs gradients AT interior values;
        a fused segment node only maps output cotangents to segment
        inputs, so a target produced inside the segment would be
        unreachable. Replaying the recorded wiring through the per-op
        path restores that granularity (cost: per-op dispatch, but only
        on the explicit-targets path)."""
        if not self.pending:
            self._reset_segment()
            return
        if PERF_OBSERVER is not None:
            PERF_OBSERVER(self, reason, self.pending)
        pending = self.pending
        in_vals = self._in_vals
        in_meta = self._in_meta
        in_tensors = [r() for r in self._in_tensors]
        # reset FIRST: the per-op dispatches below must not re-record
        # into this context
        self._reset_segment()
        self.breaks.append(reason)
        self.segments_run += 1

        rspan = None
        if _OBS.ACTIVE:
            if _OBS.METRICS:
                from ..observability import metrics
                metrics.inc("segment.replays_per_op")
                metrics.inc("segment.flush_reason."
                            + reason.split(":", 1)[0])
            from ..observability.spans import span
            rspan = span(f"segment::replay_per_op[{reason}]",
                         hist="segment.replay_per_op_us", reason=reason,
                         ops=len(pending)).begin()

        try:
            self._replay_per_op(pending, in_vals, in_meta, in_tensors)
        except Exception as e:
            if rspan is not None:
                rspan.end(error=e)
            raise
        if rspan is not None:
            rspan.end()

    def _replay_per_op(self, pending, in_vals, in_meta, in_tensors):
        from .autograd import record
        from .tensor import Tensor
        if _ASYNC_SEEN:
            # per-op replay hands raw payloads to eager dispatch:
            # in-flight async results resolve first, and tensors whose
            # payload IS the pending snapshot adopt the concrete value
            # so the overwritten-in-place identity check below stays
            # exact
            resolved = resolve_pending(in_vals)
            for t, v, rv in zip(in_tensors, in_vals, resolved):
                if t is not None and t._payload is v:
                    t._payload = rv
            in_vals = resolved
        out_tensors: List[List] = []
        for pop in pending:
            ins = []
            vals = []
            for w in pop.wiring:
                if w is None:
                    ins.append(None)
                    vals.append(None)
                elif w[0] == "in":
                    t = in_tensors[w[1]]
                    v = in_vals[w[1]]
                    if t is None or t._payload is not v:
                        # input died or was overwritten in place after
                        # registration: eager ordering saw the snapshot.
                        # The stand-in adopts the record-time autograd
                        # snapshot so grads still chain through a dead
                        # intermediate's grad_node.
                        req, meta, _ = in_meta[w[1]]
                        t = Tensor(v, stop_gradient=not req)
                        if meta is not None:
                            t._autograd_meta = meta
                    ins.append(t)
                    vals.append(v)
                else:
                    t = out_tensors[w[1]][w[2]]
                    ins.append(t)
                    vals.append(t._payload)
            outs = dispatch.eager_forward(pop.op, tuple(vals), pop.attrs)
            wrapped = []
            for ref, val in zip(pop.out_refs, outs):
                ts = _live_aliases(ref)
                for t in ts:
                    t._payload = val
                tt = next((t for t in ts if not t.stop_gradient), None)
                if tt is None:
                    # no live grad-requiring alias (value interior to the
                    # trace, or only detached aliases survive): wire the
                    # graph through a fresh stand-in
                    tt = Tensor(val, stop_gradient=not ref.requires_grad)
                wrapped.append(tt)
            if any(ref.requires_grad for ref in pop.out_refs):
                record(pop.op, pop.attrs, ins, wrapped, saved_vals=vals)
            out_tensors.append(wrapped)

    # ----------------------------------------------------------- autograd
    def _register_grad(self, pending, live, live_refs, out_tensors,
                       in_tensors, in_vals, sig, in_meta=None):
        register_segment_grad(pending, live, live_refs, out_tensors,
                              in_tensors, in_vals, sig, in_meta)


def _in_grad_records(in_tensors, in_meta):
    """(requires_grad, meta, version) per input. requires_grad is the
    RECORD-time intent when a snapshot exists (eager semantics: flipping
    stop_gradient after the op must not change its grad); meta is read
    live so a grad_node attached between record and flush is seen."""
    if in_meta is not None:
        return in_meta
    return [(False, None, 0) if t is None else
            (not t.stop_gradient, t._autograd_meta, t._inplace_version)
            for t in in_tensors]


def _input_grad_eligible(t, rec, val) -> bool:
    """Can gradients flow INTO this segment input? Dead leaves (no
    grad_node, tensor gone) are excluded: their grads are unobservable."""
    req, meta, _ = rec
    if not req or not jnp.issubdtype(val.dtype, jnp.inexact):
        return False
    return t is not None or (meta is not None
                             and meta.grad_node is not None)


def _segment_needs_grad(in_tensors, in_vals, live_refs, in_meta=None) -> bool:
    """Will register_segment_grad wire a GradNode for this segment? If so
    the inputs are saved as backward residuals and must NOT be donated."""
    recs = _in_grad_records(in_tensors, in_meta)
    grad_in = any(_input_grad_eligible(t, recs[i], in_vals[i])
                  for i, t in enumerate(in_tensors))
    return grad_in and any(ref.requires_grad for ref in live_refs)


def _donatable_inputs(in_tensors, in_vals, live_refs) -> Tuple[int, ...]:
    """Inputs safe to donate: concrete jax arrays registered exactly once
    whose backing tensor is dead or no longer aliases the snapshot, whose
    shape/dtype matches some output (so XLA can actually reuse the
    buffer — avoids 'donated buffer not usable' churn), and which nothing
    else in the process still references."""
    import sys
    out_shapes = {(tuple(r.aval.shape), _dstr(np.dtype(r.aval.dtype)))
                  for r in live_refs}
    counts: Dict[int, int] = {}
    for v in in_vals:
        counts[id(v)] = counts.get(id(v), 0) + 1
    donate = []
    for i, t in enumerate(in_tensors):
        v = in_vals[i]
        if not isinstance(v, jax.Array) or isinstance(v, jax.core.Tracer):
            continue
        if getattr(v, "weak_type", False):
            # weak-typed arrays are the shared python-scalar coercion
            # cache (executor._SCALAR_CACHE): donating a shared buffer
            # would invalidate every later use
            continue
        if counts[id(v)] != 1:
            continue
        if (tuple(v.shape), _dstr(np.dtype(v.dtype))) not in out_shapes:
            continue
        if t is not None and t._payload is v:
            continue
        # sole-ownership proof: the registered tensor died or moved on,
        # but OTHER Tensors may alias the same payload (detach()/
        # Tensor(t) share it) and GradNodes may have saved it as a
        # residual — donating then deletes a buffer something live still
        # reads. Expected refs here: in_vals entry + local v +
        # getrefcount arg = 3; anything above means an outside alias.
        if sys.getrefcount(v) > 3:
            continue
        donate.append(i)
    return tuple(donate)


def register_segment_grad(pending, live, live_refs, out_tensors,
                          in_tensors, in_vals, sig, in_meta=None):
    """Wire fused GradNodes for an executed segment — one per weakly-
    connected component of the recorded dataflow. Two user-level graphs
    captured in the same window (the ambient mode makes this common)
    must stay INDEPENDENT: backward through one must not consume or
    free the other's residuals. live_refs only needs .aval /
    .requires_grad (LazyRef or a replay meta). in_tensors may contain
    None for inputs whose tensor died mid-segment (they can no longer
    receive a gradient).

    NOTE deliberately no is_grad_enabled() check here: grad intent was
    decided at RECORD time (ref.requires_grad), matching eager
    semantics — a flush that happens to run inside no_grad (e.g. a
    logging read) must not drop gradients for ops recorded outside it."""
    recs = _in_grad_records(in_tensors, in_meta)
    grad_in_all = [i for i, t in enumerate(in_tensors)
                   if _input_grad_eligible(t, recs[i], in_vals[i])]
    grad_out_all = [k for k, ref in enumerate(live_refs)
                    if ref.requires_grad]
    if not grad_in_all or not grad_out_all:
        return

    # union-find over op indices [0, n_ops) and inputs [n_ops, ...)
    n_ops = len(pending)
    parent = list(range(n_ops + len(in_vals)))

    def find(a):
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    for j, p in enumerate(pending):
        for w in p.wiring:
            if w is None:
                continue
            a = find(j)
            b = find(n_ops + w[1] if w[0] == "in" else w[1])
            if a != b:
                parent[b] = a

    comps: Dict[int, Tuple[List[int], List[int]]] = {}
    for i in grad_in_all:
        comps.setdefault(find(n_ops + i), ([], []))[0].append(i)
    for k in grad_out_all:
        comps.setdefault(find(live[k][0]), ([], []))[1].append(k)
    comps = {r: c for r, c in comps.items() if c[0] and c[1]}
    if not comps:
        return

    # each GradNode saves and differentiates only ITS slice of the
    # segment: a disjoint graph captured in the same ambient window must
    # not have its input buffers pinned as this component's residuals,
    # nor its backward FLOPs paid under a zero cotangent
    ops_by_root: Dict[int, List[int]] = {}
    for j in range(n_ops):
        ops_by_root.setdefault(find(j), []).append(j)
    ins_by_root: Dict[int, List[int]] = {}
    for i in range(len(in_vals)):
        ins_by_root.setdefault(find(n_ops + i), []).append(i)

    for r, (gi_c, go_c) in comps.items():
        comp_ops = ops_by_root[r]
        comp_ins = ins_by_root.get(r, [])
        if len(comp_ops) == n_ops and len(comp_ins) == len(in_vals):
            # sole component spans the whole segment (the steady-state
            # train-step case): no remap, and the cache key stays `sig`
            _register_component_grad(gi_c, go_c, pending, live, live_refs,
                                     out_tensors, in_tensors, in_vals, sig,
                                     recs)
            continue
        op_l = {j: lj for lj, j in enumerate(comp_ops)}
        in_l = {i: li for li, i in enumerate(comp_ins)}
        local_pending = []
        for j in comp_ops:
            p = pending[j]
            wir = tuple(None if w is None else
                        ("in", in_l[w[1]]) if w[0] == "in" else
                        ("op", op_l[w[1]], w[2]) for w in p.wiring)
            local_pending.append(_PendingOp(p.op, p.attrs, wir, p.out_refs,
                                            getattr(p, "src", None)))
        comp_ks = [k for k, (j, _) in enumerate(live) if find(j) == r]
        k_l = {k: lk for lk, k in enumerate(comp_ks)}
        local_live = [(op_l[live[k][0]], live[k][1]) for k in comp_ks]
        # global op/input index lists in the key make two segments that
        # slice to the same local structure share a compile only when
        # the remapping is identical
        comp_sig = (sig[0], tuple(sig[1][j] for j in comp_ops),
                    tuple(sig[2][i] for i in comp_ins), tuple(local_live),
                    tuple(comp_ops), tuple(comp_ins),
                    sig[4])   # MESH_EPOCH rides every derived key too
        raw = sig.sig if isinstance(sig, _CachedKey) else tuple(sig)
        if len(raw) > 5:
            # SPMD sharding component: slice the per-input specs to this
            # component's inputs so the derived backward key re-keys on
            # a re-plan / re-layout exactly like the whole-segment key
            comp_sig += ((raw[5][0],
                          tuple(raw[5][1][i] for i in comp_ins)),)
        _register_component_grad(
            [in_l[i] for i in gi_c], [k_l[k] for k in go_c],
            local_pending, local_live, [live_refs[k] for k in comp_ks],
            [out_tensors[k] for k in comp_ks],
            [in_tensors[i] for i in comp_ins],
            [in_vals[i] for i in comp_ins], comp_sig,
            [recs[i] for i in comp_ins])


def _register_component_grad(grad_in, grad_out, pending, live, live_refs,
                             out_tensors, in_tensors, in_vals, sig, recs):
    """One GradNode for one dataflow component: edges per grad-requiring
    input, output slots per grad-requiring live output (LOCAL indices)."""
    from .autograd import GradNode, _Edge
    edges = []
    versions = []
    refs = []
    for i in grad_in:
        t = in_tensors[i]
        meta = recs[i][1] if t is None else t._autograd_meta
        if meta.grad_node is not None:
            edges.append(_Edge("node", node=meta.grad_node,
                               slot=meta.out_slot))
        elif t is not None:
            edges.append(_Edge("leaf", leaf=t))
        else:       # dead leaf: grads unobservable (filtered above, but
            edges.append(_Edge(None))   # keep alignment defensively)
        versions.append(recs[i][2] if t is None else t._inplace_version)
        refs.append(None if t is None else weakref.ref(t))

    node = GradNode(
        None, {}, tuple(in_vals), edges,
        out_shapes=tuple(tuple(live_refs[k].aval.shape) for k in grad_out),
        out_dtypes=tuple(live_refs[k].aval.dtype for k in grad_out))
    node.name = "lazy_segment"
    node.saved_versions = tuple(versions)
    node.in_refs = tuple(refs)

    bwd = _segment_bwd(sig, pending, live, tuple(grad_in))

    def py_bwd(gouts, _saved=tuple(in_vals), _bwd=bwd, _refs=live_refs,
               _go=tuple(grad_out)):
        if _ASYNC_SEEN:
            # residuals saved from an async step may still be in flight
            _saved = resolve_pending(_saved)
        dispatch.bump_exec()
        # the cached vjp covers the WHOLE segment: seed this component's
        # slots, zeros elsewhere (disjoint components contribute nothing)
        cts = [jnp.zeros(r.aval.shape, r.aval.dtype) for r in _refs]
        for g, k in zip(gouts, _go):
            if g is None:
                continue
            ref = _refs[k]
            if hasattr(g, "astype") and g.dtype != ref.aval.dtype:
                g = g.astype(ref.aval.dtype)
            cts[k] = g
        grads = _bwd(list(_saved), tuple(cts))
        out = []
        for g in grads:
            if g is None or (hasattr(g, "dtype")
                             and g.dtype == jax.dtypes.float0):
                out.append(None)
            else:
                out.append(g)
        return tuple(out)

    node.py_bwd = py_bwd

    for local_k, k in enumerate(grad_out):
        t = out_tensors[k]
        if t is not None and not t.stop_gradient:
            m = t._autograd_meta
            if m.grad_node is None:
                m.grad_node = node
                m.out_slot = local_k


def _in_signature(in_vals):
    return tuple((tuple(v.shape), _dstr(v.dtype),
                  bool(getattr(v, "weak_type", False)))
                 for v in in_vals)


def _build_segment_fn(pending, live):
    """Compile body of one segment. Variadic over inputs so jax.jit's
    donate_argnums can address individual input buffers.

    With the compute telemetry plane on, each op's lowering is wrapped
    in ``jax.named_scope("<op>[<file>:<line>]")`` from its recorded
    ``_PendingOp.src`` — the HLO op_name metadata then carries paddle
    source provenance, so xplane device traces and the profiler
    statistic table can group device time by the line that recorded
    the op (observability/compute.py note_provenance/source_of).
    Decided at build (= compile) time: the off path pays nothing, and
    scope strings are metadata only — they never change what the
    program computes."""
    backend = jax.default_backend()
    scoped = _OBS.COMPUTE
    steps = []
    for p in pending:
        scope = None
        if scoped and p.src is not None:
            from ..observability.compute import scope_name
            scope = scope_name(p.op.name, p.src)
        steps.append((functools.partial(p.op.kernel_for(backend),
                                        **p.attrs),
                      p.wiring, p.op.multi_output, scope))

    def seg_fn(*inputs):
        vals: List[Tuple] = []
        for fn, wiring, multi, scope in steps:
            ins = []
            for w in wiring:
                if w is None:
                    ins.append(None)
                elif w[0] == "in":
                    ins.append(inputs[w[1]])
                else:
                    ins.append(vals[w[1]][w[2]])
            if scope is not None:
                with jax.named_scope(scope):
                    out = fn(*ins)
            else:
                out = fn(*ins)
            vals.append(tuple(out) if multi else (out,))
        return [vals[j][s] for (j, s) in live]

    return seg_fn


def _build_fused_fn(pending, live, grad_in: Tuple[int, ...], root_k: int):
    """Whole-step fusion: forward segment + vjp seeded at live output
    `root_k` as ONE program — the eager analog of the donated jitted
    train step in models/trainer.py. Returns (live_out_vals, grads)."""
    seg = _build_segment_fn(pending, live)

    def fused(*inputs):
        def f(*gvals):
            full = list(inputs)
            for v, i in zip(gvals, grad_in):
                full[i] = v
            outs = seg(*full)
            return outs[root_k], outs

        root_val, pull, outs = jax.vjp(
            f, *[inputs[i] for i in grad_in], has_aux=True)
        grads = pull(jnp.ones(root_val.shape, root_val.dtype))
        return outs, grads

    return fused


_SEG_BWD_CACHE: Dict[Tuple, Any] = ExecCache(stat="segment_bwd")


def _segment_bwd(sig, pending, live, grad_in: Tuple[int, ...]):
    key = (sig, grad_in)
    fn = _SEG_BWD_CACHE.get(key)
    if fn is None:
        if _OBS.METRICS:
            from ..observability import metrics
            metrics.inc("compiles.segment_bwd")
        seg = _build_segment_fn(pending, live)

        def bwd(inputs, cts, _seg=seg, _gi=grad_in):
            def f(*gvals):
                full = list(inputs)
                for v, i in zip(gvals, _gi):
                    full[i] = v
                return _seg(*full)
            _, pull = jax.vjp(f, *[inputs[i] for i in _gi])
            return pull(list(cts))

        fn = jax.jit(bwd)
        _SEG_BWD_CACHE[key] = fn
    return fn


def _lazy_tensor(ref: LazyRef, stop_gradient=True):
    Tensor = _TENSOR_CLS
    if Tensor is None:
        Tensor = _bind_hot_imports()
    t = object.__new__(Tensor)
    t._payload = ref
    t._stop_gradient = stop_gradient
    t._autograd_meta = _AUTOGRAD_META()
    t._inplace_version = 0
    t.name = None
    t.persistable = False
    t._dist_attr = None
    ref.add_tref(t)
    return t


class _RefMeta:
    """Replay stand-in for LazyRef (register_segment_grad contract)."""
    __slots__ = ("aval", "requires_grad")

    def __init__(self, aval, requires_grad):
        self.aval = aval
        self.requires_grad = requires_grad


class ReplayableSegment:
    """A captured segment that can be re-executed directly on fresh input
    tensors — the compiled body of jit/sot's guarded fast path. Built
    from a CaptureContext flush event; replay skips recording entirely:
    fetch inputs, run the cached executable, wrap outputs, register the
    fused GradNode."""

    def __init__(self, pending, live, live_refs, in_vals, sig):
        self.pending = pending
        self.live = live
        self.metas = [_RefMeta(r.aval, r.requires_grad) for r in live_refs]
        self.sig = sig
        # RECORD-time ambient mesh: `sig` was keyed against it, so a
        # replay must compile against the same state — not whatever
        # mesh happens to be ambient at replay time (the key and the
        # runner's sharding regime must never contradict)
        self.spmd = SPMD
        self.in_avals = tuple((tuple(v.shape), _dstr(v.dtype))
                              for v in in_vals)
        # which inputs fed grad-requiring chains at capture (replay must
        # see the same stop_gradient mask to reuse the vjp wiring)
        self.grad_mask = None

    def run(self, in_tensors):
        from .tensor import Tensor
        in_vals = [t._value for t in in_tensors]
        got = tuple((tuple(v.shape), _dstr(v.dtype)) for v in in_vals)
        if got != self.in_avals:
            raise _ReplayMismatch("input avals changed")
        runner = _SEG_CACHE.get((self.sig, ()))
        if runner is None and _persist.ACTIVE:
            runner = _disk_runner(
                "segment", (_persist_sig(self.sig), ()),
                _jit_factory(
                    lambda: _build_segment_fn(self.pending, self.live),
                    (), in_vals, self.spmd),
                cache=_SEG_CACHE, key=(self.sig, ()))
            if runner is not None:
                _SEG_CACHE[(self.sig, ())] = runner
        compiled = runner is None
        if compiled:
            runner = _compile_segment_runner(self.pending, self.live, (),
                                             in_vals, self.sig,
                                             spmd=self.spmd)
            _SEG_CACHE[(self.sig, ())] = runner
            if _persist.ACTIVE:
                _disk_store("segment", (_persist_sig(self.sig), ()),
                            runner, _SEG_CACHE, (self.sig, ()))
            if _OBS.METRICS:
                from ..observability import metrics
                metrics.inc("compiles.segment")
        dispatch.bump_exec()
        xspan = _obs_exec_span(compiled, len(self.pending)) \
            if _OBS.ACTIVE else None
        try:
            out_vals = runner(*in_vals)
        except Exception as e:
            if xspan is not None:
                xspan.end(error=e)
            raise
        if xspan is not None:
            xspan.end()
        from . import flags
        if flags.flag_value("FLAGS_check_nan_inf"):
            _nan_scan_segment(self.pending, self.live, out_vals,
                              "replayed segment output", in_vals)
        if _OBS.COMPUTE:
            from ..observability import compute as _comptel
            _comptel.count_cached(_SEG_CACHE, (self.sig, ()), "segment")
        if _OBS.MEM:
            from ..observability import memory as _memtel
            _memtel.note_segment_outputs(
                self.pending, self.live, out_vals, self.sig,
                mesh=self.spmd.desc if self.spmd is not None else None)
        outs = []
        for meta, val in zip(self.metas, out_vals):
            outs.append(Tensor(val, stop_gradient=not meta.requires_grad))
        register_segment_grad(self.pending, self.live, self.metas, outs,
                              in_tensors, in_vals, self.sig)
        return outs


class _ReplayMismatch(Exception):
    pass


# --------------------------------------------------------------- the guard
# Capture state is PER-THREAD. The window used to be process-global,
# which silently interleaved two threads' records into one segment —
# a DataLoader prefetch thread slicing Tensor batches while the main
# thread records the model corrupts the wiring (op indices race with
# concurrent resets). Per-thread windows give each thread its own
# eager order, exactly like per-thread CUDA streams in the reference;
# cross-thread tensor handoff materializes at the boundary (DataLoader
# does this for every queued batch).
import threading as _threading


class _ThreadState(_threading.local):
    def __init__(self):
        self.active: List[CaptureContext] = []   # explicit lazy_guards
        self.ambient: Optional[CaptureContext] = None


_TLS = _ThreadState()

# every open context, across threads — note_inplace must evict a
# mutated tensor's registration from ALL of them (an optimizer on the
# main thread swapping a payload another thread registered). Guarded:
# WeakSet iteration while another thread registers a context would
# RuntimeError.
_ALL_CTXS = weakref.WeakSet()
_ALL_CTXS_LOCK = _threading.Lock()


def _track_ctx(ctx: CaptureContext):
    with _ALL_CTXS_LOCK:
        _ALL_CTXS.add(ctx)


def current_context() -> Optional[CaptureContext]:
    # FLAGS_lazy_enable / FLAGS_eager_fusion are read through the
    # watcher-kept module gates, so toggling them mid-session (even
    # inside an open guard) still takes effect immediately — no stale
    # ambient state survives a flip, and the per-dispatch cost drops
    # from two registry lookups to two attribute reads
    tls = _TLS
    if not _LAZY_ENABLE:
        return None
    if tls.active:
        return tls.active[-1]
    if _EAGER_FUSION:
        if tls.ambient is None:
            tls.ambient = CaptureContext()
            _track_ctx(tls.ambient)
        return tls.ambient
    if tls.ambient is not None:
        # flag flipped off with ops pending: land them, then retire the
        # ambient context so dispatch is strictly per-op again
        ctx, tls.ambient = tls.ambient, None
        ctx.flush("ambient_disable")
    return None


def flush_active(reason: str = "materialize"):
    ctx = current_context()
    if ctx is not None:
        ctx.flush(reason)


def enable_eager_fusion(enable: bool = True) -> Optional[CaptureContext]:
    """Toggle the ambient fusion window (FLAGS_eager_fusion).

    With fusion on (the default), plain dygraph code (no lazy_guard)
    records ops into an ambient segment that runs as one cached XLA
    program at the next sync point (.numpy()/float()/backward()/segment
    cap) — the TPU-native analog of the reference's CUDA-stream
    run-ahead. Turning it off flushes anything pending and restores
    strict per-op dispatch. Returns the (calling thread's) ambient
    context when enabling."""
    from . import flags
    flags.set_flags({"FLAGS_eager_fusion": enable})
    return current_context() if not _TLS.active else _TLS.ambient


def eager_fusion_enabled() -> bool:
    from . import flags
    return bool(flags.flag_value("FLAGS_eager_fusion"))


def note_inplace(tensor):
    """Called by Tensor._replace_value_inplace: evict the tensor's input
    registration from EVERY open capture context, any thread (see
    CaptureContext.note_inplace; eviction itself is a GIL-atomic
    dict.pop)."""
    with _ALL_CTXS_LOCK:
        ctxs = list(_ALL_CTXS)
    for ctx in ctxs:
        ctx.note_inplace(tensor)


def try_fused_backward(tensors, grad_tensors) -> bool:
    """Whole-step fusion entry: backward() on a root still pending in the
    active window compiles forward+vjp as ONE cached XLA program (the
    "step cache", keyed on the segment signature + grad wiring) instead
    of flushing forward and walking the generic engine. Grads land
    directly on the leaves as in-flight futures; the graph is consumed
    (retain_graph=False semantics). Returns True when handled; any
    fallback condition returns False and the generic path runs."""
    ctx = current_context()
    if ctx is None or not ctx.pending or ctx.on_flush is not None:
        return False
    if len(tensors) != 1:
        return False
    if grad_tensors and any(g is not None for g in grad_tensors):
        return False
    root = tensors[0]
    p = root._payload
    if not getattr(p, "_is_lazy_ref", False) or p.ctx is not ctx \
            or p.op_idx is None or not p.requires_grad:
        return False
    if int(np.prod(p.aval.shape)) != 1:   # implicit seed needs a scalar
        return False
    if root._autograd_meta.hooks:
        return False

    pending = ctx.pending
    in_vals = ctx._in_vals
    in_meta = ctx._in_meta
    in_tensors = [r() for r in ctx._in_tensors]
    live, live_refs = ctx._live_outputs(pending)

    root_k = None
    for k, ref in enumerate(live_refs):
        if ref is p:
            root_k = k
        elif ref.requires_grad:
            # another grad-requiring output survives: the generic engine
            # must own the graph (user may backward through it later)
            return False
    if root_k is None:
        return False

    grad_in: List[int] = []
    for i, t in enumerate(in_tensors):
        req, meta, _ = in_meta[i]
        if not req or not jnp.issubdtype(in_vals[i].dtype, jnp.inexact):
            continue
        if meta.grad_node is not None or meta.hooks:
            # grads flow beyond this segment (even if the intermediate
            # tensor itself died), or a hook must fire: only the generic
            # engine handles that correctly
            return False
        if t is None:
            continue   # dead leaf: its grad is unobservable
        grad_in.append(i)
    if not grad_in:
        return False
    grad_in = tuple(grad_in)

    if PERF_OBSERVER is not None:
        # the fused fwd+vjp path seals the window without calling
        # flush(): report it so a perf trace's seal accounting matches
        # the segment.flush_reason.* counters exactly
        PERF_OBSERVER(ctx, "backward_fused", pending)

    # the sanitizer covers the fused fwd+vjp path exactly like a plain
    # flush — this IS the default steady-state train step, so 'error'
    # mode must stop a corrupted program here too (no donation mask:
    # fused-step inputs are the backward residuals). fixable=False:
    # the root/live layout is baked into the step-cache key, so fix
    # mode reports here instead of rewriting.
    from . import flags
    if _flags.STATIC_CHECKS_ACTIVE:
        from ..analysis import hooks as _sanitizer
        try:
            _mode = _sanitizer.check_mode()
            if _mode != "off":
                _sanitizer.on_segment_flush(
                    ctx, pending, in_vals, in_meta, in_tensors,
                    live, live_refs, (), _mode, fixable=False,
                    reason="backward_fused")
        except Exception:
            ctx._reset_segment()
            raise

    fspan = _obs_flush_span("backward_fused", len(pending), len(in_vals),
                            len(live), 0, ctx._fast_ops) \
        if _OBS.ACTIVE else None
    sig, driven = ctx._step_plan_sig(live)
    if sig is None:
        sig = ctx._signature(in_vals, live)
    key = (sig, grad_in, root_k)
    runner = _FUSED_CACHE.get(key)
    if runner is None and _persist.ACTIVE:
        run_vals = resolve_pending(in_vals) if _ASYNC_SEEN else in_vals
        runner = _disk_runner(
            "fused_step", (_persist_sig(sig), grad_in, root_k),
            _jit_factory(
                lambda: _build_fused_fn(pending, live, grad_in, root_k),
                (), run_vals, _spmd_for_compile(in_vals)),
            cache=_FUSED_CACHE, key=key, stat="fused_step")
        if runner is not None:
            _FUSED_CACHE[key] = runner
    compiled = runner is None
    if compiled and _flags.FAULT_INJECT_ACTIVE:
        # segment::compile fault site on the fused fwd+vjp path too:
        # clean up exactly like a real failed compile
        from ..distributed.resilience import faults as _faults
        try:
            _faults.inject("segment::compile")
        except Exception as e:
            ctx._reset_segment()
            if fspan is not None:
                fspan.end(error=e)
            _obs_flush_failed("backward_fused", e)
            raise
    run_vals = None
    if compiled:
        try:
            spmd_pin = _spmd_for_compile(in_vals)
            run_vals = resolve_pending(in_vals) if _ASYNC_SEEN \
                else in_vals
            runner = _compile_fused_runner(pending, live, grad_in,
                                           root_k, run_vals, key,
                                           spmd_pin)
        except Exception as e:
            # AOT compile (memory telemetry on) or pending-input
            # resolution failed: clean up exactly like a failed compile
            ctx._reset_segment()
            if fspan is not None:
                fspan.end(error=e)
            _obs_flush_failed("backward_fused", e)
            oe = _oom_convert(e, "backward_fused")
            if oe is not e:
                raise oe from e
            raise
        _FUSED_CACHE[key] = runner
        if _persist.ACTIVE:
            _disk_store("fused_step",
                        (_persist_sig(sig), grad_in, root_k),
                        runner, _FUSED_CACHE, key)
        if _OBS.METRICS:
            from ..observability import metrics
            metrics.inc("compiles.fused_step")
    dispatch.bump_exec()
    xspan = _obs_exec_span(compiled, len(pending), driven) \
        if fspan is not None else None
    try:
        if run_vals is None:     # cache hit: not resolved above
            run_vals = resolve_pending(in_vals) if _ASYNC_SEEN \
                else in_vals
        if _flags.FAULT_INJECT_ACTIVE:
            _inject_exec_oom()
        out_vals, grads = runner(*run_vals)
    except Exception as e:
        ctx._reset_segment()
        # spans end BEFORE the flight dump (report must carry them)
        if xspan is not None:
            xspan.end(error=e)
        if fspan is not None:
            fspan.end(error=e)
        _obs_flush_failed("backward_fused", e)
        oe = _oom_convert(e, "backward_fused",
                          _FUSED_CACHE.memory_info(key))
        if oe is not e:
            raise oe from e
        raise
    if xspan is not None:
        xspan.end()

    if flags.flag_value("FLAGS_check_nan_inf"):
        try:
            _nan_scan_segment(pending, live, out_vals,
                              "fused-step output", in_vals,
                              extra=("fused-step gradients", grads))
        except Exception as e:
            # a NaN trip drops the consumed trace like a failed compile
            # (leaving it armed would re-execute the whole forward as a
            # plain segment on the next read), closes the step span,
            # and triggers the flight post-mortem
            ctx._reset_segment()
            if fspan is not None:
                fspan.end(error=e)
            _obs_flush_failed("backward_fused", e)
            raise
    ctx._reset_segment()
    ctx.breaks.append("backward_fused")
    ctx.segments_run += 1

    # bind live outputs (they stay in-flight futures — tracing of the
    # next step overlaps this one's device execution)
    for ref, val in zip(live_refs, out_vals):
        for t in _live_aliases(ref):
            t._payload = val

    if SPMD is not None and _OBS.METRICS:
        # the dp gradient all-reduce (and any TP exchange) of this step
        # ran INSIDE the executable: account its estimated payload so
        # the comm-overlap report is not blind to compiled collectives
        _note_compiled_comm(_FUSED_CACHE, key, SPMD, run_vals,
                            list(out_vals) + list(grads), "fused_step")
    if _OBS.COMPUTE:
        from ..observability import compute as _comptel
        _comptel.count_cached(_FUSED_CACHE, key, "fused_step")
    if _OBS.MEM:
        from ..observability import memory as _memtel
        _memtel.note_segment_outputs(
            pending, live, out_vals, sig,
            mesh=SPMD.desc if SPMD is not None else None)
        for g in grads:
            _memtel.note_buffer(g, "fused_step.grad")

    from .autograd import GradNode, _accum
    from .tensor import Tensor
    for i, g in zip(grad_in, grads):
        t = in_tensors[i]
        meta = t._autograd_meta
        if meta.grad is None:
            meta.grad = Tensor(g, stop_gradient=True)
        else:
            meta.grad = Tensor(_accum(meta.grad._value, g),
                               stop_gradient=True)

    # the graph was consumed (retain_graph=False semantics): leave a
    # FREED GradNode on the root so a second backward() raises the same
    # "second time" error as the generic engine, instead of the root
    # looking like a leaf and the call silently no-opping
    meta = root._autograd_meta
    if meta.grad_node is None:
        tomb = GradNode(None, {}, None, [],
                        out_shapes=(tuple(p.aval.shape),),
                        out_dtypes=(p.aval.dtype,))
        tomb.name = "lazy_segment_fused"
        tomb.freed = True
        meta.grad_node = tomb
        meta.out_slot = 0
    if fspan is not None:
        fspan.end()
    return True


class lazy_guard:
    """Context manager enabling the lazy fusion window.

    with paddle_tpu.framework.lazy_guard() as ctx:
        ... eager code; ops fuse into XLA segments ...
    # exiting flushes everything pending
    """

    def __init__(self, max_segment_ops: Optional[int] = None):
        self._max = max_segment_ops
        self.ctx: Optional[CaptureContext] = None

    def __enter__(self) -> CaptureContext:
        from . import flags
        self.ctx = CaptureContext(self._max)
        if flags.flag_value("FLAGS_lazy_enable"):
            _TLS.active.append(self.ctx)
            _track_ctx(self.ctx)
            self._active = True
        else:
            self._active = False   # kill-switch: pure eager
        return self.ctx

    def __exit__(self, et, ev, tb):
        if not getattr(self, "_active", True):
            return False
        _TLS.active.pop()
        if et is None:
            self.ctx.flush("guard_exit")
        else:
            # error path: still materialize what was recorded — tensors
            # computed before the error are valid (eager would have
            # them), and leaving them lazy would poison later reads.
            # Suppress secondary failures during unwind.
            try:
                self.ctx.flush("guard_error")
            except Exception:
                self.ctx._reset_segment()
        return False


def segment_cache_size() -> int:
    return len(_SEG_CACHE)


def clear_segment_cache():
    _SEG_CACHE.clear()
    _SEG_BWD_CACHE.clear()
    _FUSED_CACHE.clear()
    _AVAL_CACHE.clear()
    if _NC is not None:
        _NC.aval_cache_clear()
