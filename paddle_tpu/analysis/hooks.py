"""Runtime wiring of the sanitizer into the hot paths.

`CaptureContext.flush` and `PassManager.run` call in here when
FLAGS_static_checks != 'off'. Both call sites pay exactly one flag read
when checks are off — the checkers themselves never load.
"""
from __future__ import annotations

import os
import sys
from typing import Optional, Tuple


def check_mode() -> str:
    """Normalized FLAGS_static_checks value: 'off' | 'warn' | 'error'
    | 'fix'. Unrecognized spellings raise — a typo ('eror') must not
    silently downgrade the requested mode or enable warn-mode
    overhead."""
    from .._core import flags
    raw = flags.flag_value("FLAGS_static_checks")
    v = str(raw).lower()
    if v in flags.STATIC_CHECKS_OFF_WORDS:
        return "off"
    if v in ("error", "raise", "strict"):
        return "error"
    if v in ("warn", "warning", "on", "true", "1"):
        return "warn"
    if v in ("fix", "autofix", "repair"):
        return "fix"
    raise ValueError(
        f"FLAGS_static_checks={raw!r}: expected 'off', 'warn', "
        f"'error', or 'fix'")


# ------------------------------------------------------------- segments

def segment_sweeps() -> int:
    """Flush-time sweeps since process start — lives in the
    observability metrics registry (`sanitizer.segment_sweeps`; counted
    unconditionally because this path only runs in warn/error mode).
    tests/test_observability.py (`test_off_mode_zero_registry_work`)
    asserts the whole registry, this counter included, stays frozen with
    FLAGS_static_checks=off (checker work is exactly 0, not merely 'too
    small to measure')."""
    from ..observability import metrics
    return metrics.counter("sanitizer.segment_sweeps").value


def fixes_applied() -> int:
    """Autofix rewrites since process start (`sanitizer.fixes_applied`
    registry counter). tests/test_analysis.py asserts it stays frozen when
    fix mode sweeps a CLEAN program — the sanitizer must never rewrite
    correct code."""
    from ..observability import metrics
    return metrics.counter("sanitizer.fixes_applied").value


def run_segment_checkers(view, subject: str, lints: bool = False,
                         strict_inplace: bool = False,
                         strict_views: bool = False):
    """THE segment checker battery — the single list both surfaces
    share (the flush hook below and `analysis.check_segment`), so a new
    checker added here reaches both. `lints` additionally runs the
    optimization lints (dead captures) — on only for fix mode (which
    repairs them silently) and the explicit check_segment API, so
    warn-mode self-linting stays free of benign-but-true waste
    reports. The flush hook runs non-strict: version-less payload
    swaps on inputs no future op reads are deliberate in cold paths
    (state loading), and the view/in-place divergence lint is
    API-only."""
    from .diagnostics import CheckReport
    from .segment_checks import (check_dead_captures,
                                 check_donation_safety,
                                 check_inplace_races, check_shape_dtype,
                                 check_tracer_leaks)
    from .alias_graph import check_view_aliases
    from .dataflow import check_cross_segment_donation
    from .numerics import check_numerics_segment
    report = CheckReport(subject)
    check_donation_safety(view, report)
    check_inplace_races(view, report, strict=strict_inplace)
    check_tracer_leaks(view, report)
    check_shape_dtype(view, report)
    check_cross_segment_donation(view, report)
    check_view_aliases(view, report, strict=strict_views)
    check_numerics_segment(view, report)
    if lints:
        check_dead_captures(view, report)
    return report


def on_segment_flush(ctx, pending, in_vals, in_meta, in_tensors,
                     live, live_refs, donate, mode: str,
                     fixable: bool = True, reason: str = "materialize",
                     in_ids: Optional[dict] = None):
    """Flush-time sanitizer pass over the segment about to execute.
    Called by CaptureContext.flush AFTER the donation mask is computed
    and BEFORE the executable runs, so 'error' mode stops a corrupting
    program from launching.

    In 'fix' mode (and `fixable`, i.e. a plain flush — the fused
    fwd+vjp path reports but never rewrites, its root/live layout is
    baked into the step-cache key) the mechanical finding classes are
    repaired in place, the checkers re-run to prove the diagnostics
    clear, and the REPAIRED (pending, donate) pair is returned for the
    flush to execute; any other mode returns None."""
    from ..observability import metrics
    metrics.counter("sanitizer.segment_sweeps").inc()
    from .segment_checks import SegmentView
    from .._core import lazy
    view = SegmentView(
        pending, in_vals, in_tensors, in_meta,
        # async flushes pass the SEAL-time registration snapshot (the
        # context has already been reset for the next segment by the
        # time the worker sweeps)
        dict(ctx._in_ids) if in_ids is None else in_ids,
        live, live_refs, donate,
        lazy._segment_needs_grad(in_tensors, in_vals, live_refs,
                                 in_meta), ctx=ctx)
    subject = f"lazy segment ({len(pending)} ops)"
    do_fix = mode == "fix" and fixable
    report = run_segment_checkers(view, subject, lints=do_fix)

    out = None
    if do_fix and not report.ok:
        from . import fixes
        result = fixes.plan_and_apply(view, report, ctx=ctx)
        if result.n_applied:
            # repaired findings still count: the per-checker
            # sanitizer.diagnostics.* contract is unconditional, and
            # dashboards must not undercount exactly when autofix is
            # masking bugs (the residual report accounts via emit)
            from .diagnostics import CheckReport
            repaired = CheckReport(subject + " (repaired)")
            repaired.diagnostics = result.consumed
            repaired.account()
            # prove the repair: the mechanical findings must clear
            report = run_segment_checkers(view, subject + " (post-fix)",
                                          lints=True)
            out = (result.pending, result.donate)
    report.emit("warn" if mode == "fix" else mode, stacklevel=5)
    # NOTE: the donation is threaded into the cross-segment ledger by
    # the FLUSH ITSELF after the executable ran (lazy.flush calls
    # dataflow.note_segment_donation post-execute) — recording here
    # would leave a phantom entry behind a failed compile/run and turn
    # a valid later program into a false cross_segment_donation error.
    return out


# ----------------------------------------------------------- numerics

def on_nan_trip(ctx, pending, in_vals, kind: str):
    """NaN-trip forensics (lazy flush/replay/fused-step NaN scans call
    in here just before re-raising FloatingPointError): re-run the
    numerics propagation over the OFFENDING segment and attach the
    ranked suspect ops to the flight dump, so the postmortem names the
    unstable op (with its file:line provenance), not just the step.
    Best-effort by contract — a forensics failure must never mask the
    FloatingPointError it is annotating."""
    try:
        from ..observability import metrics
        metrics.counter("sanitizer.nan_trips").inc()
        from ..observability import _state as _obs
        if not _obs.FLIGHT:
            return None
        from .numerics import nan_suspects
        from .segment_checks import SegmentView
        view = SegmentView(list(pending), list(in_vals),
                           [None] * len(in_vals),
                           [(None, None, 0)] * len(in_vals), {},
                           [], {}, donate=())
        suspects = nan_suspects(view)
        from ..observability import flight
        for rank, s in enumerate(suspects):
            flight.note(
                "nan_suspect", s["op_name"] or "?", rank=rank,
                op=s["op_index"], score=s["score"],
                src=s.get("provenance"), where=kind,
                reason=s["reason"][:160])
        return suspects
    except Exception:
        return None


def on_scaler_step(optimizer, mode: str):
    """optimizer.step() entry hook: check the GradScaler event window
    accumulated since the last step (scale/unscale/clip ordering,
    master weights) and clear it. Only called when checks are on AND
    the window is non-empty — unscaled training never pays."""
    from ..observability import metrics
    metrics.counter("sanitizer.scaler_sweeps").inc()
    from . import numerics
    report = numerics.check_scaler_flow(optimizer)
    numerics.clear_scaler_events()
    report.emit("warn" if mode == "fix" else mode, stacklevel=5)
    return report


# ------------------------------------------------------------ perf lint

def on_perf_flush(ctx, reason: str, pending):
    """Fusion-window seal observer (`lazy.PERF_OBSERVER` points here
    while a perf trace is active): every flush / per-op replay / fused
    backward reports its seal reason and the pending program so the
    perf analyzer (analysis/perf_checks.py) can attribute window
    breaks and host syncs to source lines. Installed only for the
    duration of a PerfRecorder trace — the steady state pays one
    module-attr read per flush."""
    from .perf_checks import _active_recorder
    rec = _active_recorder()
    if rec is not None:
        rec._on_seal(ctx, reason, pending)


# ------------------------------------------------- distributed surfaces

def on_reshard(val_ndim: int, src, dst, global_shape, mode: str):
    """Reshard-lowering hook (distributed reshard_value): validate the
    placement transition against the SPMD rules before any collective
    is planned. 'error' stops the bad transfer; fix mode has nothing
    mechanical to rewrite here, so it reports like warn."""
    from ..observability import metrics
    metrics.counter("sanitizer.reshard_sweeps").inc()
    from .diagnostics import CheckReport
    from .distributed_checks import check_reshard
    report = CheckReport("reshard transition")
    check_reshard(val_ndim, src, dst, report, global_shape=global_shape)
    report.emit("warn" if mode == "fix" else mode, stacklevel=5)
    return report


def on_pipeline_build(schedule: str, pp_size: int, num_micro: int,
                      num_chunks: int, mode: str):
    """Pipeline-runtime construction hook: lower the schedule to
    per-rank P2P programs and simulate for deadlock/ordering before the
    first batch blocks a real process group."""
    from ..observability import metrics
    metrics.counter("sanitizer.pipeline_sweeps").inc()
    from .distributed_checks import check_pipeline_schedule
    report = check_pipeline_schedule(schedule, pp_size, num_micro,
                                     num_chunks)
    report.emit("warn" if mode == "fix" else mode, stacklevel=5)
    return report


def on_world_shrink(transitions, pipeline=None):
    """Post-recovery validation (resilience.shrink_world): every
    planned reshard transition — and the shrunk pipeline schedule,
    when one is in play — is checked BEFORE the first post-recovery
    step. Always runs in 'error' semantics: recovering onto a broken
    layout (out-of-range shard, uneven split, deadlocking schedule
    over the shrunk world) is strictly worse than failing loudly, so
    this sweep does not honor FLAGS_static_checks=off.

    `transitions` is a list of (val_ndim, src_attr, dst_attr,
    global_shape); `pipeline` is (schedule, pp_size, num_micro,
    num_chunks) or None."""
    from ..observability import metrics
    metrics.counter("sanitizer.shrink_sweeps").inc()
    from .diagnostics import CheckReport
    from .distributed_checks import check_pipeline_schedule, check_reshard
    report = CheckReport("world-shrink recovery plan")
    for val_ndim, src, dst, gshape in transitions:
        check_reshard(val_ndim, src, dst, report, global_shape=gshape)
    if pipeline is not None:
        schedule, pp_size, num_micro, num_chunks = pipeline
        check_pipeline_schedule(schedule, pp_size, num_micro,
                                num_chunks, report=report)
    report.emit("error", stacklevel=4)
    return report


# ----------------------------------------------------------- SOT guards

def on_sot_entry_installed(sot_fn, mode: str):
    """Post-capture hook (SotFunction._capture): incremental sweep of
    the JUST-INSTALLED cache entry (unsatisfiable guard set, shadowed
    by a prior entry) — the moment the bug is introduced. Only the new
    entry is checked so a k-entry cache pays O(k), not O(k^2), per
    capture and earlier findings are not re-warned; the full-cache
    sweep stays available as `analysis.check_guards`."""
    from ..observability import metrics
    metrics.counter("sanitizer.guard_sweeps").inc()
    from .diagnostics import CheckReport
    from .sot_checks import check_new_entry
    name = getattr(sot_fn, "__name__", "?")
    report = CheckReport(f"sot capture ({name})")
    check_new_entry(name, sot_fn._entries, report)
    report.emit("warn" if mode == "fix" else mode, stacklevel=5)
    return report


# ------------------------------------------------------------ IR passes

def pre_pass_fingerprint(ws):
    from .program_checks import impure_fingerprint
    return impure_fingerprint(ws)


def verify_pass(ws, pass_name: str, before, mode: str):
    """PassManager post-pass verify hook: effect/purity preservation."""
    from .diagnostics import CheckReport
    from .program_checks import check_pass_effects
    report = CheckReport(f"IR pass '{pass_name}'")
    check_pass_effects(ws, pass_name, before, report)
    report.emit(mode, stacklevel=4)
    return report


def verify_pipeline(ws, mode: str):
    """End-of-pipeline shape/dtype consistency over the rewritten
    workspace (run once per compile, not per pass)."""
    from .diagnostics import CheckReport
    from .program_checks import check_program_shapes
    report = CheckReport("IR pipeline result")
    check_program_shapes(ws, report)
    report.emit(mode, stacklevel=4)
    return report


# ----------------------------------------------------------- provenance

# the installed package directory — NOT a name substring, so user code
# living under a path that happens to contain 'paddle_tpu' (a checkout
# named paddle_tpu/, ~/paddle_tpu_experiments/train.py) still gets
# provenance
_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__))) \
    + os.sep
_IS_FRAMEWORK_FILE: dict = {}   # co_filename -> bool (abspath memo)


def call_site() -> Optional[str]:
    """'file:line' of the first user frame below the framework — the
    Python source provenance a record-time diagnostic points at.
    Stdlib frames (runpy bootstrapping a -m CLI, threading glue) are
    plumbing, never the user source: a CLI-driven trace gets None
    rather than a misleading 'runpy.py:86'. Runs per recorded op in
    warn/error mode, hence the filename memo."""
    f = sys._getframe(1)
    while f is not None:
        fname = f.f_code.co_filename
        fw = _IS_FRAMEWORK_FILE.get(fname)
        if fw is None:
            ap = os.path.abspath(fname)
            # "<frozen runpy>"-style bootstrap frames are plumbing;
            # "<stdin>"/"<string>" stay USER frames — an interactive
            # session's diagnostics keep their source pointer
            fw = ap.startswith(_PKG_DIR) \
                or ap.startswith(_STDLIB_DIR) \
                or fname.startswith("<frozen")
            _IS_FRAMEWORK_FILE[fname] = fw
        if not fw:
            return f"{fname}:{f.f_lineno}"
        f = f.f_back
    return None


# runtime-infrastructure layers a perf diagnostic should see THROUGH:
# the sync/break trigger inside nn/models/vision code (a batch_norm
# running-stat read, a flash_attention dispatch) is the informative
# frame, while _core/analysis/observability frames are plumbing
_INFRA_DIRS = tuple(os.path.join(_PKG_DIR, d) + os.sep
                    for d in ("_core", "analysis", "observability",
                              "jit", "autograd"))
# stdlib frames (runpy bootstrapping a -m CLI, threading glue) are
# plumbing, never the "user source" of a perf event
_STDLIB_DIR = os.path.dirname(os.__file__) + os.sep
_FRAME_KIND: dict = {}   # co_filename -> 'user' | 'infra' | 'framework'


def perf_site() -> Tuple[Optional[str], Optional[str]]:
    """(user_site, framework_site) of the current call stack: the first
    frame OUTSIDE the package (what call_site returns — where user code
    triggered the event) and the first package frame outside the
    runtime-infrastructure layers (where in nn/models/io code the sync
    or break actually lives, e.g. nn/functional/norm.py's running-stat
    update). Either may be None."""
    user = framework = None
    f = sys._getframe(1)
    while f is not None and user is None:
        fname = f.f_code.co_filename
        kind = _FRAME_KIND.get(fname)
        if kind is None:
            ap = os.path.abspath(fname)
            if ap.startswith(_PKG_DIR):
                kind = "infra" if ap.startswith(_INFRA_DIRS) \
                    else "framework"
            elif ap.startswith(_STDLIB_DIR) or fname.startswith("<"):
                kind = "infra"
            else:
                kind = "user"
            _FRAME_KIND[fname] = kind
        if kind == "user":
            user = f"{fname}:{f.f_lineno}"
        elif kind == "framework" and framework is None:
            framework = f"{fname}:{f.f_lineno}"
        f = f.f_back
    return user, framework
