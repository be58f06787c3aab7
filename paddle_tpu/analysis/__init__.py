"""paddle_tpu.analysis — the whole-program sanitizer.

A static-analysis framework over every program representation the
framework produces:

- lazy `CaptureContext` segments (`_PendingOp` dataflow, _core/lazy.py)
- IR `Workspace` programs (ir/pass_base.py)
- the SOT guarded fast-path cache (jit/sot)
- distributed lowerings (reshard transitions, pipeline schedules)

Sixteen checkers ship: the per-program five (donation safety, in-place
races, tracer leaks, shape/dtype drift, IR pass effect/purity), the
cross-program wave — cross-segment donation (buffer identity threaded
across the fused fwd+vjp+optimizer step-cache boundary), view alias
graphs (a view of a donated/mutated base, even segments later), dead
captures (recorded ops nobody can observe, with the wasted FLOPs/bytes),
SOT guard soundness (never-firing and shadowed cache entries), reshard
placement validation, and pipeline-schedule deadlock/ordering
simulation — plus the numerics plane (numerics.py): abstract dtype +
dynamic-range interpretation feeding overflow_risk, accum_dtype,
cast_churn (fixable), scaler_flow and quant_error_budget. Surfaces:

- `FLAGS_static_checks` = off | warn | error | fix, wired into
  `CaptureContext.flush`, `try_fused_backward`, `PassManager.run`,
  reshard lowering, pipeline-runtime construction, and SOT capture;
  `fix` repairs the mechanical classes (missing note_inplace, unsafe
  donation, dead captures) in place and re-checks;
- this module's `check_segment` / `check_program` / `check_guards` /
  `check_reshard` / `check_pipeline_schedule` API;
- `python -m paddle_tpu.analysis` — traces lenet, resnet50 and bert plus
  the distributed configs and reports (`--json`, `--fix`).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

from .diagnostics import (CheckReport, Diagnostic, StaticCheckError,
                          StaticCheckWarning, SEVERITY_ERROR,
                          SEVERITY_PERF, SEVERITY_WARNING)
from .segment_checks import (SegmentView, check_dead_captures,
                             check_donation_safety,
                             check_inplace_races,
                             check_process_tracer_leaks,
                             check_shape_dtype, check_tracer_leaks)
from .program_checks import (check_pass_effects, check_program_shapes,
                             impure_fingerprint)
from .dataflow import check_cross_segment_donation
from .alias_graph import check_view_aliases
from .sot_checks import check_guards
from .distributed_checks import (check_compiled_pipeline,
                                 check_pipeline_schedule, check_reshard,
                                 compiled_pipeline_programs,
                                 simulate_pipeline)
from .perf_checks import PerfRecorder, trace_step
from .perf_checks import check_perf as _check_perf_impl
from .sharding_prop import propagate as propagate_specs
from .sharding_prop import check_sharding as _check_sharding_impl
from .mem_liveness import (CandidateMesh, analyze_liveness,
                           check_memory, plan_pod_shape,
                           step_footprint, sweep_pod_shapes)
from .planner import (PlanCandidate, PlanReport, enumerate_mesh_shapes,
                      plan_program, score_candidate, validate_plan)
from .numerics import (check_accum_dtype, check_cast_churn,
                       check_numerics_segment, check_overflow_risk,
                       check_quant_budget, check_scaler_flow,
                       nan_suspects, propagate_ranges, quant_bucket_plan,
                       quant_snr_db)
from . import alias_graph, dataflow, distributed_checks, fixes, hooks, \
    mem_liveness, numerics, perf_checks, planner, sharding_prop, \
    sot_checks

__all__ = [
    "CheckReport", "Diagnostic", "StaticCheckError",
    "StaticCheckWarning", "SegmentView", "check_segment",
    "check_program", "check_process_tracer_leaks", "check_guards",
    "check_reshard", "check_pipeline_schedule", "simulate_pipeline",
    "check_compiled_pipeline", "compiled_pipeline_programs",
    "check_cross_segment_donation", "check_view_aliases",
    "check_dead_captures", "fix_segment", "check_perf",
    "check_sharding", "propagate_specs", "PerfRecorder", "trace_step",
    "analyze_liveness", "check_memory", "step_footprint",
    "sweep_pod_shapes", "plan_pod_shape", "CandidateMesh",
    "plan_program", "score_candidate", "validate_plan",
    "enumerate_mesh_shapes", "PlanReport", "PlanCandidate",
    "check_numerics_segment", "check_overflow_risk",
    "check_accum_dtype", "check_cast_churn", "check_scaler_flow",
    "check_quant_budget", "quant_bucket_plan", "quant_snr_db",
    "propagate_ranges", "nan_suspects",
]


def check_perf(ctx_or_step) -> CheckReport:
    """Perf lint: fusion-window breaks + host syncs. Pass a step
    callable to trace one step (src capture forced — diagnostics carry
    file:line even with FLAGS_static_checks off), or an open
    CaptureContext for the purely-static sweep of its pending program
    (segment-cap prediction)."""
    return _check_perf_impl(ctx_or_step)


def check_sharding(ctx_or_view, mesh=None,
                   report: Optional[CheckReport] = None) -> CheckReport:
    """Sharding perf lint: propagate PartitionSpecs through the pending
    op graph under `mesh` (default: the active ambient mesh) and flag
    implicit reshards, mp-boundary spec mismatches and accidentally-
    replicated large tensors; the report's `sharding_comm` summary
    ranks per-op compiled-collective hotspots."""
    return _check_sharding_impl(ctx_or_view, mesh=mesh, report=report)


def check_segment(ctx_or_view, donate: Optional[Tuple[int, ...]] = None,
                  process: bool = False, lints: bool = True) -> CheckReport:
    """Run every segment checker over an open CaptureContext (or a
    prebuilt SegmentView). Non-destructive: nothing is flushed or
    mutated; the donation mask defaults to what flush() would compute.
    `lints=False` drops the optimization lints (dead captures, strict
    view/in-place divergence), leaving only the correctness checkers
    the flush hook runs.

        with lazy_guard() as ctx:
            ... record ops ...
            report = paddle_tpu.analysis.check_segment(ctx)
        assert report.ok, report.render()
    """
    if isinstance(ctx_or_view, SegmentView):
        view = ctx_or_view
    else:
        view = SegmentView.from_context(ctx_or_view, donate=donate)
    # the one shared battery (hooks.run_segment_checkers) — the flush
    # hook runs the same list non-strict/lint-free
    report = hooks.run_segment_checkers(
        view, f"lazy segment ({len(view.pending)} ops)", lints=lints,
        strict_inplace=True, strict_views=lints)
    if process:
        check_process_tracer_leaks(report)
    return report


def fix_segment(ctx_or_view, report: Optional[CheckReport] = None,
                dry_run: bool = False):
    """Repair the mechanical finding classes of `report` (computed via
    check_segment when not given) against the context/view, and return
    (FixResult, post_fix_report). With `dry_run` nothing is mutated —
    the CLI's diff-printout path."""
    if isinstance(ctx_or_view, SegmentView):
        view, ctx = ctx_or_view, None
    else:
        view = SegmentView.from_context(ctx_or_view)
        ctx = ctx_or_view
    if report is None:
        report = check_segment(view)
    result = fixes.plan_and_apply(view, report, ctx=ctx,
                                  dry_run=dry_run)
    if dry_run:
        # residual = the findings no planned repair addresses
        addressed = {id(d) for d in result.consumed}
        post = CheckReport(report.subject + " (fix dry-run residual)")
        post.diagnostics = [d for d in report.diagnostics
                            if id(d) not in addressed]
    else:
        post = check_segment(view)
    return result, post


def check_program(program_or_ws, protected: Sequence = ()) -> CheckReport:
    """Run the program-level checkers over a static Program (a fresh
    Workspace is derived) or an already-rewritten Workspace."""
    from ..ir.pass_base import Workspace
    ws = program_or_ws if isinstance(program_or_ws, Workspace) \
        else Workspace(program_or_ws)
    report = CheckReport(f"program ({len(ws.ops)} ops)")
    check_program_shapes(ws, report)
    # a standalone program has no before/after pass delta to verify,
    # but a fingerprint asymmetry against its source Program means some
    # caller-side rewrite already dropped effects
    src = getattr(ws, "program", None)
    if src is not None and src.ops is not ws.ops:
        names_src = [n.op_name for n in src.ops
                     if _is_impure(n.op_name)]
        names_ws = [n.op_name for n in ws.ops
                    if _is_impure(n.op_name)]
        if names_src != names_ws:
            report.add(
                "pass_effects",
                f"workspace impure ops {names_ws} diverged from the "
                f"recorded program's {names_src}",
                severity=SEVERITY_ERROR)
    return report


def _is_impure(name: str) -> bool:
    from ..ir.pass_base import is_impure
    return is_impure(name)
