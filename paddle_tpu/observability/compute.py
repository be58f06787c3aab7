"""Compute-efficiency telemetry plane: FLOPs accounting, MFU, roofline.

The observability stack meters time (spans/budget), bytes (memory.py)
and comm (the overlap report); this module meters the FLOP domain — the
question every MLPerf-on-pods scaling argument (1909.09756, 2011.03641)
starts from: *what fraction of the hardware's peak are we achieving,
and which ops burn the FLOPs?* Four pillars:

- **per-executable cost analysis**: the three fused-runtime compile
  sites (plain segment flush sync+async, fused fwd+vjp step, fused
  optimizer update) route through the jax AOT path while the plane is
  on, so ``compiled.cost_analysis()`` (flops, bytes accessed,
  transcendentals) is captured exactly ONCE per compile and cached on
  the ExecCache entry (``note_cost``/``cost_info``, pruned with the
  entry). Under an ambient SPMD mesh the analysis covers the
  partitioned (per-device) module, so the number is per-chip by
  construction — asserted in tests against a dp-mesh dryrun.
- **per-execution FLOP counters**: every execution of a cost-analyzed
  runner adds its cached FLOPs to ``compute.flops.{segment,fused_step,
  optimizer}`` (and ``compute.bytes_accessed``) — the meters the
  budget tool's MFU/roofline columns and ``--static-diff`` divide.
- **MFU / roofline**: ``peak_flops()`` resolves
  ``FLAGS_device_peak_flops`` (0 = per-backend autodetect with a
  documented CPU fallback); achieved FLOP/s over a measured window
  divided by it is the model-FLOPs-utilization column, and
  flops / bytes-accessed vs the ridge point (peak_flops / peak_membw)
  says compute-bound vs memory-bound.
- **source-attributed device profiles**: segment compile wraps each
  recorded op's lowering in ``jax.named_scope("<op>[<file>:<line>]")``
  from the already-captured ``_PendingOp.src``; ``note_provenance``
  parses the compiled HLO once per compile into an
  instruction-name -> ``op@file:line`` map, so xplane device traces
  and the profiler statistic table group device time by paddle source
  line (``Profiler.source_summary``).

Off-cost follows the house pattern: ``FLAGS_compute_telemetry`` is
watcher-cached into ``_state.COMPUTE`` (folded into ``_state.ACTIVE``);
off = one module-attribute read per site, zero registry and zero
analysis work (tests/test_compute_telemetry.py asserts both exactly).
"""
from __future__ import annotations

import os
import re
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional

from . import _state

_LOCK = threading.Lock()

# cost_analysis() invocations — tests assert exactly one per compile
COST_CALLS = 0

# running totals (ints, registry-independent like memory.py's
# LIVE_BYTES): per-device FLOPs / bytes-accessed priced per execution
FLOPS_EXECUTED = 0
BYTES_ACCESSED = 0
_SITE_FLOPS: Dict[str, int] = {}

# per-executable cost log: (cache stat, key) -> info. Bounded like the
# executable caches it shadows.
_EXECS: "OrderedDict" = OrderedDict()
_EXEC_CAP = 512
_EXEC_SEQ = 0

# HLO-instruction -> "op@file:line" provenance parsed from compiled
# executables (note_provenance); the profiler's source_summary consumes
# it. Bounded drop-oldest.
_HLO_SRC: "OrderedDict[str, str]" = OrderedDict()
_HLO_SRC_CAP = 16384

# achieved-GFLOP/s counter-track state: (perf_counter at last emit,
# flops accumulated since) — emitted into the chrome trace while a
# profiler records
_RATE_T0 = None
_RATE_FLOPS = 0


# ------------------------------------------------------------ analysis

def analyze(compiled, n_devices: int = 1) -> Dict:
    """Capture one compiled executable's cost analysis as a plain dict
    (counted: tests assert exactly one call per compile). The flops /
    bytes numbers describe the PARTITIONED module when the program was
    compiled against a mesh — i.e. per-chip; ``n_devices`` records the
    pricing basis. Backends without the stat degrade to an error note
    instead of raising."""
    global COST_CALLS
    with _LOCK:
        COST_CALLS += 1
    if _state.METRICS:
        from . import metrics
        metrics.inc("compute.cost_analysis_calls")
    try:
        ca = compiled.cost_analysis() or {}
        flops = ca.get("flops")
        nbytes = ca.get("bytes accessed")
        trans = ca.get("transcendentals")
        return {
            "flops": int(flops) if flops and flops > 0 else 0,
            "bytes_accessed": int(nbytes) if nbytes and nbytes > 0 else 0,
            "transcendentals": int(trans) if trans and trans > 0 else 0,
            "n_devices": int(n_devices),
        }
    except Exception as e:                            # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}",
                "n_devices": int(n_devices)}


def exec_seq() -> int:
    """Monotonic cursor over note_executable calls (the memory-plane
    pattern): snapshot before a measurement window to tell THIS run's
    compiles apart from earlier workloads'."""
    return _EXEC_SEQ


def note_executable(stat: str, key, info: Dict):
    """Record one compiled executable's cost analysis under its cache
    identity (bounded; budget aggregates over this log)."""
    global _EXEC_SEQ
    try:
        k = (stat, key)
        hash(k)
    except TypeError:
        k = (stat, id(key))
    with _LOCK:
        _EXEC_SEQ += 1
        _EXECS[k] = dict(info, seq=_EXEC_SEQ)
        _EXECS.move_to_end(k)
        while len(_EXECS) > _EXEC_CAP:
            _EXECS.popitem(last=False)


def note_execution(info: Optional[Dict], site: str):
    """One execution of a cost-analyzed runner: add its cached FLOPs /
    bytes to the module totals (and the ``compute.flops.<site>``
    counters when metrics are on). Callers gate on ``_state.COMPUTE``;
    a None/errored info (compiled before the plane was on, or the
    backend has no cost stat) is a no-op."""
    if not info or "error" in info:
        return
    flops = info.get("flops", 0)
    nbytes = info.get("bytes_accessed", 0)
    global FLOPS_EXECUTED, BYTES_ACCESSED
    with _LOCK:
        FLOPS_EXECUTED += flops
        BYTES_ACCESSED += nbytes
        _SITE_FLOPS[site] = _SITE_FLOPS.get(site, 0) + flops
    if _state.METRICS:
        from . import metrics
        if flops:
            metrics.inc("compute.flops." + site, flops)
        if nbytes:
            metrics.inc("compute.bytes_accessed", nbytes)
    if _state.TRACE and flops:
        _emit_rate(flops)


def count_cached(cache, key, site: str):
    """Per-execution counting for the ExecCache-backed sites: read the
    cost info the compile attached to this entry and price one
    execution. One dict get when the entry carries no analysis."""
    note_execution(cache.cost_info(key), site)


def _emit_rate(flops: int):
    """Achieved-GFLOP/s counter track while a profiler records: rate
    over the window since the last emission (>=1ms so a burst of tiny
    executions doesn't explode the trace)."""
    global _RATE_T0, _RATE_FLOPS
    now = time.perf_counter()
    with _LOCK:
        if _RATE_T0 is None:
            _RATE_T0, _RATE_FLOPS = now, flops
            return
        _RATE_FLOPS += flops
        dt = now - _RATE_T0
        if dt < 1e-3:
            return
        gflops = _RATE_FLOPS / dt / 1e9
        _RATE_T0, _RATE_FLOPS = now, 0
    from ..profiler import _add_counter_event
    _add_counter_event("compute.achieved_gflops", gflops, key="gflops")


def executed_flops() -> int:
    return FLOPS_EXECUTED


def executed_bytes() -> int:
    return BYTES_ACCESSED


def site_flops() -> Dict[str, int]:
    with _LOCK:
        return dict(_SITE_FLOPS)


def executable_stats() -> List[Dict]:
    with _LOCK:
        return [{"cache": k[0], **info} for k, info in _EXECS.items()]


def reset():
    """Zero every total and drop the logs (tests / fresh baselines)."""
    global COST_CALLS, FLOPS_EXECUTED, BYTES_ACCESSED
    global _RATE_T0, _RATE_FLOPS
    with _LOCK:
        COST_CALLS = 0
        FLOPS_EXECUTED = BYTES_ACCESSED = 0
        _SITE_FLOPS.clear()
        _EXECS.clear()
        _HLO_SRC.clear()
        _RATE_T0, _RATE_FLOPS = None, 0


# --------------------------------------------------------- peak / roofline

# TPU peaks live in _core/device.py (one table keyed by device_kind; an
# unknown TPU is an error there). Off a TPU the plane prices against
# documented CPU envelopes (README "Compute efficiency & MFU"): a
# nominal AVX2-FMA envelope per core and two-channel DDR4 bandwidth.
# CPU MFU is a RELATIVE meter (regressions across rounds on one box),
# not an absolute one.
_CPU_GHZ = 2.5e9
_CPU_FLOPS_PER_CYCLE = 16          # 8 fp32 lanes x FMA
_CPU_MEMBW = 25.6e9


def peak_flops() -> float:
    """Per-chip peak FLOP/s: FLAGS_device_peak_flops, or the device's
    published peak when the flag is 0."""
    from .._core import device
    from .._core.flags import flag_value
    v = float(flag_value("FLAGS_device_peak_flops"))
    if v > 0:
        return v
    if device.is_tpu():
        return device.chip_peaks().flops
    return (os.cpu_count() or 1) * _CPU_GHZ * _CPU_FLOPS_PER_CYCLE


def peak_membw() -> float:
    """Per-chip peak memory bandwidth (bytes/s) for the roofline
    ridge: FLAGS_device_peak_membw, or the device's published peak."""
    from .._core import device
    from .._core.flags import flag_value
    v = float(flag_value("FLAGS_device_peak_membw"))
    if v > 0:
        return v
    if device.is_tpu():
        return device.chip_peaks().membw
    return _CPU_MEMBW


def mfu(achieved_flops_per_s: float,
        peak: Optional[float] = None) -> float:
    """Model-FLOPs-utilization: achieved / per-chip peak."""
    peak = peak_flops() if peak is None else float(peak)
    if peak <= 0:
        return 0.0
    return achieved_flops_per_s / peak


def roofline(flops: int, bytes_accessed: int,
             peak: Optional[float] = None,
             membw: Optional[float] = None) -> Dict:
    """Arithmetic intensity (FLOP per byte accessed) against the ridge
    point peak_flops/peak_membw: above the ridge the kernel mix is
    compute-bound, below it memory-bound."""
    peak = peak_flops() if peak is None else float(peak)
    membw = peak_membw() if membw is None else float(membw)
    intensity = flops / bytes_accessed if bytes_accessed else 0.0
    ridge = peak / membw if membw else 0.0
    bound = None
    if flops:
        bound = "compute-bound" if intensity >= ridge else "memory-bound"
    return {"arith_intensity": round(intensity, 3),
            "ridge_intensity": round(ridge, 3),
            "bound": bound}


def summary() -> Dict:
    """The FLOP-domain snapshot stats()/frames surface."""
    return {
        "cost_analysis_calls": COST_CALLS,
        "flops_executed": FLOPS_EXECUTED,
        "bytes_accessed": BYTES_ACCESSED,
        "site_flops": site_flops(),
        "peak_flops": peak_flops(),
        "executables": executable_stats()[-8:],
        "provenance_entries": len(_HLO_SRC),
    }


# ------------------------------------------------- source attribution

# one HLO-text line: "%instr = ... metadata={op_name="..." ...}"
_HLO_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=.*op_name=\"([^\"]*)\"")
# the scope fragment the segment builder emits: <op>[<file>:<line>];
# the LAST match in an op_name path is the innermost (most specific)
_SCOPE_RE = re.compile(r"([\w.\-]+)\[([^\[\]]+:\d+)\]")


def scope_name(op_name: str, src: str) -> str:
    """The named_scope string for one recorded op: ``<op>[<file>:
    <line>]`` — jax drops scope names containing '@', so brackets
    carry the provenance through HLO op_name metadata and
    ``source_of`` renders it back as ``op@file:line``."""
    return f"{op_name}[{src}]"


def note_provenance(compiled):
    """Parse one compiled executable's HLO text into instruction-name
    -> ``op@file:line`` entries (once per compile, only while the
    plane is on). Device trace events are named after HLO instructions
    ("fusion.3", "dot.2"), so this map is what lets the profiler group
    device time by paddle source line."""
    try:
        txt = compiled.as_text()
    except Exception:                                 # pragma: no cover
        return
    found = {}
    for line in txt.splitlines():
        m = _HLO_LINE_RE.match(line)
        if m is None:
            continue
        scopes = _SCOPE_RE.findall(m.group(2))
        if not scopes:
            continue
        op, src = scopes[-1]
        found[m.group(1)] = f"{op}@{src}"
    if not found:
        return
    with _LOCK:
        _HLO_SRC.update(found)
        while len(_HLO_SRC) > _HLO_SRC_CAP:
            _HLO_SRC.popitem(last=False)


def source_of(event_name: str) -> Optional[str]:
    """``op@file:line`` provenance for one device-trace event name, or
    None. Thunk-level suffixes (".clone") and kernel-wrapper prefixes
    are normalized away before the lookup."""
    hit = _HLO_SRC.get(event_name)
    if hit is not None:
        return hit
    base = event_name.split(" ")[0]
    for suffix in (".clone",):
        if base.endswith(suffix):
            base = base[: -len(suffix)]
    return _HLO_SRC.get(base)


def provenance_size() -> int:
    return len(_HLO_SRC)
