"""Byte-domain telemetry plane: HBM accounting for the fused runtime.

Every other observability surface (spans, budgets, cross-rank frames)
measures the framework in the TIME domain; this module measures it in
BYTES — the resource that actually bounds batch/model scaling on TPUs
("Exploring the limits of Concurrency in ML Training on Google TPUs":
memory, not FLOPs, picks the mesh degree). Four pillars:

- **live-buffer census**: a weakref registry of device-backed payloads
  (nbytes, dtype, shape, birth site) maintained at the Tensor-creation
  and lazy bind/materialize choke points. Feeds the
  ``memory.live_bytes`` / ``memory.peak_bytes`` watermark gauges and a
  top-N accessor. The census NEVER holds a strong reference — a buffer
  leaves the moment its last owner drops it (donation included).
- **per-executable XLA memory analysis**: compile sites (plain segment
  flush, fused fwd+vjp step, fused optimizer update) route through the
  jax AOT path while the plane is on, so ``compiled.memory_analysis()``
  (temp / argument / output / generated-code bytes) is captured exactly
  ONCE per compile and cached on the ExecCache entry — the step cache
  reports its steady-state compiled footprint without re-running
  anything.
- **donation savings accounting**: the lazy-flush donation mask and the
  fused optimizer's ``donate_argnums`` sites report the bytes donated
  per step (``memory.donated_bytes``) — the concrete number the
  donation machinery buys, and what a ``fusion.window_breaks`` step
  forfeits.
- **OOM postmortem**: the three execute sites catch XLA
  RESOURCE_EXHAUSTED (and the seedable ``exec::oom`` fault-injection
  drill), write a postmortem naming the top-N live buffers with
  provenance plus the failing executable's memory analysis and the
  current watermark, then re-raise as the typed
  ``base.core.ResourceExhaustedError`` (the async flush worker latches
  the typed error, so the sync point sees the same class).

Off-cost follows the house pattern: ``FLAGS_memory_telemetry`` is
watcher-cached into the ``_state.MEM`` module gate (folded into
``_state.ACTIVE``); off = one module-attribute read at every choke
point, zero census and zero registry work
(tests/test_memory_telemetry.py asserts both exactly).
"""
from __future__ import annotations

import os
import threading
import time
import weakref
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

from . import _state

# census lock is REENTRANT: a gc triggered while it is held can fire a
# dead buffer's weakref callback (_drop) on the same thread. Metrics /
# profiler calls always happen OUTSIDE it (their locks nest the other
# way on other threads).
_LOCK = threading.RLock()


class _Entry:
    __slots__ = ("ref", "nbytes", "pd_nbytes", "shape", "dtype", "site",
                 "t_birth")


_CENSUS: Dict[int, _Entry] = {}

# running totals (ints, no registry): the census works even when the
# metrics registry is off; gauges mirror these only under _state.METRICS
LIVE_BYTES = 0
PEAK_BYTES = 0
# PER-DEVICE watermark: a buffer sharded over an N-device mesh costs
# each device only its shard — THE number that sizes dp×mp against the
# HBM budget (spmd.suggest_mesh_degree). Equals the global totals for
# unsharded runs.
LIVE_PD_BYTES = 0
PEAK_PD_BYTES = 0
DONATED_BYTES = 0
ANALYSIS_CALLS = 0
OOM_POSTMORTEMS = 0

# newest static per-device peak prediction (analysis/mem_liveness):
# {pd_bytes, desc, mesh} — the OOM postmortem prints it next to the
# measured watermark so the report says whether the OOM was statically
# foreseeable. Best-effort provenance: the last program analyzed.
STATIC_PREDICTION: Optional[Dict] = None

# per-executable memory analysis log: (cache stat, cache key) -> info.
# Bounded like the executable caches it shadows.
_EXECS: "OrderedDict[Tuple, Dict]" = OrderedDict()
_EXEC_CAP = 512

_OOM_SEQ = 0


class _SiteTLS(threading.local):
    site = None


_SITE = _SiteTLS()


def set_site(site: str):
    """Birth-site hint for buffers registered on this thread until
    clear_site() — the eager dispatch wrap point tags its outputs with
    the op name this way (Tensor.__init__ reads it)."""
    _SITE.site = site


def clear_site():
    _SITE.site = None


# ------------------------------------------------------------------ census

def note_buffer(val, site: Optional[str] = None):
    """Register one device-backed payload. Callers gate on
    ``_state.MEM``; anything that is not a concrete jax array (tracers,
    lazy refs, pending values) is ignored. Holding only a weakref, the
    census can never extend a buffer's lifetime."""
    k = id(val)
    hit = _CENSUS.get(k)        # GIL-atomic read: the common re-wrap
    if hit is not None and hit.ref() is val:
        return                  # already tracked (first birth site wins
        #                         — the shared scalar-coercion cache
        #                         re-wraps the same array every op, so
        #                         this path must stay O(dict get))
    import jax
    if not isinstance(val, jax.Array) or isinstance(val, jax.core.Tracer):
        return
    try:
        nb = int(val.nbytes)
    except Exception:
        return
    # per-device cost: a NamedSharding-committed buffer occupies only
    # its shard on each device (one isinstance check on the unsharded
    # path; shard_shape is metadata-only)
    pd = nb
    try:
        sh = val.sharding
        from jax.sharding import NamedSharding as _NS
        if isinstance(sh, _NS) and nb:
            shard = sh.shard_shape(tuple(val.shape))
            n = 1
            for s in shard:
                n *= int(s)
            tot = 1
            for s in val.shape:
                tot *= int(s)
            pd = int(nb * n / tot) if tot else nb
    except Exception:
        pd = nb
    if site is None:
        site = _SITE.site or "tensor.create"
    global LIVE_BYTES, PEAK_BYTES, LIVE_PD_BYTES, PEAK_PD_BYTES
    with _LOCK:
        ex = _CENSUS.get(k)
        if ex is not None:
            if ex.ref() is not None:
                return
            # id reuse beat the dead entry's callback: replace it
            LIVE_BYTES -= ex.nbytes
            LIVE_PD_BYTES -= ex.pd_nbytes
            del _CENSUS[k]
        e = _Entry()
        e.ref = weakref.ref(val, lambda _r, _k=k: _drop(_k))
        e.nbytes = nb
        e.pd_nbytes = pd
        e.shape = tuple(val.shape)
        e.dtype = str(val.dtype)
        e.site = site
        e.t_birth = time.perf_counter()
        _CENSUS[k] = e
        LIVE_BYTES += nb
        LIVE_PD_BYTES += pd
        if LIVE_BYTES > PEAK_BYTES:
            PEAK_BYTES = LIVE_BYTES
        if LIVE_PD_BYTES > PEAK_PD_BYTES:
            PEAK_PD_BYTES = LIVE_PD_BYTES
        live, peak = LIVE_BYTES, PEAK_BYTES
    _publish(live, peak)


def _drop(k: int):
    """Weakref callback: the payload died (freed, or deleted by
    donation and then released) — remove it from the census."""
    global LIVE_BYTES, LIVE_PD_BYTES
    with _LOCK:
        e = _CENSUS.get(k)
        if e is None or e.ref() is not None:
            return              # already replaced by an id-reuse insert
        del _CENSUS[k]
        LIVE_BYTES -= e.nbytes
        LIVE_PD_BYTES -= e.pd_nbytes
        live, peak = LIVE_BYTES, PEAK_BYTES
    _publish(live, peak)


def _publish(live: int, peak: int):
    """Mirror the census totals into the consumers that are on. Called
    OUTSIDE the census lock (see _LOCK note)."""
    if _state.METRICS:
        from . import metrics
        metrics.gauge("memory.live_bytes").set(live)
        metrics.gauge("memory.peak_bytes").set(peak)
    if _state.TRACE:
        from ..profiler import _add_counter_event
        _add_counter_event("memory.live_bytes", live)


def note_segment_outputs(pending, live, out_vals, sig=None, mesh=None):
    """Census registration for a flushed/replayed segment's live
    outputs: birth site = segment signature tag + producing op, plus
    the ambient mesh descriptor when the step ran sharded
    (``seg@<sig>:<op>#i@dp2xmp4``) — an OOM postmortem on a sharded
    run then names which mesh configuration filled the device."""
    try:
        tag = (hash(sig) & 0xFFFF) if sig is not None else 0
    except TypeError:
        tag = 0
    suffix = f"@{mesh}" if mesh else ""
    for (j, _s), val in zip(live, out_vals):
        note_buffer(val, f"seg@{tag:04x}:{pending[j].op.name}#{j}{suffix}")


def note_donated(nbytes: int):
    """Account bytes handed to XLA via buffer donation this step (lazy
    flush donation mask, optimizer donate_argnums)."""
    global DONATED_BYTES
    n = int(nbytes)
    with _LOCK:
        DONATED_BYTES += n
    if _state.METRICS:
        from . import metrics
        metrics.inc("memory.donated_bytes", n)


def note_static_prediction(pd_bytes: int, desc: str,
                           mesh: Optional[str] = None):
    """Record the newest static per-device peak prediction (the
    mem-liveness pass calls this whenever it analyzes a program as it
    will actually run — not for candidate-shape sweeps). Read back by
    the OOM postmortem."""
    global STATIC_PREDICTION
    STATIC_PREDICTION = {"pd_bytes": int(pd_bytes), "desc": str(desc),
                         "mesh": mesh}


def device_bytes() -> Dict[str, int]:
    """Live census bytes per device id — STRING-keyed (device ids are
    ints; an int-keyed map silently becomes string-keyed after one
    json round trip, the PR-8 step-table bug class, so the map is born
    string-keyed). Sharded buffers charge each device its own shard;
    resolution failures fall back to device '0'."""
    out: Dict[str, int] = {}
    with _LOCK:
        vals = [e.ref() for e in _CENSUS.values()]
    for val in vals:
        if val is None:
            continue
        try:
            for sh in val.addressable_shards:
                k = str(sh.device.id)
                out[k] = out.get(k, 0) + int(sh.data.nbytes)
        except Exception:
            try:
                out["0"] = out.get("0", 0) + int(val.nbytes)
            except Exception:
                pass
    return out


def live_bytes() -> int:
    return LIVE_BYTES


def peak_bytes() -> int:
    return PEAK_BYTES


def per_device_bytes() -> int:
    return LIVE_PD_BYTES


def peak_per_device_bytes() -> int:
    return PEAK_PD_BYTES


def donated_bytes() -> int:
    return DONATED_BYTES


def census_size() -> int:
    return len(_CENSUS)


def reset_peak():
    """Re-anchor the watermarks at the current live totals (budget /
    bench measurement windows)."""
    global PEAK_BYTES, PEAK_PD_BYTES
    with _LOCK:
        PEAK_BYTES = LIVE_BYTES
        PEAK_PD_BYTES = LIVE_PD_BYTES


def census(top: Optional[int] = None) -> List[Dict]:
    """Live buffers, largest first: [{nbytes, shape, dtype, site,
    age_s}]. Pure metadata — no payload references escape."""
    now = time.perf_counter()
    with _LOCK:
        rows = [{"nbytes": e.nbytes, "shape": list(e.shape),
                 "dtype": e.dtype, "site": e.site,
                 "age_s": round(now - e.t_birth, 3)}
                for e in _CENSUS.values()]
    rows.sort(key=lambda r: -r["nbytes"])
    return rows[:top] if top else rows


def reset():
    """Drop the census and zero every total (tests / fresh measurement
    baselines). Dead entries' pending callbacks tolerate the clear."""
    global LIVE_BYTES, PEAK_BYTES, DONATED_BYTES, ANALYSIS_CALLS
    global OOM_POSTMORTEMS, LIVE_PD_BYTES, PEAK_PD_BYTES
    global STATIC_PREDICTION
    with _LOCK:
        _CENSUS.clear()
        _EXECS.clear()
        LIVE_BYTES = PEAK_BYTES = DONATED_BYTES = 0
        LIVE_PD_BYTES = PEAK_PD_BYTES = 0
        ANALYSIS_CALLS = OOM_POSTMORTEMS = 0
        STATIC_PREDICTION = None


# -------------------------------------------- per-executable memory analysis

def analyze(compiled) -> Dict:
    """``compiled.memory_analysis()`` as a plain dict (counted: tests
    assert exactly one call per compile). Backends without the stat
    (some PJRT plugins) degrade to an error note instead of raising."""
    global ANALYSIS_CALLS
    with _LOCK:
        ANALYSIS_CALLS += 1
    if _state.METRICS:
        from . import metrics
        metrics.inc("memory.analysis_calls")
    try:
        ma = compiled.memory_analysis()
        return {"temp_bytes": int(ma.temp_size_in_bytes),
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "generated_code_bytes":
                    int(ma.generated_code_size_in_bytes)}
    except Exception as e:                           # pragma: no cover
        return {"error": f"{type(e).__name__}: {e}"}


_EXEC_SEQ = 0


def exec_seq() -> int:
    """Monotonic cursor over note_executable calls: snapshot it before
    a measurement window to tell THIS run's compiles apart from every
    earlier workload's in the process-global log."""
    return _EXEC_SEQ


def note_executable(stat: str, key, info: Dict):
    """Record one compiled executable's analysis under its cache
    identity (bounded; budget/stats aggregate over this log)."""
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
    if _state.METRICS and "error" not in info:
        from . import metrics
        for field in ("temp_bytes", "argument_bytes", "output_bytes",
                      "generated_code_bytes"):
            v = info.get(field)
            if v:
                metrics.inc("compiles.bytes." + field[:-6], v)


def aot_compile(jitted, args, kwargs: Optional[Dict] = None,
                stat: str = "segment", cache=None, key=None,
                n_devices: int = 1):
    """Compile a jitted callable through the AOT path so the Compiled
    executable (donation baked in) doubles as the cached runner AND its
    analyses are captured exactly once per compile — a later cache hit
    runs the same executable with zero analysis work. One
    ``lower().compile()`` serves BOTH telemetry planes: the memory
    plane's ``memory_analysis()`` (``_state.MEM``) and the compute
    plane's ``cost_analysis()`` + HLO source provenance
    (``_state.COMPUTE``) — callers gate on either being on. Returns a
    runner callable with the same concrete-array arguments (the
    executable cache key already pins the input signature); tracer
    arguments on a later call fall back to the jit wrapper, because a
    Compiled object cannot inline into an enclosing jax trace — and
    the cached runner outlives the telemetry session."""
    import jax
    compiled = jitted.lower(*args, **(kwargs or {})).compile()
    info = None
    if _state.MEM:
        info = analyze(compiled)
        note_executable(stat, key, info)
        if cache is not None and key is not None \
                and hasattr(cache, "note_memory"):
            cache.note_memory(key, info)
    cinfo = None
    if _state.COMPUTE:
        from . import compute as _comptel
        cinfo = _comptel.analyze(compiled, n_devices)
        _comptel.note_executable(stat, key, cinfo)
        if cache is not None and key is not None \
                and hasattr(cache, "note_cost"):
            cache.note_cost(key, cinfo)
        _comptel.note_provenance(compiled)

    def runner(*vals, _compiled=compiled, _jitted=jitted,
               _kw=dict(kwargs or {}), _tracer=jax.core.Tracer):
        for v in vals:
            if isinstance(v, _tracer):
                # static kwargs are baked into the Compiled; the jit
                # fallback needs them passed explicitly
                return _jitted(*vals, **_kw)
        return _compiled(*vals)

    runner.memory_analysis_info = info
    runner.cost_analysis_info = cinfo
    # the raw Compiled rides along so the persistent executable cache
    # (_core/persist.py) can serialize it without re-lowering
    runner.aot_executable = compiled
    return runner


def executable_stats() -> List[Dict]:
    """[{cache, <analysis fields>}] for every recorded executable."""
    with _LOCK:
        return [{"cache": k[0], **info} for k, info in _EXECS.items()]


def summary() -> Dict:
    """The byte-domain snapshot stats()/frames surface."""
    execs = executable_stats()
    return {
        "live_bytes": LIVE_BYTES,
        "peak_bytes": PEAK_BYTES,
        "live_per_device_bytes": LIVE_PD_BYTES,
        "peak_per_device_bytes": PEAK_PD_BYTES,
        "donated_bytes": DONATED_BYTES,
        "census": census_size(),
        "analysis_calls": ANALYSIS_CALLS,
        "oom_postmortems": OOM_POSTMORTEMS,
        # STRING-keyed per-device byte map (json-round-trip safe — the
        # PR-8 step-table key-type bug class)
        "per_device": device_bytes(),
        "static_prediction": dict(STATIC_PREDICTION)
        if STATIC_PREDICTION else None,
        "top": census(8),
        "executables": execs[-8:],
    }


# ---------------------------------------------------------- OOM postmortem

def is_oom(err: BaseException) -> bool:
    """XLA RESOURCE_EXHAUSTED (real, or the synthetic ``exec::oom``
    fault kind — both carry the status name in their message)."""
    return "RESOURCE_EXHAUSTED" in str(err)


def on_oom(err: BaseException, where: str, mem_info: Optional[Dict] = None,
           top: int = 16):
    """Build the OOM postmortem and return the typed error to raise.
    Already-typed framework errors pass through untouched (no double
    wrapping when an async worker's converted error re-surfaces)."""
    from ..base.core import EnforceNotMet, ResourceExhaustedError
    if isinstance(err, EnforceNotMet):
        return err
    global OOM_POSTMORTEMS
    with _LOCK:
        OOM_POSTMORTEMS += 1
    top_rows = census(top) if _state.MEM else []
    path = None
    try:
        path = _write_postmortem(where, err, top_rows, mem_info)
    except Exception:                                # pragma: no cover
        path = None
    if _state.METRICS:
        from . import metrics
        metrics.inc("memory.oom_postmortems")
    if _state.FLIGHT:
        from . import flight
        flight.note("oom", where, live_bytes=LIVE_BYTES,
                    peak_bytes=PEAK_BYTES)
    if top_rows:
        r = top_rows[0]
        head = (f"largest live buffer {r['nbytes']} B "
                f"{r['dtype']}{r['shape']} born at {r['site']}")
    else:
        head = ("census empty — was FLAGS_memory_telemetry on while "
                "the workload ran?")
    hint = (f"memory postmortem written to {path}" if path
            else "set FLAGS_memory_telemetry=true for a live-buffer "
                 "census in this report")
    e = ResourceExhaustedError(
        f"XLA out of memory (RESOURCE_EXHAUSTED) at {where}: "
        f"live {LIVE_BYTES} B, peak {PEAK_BYTES} B, {head}",
        context=hint)
    e.postmortem_path = path
    e.__cause__ = err
    return e


def _write_postmortem(where: str, err: BaseException, top_rows: List[Dict],
                      mem_info: Optional[Dict]) -> str:
    """One readable report: watermark, the failing executable's memory
    analysis, the top live buffers with provenance, and the flight ring
    when it is armed. Filed next to (and pruned with) the flight
    dumps."""
    from . import flight
    global _OOM_SEQ
    lines = [f"== paddle_tpu OOM postmortem ({where}) ==",
             f"error: {repr(err)[:500]}",
             f"watermark: live={LIVE_BYTES} B  peak={PEAK_BYTES} B  "
             f"donated_total={DONATED_BYTES} B  "
             f"census={census_size()} buffer(s)"]
    sp = STATIC_PREDICTION
    if sp:
        # was this OOM statically foreseeable? Compare the mem-lint
        # prediction for the program against the measured per-device
        # PEAK watermark — the high-water mark the device actually
        # reached, not whatever happens to be live at failure time
        verdict = ("FORESEEABLE — the static plan predicted at least "
                   "the measured watermark; `python -m "
                   "paddle_tpu.analysis --mem` would have flagged "
                   "oom_risk before the first run"
                   if sp["pd_bytes"] >= PEAK_PD_BYTES else
                   "under-predicted — the measured watermark exceeds "
                   "the static plan (untracked allocations or a "
                   "workload the recorded program does not cover)")
        lines.append(
            f"static predicted peak: {sp['pd_bytes']} B/device "
            f"({sp['desc']}, mesh {sp['mesh'] or 'dp1'}) vs measured "
            f"peak {PEAK_PD_BYTES} B/device: {verdict}")
    else:
        lines.append("static predicted peak: none recorded (run the "
                     "mem lint — analysis.check_memory / `--mem` — "
                     "over the step to know OOM risk before running)")
    if mem_info:
        pretty = " ".join(f"{k}={v}" for k, v in mem_info.items())
        lines.append(f"failing executable memory analysis: {pretty}")
    else:
        lines.append("failing executable memory analysis: unavailable "
                     "(compile predates FLAGS_memory_telemetry, or the "
                     "compile itself failed)")
    lines.append(f"top {len(top_rows)} live buffer(s) by size:")
    for i, r in enumerate(top_rows, 1):
        lines.append(f"  {i:>3}. {r['nbytes']:>12} B  "
                     f"{r['dtype']}{r['shape']}  {r['site']}  "
                     f"age={r['age_s']}s")
    if not top_rows:
        lines.append("  (none recorded)")
    lines.append("")
    lines.append(flight.record() if _state.FLIGHT
                 else "(flight recorder off — no event ring)")
    d = flight._dump_dir()
    os.makedirs(d, exist_ok=True)
    with _LOCK:
        _OOM_SEQ += 1
        seq = _OOM_SEQ
    rank = flight._rank()
    tag = f"r{rank}_" if rank is not None else ""
    path = os.path.join(d, f"flight_oom_{tag}{os.getpid()}_{seq}.txt")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    flight._prune_dumps(d, rank)
    return path
