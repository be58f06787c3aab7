"""Persistent compiled-executable cache (FLAGS_executable_cache_dir).

Process restart, elastic re-plan and serving cold-start used to pay
``lower().compile()`` for every sealed segment, fused step and
optimizer update — the goodput ledger's compile bucket prices exactly
this badput (an adaptive re-plan is mostly recompile). This
module serializes compiled executables through jax's AOT surface
(SNIPPETS [1] pjit Lowered/compile split -> serialize_executable) under
a content-addressed filename, so an ``ExecCache`` miss consults disk
before compiling.

Key scheme: sha256 over ``repr((VERSION, jax version, backend, kind,
normalized key))`` where the caller passes its cache key with the
session-local ``MESH_EPOCH`` component replaced by 0 — the epoch salt
exists to invalidate *in-memory* entries across re-plans, but every
structural consequence of a re-plan (mesh layout, shard specs, world
size) is already inside the signature (``shard_sig`` / spmd specs), so
two processes or two re-plan cycles with the same structure share one
disk entry. Every key component is an interned primitive (strings,
ints, tuples), making ``repr`` stable across processes.

File layout (``<kind>-<digest>.ptxc``): MAGIC + hex sha256 of the
payload + newline + pickled payload dict. Writes are atomic
(temp + fsync + os.replace — the checkpoint.py torn-save pattern);
loads verify magic, checksum, version, jax version, backend and the
full key repr BEFORE trusting the pickle, so a truncated, corrupted or
wrong-version file falls back to a clean recompile with a
``cache.persist.reject`` counter and a flight-recorder note — never a
crash. The PR-9/PR-12 memory/cost analyses and the compiled-comm
estimate ride the payload so warm loads keep their meters.
"""
from __future__ import annotations

import hashlib
import logging
import os
import pickle
import tempfile
from typing import Any, Dict, Optional

from . import flags as _flags
from ..observability import _state as _OBS

_LOG = logging.getLogger(__name__)

VERSION = 2    # 2: payload carries the executable's device ids
MAGIC = b"PTXC1\n"
_SUFFIX = ".ptxc"

# Watcher-cached gate (the STATIC_CHECKS_ACTIVE pattern): ACTIVE is True
# iff FLAGS_executable_cache_dir names a directory. Hot paths pay one
# module-attribute read while the cache is off.
ACTIVE = False
_DIR = ""


def _sync_dir_gate(value):
    global ACTIVE, _DIR
    _DIR = str(value or "").strip()
    ACTIVE = bool(_DIR)


_flags.watch_flag("FLAGS_executable_cache_dir", _sync_dir_gate)


def _count(stat: str, reason: str = None):
    if _OBS.METRICS:
        from ..observability import metrics
        metrics.inc("cache.persist." + stat)
    if reason is not None:
        _LOG.warning("persistent executable cache: %s", reason)
        if _OBS.FLIGHT:
            from ..observability import flight
            flight.note("cache.persist", stat, reason=reason)


def _env() -> tuple:
    import jax
    return jax.__version__, jax.default_backend()


def digest(kind: str, norm_key) -> str:
    """Content digest of a normalized cache key. The caller has already
    zeroed the MESH_EPOCH component; everything else (op stream, input
    signature, donation, shard structure) is part of the identity."""
    jver, backend = _env()
    text = repr((VERSION, jver, backend, kind, norm_key))
    return hashlib.sha256(text.encode()).hexdigest()


def path_for(kind: str, norm_key) -> str:
    return os.path.join(_DIR, kind + "-" + digest(kind, norm_key) + _SUFFIX)


# ------------------------------------------------------------------ store

def store(kind: str, norm_key, compiled, extra: Optional[Dict] = None):
    """Serialize one compiled executable (plus its telemetry sidecars)
    under its digest. Failures are logged and swallowed — persistence
    must never take down the step that compiled."""
    if not ACTIVE:
        return False
    try:
        from jax.experimental.serialize_executable import serialize
        blob, in_tree, out_tree = serialize(compiled)
        jver, backend = _env()
        payload = {
            "version": VERSION,
            "jax": jver,
            "backend": backend,
            "kind": kind,
            "key": repr(norm_key),
            "blob": blob,
            "in_tree": in_tree,
            "out_tree": out_tree,
            # the devices it was compiled for: jax binds a deserialized
            # executable to EVERY device of the backend unless told
            "device_ids": [d.id for d in
                           compiled.runtime_executable().local_devices()],
        }
        if extra:
            payload.update(extra)
        raw = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
        body = MAGIC + hashlib.sha256(raw).hexdigest().encode() + b"\n" + raw
        os.makedirs(_DIR, exist_ok=True)
        path = path_for(kind, norm_key)
        fd, tmp = tempfile.mkstemp(
            dir=_DIR, prefix=".tmp_" + os.path.basename(path))
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(body)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        _count("store")
        _prune_disk()
        return True
    except Exception as e:                      # pragma: no cover - env
        _LOG.warning("persistent executable cache: store failed for "
                     "%s: %s", kind, e)
        return False


def _prune_disk():
    """Oldest-mtime eviction down to FLAGS_executable_cache_disk_max_mb
    after each store (0 = unbounded)."""
    budget = _flags.flag_value("FLAGS_executable_cache_disk_max_mb")
    if not budget:
        return
    budget_bytes = int(budget) << 20
    try:
        entries = []
        for name in os.listdir(_DIR):
            if not name.endswith(_SUFFIX):
                continue
            p = os.path.join(_DIR, name)
            st = os.stat(p)
            entries.append((st.st_mtime, st.st_size, p))
        total = sum(e[1] for e in entries)
        entries.sort()
        while total > budget_bytes and entries:
            mtime, size, p = entries.pop(0)
            os.unlink(p)
            total -= size
    except OSError:
        pass


# ------------------------------------------------------------------- load

def load(kind: str, norm_key) -> Optional[Dict]:
    """Return the verified payload dict for a key, or None (miss or
    reject). Every integrity failure — short file, bad magic, torn
    write, checksum mismatch, version/backend/key drift — is a clean
    recompile with a logged reason, never a crash."""
    if not ACTIVE:
        return None
    path = path_for(kind, norm_key)
    try:
        with open(path, "rb") as f:
            body = f.read()
    except OSError:
        _count("miss")
        return None
    try:
        if not body.startswith(MAGIC):
            raise ValueError("bad magic (not a cache entry)")
        rest = body[len(MAGIC):]
        nl = rest.find(b"\n")
        if nl != 64:
            raise ValueError("malformed checksum header")
        expect = rest[:64].decode("ascii")
        raw = rest[65:]
        got = hashlib.sha256(raw).hexdigest()
        if got != expect:
            raise ValueError(
                f"checksum mismatch (recorded {expect[:12]}.., "
                f"computed {got[:12]}..) — torn or corrupted entry")
        payload = pickle.loads(raw)
        jver, backend = _env()
        if payload.get("version") != VERSION:
            raise ValueError(
                f"format version {payload.get('version')} != {VERSION}")
        if payload.get("jax") != jver:
            raise ValueError(
                f"jax version {payload.get('jax')} != {jver}")
        if payload.get("backend") != backend:
            raise ValueError(
                f"backend {payload.get('backend')} != {backend}")
        if payload.get("key") != repr(norm_key):
            raise ValueError("key repr mismatch (digest collision or "
                             "stale entry)")
    except Exception as e:
        _count("reject", reason=f"{os.path.basename(path)}: {e}; "
                                "recompiling")
        return None
    _count("hit")
    return payload


def make_runner(payload: Dict, jit_factory, kwargs: Optional[Dict] = None):
    """Rehydrate a loaded payload into the aot_compile runner shape:
    the deserialized executable serves concrete-array calls; tracer
    arguments fall back to a jit wrapper built ON DEMAND by
    `jit_factory` (a Compiled object cannot inline into an enclosing
    trace, but building the wrapper eagerly would bump the compile
    counters a warm load exists to avoid). Returns None when
    deserialization itself fails (payload from a device topology this
    process cannot load), which the caller treats as a miss."""
    import jax
    try:
        from jax.experimental.serialize_executable import \
            deserialize_and_load
        by_id = {d.id: d for d in jax.devices()}
        compiled = deserialize_and_load(
            payload["blob"], payload["in_tree"], payload["out_tree"],
            execution_devices=[by_id[i] for i in payload["device_ids"]])
    except Exception as e:
        _count("reject", reason=f"deserialize failed ({e}); recompiling")
        return None

    jit_cell = []

    def runner(*vals, _compiled=compiled, _kw=dict(kwargs or {}),
               _tracer=jax.core.Tracer):
        for v in vals:
            if isinstance(v, _tracer):
                if not jit_cell:
                    jit_cell.append(jit_factory())
                return jit_cell[0](*vals, **_kw)
        return _compiled(*vals)

    runner.memory_analysis_info = payload.get("mem")
    runner.cost_analysis_info = payload.get("cost")
    runner.persisted = True
    return runner


def sidecars(compiled_or_runner, cache=None, key=None) -> Dict:
    """Collect the telemetry sidecars to persist alongside a compiled
    executable: the aot_compile runner's captured analyses plus the
    cache entry's compiled-comm estimate."""
    extra = {}
    mem = getattr(compiled_or_runner, "memory_analysis_info", None)
    if mem:
        extra["mem"] = mem
    cost = getattr(compiled_or_runner, "cost_analysis_info", None)
    if cost:
        extra["cost"] = cost
    if cache is not None and key is not None:
        comm = cache.comm_info(key) if hasattr(cache, "comm_info") else None
        if comm:
            extra["comm"] = comm
    return extra


def renote(payload: Dict, stat: str, cache=None, key=None):
    """Re-attach persisted analyses to the in-memory cache entry and
    the telemetry logs so a warm load keeps its meters (budget/stats
    aggregate over note_executable; ExecCache entries price comm and
    FLOPs per execution)."""
    mem = payload.get("mem")
    cost = payload.get("cost")
    comm = payload.get("comm")
    if cache is not None and key is not None:
        if mem and hasattr(cache, "note_memory"):
            cache.note_memory(key, mem)
        if cost and hasattr(cache, "note_cost"):
            cache.note_cost(key, cost)
        if comm and hasattr(cache, "note_comm"):
            cache.note_comm(key, comm)
    if mem and _OBS.MEM:
        from ..observability import memory as _memtel
        _memtel.note_executable(stat, key, dict(mem, persisted=True))
    if cost and _OBS.COMPUTE:
        from ..observability import compute as _comptel
        _comptel.note_executable(stat, key, dict(cost, persisted=True))
