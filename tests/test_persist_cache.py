"""Persistent executable cache (FLAGS_executable_cache_dir) — disk
roundtrip, integrity rejection, and counter/hygiene contracts.

Contracts under test:

- an ExecCache miss consults disk BEFORE lower().compile(): a process
  that already stored a segment reloads it without bumping
  ``compiles.segment`` (the warm-restart core, drilled cross-process
  by `test_cross_process_warm_start`);
- every integrity failure — truncation, flipped payload bytes, bad
  magic, a wrong format version — is a CLEAN recompile with a
  ``cache.persist.reject`` counter and a logged reason, never a crash,
  and the recompile immediately re-stores a good entry;
- ``cache.persist.{hit,miss,store}`` count what they say;
- the mtime pruner keeps the directory under
  FLAGS_executable_cache_disk_max_mb.
"""
import glob
import hashlib
import os
import pickle

import numpy as np
import pytest

import paddle_tpu as paddle
from conftest import with_flag
from paddle_tpu._core import lazy, persist
from paddle_tpu.observability import metrics


@pytest.fixture
def checks_off():
    with with_flag("FLAGS_static_checks", "off"):
        yield


def _counter(name):
    return metrics.counter(name).value


def _chain(x, n=6):
    y = x
    for _ in range(n):
        y = y * 1.02 + 0.002
    return np.asarray(y._value)


def _fresh_compile(x, n=6):
    """Clear the in-memory runner cache so the next seal either loads
    from disk or compiles."""
    lazy.clear_segment_cache()
    return _chain(x, n)


def _entries(d):
    return sorted(glob.glob(os.path.join(d, "*" + persist._SUFFIX)))


def test_store_then_warm_load_skips_compile(checks_off, tmp_path):
    with with_flag("FLAGS_observability", True), \
            with_flag("FLAGS_executable_cache_dir", str(tmp_path)):
        x = paddle.to_tensor(np.full((8, 8), 1.5, "float32"))
        s0 = _counter("cache.persist.store")
        ref = _fresh_compile(x)
        assert _counter("cache.persist.store") > s0, "nothing persisted"
        assert _entries(str(tmp_path)), "no .ptxc entry on disk"
        c0 = _counter("compiles.segment")
        h0 = _counter("cache.persist.hit")
        np.testing.assert_array_equal(_fresh_compile(x), ref)
        assert _counter("cache.persist.hit") > h0, "disk never consulted"
        assert _counter("compiles.segment") == c0, \
            "warm load still recompiled"


def test_cold_miss_counts(checks_off, tmp_path):
    with with_flag("FLAGS_observability", True), \
            with_flag("FLAGS_executable_cache_dir", str(tmp_path)):
        x = paddle.to_tensor(np.full((4, 4), 2.5, "float32"))
        m0 = _counter("cache.persist.miss")
        _fresh_compile(x)
        assert _counter("cache.persist.miss") > m0


def _corrupt_each(entries, mutate):
    for p in entries:
        with open(p, "rb") as f:
            body = f.read()
        with open(p, "wb") as f:
            f.write(mutate(body))


def _reject_drill(tmp_path, x, ref, mutate, label):
    """Corrupt every entry with `mutate`, then re-run from a cold
    in-memory cache: the load must reject (counted), recompile cleanly
    and re-store a verified entry."""
    entries = _entries(str(tmp_path))
    assert entries, "drill needs stored entries"
    _corrupt_each(entries, mutate)
    r0 = _counter("cache.persist.reject")
    c0 = _counter("compiles.segment")
    np.testing.assert_array_equal(_fresh_compile(x), ref), label
    assert _counter("cache.persist.reject") > r0, \
        f"{label}: corruption not rejected"
    assert _counter("compiles.segment") > c0, \
        f"{label}: rejected entry did not recompile"
    # the recompile re-stored a good entry: next cold run hits again
    h0 = _counter("cache.persist.hit")
    np.testing.assert_array_equal(_fresh_compile(x), ref)
    assert _counter("cache.persist.hit") > h0, \
        f"{label}: recompile did not heal the entry"


def test_truncated_entry_recompiles(checks_off, tmp_path):
    with with_flag("FLAGS_observability", True), \
            with_flag("FLAGS_executable_cache_dir", str(tmp_path)):
        x = paddle.to_tensor(np.full((8, 8), 0.75, "float32"))
        ref = _fresh_compile(x)
        _reject_drill(tmp_path, x, ref,
                      lambda b: b[:max(8, len(b) // 3)], "truncated")


def test_flipped_payload_bytes_recompile(checks_off, tmp_path):
    with with_flag("FLAGS_observability", True), \
            with_flag("FLAGS_executable_cache_dir", str(tmp_path)):
        x = paddle.to_tensor(np.full((8, 8), 0.25, "float32"))
        ref = _fresh_compile(x)

        def flip(b):
            mid = len(b) // 2
            return b[:mid] + bytes([b[mid] ^ 0xFF]) + b[mid + 1:]

        _reject_drill(tmp_path, x, ref, flip, "checksum")


def test_bad_magic_recompiles(checks_off, tmp_path):
    with with_flag("FLAGS_observability", True), \
            with_flag("FLAGS_executable_cache_dir", str(tmp_path)):
        x = paddle.to_tensor(np.full((4, 8), 1.25, "float32"))
        ref = _fresh_compile(x)
        _reject_drill(tmp_path, x, ref,
                      lambda b: b"NOTC1\n" + b[len(persist.MAGIC):],
                      "magic")


def test_wrong_version_recompiles(checks_off, tmp_path):
    """A payload stamped with a future format version (checksum made
    VALID again, so only the version gate can catch it) rejects with a
    reason instead of being unpickled into the wrong shape."""
    with with_flag("FLAGS_observability", True), \
            with_flag("FLAGS_executable_cache_dir", str(tmp_path)):
        x = paddle.to_tensor(np.full((8, 4), 1.75, "float32"))
        ref = _fresh_compile(x)

        def restamp(b):
            raw = b[len(persist.MAGIC) + 65:]
            payload = pickle.loads(raw)
            payload["version"] = persist.VERSION + 99
            raw = pickle.dumps(payload,
                               protocol=pickle.HIGHEST_PROTOCOL)
            return (persist.MAGIC
                    + hashlib.sha256(raw).hexdigest().encode()
                    + b"\n" + raw)

        _reject_drill(tmp_path, x, ref, restamp, "version")


def test_reject_flight_note_and_log(checks_off, tmp_path, caplog):
    import logging
    from paddle_tpu.observability import flight
    with with_flag("FLAGS_observability", True), \
            with_flag("FLAGS_flight_recorder", True), \
            with_flag("FLAGS_executable_cache_dir", str(tmp_path)):
        x = paddle.to_tensor(np.full((8, 8), 3.5, "float32"))
        _fresh_compile(x)
        _corrupt_each(_entries(str(tmp_path)), lambda b: b[:16])
        with caplog.at_level(logging.WARNING,
                             logger="paddle_tpu._core.persist"):
            _fresh_compile(x)
        assert any("recompiling" in r.getMessage()
                   for r in caplog.records)
        notes = [e for e in flight.entries()
                 if e[1] == "cache.persist" and e[2] == "reject"]
        assert notes, "reject left no flight-recorder note"


def test_disk_budget_prunes_oldest(checks_off, tmp_path):
    with with_flag("FLAGS_observability", True), \
            with_flag("FLAGS_executable_cache_dir", str(tmp_path)), \
            with_flag("FLAGS_executable_cache_disk_max_mb", 1):
        # distinct shapes -> distinct entries, until the budget evicts
        for i, shape in enumerate([(4, 4), (8, 8), (16, 16), (4, 16)]):
            x = paddle.to_tensor(np.full(shape, 1.0 + i, "float32"))
            _fresh_compile(x)
        total = sum(os.path.getsize(p) for p in _entries(str(tmp_path)))
        assert total <= 1 << 20, "pruner exceeded the disk budget"


def test_inactive_without_dir(checks_off, tmp_path):
    """Both flags off: zero disk traffic (the off-freeze contract)."""
    assert not persist.ACTIVE
    x = paddle.to_tensor(np.full((8, 8), 4.5, "float32"))
    _fresh_compile(x)
    assert not _entries(str(tmp_path))
    assert persist.load("segment", ("anything",)) is None


# --------------------------------------- cross-process warm start

_WARM_WORKER = """
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.observability import metrics

paddle.set_flags({"FLAGS_static_checks": "off",
                  "FLAGS_observability": True,
                  "FLAGS_executable_cache_dir": sys.argv[1]})
x = paddle.to_tensor(np.full((16, 16), 1.5, "float32"))
y = x
for _ in range(10):
    y = y * 1.002 + 0.002
np.asarray(y._value)
counters = metrics.snapshot()["counters"]
print(json.dumps(
    {"compiles": {k: v for k, v in counters.items()
                  if k.startswith("compiles.")
                  and not k.startswith("compiles.bytes.")},
     "persist": {k: v for k, v in counters.items()
                 if k.startswith("cache.persist.")}}))
"""


def test_cross_process_warm_start(tmp_path):
    """The elastic warm-start contract (joiner/hot-spare half of the
    grow drill): a SECOND fresh process pointed at the first process's
    FLAGS_executable_cache_dir reconstructs its executables from disk
    — cache.persist.hit > 0 and ZERO fresh compiles.* (the persist key
    is content-addressed over jax version + backend + MESH_EPOCH-zeroed
    segment key, so distinct processes on one host/toolchain collide
    on purpose)."""
    import json
    import subprocess
    import sys

    cache = tmp_path / "shared_cache"
    cache.mkdir()
    worker = tmp_path / "warm_worker.py"
    worker.write_text(_WARM_WORKER)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    def run_once(tag):
        out = subprocess.run(
            [sys.executable, str(worker), str(cache)],
            capture_output=True, text=True, env=env, timeout=300)
        assert out.returncode == 0, f"{tag}: {out.stderr[-2000:]}"
        return json.loads([ln for ln in out.stdout.splitlines()
                           if ln.startswith("{")][-1])

    cold = run_once("cold")
    assert sum(cold["compiles"].values()) > 0, \
        "cold process compiled nothing — the drill proves nothing"
    assert cold["persist"].get("cache.persist.store", 0) > 0

    warm = run_once("warm")
    assert warm["persist"].get("cache.persist.hit", 0) > 0, \
        "second process never loaded the survivors' executables"
    assert sum(warm["compiles"].values()) == 0, \
        f"warm process recompiled: {warm['compiles']}"
