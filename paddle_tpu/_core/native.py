"""ctypes bridge to the native runtime library (csrc/).

The reference keeps its runtime substrate in C++ (SURVEY.md §2a/§2e); here
the TPU-native equivalents — TCPStore rendezvous, auto-growth best-fit
host allocator, prefetching data feed, flag registry — live in
csrc/libpaddle_tpu_rt.so, built on first use with g++ (no pybind: plain C
ABI + ctypes, the same dlopen shape as the reference's custom-device
plugin ABI, paddle/phi/backends/device_ext.h:96)."""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import sys
import threading

_LOG = logging.getLogger(__name__)

_lib = None
_lib_lock = threading.Lock()
_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_BUILD = os.path.join(_CSRC, "build")
_SO = os.path.join(_BUILD, "libpaddle_tpu_rt.so")
_STAMP = os.path.join(_BUILD, "sources.sha256")
_BUILD_LOG = os.path.join(_BUILD, "build.log")


def _sources_digest() -> str:
    """Digest of what the build is made FROM: every .cc/.h under csrc/
    (derived, so build.sh and this list cannot diverge), build.sh and the
    interpreter the extension is built against. csrc/build/ is git-ignored
    and may be a copy from another machine, so staleness is decided by
    content, never by an mtime inside it."""
    import glob
    h = hashlib.sha256(repr(sys.version_info[:2]).encode())
    for p in sorted(glob.glob(os.path.join(_CSRC, "*.cc"))
                    + glob.glob(os.path.join(_CSRC, "*.h"))
                    + [os.path.join(_CSRC, "build.sh")]):
        h.update(os.path.basename(p).encode())
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _needs_build() -> bool:
    try:
        with open(_STAMP) as f:
            return f.read().strip() != _sources_digest() \
                or not os.path.exists(_SO)
    except OSError:
        return True


def _build():
    env = dict(os.environ)
    env["PT_PYTHON"] = sys.executable   # ABI-match the extension build
    proc = subprocess.run(["sh", os.path.join(_CSRC, "build.sh")],
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          text=True, env=env)
    os.makedirs(_BUILD, exist_ok=True)
    with open(_BUILD_LOG, "w") as f:
        f.write(proc.stdout)
    if proc.returncode:
        raise RuntimeError(
            f"csrc/build.sh exited {proc.returncode}:\n{proc.stdout[-2000:]}")
    with open(_STAMP, "w") as f:
        f.write(_sources_digest())


def _bind(lib):
    c = ctypes
    lib.pt_last_error.restype = c.c_char_p
    lib.pt_store_server_start.restype = c.c_void_p
    lib.pt_store_server_start.argtypes = [c.c_int]
    lib.pt_store_server_port.restype = c.c_int
    lib.pt_store_server_port.argtypes = [c.c_void_p]
    lib.pt_store_server_stop.argtypes = [c.c_void_p]
    lib.pt_store_client_connect.restype = c.c_void_p
    lib.pt_store_client_connect.argtypes = [c.c_char_p, c.c_int, c.c_int]
    lib.pt_store_client_close.argtypes = [c.c_void_p]
    lib.pt_store_set.restype = c.c_int
    lib.pt_store_set.argtypes = [c.c_void_p, c.c_char_p, c.c_void_p,
                                 c.c_uint32]
    lib.pt_store_get.restype = c.c_int64
    lib.pt_store_get.argtypes = [c.c_void_p, c.c_char_p, c.c_void_p,
                                 c.c_int64, c.c_uint32]
    lib.pt_store_wait.restype = c.c_int
    lib.pt_store_wait.argtypes = [c.c_void_p, c.c_char_p, c.c_uint32]
    lib.pt_store_del.restype = c.c_int
    lib.pt_store_del.argtypes = [c.c_void_p, c.c_char_p]
    lib.pt_store_add.restype = c.c_int64
    lib.pt_store_add.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]

    lib.pt_alloc_create.restype = c.c_void_p
    lib.pt_alloc_create.argtypes = [c.c_uint64]
    lib.pt_alloc_destroy.argtypes = [c.c_void_p]
    lib.pt_alloc_malloc.restype = c.c_void_p
    lib.pt_alloc_malloc.argtypes = [c.c_void_p, c.c_uint64]
    lib.pt_alloc_free.restype = c.c_int
    lib.pt_alloc_free.argtypes = [c.c_void_p, c.c_void_p]
    lib.pt_alloc_stats.argtypes = [c.c_void_p,
                                   c.POINTER(c.c_uint64),
                                   c.POINTER(c.c_uint64)]

    lib.pt_feed_create.restype = c.c_void_p
    lib.pt_feed_create.argtypes = [c.c_char_p, c.c_int64, c.c_int64,
                                   c.c_int, c.c_uint64, c.c_int]
    lib.pt_feed_num_windows.restype = c.c_int64
    lib.pt_feed_num_windows.argtypes = [c.c_void_p]
    lib.pt_feed_next.restype = c.c_int
    lib.pt_feed_next.argtypes = [c.c_void_p, c.c_void_p]
    lib.pt_feed_destroy.argtypes = [c.c_void_p]

    lib.pt_flag_define.restype = c.c_int
    lib.pt_flag_define.argtypes = [c.c_char_p, c.c_char_p]
    lib.pt_flag_set.restype = c.c_int
    lib.pt_flag_set.argtypes = [c.c_char_p, c.c_char_p]
    lib.pt_flag_get.restype = c.c_int64
    lib.pt_flag_get.argtypes = [c.c_char_p, c.c_char_p, c.c_int64]

    lib.ptcc_create.restype = c.c_void_p
    lib.ptcc_create.argtypes = [c.c_int, c.c_int]
    lib.ptcc_listen_port.restype = c.c_int
    lib.ptcc_listen_port.argtypes = [c.c_void_p]
    lib.ptcc_connect.restype = c.c_int
    lib.ptcc_connect.argtypes = [c.c_void_p, c.c_char_p]
    lib.ptcc_all_reduce.restype = c.c_int
    lib.ptcc_all_reduce.argtypes = [c.c_void_p, c.c_void_p, c.c_int64,
                                    c.c_int, c.c_int]
    lib.ptcc_reduce_scatter.restype = c.c_int
    lib.ptcc_reduce_scatter.argtypes = [c.c_void_p, c.c_void_p,
                                        c.c_void_p, c.c_int64, c.c_int,
                                        c.c_int]
    lib.ptcc_all_gather.restype = c.c_int
    lib.ptcc_all_gather.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p,
                                    c.c_int64]
    lib.ptcc_broadcast.restype = c.c_int
    lib.ptcc_broadcast.argtypes = [c.c_void_p, c.c_void_p, c.c_int64,
                                   c.c_int]
    lib.ptcc_send.restype = c.c_int
    lib.ptcc_send.argtypes = [c.c_void_p, c.c_void_p, c.c_int64, c.c_int]
    lib.ptcc_recv.restype = c.c_int
    lib.ptcc_recv.argtypes = [c.c_void_p, c.c_void_p, c.c_int64, c.c_int]
    lib.ptcc_barrier.restype = c.c_int
    lib.ptcc_barrier.argtypes = [c.c_void_p]
    lib.ptcc_destroy.argtypes = [c.c_void_p]

    lib.pt_plugin_load.restype = c.c_char_p
    lib.pt_plugin_load.argtypes = [c.c_char_p]
    lib.pt_plugin_device_count.restype = c.c_int
    lib.pt_plugin_device_count.argtypes = [c.c_char_p]
    lib.pt_plugin_malloc.restype = c.c_void_p
    lib.pt_plugin_malloc.argtypes = [c.c_char_p, c.c_int, c.c_uint64]
    lib.pt_plugin_free.restype = c.c_int
    lib.pt_plugin_free.argtypes = [c.c_char_p, c.c_int, c.c_void_p]
    lib.pt_plugin_memcpy.restype = c.c_int
    lib.pt_plugin_memcpy.argtypes = [c.c_char_p, c.c_int, c.c_void_p,
                                     c.c_void_p, c.c_uint64, c.c_int]
    lib.pt_plugin_mem_stats.restype = c.c_int
    lib.pt_plugin_mem_stats.argtypes = [c.c_char_p, c.c_int,
                                        c.POINTER(c.c_uint64),
                                        c.POINTER(c.c_uint64)]
    lib.pt_plugin_stream_check.restype = c.c_int
    lib.pt_plugin_stream_check.argtypes = [c.c_char_p, c.c_int]
    lib.pt_plugin_ccl_all_reduce.restype = c.c_int
    lib.pt_plugin_ccl_all_reduce.argtypes = [c.c_char_p, c.c_int,
                                             c.c_void_p, c.c_uint64,
                                             c.c_int, c.c_int]
    lib.pt_custom_op_load.restype = c.c_int
    lib.pt_custom_op_load.argtypes = [c.c_char_p, c.c_char_p]
    lib.pt_custom_op_call.restype = c.c_int
    lib.pt_custom_op_call.argtypes = [c.c_char_p,
                                      c.POINTER(c.c_void_p),
                                      c.POINTER(c.c_int64), c.c_int,
                                      c.c_void_p, c.c_int64]
    return lib


def get_lib() -> ctypes.CDLL:
    """Load (building if stale) the native runtime. Raises when the
    toolchain is unavailable: every caller needs the library."""
    global _lib
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is None:
            if _needs_build():
                _build()
            _lib = _bind(ctypes.CDLL(_SO))
    return _lib


def last_error() -> str:
    return get_lib().pt_last_error().decode()


def bind_jit(lib):
    """ctypes signatures for the C++ jit layer (bound lazily: only the
    inference path needs them)."""
    import ctypes as c
    if getattr(lib, "_jit_bound", False):
        return lib
    lib.pt_jit_open.restype = c.c_void_p
    lib.pt_jit_open.argtypes = [c.c_char_p]
    lib.pt_jit_num_params.restype = c.c_int
    lib.pt_jit_num_params.argtypes = [c.c_void_p]
    lib.pt_jit_param_name.restype = c.c_char_p
    lib.pt_jit_param_name.argtypes = [c.c_void_p, c.c_int]
    lib.pt_jit_param_dtype.restype = c.c_char_p
    lib.pt_jit_param_dtype.argtypes = [c.c_void_p, c.c_int]
    lib.pt_jit_param_shape.restype = c.c_int
    lib.pt_jit_param_shape.argtypes = [c.c_void_p, c.c_int,
                                       c.POINTER(c.c_int64), c.c_int]
    lib.pt_jit_param_data.restype = c.c_void_p
    lib.pt_jit_param_data.argtypes = [c.c_void_p, c.c_int,
                                      c.POINTER(c.c_uint64)]
    lib.pt_jit_program.restype = c.c_void_p
    lib.pt_jit_program.argtypes = [c.c_void_p, c.POINTER(c.c_uint64)]
    lib.pt_jit_close.argtypes = [c.c_void_p]
    lib._jit_bound = True
    return lib


_HOST_POOL = None


def host_pool():
    """Process-wide native host memory pool (csrc/allocator.cc), sized
    by FLAGS_host_alloc_chunk_kb at first use — the python face of the
    reference's host AllocatorFacade."""
    global _HOST_POOL
    if _HOST_POOL is None:
        from . import flags
        lib = get_lib()
        _HOST_POOL = lib.pt_alloc_create(
            int(flags.flag_value("FLAGS_host_alloc_chunk_kb")) * 1024)
    return _HOST_POOL


_EAGER_CORE = None
_EAGER_CORE_TRIED = False
_EAGER_CORE_WHY_NOT = "not asked for yet"


def get_eager_core():
    """The eager hot-path CPython extension (csrc/eager_core.cc):
    dispatch-key construction, backward in-degree BFS, and the NATIVE
    RECORD CORE — interned shape/dtype atoms, the record-time out-aval
    cache (C key build + lookup), the sig-entry intern, and the
    trace-stable skeleton matcher ``skel_record`` that replays one
    recorded op per C call (lazy.py arms/validates the skeleton and
    stands alone in pure python when this returns None). Returns None
    when unavailable: the python record path is a supported path
    (correct, slower per op), so a failed build is logged once and
    `eager_core_status()` says which path this process is on; set
    PT_DISABLE_NATIVE_EAGER=1 to force the python path. Consumers
    cache their own resolution (dispatch._EAGER_CORE, lazy._NC) so
    the fallback tests (tests/test_record_fastpath.py) can force either
    prong in-process."""
    global _EAGER_CORE, _EAGER_CORE_TRIED, _EAGER_CORE_WHY_NOT
    if _EAGER_CORE_TRIED:
        return _EAGER_CORE
    _EAGER_CORE_TRIED = True
    if os.environ.get("PT_DISABLE_NATIVE_EAGER") == "1":
        _EAGER_CORE_WHY_NOT = "PT_DISABLE_NATIVE_EAGER=1"
        return None
    try:
        get_lib()   # builds csrc (including the extension)
        import importlib.util
        so = os.path.join(_BUILD, "pt_eager_core.so")
        spec = importlib.util.spec_from_file_location("pt_eager_core", so)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _EAGER_CORE = mod
    except Exception as e:   # any build/load failure selects the python path
        _EAGER_CORE_WHY_NOT = (f"{type(e).__name__}: {e}; build log: "
                               f"{_BUILD_LOG}")
        _LOG.warning("native eager core unavailable, recording in python: "
                     "%s", _EAGER_CORE_WHY_NOT)
    return _EAGER_CORE


def eager_core_status() -> str:
    """Which eager record path this process is on: "native", or
    "python (<why the native core is not loaded>)"."""
    if get_eager_core() is not None:
        return "native"
    return f"python ({_EAGER_CORE_WHY_NOT})"
