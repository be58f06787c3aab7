"""Device / place management.

TPU-native analog of the reference's place + device manager
(paddle/phi/common/place.h, paddle/phi/backends/device_manager.h:134,
python/paddle/device/__init__.py:284 set_device). Devices are PJRT devices
enumerated by JAX; "TPUPlace(i)" maps to jax.devices('tpu')[i].

This module is also the ONE place that answers "is this a TPU"
(`is_tpu`), "do Pallas kernels compile or interpret" (`pallas_interpret`),
"what are this chip's peaks" (`chip_peaks`) and "where does the compile
cache live" (`enable_compile_cache`). Nothing else in the package tests
the backend string or keeps a peak.
"""
from __future__ import annotations

import glob
import os
from typing import NamedTuple

import jax


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def get_device_id(self):
        return self.device_id


class TPUPlace(Place):
    device_type = "tpu"


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class CustomPlace(Place):
    def __init__(self, dev_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = dev_type


_current_device = None


def _default_device_str() -> str:
    backend = jax.default_backend()
    return f"{backend}:0" if backend != "cpu" else "cpu"


def set_device(device: str):
    """paddle.device.set_device analog ('tpu:0', 'cpu')."""
    global _current_device
    _current_device = device
    return get_device_place(device)


def get_device() -> str:
    return _current_device or _default_device_str()


def get_device_place(device: str = None) -> Place:
    device = device or get_device()
    if device == "cpu":
        return CPUPlace()
    if ":" in device:
        kind, idx = device.split(":")
    else:
        kind, idx = device, 0
    if kind == "tpu":
        return TPUPlace(int(idx))
    return CustomPlace(kind, int(idx))


def jax_device(place: Place = None):
    """Resolve a Place to a jax Device object. No place = the default
    backend's device 0; a TPUPlace names a chip that must exist (no
    clamping: TPUPlace(3) on a one-chip host is an error, not chip 0)."""
    if place is None:
        return jax.devices()[0]
    if isinstance(place, CPUPlace):
        return jax.devices("cpu")[0]
    devs = jax.devices(place.device_type)
    if not 0 <= place.device_id < len(devs):
        raise ValueError(
            f"{place!r}: this process sees {len(devs)} "
            f"{place.device_type} device(s)")
    return devs[place.device_id]


def place_of(value) -> Place:
    try:
        dev = next(iter(value.devices()))
    except Exception:
        return get_device_place()
    if dev.platform == "tpu":
        return TPUPlace(dev.id)
    if dev.platform == "cpu":
        return CPUPlace()
    return CustomPlace(dev.platform, dev.id)


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    """True when this host has TPU chips for the installed libtpu to
    drive. Reads PCI ids, so it starts no backend and takes no chip."""
    return tpu_chips_on_host() > 0


# ------------------------------------------------------------ is this a TPU

def is_tpu() -> bool:
    """THE backend predicate: every "compile for the chip or not"
    decision in the package (flash gate, Pallas mode, trial runner)
    asks here. Starts the backend, like any first use of JAX."""
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Mode of every ``pl.pallas_call`` in ops/pallas: Mosaic-compiled on
    a TPU, always; the interpreter anywhere else, because no other
    backend can compile the kernels — the mode of the CPU test suite."""
    return not is_tpu()


_GOOGLE_PCI_VENDOR = "0x1ae0"


def tpu_chips_on_host() -> int:
    """Accelerator functions of Google's PCI vendor id on this host.
    For a parent process that must know whether its children would
    contend for chips without opening one itself (launch/main.py)."""
    n = 0
    for path in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            with open(path) as f:
                n += f.read().strip() == _GOOGLE_PCI_VENDOR
        except OSError:
            continue
    return n


# --------------------------------------------------------------- chip peaks

class ChipPeaks(NamedTuple):
    flops: float    # dense bf16 FLOP/s per chip
    membw: float    # HBM bytes/s per chip


# Published per-chip peaks (Google Cloud TPU documentation, one page per
# generation), keyed by the exact `device_kind` string the runtime
# reports — read from libtpu's own topology descriptions, e.g.
# get_topology_desc("tpu", "v5e:2x2").devices[0].device_kind. Exact keys:
# "TPU v5" is a v5p and must not answer for "TPU v5 lite" (a v5e).
CHIP_PEAKS = {
    "TPU v2": ChipPeaks(45e12, 700e9),
    "TPU v3": ChipPeaks(123e12, 900e9),
    "TPU v4": ChipPeaks(275e12, 1228e9),
    "TPU v5 lite": ChipPeaks(197e12, 819e9),
    "TPU v5": ChipPeaks(459e12, 2765e9),
    "TPU v6 lite": ChipPeaks(918e12, 1640e9),
}


def chip_peaks(device_kind: str = None) -> ChipPeaks:
    """Peaks of `device_kind` (default: this process's device 0). A
    device that is not in the table is an error, never a default."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(CHIP_PEAKS)}. Add its row to CHIP_PEAKS in "
            "paddle_tpu/_core/device.py with the source") from None


# ------------------------------------------------- persistent compile cache

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point
    that touches the chip (chip_smoke.py, benchmarks/run.py,
    __graft_entry__.py) — not at package import, so library users and
    tier-1 keep JAX's defaults. Returns the directory in use.

    Where JAX_COMPILATION_CACHE_DIR is set JAX already reads it and no
    code names another directory. Where it is not, the cache goes to one
    fixed git-ignored path in the checkout: the path is part of the
    cache key, so it is never derived from a pid, a time or a temp name.

    Every executable is cached (minimum compile time 0, not JAX's 1 s):
    the eager fusion window compiles dozens of sub-second programs per
    model, which together are most of a cold eager start.

    Metadata is part of the key (JAX leaves it out by default): the
    `op_name` of a compiled instruction carries the stage the program
    gave it (`models/stages.py`) and a device trace is read by it, so an
    executable cached from a program with other scopes is another
    program, though its computation is the same.

    It also registers the recorder of program-building
    (`observability/programs.py`): from here on every trace, lowering,
    compile and cache load of the process is a span, and
    `observability.stats()["programs"]` says which program missed."""
    from ..observability import programs
    programs.register()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
