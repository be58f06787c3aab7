"""Published peaks of one chip, keyed by the exact `device_kind` JAX reports.

The benchmark keeps its own table (the program has one in
`paddle_tpu/_core/device.CHIP_PEAKS`): the yardstick may not move with the
program. A device that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip. "TPU v5 lite" is what
libtpu calls a v5e (`get_topology_desc("tpu", "v5e:2x2").devices[0]
.device_kind`); "TPU v5" is a v5p and must not answer for it.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float        # dense bfloat16 FLOP/s of one chip
    hbm_bytes_s: float  # HBM bytes/s of one chip
    hbm_bytes: float    # HBM capacity of one chip


PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9, 16e9),
}


def peaks_of(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmarks/peaks.py (known: {sorted(PEAKS)}); add its row "
            "with the source") from None
