"""Pallas TPU kernel layer.

This is the TPU-native replacement for two reference subsystems at once:
the dynloaded CUDA flash-attention library
(paddle/phi/backends/dynload/flashattn.cc) and the hand-fused CUDA kernels
under paddle/phi/kernels/fusion/gpu (fused_attention, fused_rms_norm,
swiglu, rope). Instead of NVRTC/CINN codegen, hot ops are written directly
against the TPU memory hierarchy (HBM -> VMEM -> MXU/VPU) with
jax.experimental.pallas. On a TPU every kernel is Mosaic-compiled; off a TPU
the same kernels run in the Pallas interpreter (`_core.device.
pallas_interpret`), which keeps them testable on the CPU mesh, and
tests/test_tpu_aot_compile.py runs the real TPU compiler on them in tier-1.
`stream_mix` (the mixing of several residual streams, models/mla_moe.py) is
imported as a module: `read_in`, `write_back`; so is `grouped_matmul` (the
grouped products of the dropless experts path, ops/moe.py): `grouped_dot`;
and `delta_rule` (the chunk terms of the gated delta rule, forward and
backward, ops/linear_attention.py): `chunk_terms`, `taken`.
"""
from .flash_attention import flash_attention, mha_forward, mha_seq_major
from .fused import rms_norm, swiglu, fused_rotary_position_embedding

__all__ = [
    "flash_attention", "mha_forward", "mha_seq_major", "rms_norm", "swiglu",
    "fused_rotary_position_embedding",
]
