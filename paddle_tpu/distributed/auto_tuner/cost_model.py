"""Analytic cost/memory models for parallel-config search
(distributed/auto_tuner/cost_model.py, memory_cost_model.py analogs),
parameterized for TPU: MXU-bound compute, ICI collective bandwidth,
per-chip HBM."""
from __future__ import annotations

from typing import Dict

from ..._core.device import CHIP_PEAKS

# default hardware model (one v5e chip): tunable via the config dict
_DEFAULTS = dict(
    chip_flops=CHIP_PEAKS["TPU v5 lite"].flops,   # bf16 FLOP/s per chip
    hbm_bytes=16e9,             # per chip
    ici_bandwidth=4.5e10,       # bytes/s per link, ring
    mfu=0.4,
)


def _cfg(config: Dict):
    c = dict(_DEFAULTS)
    c.update({k: v for k, v in config.items() if k in c})
    return c


def estimate_memory(config: Dict) -> float:
    """Per-chip training memory (bytes) for a decoder LLM under the given
    parallel config: params/grads/optimizer-state split over mp*pp(*ZeRO),
    activations split over dp/mp with remat reducing to layer boundaries."""
    h = config.get("hidden_size", 1024)
    L = config.get("num_layers", 24)
    v = config.get("vocab_size", 50304)
    s = config.get("seq_len", 1024)
    b = config.get("micro_batch_size", 1)
    dp = config.get("dp_degree", 1)
    mp = config.get("mp_degree", 1)
    pp = config.get("pp_degree", 1)
    zero = config.get("sharding_stage", 0)
    recompute = config.get("recompute", True)

    # a measured parameter count beats the decoder-LLM formula
    n_params = config.get("n_params") or (12 * L * h * h + 2 * v * h)
    shard = mp * pp * (dp if zero >= 1 else 1)
    # bf16 params + fp32 master/m/v (16 bytes/param when ZeRO shards all)
    param_bytes = n_params * 2 / (mp * pp)
    opt_bytes = n_params * 14 / shard
    act_per_layer = s * b * h * (2 if recompute else 34)
    act_bytes = act_per_layer * (L / pp) / max(mp, 1)
    return param_bytes + opt_bytes + act_bytes


def estimate_step_cost(config: Dict) -> float:
    """Predicted seconds/step: max(compute, comm) per pipeline stage plus
    bubble overhead."""
    c = _cfg(config)
    h = config.get("hidden_size", 1024)
    L = config.get("num_layers", 24)
    v = config.get("vocab_size", 50304)
    s = config.get("seq_len", 1024)
    gb = config.get("global_batch_size", 8)
    dp = config.get("dp_degree", 1)
    mp = config.get("mp_degree", 1)
    pp = config.get("pp_degree", 1)
    micro = config.get("pp_microbatches", 2 * pp)

    n_params = config.get("n_params") or (12 * L * h * h + 2 * v * h)
    flops = 6 * gb * s * n_params    # fwd+bwd matmul FLOPs (6N rule)
    compute_t = flops / (dp * mp * pp) / (c["chip_flops"] * c["mfu"])
    # dp grad allreduce (ring) + mp per-layer allreduce volumes
    dp_comm = 2 * n_params * 2 * (dp - 1) / dp / c["ici_bandwidth"] \
        if dp > 1 else 0.0
    mp_comm = (4 * L * gb / dp * s * h * 2 * (mp - 1) / mp
               / c["ici_bandwidth"]) if mp > 1 else 0.0
    bubble = (pp - 1) / max(micro, 1)
    return (max(compute_t, mp_comm) * (1 + bubble)) + dp_comm
