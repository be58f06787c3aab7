"""Global runtime flag registry.

TPU-native analog of the reference's exported-flag registry
(paddle/common/flags.h:242-291 `PHI_DEFINE_EXPORTED_*`, ~187 flags in
flags.cc) with env-var override and get/set from Python
(python/paddle/base/framework.py:132,157 set_flags/get_flags).
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict, Iterable, Union

_LOCK = threading.RLock()
_REGISTRY: Dict[str, "Flag"] = {}


class Flag:
    __slots__ = ("name", "default", "value", "type", "help")

    def __init__(self, name: str, default: Any, help: str = ""):
        self.name = name
        self.default = default
        self.type = type(default)
        self.help = help
        env = os.environ.get(name)
        self.value = _parse(env, self.type) if env is not None else default


def _parse(text: str, ty: type):
    if ty is bool:
        return text.lower() in ("1", "true", "yes", "on")
    return ty(text)


def define_flag(name: str, default: Any, help: str = "") -> Flag:
    with _LOCK:
        if name in _REGISTRY:
            return _REGISTRY[name]
        flag = Flag(name, default, help)
        _REGISTRY[name] = flag
        return flag


def get_flags(flags: Union[str, Iterable[str]]) -> Dict[str, Any]:
    if isinstance(flags, str):
        flags = [flags]
    with _LOCK:
        out = {}
        for name in flags:
            key = _resolve(name)
            if key not in _REGISTRY:
                raise ValueError(f"unknown flag: {name}")
            out[name] = _REGISTRY[key].value
        return out


def set_flags(flags: Dict[str, Any]) -> None:
    with _LOCK:
        # resolve + parse EVERYTHING before mutating anything: a typo'd
        # name or unparseable value mid-dict must not leave earlier
        # flags written to the registry with their watcher-cached gates
        # (STATIC_CHECKS_ACTIVE, observability _state) never updated
        updates = []
        for name, value in flags.items():
            key = _resolve(name)
            if key not in _REGISTRY:
                raise ValueError(f"unknown flag: {name}")
            flag = _REGISTRY[key]
            parsed = _parse(value, flag.type) \
                if isinstance(value, str) and flag.type is not str \
                else flag.type(value)
            updates.append((key, flag, parsed))
        fire = []
        for key, flag, parsed in updates:
            flag.value = parsed
            for cb in _WATCHERS.get(key, ()):
                fire.append((cb, parsed))
    # callbacks run outside the registry lock (they may read other flags)
    for cb, value in fire:
        cb(value)


# flag-change watchers: subsystems that cache a flag into a module-level
# fast gate (observability ACTIVE, profiler host-tracer level) register
# here so set_flags keeps the cached copy coherent without the hot path
# paying a registry lookup per event.
_WATCHERS: Dict[str, list] = {}


def watch_flag(name: str, callback) -> None:
    """Invoke `callback(value)` now and after every set_flags update of
    `name` (alias-resolved)."""
    with _LOCK:
        key = _resolve(name)
        if key not in _REGISTRY:
            raise ValueError(f"unknown flag: {name}")
        _WATCHERS.setdefault(key, []).append(callback)
        value = _REGISTRY[key].value
    callback(value)


# reference-name aliases: the subset of the reference's ~187 PHI flags
# (paddle/common/flags.cc) with a live TPU-native equivalent maps here
# so get/set accept the reference spelling. Flags whose job is absorbed
# by XLA/PJRT (allocator fractions, cudnn autotune, stream pools) have
# no entry — silently accepting them would be cosmetic.
_ALIASES = {
    "FLAGS_fuse_parameter_memory_size": "FLAGS_fuse_buffer_size_mb",
    "FLAGS_pg_timeout": "FLAGS_comm_task_timeout_s",
}


def _resolve(name: str) -> str:
    return _ALIASES.get(name, name)


def flag_value(name: str):
    return _REGISTRY[_resolve(name)].value


# Core flags (analogs of the reference's most-used ones).
define_flag("FLAGS_check_nan_inf", False,
            "Scan op outputs for NaN/Inf after each eager op (debug).")

# Watcher-kept gate (STATIC_CHECKS_ACTIVE pattern): the lazy record
# path captures per-op source provenance while the NaN scan is armed,
# so a FloatingPointError names the producing op's file:line even with
# the sanitizer off.
NAN_CHECK_ACTIVE = False


def _sync_nan_check_gate(value):
    global NAN_CHECK_ACTIVE
    NAN_CHECK_ACTIVE = bool(value)


watch_flag("FLAGS_check_nan_inf", _sync_nan_check_gate)
define_flag("FLAGS_call_stack_level", 1,
            "Error message verbosity: 0 brief, 1 python stack, 2 full.")
define_flag("FLAGS_eager_compile_cache_size", 4096,
            "Max cached compiled executables for eager op dispatch "
            "(0 = unlimited).")
define_flag("FLAGS_log_compiles", False, "Log XLA compilations of eager ops.")
define_flag("FLAGS_seed", 0, "Default global random seed.")
define_flag("FLAGS_tpu_matmul_precision", "default",
            "Matmul precision: default|high|highest.")
define_flag("FLAGS_benchmark", False, "Block on every eager op (for timing).")
define_flag("FLAGS_apply_ir_passes", True,
            "run the IR pass pipeline when compiling static Programs")

# ---- distributed runtime knobs (each read by a live consumer)
define_flag("FLAGS_fuse_buffer_size_mb", 25,
            "DataParallel gradient-fusion bucket size in MB "
            "(reducer comm_buffer_size default).")
define_flag("FLAGS_comm_task_timeout_s", 1800.0,
            "CommTaskManager watchdog timeout per collective (the "
            "reference's FLAGS_pg_timeout role).")
define_flag("FLAGS_comm_idle_poll_limit", 10,
            "Native collective engine: consecutive 60s zero-progress "
            "polls before a transfer is declared dead.")
define_flag("FLAGS_tcp_store_timeout_s", 300.0,
            "TCPStore client connect/get timeout in seconds.")
define_flag("FLAGS_launch_max_restarts", 0,
            "Launcher: restarts-with-rerank before giving up "
            "(elastic manager behavior).")

define_flag("FLAGS_lazy_max_segment_ops", 256,
            "Lazy fusion window: pending ops per segment before a forced "
            "flush (caps XLA program size and peak trace memory).")

# ---- compile / memory knobs
define_flag("FLAGS_recompute_segments", 2,
            "Default segment count for the recompute program pass "
            "(jax.checkpoint regions).")
define_flag("FLAGS_amp_dtype", "bfloat16",
            "Default auto-cast dtype for amp O1/O2 (bf16 is the TPU "
            "tensor-core dtype the way fp16 is CUDA's).")

# ---- io / misc
define_flag("FLAGS_dataloader_num_workers", 0,
            "Default DataLoader worker count when not passed.")
define_flag("FLAGS_profiler_dir", "",
            "Directory for chrome-trace exports ('' = cwd).")
define_flag("FLAGS_dataloader_prefetch_factor", 2,
            "Default DataLoader prefetch batches per worker.")

# ---- SOT / lazy capture knobs (jit/sot, _core/lazy)
define_flag("FLAGS_sot_cache_entries", 8,
            "Max guarded fast-path entries kept per SotFunction.")
define_flag("FLAGS_sot_inline_depth", 8,
            "Max recursive bytecode-inline depth in the SOT executor.")
define_flag("FLAGS_sot_step_budget", 2_000_000,
            "Max interpreted bytecode steps per SOT frame before the "
            "frame falls back to native execution.")
define_flag("FLAGS_sot_guard_size_cap", 64,
            "Largest container/array value-guarded by SOT; larger "
            "inputs refuse the fast path instead.")
define_flag("FLAGS_lazy_enable", True,
            "Kill-switch for the lazy fusion window: when false, "
            "lazy_guard() becomes a no-op and ops dispatch eagerly.")
define_flag("FLAGS_eager_fusion", True,
            "Ambient fusion window: plain dygraph code (no lazy_guard) "
            "records into a segment that runs as one cached XLA program "
            "at the next sync point. The eager hot-path default; false "
            "restores strict per-op dispatch.")
define_flag("FLAGS_executable_cache_capacity", 1024,
            "LRU capacity for each compiled-executable cache (lazy "
            "segment/bwd/fused-step + eager fwd/bwd); 0 = unbounded.")
define_flag("FLAGS_lazy_donate_inputs", True,
            "Donate lazy-segment input buffers whose backing tensor is "
            "dead or overwritten at flush (XLA reuses them in place).")
define_flag("FLAGS_record_fast_path", True,
            "Trace-stable record fast path: after a sealed segment's "
            "signature memo proves the op stream repeats, later "
            "iterations replay the retained op skeleton — matching "
            "(op, attrs, input wiring) position-for-position and "
            "skipping aval inference / cache-key construction / attrs "
            "copying per recorded op, re-binding only external input "
            "payloads. Any mismatch falls back to the full record path "
            "for the rest of the segment; mesh-epoch bumps, replans, "
            "relevant set_flags and mid-segment in-place swaps "
            "invalidate the skeleton. Off = the exact pre-existing "
            "per-op record behavior.")
define_flag("FLAGS_step_replay_after", 3,
            "Whole-step driver promotion threshold: after this many "
            "consecutive clean skeleton replays of a sealed segment "
            "(runner already cached), the seal path promotes to a "
            "step plan — one driver call validates liveness/donation "
            "and executes the cached executable directly, skipping "
            "signature memo probing and flush bookkeeping; recording "
            "itself drops per-op validation to wiring identity checks. "
            "Any mismatch demotes that step to per-op skeleton replay "
            "and re-arms the streak. 0 disables promotion.")
define_flag("FLAGS_executable_cache_dir", "",
            "Persistent compiled-executable cache directory ('' = "
            "off): sealed-segment / fused-step / optimizer runners are "
            "serialized (jax AOT) under an epoch-normalized signature "
            "digest with checksum + version/backend stamps, and cache "
            "misses consult disk before lower().compile() — process "
            "restart, elastic re-plan and serving cold-start load "
            "instead of recompiling. Memory/cost analyses persist "
            "alongside so warm loads keep their meters.")
define_flag("FLAGS_executable_cache_disk_max_mb", 512,
            "Persistent executable cache disk budget in MB: after each "
            "store, oldest-mtime entries are pruned until the cache "
            "directory fits (0 = unbounded).")
define_flag("FLAGS_async_flush", False,
            "Hand sealed lazy segments to a single-worker flush "
            "executor: compile+execute launch off the Python thread "
            "while eager recording continues; results materialize "
            "through pending-value placeholders and worker errors "
            "re-raise at the next sync point (_value read, backward, "
            "drain). Off = the exact pre-existing synchronous path.")
define_flag("FLAGS_prefetch_depth", 2,
            "Device-feed double-buffer depth: DevicePrefetcher (and the "
            "bench input path) keeps this many upcoming batches' "
            "host->device transfers in flight so step N+1's inputs "
            "land while step N executes (0/1 = no overlap).")
define_flag("FLAGS_optimizer_donate_params", True,
            "Donate old parameter/state buffers into the fused optimizer "
            "update so XLA updates them in place (no per-step copy).")

# ---- AMP / GradScaler defaults (amp/grad_scaler.py)
define_flag("FLAGS_amp_init_loss_scaling", 65536.0,
            "GradScaler default init_loss_scaling.")
define_flag("FLAGS_amp_incr_every_n_steps", 2000,
            "GradScaler default good-step interval before scale growth.")
define_flag("FLAGS_amp_decr_every_n_nan_or_inf", 1,
            "GradScaler default bad-step count before scale shrink.")

# ---- debug nets
define_flag("FLAGS_check_nan_inf_level", 0,
            "NaN/Inf scan action: 0 raise, 1 warn and continue.")
define_flag("FLAGS_static_checks", "off",
            "Program sanitizer level: 'off' (no cost), 'warn' (run the "
            "paddle_tpu.analysis checkers over every flushed lazy "
            "segment, IR pass, reshard lowering, pipeline build and "
            "SOT capture, emitting StaticCheckWarning), 'error' (raise "
            "StaticCheckError on any violation), 'fix' (repair the "
            "mechanical classes — missing note_inplace, unsafe "
            "donation, dead captures — in place, re-check, and warn "
            "for whatever could not be repaired).")
define_flag("FLAGS_dead_capture_min_flops", 1024,
            "Dead-capture lint floor: segments whose dead ops waste "
            "fewer estimated FLOPs than this AND fewer output bytes "
            "than FLAGS_dead_capture_min_bytes are not reported "
            "(scalar bookkeeping the user cannot act on; 0 reports "
            "everything). Fix-mode pruning honors the same floor.")
define_flag("FLAGS_dead_capture_min_bytes", 4096,
            "Dead-capture lint floor companion: minimum wasted output "
            "bytes before a dead capture below the FLOPs floor is "
            "still reported.")
define_flag("FLAGS_numerics_seed_log2max", 4.0,
            "Numerics plane input range seed: segment inputs are "
            "assumed bounded by 2^this (|x| <= 16 by default — "
            "normalized activations/params). The range lattice "
            "(analysis/numerics.py) propagates from here; raising it "
            "makes the overflow_risk checker more pessimistic.")
define_flag("FLAGS_numerics_accum_k", 16384,
            "accum_dtype lint floor: minimum reduction length K before "
            "a matmul/reduction accumulating directly into fp16/bf16 "
            "is flagged (sqrt(K)*eps relative error reaches ~0.5 for "
            "bf16 at K=16384; 0 flags every low-precision reduction).")
define_flag("FLAGS_numerics_min_snr_db", 20.0,
            "quant_error_budget gate: minimum statically-priced "
            "quantization SNR (dB) per gradient bucket before an "
            "int8/fp8 collective plan passes pre-flight.")
define_flag("FLAGS_sharding_replicated_min_bytes", 1 << 20,
            "Sharding perf lint (analysis/sharding_prop.py): minimum "
            "redundant bytes (tensor size x (mesh size - 1)) before a "
            "fully-replicated input to an otherwise-sharded program is "
            "flagged (small scalars/stats are legitimately replicated; "
            "0 flags everything).")
define_flag("FLAGS_sharding_comm_min_bytes", 1024,
            "Sharding perf lint: minimum total priced compiled-"
            "collective traffic per execution before the ranked "
            "comm-hotspot summary diagnostic is attached to the "
            "report (0 reports any non-zero traffic).")
# off-synonym values the hot-path gates (lazy record/flush, PassManager)
# test membership against — keeps '0'/'false' spellings from paying the
# analysis import or even a str() call per recorded op. The lowercase
# frozenset is the single source of truth (check_mode() normalizes
# against it); STATIC_CHECKS_OFF adds the common case/type variants so
# the raw-value gate needs no normalization, as a frozenset because the
# membership test runs once per recorded op.
STATIC_CHECKS_OFF_WORDS = frozenset(
    ("off", "0", "false", "none", "disable", "disabled", ""))
STATIC_CHECKS_OFF = frozenset(
    w for word in STATIC_CHECKS_OFF_WORDS
    for w in (word, word.capitalize(), word.upper())
) | {0, False, None}

# Cached module-level gate for the record/flush hot paths: True iff
# FLAGS_static_checks is not an off-spelling. A watch_flag callback
# keeps it coherent (env init and every set_flags land here), so the
# per-recorded-op gate is one attribute read instead of a registry
# resolve + frozenset test per op.
STATIC_CHECKS_ACTIVE = False


def _sync_static_checks_gate(value):
    global STATIC_CHECKS_ACTIVE
    STATIC_CHECKS_ACTIVE = value not in STATIC_CHECKS_OFF


watch_flag("FLAGS_static_checks", _sync_static_checks_gate)

# ---- fault tolerance / resilience (distributed/resilience)
define_flag("FLAGS_fault_inject", "",
            "Deterministic fault-injection plan ('' = off, zero cost): "
            "'seed=N;site[@occ]=kind[(arg)][:prob];...' where site is a "
            "named injection point (store::get, pg::init, "
            "comm::all_reduce, segment::compile, exec::oom, step::N, "
            "ckpt::save; trailing * wildcards match) and kind is fail "
            "| die | delay(s) | stuck(s) | oom (synthetic XLA "
            "RESOURCE_EXHAUSTED at the execute sites). See "
            "distributed/resilience/faults.py.")
define_flag("FLAGS_retry_max_attempts", 3,
            "RetryPolicy default attempt budget for transient failures "
            "(TCPStore ops, process-group bring-up, host collectives, "
            "checkpoint I/O).")
define_flag("FLAGS_retry_backoff_s", 0.05,
            "RetryPolicy base backoff delay in seconds (exponential "
            "with deterministic jitter).")
define_flag("FLAGS_elastic_max_retries", 2,
            "ElasticStep: rollback-and-rerun attempts per training step "
            "before the failure propagates.")
define_flag("FLAGS_checkpoint_keep", 3,
            "CheckpointManager: verified checkpoint generations kept on "
            "disk (older generations pruned after each save; load "
            "auto-falls-back to the newest verified older generation "
            "when the latest fails its checksum).")
define_flag("FLAGS_checkpoint_interval_steps", 0,
            "AdaptiveTrainer: auto-checkpoint every N step boundaries "
            "through the retention manager (0 = off). Bounds the "
            "preemption-recovery badput to one interval without a "
            "call-site convention; a trainer built with an explicit "
            "checkpoint_every overrides the flag.")
define_flag("FLAGS_elastic_grow_chunk_kb", 512,
            "grow_world state broadcast: TCPStore chunk size in KiB for "
            "the survivor->joiner state transfer (each chunk is "
            "sha256-checksummed; the whole payload is verified before "
            "unpickling).")

# Cached module-level gate for the fault-injection hot-path hooks
# (store ops, collectives, segment compile, elastic steps): True iff
# FLAGS_fault_inject names a plan. Same watcher-kept-coherent pattern
# as STATIC_CHECKS_ACTIVE — the off path pays one attribute read and
# never imports the resilience package.
FAULT_INJECT_ACTIVE = False


def _sync_fault_inject_gate(value):
    global FAULT_INJECT_ACTIVE
    FAULT_INJECT_ACTIVE = bool(str(value).strip())


watch_flag("FLAGS_fault_inject", _sync_fault_inject_gate)

# Cached module-level gate for the async flush pipeline (the
# STATIC_CHECKS_ACTIVE pattern): True iff FLAGS_async_flush is on. The
# per-flush gate is one attribute read; the executor module is never
# imported while this is False.
ASYNC_FLUSH_ACTIVE = False


def _sync_async_flush_gate(value):
    global ASYNC_FLUSH_ACTIVE
    ASYNC_FLUSH_ACTIVE = bool(value)


watch_flag("FLAGS_async_flush", _sync_async_flush_gate)

# ---- kernels / pallas
define_flag("FLAGS_flash_interpret", False,
            "Off a TPU, let the compiled GPT trainer use the Pallas flash "
            "kernel in interpret mode instead of the einsum path (CPU "
            "mesh tests, multichip dryrun). No effect on a TPU, where "
            "the kernel is always Mosaic-compiled.")
define_flag("FLAGS_moe_capacity_factor", 1.25,
            "Default MoE gating capacity factor.")

# ---- distributed transport / pipeline
define_flag("FLAGS_pg_native_transport", True,
            "Allow the native socket collective engine; false forces "
            "the pure-python store-relay fallback on every rank.")
define_flag("FLAGS_pipeline_stash_warn_mb", 0,
            "Warn when a pipeline runtime's activation stash exceeds "
            "this many MB (0 = off).")
define_flag("FLAGS_pipeline_max_inflight", 0,
            "Hard cap on stashed in-flight micro-batches per pipeline "
            "rank (0 = unlimited; exceeding raises).")
define_flag("FLAGS_dp_broadcast_params", True,
            "DataParallel broadcasts parameters from rank 0 at wrap "
            "time so replicas start identical.")
define_flag("FLAGS_elastic_heartbeat_interval_s", 0.5,
            "ElasticManager heartbeat/watch interval in seconds.")
define_flag("FLAGS_elastic_eviction_debounce", 3,
            "ElasticManager: consecutive missed/stale heartbeat probes "
            "before a node is evicted from membership (the PR-6 drill "
            "showed 8 cold XLA compiles starve every peer's heartbeat "
            "thread — one slow scan must not publish a member::leave "
            "epoch; 1 restores the old evict-on-first-miss behavior).")
define_flag("FLAGS_watchdog_check_interval_s", 1.0,
            "CommTaskManager watchdog poll interval in seconds.")
define_flag("FLAGS_auto_tuner_max_trials", 0,
            "Auto-tuner default measured-trial count (0 = cost-model "
            "ranking only).")

# ---- compile caches
define_flag("FLAGS_dy2static_cache_limit", 64,
            "Max cached (signature -> executable) entries per "
            "to_static function before oldest eviction.")

# ---- inference defaults (inference/Config)
define_flag("FLAGS_inference_opt_level", 2,
            "Default inference Config optimization level.")
define_flag("FLAGS_inference_donate_inputs", False,
            "Default inference Config input-donation setting.")

# ---- profiler
define_flag("FLAGS_host_tracer_level", 1,
            "Host tracer detail: 0 off, 1 ops, 2 ops+python ranges.")
define_flag("FLAGS_profiler_max_events", 1_000_000,
            "Host tracer event-buffer cap (oldest dropped beyond it).")
define_flag("FLAGS_profiler_fused_runtime", False,
            "Profiler keeps the fusion window ON while recording: no "
            "per-op host events (op::*), the trace instead carries the "
            "fused-runtime spans (segment flush/compile/execute, fused "
            "step, optimizer) the steady-state hot path actually runs.")

# ---- observability (paddle_tpu.observability)
define_flag("FLAGS_distributed_telemetry", False,
            "Cross-rank telemetry plane: each rank periodically "
            "publishes a compact frame (metrics/span-histogram deltas, "
            "step index, mesh epoch, recent span events) through the "
            "TCPStore under __telem/ keys, and rank 0 merges them into "
            "a cluster step table (per-rank skew, straggler flags), a "
            "comm-overlap report, and a merged per-rank chrome trace. "
            "Off = one module-level check per step, zero registry and "
            "zero store work (tests/test_distributed_telemetry.py).")
define_flag("FLAGS_distributed_telemetry_interval", 1,
            "Telemetry plane: steps between frame publications (1 = "
            "every step boundary).")
define_flag("FLAGS_distributed_telemetry_events", 4096,
            "Telemetry plane: span events buffered per rank between "
            "frame publications (oldest dropped beyond it).")
define_flag("FLAGS_telemetry_straggler_factor", 1.25,
            "Step-table straggler flag: a rank is flagged when its "
            "per-step time exceeds the step's cross-rank median by "
            "this factor (and by FLAGS_telemetry_straggler_min_us).")
define_flag("FLAGS_telemetry_straggler_min_us", 1000.0,
            "Step-table straggler flag: minimum absolute skew "
            "(slowest minus median, us) before a rank is flagged — "
            "filters factor-trips on micro-second steps.")
define_flag("FLAGS_telemetry_postmortem_grace_s", 3.0,
            "Distributed flight postmortem: how long rank 0 polls the "
            "store for survivor rings before writing the aggregated "
            "report with whatever arrived.")
define_flag("FLAGS_observability", False,
            "Collect runtime metrics (counters/gauges/histograms) at "
            "the fused-runtime instrumentation points; off = the hot "
            "paths pay one module-level check and zero registry work.")
define_flag("FLAGS_memory_telemetry", False,
            "Byte-domain telemetry plane (observability/memory.py): "
            "live-buffer census with birth-site provenance at the "
            "Tensor-creation and lazy bind choke points, per-compile "
            "XLA memory_analysis cached on the executable-cache entry, "
            "donation savings accounting, and OOM postmortems at the "
            "execute sites. Off = one module-level check per choke "
            "point, zero census and zero registry work "
            "(tests/test_memory_telemetry.py).")
define_flag("FLAGS_compute_telemetry", False,
            "Compute-efficiency telemetry plane (observability/"
            "compute.py): per-executable XLA cost_analysis (FLOPs, "
            "bytes accessed, transcendentals) captured once per compile "
            "at the three fused-runtime compile sites and cached on the "
            "executable-cache entry, per-execution FLOP counters "
            "(compute.flops.{segment,fused_step,optimizer}), MFU/"
            "roofline columns in the budget tool, and source-attributed "
            "device profiles (each recorded op's lowering wrapped in a "
            "jax.named_scope carrying its paddle file:line). Off = one "
            "module-level check per site, zero registry and zero "
            "analysis work (tests/test_compute_telemetry.py).")
define_flag("FLAGS_device_peak_flops", 0.0,
            "Per-chip peak FLOP/s the MFU column divides by. 0 = "
            "the device's published peak: a TPU from the device_kind "
            "table in _core/device.py (an unknown kind is an error), "
            "the CPU backend from a nominal cores x 2.5 GHz x 16 "
            "fp32-FLOPs/cycle AVX2-FMA envelope (documented in README "
            "— CPU MFU is a relative meter, not an absolute one).")
define_flag("FLAGS_device_peak_membw", 0.0,
            "Per-chip peak memory bandwidth in bytes/s for the "
            "roofline ridge point (peak_flops / peak_membw). 0 = "
            "the device's published peak: a TPU from the device_kind "
            "table in _core/device.py, the CPU backend from a nominal "
            "25.6 GB/s two-channel DDR4 envelope.")
define_flag("FLAGS_memory_budget_bytes", 0,
            "Per-device HBM budget in bytes for the cross-rank memory "
            "column: budget --distributed flags the rank whose peak is "
            "nearest this budget (0 = unknown; the highest absolute "
            "peak is flagged instead).")
define_flag("FLAGS_goodput", False,
            "Goodput plane (observability/goodput.py): per-process "
            "wall-clock attribution ledger partitioning the job "
            "timeline into exclusive states (productive execute, "
            "compile, input wait, comm wait, host gap, checkpoint "
            "I/O, recovery, idle) with bucket additivity asserted, a "
            "bounded step-time ring feeding anomaly detection, and a "
            "hang watchdog that captures stacks + dumps the flight "
            "ring when no step progress happens within "
            "FLAGS_goodput_hang_factor x the median step time. Off = "
            "one module-level check per probe, zero ring mutations "
            "(tests/test_goodput.py).")
define_flag("FLAGS_goodput_hang_factor", 8.0,
            "Goodput hang watchdog: the job is declared hung when no "
            "probe-visible progress happens within this factor x the "
            "rolling median step time (floored by "
            "FLAGS_goodput_hang_min_s).")
define_flag("FLAGS_goodput_hang_min_s", 1.0,
            "Goodput hang watchdog: floor on the dynamic timeout so "
            "micro-second steps cannot arm a hair-trigger deadline "
            "over a legitimate recompile.")
define_flag("FLAGS_goodput_hang_poll_s", 0.25,
            "Goodput hang watchdog: watchdog-thread poll interval in "
            "seconds (bounds detection latency beyond the timeout).")
define_flag("FLAGS_goodput_spike_factor", 3.0,
            "Goodput anomaly detection: a step slower than this "
            "factor x the rolling median counts "
            "goodput.anomalies.step_spike (same factor watches loss "
            "divergence via note_loss).")
define_flag("FLAGS_goodput_ring", 128,
            "Goodput step-time ring capacity (rolling median window "
            "for the spike and hang thresholds).")
define_flag("FLAGS_flight_max_dumps", 32,
            "Flight-recorder dump retention: per-rank cap on "
            "flight_*.txt files kept in FLAGS_flight_recorder_dir "
            "(oldest pruned first after each dump; rank-aware so one "
            "rank's churn cannot evict another rank's postmortem; "
            "0 = unlimited).")
define_flag("FLAGS_flight_recorder", False,
            "Keep a bounded ring buffer of recent runtime events "
            "(spans, flushes, cache decisions) and dump a readable "
            "report on enforce errors, failed flushes, and sanitizer "
            "error-mode trips.")
define_flag("FLAGS_flight_recorder_capacity", 512,
            "Flight-recorder ring size (events kept).")
define_flag("FLAGS_flight_recorder_dir", "",
            "Directory for flight-record dumps ('' = FLAGS_profiler_dir "
            "or cwd).")
define_flag("FLAGS_monitor", False,
            "Live monitoring plane (observability/timeseries.py): a "
            "daemon sampler records counter rates (steps/s, tokens/s, "
            "compiles, cache hit rate), byte/census gauges, goodput "
            "fractions and per-step MFU into bounded per-series rings "
            "every FLAGS_monitor_interval_s, feeding the /metrics "
            "exporter and the online regression watchdog. Off = one "
            "module-level check per step hook, zero registry work, no "
            "sampler thread, no bound port (tests/test_monitor.py).")
define_flag("FLAGS_monitor_interval_s", 1.0,
            "Monitor sampler period in seconds (each tick appends one "
            "timestamped sample per series).")
define_flag("FLAGS_monitor_port", 0,
            "Monitor HTTP exporter port serving /metrics (Prometheus "
            "text exposition), /healthz, /snapshot and "
            "/timeseries?name=. 0 = no HTTP endpoint (sampler rings "
            "still record for in-process readers).")
define_flag("FLAGS_monitor_host", "127.0.0.1",
            "Monitor exporter bind address. Loopback by default — "
            "bind a routable interface explicitly to let an external "
            "Prometheus scrape the job.")
define_flag("FLAGS_monitor_ring", 512,
            "Monitor per-series ring capacity (samples kept per "
            "series; at the default 1 s interval ~8.5 min of trend).")
define_flag("FLAGS_monitor_regression_factor", 1.5,
            "Online regression watchdog: a headline series (step "
            "duration, tokens/s, goodput fraction) deviating past "
            "this factor from its EWMA baseline, sustained for "
            "FLAGS_monitor_regression_steps consecutive samples, "
            "counts monitor.regressions and leaves a flight note "
            "with baseline-vs-current evidence.")
define_flag("FLAGS_monitor_regression_steps", 5,
            "Consecutive deviating samples required before the "
            "regression watchdog fires (debounce against one-off "
            "recompiles or input stalls).")
define_flag("FLAGS_monitor_deep_capture_steps", 0,
            "When > 0, a fired regression arms a one-shot deep "
            "capture: the profiler (fused_runtime) traces the next K "
            "steps and the trace is dumped beside the flight ring "
            "(subject to the same rank-aware retention).")

# ---- model-surface defaults
define_flag("FLAGS_onnx_opset", 13,
            "Minimum default-domain opset version for ONNX export "
            "(raised per-op when an emitted op needs newer).")
define_flag("FLAGS_hapi_log_freq", 1,
            "hapi ProgBarLogger default step logging frequency.")
define_flag("FLAGS_asp_mask_algo", "mask_1d",
            "Default ASP 2:4 pruning mask algorithm.")
define_flag("FLAGS_quant_bits", 8,
            "Default quantization bit width for observers/QAT.")

# ---- sparse
define_flag("FLAGS_sparse_validate_indices", False,
            "Bounds-check sparse indices at construction (debug).")

# ---- IR
define_flag("FLAGS_ir_pass_disable", "",
            "Comma-separated IR pass names to skip in the pipeline.")
define_flag("FLAGS_enable_auto_layout", False,
            "Run the NHWC auto-layout pass in the static pipeline "
            "(transpose-sunk NHWC convs, auto_layout_pass.cc role).")

# ---- remaining runtime knobs
define_flag("FLAGS_rpc_timeout_s", 180.0,
            "Default rpc_sync/rpc_async call timeout in seconds.")
define_flag("FLAGS_conv_data_format", "NCHW",
            "Default conv/pool data layout when data_format is not "
            "passed (the DataLayout default of the reference).")
define_flag("FLAGS_launch_log_dir", "log",
            "Default --log_dir for paddle.distributed.launch.")
define_flag("FLAGS_host_alloc_chunk_kb", 256,
            "Native host allocator pool chunk size in KB "
            "(csrc/allocator.cc pt_alloc_create).")
define_flag("FLAGS_zb_w_extra_delay", 0,
            "Extra micro-batches of weight-grad (W) deferral in the "
            "ZeroBubble schedule beyond the warmup depth.")
define_flag("FLAGS_amp_level", "O1",
            "Default auto_cast level when not passed.")
define_flag("FLAGS_allow_pickle_load", False,
            "Permit loading legacy pickle parameter files (pickle can "
            "execute code; PT_ALLOW_PICKLE_LOAD=1 is the env spelling).")
define_flag("FLAGS_jit_save_meta", True,
            "jit.save writes the .pdmeta named-IO sidecar used by the "
            "inference AnalysisPredictor.")
define_flag("FLAGS_ckpt_strict_load", True,
            "Distributed checkpoint load fails on missing/unexpected "
            "keys instead of loading the intersection.")
define_flag("FLAGS_guard_log", False,
            "Log SOT guard-set contents and fast-path misses (debug).")



