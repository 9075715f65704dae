"""Centralized environment-variable configuration knobs.

TPU-native analog of the reference's env plane: knob names are centralized in
``horovod/common/common.h:64-90`` and parsed in ``BackgroundThreadLoop``
(``horovod/common/operations.cc:416-513``) and ``common/utils/env_parser.cc``.

We keep the ``HOROVOD_`` prefix for the knobs that have direct parity meaning so a
Horovod user can carry their environment over unchanged, and add ``HOROVOD_TPU_``
knobs for TPU-only behavior.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional

# --- knob names (parity: common.h:64-90) ---
HOROVOD_FUSION_THRESHOLD = "HOROVOD_FUSION_THRESHOLD"
HOROVOD_CYCLE_TIME = "HOROVOD_CYCLE_TIME"
HOROVOD_TIMELINE = "HOROVOD_TIMELINE"
HOROVOD_TIMELINE_MARK_CYCLES = "HOROVOD_TIMELINE_MARK_CYCLES"
HOROVOD_AUTOTUNE = "HOROVOD_AUTOTUNE"
HOROVOD_AUTOTUNE_LOG = "HOROVOD_AUTOTUNE_LOG"
HOROVOD_AUTOTUNE_WARMUP_SAMPLES = "HOROVOD_AUTOTUNE_WARMUP_SAMPLES"
HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE = "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"
HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES = "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"
HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE = "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE"
HOROVOD_STALL_CHECK_DISABLE = "HOROVOD_STALL_CHECK_DISABLE"
HOROVOD_STALL_CHECK_TIME_SECONDS = "HOROVOD_STALL_CHECK_TIME_SECONDS"
HOROVOD_STALL_SHUTDOWN_TIME_SECONDS = "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"
HOROVOD_HIERARCHICAL_ALLREDUCE = "HOROVOD_HIERARCHICAL_ALLREDUCE"
HOROVOD_HIERARCHICAL_ALLGATHER = "HOROVOD_HIERARCHICAL_ALLGATHER"
# bounds the engine's builder cache (ResponseCache analog, engine._builder)
HOROVOD_CACHE_CAPACITY = "HOROVOD_CACHE_CAPACITY"
# (HOROVOD_BATCH_D2D_MEMCOPIES has no TPU analog — XLA owns device memcpy
# batching — and is intentionally not a knob here.)
HOROVOD_LOG_LEVEL = "HOROVOD_LOG_LEVEL"
# disables the per-op join round (ragged-batch Join support,
# operations.cc:1004-1040); set =1 to shave the metadata exchange off the
# eager hot path when no rank will ever run out of data early
HOROVOD_JOIN_DISABLE = "HOROVOD_JOIN_DISABLE"
HOROVOD_RANK = "HOROVOD_RANK"
HOROVOD_SIZE = "HOROVOD_SIZE"
HOROVOD_LOCAL_RANK = "HOROVOD_LOCAL_RANK"
HOROVOD_LOCAL_SIZE = "HOROVOD_LOCAL_SIZE"
HOROVOD_CROSS_RANK = "HOROVOD_CROSS_RANK"
HOROVOD_CROSS_SIZE = "HOROVOD_CROSS_SIZE"
HOROVOD_HOSTNAME = "HOROVOD_HOSTNAME"
HOROVOD_ELASTIC = "HOROVOD_ELASTIC"
HOROVOD_GLOO_RENDEZVOUS_ADDR = "HOROVOD_GLOO_RENDEZVOUS_ADDR"
HOROVOD_GLOO_RENDEZVOUS_PORT = "HOROVOD_GLOO_RENDEZVOUS_PORT"
HOROVOD_GLOO_TIMEOUT_SECONDS = "HOROVOD_GLOO_TIMEOUT_SECONDS"
HOROVOD_GLOO_IFACE = "HOROVOD_GLOO_IFACE"

# TPU-only knobs
HOROVOD_TPU_COORDINATOR = "HOROVOD_TPU_COORDINATOR"          # host:port of jax coordinator
HOROVOD_TPU_NUM_PROCESSES = "HOROVOD_TPU_NUM_PROCESSES"
HOROVOD_TPU_PROCESS_ID = "HOROVOD_TPU_PROCESS_ID"
# coordination-service failure detection (seconds); defaults are tighter in
# elastic mode so peer crashes surface quickly (core/backend.py init())
HOROVOD_TPU_HEARTBEAT_TIMEOUT = "HOROVOD_TPU_HEARTBEAT_TIMEOUT"
HOROVOD_TPU_SHUTDOWN_TIMEOUT = "HOROVOD_TPU_SHUTDOWN_TIMEOUT"
# coordinator-last teardown: how long rank 0 waits for peers'
# disconnect flags before shutting the coordination service
HOROVOD_TPU_SHUTDOWN_ORDER_TIMEOUT = "HOROVOD_TPU_SHUTDOWN_ORDER_TIMEOUT"
HOROVOD_TPU_DEBUG_CONSISTENCY = "HOROVOD_TPU_DEBUG_CONSISTENCY"
# steady-state metadata cache (the ResponseCache role for allgather sizes /
# alltoall splits, response_cache.h:45-102): after WARMUP identical blocking
# exchanges per name, the exchange goes fire-and-forget with a deferred
# consistency check at extract time; =0 disables (always block)
HOROVOD_TPU_META_CACHE = "HOROVOD_TPU_META_CACHE"
# grouped allreduce as ONE launch (pack+collective+unpack for every bucket
# in a single jitted program); =0 restores the per-bucket two-dispatch form
HOROVOD_TPU_SINGLE_LAUNCH = "HOROVOD_TPU_SINGLE_LAUNCH"
HOROVOD_TPU_META_CACHE_WARMUP = "HOROVOD_TPU_META_CACHE_WARMUP"
# step-capture replay (core/replay.py): record the dispatch stream between
# hvd.step_begin()/step_end() and, once the same signature repeats WARMUP
# times, service the whole step with one fused XLA launch; =0 disables
HOROVOD_TPU_STEP_REPLAY = "HOROVOD_TPU_STEP_REPLAY"
HOROVOD_TPU_STEP_REPLAY_WARMUP = "HOROVOD_TPU_STEP_REPLAY_WARMUP"
# metrics registry (horovod_tpu/metrics.py): =0 disables every instrument
# (lock-free no-ops on the dispatch hot path); FILE enables the periodic
# JSONL emitter; INTERVAL (seconds) paces the emitter/KV-publish/timeline-
# counter thread
HOROVOD_TPU_METRICS = "HOROVOD_TPU_METRICS"
HOROVOD_TPU_METRICS_FILE = "HOROVOD_TPU_METRICS_FILE"
HOROVOD_TPU_METRICS_INTERVAL = "HOROVOD_TPU_METRICS_INTERVAL"
# ZeRO-1 optimizer-state sharding default for optimizers constructed with
# sharded=None (DistributedEagerOptimizer): gradients sync via bucketed
# reduce-scatter + shard-local update + fused allgather instead of
# allreduce + replicated update (docs/sharded_optimizer.md). Also offered
# as an autotune categorical; resolved once per optimizer at state init.
HOROVOD_TPU_SHARD_OPTIMIZER = "HOROVOD_TPU_SHARD_OPTIMIZER"
# bucket-pipelined comm/compute overlap (ISSUE 6): how the fused-step
# builders order/split their per-bucket collectives. "off" = the PR 1
# serial chain (pack/reduce/unpack interleaved, one monolithic launch);
# "interleave" = one launch whose trace order is pack..., collective...,
# unpack... (collectives back-to-back, async-overlappable); "staged" =
# the replay engine splits the captured step into per-bucket sub-launches
# so bucket i's collective is in flight while the host dispatches bucket
# i+1's pack; "auto" (default) picks per (bytes, topology) — see
# Engine._overlap_mode. Also an autotune categorical ("overlap_pipeline").
HOROVOD_TPU_OVERLAP_PIPELINE = "HOROVOD_TPU_OVERLAP_PIPELINE"
# auto mode switches from "interleave" to "staged" when a step's gradient
# bytes reach this threshold (and the world has >1 rank)
HOROVOD_TPU_OVERLAP_STAGE_BYTES = "HOROVOD_TPU_OVERLAP_STAGE_BYTES"
# ZeRO-1 all-gather prefetch (ISSUE 6 tentpole): split the sharded step so
# the parameter all-gather of step N+1's params launches as its own leg
# under step N's tail, held by the engine across the step boundary and
# invalidated on world-version bumps exactly like replay; =0 keeps the
# fused rs->update->ag single launch. The split rides the STAGED schedule
# only (forced, or auto-resolved staged) — under off/interleave the gather
# stays inside the fused step program, the schedule replay sustains
HOROVOD_TPU_ZERO1_PREFETCH = "HOROVOD_TPU_ZERO1_PREFETCH"
# XLA latency-hiding scheduler as a supported knob (ISSUE 6 satellite,
# on the chip not measured, docs/roofline.md section 1): =1 appends
# --xla_tpu_enable_latency_hiding_scheduler=true to XLA_FLAGS before the
# first backend touch (loud WARNING + no-op if a jax backend already
# exists — XLA parses XLA_FLAGS at backend init, not at import)
HOROVOD_TPU_XLA_LHS = "HOROVOD_TPU_XLA_LHS"
# fault injection (horovod_tpu/faults.py, which imports this constant):
# a failpoint spec string; unset means every failpoint() marker is a
# no-op. Parsed by faults._arm_from_env at import.
HOROVOD_TPU_FAULTS = "HOROVOD_TPU_FAULTS"
# cross-rank collective tracing (horovod_tpu/trace.py): =0 disables the
# trace recorder entirely (engine.trace stays None — no per-dispatch
# locking, the HOROVOD_TPU_METRICS=0 discipline); RING bounds the
# in-memory event ring; INTERVAL (seconds) paces the trace-segment KV
# publisher; DUMP_DIR is where the watchdog's flight-recorder dump lands
HOROVOD_TPU_TRACE = "HOROVOD_TPU_TRACE"
HOROVOD_TPU_TRACE_RING = "HOROVOD_TPU_TRACE_RING"
HOROVOD_TPU_TRACE_INTERVAL = "HOROVOD_TPU_TRACE_INTERVAL"
HOROVOD_TPU_TRACE_DUMP_DIR = "HOROVOD_TPU_TRACE_DUMP_DIR"
# step-health layer (horovod_tpu/observability/, ISSUE 20): =0 leaves
# engine.health None — one is-None branch on the step path, nothing
# else. WINDOW/WARMUP shape the rolling median+MAD baselines, MAD_K is
# the spike threshold in MADs, DUMP_INTERVAL rate-limits automatic
# flight dumps (seconds), HBM toggles emitter-thread memory sampling.
HOROVOD_TPU_STEP_HEALTH = "HOROVOD_TPU_STEP_HEALTH"
HOROVOD_TPU_STEP_HEALTH_WINDOW = "HOROVOD_TPU_STEP_HEALTH_WINDOW"
HOROVOD_TPU_STEP_HEALTH_WARMUP = "HOROVOD_TPU_STEP_HEALTH_WARMUP"
HOROVOD_TPU_STEP_HEALTH_MAD_K = "HOROVOD_TPU_STEP_HEALTH_MAD_K"
HOROVOD_TPU_STEP_HEALTH_DUMP_INTERVAL = (
    "HOROVOD_TPU_STEP_HEALTH_DUMP_INTERVAL")
HOROVOD_TPU_HBM = "HOROVOD_TPU_HBM"
# collective watchdog (stall_inspector.py): seconds a collective may sit
# outstanding — or a peer heartbeat may lag — before the inspector aborts
# local collectives and raises HorovodInternalError so the elastic
# run-loop can recover. 0 (default) disables the watchdog; the warning
# thresholds alone then apply, preserving the legacy hang-forever behavior.
HOROVOD_TPU_COLLECTIVE_DEADLINE = "HOROVOD_TPU_COLLECTIVE_DEADLINE"
# elastic driver slot-failure backoff (elastic/driver.py): base seconds a
# repeatedly-failing slot is suspended before re-admission (doubles per
# strike); slots past HOROVOD_ELASTIC_SLOT_FAILURE_LIMIT are out for good
HOROVOD_ELASTIC_FAILURE_BACKOFF = "HOROVOD_ELASTIC_FAILURE_BACKOFF"
HOROVOD_ELASTIC_SLOT_FAILURE_LIMIT = "HOROVOD_ELASTIC_SLOT_FAILURE_LIMIT"
# topology-aware collective algorithm selection (ISSUE 10): which lowering
# every reduction/gather bucket gets. "auto" (default) picks per
# (bytes, topology) — tree (recursive doubling) for latency-bound small
# buckets on power-of-2 worlds, the hierarchical ICI/DCN ladder when the
# topology has a non-trivial slice decomposition, flat ring otherwise;
# "flat"/"tree"/"hierarchical" force one lowering everywhere (invalid
# forcings demote to flat with a one-time WARNING, never a crash). Also an
# autotune categorical ("collective_algo": env-resolved base vs flat).
HOROVOD_TPU_COLLECTIVE_ALGO = "HOROVOD_TPU_COLLECTIVE_ALGO"
# alltoall-specific algorithm forcing (ISSUE 17): the dispatch exchange
# has its own knob because its auto crossover is calibrated separately
# (an alltoall moves every byte once; a reduction moves ~2x) and because
# a dense job may want hierarchical reductions while pinning dispatch
# flat. "auto" (default) picks per (bytes, topology) with the calibrated
# alltoall threshold; "flat"/"hierarchical" force ("tree" is not a valid
# alltoall lowering and demotes with a one-time WARNING).
HOROVOD_TPU_ALLTOALL_ALGO = "HOROVOD_TPU_ALLTOALL_ALGO"
# wire codec for the hierarchical alltoall's cross-slice (DCN) block
# transpose — the ISSUE 13 per-link placement extended to dispatched
# tokens: ICI legs always stay full precision, and the codec here is
# STATELESS (no error-feedback residual: dispatched tokens have no
# step-over-step identity for a residual to telescope against). "none"
# (default), "bf16", "fp8", "int8". Flat alltoalls ignore it.
HOROVOD_TPU_ALLTOALL_CODEC = "HOROVOD_TPU_ALLTOALL_CODEC"
# auto alltoall selection takes the flat single-phase lowering when the
# dispatch payload is at most this many bytes (two extra launch legs
# beat the DCN chunk saving only above the crossover). 0 (default) means
# "hierarchical whenever the topology factorizes"; the calibration probe
# overwrites the default with the measured crossover from the alltoall
# band's own α–β rows (an explicit value here still wins).
HOROVOD_TPU_ALLTOALL_HIER_THRESHOLD_BYTES = \
    "HOROVOD_TPU_ALLTOALL_HIER_THRESHOLD_BYTES"
# expert-parallel MoE capacity factor override (models/transformer.py
# engine-alltoall training step): tokens-per-expert capacity = ceil(
# tokens * factor / experts). 0 (default) defers to the model config's
# value; > 0 overrides it fleet-wide (the dial the autotuner/operator
# turns without touching model code).
HOROVOD_TPU_MOE_CAPACITY_FACTOR = "HOROVOD_TPU_MOE_CAPACITY_FACTOR"
# topology override (parallel/mesh.detect_topology): ranks per fast-fabric
# island (ICI slice / host) when the device-attribute probe cannot see the
# real fabric; takes precedence over launcher-derived local sizes
HOROVOD_TPU_LOCAL_SIZE = "HOROVOD_TPU_LOCAL_SIZE"
# auto mode lowers a reduction bucket to the tree form when its payload is
# at most this many bytes (latency-bound regime; ring bandwidth wins above)
HOROVOD_TPU_TREE_THRESHOLD_BYTES = "HOROVOD_TPU_TREE_THRESHOLD_BYTES"
# measured performance model (ISSUE 14, autotune/calibration.py): =1 runs
# the init-time rank-collective link probe — 3-4 message bands per
# algorithm class fitted to an α–β cost model — and overlays the measured
# ICI/DCN bandwidths on the nominal Topology tables (MeasuredTopology);
# the ring/tree and flat/hierarchical crossover thresholds are then
# derived from the fit instead of the fixed tree-threshold constant (an
# explicit HOROVOD_TPU_TREE_THRESHOLD_BYTES still wins). Off by default;
# size<=1 worlds and probe failures fall back to nominal with a WARNING.
HOROVOD_TPU_CALIBRATE = "HOROVOD_TPU_CALIBRATE"
# persistent fleet autotune (ISSUE 14, autotune/persistence.py): PERSIST
# enables saving/loading converged tuning records keyed by (model
# signature = bucket-layout digest, topology digest); DIR overrides the
# record directory (default <HOROVOD_TPU_CHECKPOINT_DIR>/autotune). A
# restarted job with a matching key warm-starts the tuner at the stored
# winner (<=1 confirmation cycle); an elastically-resized world re-tunes
# from the nearest-key prior. Records also publish to the replicated KV
# ("autotune" scope) when endpoints are wired.
HOROVOD_TPU_TUNE_PERSIST = "HOROVOD_TPU_TUNE_PERSIST"
HOROVOD_TPU_TUNE_PERSIST_DIR = "HOROVOD_TPU_TUNE_PERSIST_DIR"
# link-aware gradient compression (ISSUE 13, ops/compression.py +
# ops/collectives.py codec reducers): the wire codec applied to reduction
# payloads — "none" (default), "bf16" (cast, 2 bytes/elem), or the
# error-feedback "fp8"/"int8" (1 byte/elem, residual-carrying). On the
# hierarchical ladder only the cross-slice DCN exchange is encoded (ICI
# legs stay full precision); flat/tree selections encode the whole
# payload. Non-float buckets are never quantized. Also an autotune
# categorical ("compression": env-resolved codec vs none — only offered
# when the user enabled a codec). Resolved once per engine; the
# optimizer's compression= argument overrides per call.
HOROVOD_TPU_COMPRESSION = "HOROVOD_TPU_COMPRESSION"
# pipeline schedules (ISSUE 16, parallel/pipeline.py): SCHEDULE picks the
# microbatch schedule — "1f1b" (default, the hand-scheduled baseline),
# "interleaved" (virtual-stage round-robin chunks, Narayanan et al. 2021),
# "zb" (zero-bubble B/W backward split, Qi et al. 2023), or "auto" (pick
# schedule + microbatch count from the calibrated α–β model; an explicit
# env pin wins). Also an autotune categorical ("pipeline_schedule" riding
# the algo_sig replay re-arm edge). Degenerate combinations (m < stages,
# interleaved without virtual chunks) demote to 1f1b with a one-time
# WARNING. VIRTUAL_STAGES is the interleaved chunks-per-stage count v
# (>= 2 activates interleaving; model depth must split into stages·v
# chunks). MICROBATCHES overrides the microbatch count m (0 = caller
# decides, or the α–β model under "auto"). BOUNDARY_CODEC applies the
# PR 13 wire codecs to stage-boundary activation/cotangent hops that
# cross DCN (ICI boundaries always stay raw; "none" default).
HOROVOD_TPU_PIPELINE_SCHEDULE = "HOROVOD_TPU_PIPELINE_SCHEDULE"
HOROVOD_TPU_PIPELINE_VIRTUAL_STAGES = "HOROVOD_TPU_PIPELINE_VIRTUAL_STAGES"
HOROVOD_TPU_PIPELINE_MICROBATCHES = "HOROVOD_TPU_PIPELINE_MICROBATCHES"
HOROVOD_TPU_PIPELINE_BOUNDARY_CODEC = "HOROVOD_TPU_PIPELINE_BOUNDARY_CODEC"
# async sharded checkpointing (ISSUE 9, horovod_tpu/checkpoint/): setting
# the directory enables the durable tier — TPUState commits snapshot
# through the CheckpointManager and elastic recovery falls back to the
# last durable generation when the in-memory commit is gone
HOROVOD_TPU_CHECKPOINT_DIR = "HOROVOD_TPU_CHECKPOINT_DIR"
# replicated control plane (ISSUE 12, runner/replication.py +
# runner/http_client.py): ENDPOINTS is the client-side replica set spec
# ("h1:p1,h2:p2") overriding the single rendezvous addr for every KV
# consumer; BREAKER_* shape the per-endpoint circuit breaker
# (consecutive-failure trip count, base reopen delay); LEASE_* drive the
# primary heartbeat stream and the standby's staggered promotion timeout;
# ACK_REPLICAS overrides the write-ack quorum (0 = majority of the
# replica set); JOURNAL_MAX bounds the in-memory replication journal;
# SCOPE_BUDGET_BYTES is the per-scope byte budget behind the server's
# 429 backpressure path (0 = unlimited). All resolved once at init —
# never re-read on a request or step path (docs/control_plane.md).
HOROVOD_KV_ENDPOINTS = "HOROVOD_KV_ENDPOINTS"
HOROVOD_KV_BREAKER_FAILURES = "HOROVOD_KV_BREAKER_FAILURES"
HOROVOD_KV_BREAKER_RESET = "HOROVOD_KV_BREAKER_RESET"
HOROVOD_KV_LEASE_TIMEOUT = "HOROVOD_KV_LEASE_TIMEOUT"
HOROVOD_KV_LEASE_INTERVAL = "HOROVOD_KV_LEASE_INTERVAL"
HOROVOD_KV_ACK_REPLICAS = "HOROVOD_KV_ACK_REPLICAS"
HOROVOD_KV_JOURNAL_MAX = "HOROVOD_KV_JOURNAL_MAX"
HOROVOD_KV_SCOPE_BUDGET_BYTES = "HOROVOD_KV_SCOPE_BUDGET_BYTES"
# survivable elastic driver (ISSUE 19, elastic/failover.py): JOURNAL
# gates the driver-state journal (world versions, strikes, host deltas,
# results — replicated through the "driver" KV scope so a standby can
# reconstruct the driver after a crash); LEASE_TIMEOUT is how stale the
# driver's journaled lease heartbeat may be before a standby considers
# the driver dead and promotes; LEASE_INTERVAL paces that heartbeat.
# Distinct from HOROVOD_KV_LEASE_* (the replication tier's own lease):
# the KV lease elects a new PRIMARY REPLICA, the driver lease elects a
# new ELASTIC DRIVER on top of it. All resolved once at init (divcheck).
HOROVOD_TPU_DRIVER_JOURNAL = "HOROVOD_TPU_DRIVER_JOURNAL"
HOROVOD_TPU_DRIVER_LEASE_TIMEOUT = "HOROVOD_TPU_DRIVER_LEASE_TIMEOUT"
HOROVOD_TPU_DRIVER_LEASE_INTERVAL = "HOROVOD_TPU_DRIVER_LEASE_INTERVAL"
# hierarchical telemetry fabric (ISSUE 18, runner/aggregator.py): AGG_ENABLE
# turns on the per-slice aggregator tier — each slice's lowest-rank worker
# hosts a SliceAggregator that receives slice-local metrics/trace/stall
# publishes and rolls ONE merged payload per stream per AGG_INTERVAL to the
# replicated root (O(slices) root load instead of O(ranks)); only effective
# when the topology factorizes (1 < local_size < size). AGG_CARDINALITY
# picks the metrics rollup shape: "rank" preserves per-rank snapshots inside
# the rollup, "slice" pre-sums them to one synthetic slice<k> series set.
# AGG_FALLBACK governs what a publisher does when its aggregator is dead:
# =1 (default) degrades loudly to direct-to-root (counted in
# hvd_tpu_agg_fallback_total), =0 raises to the caller. All resolved once
# at init (divcheck) — the elastic driver re-hosts aggregators per world.
HOROVOD_TPU_AGG_ENABLE = "HOROVOD_TPU_AGG_ENABLE"
HOROVOD_TPU_AGG_INTERVAL = "HOROVOD_TPU_AGG_INTERVAL"
HOROVOD_TPU_AGG_CARDINALITY = "HOROVOD_TPU_AGG_CARDINALITY"
HOROVOD_TPU_AGG_FALLBACK = "HOROVOD_TPU_AGG_FALLBACK"
HOROVOD_TPU_CHECKPOINT_INTERVAL_STEPS = "HOROVOD_TPU_CHECKPOINT_INTERVAL_STEPS"
HOROVOD_TPU_CHECKPOINT_REDUNDANCY = "HOROVOD_TPU_CHECKPOINT_REDUNDANCY"
HOROVOD_TPU_CHECKPOINT_KEEP = "HOROVOD_TPU_CHECKPOINT_KEEP"
HOROVOD_TPU_CHECKPOINT_KV_CHUNK_BYTES = "HOROVOD_TPU_CHECKPOINT_KV_CHUNK_BYTES"

DEFAULT_FUSION_THRESHOLD_BYTES = 64 * 1024 * 1024  # operations.cc:432
DEFAULT_CYCLE_TIME_MS = 5.0                        # operations.cc:440
DEFAULT_CACHE_CAPACITY = 1024                      # operations.cc:449-456
DEFAULT_STALL_WARNING_SECONDS = 60.0               # stall_inspector.h:75
DEFAULT_OVERLAP_STAGE_BYTES = 8 * 1024 * 1024
OVERLAP_PIPELINE_MODES = ("auto", "off", "interleave", "staged")
DEFAULT_TREE_THRESHOLD_BYTES = 256 * 1024
COLLECTIVE_ALGO_MODES = ("auto", "flat", "tree", "hierarchical")
ALLTOALL_ALGO_MODES = ("auto", "flat", "hierarchical")
COMPRESSION_MODES = ("none", "bf16", "fp8", "int8")
PIPELINE_SCHEDULE_MODES = ("1f1b", "interleaved", "zb", "auto")
AGG_CARDINALITY_MODES = ("rank", "slice")
_XLA_LHS_FLAG = "--xla_tpu_enable_latency_hiding_scheduler=true"


def _get_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def _get_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or not v.strip():
        return default
    try:
        return int(v)
    except ValueError:
        return default


def _get_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None or not v.strip():
        return default
    try:
        return float(v)
    except ValueError:
        return default


def _get_choice(name: str, default: str, choices) -> str:
    v = os.environ.get(name)
    if v is None or not v.strip():
        return default
    v = v.strip().lower()
    if v not in choices:
        import logging
        logging.getLogger("horovod_tpu").warning(
            "%s=%r is not one of %s; using %r", name, v, list(choices),
            default)
        return default
    return v


JAX_COMPILATION_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """Where this checkout keeps JAX's persistent compilation cache: the
    directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part of
    what a later run must find again. Touches no jax: the launcher calls
    it to hand every worker the same directory."""
    return os.environ.get(JAX_COMPILATION_CACHE_DIR) or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that directory. With ``JAX_COMPILATION_CACHE_DIR`` set this
    sets nothing (jax reads the variable itself). Call before the first
    compile; every entry script (chip_smoke.py, benchmark/worker.py, the
    examples, the tools/ probes) calls it, and workers under the
    launcher inherit the directory through the variable."""
    path = compile_cache_dir()
    if not os.environ.get(JAX_COMPILATION_CACHE_DIR):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def apply_xla_lhs() -> bool:
    """ISSUE 6 satellite: ``HOROVOD_TPU_XLA_LHS=1`` appends
    ``--xla_tpu_enable_latency_hiding_scheduler=true`` to ``XLA_FLAGS``.

    XLA parses ``XLA_FLAGS`` when the first backend client is created, so
    this must run before the first backend touch — it is called from
    ``horovod_tpu/__init__`` at import. If a jax backend already exists
    the append would be silently ignored; that case gets a loud WARNING
    and a no-op instead (a program that must have the flag later can
    pass it per compile through ``compiler_options``, which works at any
    time).

    Returns True when the flag is (already or newly) in effect."""
    import logging
    import sys
    if not _get_bool(HOROVOD_TPU_XLA_LHS):
        return False
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_tpu_enable_latency_hiding_scheduler" in flags:
        # user already set it — theirs wins; report whether it enables
        return _XLA_LHS_FLAG in flags
    jax_mod = sys.modules.get("jax")
    if jax_mod is not None:
        # the backend registry is private and has moved between jax
        # versions — probe the known locations, and degrade LOUDLY (not
        # silently) when none resolves on a future jax
        probed = False
        backends = None
        for parent in ("_src", "lib"):
            try:
                bridge = getattr(getattr(jax_mod, parent), "xla_bridge")
                backends = bridge._backends
                probed = True
                break
            except AttributeError:
                continue
            except Exception:
                continue
        if backends:
            logging.getLogger("horovod_tpu").warning(
                "HOROVOD_TPU_XLA_LHS=1 but a jax backend is already "
                "initialized; XLA_FLAGS changes no longer take effect. "
                "Set the env var before the first jax backend touch (or "
                "pass per-compile compiler_options). Ignoring the knob.")
            return False
        if not probed:
            logging.getLogger("horovod_tpu").warning(
                "HOROVOD_TPU_XLA_LHS=1: cannot tell whether a jax "
                "backend is already initialized on this jax version; "
                "appending the flag anyway. If any jax computation ran "
                "before horovod_tpu was imported, XLA_FLAGS changes have "
                "no effect — set the env var before the first backend "
                "touch.")
    os.environ["XLA_FLAGS"] = (flags + " " + _XLA_LHS_FLAG).strip()
    return True


@dataclass
class Config:
    """Parsed runtime configuration (analog of the knob block read at
    operations.cc:416-513)."""

    fusion_threshold_bytes: int = DEFAULT_FUSION_THRESHOLD_BYTES
    cycle_time_ms: float = DEFAULT_CYCLE_TIME_MS
    cache_capacity: int = DEFAULT_CACHE_CAPACITY
    timeline_path: Optional[str] = None
    timeline_mark_cycles: bool = False
    autotune: bool = False
    autotune_log: Optional[str] = None
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    autotune_bayes_opt_max_samples: int = 20
    autotune_gaussian_process_noise: float = 0.8
    stall_check_disable: bool = False
    stall_warning_seconds: float = DEFAULT_STALL_WARNING_SECONDS
    stall_shutdown_seconds: float = 0.0
    collective_deadline: float = 0.0
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    debug_consistency: bool = False
    join_enabled: bool = True
    elastic: bool = False
    meta_cache: bool = True
    meta_cache_warmup: int = 2
    single_launch: bool = True
    step_replay: bool = True
    step_replay_warmup: int = 3
    shard_optimizer: bool = False
    overlap_pipeline: str = "auto"
    overlap_stage_bytes: int = DEFAULT_OVERLAP_STAGE_BYTES
    zero1_prefetch: bool = True
    collective_algo: str = "auto"
    tree_threshold_bytes: int = DEFAULT_TREE_THRESHOLD_BYTES
    # flat/hierarchical crossover in bytes — 0 (always hierarchical when
    # expressible) unless the init-time calibration derived a measured
    # crossover (ISSUE 14); deliberately not an env knob: it exists only
    # as a fitted quantity, the tree threshold is the user-facing dial
    hier_threshold_bytes: int = 0
    alltoall_algo: str = "auto"
    alltoall_codec: str = "none"
    # the alltoall flat/hierarchical crossover — derived-only like
    # hier_threshold_bytes (the calibration probe's alltoall band fits
    # its own α–β rows; the exchange moves every byte exactly once, so
    # the reduction crossover does not transfer)
    alltoall_hier_threshold_bytes: int = 0
    moe_capacity_factor: float = 0.0
    compression: str = "none"
    pipeline_schedule: str = "1f1b"
    pipeline_virtual_stages: int = 1
    pipeline_microbatches: int = 0
    pipeline_boundary_codec: str = "none"
    calibrate: bool = False
    tune_persist: bool = True
    tune_persist_dir: Optional[str] = None
    # NOTE: the HOROVOD_TPU_METRICS on/off switch is read by
    # metrics.metrics_enabled() (the registry outlives any Config); only
    # the emitter knobs live here
    metrics_file: Optional[str] = None
    metrics_interval: float = 10.0
    trace_enabled: bool = True
    trace_ring: int = 4096
    trace_interval: float = 5.0
    trace_dump_dir: Optional[str] = None
    step_health: bool = True
    step_health_window: int = 64
    step_health_warmup: int = 8
    step_health_mad_k: float = 3.0
    step_health_dump_interval: float = 60.0
    hbm_telemetry: bool = True
    agg_enable: bool = True
    agg_interval: float = 5.0
    agg_cardinality: str = "rank"
    agg_fallback: bool = True
    checkpoint_dir: Optional[str] = None
    checkpoint_interval_steps: int = 0
    checkpoint_redundancy: int = 1
    checkpoint_keep: int = 2
    checkpoint_kv_chunk_bytes: int = 4 * 1024 * 1024
    # knob provenance (ISSUE 14 bench satellite): tuning-relevant field
    # -> "env-forced" | "default" at parse time; the calibration overlay
    # and the autotuner overwrite entries with "calibrated" / "tuned" as
    # they take ownership, so bench results are self-describing about
    # where every knob value came from
    provenance: dict = field(default_factory=dict)
    extra: dict = field(default_factory=dict)

    # the tuned/calibrated knob surface whose provenance the bench reports
    _PROVENANCE_VARS = {
        "fusion_threshold_bytes": HOROVOD_FUSION_THRESHOLD,
        "cycle_time_ms": HOROVOD_CYCLE_TIME,
        "tree_threshold_bytes": HOROVOD_TPU_TREE_THRESHOLD_BYTES,
        "collective_algo": HOROVOD_TPU_COLLECTIVE_ALGO,
        "alltoall_algo": HOROVOD_TPU_ALLTOALL_ALGO,
        "alltoall_codec": HOROVOD_TPU_ALLTOALL_CODEC,
        "alltoall_hier_threshold_bytes":
            HOROVOD_TPU_ALLTOALL_HIER_THRESHOLD_BYTES,
        "overlap_pipeline": HOROVOD_TPU_OVERLAP_PIPELINE,
        "compression": HOROVOD_TPU_COMPRESSION,
        "pipeline_schedule": HOROVOD_TPU_PIPELINE_SCHEDULE,
        "single_launch": HOROVOD_TPU_SINGLE_LAUNCH,
        "step_replay": HOROVOD_TPU_STEP_REPLAY,
        "shard_optimizer": HOROVOD_TPU_SHARD_OPTIMIZER,
        "hierarchical_allreduce": HOROVOD_HIERARCHICAL_ALLREDUCE,
        "hierarchical_allgather": HOROVOD_HIERARCHICAL_ALLGATHER,
    }

    @classmethod
    def from_env(cls) -> "Config":
        cfg = cls._parse_env()
        cfg.provenance = {
            f: ("env-forced" if (os.environ.get(v) or "").strip()
                else "default")
            for f, v in cls._PROVENANCE_VARS.items()}
        return cfg

    @classmethod
    def _parse_env(cls) -> "Config":
        return cls(
            fusion_threshold_bytes=_get_int(
                HOROVOD_FUSION_THRESHOLD, DEFAULT_FUSION_THRESHOLD_BYTES),
            cycle_time_ms=_get_float(HOROVOD_CYCLE_TIME, DEFAULT_CYCLE_TIME_MS),
            cache_capacity=_get_int(HOROVOD_CACHE_CAPACITY, DEFAULT_CACHE_CAPACITY),
            timeline_path=os.environ.get(HOROVOD_TIMELINE) or None,
            timeline_mark_cycles=_get_bool(HOROVOD_TIMELINE_MARK_CYCLES),
            autotune=_get_bool(HOROVOD_AUTOTUNE),
            autotune_log=os.environ.get(HOROVOD_AUTOTUNE_LOG) or None,
            autotune_warmup_samples=_get_int(HOROVOD_AUTOTUNE_WARMUP_SAMPLES, 3),
            autotune_steps_per_sample=_get_int(HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE, 10),
            autotune_bayes_opt_max_samples=_get_int(
                HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES, 20),
            autotune_gaussian_process_noise=_get_float(
                HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE, 0.8),
            stall_check_disable=_get_bool(HOROVOD_STALL_CHECK_DISABLE),
            stall_warning_seconds=_get_float(
                HOROVOD_STALL_CHECK_TIME_SECONDS, DEFAULT_STALL_WARNING_SECONDS),
            stall_shutdown_seconds=_get_float(HOROVOD_STALL_SHUTDOWN_TIME_SECONDS, 0.0),
            collective_deadline=_get_float(
                HOROVOD_TPU_COLLECTIVE_DEADLINE, 0.0),
            hierarchical_allreduce=_get_bool(HOROVOD_HIERARCHICAL_ALLREDUCE),
            hierarchical_allgather=_get_bool(HOROVOD_HIERARCHICAL_ALLGATHER),
            debug_consistency=_get_bool(HOROVOD_TPU_DEBUG_CONSISTENCY),
            join_enabled=not _get_bool(HOROVOD_JOIN_DISABLE),
            elastic=_get_bool(HOROVOD_ELASTIC),
            meta_cache=_get_bool(HOROVOD_TPU_META_CACHE, True),
            meta_cache_warmup=_get_int(HOROVOD_TPU_META_CACHE_WARMUP, 2),
            single_launch=_get_bool(HOROVOD_TPU_SINGLE_LAUNCH, True),
            step_replay=_get_bool(HOROVOD_TPU_STEP_REPLAY, True),
            step_replay_warmup=_get_int(HOROVOD_TPU_STEP_REPLAY_WARMUP, 3),
            shard_optimizer=_get_bool(HOROVOD_TPU_SHARD_OPTIMIZER, False),
            overlap_pipeline=_get_choice(
                HOROVOD_TPU_OVERLAP_PIPELINE, "auto",
                OVERLAP_PIPELINE_MODES),
            overlap_stage_bytes=_get_int(HOROVOD_TPU_OVERLAP_STAGE_BYTES,
                                         DEFAULT_OVERLAP_STAGE_BYTES),
            zero1_prefetch=_get_bool(HOROVOD_TPU_ZERO1_PREFETCH, True),
            collective_algo=_get_choice(
                HOROVOD_TPU_COLLECTIVE_ALGO, "auto", COLLECTIVE_ALGO_MODES),
            tree_threshold_bytes=_get_int(
                HOROVOD_TPU_TREE_THRESHOLD_BYTES,
                DEFAULT_TREE_THRESHOLD_BYTES),
            alltoall_algo=_get_choice(
                HOROVOD_TPU_ALLTOALL_ALGO, "auto", ALLTOALL_ALGO_MODES),
            alltoall_codec=_get_choice(
                HOROVOD_TPU_ALLTOALL_CODEC, "none", COMPRESSION_MODES),
            alltoall_hier_threshold_bytes=_get_int(
                HOROVOD_TPU_ALLTOALL_HIER_THRESHOLD_BYTES, 0),
            moe_capacity_factor=_get_float(
                HOROVOD_TPU_MOE_CAPACITY_FACTOR, 0.0),
            compression=_get_choice(
                HOROVOD_TPU_COMPRESSION, "none", COMPRESSION_MODES),
            pipeline_schedule=_get_choice(
                HOROVOD_TPU_PIPELINE_SCHEDULE, "1f1b",
                PIPELINE_SCHEDULE_MODES),
            pipeline_virtual_stages=_get_int(
                HOROVOD_TPU_PIPELINE_VIRTUAL_STAGES, 1),
            pipeline_microbatches=_get_int(
                HOROVOD_TPU_PIPELINE_MICROBATCHES, 0),
            pipeline_boundary_codec=_get_choice(
                HOROVOD_TPU_PIPELINE_BOUNDARY_CODEC, "none",
                COMPRESSION_MODES),
            calibrate=_get_bool(HOROVOD_TPU_CALIBRATE, False),
            tune_persist=_get_bool(HOROVOD_TPU_TUNE_PERSIST, True),
            tune_persist_dir=os.environ.get(HOROVOD_TPU_TUNE_PERSIST_DIR)
            or None,
            metrics_file=os.environ.get(HOROVOD_TPU_METRICS_FILE) or None,
            metrics_interval=_get_float(HOROVOD_TPU_METRICS_INTERVAL, 10.0),
            trace_enabled=_get_bool(HOROVOD_TPU_TRACE, True),
            trace_ring=_get_int(HOROVOD_TPU_TRACE_RING, 4096),
            trace_interval=_get_float(HOROVOD_TPU_TRACE_INTERVAL, 5.0),
            trace_dump_dir=os.environ.get(HOROVOD_TPU_TRACE_DUMP_DIR) or None,
            step_health=_get_bool(HOROVOD_TPU_STEP_HEALTH, True),
            step_health_window=_get_int(HOROVOD_TPU_STEP_HEALTH_WINDOW, 64),
            step_health_warmup=_get_int(HOROVOD_TPU_STEP_HEALTH_WARMUP, 8),
            step_health_mad_k=_get_float(HOROVOD_TPU_STEP_HEALTH_MAD_K, 3.0),
            step_health_dump_interval=_get_float(
                HOROVOD_TPU_STEP_HEALTH_DUMP_INTERVAL, 60.0),
            hbm_telemetry=_get_bool(HOROVOD_TPU_HBM, True),
            agg_enable=_get_bool(HOROVOD_TPU_AGG_ENABLE, True),
            agg_interval=_get_float(HOROVOD_TPU_AGG_INTERVAL, 5.0),
            agg_cardinality=_get_choice(
                HOROVOD_TPU_AGG_CARDINALITY, "rank", AGG_CARDINALITY_MODES),
            agg_fallback=_get_bool(HOROVOD_TPU_AGG_FALLBACK, True),
            checkpoint_dir=os.environ.get(HOROVOD_TPU_CHECKPOINT_DIR)
            or None,
            checkpoint_interval_steps=_get_int(
                HOROVOD_TPU_CHECKPOINT_INTERVAL_STEPS, 0),
            checkpoint_redundancy=_get_int(
                HOROVOD_TPU_CHECKPOINT_REDUNDANCY, 1),
            checkpoint_keep=_get_int(HOROVOD_TPU_CHECKPOINT_KEEP, 2),
            checkpoint_kv_chunk_bytes=_get_int(
                HOROVOD_TPU_CHECKPOINT_KV_CHUNK_BYTES, 4 * 1024 * 1024),
        )
