"""Central configuration-knob registry (ISSUE 7 satellite).

Every ``HOROVOD_*`` / ``HOROVOD_TPU_*`` environment variable the
framework reads is declared here: name -> ``{"type", "default", "help"}``
(plus ``"choices"`` for choice knobs, ``"internal": True`` for plumbing
variables the launcher/rendezvous sets rather than users, and
``"export": True`` for variables the framework only *sets* for worker
processes as part of the env contract).

The registry is linted by :mod:`horovod_tpu.analysis.knobcheck` (run
from ``tools/check.py`` and a tier-1 test): an AST scan of every
``os.environ`` / ``getenv`` / typed-helper read under ``horovod_tpu/``
fails on **undeclared** reads (a knob someone added without documenting)
and on **dead** declarations (a knob nothing reads any more). The
"Configuration knobs" section of ``docs/api.md`` is generated from this
table by ``tools/gen_api_docs.py`` — docs, code, and lint share one
source of truth, the ``METRIC_SPECS`` / ``FAULT_SPECS`` discipline
applied to the env plane.

``default`` records the *effective* default as a display string
("derived" when computed from topology/context at runtime). Parsing
stays where it always was (``common/env.py`` helpers and the call
sites); this table adds no runtime indirection.
"""

from __future__ import annotations

from typing import Dict

KNOB_SPECS: Dict[str, dict] = {
    # -- core engine / fusion (parity: common.h:64-90) ----------------------
    "HOROVOD_FUSION_THRESHOLD": {
        "type": "int", "default": str(64 * 1024 * 1024),
        "help": "Fusion-buffer bucket size in bytes for grouped/sharded "
                "collectives (operations.cc:432 parity); autotunable."},
    "HOROVOD_CYCLE_TIME": {
        "type": "float", "default": "5.0",
        "help": "Engine cycle-loop wake interval in ms (handle "
                "retirement cadence); autotunable."},
    "HOROVOD_CACHE_CAPACITY": {
        "type": "int", "default": "1024",
        "help": "LRU capacity of the engine builder cache and the "
                "steady-state metadata cache (ResponseCache analog)."},
    "HOROVOD_JOIN_DISABLE": {
        "type": "bool", "default": "0",
        "help": "Disable the per-op Join advertisement round (shaves one "
                "fire-and-forget exchange per op when no rank can run out "
                "of data early)."},
    "HOROVOD_JOIN_META_SLOTS": {
        "type": "int", "default": "16",
        "help": "Inline metadata slots in the fixed-shape join round; "
                "larger grouped calls spill into one overflow exchange."},
    "HOROVOD_HIERARCHICAL_ALLREDUCE": {
        "type": "bool", "default": "0",
        "help": "Two-level intra/inter-node allreduce when the topology "
                "has a non-trivial homogeneous factorization."},
    "HOROVOD_HIERARCHICAL_ALLGATHER": {
        "type": "bool", "default": "0",
        "help": "Two-level intra/inter-node allgather (local gather, "
                "cross exchange, local fan-out)."},
    "HOROVOD_TPU_SINGLE_LAUNCH": {
        "type": "bool", "default": "1",
        "help": "Service a grouped allreduce as one pack launch plus one "
                "reduce+unpack program; =0 restores the per-bucket "
                "two-dispatch form."},
    "HOROVOD_TPU_META_CACHE": {
        "type": "bool", "default": "1",
        "help": "Steady-state size-negotiation cache for unequal "
                "allgather/alltoall: hot entries skip the blocking "
                "exchange with a deferred extract-time check."},
    "HOROVOD_TPU_META_CACHE_WARMUP": {
        "type": "int", "default": "2",
        "help": "Identical world observations before a size-cache entry "
                "goes hot (fire-and-forget exchanges)."},
    "HOROVOD_TPU_DEBUG_CONSISTENCY": {
        "type": "bool", "default": "0",
        "help": "Allgather a submission fingerprint before every "
                "collective and raise descriptive cross-rank mismatch "
                "errors (controller.cc:380-623 debug mode)."},
    # -- step-capture replay ------------------------------------------------
    "HOROVOD_TPU_STEP_REPLAY": {
        "type": "bool", "default": "1",
        "help": "Record the dispatch stream between step markers and "
                "service steady-state steps as one fused XLA launch."},
    "HOROVOD_TPU_STEP_REPLAY_WARMUP": {
        "type": "int", "default": "3",
        "help": "Identical step signatures required before a replay "
                "stream arms."},
    # -- comm/compute overlap (ISSUE 6) -------------------------------------
    "HOROVOD_TPU_OVERLAP_PIPELINE": {
        "type": "choice", "default": "auto",
        "choices": ("auto", "off", "interleave", "staged"),
        "help": "Collective schedule of the fused step: serial chain, "
                "back-to-back interleave, per-bucket staged sub-launches, "
                "or auto per (bytes, topology)."},
    "HOROVOD_TPU_OVERLAP_STAGE_BYTES": {
        "type": "int", "default": str(8 * 1024 * 1024),
        "help": "Auto mode switches interleave -> staged when a step's "
                "gradient bytes reach this threshold."},
    "HOROVOD_TPU_ZERO1_PREFETCH": {
        "type": "bool", "default": "1",
        "help": "Split the ZeRO-1 step so the parameter all-gather rides "
                "as its own prefetch leg under the step tail (staged "
                "schedule only)."},
    "HOROVOD_TPU_XLA_LHS": {
        "type": "bool", "default": "0",
        "help": "Append --xla_tpu_enable_latency_hiding_scheduler=true "
                "to XLA_FLAGS before the first backend touch."},
    # -- topology-aware collective algorithm selection (ISSUE 10) -----------
    "HOROVOD_TPU_COLLECTIVE_ALGO": {
        "type": "choice", "default": "auto",
        "choices": ("auto", "flat", "tree", "hierarchical"),
        "help": "Collective lowering per reduction/gather bucket: auto "
                "picks flat-ring vs tree (recursive doubling, small "
                "latency-bound buckets) vs hierarchical (intra-slice RS "
                "over ICI, 1/local_size cross-slice exchange over DCN, "
                "AG back) per (bytes, topology); forced values demote to "
                "flat with a one-time WARNING when invalid."},
    "HOROVOD_TPU_COMPRESSION": {
        "type": "choice", "default": "none",
        "choices": ("none", "bf16", "fp8", "int8"),
        "help": "Link-aware wire codec for reduction payloads (ISSUE "
                "13): bf16 casts (2 bytes/elem); fp8/int8 quantize with "
                "error feedback (1 byte/elem, a rank-local residual per "
                "fusion bucket carries the quantization error forward). "
                "On the hierarchical ladder only the cross-slice DCN "
                "exchange is encoded — ICI legs stay full precision; "
                "flat/tree lowerings encode the whole payload. Non-float "
                "buckets are never quantized; fp8 demotes to int8 on jax "
                "builds without a float8 dtype. Also an autotune "
                "categorical (codec vs none) when enabled."},
    "HOROVOD_TPU_ALLTOALL_ALGO": {
        "type": "choice", "default": "auto",
        "choices": ("auto", "flat", "hierarchical"),
        "help": "Alltoall lowering per dispatch bucket (ISSUE 17): auto "
                "picks flat (one whole-world exchange) vs hierarchical "
                "(intra-slice ICI exchange, then an inter-slice DCN block "
                "transpose where each DCN link carries O(n/slices) blocks "
                "instead of O(n)) per (bytes, topology); forced "
                "hierarchical demotes to flat with a one-time WARNING "
                "when the topology has no homogeneous factorization. "
                "Selection uses the alltoall-specific calibrated "
                "threshold, not the allreduce one."},
    "HOROVOD_TPU_ALLTOALL_CODEC": {
        "type": "choice", "default": "none",
        "choices": ("none", "bf16", "fp8", "int8"),
        "help": "Wire codec for the hierarchical alltoall's cross-slice "
                "DCN leg only (ICI legs always stay full precision, and "
                "the flat lowering never encodes). Stateless — dispatched "
                "tokens have no step-over-step identity, so no error "
                "feedback; fp8/int8 quantize per-sender with a shared "
                "scale exchanged alongside the payload. Non-float "
                "payloads are never quantized."},
    "HOROVOD_TPU_ALLTOALL_HIER_THRESHOLD_BYTES": {
        "type": "int", "default": "0 (hierarchical whenever possible)",
        "help": "Auto alltoall selection keeps the flat single-phase "
                "lowering when the dispatch payload is at most this "
                "many bytes (the two-phase ladder's extra launch legs "
                "only pay off above the crossover). The calibration "
                "probe's alltoall band overwrites the 0 default with "
                "the measured crossover; an explicit value here wins "
                "over calibration."},
    "HOROVOD_TPU_MOE_CAPACITY_FACTOR": {
        "type": "float", "default": "0 (model config decides)",
        "help": "Capacity-factor override for expert-parallel MoE "
                "routing through the engine alltoall: per-expert "
                "capacity = ceil(tokens * factor / n_experts). 0 defers "
                "to the model's TransformerConfig value. Larger values "
                "drop fewer tokens at the cost of more dispatch bytes."},
    # -- pipeline schedules (ISSUE 16) --------------------------------------
    "HOROVOD_TPU_PIPELINE_SCHEDULE": {
        "type": "choice", "default": "1f1b",
        "choices": ("1f1b", "interleaved", "zb", "auto"),
        "help": "Pipeline-parallel microbatch schedule "
                "(parallel/pipeline.py): 1f1b is the hand-scheduled "
                "baseline; interleaved runs round-robin virtual-stage "
                "chunks (bubble q/(m+q), q=(p-1)/v); zb splits the "
                "backward into B (activation-grad) and W (weight-grad) "
                "passes with W deferred into the drain bubble; auto picks "
                "schedule + microbatch count from the calibrated "
                "alpha-beta model (env pin wins). All schedules are "
                "bitwise-trajectory-equal to 1f1b at matched microbatch "
                "count; degenerate combinations (m < stages, interleaved "
                "with v < 2) demote to 1f1b with a one-time WARNING. "
                "Also an autotune categorical riding the algo_sig replay "
                "re-arm edge."},
    "HOROVOD_TPU_PIPELINE_VIRTUAL_STAGES": {
        "type": "int", "default": "1",
        "help": "Virtual chunks per pipeline stage (interleaved "
                "schedule): >= 2 activates interleaving, model depth "
                "must split into stages*v chunks. Chunk c runs on stage "
                "c % stages (round-robin placement)."},
    "HOROVOD_TPU_PIPELINE_MICROBATCHES": {
        "type": "int", "default": "0",
        "help": "Microbatch count override for pipeline train steps "
                "(0 = the caller's count, or the alpha-beta model's "
                "pick under schedule=auto; must divide the global "
                "batch)."},
    "HOROVOD_TPU_PIPELINE_BOUNDARY_CODEC": {
        "type": "choice", "default": "none",
        "choices": ("none", "bf16", "fp8", "int8"),
        "help": "Wire codec for stage-boundary activation/cotangent "
                "hops that cross DCN (PR 13 codecs, stateless — no "
                "error feedback on the non-reduction path). ICI "
                "boundaries always stay raw: the partial-ppermute split "
                "only moves quantized bytes on the coded edges."},
    "HOROVOD_TPU_LOCAL_SIZE": {
        "type": "int", "default": "derived",
        "help": "Topology override: ranks per fast-fabric island "
                "(ICI slice / host) when the device-attribute probe "
                "cannot see the real fabric; wins over launcher-derived "
                "local sizes."},
    "HOROVOD_TPU_TREE_THRESHOLD_BYTES": {
        "type": "int", "default": str(256 * 1024),
        "help": "Auto algorithm selection lowers a reduction bucket to "
                "the tree form when its payload is at most this many "
                "bytes."},
    # -- ZeRO-1 sharded optimizer -------------------------------------------
    "HOROVOD_TPU_SHARD_OPTIMIZER": {
        "type": "bool", "default": "0",
        "help": "Default for optimizers constructed with sharded=None: "
                "bucketed reduce-scatter -> shard-local update -> fused "
                "all-gather (optimizer state / world size)."},
    # -- autotune -----------------------------------------------------------
    "HOROVOD_AUTOTUNE": {
        "type": "bool", "default": "0",
        "help": "Enable the Bayesian autotuner over the joint knob space: "
                "fusion threshold, cycle time, tree threshold, and the "
                "categorical knobs (collective_algo, overlap mode, "
                "compression codec, hierarchy, replay, sharding)."},
    "HOROVOD_TPU_CALIBRATE": {
        "type": "bool", "default": "0",
        "help": "Run the init-time rank-collective link probe (ISSUE 14): "
                "3-4 message bands per algorithm class fitted to an "
                "alpha-beta cost model, measured ICI/DCN bandwidths "
                "overlaid on the nominal Topology tables, and the "
                "ring/tree and flat/hierarchical crossover thresholds "
                "derived from the fit (an explicit "
                "HOROVOD_TPU_TREE_THRESHOLD_BYTES still wins). Probe "
                "results are exchanged through the agreement path so "
                "every rank selects identically; size<=1 worlds and "
                "probe failures fall back to the nominal tables."},
    "HOROVOD_TPU_TUNE_PERSIST": {
        "type": "bool", "default": "1",
        "help": "Persist converged autotune settings keyed by (model "
                "signature = bucket-layout digest, topology digest) into "
                "the tuning-record directory and the replicated KV, and "
                "warm-start a restarted job with a matching key at the "
                "stored winner (<=1 confirmation cycle); an elastically "
                "resized world re-tunes from the nearest-key prior. "
                "Effective only when a record directory resolves (this "
                "knob's DIR, or <checkpoint dir>/autotune) or KV "
                "endpoints are wired."},
    "HOROVOD_TPU_TUNE_PERSIST_DIR": {
        "type": "str", "default": "",
        "help": "Directory for persisted tuning records (default: "
                "<HOROVOD_TPU_CHECKPOINT_DIR>/autotune when the "
                "checkpoint tier is enabled)."},
    "HOROVOD_AUTOTUNE_LOG": {
        "type": "str", "default": "",
        "help": "CSV file receiving one line per autotune sample."},
    "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": {
        "type": "int", "default": "3",
        "help": "Discarded warmup samples before scoring begins."},
    "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": {
        "type": "int", "default": "10",
        "help": "Steps aggregated into one autotune throughput sample."},
    "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": {
        "type": "int", "default": "20",
        "help": "Samples before the tuner converges on the best knob "
                "setting."},
    "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE": {
        "type": "float", "default": "0.8",
        "help": "GP noise prior for the Bayesian optimizer."},
    # -- stall inspector / collective watchdog ------------------------------
    "HOROVOD_STALL_CHECK_DISABLE": {
        "type": "bool", "default": "0",
        "help": "Disable stall warning/shutdown tiers (the collective "
                "watchdog still arms when a deadline is set)."},
    "HOROVOD_STALL_CHECK_TIME_SECONDS": {
        "type": "float", "default": "60.0",
        "help": "Outstanding-op age before a stall warning "
                "(stall_inspector.h:75 parity)."},
    "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS": {
        "type": "float", "default": "0.0",
        "help": "Outstanding-op age before the process aborts (0 "
                "disables; the terminal tier for hangs with no Python "
                "edge left)."},
    "HOROVOD_TPU_COLLECTIVE_DEADLINE": {
        "type": "float", "default": "0.0",
        "help": "Seconds a collective may sit outstanding (or a peer "
                "heartbeat lag) before the watchdog poisons the engine "
                "and raises the elastic-recoverable error; 0 disables."},
    # -- async sharded checkpointing (ISSUE 9) ------------------------------
    "HOROVOD_TPU_CHECKPOINT_DIR": {
        "type": "str", "default": "",
        "help": "Checkpoint root directory; setting it enables the "
                "durable tier (TPUState commits snapshot asynchronously "
                "through the CheckpointManager and elastic recovery "
                "falls back to the last durable generation when the "
                "in-memory commit is gone)."},
    "HOROVOD_TPU_CHECKPOINT_INTERVAL_STEPS": {
        "type": "int", "default": "0",
        "help": "Auto-snapshot every N completed engine steps via the "
                "step hook (needs a registered state provider); 0 "
                "leaves snapshots to explicit commit()/snapshot() "
                "calls."},
    "HOROVOD_TPU_CHECKPOINT_REDUNDANCY": {
        "type": "int", "default": "1",
        "help": "Peer-replica degree: rank r also holds ranks "
                "(r+1..r+d)%N's shards, so up to d lost hosts restore "
                "from neighbors over the wire instead of blob storage."},
    "HOROVOD_TPU_CHECKPOINT_KEEP": {
        "type": "int", "default": "2",
        "help": "Complete checkpoint generations retained per rank; "
                "older ones (and partial generations) are "
                "garbage-collected."},
    "HOROVOD_TPU_CHECKPOINT_KV_CHUNK_BYTES": {
        "type": "int", "default": str(4 * 1024 * 1024),
        "help": "Chunk size for large-value shard transfers through the "
                "rendezvous KV (one multi-hundred-MB PUT would fight "
                "the capped per-request socket timeout)."},
    # -- replicated control plane (ISSUE 12) --------------------------------
    "HOROVOD_KV_ENDPOINTS": {
        "type": "str", "default": "",
        "help": "Control-plane replica set (\"h1:p1,h2:p2\") every KV "
                "client fails over across; overrides the single "
                "rendezvous addr/port for publishers, checkpointing, and "
                "fault arming. Resolved once at init."},
    "HOROVOD_KV_BREAKER_FAILURES": {
        "type": "int", "default": "3",
        "help": "Consecutive transport failures before a KV endpoint's "
                "circuit breaker trips open (half-open probe after a "
                "jittered, per-trip-doubling reopen delay)."},
    "HOROVOD_KV_BREAKER_RESET": {
        "type": "float", "default": "0.5",
        "help": "Base seconds a tripped KV endpoint breaker stays open "
                "before its half-open probe (doubles per trip, "
                "jittered)."},
    "HOROVOD_KV_LEASE_TIMEOUT": {
        "type": "float", "default": "2.0",
        "help": "Seconds a standby tolerates lease silence from the "
                "primary before promoting itself (staggered by its "
                "replica-set index; the fenced-epoch handoff)."},
    "HOROVOD_KV_LEASE_INTERVAL": {
        "type": "float", "default": "0.5",
        "help": "Seconds between the primary's lease/catch-up "
                "replication ticks to each standby."},
    "HOROVOD_KV_ACK_REPLICAS": {
        "type": "int", "default": "0",
        "help": "Replicas (including the primary) that must apply a "
                "write before it is acked; 0 = majority of the "
                "configured replica set."},
    "HOROVOD_KV_JOURNAL_MAX": {
        "type": "int", "default": "8192",
        "help": "In-memory replication journal entries retained; peers "
                "behind the retained window resync via a full snapshot "
                "push."},
    "HOROVOD_KV_SCOPE_BUDGET_BYTES": {
        "type": "int", "default": "0",
        "help": "Per-scope KV byte budget behind the 429 + Retry-After "
                "backpressure path (telemetry publishers shed on it, "
                "counted in hvd_tpu_kv_shed_bytes_total); 0 = "
                "unlimited."},
    # -- metrics & telemetry ------------------------------------------------
    "HOROVOD_TPU_METRICS": {
        "type": "bool", "default": "1",
        "help": "Master switch for the metrics registry; =0 makes every "
                "instrument a shared lock-free no-op."},
    "HOROVOD_TPU_METRICS_FILE": {
        "type": "str", "default": "",
        "help": "JSONL file the periodic metrics emitter appends "
                "snapshots to."},
    "HOROVOD_TPU_METRICS_INTERVAL": {
        "type": "float", "default": "10.0",
        "help": "Seconds between metrics emitter ticks (JSONL / KV "
                "publish / timeline counter samples)."},
    # -- cross-rank tracing -------------------------------------------------
    "HOROVOD_TPU_TRACE": {
        "type": "bool", "default": "1",
        "help": "Cross-rank collective tracing; =0 leaves engine.trace "
                "None (no per-dispatch locking)."},
    "HOROVOD_TPU_TRACE_RING": {
        "type": "int", "default": "4096",
        "help": "Per-rank in-memory trace ring capacity (events)."},
    "HOROVOD_TPU_TRACE_INTERVAL": {
        "type": "float", "default": "5.0",
        "help": "Seconds between trace-segment KV publishes and clock "
                "beacons."},
    "HOROVOD_TPU_TRACE_DUMP_DIR": {
        "type": "str", "default": "",
        "help": "Directory for the watchdog's flight-recorder trace dump "
                "(hvd_tpu_flight_rank<r>.json)."},
    # -- step health (ISSUE 20) ---------------------------------------------
    "HOROVOD_TPU_STEP_HEALTH": {
        "type": "bool", "default": "1",
        "help": "Per-step health digests + online anomaly detection; =0 "
                "leaves engine.health None (one is-None branch on the "
                "step path, nothing else)."},
    "HOROVOD_TPU_STEP_HEALTH_WINDOW": {
        "type": "int", "default": "64",
        "help": "Rolling-baseline window (steps) for the median+MAD "
                "anomaly detector."},
    "HOROVOD_TPU_STEP_HEALTH_WARMUP": {
        "type": "int", "default": "8",
        "help": "Steps of history required before the detector "
                "classifies anything (the warmup gate)."},
    "HOROVOD_TPU_STEP_HEALTH_MAD_K": {
        "type": "float", "default": "3.0",
        "help": "Spike threshold in MADs above the rolling median; "
                "sustained regressions use half of it."},
    "HOROVOD_TPU_STEP_HEALTH_DUMP_INTERVAL": {
        "type": "float", "default": "60.0",
        "help": "Minimum seconds between automatic flight-recorder "
                "dumps (anomaly- and elastic-restore-triggered; the "
                "watchdog's one-shot escalation dump is not rate-"
                "limited)."},
    "HOROVOD_TPU_HBM": {
        "type": "bool", "default": "1",
        "help": "Sample device.memory_stats() on the metrics-emitter "
                "thread (hvd_tpu_hbm_bytes gauges + digest watermark); "
                "platforms without memory stats auto-disable."},
    # -- hierarchical telemetry ---------------------------------------------
    "HOROVOD_TPU_AGG_ENABLE": {
        "type": "bool", "default": "1",
        "help": "Per-slice telemetry aggregators: each slice's lowest "
                "rank hosts a SliceAggregator that pre-merges the "
                "slice's metrics/trace/stall publishes and rolls one "
                "payload per stream per interval to the root (O(slices) "
                "root load); no-op on flat topologies."},
    "HOROVOD_TPU_AGG_INTERVAL": {
        "type": "float", "default": "5.0",
        "help": "Seconds between a slice aggregator's rollup pushes to "
                "the root KV."},
    "HOROVOD_TPU_AGG_CARDINALITY": {
        "type": "choice", "default": "rank",
        "choices": ("rank", "slice"),
        "help": "Metrics rollup shape: 'rank' preserves per-rank "
                "snapshots inside the slice rollup; 'slice' pre-sums "
                "them into one synthetic slice<k> series set (cheaper "
                "root scrape, loses rank attribution)."},
    "HOROVOD_TPU_AGG_FALLBACK": {
        "type": "bool", "default": "1",
        "help": "When a slice aggregator is unreachable, publishers "
                "degrade to direct-to-root (counted in "
                "hvd_tpu_agg_fallback_total, WARNING on first flip); "
                "=0 raises the publish error to the caller instead."},
    # -- timeline -----------------------------------------------------------
    "HOROVOD_TIMELINE": {
        "type": "str", "default": "",
        "help": "Chrome-trace timeline output path (rank>0 suffixes "
                ".rank<r>)."},
    "HOROVOD_TIMELINE_MARK_CYCLES": {
        "type": "bool", "default": "0",
        "help": "Mark engine cycle boundaries in the timeline."},
    # -- fault injection ----------------------------------------------------
    "HOROVOD_TPU_FAULTS": {
        "type": "spec", "default": "",
        "help": "Failpoint spec string "
                "(name[@rank]=N*action(args)->..., docs/"
                "fault_tolerance.md); unset leaves every failpoint a "
                "no-op."},
    # -- elastic ------------------------------------------------------------
    "HOROVOD_ELASTIC": {
        "type": "bool", "default": "0",
        "help": "Elastic mode: tighter failure-detection timeouts and "
                "re-rendezvous on membership changes."},
    "HOROVOD_ELASTIC_TIMEOUT": {
        "type": "float", "default": "600",
        "help": "Seconds to wait for the elastic world to (re)form "
                "before giving up (falls back to "
                "HOROVOD_GLOO_TIMEOUT_SECONDS)."},
    "HOROVOD_ELASTIC_MAX_RUNTIME_RETRIES": {
        "type": "int", "default": "3",
        "help": "Consecutive raw-runtime failures the elastic run-loop "
                "recovers before escalating (resets on commit "
                "progress)."},
    "HOROVOD_ELASTIC_FAILURE_BACKOFF": {
        "type": "float", "default": "5.0",
        "help": "Base seconds a repeatedly-failing slot is suspended "
                "before re-admission (doubles per strike)."},
    "HOROVOD_ELASTIC_SLOT_FAILURE_LIMIT": {
        "type": "int", "default": "4",
        "help": "Slot failure strikes before the host is blacklisted "
                "for good."},
    "HOROVOD_TPU_DRIVER_JOURNAL": {
        "type": "bool", "default": "1",
        "help": "Journal every elastic-driver state transition through "
                "the replicated 'driver' KV scope so a standby can "
                "reconstruct the driver after a crash (elastic/"
                "failover.py). On by default; only effective when the "
                "rendezvous server is replication-enabled."},
    "HOROVOD_TPU_DRIVER_LEASE_TIMEOUT": {
        "type": "float", "default": "2.0",
        "help": "Seconds the driver's journaled lease heartbeat may go "
                "stale before a standby considers the driver dead and "
                "promotes. Distinct from HOROVOD_KV_LEASE_TIMEOUT: that "
                "elects a new primary replica, this elects a new elastic "
                "driver on top of it."},
    "HOROVOD_TPU_DRIVER_LEASE_INTERVAL": {
        "type": "float", "default": "0.5",
        "help": "Seconds between driver lease heartbeats written to the "
                "journal scope (paced by the discovery loop)."},
    # -- attention / Pallas kernels -----------------------------------------
    "HOROVOD_SPLASH": {
        "type": "choice", "default": "1",
        "choices": ("0", "1", "force", "true", "false", "yes", "no",
                    "on", "off"),
        "help": "Splash-attention kernel for local attention: 0 off "
                "(the stock flash kernel instead), 1 on wherever the "
                "shape allows (on a TPU); force reads as 1 since the "
                "under-remat degrade it overrode is gone; boolean "
                "aliases accepted in both directions, unknown tokens "
                "warn and take the default."},
    "HOROVOD_RING_PALLAS": {
        "type": "bool", "default": "1",
        "help": "Pallas blockwise kernel inside ring attention; =0 "
                "forces the pure-JAX fallback."},
    "HOROVOD_RING_CHUNK": {
        "type": "int", "default": "512",
        "help": "KV chunk rows per ring-attention step."},
    "HOROVOD_RING_SEG_BLOCK": {
        "type": "int", "default": "1024",
        "help": "Preferred segment block size for the ring-attention "
                "Pallas kernel."},
    "HOROVOD_ADASUM_PALLAS": {
        "type": "bool", "default": "0",
        "help": "Pallas fused dot/norm kernel inside Adasum combine "
                "(TPU only)."},
    "HOROVOD_PALLAS_PACK": {
        "type": "bool", "default": "0",
        "help": "Pallas fusion-buffer pack kernel for grouped "
                "collectives (also an autotune categorical)."},
    # -- logging ------------------------------------------------------------
    "HOROVOD_LOG_LEVEL": {
        "type": "str", "default": "warning",
        "help": "Framework log level (trace/debug/info/warning/error/"
                "fatal)."},
    # -- launcher / rendezvous plumbing (set by tpurun & the elastic
    #    driver; users rarely set these directly) ---------------------------
    "HOROVOD_GLOO_RENDEZVOUS_ADDR": {
        "type": "str", "default": "", "internal": True,
        "help": "Rendezvous/KV server address the launcher hands to "
                "workers."},
    "HOROVOD_GLOO_RENDEZVOUS_PORT": {
        "type": "int", "default": "", "internal": True,
        "help": "Rendezvous/KV server port."},
    "HOROVOD_GLOO_TIMEOUT_SECONDS": {
        "type": "float", "default": "600", "internal": True,
        "help": "Rendezvous long-poll / KV operation timeout."},
    "HOROVOD_GLOO_IFACE": {
        "type": "str", "default": "", "internal": True,
        "help": "Network interface advertised for worker-to-worker "
                "control connections."},
    "HOROVOD_HOSTNAME": {
        "type": "str", "default": "derived", "internal": True,
        "help": "This worker's hostname as assigned by the launcher."},
    "HOROVOD_RANK": {
        "type": "int", "default": "0", "internal": True,
        "help": "This worker's world rank (launcher-assigned)."},
    "HOROVOD_SIZE": {
        "type": "int", "default": "derived", "internal": True,
        "export": True,
        "help": "World size, exported to worker environments (the "
                "framework itself reads HOROVOD_TPU_NUM_PROCESSES)."},
    "HOROVOD_LOCAL_RANK": {
        "type": "int", "default": "0", "internal": True,
        "help": "Rank within this host."},
    "HOROVOD_LOCAL_SIZE": {
        "type": "int", "default": "1", "internal": True,
        "help": "Workers on this host."},
    "HOROVOD_CROSS_RANK": {
        "type": "int", "default": "derived", "internal": True,
        "help": "This host's index across hosts."},
    "HOROVOD_CROSS_SIZE": {
        "type": "int", "default": "derived", "internal": True,
        "help": "Number of hosts."},
    "HOROVOD_TASK_SECRET": {
        "type": "str", "default": "", "internal": True,
        "help": "Hex job secret signing task-agent RPCs (stripped from "
                "worker environments)."},
    "HOROVOD_TPU_SHARED_FS": {
        "type": "bool", "default": "0", "internal": True,
        "help": "Acknowledge that the programmatic-run tempdir is on a "
                "filesystem shared by every remote host."},
    "HOROVOD_TPU_COORDINATOR": {
        "type": "str", "default": "", "internal": True,
        "help": "host:port of the JAX distributed coordinator."},
    "HOROVOD_TPU_NUM_PROCESSES": {
        "type": "int", "default": "derived", "internal": True,
        "help": "Process count for jax.distributed.initialize."},
    "HOROVOD_TPU_PROCESS_ID": {
        "type": "int", "default": "derived", "internal": True,
        "help": "This process's id for jax.distributed.initialize "
                "(falls back to HOROVOD_RANK)."},
    "HOROVOD_TPU_WORLD_VERSION": {
        "type": "int", "default": "0", "internal": True,
        "help": "Elastic world version the rendezvous stamps on every "
                "re-init; replay and prefetch invalidate when it bumps."},
    "HOROVOD_TPU_HEARTBEAT_TIMEOUT": {
        "type": "int", "default": "100 (10 when elastic)",
        "internal": True,
        "help": "Coordination-service heartbeat timeout in seconds."},
    "HOROVOD_TPU_SHUTDOWN_TIMEOUT": {
        "type": "int", "default": "300 (30 when elastic)",
        "internal": True,
        "help": "Coordination-service shutdown timeout in seconds."},
    "HOROVOD_TPU_SHUTDOWN_ORDER_TIMEOUT": {
        "type": "float", "default": "10", "internal": True,
        "help": "Seconds rank 0 waits for peers' disconnect flags before "
                "shutting the coordination service (coordinator-last "
                "teardown)."},
}
