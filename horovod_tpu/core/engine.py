"""Eager collective engine: the TPU-native analog of the reference's core
runtime loop.

The reference funnels every framework op through EnqueueTensorAllreduce/...
(operations.cc:824-1040) into a TensorQueue drained by a background thread that
negotiates, fuses, and launches NCCL/MPI kernels (operations.cc:354-616). Under
JAX none of that machinery is needed for correctness: dispatch is already
asynchronous (the XLA runtime queues work on device streams) and SPMD execution
makes cross-rank readiness implicit. What remains, and lives here:

- **Handle-based async API** (parity: torch/handle_manager.{h,cc} +
  torch/mpi_ops.py poll/synchronize): every op returns a handle; ``poll`` maps
  to ``jax.Array`` readiness, ``synchronize`` to ``block_until_ready``.
- **Duplicate-name detection** (common.h:163-166 DUPLICATE_NAME_ERROR).
- **Fusion/bucketing** for grouped ops (controller.cc:652-773 FuseResponses +
  fusion_buffer_manager): tensors are packed into <= threshold-byte buckets per
  dtype and reduced with one collective launch per bucket.
- **Builder cache** (the jit-compile analog of the ResponseCache,
  response_cache.h:45-102): steady-state ops skip all Python-side setup.
- **Timeline + stall-inspector hooks** around enqueue/completion.
"""

from __future__ import annotations

import logging
import os
import re
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..common import env as env_mod
from ..common import scopes
from ..common.exceptions import DuplicateNameError, HorovodInternalError
from ..faults import failpoint
from ..common.lru import lru_get, lru_put, lru_touch
from ..common.reduce_ops import ReduceOp
from ..metrics import registry as metrics_registry
from ..ops import collectives as C
from ..ops import compression as comp
from ..parallel.mesh import WORLD_AXIS, detect_topology
from .backend import Backend

# residual-lineage name templates share replay's digit normalization
# ("grad.s17" and "grad.s18" are the same logical per-step call)
_DIGITS = re.compile(r"\d+")


def _translate_failure(fn, *args, **kwargs):
    """Run a dispatch/completion call, converting runtime failures into
    HorovodInternalError — the exception the elastic run-loop catches to
    restore committed state and re-rendezvous (ADVICE r1-high; reference
    behavior: framework ops wrap core failures in HorovodInternalError).

    Only execution-boundary calls are wrapped (jitted collective dispatch,
    block_until_ready/is_ready); argument validation raises before reaching
    here, so a ValueError here is a collective failure (e.g. XLA's
    "Gloo all-reduce failed ... Connection closed by peer" surfaces as
    ValueError), not a user error."""
    try:
        return fn(*args, **kwargs)
    except (DuplicateNameError, HorovodInternalError):
        raise
    except Exception as e:
        raise HorovodInternalError(
            f"collective execution failed (peer crashed or runtime error): "
            f"{type(e).__name__}: {e}") from e


def _check_average_dtype(x, op):
    """User-argument validation must precede dispatch so it surfaces as a
    plain ValueError, not a translated HorovodInternalError (parity with the
    reference frontends' integer-average rejection)."""
    if op == ReduceOp.AVERAGE and jnp.issubdtype(x.dtype, jnp.integer):
        raise ValueError(
            "Averaging is not supported for integer tensors; use op=Sum "
            "(parity with the reference frontends' integer-average rejection)")


class LaunchGroup:
    """Shared completion latch for every handle born from one fused launch.

    All outputs of a single jitted program complete together, so readiness of
    one representative output implies readiness of all — one is_ready /
    block_until_ready RPC per *launch* instead of per tensor (the role of the
    reference's single completion event per fused buffer,
    gpu_operations.cc:47-87 FinalizeGPUQueue)."""

    __slots__ = ("_rep", "_done", "_lock")

    _GUARDED_BY = {"_done": "_lock"}

    def __init__(self, representative: jax.Array):
        self._rep = representative
        self._done = False
        self._lock = threading.Lock()

    def ready(self) -> bool:
        # lockcheck: ignore[double-checked fast path: _done only transitions False->True, a stale read just re-polls]
        if self._done:
            return True
        ok = _translate_failure(self._rep.is_ready)
        if ok:
            # lockcheck: ignore[monotonic latch: concurrent True writes are idempotent]
            self._done = True
        return ok

    def wait(self):
        # lockcheck: ignore[double-checked fast path: a stale read falls through to the locked re-check]
        if not self._done:
            with self._lock:
                if not self._done:
                    # lockcheck: ignore[deliberate: the lock serializes waiters so one blocks and the rest inherit completion]
                    _translate_failure(self._rep.block_until_ready)
                    self._done = True


class Handle:
    """Async op handle. Readiness *is* the underlying jax.Array's readiness
    (replaces ReadyEvent + finalizer thread, gpu_operations.cc:47-87).
    Completion is driven both by the user (poll/synchronize) and by the
    engine's cycle loop, so fire-and-forget ops still clear the outstanding
    table and feed the stall inspector/timeline."""

    __slots__ = ("name", "_garrs", "_extract", "_engine", "_done", "_result",
                 "_error", "_finish_lock", "enqueue_time", "_enqueue_mono",
                 "recv_sizes", "_group", "kind")

    def __init__(self, name: str, garrs: List[jax.Array], extract: Callable,
                 engine: "Engine", group: Optional[LaunchGroup] = None,
                 kind: Optional[str] = None):
        # op kind for the enqueue->complete latency histogram (None skips
        # the observation — e.g. externally-constructed handles)
        self.kind = kind
        self.name = name
        self._garrs = garrs
        self._extract = extract
        self._engine = engine
        self._group = group
        self._done = False
        self._result = None
        self._error = None
        self._finish_lock = threading.Lock()
        self.enqueue_time = time.time()
        # monotonic twin of enqueue_time for the latency histogram (a wall
        # clock can step backwards and corrupt histogram sums)
        self._enqueue_mono = time.monotonic()
        self.recv_sizes = None  # per-rank dim-0 sizes for allgather results

    def poll(self) -> bool:
        if self._done:
            return True
        if self._group is not None:
            ready = self._group.ready()
        else:
            ready = all(_translate_failure(g.is_ready)
                        for g in self._garrs
                        if hasattr(g, "is_ready"))
        if ready:
            self._finish()
        return self._done

    def synchronize(self):
        # the user-visible completion edge: a hang armed here stalls the
        # training loop exactly like a peer that stopped contributing
        failpoint("engine.complete")
        self._engine._check_poison()
        # poll() first: if the arrays are already ready (the cycle thread
        # just hasn't retired the handle yet) this is not a blocking wait
        # and must not count as one (ADVICE r4 — host_blocks is the
        # "actual blocking waits" counter the chained-eager tests assert on)
        if not self._done and not self.poll():
            self._engine.host_blocks += 1
            with scopes.host_span(scopes.ENGINE_WAIT):
                if self._group is not None:
                    self._group.wait()
                else:
                    for g in self._garrs:
                        _translate_failure(g.block_until_ready)
            self._finish()
        if self._error is not None:
            raise self._error
        return self._result

    def result(self):
        """Extract the result WITHOUT a host block.

        The returned values are ``jax.Array`` futures: anything dispatched on
        them is ordered after this collective by XLA dataflow, so chaining an
        optimizer update onto them needs no ``synchronize()`` — dataflow *is*
        the synchronization (the role the reference fills with per-parameter
        hooks + synchronize() in torch/optimizer.py:100-135; under JAX the
        runtime's async dispatch gives the overlap for free). Errors surface
        on whichever later op first touches the value. ``synchronize()``
        remains the user-facing Horovod-blocking API."""
        if not self._done:
            # extract once, under the finish lock, and keep it: the cycle
            # thread's later _finish reuses this instead of re-running the
            # extraction (which can carry slice dispatches or a tiny flag
            # fetch) a second time on the hot path
            with self._finish_lock:
                if not self._done and self._result is None \
                        and self._error is None:
                    try:
                        self._result = self._extract(self._garrs)
                    except Exception as e:
                        self._error = e
        if self._error is not None:
            raise self._error
        return self._result

    def _finish(self):
        with self._finish_lock:
            if self._done:
                return
            try:
                if self._result is None and self._error is None:
                    self._result = self._extract(self._garrs)
            # errflow: ignore[the error is attached to the handle — every later synchronize()/result() re-raises it (handle-manager semantics)]
            except Exception as e:
                # A permanently-failed extract (e.g. the deferred size-cache
                # check) retires WITH the error attached: the handle leaves
                # the outstanding table and every later synchronize()/
                # result() re-raises — a one-shot raise would let the cycle
                # thread consume it and later reads return garbage
                # (handle-manager error semantics, torch/handle_manager.cc).
                self._error = e
            self._done = True
        self._engine._on_complete(self)


class HandleManager:
    """int handle -> Handle map (parity: torch/handle_manager.{h,cc})."""

    _GUARDED_BY = {"_next": "_lock", "_handles": "_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self._next = 0
        self._handles: Dict[int, Handle] = {}

    def allocate(self, h: Handle) -> int:
        with self._lock:
            hid = self._next
            self._next += 1
            self._handles[hid] = h
            return hid

    def get(self, hid: int) -> Handle:
        with self._lock:
            if hid not in self._handles:
                raise ValueError(f"unknown handle {hid}")
            return self._handles[hid]

    def release(self, hid: int):
        with self._lock:
            self._handles.pop(hid, None)


# Join-protocol metadata encoding (operations.cc:1004-1040 EnqueueTensorJoin;
# zero-tensor substitution tensor_queue.h:39-41). A joined rank learns each
# pending op's (kind, op/root, dtype, shape) from these rows and dispatches a
# matching zero-tensor launch until every rank has joined.
_KIND_CODES = {"allreduce": 1, "grouped_allreduce": 2, "allgather": 3,
               "broadcast": 4, "alltoall": 5, "reducescatter": 6,
               "barrier": 7, "adasum": 8, "grouped_broadcast": 9,
               "sharded_step": 10, "grouped_alltoall": 11}
_DTYPE_CODES = {"float32": 1, "float64": 2, "float16": 3, "bfloat16": 4,
                "int8": 5, "int16": 6, "int32": 7, "int64": 8,
                "uint8": 9, "uint16": 10, "uint32": 11, "uint64": 12,
                "bool": 13}
_CODE_DTYPES = {v: k for k, v in _DTYPE_CODES.items()}
_JOIN_META_DIMS = 7
_JOIN_META_LEN = 3 + _JOIN_META_DIMS  # [op_or_root, dtype, ndim, d0..d6]
# Metadata rows carried inline in the fixed-shape join round (the round's
# shape must be identical on every rank *including ranks sitting in join()
# that cannot know k in advance*, so it is padded to a fixed slot count;
# ops with more tensors spill into one overflow exchange whose size both
# sides derive deterministically from the head). 16 slots keep the head at
# ~1.3 KB — single-tensor ops dominate, and large grouped calls pay one
# extra (still async) overflow dispatch.
_JOIN_META_SLOTS = int(os.environ.get("HOROVOD_JOIN_META_SLOTS", "16"))
_JOIN_HEAD_LEN = 4 + _JOIN_META_SLOTS * _JOIN_META_LEN


def _join_meta_row(x, op_or_root: int) -> np.ndarray:
    code = _DTYPE_CODES.get(str(x.dtype))
    if code is None:
        raise ValueError(f"dtype {x.dtype} unsupported under the Join "
                         f"protocol; set HOROVOD_JOIN_DISABLE=1")
    if x.ndim > _JOIN_META_DIMS:
        raise ValueError(f"ndim {x.ndim} > {_JOIN_META_DIMS} unsupported "
                         f"under the Join protocol")
    dims = [int(d) for d in x.shape] + [-1] * (_JOIN_META_DIMS - x.ndim)
    return np.array([op_or_root, code, x.ndim] + dims, dtype=np.int64)


class Engine:
    # lock discipline (tools/check.py lockcheck): the outstanding-op table
    # is shared between the user thread, the cycle loop, and handle
    # completion; the ZeRO-1 prefetch registry is mutated by the dispatch
    # path and invalidated from replay/join/elastic edges. Everything else
    # on the engine (builders, meta cache, replay state, counters) is
    # dispatching-thread-only by design (see StepReplay's docstring).
    _GUARDED_BY = {
        "_outstanding": "_lock",
        "_zero1_prefetch": "_lock",
        "_ef_residuals": "_lock",
    }

    def __init__(self, backend: Backend, config: env_mod.Config):
        self.backend = backend
        self.config = config
        self.handles = HandleManager()
        self._builders: Dict[tuple, Callable] = {}
        self._outstanding: Dict[str, Handle] = {}
        self._lock = threading.Lock()
        self._auto_counter = {}
        # blocking metadata read-backs performed (see _fetch_exchange);
        # the steady-state eager allreduce path must not grow this
        self.host_fetches = 0
        # blocking result waits (Handle.synchronize reaching an actual wait);
        # the chained eager optimizer path must not grow this either
        self.host_blocks = 0
        # steady-state metadata cache (ResponseCache role for allgather
        # sizes / alltoall splits, response_cache.h:45-102): name -> last
        # world observation + streak; hot entries skip the blocking exchange
        self._meta_cache: Dict[tuple, dict] = {}
        # deferred (extract-time) verifications of cached metadata performed
        self.deferred_meta_checks = 0
        # observability hooks, wired by GlobalState when timeline/stall are on
        self.on_enqueue: Optional[Callable[[str, str, int], None]] = None
        self.on_done: Optional[Callable[[str], None]] = None
        # cross-rank trace recorder (horovod_tpu/trace.py), wired by
        # GlobalState unless HOROVOD_TPU_TRACE=0: stamps every collective
        # with a deterministic correlation id at enqueue and records the
        # enqueue/dispatch/complete phases into a bounded ring. When None
        # (tracing off) each hook site below is a single is-None check —
        # the HOROVOD_TPU_METRICS=0 no-new-locking guarantee.
        self.trace = None
        # step-health monitor (horovod_tpu/observability/, ISSUE 20):
        # wired by GlobalState unless HOROVOD_TPU_STEP_HEALTH=0. Same
        # discipline as trace: when None, step_end pays exactly one
        # is-None branch and nothing else.
        self.health = None
        # per-activity sub-span hook (timeline ACTIVITY events, the nested
        # spans of timeline.h:77 NEGOTIATING->TOP_LEVEL->ACTIVITY)
        self.on_activity: Optional[Callable[[str, str, float], None]] = None
        # autotuner (parameter_manager.h): wired by GlobalState when
        # HOROVOD_AUTOTUNE=1; scores throughput per drain-cycle and retunes
        # fusion_threshold / cycle_time
        self.parameter_manager = None
        self._pm_marked_token = -1
        # engine-issued XLA program launches (collectives, packs, metadata
        # exchanges, replay steps); the bench's dispatch-count attribution
        # of the eager-vs-SPMD gap reads deltas of this
        self.dispatch_count = 0
        # metrics registry instruments (horovod_tpu/metrics.py). With
        # HOROVOD_TPU_METRICS=0 every instrument is a shared lock-free
        # no-op and _m_enabled short-circuits the bookkeeping branches, so
        # the dispatch hot path takes no per-dispatch lock.
        _reg = metrics_registry()
        self._m_enabled = _reg.enabled
        self._m_dispatches = _reg.counter("hvd_tpu_dispatches_total")
        self._m_wire = _reg.counter("hvd_tpu_wire_bytes_total")
        self._m_collectives = _reg.counter("hvd_tpu_collectives_total")
        self._m_buckets = _reg.counter("hvd_tpu_fusion_buckets_total")
        self._m_bucket_bytes = _reg.counter("hvd_tpu_fusion_bucket_bytes_total")
        self._m_fill = _reg.gauge("hvd_tpu_fusion_bucket_fill_pct")
        self._m_latency = _reg.histogram("hvd_tpu_op_latency_seconds")
        # elastic world identity: an elastic reset re-inits with a bumped
        # HOROVOD_TPU_WORLD_VERSION; the step-replay subsystem invalidates
        # every armed stream when this moves
        self.world_version = int(
            os.environ.get("HOROVOD_TPU_WORLD_VERSION", "0") or 0)
        # ZeRO-1 sharded optimizer steps: update_key -> shard-update closure
        # (the traceable rs->update->ag middle phase); the replay builder
        # resolves keys here so a captured sharded step can fuse the update
        # into the single replayed launch
        self._sharded_updates: Dict[tuple, Callable] = {}
        # Bucket-pipelined comm/compute overlap (ISSUE 6): the env-resolved
        # base mode ("auto"/"interleave"/"staged"; an explicit "off" leaves
        # "auto" as the base so the autotune categorical can still explore
        # turning overlap ON), plus the held ZeRO-1 all-gather prefetch
        # legs — update_key -> {"world_version"} — that ride across step
        # boundaries and are invalidated on world-version bumps exactly
        # like replay streams. The registry records only the accounting
        # row: the leg's buffers stay alive through its consumers'
        # dataflow futures, never through the engine.
        self._overlap_base = (config.overlap_pipeline
                              if config.overlap_pipeline != "off"
                              else "auto")
        # Topology-aware collective algorithm selection (ISSUE 10): the
        # fabric descriptor is resolved ONCE per engine (an elastic reset
        # builds a fresh engine, so a resized world re-detects) and
        # threaded to every builder through _choose_algo. The autotune
        # categorical toggles the env-resolved base vs flat, the
        # overlap_pipeline pattern. The group mesh holds exactly one
        # device per RANK, so probing its slice_index attributes yields
        # ranks-per-slice (the engine's unit) — probing all local chips
        # would conflate devices-per-slice with ranks-per-slice on
        # multi-chip-per-process worlds.
        group_devs = (list(backend.group_mesh.devices.flat)
                      if backend.group_mesh is not None else None)
        self.topology = detect_topology(size=backend.size(),
                                        local_size=backend.local_size(),
                                        devices=group_devs)
        self._algo_base = (config.collective_algo
                           if config.collective_algo != "flat" else "auto")
        # Pallas fusion-pack knob resolved ONCE here (divcheck
        # capture-impure-read fix): the per-call env read on the grouped
        # dispatch path let a mid-run HOROVOD_PALLAS_PACK flip switch the
        # launch structure between two otherwise-identical steps — under
        # an armed replay stream some calls would diverge from the stream
        # they were captured from. Knobs resolve at init; live retuning
        # stays with the broadcast-synced autotune categorical.
        from ..ops.pallas_kernels import pack_pallas_enabled
        self._pack_pallas_base = pack_pallas_enabled()
        self._m_algo = _reg.counter("hvd_tpu_collective_algo_total")
        # Link-aware gradient compression (ISSUE 13): the wire-codec base
        # is resolved ONCE here (divcheck discipline — the autotune
        # categorical toggles it live, broadcast-synced); error-feedback
        # residual buffers live in _ef_residuals, keyed per logical
        # fusion bucket, written on the dispatch path and invalidated
        # from replay/join/elastic edges exactly like the ZeRO-1
        # prefetch legs (invalidate, never poison).
        self._codec_base = config.compression
        self._m_codec = _reg.counter("hvd_tpu_compression_codec_total")
        self._m_saved = _reg.counter(
            "hvd_tpu_compression_bytes_saved_total")
        self._m_res_inval = _reg.counter(
            "hvd_tpu_compression_residual_invalidations_total")
        self._ef_residuals: Dict[tuple, dict] = {}
        self._zero1_prefetch: Dict[tuple, dict] = {}
        self._in_step_bracket = False
        self._overlap_step_noted = False
        self._m_overlap_stages = _reg.counter(
            "hvd_tpu_overlap_stage_launches_total")
        self._m_overlap_steps = _reg.counter("hvd_tpu_overlap_steps_total")
        self._m_prefetch = _reg.counter("hvd_tpu_overlap_prefetch_total")
        self._m_prefetch_inval = _reg.counter(
            "hvd_tpu_overlap_prefetch_invalidations_total")
        # step-capture replay (core/replay.py): records the dispatch stream
        # between step_begin/step_end and re-executes steady-state steps as
        # one fused launch
        from .replay import StepReplay
        self._replay = StepReplay(self)
        # replay observability hooks, wired by GlobalState
        self.on_replay: Optional[Callable[[str, str], None]] = None
        self.replay_fallback_counter: Optional[Callable[[str], None]] = None
        # join()-idleness hook (wired to the stall inspector): a rank
        # parked in join() legitimately stops advancing its step
        # heartbeat, and the collective watchdog's peer leg must not
        # mistake that for a hang
        self.on_join_state: Optional[Callable[[bool], None]] = None
        # checkpoint snapshot hook (ISSUE 9): called with the monotonic
        # completed-step index at every step_end — GlobalState wires it
        # to CheckpointManager.on_step for interval-driven async
        # snapshots riding the step boundary, never the step body
        self.step_index = 0
        self.on_step_complete: Optional[Callable[[int], None]] = None
        self._hier_ok: Optional[bool] = None
        # One-shot flag: the next engine-method call is a Join zero-tensor
        # substitute — it must skip its own join round (the join() loop
        # already ran it) and send wildcard consistency rows (its auto name
        # legitimately differs from the active ranks' tensor name).
        self._join_substitute = False
        # Collective-watchdog poison: once the stall inspector's deadline
        # escalation fires, every subsequent submission/synchronize raises
        # this error instead of hanging behind the wedged collective —
        # the engine is unusable until the elastic reset rebuilds it.
        self._poison: Optional[Exception] = None
        # Resolve the hierarchical-homogeneity agreement EAGERLY, here at
        # init — a collectively-synchronized point every rank reaches
        # before any collective or join() can start. Resolving it lazily
        # at the first selection collided with the Join protocol (the
        # active rank's agreement exchange has no advertisement a joined
        # peer could match), and gating entry on the rank-local topology
        # view would deadlock heterogeneous worlds; one tiny exchange per
        # engine lifetime buys a pure cached read on every later
        # selection.
        if backend.size() > 1:
            self._hierarchical_ok()
        # Measured performance model (ISSUE 14): with HOROVOD_TPU_CALIBRATE
        # the init-time rank-collective probe overlays measured link rates
        # on the nominal topology tables and derives the selection
        # crossovers from the fitted α–β model. Runs HERE — after the
        # homogeneity agreement, before any training collective — so the
        # probe's collectives are in lockstep and every later selection
        # reads calibrated thresholds. Nominal tables are the fallback on
        # size<=1 worlds, disabled probing, or probe failure.
        # The frozen-bucket-layout digest that keys persisted tuning
        # records (autotune/persistence.py); resolved lazily at the first
        # grouped call, when the engine first sees the gradient set.
        self._model_sig: Optional[str] = None
        if config.calibrate and backend.size() > 1:
            self._apply_calibration()
        elif self._m_enabled:
            _reg.gauge("hvd_tpu_topology_calibrated").set(0.0)
        # Cycle loop: the analog of RunLoopOnce (operations.cc:566-616) — wakes
        # every cycle_time_ms to retire completed handles so fire-and-forget
        # async ops clear the outstanding table without user poll/synchronize.
        # Event-paced (not time.sleep + flag): stop() wakes the loop and
        # JOINS it, so an elastic teardown never leaves a zombie cycle
        # thread retiring handles while the next world's engine spins up
        # (errflow leak-on-raise audit; the StallInspector.stop pattern).
        self._cycle_stop = threading.Event()
        self._cycle_thread = threading.Thread(target=self._cycle_loop,
                                              name="hvd-cycle", daemon=True)
        self._cycle_thread.start()

    def stop(self):
        self._cycle_stop.set()
        if self._cycle_thread.is_alive() and \
                threading.current_thread() is not self._cycle_thread:
            self._cycle_thread.join(timeout=10)

    def poison(self, err: Exception):
        """Mark the engine dead (collective-watchdog escalation): every
        later submission, synchronize, barrier, or join raises ``err``.
        Irreversible for this Engine — the elastic reset path builds a
        fresh one."""
        self._poison = err

    def _check_poison(self):
        if self._poison is not None:
            raise self._poison

    def _cycle_loop(self):
        # cycle time is re-read every wait so the autotuner can retune it
        # live (parameter_manager.h:178-220); the Event wait (vs sleep)
        # lets stop() wake and join the loop immediately
        while not self._cycle_stop.wait(
                max(self.config.cycle_time_ms, 1.0) / 1000.0):
            with self._lock:
                pending = list(self._outstanding.values())
            for h in pending:
                try:
                    h.poll()
                except Exception:  # retire errors surface at synchronize time
                    pass

    # -- internals ---------------------------------------------------------

    def _axis(self) -> str:
        return WORLD_AXIS

    def _builder(self, key: tuple, make: Callable):
        # The builder cache is the ResponseCache analog
        # (response_cache.h:45-102); HOROVOD_CACHE_CAPACITY bounds it with
        # LRU eviction, so a working set one entry over capacity doesn't
        # re-trace its hottest builder every cycle (ADVICE r2).
        fn = lru_get(self._builders, key)
        self._last_builder_fresh = fn is None
        if fn is None:
            fn = lru_put(self._builders, key, make(),
                         self.config.cache_capacity)
        return fn

    def _auto_name(self, kind: str) -> str:
        n = self._auto_counter.get(kind, 0)
        self._auto_counter[kind] = n + 1
        return f"{kind}.noname.{n}"

    def _count_dispatch(self):
        """One engine-issued XLA launch: the legacy counter plus the
        registry counter (scraped as hvd_tpu_dispatches_total)."""
        self.dispatch_count += 1
        self._m_dispatches.inc()

    # -- measured performance model (ISSUE 14) -----------------------------

    def _apply_calibration(self):
        """Run the init-time link probe and install the measured overlay:
        topology becomes a MeasuredTopology, and — unless the user pinned
        HOROVOD_TPU_TREE_THRESHOLD_BYTES — the ring/tree and
        flat/hierarchical crossovers become the fitted model's derived
        values. The probe result was cross-rank agreed inside
        calibrate_engine, so the installed thresholds are identical
        everywhere (the selection-determinism invariant)."""
        from ..autotune.calibration import (calibrate_engine,
                                            derived_alltoall_threshold_bytes,
                                            derived_thresholds)
        measured = calibrate_engine(self)
        _reg = metrics_registry()
        if measured is None:
            _reg.gauge("hvd_tpu_topology_calibrated").set(0.0)
            return
        self.topology = measured
        tree_thr, hier_thr = derived_thresholds(measured)
        prov = self.config.provenance
        if prov.get("tree_threshold_bytes") == "env-forced":
            logging.getLogger("horovod_tpu").info(
                "calibration derived tree threshold %d B but "
                "HOROVOD_TPU_TREE_THRESHOLD_BYTES is set; the explicit "
                "knob wins", tree_thr)
        else:
            self.config.tree_threshold_bytes = tree_thr
            prov["tree_threshold_bytes"] = "calibrated"
        self.config.hier_threshold_bytes = hier_thr
        prov["hier_threshold_bytes"] = "calibrated"
        # alltoall's own crossover (ISSUE 17): installed only when the
        # alltoall band actually probed both classes — an unprobed band
        # keeps the nominal default, and an explicit env knob wins.
        a2a_thr = derived_alltoall_threshold_bytes(measured)
        if a2a_thr is not None:
            if prov.get("alltoall_hier_threshold_bytes") == "env-forced":
                logging.getLogger("horovod_tpu").info(
                    "calibration derived alltoall crossover %d B but "
                    "HOROVOD_TPU_ALLTOALL_HIER_THRESHOLD_BYTES is set; "
                    "the explicit knob wins", a2a_thr)
            else:
                self.config.alltoall_hier_threshold_bytes = a2a_thr
                prov["alltoall_hier_threshold_bytes"] = "calibrated"
        _reg.gauge("hvd_tpu_topology_calibrated").set(1.0)
        link_g = _reg.gauge("hvd_tpu_link_gbps")
        link_g.set(measured.ici_gbps, link="ici", source="measured")
        link_g.set(measured.dcn_gbps, link="dcn", source="measured")
        link_g.set(measured.nominal_ici_gbps, link="ici", source="nominal")
        link_g.set(measured.nominal_dcn_gbps, link="dcn", source="nominal")

    def _note_model_sig(self, tensors) -> None:
        """Freeze the model signature at the FIRST grouped call: the
        digest of the gradient set's (shape, dtype) layout — the
        persistence key half that identifies "the same model" across
        restarts and resizes. Shapes only, never names (the optimizer's
        per-step names carry digits) and never values."""
        if self._model_sig is not None or not tensors:
            return
        import hashlib
        text = ";".join(f"{tuple(t.shape)}:{t.dtype}" for t in tensors)
        self._model_sig = hashlib.sha256(text.encode()).hexdigest()

    def model_signature(self) -> Optional[str]:
        """The frozen bucket-layout digest (None before the first grouped
        call)."""
        return self._model_sig

    # -- topology-aware collective algorithm selection (ISSUE 10) ----------

    def _choose_algo(self, kind: str, nbytes: int) -> str:
        """The per-bucket algorithm for one collective of ``kind`` moving
        ``nbytes``: the engine face of ops.collectives.choose_algorithm,
        with two engine-only concerns layered on top — the legacy
        hierarchy env knobs act as a forced preference for their kind,
        and any hierarchical outcome (auto or forced) additionally
        requires the collectively-agreed homogeneity check
        (_hierarchical_ok), because a rank-local topology read can
        diverge on heterogeneous host assignments and selection MUST be
        identical on every rank (the programs must match)."""
        topo = self.topology
        if topo.size <= 1:
            return C.ALGO_FLAT
        # The homogeneity agreement is resolved at engine init (see
        # __init__) so this is a cached read on every path, and it is
        # consulted REGARDLESS of the rank-local topology view: gating
        # the agreement on topo.hierarchical_ok would let heterogeneous
        # worlds diverge (ranks whose local view factorizes entering an
        # exchange flat-view ranks skip — a deadlock). A heterogeneous
        # world uniformly agrees on "no hierarchy".
        hier_ok = self._hierarchical_ok()
        if kind == "alltoall":
            # alltoall has its OWN knob and its own calibrated crossover
            # (ISSUE 17): the dispatch payload's flat-vs-two-phase
            # economics (O(n) vs O(n/slices) DCN chunks) share nothing
            # with the reduction ladder's, so neither the forced
            # collective_algo nor hier_threshold_bytes apply. An unset
            # (0) alltoall threshold means "hierarchical whenever the
            # topology factorizes", same as the reduction default.
            force = self.config.alltoall_algo
            if force != "auto":
                algo = C.validate_algorithm(kind, force, topo.size,
                                            topo.local_size)
            else:
                algo = C.choose_algorithm(
                    kind, nbytes, topo,
                    tree_threshold_bytes=self.config.tree_threshold_bytes,
                    hier_threshold_bytes=(
                        self.config.alltoall_hier_threshold_bytes))
            if algo == C.ALGO_HIERARCHICAL and not hier_ok:
                return C.ALGO_FLAT
            return algo
        force = self.config.collective_algo
        if force != "auto":
            algo = C.validate_algorithm(kind, force, topo.size,
                                        topo.local_size)
        elif kind == "allreduce" and self.config.hierarchical_allreduce \
                and hier_ok:
            algo = C.ALGO_HIERARCHICAL
        elif kind == "allgather" and self.config.hierarchical_allgather \
                and hier_ok:
            algo = C.ALGO_HIERARCHICAL
        else:
            algo = C.choose_algorithm(
                kind, nbytes, topo,
                tree_threshold_bytes=self.config.tree_threshold_bytes,
                hier_threshold_bytes=self.config.hier_threshold_bytes)
        if algo == C.ALGO_HIERARCHICAL and not hier_ok:
            return C.ALGO_FLAT
        return algo

    def _bucket_algos(self, kind: str, tensors, buckets,
                      count: bool = True) -> tuple:
        """Per-fusion-bucket algorithm selection for one grouped call
        (each bucket is its own (bytes, topology) decision — a step's
        small latency-bound bucket can lower to tree while its big
        bucket takes the hierarchical ladder). ``count=True`` records
        the selections in hvd_tpu_collective_algo_total — pass False on
        re-derivations of the same call's choice."""
        algos = tuple(
            self._choose_algo(kind, sum(tensors[i].nbytes for i in idxs))
            for idxs in buckets)
        if count and self._m_enabled:
            for a in algos:
                self._m_algo.inc(kind=kind, algo=a)
        return algos

    def _algo_sig(self) -> tuple:
        """Knob state the algorithm selection depends on — compared to
        detect a mid-call (autotune sample boundary) flip and by replay
        to re-arm on any move."""
        cfg = self.config
        return (cfg.collective_algo, cfg.tree_threshold_bytes,
                cfg.hier_threshold_bytes,
                cfg.hierarchical_allreduce, cfg.hierarchical_allgather,
                cfg.compression,
                # alltoall selection knobs (ISSUE 17): an algo/codec/
                # threshold move must re-arm a2a replay segments
                cfg.alltoall_algo, cfg.alltoall_codec,
                cfg.alltoall_hier_threshold_bytes,
                # pipeline schedule knobs (ISSUE 16): a schedule or codec
                # move changes the captured step program, so replay must
                # re-warm on the same edge the collective knobs use
                cfg.pipeline_schedule, cfg.pipeline_virtual_stages,
                cfg.pipeline_boundary_codec)

    # -- link-aware gradient compression (ISSUE 13) ------------------------

    def _call_codec(self, override: Optional[str],
                    op: Optional[ReduceOp] = None) -> str:
        """The call-level wire codec: the explicit per-call override (the
        optimizer's ``compression=`` argument, carried in the replay sig
        so armed programs match) or the engine knob
        (HOROVOD_TPU_COMPRESSION / the autotune categorical). "none" on
        size<=1 worlds and for non-additive reductions — only SUM and
        AVERAGE have a decode-sum decomposition."""
        if self.topology.size <= 1:
            return comp.CODEC_NONE
        if op is not None and op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
            return comp.CODEC_NONE
        base = override if override is not None else self.config.compression
        return base if base in comp.CODECS else comp.CODEC_NONE

    def _bucket_codecs(self, kind: str, tensors, buckets, call_codec: str,
                       count: bool = True) -> tuple:
        """Per-fusion-bucket codec resolution (deterministic in
        (call codec, bucket dtype) — every rank resolves the same
        program; non-float buckets are never quantized). ``count=True``
        records the selections in hvd_tpu_compression_codec_total."""
        if call_codec == comp.CODEC_NONE:
            return (comp.CODEC_NONE,) * len(buckets)
        out = tuple(comp.resolve_codec(call_codec,
                                       tensors[idxs[0]].dtype)
                    for idxs in buckets)
        if count and self._m_enabled:
            for c in out:
                self._m_codec.inc(kind=kind, codec=c)
        return out

    def _a2a_codecs(self, tensors, buckets, algos,
                    count: bool = True) -> tuple:
        """Per-bucket wire codec for an alltoall dispatch group (ISSUE
        17): the HOROVOD_TPU_ALLTOALL_CODEC knob resolved per bucket
        dtype — but ONLY for hierarchical buckets, because the codec
        applies to the cross-slice DCN leg and a flat bucket has no
        slow-link leg to encode (the ISSUE 13 placement rule). Stateless
        (no error feedback): dispatched tokens have no step-over-step
        identity for a residual to telescope against."""
        base = self.config.alltoall_codec
        if base == comp.CODEC_NONE or self.topology.size <= 1:
            return (comp.CODEC_NONE,) * len(buckets)
        out = tuple(
            comp.resolve_codec(base, tensors[idxs[0]].dtype)
            if algo == C.ALGO_HIERARCHICAL else comp.CODEC_NONE
            for idxs, algo in zip(buckets, algos))
        if count and self._m_enabled:
            for c in out:
                if c != comp.CODEC_NONE:
                    self._m_codec.inc(kind="alltoall", codec=c)
        return out

    def _a2a_links(self, tensors, buckets, algos, codecs):
        """Per-tensor link-byte split for alltoall dispatch traffic —
        :meth:`_tensor_links` with the kind="alltoall" split, which
        additionally needs the world size (C = size/local_size slices
        set the (C-1)/C DCN share of the block transpose). Same
        None-when-nobody-consumes contract."""
        if self.topology.size <= 1 or not tensors:
            return None
        if not self._m_enabled and self.trace is None:
            return None
        local = self.topology.local_size
        size = self.topology.size
        links = [None] * len(tensors)
        for idxs, algo, codec in zip(buckets, algos, codecs):
            for i in idxs:
                links[i] = C.link_split(
                    algo, tensors[i].nbytes, local, kind="alltoall",
                    codec=codec,
                    itemsize=jnp.dtype(tensors[i].dtype).itemsize,
                    size=size)
        return links

    def _residual_key(self, tag: str, name: Optional[str], bucket: int,
                      algo: str, codec: str, elems: int,
                      dtype_str: str) -> tuple:
        """Identity of one error-feedback residual lineage: the
        digit-normalized call name (the optimizer's per-step names
        collapse to one template) plus the bucket's position, lowering,
        codec, and shape. Replay's armed programs derive the same keys
        from their captured sigs, so residual lineage carries across the
        eager-warmup -> replay transition for single-call steps."""
        return (tag, _DIGITS.sub("#", name or ""), bucket, algo, codec,
                int(elems), dtype_str)

    def _grouped_residuals(self, tag: str, name: Optional[str], tensors,
                           buckets, algos, codecs) -> list:
        """Residual bookkeeping rows for one grouped call: ``(bucket,
        key, elems, dtype)`` per error-feedback bucket, in bucket order —
        exactly the order the builders append residual I/O in."""
        out = []
        n = self.topology.size
        local = self.topology.local_size
        for b, (idxs, algo, codec) in enumerate(zip(buckets, algos,
                                                    codecs)):
            if codec not in comp.EF_CODECS:
                continue
            total = sum(int(tensors[i].size) for i in idxs)
            elems = C.codec_residual_elems("reduce", total, n, local,
                                           algo, codec)
            dt = str(tensors[idxs[0]].dtype)
            out.append((b, self._residual_key(tag, name, b, algo, codec,
                                              elems, dt), elems, dt))
        return out

    def _residual_fetch(self, key: tuple, elems: int, dtype):
        """This rank's residual buffer for one EF bucket — zeros on first
        use, after invalidation, or on any shape drift (a fusion-layout
        move makes the old residual meaningless; starting fresh only
        costs one step of compression error)."""
        with self._lock:
            ent = self._ef_residuals.get(key)
            if ent is not None and ent["world_version"] == \
                    self.world_version:
                buf = ent["buf"]
                if int(buf.shape[0]) == int(elems):
                    return buf
        return jnp.zeros((int(elems),), jnp.dtype(dtype))

    def _residual_store(self, key: tuple, garr) -> None:
        # from_replicated is a zero-dispatch shard read: the stored value
        # is this rank's own new residual (the P() out-spec claims
        # replication the world-view convention never relies on)
        buf = self.backend.from_replicated(garr)
        with self._lock:
            self._ef_residuals[key] = {
                "world_version": self.world_version, "buf": buf}
            while len(self._ef_residuals) > self.config.cache_capacity:
                self._ef_residuals.pop(next(iter(self._ef_residuals)))

    def invalidate_residuals(self, reason: str) -> None:
        """Drop every error-feedback residual buffer (join(), elastic
        world-version bumps, explicit resets — the prefetch-leg
        invalidation contract: invalidate, never poison; the next
        compressed step simply starts a fresh lineage)."""
        with self._lock:
            dropped = len(self._ef_residuals)
            self._ef_residuals.clear()
        if dropped:
            self._m_res_inval.inc(dropped)
            self._emit_replay("residual-invalidate", reason)

    def _m_codec_saved(self, kind: str, tensors, buckets, algos,
                       codecs, links=None, size: int = 0) -> None:
        """Wire bytes the codecs removed, by link — the measurable face
        of the compression win next to the (already-encoded)
        hvd_tpu_wire_bytes_total series. Both series follow the
        registry's submitted-payload convention (what this rank hands to
        the collective, each byte once — the same convention the
        uncompressed ladder is booked under, so before/after deltas stay
        apples-to-apples). ``links`` reuses a per-tensor encoded split
        the caller already derived (:meth:`_tensor_links`)."""
        if not self._m_enabled:
            return
        local = self.topology.local_size
        for idxs, algo, codec in zip(buckets, algos, codecs):
            if codec == comp.CODEC_NONE:
                continue
            for i in idxs:
                t = tensors[i]
                orig = C.link_split(algo, t.nbytes, local, kind=kind,
                                    size=size)
                enc = (links[i] if links is not None and links[i]
                       else C.link_split(
                           algo, t.nbytes, local, kind=kind, codec=codec,
                           itemsize=jnp.dtype(t.dtype).itemsize,
                           size=size))
                for link, b in orig.items():
                    saved = b - enc.get(link, 0)
                    if saved > 0:
                        self._m_saved.inc(saved, link=link)

    def _tensor_links(self, kind: str, tensors, buckets=None, algos=None,
                      codecs=None):
        """Per-tensor link-byte split for wire accounting and trace
        stamping: each tensor inherits its fusion bucket's algorithm.
        ``buckets=None`` derives the live bucketing (the same rule the
        dispatch path applies). Returns a list of {link: bytes} dicts
        aligned with ``tensors``, or None when nobody would consume them
        (size <= 1, or metrics AND tracing both off — the link
        derivation must cost nothing on a fully-quiet hot path)."""
        if self.topology.size <= 1 or not tensors:
            return None
        if not self._m_enabled and self.trace is None:
            return None
        if buckets is None:
            buckets = bucket_by_size(tensors,
                                     self.config.fusion_threshold_bytes)
        if algos is None:
            algos = self._bucket_algos(kind, tensors, buckets)
        if codecs is None:
            codecs = (comp.CODEC_NONE,) * len(buckets)
        local = self.topology.local_size
        links = [None] * len(tensors)
        for idxs, algo, codec in zip(buckets, algos, codecs):
            for i in idxs:
                links[i] = C.link_split(
                    algo, tensors[i].nbytes, local, kind=kind,
                    codec=codec,
                    itemsize=jnp.dtype(tensors[i].dtype).itemsize)
        return links

    def _m_account(self, kind: str, tensors, links=None):
        """Wire-byte accounting at collective submission: payload bytes this
        rank hands to the collective, split by op kind, dtype, and fabric
        link (the reference's TensorQueue size accounting, made
        scrapeable). Counted before replay interception — a replayed step
        moves the same bytes. ``links`` (from :meth:`_tensor_links`)
        splits hierarchical buckets into their ICI and DCN legs; without
        it every byte rides link="flat" (whole-fabric)."""
        if not self._m_enabled:
            return
        self._m_collectives.inc(1.0, kind=kind)
        for i, t in enumerate(tensors):
            split = links[i] if links else None
            if split:
                for link, b in split.items():
                    if b:
                        self._m_wire.inc(b, kind=kind, dtype=str(t.dtype),
                                         link=link)
            else:
                self._m_wire.inc(t.nbytes, kind=kind, dtype=str(t.dtype),
                                 link="flat")

    def _m_buckets_obs(self, tensors, buckets):
        """Fusion-bucket fill efficiency for one grouped/sharded call."""
        if not self._m_enabled or not buckets:
            return
        total = 0
        for idxs in buckets:
            b = sum(tensors[i].nbytes for i in idxs)
            total += b
            self._m_bucket_bytes.inc(b)
        self._m_buckets.inc(len(buckets))
        thr = max(self.config.fusion_threshold_bytes, 1)
        self._m_fill.set(100.0 * total / (len(buckets) * thr))

    def _register(self, name: Optional[str], kind: str, nbytes: int,
                  link_bytes: Optional[dict] = None) -> str:
        # every collective submission funnels through here — the canonical
        # failpoint for "this rank's op never starts"
        failpoint("engine.enqueue")
        self._check_poison()
        name = name or self._auto_name(kind)
        with self._lock:
            existing = self._outstanding.get(name)
        if existing is not None:
            # The prior op may have completed on-device without anyone polling
            # yet — only a genuinely in-flight duplicate is an error
            # (common.h:163-166 DUPLICATE_NAME_ERROR).
            if not existing.poll():
                raise DuplicateNameError(
                    f"Duplicate tensor name {name!r} submitted before the prior "
                    f"operation completed (common.h:163-166)")
        if self.trace is not None:
            # stamp the correlation id BEFORE the on_enqueue hook so the
            # timeline closure can tag its span with trace.live_corr(name)
            self.trace.record_enqueue(name, kind, nbytes, self.world_version,
                                      link_bytes=link_bytes)
        if self.on_enqueue is not None:
            self.on_enqueue(name, kind, nbytes)
        return name

    def _track(self, name: str, h: Handle):
        with self._lock:
            self._outstanding[name] = h

    # -- step-capture replay (core/replay.py) ------------------------------

    def step_begin(self):
        """Mark the start of one eager training step. Between step_begin and
        step_end the engine records the ordered dispatch stream; once the
        same signature repeats ``step_replay_warmup`` times, matching steps
        are serviced by a single fused XLA launch (see core/replay.py)."""
        if self.trace is not None:
            self.trace.record_step(begin=True)
        self._in_step_bracket = True
        self._overlap_step_noted = False
        self._replay.step_begin()

    def step_end(self):
        self._replay.step_end()
        self._in_step_bracket = False
        if self.trace is not None:
            self.trace.record_step(begin=False)
        self.step_index += 1
        if self.health is not None:
            self.health.on_step_end()
        if self.on_step_complete is not None:
            try:
                self.on_step_complete(self.step_index)
            except Exception:
                logging.getLogger("horovod_tpu").debug(
                    "step-complete hook failed", exc_info=True)

    def _refresh_world_version(self) -> int:
        """Pick up an elastic world-version bump. A reset normally rebuilds
        the Engine (backend.shutdown + init), but the rendezvous records the
        new version in HOROVOD_TPU_WORLD_VERSION — re-reading it here keeps
        the replay invalidation guard live even for an engine object that
        survives a re-rendezvous. The attribute only moves forward (tests
        may bump it directly)."""
        # divcheck: ignore[this re-read IS the replay re-arm edge: the rendezvous stamps the bump before any rank re-enters a step, and the value only moves forward]
        v = os.environ.get("HOROVOD_TPU_WORLD_VERSION")
        if v:
            try:
                ev = int(v)
            except ValueError:
                return self.world_version
            if ev > self.world_version:
                self.world_version = ev
        return self.world_version

    @property
    def replay(self):
        return self._replay

    # -- bucket-pipelined comm/compute overlap (ISSUE 6) -------------------

    def _overlap_mode(self, nbytes: int = 0, n_buckets: int = 1,
                      sharded: bool = False) -> str:
        """Resolve the overlap pipeline mode for one step: "off" (the PR 1
        serial chain), "interleave" (one launch, collectives traced
        back-to-back), or "staged" (replay splits the step into per-bucket
        sub-launches). "auto" picks per (bytes, topology): staged only
        pays when there is more than one pipeline stage to overlap, the
        payload is large enough that wire time dwarfs the extra dispatches
        (``overlap_stage_bytes``), and the world actually has peers;
        otherwise interleave — same launch count as serial, strictly freer
        schedule.

        One restriction applies to every resolution path (forced or auto):
        in Join-live worlds "staged" demotes to "interleave" — a joined
        peer's zero substitute services the advertisement with ONE grouped
        program, and splitting the active ranks' step into sub-launches is
        a wire-sequence risk not worth taking next to a blocked peer. The
        eager split and replay's stage plan both resolve through here, so
        warmup and steady state always pick the same schedule."""
        base = self.config.overlap_pipeline
        if base == "off":
            return "off"
        mode = base
        if base == "auto":
            mode = ("staged"
                    if (self.backend.size() > 1 and (sharded or n_buckets > 1)
                        and nbytes >= self.config.overlap_stage_bytes)
                    else "interleave")
        if (mode == "staged" and self.config.join_enabled
                and self.backend.size() > 1):
            return "interleave"
        return mode

    def _note_overlap_step(self, mode: str) -> None:
        """Count a step serviced by a pipelined schedule. Inside a
        step_begin/step_end bracket the latch keeps k grouped launches
        from inflating the counter's 'steps' semantics (one bump per
        bracketed step); an unbracketed call counts as its own degenerate
        step. Replayed steps bump the counter in replay.py — interception
        returns before this path runs, so the two never double-count."""
        if self._in_step_bracket:
            if self._overlap_step_noted:
                return
            self._overlap_step_noted = True
        self._m_overlap_steps.inc(mode=mode)

    def _note_prefetch(self, update_key: tuple) -> None:
        """Record a launched ZeRO-1 all-gather prefetch leg. The leg is
        held across the step boundary (nothing blocks on it at step_end —
        consumers chain on its dataflow futures, which is also what keeps
        its buffers alive; the registry row carries only the world version
        for invalidation accounting) and dropped on world-version bumps,
        join(), and explicit resets. The row is retired — without counting
        an invalidation — when the next step's grads for the same
        ``update_key`` arrive (sharded_step's head): those grads were
        computed from the leg's gathered params, i.e. the leg was reused,
        so ``hvd_tpu_overlap_prefetch_invalidations_total`` only ever
        counts legs genuinely dropped before reuse."""
        # The registry is written here on the dispatch path but cleared
        # from replay/join/elastic invalidation edges that can run on the
        # worker-notification or watchdog threads — the unguarded dict
        # raced its own invalidation sweep (lockcheck off-lock-access
        # regression, tests/test_race_regressions.py).
        with self._lock:
            self._zero1_prefetch[update_key] = {
                "world_version": self.world_version}
        self._m_prefetch.inc()

    def invalidate_prefetch(self, reason: str) -> None:
        """Drop every held prefetch leg (the replay-invalidation contract
        applied to the prefetch subsystem: invalidate, never poison — the
        next sharded step simply re-gathers)."""
        with self._lock:
            dropped = len(self._zero1_prefetch)
            self._zero1_prefetch.clear()
        if not dropped:
            return
        self._m_prefetch_inval.inc(dropped)
        self._emit_replay("prefetch-invalidate", reason)

    def _prefetch_gc(self) -> None:
        """Drop held legs — and error-feedback residual buffers — whose
        world version is stale (an elastic bump observed outside the
        replay step markers)."""
        v = self.world_version
        with self._lock:
            stale = [k for k, ent in self._zero1_prefetch.items()
                     if ent["world_version"] != v]
            for k in stale:
                del self._zero1_prefetch[k]
            stale_res = [k for k, ent in self._ef_residuals.items()
                         if ent["world_version"] != v]
            for k in stale_res:
                del self._ef_residuals[k]
        if stale:
            self._m_prefetch_inval.inc(len(stale))
            self._emit_replay("prefetch-invalidate",
                              f"world-version bump (-> {v})")
        if stale_res:
            self._m_res_inval.inc(len(stale_res))
            self._emit_replay("residual-invalidate",
                              f"world-version bump (-> {v})")

    def _emit_replay(self, event: str, detail: str):
        if self.on_replay is not None:
            self.on_replay(event, detail)

    def _pm_step(self, nbytes: int):
        """Autotune step boundary + live knob application (the block the
        grouped-allreduce path used to inline). Guarded by the replay step
        token so a step serviced partly by replay and partly by the normal
        path marks exactly once; outside step markers every grouped call
        marks, the legacy cadence."""
        pm = self.parameter_manager
        if pm is None:
            return
        tok = self._replay.pm_token()
        if tok is not None:
            if tok == self._pm_marked_token:
                return
            self._pm_marked_token = tok
        # persistent-autotune warm start (ISSUE 14): one-shot, at the
        # first step boundary — the earliest point the model signature
        # exists. Every rank reaches this call in the same program order
        # and the record rides the parameter-sync broadcast inside, so
        # the adopted knob vector is identical everywhere. getattr: the
        # pm face is duck-typed (test doubles implement a subset).
        warm = getattr(pm, "maybe_warm_start", None)
        if warm is not None:
            warm(self._model_sig)
        if pm.active:
            # program-ordered autotune step boundary: score the previous
            # step, possibly retune knobs (collective sync inside is safe
            # here — every rank hits this call in the same order)
            pm.step_mark(nbytes)
        # knob values apply while tuning AND after convergence (the winner
        # must stick, controller.cc:34-48 SynchronizeParameters)
        self.config.fusion_threshold_bytes = pm.fusion_threshold_bytes
        self.config.cycle_time_ms = pm.cycle_time_ms
        # categorical knobs (parameter_manager.h:225-228): hierarchy /
        # Pallas-pack / replay choices flip between samples, synchronized
        # across ranks by the pm's rank-0 broadcast at sample boundaries
        for knob in ("hierarchical_allreduce", "hierarchical_allgather",
                     "single_launch", "step_replay", "shard_optimizer"):
            if pm.tunes(knob):
                setattr(self.config, knob, pm.categorical_value(knob))
        # string-mode knobs (ISSUE 14 joint space): the tuner explores
        # the declared choice set directly — the value IS the config
        # string. Legacy boolean declarations keep the PR 6/10/13
        # base-vs-off encoding so older wirings stay valid.
        if pm.tunes("overlap_pipeline"):
            v = pm.categorical_value("overlap_pipeline")
            self.config.overlap_pipeline = (
                v if isinstance(v, str)
                else (self._overlap_base if v else "off"))
        if pm.tunes("collective_algo"):
            v = pm.categorical_value("collective_algo")
            self.config.collective_algo = (
                v if isinstance(v, str)
                else (self._algo_base if v else "flat"))
        # compression is only offered when the user enabled a codec —
        # autotune never silently turns lossy compression ON (state.py)
        if pm.tunes("compression"):
            v = pm.categorical_value("compression")
            self.config.compression = (
                v if isinstance(v, str)
                else (self._codec_base if v else comp.CODEC_NONE))
        # pipeline schedule (ISSUE 16): a string categorical like the
        # above — a move lands in _algo_sig, so the armed pipeline step
        # re-warms with the new schedule's table program
        if pm.tunes("pipeline_schedule"):
            v = pm.categorical_value("pipeline_schedule")
            if isinstance(v, str):
                self.config.pipeline_schedule = v
        # the tree threshold joined the numeric dims (ISSUE 14): the
        # calibrated derivation seeds it, the GP refines it; replay
        # re-arms through _algo_sig on every move
        if getattr(pm, "tunes_tree_threshold", False):
            self.config.tree_threshold_bytes = pm.tree_threshold_bytes

    def _dispatch(self, names, fn, *args):
        """Dispatch with failure translation + a timeline ACTIVITY span per
        involved tensor (QUEUE/MEMCPY/NCCL_* span analog, common.h:32-62;
        the reference records activities for every tensor of a fused
        response). A fresh builder means this call traced + compiled, which
        dwarfs a real dispatch — labeled separately so timelines stay
        readable."""
        fresh = getattr(self, "_last_builder_fresh", False)
        activity = "XLA_COMPILE_AND_DISPATCH" if fresh else "XLA_DISPATCH"
        self._last_builder_fresh = False
        if isinstance(names, str):
            names = [names]
        # a hang armed here models a peer wedged mid-launch: the op is
        # already in the outstanding table (stall inspector visible), so
        # the collective watchdog can escalate and break the hang with
        # HorovodInternalError — the exception the elastic loop recovers
        failpoint("engine.dispatch")
        self._count_dispatch()
        t0 = time.perf_counter()
        try:
            with scopes.host_span(scopes.ENGINE_COMPILE_DISPATCH if fresh
                                  else scopes.ENGINE_DISPATCH):
                return _translate_failure(fn, *args)
        finally:
            if self.trace is not None:
                self.trace.record_dispatch(names, activity,
                                           time.perf_counter() - t0)
            if self.on_activity is not None:
                dur = (time.perf_counter() - t0) * 1e6
                for n in names:
                    self.on_activity(n, activity, dur)

    # -- Join protocol (operations.cc:1004-1040, tensor_queue.h:39-41) ------

    def _consume_substitute(self) -> bool:
        sub = self._join_substitute
        self._join_substitute = False
        return sub

    def _join_head(self, flag: int, rounds: int, kind_code: int,
                   metas) -> np.ndarray:
        """Build the fixed-shape join-round vector:
        [flag, rounds, kind, k, meta_slot_0.., zero padding]."""
        vec = np.zeros((_JOIN_HEAD_LEN,), dtype=np.int64)
        k = len(metas) if metas is not None else 0
        vec[0:4] = (flag, rounds, kind_code, k)
        if k:
            inline = metas[:_JOIN_META_SLOTS]
            vec[4:4 + len(inline) * _JOIN_META_LEN] = np.concatenate(inline)
        return vec

    def _join_sync(self, kind: str, metas, skip: bool = False):
        """Per-op join round — **fire-and-forget on the hot path**. One
        fixed-shape allgather carries [active-flag, kind, k, metadata...];
        active ranks dispatch it asynchronously and never read the result,
        so the steady state pays one extra tiny collective launch and ZERO
        host round-trips per op (the role of the reference's per-cycle
        bit-vector fast path, controller.cc:133-203, re-thought for SPMD:
        readiness negotiation is unnecessary, only joined ranks need the
        advertisement, and they are blocked in join() with time to read it).
        Ranks sitting in join() fetch the round, learn the op, and dispatch
        a matching zero-tensor substitute in the same program order.

        Broadcast is NOT special-cased here any more (VERDICT r3 item 2):
        the joined-root check rides the broadcast program itself (the root's
        active bit is broadcast in the same launch, build_broadcast_flagged)
        and is enforced at extract time, so the active path stays
        fetch-free. ``skip=True`` on the substitute dispatch itself — its
        round already ran inside the join() loop."""
        if skip or not self.config.join_enabled or self.backend.size() <= 1:
            return
        k = len(metas)
        self._dispatch_exchange(self._join_head(0, 0, _KIND_CODES[kind],
                                                metas))
        if k > _JOIN_META_SLOTS:
            # overflow metadata: both sides derive this exchange's existence
            # and shape from the head (k > slots), so it stays async too
            self._dispatch_exchange(
                np.concatenate(metas[_JOIN_META_SLOTS:]))

    def join(self) -> int:
        """This rank is out of data: keep matching peers' collectives with
        zero tensors until every rank joins; returns the last joining rank
        (reference join semantics, operations.cc:1004-1040)."""
        # The world is entering a ragged-batch phase: every armed replay
        # stream is invalid until steady state re-establishes itself
        # (ISSUE r5 tentpole: replay must fall back while join is active).
        self._replay.invalidate_all("join() entered")
        self._check_poison()
        size = self.backend.size()
        if size <= 1:
            return 0
        if self.on_join_state is not None:
            self.on_join_state(True)
        try:
            return self._join_loop(size)
        finally:
            if self.on_join_state is not None:
                self.on_join_state(False)

    def _join_loop(self, size: int) -> int:
        if not self.config.join_enabled:
            # legacy behavior: barrier-style consensus only
            self.barrier()
            return size - 1
        rounds = 0
        while True:
            head = self._exchange_sizes(self._join_head(1, rounds, 0, None))
            joined = head[:, 0] == 1
            if joined.all():
                # everyone is in join(): the last joiner has the fewest
                # rounds; ties break to the highest rank (deterministic —
                # every rank sees the same matrix)
                min_rounds = head[:, 1].min()
                return int(max(r for r in range(size)
                               if head[r, 1] == min_rounds))
            act = int(np.argmin(joined))   # first still-active rank
            kind_code = int(head[act, 2])
            k = int(head[act, 3])
            metas = None
            if k:
                inline = min(k, _JOIN_META_SLOTS)
                metas = head[act, 4:4 + inline * _JOIN_META_LEN] \
                    .reshape(inline, _JOIN_META_LEN)
                if k > _JOIN_META_SLOTS:
                    flat = self._exchange_sizes(np.zeros(
                        ((k - _JOIN_META_SLOTS) * _JOIN_META_LEN,),
                        dtype=np.int64))
                    metas = np.concatenate(
                        [metas,
                         flat[act].reshape(-1, _JOIN_META_LEN)])
            dead_root = None
            if kind_code in (_KIND_CODES["broadcast"],
                             _KIND_CODES["grouped_broadcast"]) \
                    and metas is not None:
                root = int(metas[0][0])
                if root == self.backend.rank() or head[root, 0] == 1:
                    # A joined broadcast root has no data. Unlike r3, the
                    # substitute IS dispatched first (with active=0 for the
                    # root) so the active ranks' collective matches and
                    # nothing hangs — they see the zero flag and raise at
                    # extract; every joined rank raises here (ADVICE r2:
                    # all ranks must raise, not only the root).
                    dead_root = root
            self._dispatch_substitute(kind_code, metas)
            if dead_root is not None:
                raise HorovodInternalError(
                    f"broadcast root rank {dead_root} has already joined; "
                    f"it has no data to broadcast")
            rounds += 1

    def _dispatch_substitute(self, kind_code: int, metas):
        """Dispatch a zero-tensor stand-in matching the active ranks' op
        (tensor_queue.h:39-41 zero substitution). Runs the normal engine
        method so every internal exchange/collective lines up with the
        active ranks'."""
        kind = {v: k for k, v in _KIND_CODES.items()}[kind_code]
        if kind == "barrier":
            self._join_substitute = True
            self.barrier()
            return

        def zero(row):
            dtype = _CODE_DTYPES[int(row[1])]
            shape = tuple(int(d) for d in row[3:3 + int(row[2])])
            return jnp.zeros(shape, dtype)

        self._join_substitute = True
        if kind == "grouped_allreduce":
            # the advertised op field packs the call codec in its high
            # bits (allreduce/grouped_allreduce submission sites): the
            # substitute must compile the SAME compressed program as the
            # active ranks or the collective sequences diverge
            code = int(metas[0][0])
            op = ReduceOp(code & 15)
            sub_codec = comp.CODECS[(code >> 4) % len(comp.CODECS)]
            hs = self.grouped_allreduce([zero(r) for r in metas], op=op,
                                        codec=sub_codec)
            for h in hs:
                h.synchronize()
        elif kind == "allreduce":
            code = int(metas[0][0])
            self.allreduce(
                zero(metas[0]), op=ReduceOp(code & 15),
                codec=comp.CODECS[(code >> 4) % len(comp.CODECS)]
            ).synchronize()
        elif kind == "adasum":
            from ..ops.adasum import adasum_allreduce_handle
            adasum_allreduce_handle(self, zero(metas[0])).synchronize()
        elif kind == "allgather":
            code = int(metas[0][0])
            self.allgather(zero(metas[0]), equal_sizes=bool(code & 1),
                           _sub_hash=code >> 1).synchronize()
        elif kind == "broadcast":
            self.broadcast(zero(metas[0]),
                           root_rank=int(metas[0][0])).synchronize()
        elif kind == "grouped_broadcast":
            hs = self.grouped_broadcast([zero(r) for r in metas],
                                        root_rank=int(metas[0][0]))
            for h in hs:
                h.synchronize()
        elif kind == "reducescatter":
            self.reducescatter(zero(metas[0]),
                               op=ReduceOp(int(metas[0][0]))).synchronize()
        elif kind == "sharded_step":
            # A zero substitute cannot stand in for a sharded optimizer
            # step: this joined rank OWNS a parameter shard, and the
            # all-gather leg would publish a garbage (non-updated) shard
            # into every peer's parameters — silent model corruption. Fail
            # loudly instead (peers' unmatched collective surfaces as a
            # HorovodInternalError through _translate_failure).
            raise HorovodInternalError(
                "sharded optimizer steps cannot be matched by a join() "
                "zero substitute: a rank without data still owns a "
                "parameter shard that must keep receiving real updates. "
                "Keep stepping with zero gradients instead of join(), or "
                "use the replicated (sharded=False) optimizer for "
                "ragged-batch workloads (see docs/sharded_optimizer.md)")
        elif kind == "alltoall":
            code = int(metas[0][0])
            z = zero(metas[0])
            d0 = int(z.shape[0]) if z.ndim else 0
            size = self.backend.size()
            if d0 % size == 0:
                splits = None
            else:
                # spread the zero rows evenly, mirroring the divisible path
                # (alltoall() overrides both z and splits when this rank
                # has a cache entry for the advertised name)
                base, rem = divmod(d0, size)
                splits = np.array([base + (1 if i < rem else 0)
                                   for i in range(size)], dtype=np.int32)
            self.alltoall(z, splits=splits,
                          _sub_hash=code >> 1).synchronize()
        elif kind == "grouped_alltoall":
            # even-splits contract: the advertised shapes already divide
            # the world, so a zero group matches the active ranks' program
            hs = self.grouped_alltoall([zero(r) for r in metas])
            for h in hs:
                h.synchronize()
        else:
            raise HorovodInternalError(
                f"unknown substitute kind code {kind_code}")

    # -- debug-mode cross-rank consistency (controller.cc:380-623) ---------

    @staticmethod
    def _h63(s: str) -> int:
        import hashlib
        return int.from_bytes(hashlib.md5(s.encode()).digest()[:8],
                              "little") >> 1

    _META_DIMS = 6

    def _debug_check(self, name: str, kind: str, tensors, op_code: int = -1,
                     check_dim0: bool = True, wildcard: bool = False):
        """When HOROVOD_TPU_DEBUG_CONSISTENCY=1, allgather a compact
        (name-hash, kind, op, dtype, shape) fingerprint before dispatch and
        raise the same descriptive error on every rank on any mismatch — the
        debug-mode stand-in for the reference coordinator's submission
        validation (controller.cc:380-623), which SPMD removes from the hot
        path. ``check_dim0=False`` exempts dim 0 (allgather's legitimate
        per-rank row counts, collective_operations.cc:88-195)."""
        if not self.config.debug_consistency or self.backend.size() <= 1:
            return
        from ..common.exceptions import (ConsistencyError,
                                         TensorDtypeMismatchError,
                                         TensorShapeMismatchError)
        rows = []
        for t in tensors:
            if wildcard:
                # Join zero-substitute: it must take part in the exchange
                # (peers are mid-allgather) but its auto-generated name
                # legitimately differs — sentinel rows are skipped by every
                # rank's comparison.
                rows.append([-9] * (5 + self._META_DIMS))
                continue
            dims = [int(d) for d in t.shape[:self._META_DIMS]]
            dims += [-1] * (self._META_DIMS - len(dims))
            if not check_dim0 and t.ndim:
                dims[0] = -2  # wildcard
            rows.append([self._h63(name), self._h63(kind), op_code,
                         self._h63(str(t.dtype)), t.ndim] + dims)
        local = np.asarray(rows, dtype=np.int64).reshape(-1)
        world = self._exchange_sizes(local)  # (size, k)
        me = self.backend.rank()
        if wildcard:
            return
        for r in range(world.shape[0]):
            if world[r][0] == -9:  # a joined rank's sentinel
                continue
            if (world[r] == world[me]).all():
                continue
            a = world[me].reshape(len(tensors), -1)
            b = world[r].reshape(len(tensors), -1)
            for i in range(len(tensors)):
                if (a[i] == b[i]).all():
                    continue
                loc = (f"rank {me}: name={name!r} kind={kind} op={op_code} "
                       f"dtype={tensors[i].dtype} shape={tensors[i].shape}")
                if a[i][0] != b[i][0] or a[i][1] != b[i][1]:
                    raise ConsistencyError(
                        f"Mismatched collective submissions: rank {r} "
                        f"submitted a different tensor name or operation "
                        f"type at this call index ({loc}); every rank must "
                        f"submit the same named collectives in the same "
                        f"order (controller.cc:380-623)")
                if a[i][2] != b[i][2]:
                    raise ConsistencyError(
                        f"Mismatched reduce op for tensor {name!r}: rank {r} "
                        f"used op code {int(b[i][2])}, this rank "
                        f"{int(a[i][2])} ({loc})")
                if a[i][3] != b[i][3]:
                    raise TensorDtypeMismatchError(
                        f"Mismatched dtype for tensor {name!r}: rank {r} "
                        f"disagrees with this rank's {tensors[i].dtype} "
                        f"({loc})")
                raise TensorShapeMismatchError(
                    f"Mismatched shape for tensor {name!r}: rank {r} sent "
                    f"ndim={int(b[i][4])} dims="
                    f"{[int(d) for d in b[i][5:] if d != -1]} vs this "
                    f"rank's {tuple(tensors[i].shape)} ({loc})")
            # rows differed but per-tensor comparison found no cause
            raise ConsistencyError(
                f"Mismatched collective submission metadata with rank {r} "
                f"for {name!r} ({kind})")

    def _on_complete(self, h: Handle):
        with self._lock:
            self._outstanding.pop(h.name, None)
        if self._m_enabled and h.kind is not None:
            self._m_latency.observe(time.monotonic() - h._enqueue_mono,
                                    kind=h.kind)
        if self.trace is not None:
            self.trace.record_done(h.name)
        if self.on_done is not None:
            self.on_done(h.name)

    def _single(self, name: str, garr: jax.Array,
                replicated: bool = True,
                kind: Optional[str] = None) -> Handle:
        extract = (self.backend.from_replicated if replicated
                   else self.backend.from_global)
        h = Handle(name, [garr], lambda gs: extract(gs[0]), self, kind=kind)
        self._track(name, h)
        return h

    def _hierarchical_ok(self) -> bool:
        """One-time, *collectively agreed* decision whether hierarchical
        allreduce is usable. Every rank must pick the same program
        (mpi_controller.cc:26-82 homogeneity check): a rank-local local_size
        test would diverge on heterogeneous host assignments, so the first
        caller allgathers local_size and requires uniformity."""
        if self._hier_ok is not None:
            return self._hier_ok
        local = self.topology.local_size
        size = self.backend.size()
        if size == 1:
            self._hier_ok = False
            return False
        sizes = self._exchange_sizes(np.array([local], dtype=np.int32))[:, 0]
        self._hier_ok = bool((sizes == sizes[0]).all() and
                             1 < local < size and size % local == 0)
        return self._hier_ok

    def _allreduce_builder(self, op: ReduceOp, prescale_factor: float,
                           postscale_factor: float,
                           algo: str = C.ALGO_FLAT):
        """Flat vs tree vs hierarchical allreduce dispatch (the role of
        OperationManager priority selection, operations.cc:142-249), per
        the topology-aware choice the caller resolved with
        :meth:`_choose_algo`."""
        mesh = self.backend.group_mesh
        local = self.topology.local_size
        if algo == C.ALGO_HIERARCHICAL:
            return self._builder(
                ("hier_allreduce", op, local, prescale_factor,
                 postscale_factor),
                lambda: C.build_hierarchical_allreduce(
                    mesh, self._axis(), local, op, prescale_factor,
                    postscale_factor))
        if algo == C.ALGO_TREE:
            return self._builder(
                ("tree_allreduce", op, prescale_factor, postscale_factor),
                lambda: C.build_tree_allreduce(
                    mesh, self._axis(), op, prescale_factor,
                    postscale_factor))
        return self._builder(
            ("allreduce", op, prescale_factor, postscale_factor),
            lambda: C.build_allreduce(mesh, self._axis(), op,
                                      prescale_factor, postscale_factor))

    # -- collectives -------------------------------------------------------

    def allreduce(self, tensor, name: Optional[str] = None,
                  op: ReduceOp = ReduceOp.SUM,
                  prescale_factor: float = 1.0,
                  postscale_factor: float = 1.0,
                  codec: Optional[str] = None) -> Handle:
        x = jnp.asarray(tensor)
        orig_name = name   # residual-lineage template (pre-registration)
        sub = self._consume_substitute()
        _check_average_dtype(x, op)
        algo, links = C.ALGO_FLAT, None
        call_codec = self._call_codec(codec, op)
        bucket_codec = comp.CODEC_NONE
        if self.topology.size > 1:
            algo = self._choose_algo("allreduce", x.nbytes)
            bucket_codec = self._bucket_codecs("allreduce", [x], [[0]],
                                               call_codec)[0]
            if self._m_enabled:
                self._m_algo.inc(kind="allreduce", algo=algo)
            if self._m_enabled or self.trace is not None:
                links = [C.link_split(algo, x.nbytes,
                                      self.topology.local_size,
                                      codec=bucket_codec,
                                      itemsize=jnp.dtype(
                                          x.dtype).itemsize)]
            self._m_codec_saved("allreduce", [x], [[0]], (algo,),
                                (bucket_codec,), links)
        self._m_account("allreduce", [x], links)
        r = self._replay.intercept("allreduce", [x], int(op),
                                   prescale_factor, postscale_factor, name,
                                   sub, extra=(call_codec,))
        if r is not None:
            return r[0]
        name = self._register(name, "allreduce", x.nbytes,
                              link_bytes=links[0] if links else None)
        # the advertised op field carries the call codec in its high bits
        # so a joined peer's zero substitute resolves the SAME compressed
        # program (ReduceOp codes fit in 4 bits)
        self._join_sync("allreduce",
                        [_join_meta_row(
                            x, int(op)
                            | (comp.CODECS.index(call_codec) << 4))],
                        skip=sub)
        self._debug_check(name, "allreduce", [x], op_code=int(op),
                          wildcard=sub)
        if bucket_codec != comp.CODEC_NONE:
            failpoint("compression.encode")
            elems = C.codec_residual_elems(
                "reduce", int(np.prod(x.shape)) if x.ndim else 1,
                self.topology.size, self.topology.local_size, algo,
                bucket_codec)
            fn = self._builder(
                ("codec_allreduce", op, prescale_factor, postscale_factor,
                 tuple(x.shape), str(x.dtype), algo, bucket_codec),
                lambda: C.build_codec_allreduce(
                    self.backend.group_mesh, self._axis(), op,
                    tuple(x.shape), x.dtype, algo, bucket_codec,
                    prescale_factor, postscale_factor,
                    self.topology.local_size))
            if bucket_codec in comp.EF_CODECS:
                key = self._residual_key("gar", orig_name, 0, algo,
                                         bucket_codec, elems, str(x.dtype))
                res = self._residual_fetch(key, elems, x.dtype)
                out, new_res = self._dispatch(
                    name, lambda: fn(self.backend.to_global(x),
                                     self.backend.world_view(res)))
                self._residual_store(key, new_res)
            else:
                out = self._dispatch(
                    name, lambda: fn(self.backend.to_global(x)))
            return self._single(name, out, kind="allreduce")
        fn = self._allreduce_builder(op, prescale_factor, postscale_factor,
                                     algo)
        out = self._dispatch(name, lambda: fn(self.backend.to_global(x)))
        return self._single(name, out, kind="allreduce")

    def grouped_allreduce(self, tensors: Sequence, name: Optional[str] = None,
                          op: ReduceOp = ReduceOp.SUM,
                          prescale_factor: float = 1.0,
                          postscale_factor: float = 1.0,
                          codec: Optional[str] = None) -> List[Handle]:
        """Fused allreduce of many tensors: bucketed packing (one collective per
        <= fusion_threshold bucket per dtype), mirroring FuseResponses
        (controller.cc:652-773). ``codec`` overrides the engine's wire
        codec for this call (the optimizer's ``compression=`` argument,
        ISSUE 13); None defers to HOROVOD_TPU_COMPRESSION."""
        with scopes.host_span(scopes.ENGINE_GROUPED_ALLREDUCE):
            return self._grouped_allreduce(tensors, name, op,
                                           prescale_factor,
                                           postscale_factor, codec)

    def _grouped_allreduce(self, tensors, name, op, prescale_factor,
                           postscale_factor, codec) -> List[Handle]:
        tensors = [jnp.asarray(t) for t in tensors]
        sub = self._consume_substitute()
        for t in tensors:
            _check_average_dtype(t, op)
        links = None
        call_codec = self._call_codec(codec, op)
        derived = None   # (threshold, sig, buckets, algos, codecs) reuse
        if tensors:
            # selection + link attribution ride the live bucketing; wire
            # accounting stays BEFORE replay interception so replayed
            # steps keep counting the bytes they move. The derivation is
            # kept for the dispatch path below — recomputed only if
            # _pm_step retunes the fusion threshold mid-call.
            if self.topology.size > 1:
                thr0 = self.config.fusion_threshold_bytes
                b0 = bucket_by_size(tensors, thr0)
                a0 = self._bucket_algos("allreduce", tensors, b0)
                c0 = self._bucket_codecs("grouped_allreduce", tensors, b0,
                                         call_codec)
                links = self._tensor_links("allreduce", tensors, b0, a0,
                                           c0)
                self._m_codec_saved("allreduce", tensors, b0, a0, c0,
                                    links)
                derived = (thr0, self._algo_sig(), b0, a0, c0)
            self._m_account("grouped_allreduce", tensors, links)
            r = self._replay.intercept("grouped_allreduce", tensors, int(op),
                                       prescale_factor, postscale_factor,
                                       name, sub, extra=(call_codec,))
            if r is not None:
                return r
        # the advertised op field carries the call codec in its high bits
        # (see allreduce) so a joined peer's substitute compiles the same
        # compressed program
        self._join_sync("grouped_allreduce",
                        [_join_meta_row(
                            t, int(op)
                            | (comp.CODECS.index(call_codec) << 4))
                         for t in tensors],
                        skip=sub)
        self._note_model_sig(tensors)
        self._pm_step(sum(t.nbytes for t in tensors))
        names = [self._register(None if name is None else f"{name}.{i}",
                                "grouped_allreduce", t.nbytes,
                                link_bytes=links[i] if links else None)
                 for i, t in enumerate(tensors)]
        self._debug_check(names[0] if names else "empty", "grouped_allreduce",
                          tensors, op_code=int(op), wildcard=sub)
        if not tensors:
            return []
        if derived is not None \
                and derived[0] == self.config.fusion_threshold_bytes \
                and derived[1] == self._algo_sig():
            buckets, algos, codecs = derived[2], derived[3], derived[4]
        else:
            # _pm_step retuned a selection knob mid-call (or size-1
            # world): re-derive so THIS call's buckets and algorithms
            # track the live knobs (selection was already counted at
            # accounting time)
            buckets = bucket_by_size(tensors,
                                     self.config.fusion_threshold_bytes)
            algos = self._bucket_algos("allreduce", tensors, buckets,
                                       count=False)
            codecs = self._bucket_codecs("grouped_allreduce", tensors,
                                         buckets, call_codec, count=False)
        self._m_buckets_obs(tensors, buckets)
        if any(c != comp.CODEC_NONE for c in codecs):
            failpoint("compression.encode")
        # ONE residual-row derivation for both dispatch forms below: the
        # single-launch and per-bucket paths must produce identical keys
        # or error-feedback lineage would silently reset on a
        # single_launch flip (_residual_fetch returns zeros on any
        # key/shape mismatch)
        ef_info = self._grouped_residuals("gar", name, tensors, buckets,
                                          algos, codecs)
        ef_by_bucket = {row[0]: row for row in ef_info}
        mesh = self.backend.group_mesh
        hier_local = self.topology.local_size
        from ..ops.pallas_kernels import pack_pallas, pack_pallas_supported
        pm = self.parameter_manager
        use_pallas_pack = (pm.categorical_value("pallas_pack")
                           if pm is not None and pm.tunes("pallas_pack")
                           else self._pack_pallas_base)
        results: Dict[int, jax.Array] = {}
        if not use_pallas_pack and self.config.single_launch:
            # TWO launches for the whole group (VERDICT r4 weak #1):
            # pack-all (local jit, emits per-bucket buffers already
            # carrying the (1, ...) block dim so the global lift is pure
            # metadata), then one reduce+unpack program for every bucket —
            # where the per-bucket form cost 2·n_buckets dispatches plus
            # ~2 eager lift dispatches per tensor.
            shapes = tuple(tuple(t.shape) for t in tensors)
            dtypes = tuple(str(t.dtype) for t in tensors)
            bkey = tuple(tuple(b) for b in buckets)
            # overlap (ISSUE 6): trace the program's collectives
            # back-to-back so no unpack interposes between two buckets'
            # reduces — same launch count, strictly freer schedule
            pipe = self._overlap_mode(sum(t.nbytes for t in tensors),
                                      len(buckets)) != "off"
            if pipe:
                self._note_overlap_step("interleave")
            pack_fn = self._builder(
                ("pack_group", shapes, dtypes, bkey),
                lambda: C.build_pack_group(buckets))
            self._count_dispatch()
            packed = _translate_failure(pack_fn, *tensors)
            fn = self._builder(
                ("grouped_allreduce", op, prescale_factor,
                 postscale_factor, shapes, dtypes, bkey, hier_local, pipe,
                 algos, codecs),
                lambda: C.build_grouped_allreduce(
                    mesh, self._axis(), op, shapes,
                    [t.dtype for t in tensors], buckets,
                    prescale_factor, postscale_factor, hier_local,
                    pipeline=pipe, algos=algos, codecs=codecs))
            res_args = [self.backend.world_view(
                self._residual_fetch(k, e, dt))
                for _, k, e, dt in ef_info]
            outs = self._dispatch(
                names,
                lambda: fn(*([self.backend.to_global(p, batched=True)
                              for p in packed] + res_args)))
            for j, (_, k, _, _) in enumerate(ef_info):
                self._residual_store(k, outs[len(tensors) + j])
            group = LaunchGroup(outs[-1])
            for i in range(len(tensors)):
                results[i] = (outs[i], group)
        else:
            # Per-bucket two-dispatch form (pack, then reduce+unpack) —
            # kept for the Pallas pack kernel, whose packing is its own
            # launch (autotune's pallas_pack categorical flips this).
            for b, idxs in enumerate(buckets):
                bucket = [tensors[i] for i in idxs]
                shapes = tuple(tuple(t.shape) for t in bucket)
                dtype = bucket[0].dtype
                algo = algos[b]
                bcodec = codecs[b]
                self._count_dispatch()
                if use_pallas_pack and pack_pallas_supported(shapes, dtype):
                    packed = _translate_failure(pack_pallas, bucket)
                else:
                    pack_fn = self._builder(
                        ("pack", shapes, str(dtype)),
                        lambda: C.build_pack(shapes, dtype))
                    packed = _translate_failure(pack_fn, *bucket)
                fn = self._builder(
                    ("fused_allreduce", op, prescale_factor,
                     postscale_factor, shapes, str(dtype), hier_local,
                     algo, bcodec),
                    lambda: C.build_fused_allreduce(
                        mesh, self._axis(), op, shapes, dtype,
                        prescale_factor, postscale_factor, hier_local,
                        algo=algo, codec=bcodec))
                if bcodec in comp.EF_CODECS:
                    _, key, elems, _dt = ef_by_bucket[b]
                    res = self._residual_fetch(key, elems, dtype)
                    outs = self._dispatch(
                        [names[i] for i in idxs],
                        lambda: fn(self.backend.to_global(packed),
                                   self.backend.world_view(res)))
                    self._residual_store(key, outs[-1])
                    outs = outs[:-1]
                else:
                    outs = self._dispatch(
                        [names[i] for i in idxs],
                        lambda: fn(self.backend.to_global(packed)))
                group = LaunchGroup(outs[-1])
                for pos, i in enumerate(idxs):
                    results[i] = (outs[pos], group)
        handles = []
        for i, nm in enumerate(names):
            garr, group = results[i]
            h = Handle(nm, [garr],
                       lambda gs: self.backend.from_replicated(gs[0]), self,
                       group=group, kind="grouped_allreduce")
            self._track(nm, h)
            handles.append(h)
        return handles

    def shard_layout(self, total_bytes: int) -> tuple:
        """The durable-checkpoint byte-shard layout for this world:
        ``(padded, shard) = shard_spec(total_bytes, world_size)`` — the
        same ZeRO-1 padding rule the sharded optimizer uses, exposed so
        the checkpoint subsystem and the engine can never disagree on
        who owns which byte range (ISSUE 9)."""
        return C.shard_spec(int(total_bytes), self.backend.size())

    def sharded_step(self, tensors: Sequence, update_fn: Callable,
                     update_key: tuple, state_leaves: Sequence,
                     name: Optional[str] = None,
                     op: ReduceOp = ReduceOp.AVERAGE,
                     prescale_factor: float = 1.0,
                     postscale_factor: float = 1.0,
                     buckets: Optional[Sequence] = None,
                     codec: Optional[str] = None) -> List[Handle]:
        """ZeRO-1 optimizer-state-sharded gradient sync + update: bucket and
        pack the gradients (fusion logic of grouped_allreduce), reduce-
        scatter each bucket, run ``update_fn`` on this rank's shards only,
        and all-gather the updated parameter shards — all of it (after the
        pack) ONE launch. Same wire bytes as the fused allreduce (RS + AG),
        1/world_size of the optimizer-update FLOPs and state memory.

        ``update_fn(shards, state_leaves) -> (new_param_shards,
        new_state_leaves)`` is traced into the program (collective-free,
        state-shape-stable); ``update_key`` is its stable identity for the
        builder cache and the replay registry. Returns one handle per
        gradient (the full updated parameter tensor, replicated by
        construction) followed by one per state leaf (this rank's new
        shard-local state).

        ``buckets`` is the caller's FROZEN fusion layout (the sharded
        optimizer pins it at state-init time so a live autotune move of
        the fusion threshold cannot invalidate shard-shaped state
        mid-run); None re-derives from the current threshold."""
        tensors = [jnp.asarray(t) for t in tensors]
        state_leaves = [jnp.asarray(s) for s in state_leaves]
        if not tensors:
            raise ValueError("sharded_step needs at least one gradient")
        sub = self._consume_substitute()
        for t in tensors:
            _check_average_dtype(t, op)
        if buckets is None:
            buckets = bucket_by_size(tensors,
                                     self.config.fusion_threshold_bytes)
        bkey = tuple(tuple(b) for b in buckets)
        # topology-aware leg selection (ISSUE 10): the reduce-scatter leg
        # is pinned flat (shard-ownership invariant, ops/collectives.py
        # validate_algorithm), the return all-gather picks flat vs the
        # hierarchical two-level gather per bucket
        ag_algos = self._bucket_algos("allgather", tensors, buckets)
        ag_links = self._tensor_links("allgather", tensors, buckets,
                                      ag_algos)
        # wire codec (ISSUE 13): the GRADIENT reduce-scatter legs are
        # compressed (pre-scatter encode, rank-local decode — ownership
        # untouched); the parameter all-gather stays full precision
        call_codec = self._call_codec(codec, op)
        rs_codecs = self._bucket_codecs("reducescatter", tensors, buckets,
                                        call_codec)
        codec_of = {}
        for idxs, c in zip(buckets, rs_codecs):
            for i in idxs:
                codec_of[i] = c
        # wire accounting: a sharded step moves each gradient bucket once
        # as a reduce-scatter and once back as the parameter all-gather
        if self._m_enabled:
            self._m_collectives.inc(1.0, kind="sharded_step")
            for _ in buckets:
                self._m_algo.inc(kind="reducescatter", algo=C.ALGO_FLAT)
            local = self.topology.local_size
            for i, t in enumerate(tensors):
                rs_split = C.link_split(
                    C.ALGO_FLAT, t.nbytes, local, kind="reducescatter",
                    codec=codec_of.get(i, comp.CODEC_NONE),
                    itemsize=jnp.dtype(t.dtype).itemsize)
                self._m_wire.inc(rs_split["flat"], kind="reducescatter",
                                 dtype=str(t.dtype), link="flat")
                split = (ag_links[i] if ag_links
                         else {"flat": t.nbytes})
                for link, b in split.items():
                    if b:
                        self._m_wire.inc(b, kind="allgather",
                                         dtype=str(t.dtype), link=link)
            self._m_codec_saved("reducescatter", tensors, buckets,
                                (C.ALGO_FLAT,) * len(buckets), rs_codecs)
        self._m_buckets_obs(tensors, buckets)
        # register BEFORE replay interception: a replayed launch resolves
        # the update closure from this registry at trace time. LRU-bounded
        # like the builder cache (an armed program only reads the registry
        # when it first traces, so eviction after arming is harmless).
        lru_put(self._sharded_updates, update_key, update_fn,
                self.config.cache_capacity)
        all_ts = tensors + state_leaves
        r = self._replay.intercept("sharded_step", all_ts, int(op),
                                   prescale_factor, postscale_factor, name,
                                   sub,
                                   extra=(update_key, len(tensors), bkey,
                                          call_codec))
        if r is not None:
            return r
        self._join_sync("sharded_step",
                        [_join_meta_row(t, int(op)) for t in tensors],
                        skip=sub)
        self._note_model_sig(tensors)
        self._pm_step(sum(t.nbytes for t in tensors))
        def _sharded_link_bytes(i, t):
            # a sharded tensor moves once over the flat rs ring (encoded
            # when a codec is live) and once back over the (possibly
            # hierarchical) full-precision ag leg
            if i >= len(tensors):
                return None
            rs = C.link_split(C.ALGO_FLAT, t.nbytes,
                              self.topology.local_size,
                              kind="reducescatter",
                              codec=codec_of.get(i, comp.CODEC_NONE),
                              itemsize=jnp.dtype(t.dtype).itemsize)
            merged = {"flat": int(rs["flat"])}
            for link, b in (ag_links[i] if ag_links
                            else {"flat": int(t.nbytes)}).items():
                merged[link] = merged.get(link, 0) + int(b)
            return merged

        names = [self._register(None if name is None else f"{name}.{i}",
                                "sharded_step", t.nbytes,
                                link_bytes=_sharded_link_bytes(i, t))
                 for i, t in enumerate(all_ts)]
        self._debug_check(names[0], "sharded_step", tensors,
                          op_code=int(op), wildcard=sub)
        mesh = self.backend.group_mesh
        shapes = tuple(tuple(t.shape) for t in tensors)
        dtypes = tuple(str(t.dtype) for t in tensors)
        st_shapes = tuple(tuple(s.shape) for s in state_leaves)
        st_dtypes = tuple(str(s.dtype) for s in state_leaves)
        pack_fn = self._builder(("pack_group", shapes, dtypes, bkey),
                                lambda: C.build_pack_group(buckets))
        self._count_dispatch()
        packed = _translate_failure(pack_fn, *tensors)
        # error-feedback residual rows for the compressed rs legs, in
        # bucket order (the builders' residual I/O order)
        rs_ef = []
        for b, (bidxs, bc) in enumerate(zip(buckets, rs_codecs)):
            if bc in comp.EF_CODECS:
                total = sum(int(tensors[i].size) for i in bidxs)
                elems = C.codec_residual_elems(
                    "sharded", total, self.topology.size, 0, None, bc)
                rs_ef.append((b, ("zrs", update_key, b, bc, elems), elems,
                              str(tensors[bidxs[0]].dtype)))
        if any(c != comp.CODEC_NONE for c in rs_codecs):
            failpoint("compression.encode")
        # overlap (ISSUE 6): a stale world version invalidates held
        # prefetch legs even when the caller runs outside step markers
        self._refresh_world_version()
        self._prefetch_gc()
        # the grads arriving now were computed from the previous leg's
        # gathered params — that leg was REUSED, so retire its registry row
        # (after the gc above, which must still count bump-stranded rows):
        # invalidation counters only ever see legs dropped before this point
        with self._lock:
            self._zero1_prefetch.pop(update_key, None)
        mode = self._overlap_mode(sum(t.nbytes for t in tensors),
                                  len(buckets), sharded=True)
        # the split leg is a property of the STAGED schedule — the one
        # replay sustains with a zupd+zag stage plan. Splitting under
        # interleave would launch warmup-only legs that vanish (and strand
        # registry rows) the moment replay arms its monolithic program.
        prefetch = self.config.zero1_prefetch and mode == "staged"
        if not prefetch:
            if mode != "off":
                # mode label = the schedule actually dispatched: this
                # branch is ONE fused pipelined launch however the config
                # resolved, i.e. interleave scheduling (the staged split
                # only exists in replay's stage plan / the prefetch branch)
                self._note_overlap_step("interleave")
            fn = self._builder(
                ("sharded_step", op, prescale_factor, postscale_factor,
                 shapes, dtypes, bkey, st_shapes, st_dtypes, update_key,
                 mode != "off", ag_algos, rs_codecs),
                lambda: C.build_sharded_step(
                    mesh, self._axis(), op, shapes,
                    [t.dtype for t in tensors],
                    buckets, st_shapes, st_dtypes, update_fn,
                    prescale_factor, postscale_factor,
                    pipeline=(mode != "off"),
                    local_size=self.topology.local_size,
                    ag_algos=ag_algos, codecs=rs_codecs))
            res_args = [self.backend.world_view(
                self._residual_fetch(k, e, dt))
                for _, k, e, dt in rs_ef]
            outs = self._dispatch(
                names,
                lambda: fn(*([self.backend.to_global(p, batched=True)
                              for p in packed]
                             + [self.backend.world_view(s)
                                for s in state_leaves] + res_args)))
            for j, (_, k, _, _) in enumerate(rs_ef):
                self._residual_store(k, outs[len(all_ts) + j])
            group = LaunchGroup(outs[-1])
            handles = []
            for i, nm in enumerate(names):
                h = Handle(nm, [outs[i]],
                           lambda gs: self.backend.from_replicated(gs[0]),
                           self, group=group, kind="sharded_step")
                self._track(nm, h)
                handles.append(h)
            return handles
        # -- split ZeRO-1 step with all-gather prefetch (the tentpole) --
        # Launch 1: rs -> shard-local update, returning the STACKED updated
        # parameter shards + new state. Launch 2 (the prefetch leg): the
        # parameter all-gather, riding as its own launch under the step's
        # tail — state consumers never wait on it, step N+1's forward
        # chains onto its dataflow futures, and the engine holds the leg
        # across the step boundary (dropped on world-version bumps).
        upd_fn = self._builder(
            ("sharded_update", op, prescale_factor, postscale_factor,
             shapes, dtypes, bkey, st_shapes, st_dtypes, update_key,
             rs_codecs),
            lambda: C.build_sharded_update(
                mesh, self._axis(), op, shapes, [t.dtype for t in tensors],
                buckets, st_shapes, st_dtypes, update_fn,
                prescale_factor, postscale_factor, packed=True,
                codecs=rs_codecs))
        res_args = [self.backend.world_view(self._residual_fetch(k, e, dt))
                    for _, k, e, dt in rs_ef]
        outs = self._dispatch(
            names,
            lambda: upd_fn(*([self.backend.to_global(p, batched=True)
                              for p in packed]
                             + [self.backend.world_view(s)
                                for s in state_leaves] + res_args)))
        shard_garrs = outs[:len(buckets)]
        state_garrs = outs[len(buckets):len(buckets) + len(state_leaves)]
        for j, (_, k, _, _) in enumerate(rs_ef):
            self._residual_store(
                k, outs[len(buckets) + len(state_leaves) + j])
        upd_group = LaunchGroup(outs[-1])
        failpoint("overlap.prefetch")
        ag_fn = self._builder(
            ("zero1_prefetch_allgather", shapes, dtypes, bkey, ag_algos),
            lambda: C.build_grouped_allgather(
                mesh, self._axis(), shapes, [t.dtype for t in tensors],
                buckets, pipeline=True,
                local_size=self.topology.local_size, algos=ag_algos))
        ag_outs = self._dispatch(names[:len(tensors)],
                                 lambda: ag_fn(*shard_garrs))
        ag_group = LaunchGroup(ag_outs[-1])
        self._note_prefetch(update_key)
        self._m_overlap_stages.inc(2.0, kind="sharded_prefetch")
        # two staged sub-launches, matching the stage-launch accounting
        # above — this branch is only reachable with mode == "staged"
        self._note_overlap_step("staged")
        handles = []
        for i, nm in enumerate(names):
            if i < len(tensors):
                garr, group = ag_outs[i], ag_group
            else:
                garr, group = state_garrs[i - len(tensors)], upd_group
            h = Handle(nm, [garr],
                       lambda gs: self.backend.from_replicated(gs[0]),
                       self, group=group, kind="sharded_step")
            self._track(nm, h)
            handles.append(h)
        return handles

    def allgather(self, tensor, name: Optional[str] = None,
                  equal_sizes: bool = False,
                  _sub_hash: Optional[int] = None) -> Handle:
        """Allgather with possibly different dim-0 sizes per rank
        (collective_operations.cc:88-195 displacement math): a small size
        exchange first, then pad to max and gather, then slice+concat.

        ``equal_sizes=True`` is the caller's contract that every rank's
        dim 0 matches (e.g. a statically-shaped per-step exchange): the
        size negotiation is skipped entirely — no exchange, no cache, no
        deferred check (debug-consistency mode then validates dim 0 too).

        ``_sub_hash`` (internal): a join substitute replaying an active
        rank's op passes the advertised name hash so it can find ITS OWN
        cache entry for that name — it then contributes a zero tensor of
        its previously-advertised size and replays the exact hot/cold
        exchange behavior of its peers (same collective sequence, and the
        hot peers' deferred check still sees an unchanged world)."""
        x = jnp.asarray(tensor)
        sub = self._consume_substitute()
        ag_algo = self._choose_algo("allgather", x.nbytes)
        if self._m_enabled and self.backend.size() > 1:
            self._m_algo.inc(kind="allgather", algo=ag_algo)
        links = None
        if self.backend.size() > 1 and (self._m_enabled
                                        or self.trace is not None):
            links = [C.link_split(ag_algo, x.nbytes,
                                  self.topology.local_size,
                                  kind="allgather")]
        self._m_account("allgather", [x], links)
        self._replay.observe("allgather", sub, [x], name)
        name = self._register(name, "allgather", x.nbytes,
                              link_bytes=links[0] if links else None)
        key_hash = _sub_hash if _sub_hash is not None else \
            self._meta_hash(name)
        # allgather's op_or_root meta field carries (hash << 1) | equal_bit
        # so the substitute can mirror both the cache key and the
        # no-exchange fast path (a substitute that dispatched an exchange
        # its peers skipped would desynchronize the collective sequence)
        self._join_sync("allgather",
                        [_join_meta_row(x, (key_hash << 1)
                                        | (1 if equal_sizes else 0))],
                        skip=sub)
        self._debug_check(name, "allgather", [x], check_dim0=equal_sizes,
                          wildcard=sub)
        mesh = self.backend.group_mesh
        size = self.backend.size()
        if _sub_hash is not None and not equal_sizes:
            ent = self._meta_cache.get(("allgather", _sub_hash))
            if ent is not None:
                old_d0 = int(ent["local"][0])
                if x.ndim == 0:
                    x = x[None]
                x = jnp.zeros((old_d0,) + tuple(x.shape[1:]), x.dtype)
        d0 = int(x.shape[0]) if x.ndim else 1
        if equal_sizes:
            world = np.full((size, 1), d0, dtype=np.int32)
            deferred = None
        else:
            world, deferred = self._exchange_sizes_cached(
                "allgather", key_hash, np.array([d0], dtype=np.int32))
        sizes = world[:, 0]
        max_d0 = int(sizes.max()) if size > 1 else d0
        if x.ndim == 0:
            x = x[None]
        if deferred is not None and deferred["stale_local"] and d0 > max_d0:
            # this rank's rows grew past the hot peers' cached program
            # shape; dispatch the cached shape anyway (content is garbage —
            # every rank raises at extract via the failed deferred check)
            x = x[:max_d0]
            d0 = max_d0
        pad = max_d0 - d0
        xp = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if pad else x
        if ag_algo == C.ALGO_HIERARCHICAL:
            local = self.topology.local_size
            fn = self._builder(
                ("hier_allgather", local),
                lambda: C.build_hierarchical_allgather(mesh, self._axis(),
                                                       local))
        else:
            fn = self._builder(("allgather",),
                               lambda: C.build_allgather(mesh, self._axis()))
        out = self._dispatch(name, lambda: fn(self.backend.to_global(xp)))

        def extract(gs):
            self._verify_deferred(name, deferred)
            local = self.backend.from_replicated(gs[0])  # (size*max_d0, *s)
            if all(int(s) == max_d0 for s in sizes):
                return local
            parts = [local[r * max_d0: r * max_d0 + int(sizes[r])]
                     for r in range(size)]
            return jnp.concatenate(parts, axis=0)

        h = Handle(name, [out], extract, self, kind="allgather")
        h.recv_sizes = np.asarray(sizes)
        self._track(name, h)
        return h

    def broadcast(self, tensor, root_rank: int, name: Optional[str] = None) -> Handle:
        x = jnp.asarray(tensor)
        sub = self._consume_substitute()
        self._m_account("broadcast", [x])
        r = self._replay.intercept("broadcast", [x], root_rank, 1.0, 1.0,
                                   name, sub)
        if r is not None:
            return r[0]
        name = self._register(name, "broadcast", x.nbytes)
        self._join_sync("broadcast", [_join_meta_row(x, root_rank)], skip=sub)
        self._debug_check(name, "broadcast", [x], op_code=root_rank,
                          wildcard=sub)
        mesh = self.backend.group_mesh
        if not self.config.join_enabled or self.backend.size() <= 1:
            fn = self._builder(
                ("broadcast", root_rank),
                lambda: C.build_broadcast(mesh, self._axis(), root_rank))
            out = self._dispatch(name, lambda: fn(self.backend.to_global(x)))
            return self._single(name, out, kind="broadcast")
        # Join-enabled worlds carry the root's active bit in the same launch
        # (build_broadcast_flagged): a join substitute from a joined root
        # sends active=0, and extract raises instead of returning zeros —
        # the joined-root error with no blocking submission-side round-trip.
        fn = self._builder(
            ("broadcast_flagged", root_rank),
            lambda: C.build_broadcast_flagged(mesh, self._axis(), root_rank))
        active = np.zeros((1,), np.int32) if sub else np.ones((1,), np.int32)
        out, flag = self._dispatch(
            name, lambda: fn(self.backend.to_global(x),
                             self.backend.to_global(active)))

        def extract(gs):
            data, fl = gs
            got = int(_translate_failure(
                np.asarray, self.backend.from_replicated(fl))[0])
            if got != 1:
                raise HorovodInternalError(
                    f"broadcast root rank {root_rank} has already joined "
                    f"and has no data to broadcast")
            return self.backend.from_replicated(data)

        h = Handle(name, [out, flag], extract, self, kind="broadcast")
        self._track(name, h)
        return h

    def grouped_broadcast(self, tensors: Sequence, root_rank: int,
                          name: Optional[str] = None) -> List[Handle]:
        """Fused broadcast of many tensors: bucketed packing, one collective
        launch per <= fusion_threshold bucket per dtype, ONE root-active
        flag read per bucket — the fusion-buffer treatment applied to
        broadcast_parameters' init storm (N per-leaf launches + N blocking
        waits collapse to a handful; reference fusion rationale,
        controller.cc:652-773)."""
        tensors = [jnp.asarray(t) for t in tensors]
        sub = self._consume_substitute()
        if not tensors:
            return []
        self._m_account("grouped_broadcast", tensors)
        r = self._replay.intercept("grouped_broadcast", tensors, root_rank,
                                   1.0, 1.0, name, sub)
        if r is not None:
            return r
        self._join_sync("grouped_broadcast",
                        [_join_meta_row(t, root_rank) for t in tensors],
                        skip=sub)
        names = [self._register(None if name is None else f"{name}.{i}",
                                "grouped_broadcast", t.nbytes)
                 for i, t in enumerate(tensors)]
        self._debug_check(names[0], "grouped_broadcast", tensors,
                          op_code=root_rank, wildcard=sub)
        mesh = self.backend.group_mesh
        check_join = self.config.join_enabled and self.backend.size() > 1
        active = np.zeros((1,), np.int32) if sub else np.ones((1,), np.int32)
        results: Dict[int, tuple] = {}
        bc_buckets = bucket_by_size(tensors,
                                    self.config.fusion_threshold_bytes)
        self._m_buckets_obs(tensors, bc_buckets)
        for idxs in bc_buckets:
            bucket = [tensors[i] for i in idxs]
            shapes = tuple(tuple(t.shape) for t in bucket)
            dtype = bucket[0].dtype
            pack_fn = self._builder(("pack", shapes, str(dtype)),
                                    lambda: C.build_pack(shapes, dtype))
            packed = _translate_failure(pack_fn, *bucket)
            fn = self._builder(
                ("fused_broadcast", root_rank, shapes, str(dtype)),
                lambda: C.build_fused_broadcast(mesh, self._axis(),
                                                root_rank, shapes, dtype))
            outs = self._dispatch(
                [names[i] for i in idxs],
                lambda: fn(self.backend.to_global(packed),
                           self.backend.to_global(active)))
            flag = outs[-1]
            group = LaunchGroup(flag)
            gate = {"state": None}   # None -> unchecked; True/False
            for pos, i in enumerate(idxs):
                results[i] = (outs[pos], flag, group, gate)
        handles = []
        for i, nm in enumerate(names):
            garr, flag, group, gate = results[i]

            def extract(gs, _flag=flag, _gate=gate):
                # one flag fetch per BUCKET; every leaf of a dead-root
                # bucket raises (never silently returns zeros)
                if check_join and _gate["state"] is None:
                    got = int(_translate_failure(
                        np.asarray, self.backend.from_replicated(_flag))[0])
                    _gate["state"] = (got == 1)
                if check_join and not _gate["state"]:
                    raise HorovodInternalError(
                        f"broadcast root rank {root_rank} has already "
                        f"joined and has no data to broadcast")
                return self.backend.from_replicated(gs[0])

            h = Handle(nm, [garr], extract, self, group=group,
                       kind="grouped_broadcast")
            self._track(nm, h)
            handles.append(h)
        return handles

    def alltoall(self, tensor, splits=None, name: Optional[str] = None,
                 _sub_hash: Optional[int] = None) -> Handle:
        """Alltoall with optional uneven splits (operations.cc:951,
        mpi_operations.cc:380 MPI_Alltoallv semantics). Returns handle whose
        result is (received_tensor, recv_splits). ``_sub_hash``: see
        :meth:`allgather` — the join-substitute replay path.

        Topology-aware lowering (ISSUE 17): a rank whose splits are even
        selects flat vs the hierarchical two-phase exchange per
        (bytes, topology) through :meth:`_choose_algo` and books its wire
        bytes under the ICI/DCN link split (stamped on the trace enqueue
        event too). The hierarchical program actually dispatches only
        when the EXCHANGED splits matrix is uniform — a collectively
        agreed predicate, and uniformity implies every rank's payload
        bytes (hence selection) were identical, so the demotion to flat
        on ragged worlds can never diverge. Explicit uneven splits keep
        the flat whole-world exchange; padding bytes are never counted
        as wire bytes (accounting uses ``x.nbytes``, pre-padding)."""
        x = jnp.asarray(tensor)
        sub = self._consume_substitute()
        size = self.backend.size()
        mesh = self.backend.group_mesh
        if _sub_hash is not None:
            ent = self._meta_cache.get(("alltoall", _sub_hash))
            if ent is not None:
                # contribute zeros under the joined rank's OLD splits so
                # hot peers' cached world (and program shapes) still match
                splits = ent["local"].astype(np.int32)
                x = jnp.zeros((int(splits.sum()),) + tuple(x.shape[1:]),
                              x.dtype)
        if splits is None:
            if int(x.shape[0]) % size != 0:
                raise ValueError(
                    f"alltoall without splits requires dim0 ({x.shape[0]}) divisible "
                    f"by size ({size})")
            splits = np.full((size,), int(x.shape[0]) // size, dtype=np.int32)
        else:
            splits = np.asarray(splits, dtype=np.int32)
            if splits.sum() != int(x.shape[0]):
                raise ValueError("splits must sum to tensor dim 0")
        d0 = int(x.shape[0])
        rowbytes = x.nbytes // d0 if d0 else 0
        # Rank-local selection hint for accounting/trace; the dispatched
        # lowering is re-agreed from the exchanged matrix below. In the
        # steady even-splits case (the MoE dispatch shape) hint and
        # dispatch always coincide.
        hint = C.ALGO_FLAT
        codec = comp.CODEC_NONE
        links = None
        if size > 1 and splits.size and bool((splits == splits[0]).all()):
            hint = self._choose_algo("alltoall", x.nbytes)
            if self._m_enabled:
                self._m_algo.inc(kind="alltoall", algo=hint)
            codec = self._a2a_codecs([x], [[0]], (hint,))[0]
            links = self._a2a_links([x], [[0]], (hint,), (codec,))
            self._m_codec_saved("alltoall", [x], [[0]], (hint,), (codec,),
                                links, size=size)
        self._m_account("alltoall", [x], links)
        self._replay.observe("alltoall", sub, [x], name)
        name = self._register(name, "alltoall", x.nbytes,
                              link_bytes=links[0] if links else None)
        key_hash = _sub_hash if _sub_hash is not None else \
            self._meta_hash(name)
        self._join_sync("alltoall", [_join_meta_row(x, key_hash << 1)],
                        skip=sub)
        self._debug_check(name, "alltoall", [x], check_dim0=False,
                          wildcard=sub)
        # Exchange the full splits matrix: recv_splits[r] = splits_of_rank_r[me]
        # (controller's AlltoallGetRecvSplits, mpi_controller.cc:212).
        all_splits, deferred = self._exchange_sizes_cached(
            "alltoall", key_hash, splits)  # (size, size)
        me = self.backend.rank()
        recv_splits = all_splits[:, me]
        max_chunk = int(all_splits.max()) if size > 1 else int(splits.max())
        uniform = size > 1 and bool((all_splits == all_splits[0, 0]).all())
        if deferred is not None and deferred["stale_local"]:
            # splits changed after peers' cache went hot: dispatch with the
            # cached program shape (clamped garbage chunks) so nothing
            # hangs; every rank raises at extract
            splits = np.minimum(splits, max_chunk)
            if uniform:
                # this rank's live bytes changed but peers dispatch the
                # cached-shape program — re-derive the selection from the
                # AGREED matrix so the programs still match
                hint = self._choose_algo(
                    "alltoall", int(all_splits[0, 0]) * size * rowbytes)
                codec = self._a2a_codecs([x], [[0]], (hint,),
                                         count=False)[0]
        algo = hint if uniform else C.ALGO_FLAT
        # Pad each send chunk to max_chunk, run equal alltoall, slice out.
        offs = np.concatenate([[0], np.cumsum(splits)[:-1]])
        chunks = [jax.lax.dynamic_slice_in_dim(x, int(offs[r]), int(splits[r]))
                  for r in range(size)]
        padded = jnp.concatenate([
            jnp.pad(c, [(0, max_chunk - c.shape[0])] + [(0, 0)] * (x.ndim - 1))
            for c in chunks]) if size > 1 else x
        if algo == C.ALGO_HIERARCHICAL:
            local = self.topology.local_size
            fn = self._builder(
                ("alltoall", C.ALGO_HIERARCHICAL, codec, local),
                lambda: C.build_hierarchical_alltoall(
                    mesh, self._axis(), local, codec))
        else:
            fn = self._builder(("alltoall",),
                               lambda: C.build_alltoall(mesh, self._axis()))
        out = self._dispatch(name, lambda: fn(self.backend.to_global(padded)))

        def extract(gs):
            self._verify_deferred(name, deferred)
            local = self.backend.from_global(gs[0])  # (size*max_chunk, *s)
            if size == 1:
                return local, jnp.asarray(recv_splits)
            parts = [local[r * max_chunk: r * max_chunk + int(recv_splits[r])]
                     for r in range(size)]
            return jnp.concatenate(parts, axis=0), jnp.asarray(recv_splits)

        h = Handle(name, [out], extract, self, kind="alltoall")
        self._track(name, h)
        return h

    def grouped_alltoall(self, tensors: Sequence,
                         name: Optional[str] = None) -> List[Handle]:
        """Fused even-split alltoall of many tensors (ISSUE 17): the
        dispatch-traffic analog of :meth:`grouped_allreduce`, closing the
        last fusion-bucketing gap in the op surface. Each tensor's dim 0
        must divide the world size (the capacity-routed MoE dispatch
        shape — fixed per step, identical on every rank, which is what
        makes the call REPLAYABLE: a steady-state MoE-EP step collapses
        to one fused launch). Per fusion bucket the member chunk
        matrices concatenate into one exchange buffer, the bucket picks
        flat vs hierarchical per (bytes, topology), and the
        HOROVOD_TPU_ALLTOALL_CODEC codec encodes hierarchical buckets'
        DCN leg only. Returns one handle per tensor whose result is the
        received tensor (recv splits are even by contract)."""
        tensors = [jnp.asarray(t) for t in tensors]
        sub = self._consume_substitute()
        size = self.backend.size()
        for t in tensors:
            if t.ndim == 0 or int(t.shape[0]) % size != 0:
                raise ValueError(
                    f"grouped_alltoall requires every tensor's dim 0 "
                    f"divisible by size ({size}); got {tuple(t.shape)}. "
                    f"Use alltoall(splits=...) for ragged dispatch.")
        links = None
        derived = None   # (threshold, sig, buckets, algos, codecs) reuse
        if tensors:
            if self.topology.size > 1:
                thr0 = self.config.fusion_threshold_bytes
                b0 = bucket_by_size(tensors, thr0)
                a0 = self._bucket_algos("alltoall", tensors, b0)
                c0 = self._a2a_codecs(tensors, b0, a0)
                links = self._a2a_links(tensors, b0, a0, c0)
                self._m_codec_saved("alltoall", tensors, b0, a0, c0,
                                    links, size=size)
                derived = (thr0, self._algo_sig(), b0, a0, c0)
            self._m_account("grouped_alltoall", tensors, links)
            r = self._replay.intercept("grouped_alltoall", tensors, 0,
                                       1.0, 1.0, name, sub)
            if r is not None:
                return r
        self._join_sync("grouped_alltoall",
                        [_join_meta_row(t, 0) for t in tensors], skip=sub)
        names = [self._register(None if name is None else f"{name}.{i}",
                                "grouped_alltoall", t.nbytes,
                                link_bytes=links[i] if links else None)
                 for i, t in enumerate(tensors)]
        self._debug_check(names[0] if names else "empty",
                          "grouped_alltoall", tensors, wildcard=sub)
        if not tensors:
            return []
        if derived is not None \
                and derived[0] == self.config.fusion_threshold_bytes \
                and derived[1] == self._algo_sig():
            buckets, algos, codecs = derived[2], derived[3], derived[4]
        else:
            buckets = bucket_by_size(tensors,
                                     self.config.fusion_threshold_bytes)
            algos = self._bucket_algos("alltoall", tensors, buckets,
                                       count=False)
            codecs = self._a2a_codecs(tensors, buckets, algos,
                                      count=False)
        self._m_buckets_obs(tensors, buckets)
        mesh = self.backend.group_mesh
        local = self.topology.local_size
        shapes = tuple(tuple(t.shape) for t in tensors)
        dtypes = tuple(str(t.dtype) for t in tensors)
        bkey = tuple(tuple(b) for b in buckets)
        fn = self._builder(
            ("grouped_alltoall", shapes, dtypes, bkey, local, algos,
             codecs),
            lambda: C.build_grouped_alltoall(
                mesh, self._axis(), shapes, [t.dtype for t in tensors],
                buckets, local_size=local, algos=algos, codecs=codecs))
        outs = self._dispatch(
            names,
            lambda: fn(*[self.backend.to_global(t) for t in tensors]))
        group = LaunchGroup(outs[-1])
        handles = []
        for i, nm in enumerate(names):
            h = Handle(nm, [outs[i]],
                       lambda gs: self.backend.from_global(gs[0]), self,
                       group=group, kind="grouped_alltoall")
            self._track(nm, h)
            handles.append(h)
        return handles

    def reducescatter(self, tensor, name: Optional[str] = None,
                      op: ReduceOp = ReduceOp.SUM) -> Handle:
        if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
            raise ValueError(f"reducescatter supports Sum and Average, got {op!r}")
        x = jnp.asarray(tensor)
        sub = self._consume_substitute()
        _check_average_dtype(x, op)
        self._m_account("reducescatter", [x])
        self._replay.observe("reducescatter", sub, [x], name)
        name = self._register(name, "reducescatter", x.nbytes)
        self._join_sync("reducescatter", [_join_meta_row(x, int(op))],
                        skip=sub)
        self._debug_check(name, "reducescatter", [x], op_code=int(op),
                          wildcard=sub)
        size = self.backend.size()
        if x.ndim == 0:
            raise ValueError("reducescatter requires a tensor with dim 0")
        d0 = int(x.shape[0])
        # Pad dim 0 to divisibility inside the builder and slice the shard
        # back (the allgather inverse): rank r owns rows
        # [r*chunk, min((r+1)*chunk, d0)) per the shared ZeRO-1 shard
        # assignment — trailing ranks get fewer (possibly zero) rows, and
        # concatenating every rank's shard reproduces the full reduced
        # tensor exactly.
        padded, chunk = C.shard_spec(d0, size)
        pad = padded - d0
        mesh = self.backend.group_mesh
        fn = self._builder(("reducescatter", op, pad),
                           lambda: C.build_reducescatter(mesh, self._axis(),
                                                         op, pad_rows=pad))
        out = self._dispatch(name, lambda: fn(self.backend.to_global(x)))
        if not pad:
            return self._single(name, out, replicated=False,
                                kind="reducescatter")
        rank = self.backend.rank()
        keep = min(chunk, max(d0 - rank * chunk, 0))

        def extract(gs):
            shard = self.backend.from_global(gs[0])  # (chunk, *s) padded
            return shard if keep == chunk else shard[:keep]

        h = Handle(name, [out], extract, self, kind="reducescatter")
        h.recv_sizes = np.array(
            [min(chunk, max(d0 - r * chunk, 0)) for r in range(size)])
        self._track(name, h)
        return h

    def barrier(self):
        self._check_poison()
        sub = self._consume_substitute()
        self._m_account("barrier", [])
        self._replay.observe("barrier", sub)
        self._join_sync("barrier", [], skip=sub)
        mesh = self.backend.group_mesh
        fn = self._builder(("barrier",), lambda: C.build_barrier(mesh, self._axis()))
        self._count_dispatch()
        out = _translate_failure(
            lambda: fn(self.backend.to_global(jnp.zeros((), jnp.int32))))
        _translate_failure(out.block_until_ready)

    # -- helpers -----------------------------------------------------------

    def _dispatch_exchange(self, local_vec: np.ndarray) -> jax.Array:
        """Launch a tiny metadata allgather WITHOUT waiting: returns the
        global array future. The join fast path relies on this being
        fire-and-forget (no host round-trip on the active ranks)."""
        mesh = self.backend.group_mesh
        fn = self._builder(("allgather",),
                           lambda: C.build_allgather(mesh, self._axis()))
        self._count_dispatch()
        return _translate_failure(
            lambda: fn(self.backend.to_global(jnp.asarray(local_vec))))

    def _fetch_exchange(self, garr: jax.Array, vec_shape) -> np.ndarray:
        """Blocking read-back of a _dispatch_exchange result. Every call is
        one host round-trip; ``host_fetches`` counts them so tests (and the
        bench) can assert the steady-state eager path performs none."""
        self.host_fetches += 1
        with scopes.host_span(scopes.ENGINE_FETCH):
            local = self.backend.from_replicated(garr)
            return _translate_failure(np.asarray, local).reshape(
                self.backend.size(), *vec_shape)

    def _exchange_sizes(self, local_vec: np.ndarray) -> np.ndarray:
        """Tiny metadata allgather used by unequal allgather/alltoall; the
        eager analog of the controller's size negotiation. Blocking (returns
        concrete numpy)."""
        if self.backend.size() == 1:
            return np.asarray(local_vec)[None]
        garr = self._dispatch_exchange(local_vec)
        return self._fetch_exchange(garr, np.asarray(local_vec).shape)

    def _meta_hash(self, name: str) -> int:
        """30-bit name hash used as the metadata-cache key and carried in
        join meta rows (packed with flag bits), so a join substitute can
        find the joined rank's own cache entry for the op it is matching.
        30 bits because meta rows ride jnp int arrays that are int32 on the
        wire under JAX's default x64-disabled mode — a wider hash would
        truncate silently. A (rare) collision merges two names' size-cache
        entries; differing sizes then surface through the deferred check as
        a loud mismatch, never silent corruption."""
        return self._h63(name) & ((1 << 30) - 1)

    def _exchange_sizes_cached(self, kind: str, key_hash: int,
                               local_vec: np.ndarray):
        """Size negotiation with a per-name steady-state cache (the
        ResponseCache role, response_cache.h:45-102): after ``warmup``
        consecutive identical world observations for (kind, name), the
        exchange switches to a fire-and-forget advertisement — the cached
        sizes shape the program NOW, and a consistency check against the
        in-flight exchange is deferred to extract time (the user's first
        natural sync point). Returns (world, deferred); pass ``deferred`` to
        :meth:`_verify_deferred` inside the handle's extract."""
        if self.backend.size() == 1:
            return np.asarray(local_vec)[None], None
        local_vec = np.asarray(local_vec)
        key = (kind, key_hash)
        ent = self._meta_cache.get(key)
        if (self.config.meta_cache and ent is not None
                and ent["streak"] >= self.config.meta_cache_warmup):
            lru_touch(self._meta_cache, key, ent)
            garr = self._dispatch_exchange(local_vec)
            # If THIS rank's sizes changed while peers are hot, taking the
            # blocking path here would make this rank build a differently-
            # shaped collective program than its hot peers — a hang, not an
            # error. Instead: keep the cached (stale) world so every rank
            # dispatches the SAME program (the call site reconciles its
            # input to the cached shape; the data is garbage), and force
            # the deferred check to fail on every rank — peers see the
            # changed advertisement, this rank knows it changed.
            stale = not np.array_equal(ent["local"], local_vec)
            deferred = {"key": key, "garr": garr, "expected": ent["world"],
                        "shape": local_vec.shape, "error": None,
                        "checked": False, "stale_local": stale}
            return ent["world"], deferred
        world = self._exchange_sizes(local_vec)
        if ent is not None and np.array_equal(ent["world"], world):
            ent["streak"] += 1
            ent["local"] = local_vec.copy()
            # MRU-touch on the warming path too (ADVICE r4): under cache
            # pressure an entry one call short of hot must not be the LRU
            # victim or it never reaches steady state. lru_touch tolerates
            # the cycle thread having concurrently invalidated the entry
            # while this thread blocked in _exchange_sizes — and
            # re-inserting is sound even then, because the fresh exchange
            # just confirmed ent["world"] is the live world observation.
            lru_touch(self._meta_cache, key, ent)
        else:
            lru_put(self._meta_cache, key,
                    {"world": world, "streak": 1, "local": local_vec.copy()},
                    self.config.cache_capacity)
        return world, None

    def _verify_deferred(self, name: str, deferred) -> None:
        """Extract-time consistency check of a fire-and-forget size exchange:
        compare what peers actually advertised against the cached sizes the
        program was built with. A mismatch means the result is garbage —
        invalidate the cache entry and raise on every rank (loud, never
        silent corruption). The read costs one tiny host fetch at a moment
        the caller is already blocking on the real result.

        The outcome is REMEMBERED: the engine's cycle thread also drives
        extracts (and swallows their exceptions as retire noise), so a
        one-shot check would let it consume the error and a later user
        synchronize() would silently return the garbage. Every extract of a
        mismatched handle re-raises."""
        if deferred is None:
            return
        if deferred["checked"]:
            if deferred["error"] is not None:
                raise deferred["error"]
            return
        mismatch = deferred["stale_local"]
        if not mismatch:
            self.deferred_meta_checks += 1
            local = self.backend.from_replicated(deferred["garr"])
            world = _translate_failure(np.asarray, local).reshape(
                self.backend.size(), *deferred["shape"])
            mismatch = not np.array_equal(world, deferred["expected"])
        deferred["checked"] = True
        if mismatch:
            self._meta_cache.pop(deferred["key"], None)
            deferred["error"] = HorovodInternalError(
                f"steady-state size cache mismatch for {name!r}: tensor "
                f"sizes changed after {self.config.meta_cache_warmup} "
                f"identical exchanges (cached "
                f"{deferred['expected'].tolist()}). The op's result was "
                f"discarded on every rank. Use distinct tensor names for "
                f"varying-size collectives, or set "
                f"HOROVOD_TPU_META_CACHE=0.")
            raise deferred["error"]


def bucket_by_size(tensors: Sequence[jax.Array], threshold_bytes: int) -> List[List[int]]:
    """Group tensor indices into fusion buckets: same dtype, cumulative size
    <= threshold (mixed-dtype look-ahead of controller.cc:652-773 becomes
    simple per-dtype bucketing since packing is free under XLA)."""
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    cur_dtype = None
    for i, t in enumerate(tensors):
        nb = t.nbytes
        if cur and (t.dtype != cur_dtype or cur_bytes + nb > threshold_bytes):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
        cur_dtype = t.dtype
    if cur:
        buckets.append(cur)
    return buckets
