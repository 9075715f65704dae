"""Step-capture replay: record the eager dispatch stream, re-execute it as
one fused XLA launch.

The reference's core runtime exists to amortize per-op dispatch cost
(tensor fusion, controller.cc:652-773, + the ResponseCache,
response_cache.h:45-102). Our eager path still paid that cost per step:
even the single-launch grouped allreduce is pack-dispatch + reduce-dispatch
plus per-leaf Python bookkeeping (registration, join advertisement,
bucketing, handle tracking). This module is the CUDA-graph-style answer:

- The engine exposes ``step_begin()``/``step_end()`` markers (surfaced as
  ``hvd.step_begin``/``hvd.step_end``/``hvd.step()``; the eager optimizer
  wraps its reduction phase in them automatically).
- Between markers the engine reports every collective call here as a
  :class:`CallSig` — (kind, op/root, dtypes, shapes, scale factors,
  digit-normalized name). The ordered tuple of sigs is the step's
  **signature**.
- Once the same signature repeats ``HOROVOD_TPU_STEP_REPLAY_WARMUP``
  times, the stream is **armed**: one jitted program
  (``ops.collectives.build_replay_step``) covering every recorded call —
  pack, per-bucket collective, unpack — is compiled, and subsequent
  matching steps are serviced by a SINGLE dispatch (plus one
  fire-and-forget join advertisement when the Join protocol is live).
- Any divergence — a different op, a wait before the stream completes, a
  substitute dispatch, extra ops after the recorded stream — falls back
  transparently: tensors buffered so far are flushed through the recorded
  program (missing slots zero-padded; slot outputs are independent, so the
  prefix results are exact), the step finishes on the normal path, and a
  timeline event + stall-inspector-visible counter record the fallback.
- ``join()`` and an elastic world-version bump invalidate every armed
  stream (the program may no longer match the world).

Multiple distinct step signatures (e.g. alternating train/eval) each get
their own armed program; prefix-ambiguous candidates are disambiguated by
the next op or at ``step_end``.
"""

from __future__ import annotations

import re
import time
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np
import jax.numpy as jnp

from ..common import scopes
from ..common.lru import lru_get, lru_put
from ..metrics import registry as metrics_registry
from ..ops import collectives as _C
from ..ops import compression as _comp

# step counters in tensor names ("grad.s17", "bench.grad.42") must not make
# otherwise-identical steps look distinct — normalize digit runs away
_DIGITS = re.compile(r"\d+")

_REDUCE_KINDS = ("allreduce", "grouped_allreduce")
_BCAST_KINDS = ("broadcast", "grouped_broadcast")
_SHARDED_KINDS = ("sharded_step",)
# even-split alltoall dispatch groups (ISSUE 17): fixed shapes by
# contract (capacity-routed MoE dispatch), so the exchange is replayable;
# the uneven-splits eager alltoall stays on the observe() path — its
# splits negotiation cannot be baked into a captured program
_A2A_KINDS = ("grouped_alltoall",)
_MAX_STREAMS = 16  # bound the per-signature table (LRU)


class CallSig(NamedTuple):
    """One recorded engine call: the replay key the ISSUE names —
    (kind, op, dtype, shape, name) — plus the scale factors that change the
    compiled program."""
    kind: str
    code: int          # ReduceOp code, or root rank for broadcasts
    shapes: tuple      # per-tensor shape tuples
    dtypes: tuple      # per-tensor dtype strings
    pre: float
    post: float
    name: str          # digit-normalized name template
    replayable: bool
    extra: tuple = ()  # sharded_step: (update_key, n_grads, frozen buckets)


def _make_sig(kind: str, tensors, code: int, pre: float, post: float,
              name: Optional[str], replayable: bool,
              extra: tuple = ()) -> CallSig:
    return CallSig(
        kind, int(code),
        tuple(tuple(int(d) for d in t.shape) for t in tensors),
        tuple(str(t.dtype) for t in tensors),
        float(pre), float(post),
        _DIGITS.sub("#", name or ""), replayable, tuple(extra))


class _LeafProxy:
    """Shape/dtype stand-in with the ``.nbytes``/``.dtype`` surface
    ``bucket_by_size`` consumes, so arming can bucket without tensors."""
    __slots__ = ("shape", "dtype", "nbytes")

    def __init__(self, shape, dtype_str):
        self.shape = shape
        self.dtype = np.dtype(dtype_str)  # ml_dtypes registers bfloat16
        self.nbytes = int(np.prod(shape)) * self.dtype.itemsize \
            if shape else self.dtype.itemsize


class _Bound:
    """Live result of one replayed tensor: the thin post-launch handle
    surface (poll/result/synchronize), completion shared through the
    launch's :class:`~.engine.LaunchGroup` — one readiness RPC per replayed
    step, not per tensor."""
    __slots__ = ("_garr", "_group", "_engine", "_val", "_have")

    def __init__(self, garr, group, engine):
        self._garr = garr
        self._group = group
        self._engine = engine
        self._val = None
        self._have = False

    def poll(self) -> bool:
        return self._group.ready()

    def result(self):
        if not self._have:
            self._val = self._engine.backend.from_replicated(self._garr)
            self._have = True
        return self._val

    def synchronize(self):
        if not self._group.ready():
            self._engine.host_blocks += 1
            with scopes.host_span(scopes.ENGINE_WAIT):
                self._group.wait()
        return self.result()


class ReplayHandle:
    """Handle returned while a step is being replayed. Until the recorded
    stream completes, the fused launch has not happened yet — any wait or
    result access forces it (zero-padding slots not yet submitted, an
    observable fallback)."""
    __slots__ = ("_replay", "name", "recv_sizes", "_bound")

    def __init__(self, replay: "StepReplay", name: str):
        self._replay = replay
        self.name = name
        self.recv_sizes = None
        self._bound: Optional[_Bound] = None

    def _require(self) -> _Bound:
        if self._bound is None:
            self._replay.force_launch()
        return self._bound

    def poll(self) -> bool:
        return self._require().poll()

    def result(self):
        return self._require().result()

    def synchronize(self):
        return self._require().synchronize()


class _Armed(NamedTuple):
    stream: tuple                 # tuple[CallSig]
    segments: tuple               # build_replay_step segment specs
    builder_key: tuple
    nbytes: int
    threshold: int
    hier_local: int
    join_metas: Optional[list]    # np rows for the one-step advertisement
    join_kind: str = "grouped_allreduce"   # advertisement kind for the rows
    # bucket-pipelined overlap (ISSUE 6): resolved mode + the per-bucket
    # stage plan for "staged" mode (empty = monolithic launch)
    mode: str = "off"
    stages: tuple = ()
    n_buckets: int = 1
    has_sharded: bool = False
    # zero1_prefetch as resolved when the stage plan was built — a live
    # flip of the knob must rebuild the armed program
    prefetch: bool = True
    # topology-aware algorithm selection (ISSUE 10): the knob state the
    # per-bucket algos embedded in `segments` were resolved under (a live
    # move rebuilds the armed program), plus the total per-link byte
    # split stamped on the fused launch's trace event
    algo_sig: tuple = ()
    link_bytes: Optional[dict] = None
    # link-aware wire compression (ISSUE 13): the error-feedback residual
    # rows — (engine residual key, elems, dtype) in the replay program's
    # residual I/O order — and whether ANY bucket carries a codec (the
    # compression.encode failpoint gate)
    residual_specs: tuple = ()
    has_codec: bool = False


class StepReplay:
    """Per-engine capture/replay state machine. All mutation happens on the
    dispatching (user) thread; the cycle thread only polls the tracked
    representative handle.

    Lock discipline (tools/check.py lockcheck): deliberately NO locks and
    no ``_GUARDED_BY`` — the single-thread confinement above is the
    synchronization. The engine state replay touches from other threads'
    edges (the ZeRO-1 prefetch registry it invalidates, the outstanding
    table its launches ride) is guarded on the Engine side; anything added
    here that a background thread must touch belongs on the engine with an
    annotation, not in this class."""

    def __init__(self, engine):
        self.engine = engine
        # signature -> {"streak": int, "armed": _Armed|None}
        self._seen: Dict[tuple, dict] = {}
        self._mode = "idle"   # idle|off|record|replay|drain
        self._in_step = False
        self._step_token = 0
        self._world_version = engine.world_version
        self._recording: List[CallSig] = []
        # replay-mode per-step state
        self._cands: List[tuple] = []
        self._pos = 0
        self._buffered: List[list] = []
        self._handles: List[List[ReplayHandle]] = []
        self._launched = False
        # observability counters (bench + stall inspector read these)
        self.replayed_steps = 0
        self.captured_streams = 0
        self.fallbacks = 0
        # registry instruments (horovod_tpu/metrics.py): the scrapeable
        # face of the same lifecycle — arm/replay/fallback/invalidate plus
        # the step counter the replayed-vs-eager ratio derives from
        _reg = metrics_registry()
        self._m_steps = _reg.counter("hvd_tpu_steps_total")
        self._m_armed = _reg.counter("hvd_tpu_replay_armed_total")
        self._m_replayed = _reg.counter("hvd_tpu_replay_replayed_steps_total")
        self._m_fallbacks = _reg.counter("hvd_tpu_replay_fallbacks_total")
        self._m_invalidations = _reg.counter(
            "hvd_tpu_replay_invalidations_total")

    # -- step lifecycle ----------------------------------------------------

    def pm_token(self) -> Optional[int]:
        """Autotune step identity: one ``step_mark`` per marked step (None
        outside markers preserves the per-grouped-call legacy cadence)."""
        return self._step_token if self._in_step else None

    def step_begin(self):
        if self._in_step:
            self.step_end()
        eng = self.engine
        self._step_token += 1
        self._in_step = True
        self._recording = []
        self._pos = 0
        self._buffered = []
        self._handles = []
        self._launched = False
        version = eng._refresh_world_version()
        # divcheck: agreed[world-version bumps are rendezvous-stamped before any rank re-enters a step, so every rank compares the same pair at its next step_begin]
        if version != self._world_version:
            self.invalidate_all("world-version bump "
                                f"({self._world_version} -> {version})")
            self._world_version = version
        if not eng.config.step_replay:
            self._mode = "off"
            return
        cands = [s for s, ent in self._seen.items()
                 if self._current_armed(s, ent) is not None]
        if cands:
            self._mode = "replay"
            self._cands = cands
        else:
            self._mode = "record"

    def step_end(self):
        if not self._in_step:
            return
        self._m_steps.inc()
        try:
            if self._mode == "replay" and self._pos > 0 and not self._launched:
                complete = [s for s in self._cands if len(s) == self._pos]
                if complete:
                    # prefix-ambiguity resolved by the step ending here
                    self._launch(complete[0])
                else:
                    self._fallback("step ended before the recorded stream "
                                   "completed")
            stream = tuple(self._recording)
            if stream:
                self._note_stream(stream)
        finally:
            self._mode = "idle"
            self._in_step = False
            self._cands = []

    def _note_stream(self, stream: tuple):
        ent = lru_get(self._seen, stream)
        if ent is None:
            ent = lru_put(self._seen, stream, {"streak": 0, "armed": None},
                          _MAX_STREAMS)
        ent["streak"] += 1
        cfg = self.engine.config
        if (ent["armed"] is None and cfg.step_replay
                and not cfg.debug_consistency
                and ent["streak"] >= max(cfg.step_replay_warmup, 1)):
            ent["armed"] = self._build_armed(stream)
            if ent["armed"] is not None:
                self.captured_streams += 1
                self._m_armed.inc()
                self.engine._emit_replay(
                    "capture",
                    f"armed after {ent['streak']} identical steps: "
                    f"{len(stream)} ops, "
                    f"{sum(len(s.shapes) for s in stream)} tensors")

    def invalidate_all(self, reason: str):
        """Drop every armed stream and recorded streak (join(), elastic
        world-version bumps, explicit resets). Held ZeRO-1 prefetch legs
        and error-feedback residual buffers ride the same invalidation
        edge — neither must outlive the world it was computed for
        (invalidate, not poison)."""
        self.engine.invalidate_prefetch(reason)
        self.engine.invalidate_residuals(reason)
        had_armed = any(e.get("armed") for e in self._seen.values())
        self._seen.clear()
        if self._mode in ("replay", "drain"):
            if self._pos > 0 and not self._launched:
                self._fallback(f"invalidated mid-step: {reason}")
            self._mode = "record" if self._in_step else "idle"
            self._cands = []
        if had_armed:
            self._m_invalidations.inc()
            self.engine._emit_replay("invalidate", reason)

    # -- per-call interception --------------------------------------------

    def intercept(self, kind: str, tensors: Sequence, code: int, pre: float,
                  post: float, name: Optional[str], sub: bool,
                  extra: tuple = ()):
        """Called by every engine collective entry point. Returns None to
        proceed on the normal path, or the list of handles servicing the
        call from the (pending) fused launch."""
        mode = self._mode
        if mode in ("idle", "off"):
            return None
        if sub:
            # a Join zero-substitute mid-step: never replay it, and a step
            # containing one is not steady state
            if mode in ("replay", "drain"):
                self._fallback("join substitute dispatched mid-step")
            self._recording.append(_make_sig(kind, tensors, code, pre, post,
                                             name, replayable=False,
                                             extra=extra))
            return None
        sig = _make_sig(
            kind, tensors, code, pre, post, name,
            replayable=kind in (_REDUCE_KINDS + _BCAST_KINDS
                                + _SHARDED_KINDS + _A2A_KINDS),
            extra=extra)
        self._recording.append(sig)
        if mode == "record":
            return None
        if mode == "drain":
            # more ops than the replayed stream had: the prefix was already
            # serviced correctly; finish the step on the normal path and let
            # the longer signature be learned from _recording
            self._fallback("ops submitted after the replayed stream "
                           "completed")
            return None
        # mode == "replay"
        if kind in ("grouped_allreduce", "sharded_step"):
            # program-ordered autotune boundary (the normal grouped path's
            # step_mark); may reenter the engine (parameter broadcast) and
            # knock us out of replay — re-check after. For sharded steps
            # only the GRADIENT bytes score (the normal path's convention;
            # state leaves ride the call but not the wire)
            n_counted = extra[1] if kind == "sharded_step" else len(tensors)
            self.engine._pm_step(sum(t.nbytes
                                     for t in tensors[:n_counted]))
            if self._mode != "replay":
                return None
        cands = [s for s in self._cands
                 if len(s) > self._pos and s[self._pos] == sig]
        if not cands:
            self._fallback(f"signature divergence at op {self._pos} "
                           f"({kind})")
            return None
        self._cands = cands
        handles = [ReplayHandle(self, f"{name or kind}.{j}")
                   for j in range(len(tensors))]
        self._buffered.append(list(tensors))
        self._handles.append(handles)
        self._pos += 1
        complete = [s for s in cands if len(s) == self._pos]
        if complete and len(cands) == 1:
            self._launch(complete[0])
            self._mode = "drain"
        return handles

    def observe(self, kind: str, sub: bool, tensors: Sequence = (),
                name: Optional[str] = None):
        """Record (or fall back on) an engine call replay cannot service —
        allgather/uneven-alltoall/reducescatter/barrier/adasum. A step
        containing one never arms; encountering one while replaying is a
        divergence. (Even-split ``grouped_alltoall`` calls take
        :meth:`intercept` instead — they replay, ISSUE 17.)"""
        mode = self._mode
        if mode in ("idle", "off"):
            return
        if mode in ("replay", "drain"):
            self._fallback(f"unreplayable op {kind} mid-step")
        self._recording.append(_make_sig(kind, tensors, 0, 1.0, 1.0, name,
                                         replayable=False))

    def force_launch(self):
        """A ReplayHandle was awaited before the recorded stream completed:
        dispatch now. A candidate complete at this position launches clean;
        otherwise zero-pad (observable fallback)."""
        if self._launched:
            return
        complete = [s for s in self._cands if len(s) == self._pos]
        if complete:
            self._launch(complete[0])
            self._mode = "drain"
        else:
            self._fallback("handle awaited before the recorded stream "
                           "completed")

    # -- internals ---------------------------------------------------------

    def _current_armed(self, stream: tuple, ent: dict) -> Optional[_Armed]:
        """The armed program, re-derived if a tuned knob (fusion threshold,
        hierarchy) moved since it was built."""
        armed = ent.get("armed")
        if armed is None:
            return None
        cfg = self.engine.config
        hier = self._hier_local()
        if (armed.threshold != cfg.fusion_threshold_bytes
                or armed.hier_local != hier
                or armed.algo_sig != self._algo_sig()
                or armed.mode != self._overlap_mode(armed.nbytes,
                                                    armed.n_buckets,
                                                    armed.has_sharded)
                or armed.prefetch != bool(cfg.zero1_prefetch)):
            armed = self._build_armed(stream)
            ent["armed"] = armed
        return armed

    def _algo_sig(self) -> tuple:
        """Knob state the per-bucket algorithm selection depends on — a
        move of any of these must rebuild armed programs so eager warmup
        and the armed program always resolve the same schedule (the
        fusion-threshold rebuild contract applied to ISSUE 10). One
        source of truth: the engine's signature, also used by the
        grouped path's mid-call reuse guard.

        The pipeline schedule knobs (ISSUE 16) ride this same edge: a
        pipeline train step keeps its whole microbatch loop inside one
        jitted lax.scan (already a single launch — the O(1)-dispatch
        property is the scan's, not replay's), and only its DP gradient
        sync + optimizer update flow through the engine as replayable
        dispatches. When the autotuner flips pipeline_schedule /
        virtual_stages / boundary_codec, the STEP the model rebuilds is
        a different program with the same dispatch signature — so the
        sig move here forces the re-warm that keeps the armed launch and
        the new schedule's table program in lockstep."""
        return self.engine._algo_sig()

    def _overlap_mode(self, nbytes: int, n_buckets: int,
                      has_sharded: bool) -> str:
        """The engine's overlap mode for this stream. The Join-live
        demotion (staged -> interleave next to a blocked peer) lives in
        Engine._overlap_mode so the eager warmup path and the armed
        program always resolve the same schedule."""
        return self.engine._overlap_mode(nbytes, n_buckets, has_sharded)

    def _hier_local(self) -> int:
        eng = self.engine
        if eng.config.hierarchical_allreduce and eng._hierarchical_ok():
            return eng.backend.local_size()
        return 0

    def _build_armed(self, stream: tuple) -> Optional[_Armed]:
        eng = self.engine
        cfg = eng.config
        if not all(sig.replayable for sig in stream):
            return None
        join_live = cfg.join_enabled and eng.backend.size() > 1
        # segments: consecutive calls sharing (class, code, scales) fuse;
        # sharded steps are one segment each (their update closures must
        # not be merged across calls)
        from .engine import bucket_by_size, _DTYPE_CODES, _JOIN_META_DIMS
        segs: List[dict] = []
        for sig in stream:
            if sig.kind in _SHARDED_KINDS:
                cls = "sharded"
            elif sig.kind in _REDUCE_KINDS:
                cls = "reduce"
            elif sig.kind in _A2A_KINDS:
                cls = "a2a"
            else:
                cls = "bcast"
            key = (cls, sig.code, sig.pre, sig.post) + tuple(sig.extra)
            if cls == "sharded" or not segs or segs[-1]["key"] != key:
                segs.append({"key": key, "cls": cls, "shapes": [],
                             "dtypes": [], "extra": sig.extra,
                             "name": sig.name})
            segs[-1]["shapes"].extend(sig.shapes)
            segs[-1]["dtypes"].extend(sig.dtypes)
        join_metas = None
        join_kind = "grouped_allreduce"
        if join_live:
            # Joined peers match the advertisement with a zero substitute
            # whose wire sequence must be identical to the replay program's:
            # true for a single reduce segment (per-bucket reduce
            # collectives) and for a single sharded segment (the sharded
            # advertisement raises on the joined rank, same as the normal
            # sharded path). Anything else — including a2a segments, whose
            # substitute would interleave its own join round mid-step —
            # stays unarmed in Join worlds (MoE replay runs under
            # HOROVOD_JOIN_DISABLE=1, docs/parallelism.md).
            if len(segs) != 1 or segs[0]["cls"] not in ("reduce", "sharded"):
                return None
            op_code = segs[0]["key"][1]
            adv_shapes = segs[0]["shapes"]
            adv_dtypes = segs[0]["dtypes"]
            if segs[0]["cls"] == "reduce":
                # the advertised op field packs the call codec (the
                # engine's submission-site convention) so a joined peer's
                # substitute resolves the same compressed program
                adv_codec = (segs[0]["extra"][0] if segs[0]["extra"]
                             else _comp.CODEC_NONE)
                op_code = int(op_code) | (
                    _comp.CODECS.index(adv_codec) << 4)
            if segs[0]["cls"] == "sharded":
                join_kind = "sharded_step"
                n_grads = segs[0]["extra"][1]
                adv_shapes = adv_shapes[:n_grads]
                adv_dtypes = adv_dtypes[:n_grads]
            rows = []
            for shape, dt in zip(adv_shapes, adv_dtypes):
                code = _DTYPE_CODES.get(dt)
                if code is None or len(shape) > _JOIN_META_DIMS:
                    return None
                dims = list(shape) + [-1] * (_JOIN_META_DIMS - len(shape))
                rows.append(np.array([op_code, code, len(shape)] + dims,
                                     dtype=np.int64))
            join_metas = rows
        hier_local = self._hier_local()
        topo_local = eng.topology.local_size
        world = eng.backend.size()
        built = []
        seg_dtypes = []
        seg_res = []       # per built segment: per-bucket residual spec
        nbytes = 0
        link_total: Dict[str, int] = {}

        def _note_links(algo: str, b: int, kind: str = "allreduce",
                        codec: str = _comp.CODEC_NONE, itemsize: int = 4):
            for link, v in _C.link_split(algo, b, topo_local, kind=kind,
                                         codec=codec, itemsize=itemsize,
                                         size=world).items():
                link_total[link] = link_total.get(link, 0) + v

        for seg in segs:
            cls = seg["cls"]
            seg_dtypes.append(tuple(seg["dtypes"]))
            if cls == "sharded":
                # the bucket layout is the CALLER'S frozen layout (carried
                # in the sig's extra) — never re-derived from the live
                # fusion threshold, which may have moved since the sharded
                # state was initialized (shard shapes are pinned to it)
                key = seg["key"]
                _, op_code, pre, post, update_key, n_grads, bkey = key[:7]
                call_codec = key[7] if len(key) > 7 else _comp.CODEC_NONE
                proxies = [_LeafProxy(s, d)
                           for s, d in zip(seg["shapes"][:n_grads],
                                           seg["dtypes"][:n_grads])]
                nbytes += sum(p.nbytes for p in proxies)
                # the rs leg is pinned flat; the return ag picks per
                # bucket — the SAME selection the eager warmup path made
                # (engine.sharded_step), so armed and eager programs agree
                ag_algos = tuple(
                    eng._choose_algo("allgather",
                                     sum(proxies[i].nbytes for i in b))
                    for b in bkey)
                # rs-leg codec resolution mirrors engine.sharded_step
                rs_codecs = eng._bucket_codecs("reducescatter", proxies,
                                               bkey, call_codec,
                                               count=False)
                res_specs = []
                for b, (idxs, c) in enumerate(zip(bkey, rs_codecs)):
                    bb = sum(proxies[i].nbytes for i in idxs)
                    it = proxies[idxs[0]].dtype.itemsize
                    _note_links("flat", bb, kind="reducescatter",
                                codec=c, itemsize=it)          # rs leg
                    _note_links(ag_algos[b], bb, kind="allgather")  # ag
                    if c in _comp.EF_CODECS:
                        total = sum(
                            int(np.prod(proxies[i].shape))
                            if proxies[i].shape else 1 for i in idxs)
                        elems = _C.codec_residual_elems(
                            "sharded", total, world, 0, None, c)
                        res_specs.append((("zrs", update_key, b, c,
                                           elems), elems,
                                          str(proxies[idxs[0]].dtype)))
                    else:
                        res_specs.append(None)
                seg_res.append(tuple(res_specs))
                built.append(("sharded", (op_code, update_key, n_grads),
                              pre, post, (topo_local, ag_algos,
                                          rs_codecs),
                              tuple(seg["shapes"]), bkey))
                continue
            key = seg["key"]
            _, code, pre, post = key[:4]
            call_codec = (key[4] if cls == "reduce" and len(key) > 4
                          else _comp.CODEC_NONE)
            proxies = [_LeafProxy(s, d)
                       for s, d in zip(seg["shapes"], seg["dtypes"])]
            nbytes += sum(p.nbytes for p in proxies)
            buckets = bucket_by_size(proxies, cfg.fusion_threshold_bytes)
            if cls == "reduce":
                # per-bucket topology-aware lowering (ISSUE 10) + wire
                # codec (ISSUE 13), resolved through the same engine
                # selection the warmup path used — armed and eager
                # programs (and residual lineages) agree
                algos = tuple(
                    eng._choose_algo("allreduce",
                                     sum(proxies[i].nbytes for i in b))
                    for b in buckets)
                codecs = eng._bucket_codecs("grouped_allreduce", proxies,
                                            buckets, call_codec,
                                            count=False)
                res_specs = []
                for b, (idxs, algo, c) in enumerate(zip(buckets, algos,
                                                        codecs)):
                    bb = sum(proxies[i].nbytes for i in idxs)
                    it = proxies[idxs[0]].dtype.itemsize
                    _note_links(algo, bb, codec=c, itemsize=it)
                    if c in _comp.EF_CODECS:
                        total = sum(
                            int(np.prod(proxies[i].shape))
                            if proxies[i].shape else 1 for i in idxs)
                        elems = _C.codec_residual_elems(
                            "reduce", total, world, topo_local, algo, c)
                        rkey = eng._residual_key(
                            "gar", seg["name"], b, algo, c, elems,
                            str(proxies[idxs[0]].dtype))
                        res_specs.append((rkey, elems,
                                          str(proxies[idxs[0]].dtype)))
                    else:
                        res_specs.append(None)
                seg_res.append(tuple(res_specs))
                topo_field = (topo_local, algos, codecs)
            elif cls == "a2a":
                # per-bucket flat/hierarchical selection + the stateless
                # DCN-leg codec (ISSUE 17), resolved through the same
                # engine helpers the eager warmup path used — armed and
                # eager programs agree, a knob move re-arms via algo_sig,
                # and no residual rows ever (the codec is one-shot)
                algos = tuple(
                    eng._choose_algo("alltoall",
                                     sum(proxies[i].nbytes for i in b))
                    for b in buckets)
                codecs = eng._a2a_codecs(proxies, buckets, algos,
                                         count=False)
                for idxs, algo, c in zip(buckets, algos, codecs):
                    _note_links(algo, sum(proxies[i].nbytes for i in idxs),
                                kind="alltoall", codec=c,
                                itemsize=proxies[idxs[0]].dtype.itemsize)
                seg_res.append((None,) * len(buckets))
                topo_field = (topo_local, algos, codecs)
            else:
                for b in buckets:
                    _note_links("flat", sum(proxies[i].nbytes for i in b))
                seg_res.append((None,) * len(buckets))
                topo_field = 0
            built.append((cls, code, pre, post, topo_field,
                          tuple(seg["shapes"]),
                          tuple(tuple(b) for b in buckets)))
        n_buckets = sum(len(seg[6]) for seg in built)
        has_sharded = any(seg[0] == "sharded" for seg in built)
        has_codec = any(
            isinstance(seg[4], tuple) and len(seg[4]) > 2
            and any(c != _comp.CODEC_NONE for c in seg[4][2])
            for seg in built)
        residual_specs = tuple(spec for specs in seg_res
                               for spec in specs if spec is not None)
        mode = self._overlap_mode(nbytes, n_buckets, has_sharded)
        prefetch = bool(cfg.zero1_prefetch)
        stages = (self._stage_plan(built, seg_dtypes, prefetch, seg_res)
                  if mode == "staged" else ())
        algo_sig = self._algo_sig()
        return _Armed(stream, tuple(built),
                      ("replay_step", stream, cfg.fusion_threshold_bytes,
                       hier_local, mode, algo_sig,
                       tuple(seg[4] for seg in built)),
                      nbytes, cfg.fusion_threshold_bytes, hier_local,
                      join_metas, join_kind, mode, stages, n_buckets,
                      has_sharded, prefetch, algo_sig, dict(link_total),
                      residual_specs, has_codec)

    @staticmethod
    def _stage_plan(built: tuple, seg_dtypes: list,
                    prefetch: bool = True,
                    seg_res: Optional[list] = None) -> tuple:
        """Split the armed segment list into per-bucket sub-launches (the
        "staged" overlap mode): stage k's collective is already in flight
        while the host dispatches stage k+1's pack — dispatch-level
        pipelining the monolithic launch cannot express. A sharded segment
        becomes TWO stages: the rs->shard-update launch, then the
        parameter all-gather launch (the ZeRO-1 prefetch leg that rides
        under the step's tail) — unless ``prefetch`` is off
        (HOROVOD_TPU_ZERO1_PREFETCH=0), which keeps the documented fused
        rs->update->ag single launch per sharded segment. Stage tuples:

        - ``("seg", sub_segment, in_idx, out_idx)`` — one bucket of a
          reduce/bcast segment as a single-bucket replay program;
        - ``("zupd", segment, in_idx, state_out_idx)`` — rs + shard-local
          update, emitting stacked shards + new state;
        - ``("zag", grad_shapes, grad_dtypes, buckets, out_idx,
          update_key, local_size, ag_algos)`` — the prefetch all-gather,
          consuming the previous zupd stage's shard outputs (per-bucket
          flat/hierarchical selection riding along, ISSUE 10).

        Every "seg"/"zupd" stage tuple ends with ``res_specs`` — the
        ``(engine residual key, elems, dtype)`` rows for that stage's
        error-feedback buckets (ISSUE 13), in the stage program's
        residual I/O order (empty when no codec is live)."""
        stages = []
        base = 0
        if seg_res is None:
            seg_res = [(None,) * len(seg[6]) for seg in built]
        for seg, dtypes, res_row in zip(built, seg_dtypes, seg_res):
            cls, code, pre, post, topo_field, shapes, buckets = seg
            local, algos, codecs = _C._seg_algo_spec(topo_field,
                                                     len(buckets))
            seg_specs = tuple(r for r in res_row if r is not None)
            if cls == "sharded" and not prefetch:
                # prefetch disabled: one fused rs->update->ag sub-launch
                io = tuple(range(base, base + len(shapes)))
                stages.append(("seg", seg, io, io, seg_specs))
            elif cls == "sharded":
                op_code, update_key, n_grads = code
                in_idx = tuple(range(base, base + len(shapes)))
                state_out_idx = tuple(range(base + n_grads,
                                            base + len(shapes)))
                stages.append(("zupd", seg, in_idx, state_out_idx,
                               seg_specs))
                stages.append(("zag", tuple(shapes[:n_grads]),
                               tuple(dtypes[:n_grads]), buckets,
                               tuple(range(base, base + n_grads)),
                               update_key, local, algos))
            else:
                for bi, idxs in enumerate(buckets):
                    sub_shapes = tuple(shapes[i] for i in idxs)
                    sub_seg = (cls, code, pre, post,
                               (local, (algos[bi],), (codecs[bi],)),
                               sub_shapes, (tuple(range(len(idxs))),))
                    io = tuple(base + i for i in idxs)
                    spec = res_row[bi]
                    stages.append(("seg", sub_seg, io, io,
                                   (spec,) if spec is not None else ()))
            base += len(shapes)
        return tuple(stages)

    def _fallback(self, reason: str):
        self.fallbacks += 1
        # digit-normalized reason keeps the label set bounded ("divergence
        # at op 3" and "at op 7" are one series)
        self._m_fallbacks.inc(reason=_DIGITS.sub("#", reason))
        eng = self.engine
        if eng.replay_fallback_counter is not None:
            eng.replay_fallback_counter(reason)
        eng._emit_replay("fallback", reason)
        if self._pos > 0 and not self._launched:
            # flush the buffered prefix through the recorded program with
            # zero-padded missing slots — every rank reaches this fallback
            # at the same program point, so the launch still matches peers
            # (and any joined rank's substitute); slot outputs are
            # independent, so the prefix results are exact
            self._launch(min(self._cands, key=len), padded=True)
        self._mode = "record" if self._in_step else "idle"
        self._cands = []

    def _launch(self, stream: tuple, padded: bool = False):
        with scopes.host_span(scopes.REPLAY_LAUNCH):
            self._launch_armed(stream, padded)

    def _launch_armed(self, stream: tuple, padded: bool):
        from . import engine as engine_mod
        eng = self.engine
        ent = self._seen.get(stream)
        armed = self._current_armed(stream, ent) if ent else None
        if armed is None:  # knob moved to an unarmable config mid-step
            armed = self._build_armed(stream)
        if armed is None:
            raise engine_mod.HorovodInternalError(
                "replay stream lost its armed program mid-step")
        flat = []
        for ci, sig in enumerate(stream):
            bufs = self._buffered[ci] if ci < len(self._buffered) else None
            if bufs is None:
                bufs = [jnp.zeros(s, jnp.dtype(d))
                        for s, d in zip(sig.shapes, sig.dtypes)]
            flat.extend(bufs)
        if armed.join_metas is not None:
            # one fire-and-forget advertisement for the WHOLE step (the
            # per-op join rounds the recorded path paid, collapsed to one)
            eng._join_sync(armed.join_kind, armed.join_metas)
        rep_name = f"replay.step.{self._step_token & 1023}"
        if eng.trace is not None:
            # the fused launch bypasses _register: stamp its correlation id
            # here so replayed steps stay joinable across ranks (every rank
            # replays the same stream in the same step, so the per-name
            # sequence numbers agree)
            eng.trace.record_enqueue(rep_name, "replay", armed.nbytes,
                                     eng.world_version,
                                     link_bytes=armed.link_bytes)
        if eng.on_enqueue is not None:
            eng.on_enqueue(rep_name, "replay", armed.nbytes)
        if armed.has_codec:
            # same chaos seam as the eager compressed submission sites
            engine_mod.failpoint("compression.encode")
        if armed.mode == "staged" and armed.stages:
            slot_garrs, slot_groups, group = self._launch_stages(
                armed, flat, rep_name)
            n_launches = len(armed.stages)
        else:
            fn = eng._builder(armed.builder_key,
                              lambda: engine_mod.C.build_replay_step(
                                  eng.backend.group_mesh, eng._axis(),
                                  armed.segments,
                                  sharded_updates=eng._sharded_updates,
                                  pipeline=(armed.mode != "off")))
            res_args = [eng.backend.world_view(
                eng._residual_fetch(k, e, dt))
                for k, e, dt in armed.residual_specs]
            t0 = time.perf_counter()
            outs = engine_mod._translate_failure(
                lambda: fn(*([eng.backend.world_view(t) for t in flat]
                             + res_args)))
            eng._count_dispatch()
            if eng.trace is not None:
                eng.trace.record_dispatch(rep_name, "XLA_REPLAY_DISPATCH",
                                          time.perf_counter() - t0)
            if eng.on_activity is not None:
                eng.on_activity(rep_name, "XLA_REPLAY_DISPATCH",
                                (time.perf_counter() - t0) * 1e6)
            for j, (k, _, _) in enumerate(armed.residual_specs):
                eng._residual_store(k, outs[len(flat) + j])
            group = engine_mod.LaunchGroup(outs[-1])
            slot_garrs = list(outs[:len(flat)])
            slot_groups = [group] * len(flat)
            n_launches = 1
        if armed.mode != "off":
            eng._m_overlap_steps.inc(mode=armed.mode)
        k = 0
        for ci, sig in enumerate(stream):
            hs = self._handles[ci] if ci < len(self._handles) else None
            for j in range(len(sig.shapes)):
                if hs is not None:
                    hs[j]._bound = _Bound(slot_garrs[k], slot_groups[k],
                                          eng)
                k += 1
        # ONE tracked representative per replayed step: retires through the
        # cycle loop, feeds the stall inspector and timeline done events
        rep = engine_mod.Handle(rep_name, [slot_garrs[-1]],
                                lambda gs: None, eng,
                                group=group, kind="replay")
        eng._track(rep_name, rep)
        self._launched = True
        if not padded:
            self.replayed_steps += 1
            self._m_replayed.inc()
            eng._emit_replay(
                "replay", f"{len(flat)} tensors in {n_launches} "
                f"launch(es) ({rep_name}, overlap={armed.mode})")

    def _launch_stages(self, armed: _Armed, flat: list, rep_name: str):
        """Dispatch one armed step as its per-bucket stage pipeline
        ("staged" overlap mode): each stage is its own launch, so stage
        k's collective is on the wire while the host dispatches stage
        k+1's pack — and the final "zag" stage is the ZeRO-1 parameter
        all-gather prefetch leg the engine holds across the step boundary.
        Returns (slot_garrs, slot_groups, last_group)."""
        from . import engine as engine_mod
        from ..common.reduce_ops import ReduceOp
        from ..faults import failpoint
        eng = self.engine
        mesh = eng.backend.group_mesh
        axis = eng._axis()
        slot_garrs: list = [None] * len(flat)
        slot_groups: list = [None] * len(flat)
        held_shards = None
        group = None
        for st in armed.stages:
            t0 = time.perf_counter()
            kind = st[0]
            if kind == "seg":
                _, sub_seg, in_idx, out_idx, res_specs = st
                fn = eng._builder(
                    ("replay_stage", sub_seg),
                    lambda: engine_mod.C.build_replay_step(
                        mesh, axis, (sub_seg,),
                        sharded_updates=eng._sharded_updates,
                        pipeline=True))
                args = [eng.backend.world_view(flat[i]) for i in in_idx] \
                    + [eng.backend.world_view(
                        eng._residual_fetch(k, e, dt))
                       for k, e, dt in res_specs]
                outs = engine_mod._translate_failure(lambda: fn(*args))
                group = engine_mod.LaunchGroup(outs[-1])
                for pos, i in enumerate(out_idx):
                    slot_garrs[i] = outs[pos]
                    slot_groups[i] = group
                for j, (k, _, _) in enumerate(res_specs):
                    eng._residual_store(k, outs[len(out_idx) + j])
            elif kind == "zupd":
                _, seg, in_idx, state_out_idx, res_specs = st
                _cls, code, pre, post, topo_field, shapes, buckets = seg
                op_code, update_key, n_grads = code
                _local, _ag_algos, rs_codecs = engine_mod.C._seg_algo_spec(
                    topo_field, len(buckets))
                # registry read stays inside the builder factory so it
                # happens at trace time only (the monolithic path's
                # documented LRU contract: eviction after arming is
                # harmless) — a steady-state dispatch never touches it
                fn = eng._builder(
                    ("replay_zupd", seg),
                    lambda: engine_mod.C.build_sharded_update(
                        mesh, axis, ReduceOp(op_code),
                        tuple(shapes[:n_grads]), None, buckets,
                        tuple(shapes[n_grads:]), None,
                        eng._sharded_updates[update_key], pre, post,
                        packed=False, codecs=rs_codecs))
                args = [eng.backend.world_view(flat[i]) for i in in_idx] \
                    + [eng.backend.world_view(
                        eng._residual_fetch(k, e, dt))
                       for k, e, dt in res_specs]
                outs = engine_mod._translate_failure(lambda: fn(*args))
                group = engine_mod.LaunchGroup(outs[-1])
                held_shards = outs[:len(buckets)]
                n_state = len(shapes) - n_grads
                for pos, i in enumerate(state_out_idx):
                    slot_garrs[i] = outs[len(buckets) + pos]
                    slot_groups[i] = group
                for j, (k, _, _) in enumerate(res_specs):
                    eng._residual_store(
                        k, outs[len(buckets) + n_state + j])
            else:  # "zag": the prefetch leg, consuming the zupd shards
                (_, gshapes, gdtypes, buckets, out_idx, update_key,
                 ag_local, ag_algos) = st
                failpoint("overlap.prefetch")
                # same cache key as the eager prefetch leg (engine.py's
                # sharded_step): the programs are byte-identical, so the
                # first staged step reuses the warmup path's compile
                fn = eng._builder(
                    ("zero1_prefetch_allgather", gshapes, gdtypes,
                     buckets, ag_algos),
                    lambda: engine_mod.C.build_grouped_allgather(
                        mesh, axis, gshapes, gdtypes, buckets,
                        pipeline=True, local_size=ag_local,
                        algos=ag_algos))
                shards = held_shards
                outs = engine_mod._translate_failure(lambda: fn(*shards))
                group = engine_mod.LaunchGroup(outs[-1])
                eng._note_prefetch(update_key)
                for pos, i in enumerate(out_idx):
                    slot_garrs[i] = outs[pos]
                    slot_groups[i] = group
            eng._count_dispatch()
            eng._m_overlap_stages.inc(kind="replay_" + kind)
            if eng.trace is not None:
                eng.trace.record_dispatch(rep_name, "XLA_REPLAY_DISPATCH",
                                          time.perf_counter() - t0)
            if eng.on_activity is not None:
                eng.on_activity(rep_name, "XLA_REPLAY_DISPATCH",
                                (time.perf_counter() - t0) * 1e6)
        return slot_garrs, slot_groups, group
