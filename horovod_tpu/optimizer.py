"""Distributed optimizer integration.

Reference surface: ``hvd.DistributedOptimizer`` wraps a framework optimizer so
every gradient is allreduced before the update (torch/optimizer.py:100-186:
per-parameter hooks fire allreduce_async as grads become ready, synchronize()
waits, step() applies; tensorflow/__init__.py:259-301 compute_gradients
override; backward_passes_per_step accumulates locally between reductions).

TPU-native design — two execution paths, same semantics:

1. :func:`distributed` — an ``optax.GradientTransformation`` wrapper for the
   **SPMD path**: used inside a ``pjit``/``shard_map``-traced train step, it
   reduces gradients across a mesh axis with ``lax.psum``. This is the
   idiomatic TPU hot path: XLA fuses the reduction into the step program and
   overlaps it with backward compute (the reference needed hooks + extra
   streams for that overlap; XLA's scheduler does it from the dataflow graph).

2. :func:`distributed_eager` — for the **process-parallel eager path** (one
   process per chip, Horovod-style): gradients are bucketed (fusion threshold,
   controller.cc:652-773) and allreduced through the engine between
   ``grad()`` and ``opt.update()``.

Both support op=Average|Sum|Adasum, gradient compression
(ops/compression.py), and ``backward_passes_per_step`` local accumulation.
"""

from __future__ import annotations

import time as _time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from .common import env as _env
from .common import scopes
from .common.lru import lru_get, lru_put
from .common.reduce_ops import ReduceOp, Average, Sum, Adasum
from .metrics import registry as _metrics_registry
from .ops import collectives as C
from .ops import compression as _compression
from .ops.adasum import adasum_p
from .ops.compression import Compression


# ---------------------------------------------------------------------------
# SPMD path
# ---------------------------------------------------------------------------

def _vma_tracking_active(axis_name: str) -> bool:
    """Whether the surrounding shard_map tracks varying manual axes
    (``check_vma=True``). Under ``check_vma=False`` — what every shard_map
    holding a Pallas kernel must use — every value reports an empty vma
    set and ``pcast`` is a no-op; probe by pcasting a fresh constant and
    seeing if the annotation sticks."""
    probe = jax.lax.pcast(jnp.zeros(()), (axis_name,), to="varying")
    return axis_name in jax.typeof(probe).vma


def _pre_summed(x, axis_name: str, tracking: bool) -> bool:
    """Whether ``x`` is a gradient the shard_map transpose has ALREADY
    psum'd over ``axis_name``: only decidable when the VMA system tracks
    (``tracking`` = :func:`_vma_tracking_active`, probed once per tree)
    and types ``x`` unvarying. An untracked value is treated as local,
    which is what it is: without tracking ``jax.grad`` inserts no psum."""
    return tracking and axis_name not in jax.typeof(x).vma


def allreduce_gradients(grads, axis_name: str, op: ReduceOp = Average,
                        compression=Compression.none, axis_size: Optional[int] = None):
    """Reduce a gradient pytree across ``axis_name`` inside traced code.

    The functional analog of DistributedGradientTape.gradient
    (tensorflow/__init__.py:464-518).

    VMA-aware: under a ``check_vma=True`` shard_map, ``jax.grad`` w.r.t.
    *replicated* (unvarying) params already psums gradient contributions in
    its transpose — such leaves arrive pre-summed and must not be reduced
    again (only scaled for Average). Every other leaf — varying over
    ``axis_name``, or untracked under ``check_vma=False`` — is local and
    gets the explicit collective.
    """
    wire = getattr(compression, "wire_codec", None)
    tracking = _vma_tracking_active(axis_name)

    def reduce_leaf(g):
        pre_summed = _pre_summed(g, axis_name, tracking)
        if op == Adasum:
            if pre_summed:
                raise ValueError(
                    "op=Adasum needs per-shard gradients; it cannot recover "
                    "local contributions from an implicitly pre-summed "
                    "(unvarying) gradient. Make the params varying (lax.pcast "
                    "to 'varying') before jax.grad, or compute grads of a "
                    "local loss.")
            if axis_size is None:
                raise ValueError("op=Adasum needs axis_size")
            c, ctx = compression.compress(g)
            return compression.decompress(
                adasum_p(c, axis_name, axis_size), ctx)
        if not pre_summed:
            if wire is not None:
                if op not in (Average, Sum):
                    raise ValueError(
                        "wire-codec compression supports op=Average|Sum "
                        "only")
                # per-leaf codec resolution (the engine path's rule):
                # non-float leaves never quantize, fp8 demotes to int8
                # without a float8 dtype
                rc = _compression.resolve_codec(wire, g.dtype)
                if rc == _compression.CODEC_NONE:
                    return C.allreduce_p(g, axis_name, op)
                # one-shot wire-codec reduction: no residual carry here
                # (this function is stateless) — use
                # hvd.distributed(compression=...) for the error-feedback
                # form, which threads the residual through its state
                out, _ = C.ef_allreduce_p(g, None, axis_name, rc, op)
                return out
            c, ctx = compression.compress(g)
            r = C.allreduce_p(c, axis_name, op)
            return compression.decompress(r, ctx)
        # Pre-summed by the shard_map transpose: Sum is done; Average divides.
        if op == Average:
            return g / jax.lax.psum(1, axis_name)
        if op == Sum:
            return g
        raise ValueError(f"op {op!r} unsupported for pre-summed gradients")

    return jax.tree_util.tree_map(reduce_leaf, grads)


class DistributedState(NamedTuple):
    inner_state: Any
    accum: Any          # local gradient accumulator (backward_passes_per_step)
    count: jnp.ndarray  # passes since last reduction
    # error-feedback residual tree (ISSUE 13): present only under the
    # fp8/int8 wire codecs — quantize(g + r) with the quantization error
    # carried forward across reduce events
    residual: Any = None


def distributed(inner: optax.GradientTransformation, axis_name: str = "world",
                op: ReduceOp = Average, compression=Compression.none,
                backward_passes_per_step: int = 1,
                axis_size: Optional[int] = None,
                shard_optimizer: bool = False,
                fusion_threshold_bytes: Optional[int] = None
                ) -> optax.GradientTransformation:
    """Wrap an optax optimizer so updates see cross-replica-reduced gradients.

    Use inside pjit/shard_map-traced train steps:

        opt = hvd.optimizer.distributed(optax.adam(1e-3), axis_name='data')

    With ``backward_passes_per_step=k`` the transformation accumulates k local
    gradients between reductions (torch/optimizer.py backward_passes_per_step)
    and emits zero updates on the intermediate passes.

    ``shard_optimizer=True`` selects the ZeRO-1 optimizer-state-sharded sync
    (Rajbhandari et al., 2020): gradients are packed into fusion buckets and
    reduce-scattered instead of allreduced, ``inner`` updates only this
    rank's 1/axis_size shard of each bucket (optimizer-update FLOPs and
    optimizer state shrink by the world size), and the update deltas return
    via a fused all-gather — same wire bytes as the allreduce. Requires a
    static ``axis_size``, op Average|Sum, no compression, and an elementwise
    ``inner`` (anything computing cross-parameter statistics, e.g.
    clip_by_global_norm, would see only the local shard). See
    docs/sharded_optimizer.md.
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    if shard_optimizer:
        return _distributed_zero1(inner, axis_name, op, compression,
                                  backward_passes_per_step, axis_size,
                                  fusion_threshold_bytes)
    # the wire-codec compressors (Compression.fp8/int8, ISSUE 13): the
    # SPMD path applies them whole-payload inside the traced step, with
    # the error-feedback residual carried in DistributedState (the engine
    # holds it in engine state on the eager path)
    wire = getattr(compression, "wire_codec", None)
    ef = wire in _compression.EF_CODECS
    if wire is not None and op not in (Average, Sum):
        raise ValueError("wire-codec compression (Compression.fp8/int8) "
                         "supports op=Average|Sum only")

    def _ef_reduce(grads, residuals):
        """Whole-payload error-feedback allreduce of a gradient tree:
        returns (reduced, new_residuals). Per-leaf codec resolution (the
        engine path's rule): non-float leaves take the plain collective,
        fp8 demotes to int8 without a float8 dtype. Pre-summed
        (unvarying) leaves moved no wire — nothing to compress, residual
        unchanged."""
        g_leaves, treedef = jax.tree_util.tree_flatten(grads)
        r_leaves = jax.tree_util.tree_leaves(residuals)
        tracking = _vma_tracking_active(axis_name)
        outs, new_rs = [], []
        for g, r in zip(g_leaves, r_leaves):
            if _pre_summed(g, axis_name, tracking):
                out = g / jax.lax.psum(1, axis_name) if op == Average \
                    else g
                outs.append(out)
                new_rs.append(r)
                continue
            rc = _compression.resolve_codec(wire, g.dtype)
            if rc == _compression.CODEC_NONE:
                outs.append(C.allreduce_p(g, axis_name, op))
                new_rs.append(r)
                continue
            out, new_r = C.ef_allreduce_p(g, r, axis_name, rc, op)
            outs.append(out)
            new_rs.append(new_r if new_r is not None else r)
        return (jax.tree_util.tree_unflatten(treedef, outs),
                jax.tree_util.tree_unflatten(treedef, new_rs))

    def _reduce(grads, residual):
        """(reduced gradients, new residual), under ``grad_reduce``."""
        with jax.named_scope(scopes.GRAD_REDUCE):
            if ef:
                return _ef_reduce(grads, residual)
            return allreduce_gradients(grads, axis_name, op, compression,
                                       axis_size), residual

    def init_fn(params):
        accum = jax.tree_util.tree_map(jnp.zeros_like, params) \
            if backward_passes_per_step > 1 else None
        residual = (jax.tree_util.tree_map(jnp.zeros_like, params)
                    if ef else None)
        return DistributedState(inner.init(params), accum,
                                jnp.zeros((), jnp.int32), residual)

    def update_fn(grads, state, params=None):
        if backward_passes_per_step == 1:
            reduced, new_res = _reduce(grads, state.residual)
            with jax.named_scope(scopes.OPTIMIZER):
                updates, new_inner = inner.update(reduced, state.inner_state,
                                                  params)
            return updates, DistributedState(new_inner, state.accum,
                                             state.count, new_res)

        accum = jax.tree_util.tree_map(lambda a, g: a + g, state.accum, grads)
        count = state.count + 1
        do_step = count >= backward_passes_per_step

        def reduce_and_step(_):
            # Reference semantics (torch/optimizer.py:122-149): grads are
            # *summed* across the k local passes — only the cross-replica
            # reduction averages. No /k here.
            reduced, new_res = _reduce(accum, state.residual)
            with jax.named_scope(scopes.OPTIMIZER):
                updates, new_inner = inner.update(reduced, state.inner_state,
                                                  params)
            zeroed = jax.tree_util.tree_map(jnp.zeros_like, accum)
            return (updates, new_inner, zeroed, jnp.zeros((), jnp.int32),
                    new_res)

        def skip(_):
            zero_up = jax.tree_util.tree_map(jnp.zeros_like, grads)
            return zero_up, state.inner_state, accum, count, state.residual

        updates, new_inner, new_accum, new_count, new_res = jax.lax.cond(
            do_step, reduce_and_step, skip, operand=None)
        return updates, DistributedState(new_inner, new_accum, new_count,
                                         new_res)

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# ZeRO-1 optimizer-state sharding (shared helpers + SPMD path)
# ---------------------------------------------------------------------------


class _SizeProxy:
    """shape/dtype stand-in with the ``.nbytes``/``.dtype`` surface
    ``bucket_by_size`` consumes — lets the bucket layout be computed from
    traced leaves (tracers) and from concrete arrays identically."""
    __slots__ = ("shape", "dtype", "nbytes")

    def __init__(self, shape, dtype):
        import numpy as np
        self.shape = tuple(shape)
        self.dtype = np.dtype(str(dtype))
        self.nbytes = (int(np.prod(self.shape)) if self.shape else 1) \
            * self.dtype.itemsize


def _zero1_layout(leaves, n: int, threshold: int):
    """Deterministic bucket layout for ZeRO-1: fusion buckets over the
    flattened leaves (the existing bucket_by_size logic) plus per-bucket
    (sizes, total, shard) shard assignment. Depends only on shapes/dtypes
    and the threshold, so init and every update agree."""
    from .core.engine import bucket_by_size
    import math as _math
    proxies = [_SizeProxy(l.shape, l.dtype) for l in leaves]
    buckets = bucket_by_size(proxies, threshold)
    layout = []
    for idxs in buckets:
        sizes = [int(_math.prod(proxies[i].shape)) if proxies[i].shape
                 else 1 for i in idxs]
        total = sum(sizes)
        _, shard = C.shard_spec(total, n)
        layout.append((tuple(idxs), tuple(sizes), total, shard))
    return layout


def _pack_bucket(leaves, idxs, scale=None):
    parts = []
    for i in idxs:
        v = jnp.ravel(leaves[i])
        if scale is not None and scale[i] != 1.0:
            v = v * scale[i]
        parts.append(v)
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def _in_axis(axis_name: str) -> bool:
    """Whether we are being traced inside a mesh context where
    ``axis_name`` is bound (shard_map manual region)."""
    try:
        jax.lax.axis_index(axis_name)
        return True
    except Exception:
        return False


def zero1_state_specs(state, axis_name: str):
    """PartitionSpec tree for a ZeRO-1 optimizer state: shard-local array
    leaves travel stacked over ``axis_name`` (each rank contributes its own
    shard), scalars (e.g. optax step counts) stay replicated. Use as the
    shard_map in/out spec for the state returned by
    ``distributed(..., shard_optimizer=True)``."""
    from jax.sharding import PartitionSpec as P
    return jax.tree_util.tree_map(
        lambda l: P() if getattr(l, "ndim", 0) == 0 else P(axis_name), state)


def _distributed_zero1(inner: optax.GradientTransformation, axis_name: str,
                       op: ReduceOp, compression,
                       backward_passes_per_step: int,
                       axis_size: Optional[int],
                       fusion_threshold_bytes: Optional[int]
                       ) -> optax.GradientTransformation:
    """SPMD ZeRO-1: reduce-scatter per fusion bucket, shard-local inner
    update, fused all-gather of the update deltas (see :func:`distributed`
    ``shard_optimizer=True``)."""
    if op not in (Average, Sum):
        raise ValueError("shard_optimizer=True supports op=Average|Sum only "
                         "(Adasum mixes whole updates, not shards)")
    if compression is not Compression.none:
        raise ValueError("the SPMD shard_optimizer=True path does not "
                         "compose with compression; the eager "
                         "DistributedEagerOptimizer(sharded=True, "
                         "compression=Compression.int8) path compresses "
                         "its reduce-scatter legs (docs/compression.md)")
    if backward_passes_per_step != 1:
        raise ValueError("shard_optimizer=True requires "
                         "backward_passes_per_step=1 (accumulate locally "
                         "before calling update instead)")
    if axis_size is None:
        raise ValueError("shard_optimizer=True needs a static axis_size "
                         "(shard shapes must be known at trace time)")
    n = int(axis_size)
    threshold = int(fusion_threshold_bytes
                    or _env.DEFAULT_FUSION_THRESHOLD_BYTES)

    def _shards_of(leaves, layout, scale=None, reduce_op=None):
        """Pack each bucket and either reduce-scatter it (gradients,
        ``reduce_op`` set) or slice this rank's shard (parameters)."""
        out = []
        for idxs, sizes, total, shard in layout:
            flat = _pack_bucket(leaves, idxs, scale)
            if reduce_op is not None:
                out.append(C._rs_flat(flat, axis_name, n, reduce_op))
            else:
                pad = shard * n - total
                if pad:
                    flat = jnp.concatenate(
                        [flat, jnp.zeros((pad,), flat.dtype)])
                idx = jax.lax.axis_index(axis_name)
                out.append(jax.lax.dynamic_slice_in_dim(
                    flat, idx * shard, shard))
        return out

    def init_fn(params):
        leaves = [jnp.asarray(l) for l in
                  jax.tree_util.tree_leaves(params)]
        layout = _zero1_layout(leaves, n, threshold)
        if _in_axis(axis_name):
            shards = _shards_of(leaves, layout)
        else:
            # outside the mesh axis the local shard values are unknowable;
            # zero placeholders are exact for the supported (elementwise,
            # zeros-initialized) inner family — init inside the shard_map'd
            # step to materialize true shard values
            shards = [jnp.zeros((shard,), leaves[idxs[0]].dtype)
                      for idxs, _, _, shard in layout]
        return DistributedState(inner.init(shards), None,
                                jnp.zeros((), jnp.int32))

    def update_fn(grads, state, params=None):
        if params is None:
            raise ValueError("shard_optimizer=True needs params passed to "
                             "update (the shard-local step reads them)")
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        p_leaves = jax.tree_util.tree_leaves(params)
        layout = _zero1_layout(leaves, n, threshold)
        # Pre-summed (unvarying) leaves — the shard_map transpose of
        # replicated params — have already mixed the replicas; feeding one
        # to psum_scatter would count it n times. Pre-dividing by n makes
        # the rs exact for them (n identical g/n contributions sum back to
        # g), so every leaf rides the same packed reduce-scatter.
        tracking = _vma_tracking_active(axis_name)
        scale = [1.0 / n if _pre_summed(g, axis_name, tracking)
                 else 1.0 for g in leaves]
        grad_shards = _shards_of(leaves, layout, scale=scale, reduce_op=op)
        param_shards = _shards_of(p_leaves, layout)
        upd_shards, new_inner = inner.update(grad_shards, state.inner_state,
                                             param_shards)
        outs = [None] * len(leaves)
        for b, (idxs, sizes, total, shard) in enumerate(layout):
            full = C._ag_flat(upd_shards[b], axis_name, total)
            size_by_leaf = {i: s for i, s in zip(idxs, sizes)}
            offset = 0
            for i in idxs:
                outs[i] = jax.lax.dynamic_slice_in_dim(
                    full, offset, size_by_leaf[i]).reshape(leaves[i].shape)
                offset += size_by_leaf[i]
        updates = jax.tree_util.tree_unflatten(treedef, outs)
        return updates, DistributedState(new_inner, state.accum, state.count)

    return optax.GradientTransformation(init_fn, update_fn)


# ---------------------------------------------------------------------------
# Eager process-parallel path
# ---------------------------------------------------------------------------

class ShardedEagerState(NamedTuple):
    """ZeRO-1 eager optimizer state: everything in here is sized to THIS
    rank's 1/world_size shard of each fusion bucket. ``shards`` is the
    authoritative flat master copy of the parameters (the full tensors the
    step returns are its all-gather); ``inner_state`` is the wrapped optax
    state over those shard vectors."""
    inner_state: Any
    shards: tuple       # per-bucket flat (ceil(total/n),) parameter shards


import itertools as _itertools

_ZERO1_TOKENS = _itertools.count()


class DistributedEagerOptimizer:
    """Horovod-style eager optimizer wrapper for one-process-per-chip training.

    Equivalent of _DistributedOptimizer (torch/optimizer.py:100-186): between
    computing local grads and applying the optax update, gradients are fused
    into buckets and allreduced through the engine.

        opt = hvd.optimizer.DistributedEagerOptimizer(optax.sgd(0.01))
        state = opt.init(params)
        grads = jax.grad(loss)(params, batch)          # local
        params, state = opt.update_and_apply(grads, state, params)

    ``sparse_rows`` routes embedding-style gradients through the sparse
    (allgather) path instead of the dense allreduce — the reference's
    IndexedSlices handling inside the optimizer (tensorflow/__init__.py:
    52-131; torch sparse grads, torch/optimizer.py:100-135). JAX gradients
    are dense, so the caller marks which leaves are row-sparse and how many
    rows one step can touch: ``{"embed": 64}`` matches every grad leaf whose
    tree path contains "embed" and promises <= 64 touched rows per step
    (e.g. tokens-per-batch). Each step the leaf's top-``k`` rows by L1 norm
    (a jitted device-side extraction — untouched rows are exactly zero, so
    any k >= the true touched count is lossless) are allgathered as
    (indices, values) and recombined with a jitted scatter-add — wire bytes
    scale with k·d instead of vocab·d, and the duplicate-combine never
    leaves the device (VERDICT r3 item 9).

    ``sharded=True`` selects ZeRO-1 optimizer-state partitioning
    (Rajbhandari et al., 2020; docs/sharded_optimizer.md): gradients sync
    via bucketed reduce-scatter, the optax update runs only on this rank's
    1/world_size shard (``init`` materializes only the local state shard +
    flat parameter master copy), and updated params return via a fused
    allgather — same wire bytes as the allreduce, optimizer state and
    update FLOPs divided by the world size, bitwise-identical trajectories
    for elementwise inner transforms. Steady-state steps replay as ONE
    fused launch. Requires op Average|Sum, no compression/sparse_rows;
    ``sharded=None`` defers to HOROVOD_TPU_SHARD_OPTIMIZER (also an
    autotune categorical).
    """

    def __init__(self, inner: optax.GradientTransformation, op: ReduceOp = Average,
                 compression=Compression.none, backward_passes_per_step: int = 1,
                 sparse_rows: Optional[dict] = None,
                 sharded: Optional[bool] = None):
        self.inner = inner
        self.op = op
        self.compression = compression
        # wire-codec compressors (Compression.fp8/int8, ISSUE 13): the
        # frontend leaves tensors untouched and the ENGINE encodes the
        # collective's slow-link payload per fusion bucket, error-
        # feedback residuals held in engine state — the codec override
        # rides every grouped_allreduce/sharded_step this optimizer
        # submits
        self._wire_codec = getattr(compression, "wire_codec", None)
        self.backward_passes_per_step = backward_passes_per_step
        self.sparse_rows = dict(sparse_rows or {})
        if self.sparse_rows and op not in (Average, Sum):
            raise ValueError("sparse_rows supports op=Average|Sum only")
        if self._wire_codec is not None and op not in (Average, Sum):
            raise ValueError("wire-codec compression (Compression.fp8/"
                             "int8) supports op=Average|Sum only")
        # ZeRO-1 optimizer-state sharding (docs/sharded_optimizer.md):
        # None defers to the HOROVOD_TPU_SHARD_OPTIMIZER config knob (also
        # an autotune categorical), resolved once at state init so a knob
        # flip mid-run cannot invalidate live state shapes.
        self._sharded_arg = sharded
        self._sharded: Optional[bool] = (bool(sharded)
                                         if sharded is not None else None)
        # stable identity for the engine's sharded-step builder cache:
        # id(self) could be recycled by the allocator onto a DIFFERENT
        # optimizer (stale compiled update program); a monotonic token
        # cannot
        self._zero1_token = next(_ZERO1_TOKENS)
        if sharded:
            if op not in (Average, Sum):
                raise ValueError(
                    "sharded=True supports op=Average|Sum only")
            if compression is not Compression.none \
                    and self._wire_codec is None:
                raise ValueError(
                    "sharded=True composes only with wire-codec "
                    "compression (Compression.fp8/int8, applied to the "
                    "reduce-scatter legs) or Compression.none — cast "
                    "compressors would change the packed buffers' "
                    "dtype-uniform layout")
            if self.sparse_rows:
                raise ValueError(
                    "sharded=True does not compose with sparse_rows")
        self._accum = None
        self._count = 0
        self._step = 0
        # Bounded (ADVICE r4): each distinct key pins a compiled XLA
        # program, so unbounded growth leaks device memory on long-lived
        # runs that cycle tree structures/compression contexts. Plain dicts
        # are insertion-ordered; _cache_get/_cache_put below make them LRU.
        self._apply_cache = {}
        self._extract_cache = {}
        self._ks_cache = {}
        self._layout_cache = {}   # frozen ZeRO-1 bucket layouts per tree
        self._cache_cap = 16
        self._m_sharded_step = _metrics_registry().histogram(
            "hvd_tpu_sharded_step_seconds")

    def _is_sharded(self) -> bool:
        if self._sharded is None:
            from .core.state import global_state
            st = global_state()
            self._sharded = bool(st.initialized
                                 and st.config.shard_optimizer)
            if self._sharded and (self.op not in (Average, Sum)
                                  or (self.compression is not
                                      Compression.none
                                      and self._wire_codec is None)
                                  or self.sparse_rows):
                # config-driven opt-in must not silently change an
                # incompatible optimizer; fall back to replicated
                self._sharded = False
        return self._sharded

    def init(self, params):
        if not self._is_sharded():
            return self.inner.init(params)
        return self._sharded_init(params)

    # -- ZeRO-1 sharded path (docs/sharded_optimizer.md) -------------------

    def _sharded_layout(self, leaves, treedef):
        """Bucket layout for this tree shape, FROZEN at first computation
        (state init): shard-shaped optimizer state pins the layout, so a
        later autotune move of the fusion threshold must not re-bucket a
        live run (it would either crash the shape validation or, worse,
        land mid-call). LRU-cached, which also keeps the O(leaves) layout
        walk off the per-step hot path."""
        key = (treedef,
               tuple(tuple(l.shape) for l in leaves),
               tuple(str(l.dtype) for l in leaves))
        layout = self._cache_get(self._layout_cache, key)
        if layout is None:
            eng = self._engine()
            layout = self._cache_put(
                self._layout_cache, key,
                _zero1_layout(leaves, eng.backend.size(),
                              eng.config.fusion_threshold_bytes))
        return layout

    def _sharded_init(self, params):
        """Materialize ONLY this rank's optimizer-state shard: the params
        are packed into fusion buckets, this rank's 1/world_size slice of
        each padded bucket becomes the flat master copy, and the inner
        optax state is created over those shard vectors — optimizer-state
        memory per rank is ceil(total/n) per bucket instead of total."""
        eng = self._engine()
        rank = eng.backend.rank()
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        leaves = [jnp.asarray(l) for l in p_leaves]
        layout = self._sharded_layout(leaves, treedef)
        shards = []
        for idxs, sizes, total, shard in layout:
            flat = _pack_bucket(leaves, idxs)
            pad = shard * eng.backend.size() - total
            if pad:
                flat = jnp.concatenate([flat,
                                        jnp.zeros((pad,), flat.dtype)])
            shards.append(jax.lax.dynamic_slice_in_dim(
                flat, rank * shard, shard))
        return ShardedEagerState(self.inner.init(shards), tuple(shards))

    def _sharded_update_and_apply(self, grads, opt_state, params):
        """One engine ``sharded_step`` per training step (bracketed by the
        replay markers): pack -> per-bucket reduce-scatter -> inner update
        on this rank's shards -> fused all-gather of the updated parameter
        shards. Steady state replays as ONE fused launch."""
        if not isinstance(opt_state, ShardedEagerState):
            raise ValueError(
                "sharded optimizer got a non-sharded state; create it with "
                "this optimizer's init() (or pass sharded=False)")
        eng = self._engine()
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        leaves = [jnp.asarray(l) for l in leaves]
        layout = self._sharded_layout(leaves, treedef)
        for b, (idxs, sizes, total, shard) in enumerate(layout):
            got = int(opt_state.shards[b].shape[0]) \
                if b < len(opt_state.shards) else -1
            if got != shard or len(layout) != len(opt_state.shards):
                raise ValueError(
                    f"sharded state layout mismatch (bucket {b}: state "
                    f"shard {got}, expected {shard}): the fusion "
                    f"threshold, tree structure, or world size changed "
                    f"after init(); re-create the optimizer state "
                    f"(elastic resets must re-run opt.init on the "
                    f"restored params)")
        state_leaves, state_treedef = jax.tree_util.tree_flatten(opt_state)
        inner = self.inner

        def shard_update(grad_shards, st_leaves):
            st = jax.tree_util.tree_unflatten(state_treedef, st_leaves)
            updates, new_inner = inner.update(list(grad_shards),
                                              st.inner_state,
                                              list(st.shards))
            new_shards = [p + u for p, u in zip(st.shards, updates)]
            new_state = ShardedEagerState(new_inner, tuple(new_shards))
            return new_shards, jax.tree_util.tree_leaves(new_state)

        update_key = ("zero1", self._zero1_token, treedef, state_treedef)
        t0 = _time.perf_counter()
        eng.step_begin()
        try:
            # the FROZEN layout's buckets ride along so a live fusion-
            # threshold move (autotune) can never re-bucket against the
            # shard-shaped state
            handles = eng.sharded_step(
                leaves, shard_update, update_key, state_leaves,
                name=f"grad.zero.s{self._step}", op=self.op,
                buckets=[list(idxs) for idxs, _, _, _ in layout],
                codec=self._wire_codec)
        finally:
            eng.step_end()
        # dispatch-phase wall time (pack + the fused rs->update->ag launch;
        # the collective itself completes asynchronously and is covered by
        # hvd_tpu_op_latency_seconds{kind="sharded_step"})
        self._m_sharded_step.observe(_time.perf_counter() - t0)
        self._step = (self._step + 1) % 1024
        n = len(leaves)
        new_params = jax.tree_util.tree_unflatten(
            treedef, [h.result() for h in handles[:n]])
        new_state = jax.tree_util.tree_unflatten(
            state_treedef, [h.result() for h in handles[n:]])
        return new_params, new_state

    def _cache_get(self, cache, key):
        return lru_get(cache, key)

    def _cache_put(self, cache, key, val):
        return lru_put(cache, key, val, self._cache_cap)

    def _engine(self):
        from .core.state import global_state
        st = global_state()
        if not st.initialized:
            raise ValueError("horovod_tpu has not been initialized; run hvd.init() "
                             "first.")
        return st.engine

    # -- durable checkpointing of the ZeRO-1 state (ISSUE 9) ---------------

    def checkpoint_payload(self, opt_state, params):
        """``(shards, inner_state, layout)`` for
        ``CheckpointManager.snapshot_zero1``: this rank's per-bucket flat
        parameter shards, the shard-shaped inner optax state, and the
        FROZEN bucket layout — each rank persists exactly its 1/world
        slice, and a restore at a different world size re-slices it
        (``checkpoint.shard_io.zero1_reshard``)."""
        if not isinstance(opt_state, ShardedEagerState):
            raise ValueError(
                "checkpoint_payload needs a ZeRO-1 ShardedEagerState "
                "(sharded=True); replicated states checkpoint through "
                "CheckpointManager.snapshot directly")
        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        leaves = [jnp.asarray(l) for l in p_leaves]
        layout = self._sharded_layout(leaves, treedef)
        return opt_state.shards, opt_state.inner_state, layout

    def restore_from_durable(self, restore_tree, params_template):
        """Rebuild ``(params, ShardedEagerState)`` for THIS world from a
        zero1 ``RestoreResult.tree`` (the reshard dict): full parameters
        come from the unpacked logical buckets, the master-copy shards
        and inner state from the new-world reslice — optimizer momenta
        survive an N→M elastic resize."""
        from .checkpoint import shard_io
        header = restore_tree["header"]
        p_leaves, treedef = jax.tree_util.tree_flatten(params_template)
        leaves = [jnp.asarray(l) for l in p_leaves]
        layout = self._sharded_layout(leaves, treedef)
        if len(layout) != len(header["buckets"]) or any(
                tuple(l[0]) != tuple(b["idxs"]) or l[2] != b["total"]
                for l, b in zip(layout, header["buckets"])):
            raise ValueError(
                "durable ZeRO-1 checkpoint bucket layout does not match "
                "this optimizer's (fusion threshold or tree changed); "
                "restore the parameters and re-run init() instead")
        outs = [None] * len(leaves)
        for spec, flat in zip(header["buckets"],
                              restore_tree["full_buckets"]):
            for i, vals in shard_io.unpack_bucket(flat, spec).items():
                outs[i] = jnp.asarray(vals).reshape(leaves[i].shape) \
                    .astype(leaves[i].dtype)
        params = jax.tree_util.tree_unflatten(treedef, outs)
        shards = tuple(jnp.asarray(s) for s in restore_tree["shards"])
        st_template = self.inner.init(list(shards))
        st_leaves, st_def = jax.tree_util.tree_flatten(st_template)
        restored = restore_tree["state_leaves"]
        if len(restored) != len(st_leaves):
            raise ValueError(
                f"inner optimizer state has {len(st_leaves)} leaves, "
                f"checkpoint has {len(restored)} — different inner "
                f"transform; re-run init() instead")
        inner_state = jax.tree_util.tree_unflatten(
            st_def, [jnp.asarray(r).reshape(jnp.asarray(t).shape).astype(
                jnp.asarray(t).dtype) for r, t in zip(restored, st_leaves)])
        return params, ShardedEagerState(inner_state, shards)

    def _sparse_ks(self, grads, leaves, treedef):
        """Per-leaf sparse row budget (None = dense): a grad leaf is sparse
        when its tree path contains one of the ``sparse_rows`` patterns.
        Cached per (treedef, leaf dim-0s): the path flattening + substring
        matching is O(leaves) Python work that must not ride the per-step
        hot path."""
        if not self.sparse_rows:
            return [None] * len(leaves)
        key = (treedef, tuple(int(l.shape[0]) if l.ndim else 0
                              for l in leaves))
        cached = self._cache_get(self._ks_cache, key)
        if cached is not None:
            return cached
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        ks = []
        for path, leaf in flat:
            s = jax.tree_util.keystr(path)
            k = next((v for pat, v in self.sparse_rows.items() if pat in s),
                     None)
            if k is None:
                ks.append(None)
                continue
            # the reduction runs on the ACCUMULATED grad when
            # backward_passes_per_step > 1 — each pass can touch k fresh
            # rows, so the lossless budget is k per pass
            k = int(k) * self.backward_passes_per_step
            ks.append(min(k, int(leaf.shape[0])))
        return self._cache_put(self._ks_cache, key, ks)

    def _extract_fn(self, k: int):
        """Jitted top-k row extraction: untouched rows are exactly zero, so
        taking the k largest rows by L1 norm is lossless whenever k >= the
        true touched-row count (padding rows carry zero values)."""
        fn = self._cache_get(self._extract_cache, k)
        if fn is None:
            @jax.jit
            def fn(g):
                norms = jnp.sum(jnp.abs(g), axis=tuple(range(1, g.ndim)))
                _, idx = jax.lax.top_k(norms, k)
                return idx.astype(jnp.int32), g[idx]
            self._cache_put(self._extract_cache, k, fn)
        return fn

    def _reduce_async(self, leaves, sparse_ks):
        """Compress + bucket + allreduce the dense gradient leaves and
        allgather the sparse ones as (indices, values), returning per-leaf
        reduced representations WITHOUT waiting — the arrays are dataflow
        futures (Handle.result). Per-step names let step N+1's reduction
        enter flight while step N's is still executing (the pipelining the
        reference gets from per-parameter hooks, torch/optimizer.py:
        100-135)."""
        eng = self._engine()
        # Step-capture markers (core/replay.py): the reduction phase of one
        # update IS one step of the dispatch stream — after
        # step_replay_warmup identical steps the engine services the whole
        # grouped reduction as a single fused launch.
        eng.step_begin()
        try:
            return self._reduce_async_inner(eng, leaves, sparse_ks)
        finally:
            eng.step_end()

    def _reduce_async_inner(self, eng, leaves, sparse_ks):
        dense = [i for i, k in enumerate(sparse_ks) if k is None]
        compressed, dense_ctxs = [], []
        for i in dense:
            c, ctx = self.compression.compress(leaves[i])
            compressed.append(c)
            dense_ctxs.append(ctx)
        if self.op == Adasum:
            from .ops.adasum import adasum_allreduce_handle
            handles = [adasum_allreduce_handle(
                eng, c, f"grad.adasum.s{self._step}.{i}")
                for i, c in enumerate(compressed)]
        elif compressed:
            handles = eng.grouped_allreduce(
                compressed, name=f"grad.s{self._step}", op=self.op,
                codec=self._wire_codec)
        else:
            handles = []
        reduced = [None] * len(leaves)
        ctxs = [None] * len(leaves)
        for pos, i in enumerate(dense):
            reduced[i] = handles[pos].result()
            ctxs[i] = dense_ctxs[pos]
        for i, k in enumerate(sparse_ks):
            if k is None:
                continue
            idx, vals = self._extract_fn(k)(leaves[i])
            # k is static and identical on every rank — equal_sizes skips
            # the size negotiation (no exchange on the hot path at all)
            hi = eng.allgather(idx, name=f"grad.s{self._step}.sp{i}.idx",
                               equal_sizes=True)
            hv = eng.allgather(vals, name=f"grad.s{self._step}.sp{i}.val",
                               equal_sizes=True)
            reduced[i] = (hi.result(), hv.result())
        # Rotating window, not a monotone counter (ADVICE r4): per-step
        # names exist so consecutive steps' reductions can overlap in
        # flight; 1024 distinct names bounds every per-name table
        # (registration, meta cache, observability) while leaving far more
        # in-flight steps than any pipeline reaches before a name recurs.
        self._step = (self._step + 1) % 1024
        return reduced, ctxs

    def _apply_fn(self, treedef, ctxs, sparse_ks, world_size):
        """One jitted program for decompress + sparse scatter-add combine +
        inner update + apply: a single dispatch chained onto the reduced
        arrays, instead of one eager dispatch per optax op. Cached per
        (tree structure, compression ctx, sparse layout)."""
        key = (treedef, tuple(repr(c) for c in ctxs), tuple(sparse_ks),
               world_size)
        fn = self._cache_get(self._apply_cache, key)
        if fn is None:
            comp, inner, op = self.compression, self.inner, self.op

            @jax.jit
            def hvd_apply_update(reduced_c, opt_state, params):
                p_leaves = jax.tree_util.tree_leaves(params)
                out = []
                with jax.named_scope(scopes.DECOMPRESS):
                    for r, c, k, p in zip(reduced_c, ctxs, sparse_ks,
                                          p_leaves):
                        if k is None:
                            out.append(comp.decompress(r, c))
                            continue
                        # sparse leaf: duplicate rows combine in a jitted
                        # scatter-add (the segment-sum the reference does in
                        # DeduplicateIndexedSlices) — never on the host
                        idx, vals = r
                        d = jnp.zeros(p.shape, vals.dtype).at[idx].add(vals)
                        if op == Average:
                            d = d / world_size
                        out.append(d)
                reduced = jax.tree_util.tree_unflatten(treedef, out)
                return scopes.apply_update(inner, reduced, opt_state, params)

            fn = hvd_apply_update               # scopes.APPLY_UPDATE
            self._cache_put(self._apply_cache, key, fn)
        return fn

    def reduce_gradients(self, grads):
        """Bucket + allreduce a gradient pytree across processes (blocking:
        returns concrete reduced arrays, the synchronize()-style API)."""
        eng = self._engine()
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if eng.backend.size() == 1:
            return grads
        reduced_c, ctxs = self._reduce_async(leaves, [None] * len(leaves))
        for r in reduced_c:
            r.block_until_ready()
        reduced = [self.compression.decompress(r, ctx)
                   for r, ctx in zip(reduced_c, ctxs)]
        return jax.tree_util.tree_unflatten(treedef, reduced)

    def update_and_apply(self, grads, opt_state, params):
        """Accumulate/reduce grads, run the inner optax update, apply it.

        Returns (new_params, new_opt_state). On accumulation passes (when
        backward_passes_per_step > 1 and this isn't the k-th pass) params are
        returned unchanged.

        Hot path (VERDICT r3 item 1): NO host block anywhere — the reduction
        is dispatched fire-and-forget and the (jitted) update is chained onto
        the reduced arrays; XLA dataflow orders it after the collective. The
        grad→reduce→apply phases of one step and consecutive steps all
        overlap on-device, the way the reference overlaps backward compute
        with hook-fired async allreduces (torch/optimizer.py:100-135)."""
        with scopes.host_span(scopes.OPT_UPDATE_AND_APPLY):
            return self._update_and_apply(grads, opt_state, params)

    def _update_and_apply(self, grads, opt_state, params):
        if self.backward_passes_per_step > 1:
            if self._accum is None:
                self._accum = grads
            else:
                self._accum = jax.tree_util.tree_map(lambda a, g: a + g,
                                                     self._accum, grads)
            self._count += 1
            if self._count < self.backward_passes_per_step:
                return params, opt_state
            # Summed, not averaged, across local passes (reference
            # torch/optimizer.py:122-149).
            grads = self._accum
            self._accum = None
            self._count = 0
        if self._is_sharded():
            return self._sharded_update_and_apply(grads, opt_state, params)
        eng = self._engine()
        size = eng.backend.size()
        with scopes.host_span(scopes.OPT_FLATTEN):
            leaves, treedef = jax.tree_util.tree_flatten(grads)
            sparse_ks = ([None] * len(leaves) if size == 1
                         else self._sparse_ks(grads, leaves, treedef))
        if size == 1:
            reduced_c, ctxs = leaves, [None] * len(leaves)
        else:
            with scopes.host_span(scopes.OPT_REDUCE):
                reduced_c, ctxs = self._reduce_async(leaves, sparse_ks)
        with scopes.host_span(scopes.OPT_APPLY_LOOKUP):
            fn = self._apply_fn(treedef, ctxs, sparse_ks, size)
        with scopes.host_span(scopes.OPT_APPLY_DISPATCH):
            return fn(reduced_c, opt_state, params)


def DistributedOptimizer(inner: optax.GradientTransformation, op: ReduceOp = Average,
                         compression=Compression.none,
                         backward_passes_per_step: int = 1,
                         sharded: Optional[bool] = None):
    """Reference-named factory (torch/optimizer.py:367 DistributedOptimizer).
    ``sharded=True`` selects ZeRO-1 optimizer-state partitioning (see
    :class:`DistributedEagerOptimizer` and docs/sharded_optimizer.md)."""
    return DistributedEagerOptimizer(inner, op=op, compression=compression,
                                     backward_passes_per_step=backward_passes_per_step,
                                     sharded=sharded)


# ---------------------------------------------------------------------------
# Delta-model Adasum (the reference's SECOND Adasum integration)
# ---------------------------------------------------------------------------
#
# The reference ships Adasum in two forms: gradient reduction with op=Adasum
# (covered by allreduce_gradients/DistributedEagerOptimizer above), and
# _DistributedAdasumOptimizer (torch/optimizer.py:196-364, tensorflow/
# __init__.py:303-397): apply the LOCAL optimizer step first and
# Adasum-reduce the parameter DELTA — the form that preserves Adasum's
# scale-invariance under adaptive optimizers (Adam's preconditioner runs on
# the local gradient before mixing, so the mixing weights see the actual
# step geometry). The torch code realizes delta = -α·f(g) by zeroing a
# stashed copy and diffing after an in-place step; under optax the delta
# IS the functional ``updates`` tree, so the TPU form reduces the inner
# transformation's updates — no stash, no diff.


def distributed_delta_adasum(inner: optax.GradientTransformation,
                             axis_name: str = "world",
                             axis_size: Optional[int] = None,
                             compression=Compression.none
                             ) -> optax.GradientTransformation:
    """SPMD delta-Adasum: wrap ``inner`` so its *updates* (the parameter
    delta) are Adasum-combined across ``axis_name`` inside a pjit/shard_map
    train step. Usage mirrors :func:`distributed`."""
    if axis_size is None:
        raise ValueError("distributed_delta_adasum needs axis_size")

    def init_fn(params):
        return inner.init(params)

    def update_fn(grads, state, params=None):
        # probe once per update, not per leaf (it emits a pcast each call)
        tracking = _vma_tracking_active(axis_name)

        def check(g):
            if _pre_summed(g, axis_name, tracking):
                raise ValueError(
                    "delta-Adasum needs per-shard gradients; an implicitly "
                    "pre-summed (unvarying) gradient has already mixed the "
                    "replicas. Make the params varying (lax.pcast to "
                    "'varying') before jax.grad, or compute grads of a "
                    "local loss.")
            return g
        grads = jax.tree_util.tree_map(check, grads)
        updates, new_state = inner.update(grads, state, params)

        def reduce_leaf(u):
            c, ctx = compression.compress(u)
            return compression.decompress(
                adasum_p(c, axis_name, axis_size), ctx)

        return jax.tree_util.tree_map(reduce_leaf, updates), new_state

    return optax.GradientTransformation(init_fn, update_fn)


class DistributedDeltaAdasumOptimizer:
    """Eager (process-parallel) delta-model Adasum optimizer
    (torch/optimizer.py:196-364 _DistributedAdasumOptimizer).

    Each step: the inner optax update runs on the LOCAL gradients (one
    jitted dispatch), the resulting update leaves — the parameter delta —
    are Adasum-reduced through the engine, and a jitted apply chains
    ``params + reduced_delta`` onto the reduction's dataflow futures
    (no host block, like DistributedEagerOptimizer). The inner state
    (e.g. Adam moments) advances from local gradients, exactly as the
    reference's wrapped optimizer state does.
    """

    def __init__(self, inner: optax.GradientTransformation,
                 compression=Compression.none,
                 backward_passes_per_step: int = 1):
        self.inner = inner
        self.compression = compression
        if getattr(compression, "wire_codec", None) is not None:
            raise ValueError(
                "delta-Adasum has no wire-codec path (Adasum mixes whole "
                "updates, not additive sums); use Compression.none/fp16/"
                "bf16")
        self.backward_passes_per_step = backward_passes_per_step
        self._accum = None
        self._count = 0
        self._step = 0
        self._update_cache = {}
        self._apply_cache = {}
        self._cache_cap = 16

    def init(self, params):
        return self.inner.init(params)

    def _engine(self):
        from .core.state import global_state
        st = global_state()
        if not st.initialized:
            raise ValueError("horovod_tpu has not been initialized; run "
                             "hvd.init() first.")
        return st.engine

    def _update_fn(self, treedef):
        fn = lru_get(self._update_cache, treedef)
        if fn is None:
            inner = self.inner

            @jax.jit
            def fn(grads, opt_state, params):
                updates, new_state = inner.update(grads, opt_state, params)
                return jax.tree_util.tree_leaves(updates), new_state

            fn = lru_put(self._update_cache, treedef, fn, self._cache_cap)
        return fn

    def _apply_fn(self, treedef, ctxs):
        key = (treedef, tuple(repr(c) for c in ctxs))
        fn = lru_get(self._apply_cache, key)
        if fn is None:
            comp = self.compression

            @jax.jit
            def hvd_apply_delta(reduced_c, params):     # scopes.APPLY_DELTA
                # ctx None = never compressed (the world-size-1 path applies
                # u_leaves directly; ADVICE r5): don't route through
                # decompress(r, None), whose cast is a no-op at best and a
                # dtype surprise at worst
                with jax.named_scope(scopes.DECOMPRESS):
                    deltas = [r if c is None else comp.decompress(r, c)
                              for r, c in zip(reduced_c, ctxs)]
                updates = jax.tree_util.tree_unflatten(treedef, deltas)
                with jax.named_scope(scopes.OPTIMIZER):
                    return optax.apply_updates(params, updates)

            fn = lru_put(self._apply_cache, key, hvd_apply_delta,
                         self._cache_cap)
        return fn

    def update_and_apply(self, grads, opt_state, params):
        """Local inner step -> Adasum-reduce the delta -> apply. Returns
        (new_params, new_opt_state); on intermediate accumulation passes
        params are returned unchanged."""
        with scopes.host_span(scopes.OPT_UPDATE_AND_APPLY):
            return self._update_and_apply(grads, opt_state, params)

    def _update_and_apply(self, grads, opt_state, params):
        if self.backward_passes_per_step > 1:
            if self._accum is None:
                self._accum = grads
            else:
                self._accum = jax.tree_util.tree_map(
                    lambda a, g: a + g, self._accum, grads)
            self._count += 1
            if self._count < self.backward_passes_per_step:
                return params, opt_state
            grads = self._accum
            self._accum = None
            self._count = 0
        eng = self._engine()
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        del leaves
        u_leaves, new_state = self._update_fn(treedef)(grads, opt_state,
                                                       params)
        if eng.backend.size() == 1:
            reduced, ctxs = u_leaves, [None] * len(u_leaves)
        else:
            from .ops.adasum import adasum_allreduce_handle
            compressed, ctxs = [], []
            for u in u_leaves:
                c, ctx = self.compression.compress(u)
                compressed.append(c)
                ctxs.append(ctx)
            handles = [adasum_allreduce_handle(
                eng, c, f"delta.adasum.s{self._step}.{i}")
                for i, c in enumerate(compressed)]
            reduced = [h.result() for h in handles]
            self._step = (self._step + 1) % 1024
        return self._apply_fn(treedef, ctxs)(reduced, params), new_state
