"""TPU-native data-plane collectives.

This is the equivalent of the reference's op backends (horovod/common/ops/:
MPIAllreduce mpi_operations.cc:26, NCCLAllreduce nccl_operations.cc:126,
GlooAllreduce gloo_operations.cc, MPIAllgather mpi_operations.cc:84,
MPIBroadcast :345, MPIAlltoall :380) — rebuilt as XLA collectives over a
``jax.sharding.Mesh`` instead of NCCL/MPI/Gloo calls. Two layers:

1. **In-SPMD primitives** — functions usable inside ``shard_map``/``pjit``-traced
   code, taking a mesh axis name. These are what the DistributedOptimizer and
   parallelism layers call; XLA lowers them onto ICI/DCN rings.

2. **Stacked builders** — ``build_*`` functions that, for a given mesh, return a
   jitted callable over a *stacked* global array (leading axis = group size, one
   slice per rank). This is the execution engine for the eager, Horovod-style
   named-tensor API and for single-host tests, replacing the reference's
   fusion-buffer + NCCL launch path (operations.cc:253-330).

All builders are shape-polymorphic only through the jit cache: each distinct
(shape, dtype) compiles once and is cached by ``jax.jit``.
"""

from __future__ import annotations

import logging
import math

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..common.env import DEFAULT_TREE_THRESHOLD_BYTES
from ..common.reduce_ops import ReduceOp
from . import compression as comp

logger = logging.getLogger("horovod_tpu")

# ---------------------------------------------------------------------------
# Topology-aware algorithm selection (ISSUE 10)
#
# Nothing in the stack used to *choose* a lowering: every message size got
# the same program, and hierarchy was an all-or-nothing env knob. This is
# the selection layer the reference implements as OperationManager priority
# dispatch (operations.cc:142-249) plus NCCL's per-size algorithm pick,
# rebuilt per fusion bucket: flat ring, tree (recursive halving/doubling
# for latency-bound small buckets), or the hierarchical ICI/DCN ladder,
# per (kind, bytes, Topology).
# ---------------------------------------------------------------------------

ALGO_FLAT = "flat"
ALGO_TREE = "tree"
ALGO_HIERARCHICAL = "hierarchical"
ALGORITHMS = (ALGO_FLAT, ALGO_TREE, ALGO_HIERARCHICAL)

# kinds the selection layer covers; everything else is always flat
_SELECTABLE_KINDS = ("allreduce", "reducescatter", "allgather", "alltoall")

_warned_demotions: set = set()


def _demote(key: tuple, msg: str) -> str:
    """One-time WARNING per (reason key); returns the flat algorithm —
    the satellite fix for the hard divisibility asserts: an invalid
    forcing or topology degrades, it never crashes."""
    if key not in _warned_demotions:
        _warned_demotions.add(key)
        logger.warning("collective algorithm selection: %s; using flat", msg)
    return ALGO_FLAT


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def validate_algorithm(kind: str, algo: str, n: int, local_size: int) -> str:
    """Demote an algorithm the (kind, world, topology) cannot express:

    - tree needs a power-of-2 world (the recursive-doubling pair rounds)
      and only applies to reductions;
    - hierarchical needs an exact non-trivial (cross, local)
      factorization, and never applies to reduce-scatter — the ZeRO-1
      shard-ownership convention (rank r owns contiguous chunk r of the
      padded buffer, :func:`shard_spec`) pins the scatter to the flat
      ring: a two-level scatter permutes chunk ownership, which would
      corrupt shard-shaped optimizer state and the checkpoint layout.
    """
    if algo not in ALGORITHMS:
        return _demote((kind, algo), f"unknown algorithm {algo!r}")
    if n <= 1 or algo == ALGO_FLAT:
        return ALGO_FLAT
    if algo == ALGO_TREE:
        if kind not in ("allreduce",):
            return _demote((kind, algo),
                           f"tree does not apply to {kind}")
        if not _is_pow2(n):
            return _demote((kind, algo, n),
                           f"tree needs a power-of-2 world, have {n}")
        return ALGO_TREE
    # hierarchical
    if kind == "reducescatter":
        return _demote((kind, algo),
                       "reduce-scatter keeps the flat ring (shard-"
                       "ownership invariant, see validate_algorithm)")
    if not (1 < local_size < n and n % local_size == 0):
        return _demote((kind, algo, n, local_size),
                       f"no exact (cross, local) factorization for "
                       f"world {n} with local_size {local_size}")
    return ALGO_HIERARCHICAL


def choose_algorithm(kind: str, nbytes: int, topology,
                     force: str = "auto",
                     tree_threshold_bytes: int =
                     DEFAULT_TREE_THRESHOLD_BYTES,
                     hier_threshold_bytes: int = 0) -> str:
    """Pick the lowering for ONE bucket of ``kind`` carrying ``nbytes``
    per rank over ``topology`` (a :class:`~..parallel.mesh.Topology`).

    ``force`` != "auto" pins the choice (demoted when inexpressible).
    Auto rules:

    - reductions at or under ``tree_threshold_bytes`` on a power-of-2
      world of >= 4 lower to the tree form — log2(n) latency steps
      instead of the ring's 2(n-1), the classic small-message win (at
      n=2 tree and flat are the same single exchange, so auto never
      bothers);
    - above the threshold, allreduce/allgather take the hierarchical
      ICI/DCN ladder when the topology has an exact non-trivial slice
      decomposition (cross traffic 1/local_size — the reference's
      NCCL-RS -> MPI-AR -> NCCL-AG ladder, nccl_operations.cc:180-383)
      AND the payload reaches ``hier_threshold_bytes`` — the calibrated
      flat/hierarchical crossover (autotune/calibration.py: the ladder's
      extra launches cost α before its bandwidth win pays). The default
      0 keeps the nominal always-hierarchical behavior;
    - alltoall takes the two-phase ICI-then-DCN exchange under the same
      (factorization AND threshold) rule: the flat whole-world alltoall
      pushes O(n) distinct chunks over every DCN link, while the
      two-level form first exchanges within each slice (ICI) and then
      moves O(n/slices) whole slice-blocks across DCN — the quadratic
      DCN-hop fix. The engine passes alltoall its OWN calibrated
      threshold (``Config.alltoall_hier_threshold_bytes``);
    - otherwise the flat ring.

    Deterministic in (kind, bytes, topology, knobs) — every rank that
    submits the same collective computes the same schedule, which is what
    lets the replay/overlap paths and Join substitutes resolve identical
    programs without negotiation.
    """
    n = int(topology.size)
    local = int(topology.local_size)
    if n <= 1 or kind not in _SELECTABLE_KINDS:
        return ALGO_FLAT
    if force != "auto":
        return validate_algorithm(kind, force, n, local)
    if (kind == "allreduce" and nbytes <= tree_threshold_bytes
            and n >= 4 and _is_pow2(n)):
        return ALGO_TREE
    if (kind in ("allreduce", "allgather", "alltoall")
            and topology.hierarchical_ok
            and nbytes >= hier_threshold_bytes):
        return ALGO_HIERARCHICAL
    return ALGO_FLAT


def link_split(algo: str, nbytes: int, local_size: int,
               kind: str = "allreduce", codec: str = comp.CODEC_NONE,
               itemsize: int = 4, size: int = 0) -> dict:
    """Per-fabric attribution of one bucket's payload bytes (the
    ``link`` label on ``hvd_tpu_wire_bytes_total``): each byte is counted
    once, attributed to the fabric that paces it.

    - hierarchical **allreduce**: the cross-slice exchange carries
      1/local_size of the payload over DCN (the ladder's whole point),
      the rest rides the intra-slice ICI legs;
    - hierarchical **allgather**: the cross gather moves whole slice
      blocks — EVERY payload byte crosses DCN (the win there is one
      contiguous block transfer instead of a whole-world ring, not a
      byte reduction), so the full payload is attributed to DCN;
    - hierarchical **alltoall**: the phase-2 block transpose carries the
      (C-1)/C of the payload destined for OTHER slices over DCN (C =
      ``size // local_size`` slices — ``size`` is required for this
      kind, nothing else here needs the world size); the remaining 1/C
      stays on the slice and is attributed to the ICI phase. The DCN
      leg is the (optionally) encoded one;
    - every other lowering is whole-fabric ("flat").

    ``codec`` (ISSUE 13) shrinks the *encoded* leg: on the hierarchical
    ladder only the DCN exchange is encoded — the ICI legs stay full
    precision, so their bytes are unchanged. Flat/tree allreduce
    lowerings run the compressed-RS + full-precision-AG fallback, so
    HALF the payload movement is encoded; a reduce-scatter is all
    encoded. ``itemsize`` is the uncompressed element size the codec
    ratio is computed against.

    Convention note: this is SUBMITTED-payload accounting, not
    algorithmic link traffic — the uncompressed ladder's cross RS+AG is
    likewise booked at dcn_raw though it moves ~2x that, and the encoded
    cross gather's receive volume grows with the slice count C (each
    peer's encoded shard arrives once). Before/after deltas under one
    convention stay comparable; the realized wall-clock win on the
    gather form shrinks as C approaches the compression ratio
    (docs/compression.md)."""
    nbytes = int(nbytes)

    def enc(b):
        if codec == comp.CODEC_NONE:
            return b
        return (b // itemsize) * comp.wire_itemsize(codec, itemsize)

    if algo == ALGO_HIERARCHICAL and local_size > 1:
        if kind == "allgather":
            return {"dcn": nbytes}
        if kind == "alltoall":
            cross = max(size // local_size, 1)
            dcn_raw = nbytes - nbytes // cross
            return {"dcn": enc(dcn_raw), "ici": nbytes - dcn_raw}
        dcn_raw = nbytes // local_size
        return {"dcn": enc(dcn_raw), "ici": nbytes - dcn_raw}
    if kind in ("allgather", "alltoall"):
        return {"flat": nbytes}
    if kind == "reducescatter":
        return {"flat": enc(nbytes)}
    # allreduce family: the encoded reduce-scatter half + the
    # full-precision all-gather half of the payload convention
    half = nbytes // 2
    return {"flat": enc(half) + (nbytes - half)}


def slice_groups(n: int, local_size: int):
    """The ONE slice-major rank-layout rule every two-level collective
    shares: ``(local_groups, cross_groups)`` where slice c owns the
    contiguous rank block ``[c*local_size, (c+1)*local_size)`` and cross
    group l spans the slices at local index l. Every hierarchical builder
    derives its replica groups here (and
    ``Topology.local_groups/cross_groups`` mirror the same rule for
    callers) — a layout change must never be applied to one ladder leg
    and not another, or reduce and gather silently disagree on chunk
    ownership."""
    cross = n // local_size
    local_groups = [[c * local_size + l for l in range(local_size)]
                    for c in range(cross)]
    cross_groups = [[c * local_size + l for c in range(cross)]
                    for l in range(local_size)]
    return local_groups, cross_groups


def ring_edge_is_dcn(n: int, local_size: int) -> Tuple[bool, ...]:
    """Classify the n ring edges of the slice-major layout: edge i
    connects rank i to rank (i+1) % n and is a DCN (cross-slice) edge iff
    the two ranks live on different islands under the
    :func:`slice_groups` rule. Single-island worlds have no DCN edges.
    The pipeline boundary codec (ISSUE 16) uses this to decide which
    stage-boundary hops get the wire codec — the same layout rule the
    hierarchical ladder uses, for the same reason: coding an ICI edge
    wastes precision for bandwidth that was never scarce."""
    if local_size <= 1 or local_size >= n or n % local_size:
        return tuple([False] * n)
    return tuple((i // local_size) != (((i + 1) % n) // local_size)
                 for i in range(n))


def tree_groups(n: int) -> List[List[List[int]]]:
    """Recursive-doubling round structure for a power-of-2 world: round k
    pairs ranks differing in bit k. After log2(n) pairwise psums every
    rank holds the full reduction — log2(n) latency steps vs the ring's
    2(n-1) (Thakur et al. 2005, the MPICH allreduce small-message
    algorithm)."""
    assert _is_pow2(n), n
    rounds = []
    k = 1
    while k < n:
        rounds.append([[r, r | k] for r in range(n) if not (r & k)])
        k <<= 1
    return rounds


# ---------------------------------------------------------------------------
# Link-aware wire codecs (ISSUE 13)
#
# A quantized payload cannot be summed on the wire (int8 sums overflow and
# per-sender scales differ), so every compressed reduction decodes before
# accumulating (in float32), in one of two shapes:
#
# - the hierarchical ladder keeps its ICI reduce-scatter/all-gather legs
#   full precision and replaces ONLY the cross-slice (DCN) exchange with a
#   gather of encoded shards + rank-local decode-sum — compression error
#   scales with the slow link's traffic, and with the slice count C
#   typically at or under the compression ratio, the (C-1)-fold encoded
#   gather still undercuts the full-precision cross RS+AG;
# - flat/tree selections take the whole-payload fallback: a compressed
#   reduce-scatter (all-to-all of encoded chunks, decode-sum of the owned
#   chunk) followed by a full-precision all-gather — enc + nbytes on the
#   wire vs the ring's ~2*nbytes, a win at EVERY world size (a
#   whole-payload gather's receive traffic would grow with n instead).
#
# Either way the result is identical on every member of the exchange
# group (same received data, same arithmetic), i.e. replicated by
# construction. The error-feedback codecs quantize (g + residual) and
# carry the quantization error forward in a rank-local residual buffer
# (engine state, per fusion bucket).
# ---------------------------------------------------------------------------


def codec_residual_elems(cls: str, total: int, n: int, local_size: int,
                         algo: Optional[str], codec: str) -> Optional[int]:
    """Residual-buffer length for one error-feedback bucket — the ONE
    shape rule the engine, replay, and the builders share (a disagreement
    would trace mis-shaped programs). ``cls`` is ``"reduce"`` (allreduce
    family: the residual covers the encoded leg — the local-RS shard on
    the hierarchical ladder, the whole payload otherwise) or
    ``"sharded"`` (the ZeRO-1 reduce-scatter leg: the whole zero-padded
    flat bucket, since the scatter is whole-world). None = the codec
    carries no residual."""
    if codec not in comp.EF_CODECS:
        return None
    total = int(total)
    if cls == "sharded":
        return shard_spec(total, n)[0]
    if algo == ALGO_HIERARCHICAL and local_size > 1:
        pad = (-total) % local_size
        return (total + pad) // local_size
    # flat/tree fallback: the whole zero-padded payload (the compressed
    # reduce-scatter's pre-scatter encode covers every element)
    return shard_spec(total, n)[0]


def _gathered_decode_sum(payload, scale, axis: str, groups, codec: str,
                         out_dtype):
    """The compressed sum exchange: all-gather encoded contributions (and
    their scales) over ``groups`` (None = the whole axis), decode, sum."""
    g_pay = lax.all_gather(payload, axis, axis=0, tiled=False,
                           axis_index_groups=groups)
    g_scale = None
    if scale is not None:
        g_scale = lax.all_gather(scale, axis, axis=0, tiled=False,
                                 axis_index_groups=groups)
    return comp.decode_sum(g_pay, g_scale, codec, out_dtype)


def _make_codec_reducer(axis: str, op: ReduceOp, n: int, local_size: int,
                        algo: str, codec: str):
    """Flat-buffer compressed-reduction closure: ``reduce(flat, residual)
    -> (out, new_residual)``. ``algo`` must be pre-resolved; the
    hierarchical form compresses only the cross-slice (DCN) exchange,
    every other selection (flat, and tree — whose pair rounds would
    compound quantization error) takes the whole-payload fallback: a
    compressed reduce-scatter (:func:`_rs_flat_codec`) plus a
    full-precision all-gather — enc + nbytes on the wire at every world
    size, where a whole-payload gather would receive (n-1)*enc. Only
    SUM/AVERAGE are compressible (the engine resolves other ops to codec
    "none" before reaching here)."""
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(f"wire codecs support Sum and Average, got {op!r}")
    hier = (algo == ALGO_HIERARCHICAL and 1 < local_size < n
            and n % local_size == 0)
    if hier:
        local_groups, cross_groups = slice_groups(n, local_size)

    def _reduce(flat, residual):
        if hier:
            pad = (-flat.shape[0]) % local_size
            if pad:
                flat = jnp.concatenate([flat,
                                        jnp.zeros((pad,), flat.dtype)])
            # ICI leg, full precision: intra-slice reduce-scatter
            shard = lax.psum_scatter(flat, axis, scatter_dimension=0,
                                     tiled=True,
                                     axis_index_groups=local_groups)
            # DCN leg, encoded: quantize(shard + residual), gather the
            # cross-slice contributions, decode-sum
            payload, scale, new_res = comp.ef_encode(shard, residual, codec)
            ssum = _gathered_decode_sum(payload, scale, axis, cross_groups,
                                        codec, shard.dtype)
            # ICI leg, full precision: intra-slice all-gather back
            out = lax.all_gather(ssum, axis, axis=0, tiled=True,
                                 axis_index_groups=local_groups)
            if pad:
                out = out[:-pad]
            if op == ReduceOp.AVERAGE:
                out = out / n
            return out, new_res
        total = flat.shape[0]
        shard, new_res = _rs_flat_codec(flat, residual, axis, n, op, codec)
        out = lax.all_gather(shard, axis, axis=0, tiled=True)
        if out.shape[0] != total:
            out = out[:total]
        return out, new_res

    return _reduce


def ef_allreduce_p(x, residual, axis_name: str, codec: str,
                   op: ReduceOp = ReduceOp.SUM):
    """Whole-payload compressed allreduce for traced (SPMD) code: the
    in-shard_map sibling of the engine's codec path, used by
    ``hvd.distributed(compression=Compression.int8)``. Same shape as the
    flat fallback reducer — compressed reduce-scatter
    (:func:`_rs_flat_codec`, error-feedback when ``residual`` is given)
    plus a full-precision all-gather, so the wire cost is enc + nbytes
    at every world size. ``residual`` rides in the caller's natural
    shape; divisibility padding is handled here (padding positions
    quantize exactly, so their residual is identically zero and safe to
    trim). Returns ``(reduced, new_residual)`` (``new_residual`` is None
    for non-EF codecs). The output is replicated by construction but not
    VMA-inferrable — same caveat as the ladder builders."""
    flat = x.reshape(-1)
    total = flat.shape[0]
    n = lax.psum(1, axis_name)   # constant-folds inside shard_map
    padded, _ = shard_spec(total, n)
    r = residual.reshape(-1) if residual is not None else None
    if r is not None and padded != total:
        r = jnp.concatenate([r, jnp.zeros((padded - total,), r.dtype)])
    shard, new_r = _rs_flat_codec(flat, r, axis_name, n, op, codec)
    out = lax.all_gather(shard, axis_name, axis=0, tiled=True)
    if out.shape[0] != total:
        out = out[:total]
    out = out.reshape(x.shape)
    if new_r is not None:
        if new_r.shape[0] != total:
            new_r = new_r[:total]
        new_r = new_r.reshape(x.shape)
    return out, new_r

# ---------------------------------------------------------------------------
# Layer 1: in-SPMD primitives (use inside shard_map / pjit-traced code)
# ---------------------------------------------------------------------------


def allreduce_p(x, axis_name: str, op: ReduceOp = ReduceOp.SUM,
                prescale_factor: float = 1.0, postscale_factor: float = 1.0):
    """Allreduce of ``x`` over mesh axis ``axis_name``.

    Average divides by the axis size (reference divisor logic:
    torch/mpi_ops.py:79-103). PRODUCT has no direct XLA primitive; it is an
    all_gather + per-rank multiply (exact for every dtype, incl. integers),
    finalized by a masked psum so the output is provably replicated.
    """
    if op == ReduceOp.AVERAGE and jnp.issubdtype(x.dtype, jnp.integer):
        raise ValueError(
            "Averaging is not supported for integer tensors; use op=Sum "
            "(parity with the reference frontends' integer-average rejection)")
    if prescale_factor != 1.0:
        x = x * prescale_factor
    if op in (ReduceOp.SUM, ReduceOp.AVERAGE):
        out = lax.psum(x, axis_name)
        if op == ReduceOp.AVERAGE:
            out = out / lax.psum(1, axis_name)
    elif op == ReduceOp.MIN:
        out = lax.pmin(x, axis_name)
    elif op == ReduceOp.MAX:
        out = lax.pmax(x, axis_name)
    elif op == ReduceOp.PRODUCT:
        # No XLA product-allreduce primitive: gather the n contributions and
        # multiply — exact for every dtype (incl. integers, which a
        # log-space psum construction would only approximate); keep the
        # input dtype (jnp.prod would promote int8/16 to int32). The masked
        # psum re-broadcast costs one extra collective but makes the result
        # provably replicated for shard_map's VMA checker at EVERY call
        # site (PRODUCT is a rare op).
        prod = jnp.prod(lax.all_gather(x, axis_name, axis=0, tiled=False),
                        axis=0).astype(x.dtype)
        out = broadcast_p(prod, axis_name, 0)
    else:
        raise ValueError(f"unsupported reduce op {op!r} in allreduce_p")
    if postscale_factor != 1.0:
        out = out * postscale_factor
    return out


def hierarchical_allreduce_p(x, local_axis: str, cross_axis: str,
                             op: ReduceOp = ReduceOp.SUM,
                             prescale_factor: float = 1.0,
                             postscale_factor: float = 1.0):
    """Two-level allreduce over a (cross, local) mesh.

    TPU-native rebuild of NCCLHierarchicalAllreduce
    (ops/nccl_operations.cc:180-383): reduce-scatter within the fast
    ``local`` (ICI) axis, allreduce the shards across the slow ``cross``
    (DCN) axis, then all-gather back along ``local`` — cross-axis traffic is
    1/local_size of the naive allreduce, the same bandwidth win as the
    reference's NCCL-ReduceScatter → MPI-Allreduce → NCCL-Allgather ladder.

    Falls back to padding when the leading dim does not divide local_size
    (the local_size-divisible split math at nccl_operations.cc:227-277).
    """
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        # min/max/product have no reduce-scatter decomposition benefit;
        # do the flat two-phase reduce
        out = allreduce_p(x, local_axis, op, prescale_factor, 1.0)
        return allreduce_p(out, cross_axis, op, 1.0, postscale_factor)
    if prescale_factor != 1.0:
        x = x * prescale_factor
    local_size = lax.psum(1, local_axis)
    orig_shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % local_size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    shard = lax.psum_scatter(flat, local_axis, scatter_dimension=0,
                             tiled=True)
    shard = lax.psum(shard, cross_axis)
    out = lax.all_gather(shard, local_axis, axis=0, tiled=True)
    if pad:
        out = out[:n]
    out = out.reshape(orig_shape)
    if op == ReduceOp.AVERAGE:
        out = out / (local_size * lax.psum(1, cross_axis))
    if postscale_factor != 1.0:
        out = out * postscale_factor
    return out


def allgather_p(x, axis_name: str):
    """Concatenate equal-shape per-rank tensors along dim 0 (reference
    allgather semantics, collective_operations.cc:88-195 fast path)."""
    return lax.all_gather(x, axis_name, axis=0, tiled=True)


def broadcast_p(x, axis_name: str, root_rank: int = 0):
    """Broadcast root's tensor to every rank along ``axis_name``.

    Implemented as a masked psum — one collective, no gather of non-root data
    (reference: MPIBroadcast mpi_operations.cc:345 / NCCLBroadcast)."""
    idx = lax.axis_index(axis_name)
    contrib = jnp.where(idx == root_rank, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis_name)


def alltoall_p(x, axis_name: str):
    """Equal-split alltoall: rank r sends slice s of dim 0 to rank s
    (reference: MPIAlltoall mpi_operations.cc:380 with uniform splits)."""
    size = lax.psum(1, axis_name)
    return lax.all_to_all(x.reshape(size, -1, *x.shape[1:]), axis_name,
                          split_axis=0, concat_axis=0, tiled=False).reshape(x.shape)


def hierarchical_alltoall_p(x, axis_name: str, n: int, local_size: int,
                            codec: str = comp.CODEC_NONE):
    """Two-phase equal-split alltoall for a slice-major (cross, local)
    world: same routing result as :func:`alltoall_p`, different wire path.

    Under the :func:`slice_groups` layout (rank r = c*L + l, C = n/L
    slices of L ranks) the payload is viewed as (C, L, m, *s) chunk
    blocks and exchanged in two hops:

    - **phase 1 (ICI)**: an alltoall over each local group along the L
      axis — after it, position ``[c', j]`` holds the chunk local peer
      ``j`` wants delivered to rank ``c'*L + l_me``, i.e. every row
      this rank must forward to slice ``c'`` is now resident as ONE
      contiguous block;
    - **phase 2 (DCN)**: an alltoall over each cross group along the C
      axis — whole slice-blocks transpose across slices, so each DCN
      link carries C-1 blocks of n*m/C rows instead of the flat form's
      n-1 per-rank chunks: O(n/slices) DCN transfers, the quadratic
      DCN-hop fix.

    Pure chunk routing, no arithmetic — the result is bitwise-equal to
    the flat alltoall (codec "none"). ``codec`` encodes ONLY the phase-2
    (DCN) payload — stateless, no error-feedback residual: dispatched
    tokens have no stable step-over-step identity for a residual to
    telescope against (unlike gradient buckets), so the quantization is
    one-shot and the ICI phase stays full precision (the ISSUE 13
    per-link placement rule). With a codec the output is NOT bitwise
    flat-equal. Scales ride a (C,)-gather over the cross group so each
    received block decodes with its sender's scale.
    """
    L = int(local_size)
    C = n // L
    local_groups, cross_groups = slice_groups(n, L)
    m = x.shape[0] // n
    blk = x.reshape(C, L, m, *x.shape[1:])
    # phase 1 — ICI: axis 1 has size L == local group size; tiled=False
    # consumes it and re-inserts the group-size axis in place
    y = lax.all_to_all(blk, axis_name, split_axis=1, concat_axis=1,
                       tiled=False, axis_index_groups=local_groups)
    if codec == comp.CODEC_NONE:
        z = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                           tiled=False, axis_index_groups=cross_groups)
    else:
        payload, scale = comp.encode(y, codec)
        z = lax.all_to_all(payload, axis_name, split_axis=0, concat_axis=0,
                           tiled=False, axis_index_groups=cross_groups)
        if scale is None:   # bf16: plain cast, no scale exchange
            z = comp.decode(z, None, codec, x.dtype)
        else:
            scales = lax.all_gather(scale, axis_name, axis=0, tiled=True,
                                    axis_index_groups=cross_groups)
            z = comp.decode(z, scales.reshape((C,) + (1,) * (z.ndim - 1)),
                            codec, x.dtype)
    return z.reshape(x.shape)


def reducescatter_p(x, axis_name: str, op: ReduceOp = ReduceOp.SUM):
    """Reduce-scatter along dim 0 (NCCL ReduceScatter analog,
    nccl_operations.cc:227-277). Only Sum and Average are defined."""
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(f"reducescatter supports Sum and Average, got {op!r}")
    out = lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)
    if op == ReduceOp.AVERAGE:
        out = out / lax.psum(1, axis_name)
    return out


# ---------------------------------------------------------------------------
# Layer 2: stacked builders for the eager engine
#
# A "stacked" array has global shape (group_size, *tensor_shape) sharded so that
# rank i's tensor lives on device i of the group mesh. The builders return
# jitted callables global-array -> global-array.
# ---------------------------------------------------------------------------


def _shmap(fn, mesh: Mesh, axis: str, in_specs, out_specs, check_vma=True):
    # check_vma=False is needed where the output IS replicated by
    # construction (e.g. a ppermute-pair recursion or a grouped
    # reduce-scatter/all-gather ladder that ends with every rank holding the
    # same value) but shard_map's varying-manual-axes checker cannot infer it.
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=check_vma)


def build_allreduce(mesh: Mesh, axis: str, op: ReduceOp,
                    prescale_factor: float = 1.0, postscale_factor: float = 1.0):
    """Stacked-in, replicated-out allreduce: (n, *s) -> (*s).

    The output is replicated (out_specs=P()) — every rank's addressable shard
    IS the reduced tensor, so extraction is a zero-dispatch shard read (no
    eager slice launch per tensor).
    """
    def body(x):  # x block: (1, *s)
        return allreduce_p(x[0], axis, op, prescale_factor, postscale_factor)

    fn = _shmap(body, mesh, axis, in_specs=P(axis), out_specs=P())
    return jax.jit(fn)


def build_hierarchical_allreduce(mesh: Mesh, axis: str, local_size: int,
                                 op: ReduceOp,
                                 prescale_factor: float = 1.0,
                                 postscale_factor: float = 1.0):
    """Stacked hierarchical allreduce (HOROVOD_HIERARCHICAL_ALLREDUCE,
    reference NCCLHierarchicalAllreduce nccl_operations.cc:180-383 and its
    dispatch at operations.cc:158-202).

    Runs on the same 1-D group mesh as the flat builder; the (cross, local)
    decomposition is expressed with ``axis_index_groups``: reduce-scatter
    within each local (ICI) group, psum across groups (DCN), all-gather back
    — cross traffic shrinks by 1/local_size.

    A world the ``local_size`` does not factorize demotes to the flat
    builder with a one-time WARNING (never an assert): non-divisible
    elastic worlds keep training on the flat ring.
    """
    n = int(mesh.devices.size)
    if validate_algorithm("allreduce", ALGO_HIERARCHICAL, n,
                          local_size) != ALGO_HIERARCHICAL:
        return build_allreduce(mesh, axis, op, prescale_factor,
                               postscale_factor)
    local_groups, cross_groups = slice_groups(n, local_size)

    def body(x):  # x block: (1, *s); output replicated (see build_allreduce)
        v = x[0]
        if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
            return allreduce_p(v, axis, op, prescale_factor, postscale_factor)
        if prescale_factor != 1.0:
            v = v * prescale_factor
        orig_shape = v.shape
        flat = v.reshape(-1)
        pad = (-flat.shape[0]) % n
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        # full reduce-scatter → reduce-scatter → all-gather → all-gather
        # ladder: local RS (ICI), cross RS+AG (DCN at 1/local_size volume),
        # local AG (ICI) — the reference's RS→AR→AG with the cross AR itself
        # split into RS+AG
        shard = lax.psum_scatter(flat, axis, scatter_dimension=0, tiled=True,
                                 axis_index_groups=local_groups)
        shard = lax.psum_scatter(shard, axis, scatter_dimension=0, tiled=True,
                                 axis_index_groups=cross_groups)
        out = lax.all_gather(shard, axis, axis=0, tiled=True,
                             axis_index_groups=cross_groups)
        out = lax.all_gather(out, axis, axis=0, tiled=True,
                             axis_index_groups=local_groups)
        if pad:
            out = out[:flat.shape[0] - pad]
        out = out.reshape(orig_shape)
        if op == ReduceOp.AVERAGE:
            out = out / n
        if postscale_factor != 1.0:
            out = out * postscale_factor
        return out

    fn = _shmap(body, mesh, axis, in_specs=P(axis), out_specs=P(),
                check_vma=False)
    return jax.jit(fn)


def build_tree_allreduce(mesh: Mesh, axis: str, op: ReduceOp,
                         prescale_factor: float = 1.0,
                         postscale_factor: float = 1.0):
    """Stacked recursive-doubling allreduce (the tree form
    :func:`choose_algorithm` picks for latency-bound small buckets):
    log2(n) pairwise psum rounds instead of the ring's 2(n-1) steps.
    Non-power-of-2 worlds demote to the flat builder with a one-time
    WARNING; MIN/MAX/PRODUCT ops take the flat reduction inside the same
    program (the tree decomposition is additive-only)."""
    n = int(mesh.devices.size)
    if validate_algorithm("allreduce", ALGO_TREE, n, 0) != ALGO_TREE:
        return build_allreduce(mesh, axis, op, prescale_factor,
                               postscale_factor)
    reduce_flat = _make_reduce_flat(axis, op, n, 0, ALGO_TREE)

    def body(x):  # x block: (1, *s); output replicated by construction
        v = x[0]
        if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
            return allreduce_p(v, axis, op, prescale_factor,
                               postscale_factor)
        if prescale_factor != 1.0:
            v = v * prescale_factor
        out = reduce_flat(v.reshape(-1)).reshape(v.shape)
        if postscale_factor != 1.0:
            out = out * postscale_factor
        return out

    # pair-group psums are replicated after the last round but the VMA
    # checker cannot infer replication across partial groups
    fn = _shmap(body, mesh, axis, in_specs=P(axis), out_specs=P(),
                check_vma=False)
    return jax.jit(fn)


def build_hierarchical_allgather(mesh: Mesh, axis: str, local_size: int):
    """Two-level stacked allgather (HOROVOD_HIERARCHICAL_ALLGATHER; reference
    MPIHierarchicalAllgather mpi_operations.cc:178: node-local gather through
    a shared-memory window, then a cross-node exchange of whole node blocks).

    TPU-native: gather along the fast local (ICI) sub-groups first, then
    gather the resulting node blocks along the cross (DCN) sub-groups — the
    slow links carry whole node blocks once instead of participating in the
    full-world ring. Group ranges are contiguous, so block order equals rank
    order and the result matches the flat allgather exactly.

    A world the ``local_size`` does not factorize demotes to the flat
    builder with a one-time WARNING (never an assert).
    """
    n = int(mesh.devices.size)
    if validate_algorithm("allgather", ALGO_HIERARCHICAL, n,
                          local_size) != ALGO_HIERARCHICAL:
        return build_allgather(mesh, axis)
    local_groups, cross_groups = slice_groups(n, local_size)

    def body(x):  # (1, d0, *s)
        local_block = lax.all_gather(x[0], axis, axis=0, tiled=True,
                                     axis_index_groups=local_groups)
        return lax.all_gather(local_block, axis, axis=0, tiled=True,
                              axis_index_groups=cross_groups)

    fn = _shmap(body, mesh, axis, in_specs=P(axis), out_specs=P(),
                check_vma=False)
    return jax.jit(fn)


def build_allgather(mesh: Mesh, axis: str):
    """Stacked-in, replicated-out allgather of equal-shape tensors:
    (n, d0, *s) -> (n*d0, *s) (every rank ends with the concatenation along
    dim 0 — identical everywhere, hence replicated output)."""
    def body(x):  # (1, d0, *s)
        return allgather_p(x[0], axis)

    # all_gather output is identical on every rank but not VMA-inferrable
    fn = _shmap(body, mesh, axis, in_specs=P(axis), out_specs=P(),
                check_vma=False)
    return jax.jit(fn)


def build_broadcast(mesh: Mesh, axis: str, root_rank: int):
    def body(x):
        return broadcast_p(x[0], axis, root_rank)

    fn = _shmap(body, mesh, axis, in_specs=P(axis), out_specs=P())
    return jax.jit(fn)


def build_broadcast_flagged(mesh: Mesh, axis: str, root_rank: int):
    """Broadcast that also returns the ROOT's active bit, in the same launch.

    Join-protocol support without a blocking pre-dispatch check (VERDICT r3
    item 2): a joined root dispatches its zero substitute with active=0; the
    receivers' extract reads the flag and raises instead of silently
    consuming zeros. The collective always matches (nothing hangs), and the
    active path pays no host round-trip at submission — the reference gets
    the same guarantee from its blocking negotiation phase
    (operations.cc:1004-1040 joined-root error)."""
    def body(x, a):  # x: (1, *s), a: (1,)
        return (broadcast_p(x[0], axis, root_rank),
                broadcast_p(a[0], axis, root_rank))

    fn = _shmap(body, mesh, axis, in_specs=(P(axis), P(axis)),
                out_specs=(P(), P()))
    return jax.jit(fn)


def build_alltoall(mesh: Mesh, axis: str):
    """Stacked equal-split alltoall: (n, d0, *s) -> (n, d0, *s), d0 % n == 0."""
    def body(x):
        return alltoall_p(x[0], axis)[None]

    fn = _shmap(body, mesh, axis, in_specs=P(axis), out_specs=P(axis))
    return jax.jit(fn)


def _a2a_pack(tensors, n: int):
    """View each (d0_i, *s_i) dispatch tensor as its (n, w_i) chunk matrix
    (row j = this rank's chunk bound for rank j) and concatenate the rows:
    the fusion pack for an alltoall bucket. Returns ``(packed, widths)``."""
    parts = [t.reshape(n, -1) for t in tensors]
    widths = [p.shape[1] for p in parts]
    packed = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return packed, widths


def _a2a_exchange(packed, axis: str, n: int, local_size: int, algo, codec):
    """One bucket's wire exchange: the per-bucket algo dispatch shared by
    the grouped builder and the replay "a2a" segment (``algo`` must be
    pre-validated; None means flat)."""
    if algo == ALGO_HIERARCHICAL:
        return hierarchical_alltoall_p(packed, axis, n, local_size, codec)
    return alltoall_p(packed, axis)


def build_hierarchical_alltoall(mesh: Mesh, axis: str, local_size: int,
                                codec: str = comp.CODEC_NONE):
    """Stacked two-level alltoall (:func:`hierarchical_alltoall_p`):
    (n, d0, *s) -> (n, d0, *s), d0 % n == 0, identical routing result to
    :func:`build_alltoall` with the DCN hop count cut to O(n/slices).
    ``codec`` encodes the phase-2 (DCN) leg only — stateless, no
    residual (see the primitive's docstring). A world the ``local_size``
    does not factorize demotes to the flat builder with a one-time
    WARNING (never an assert)."""
    n = int(mesh.devices.size)
    if validate_algorithm("alltoall", ALGO_HIERARCHICAL, n,
                          local_size) != ALGO_HIERARCHICAL:
        return build_alltoall(mesh, axis)

    def body(x):  # (1, d0, *s); output varies per rank like the flat form
        return hierarchical_alltoall_p(x[0], axis, n, local_size, codec)[None]

    # sub-group exchanges defeat the VMA checker's inference; the output
    # claims exactly what the flat builder's does (per-rank varying)
    fn = _shmap(body, mesh, axis, in_specs=P(axis), out_specs=P(axis),
                check_vma=False)
    return jax.jit(fn)


def build_grouped_alltoall(mesh: Mesh, axis: str, shapes, dtypes, buckets,
                           local_size: int = 0,
                           algos: Optional[Sequence[str]] = None,
                           codecs: Optional[Sequence[str]] = None):
    """ONE launch for a whole fusion group of same-shaped(-enough)
    alltoall dispatch tensors — the alltoall analog of
    :func:`build_grouped_allreduce`, closing the last fusion-bucketing
    gap in the engine's op surface. Per bucket: every member tensor
    (d0_i, *s_i) with d0_i % n == 0 is viewed as its (n, w_i) chunk
    matrix (row j = the chunk bound for rank j) and the rows are
    concatenated to ONE (n, R_b) buffer — a single whole-bucket exchange
    replaces len(bucket) wire launches, then per-tensor columns unpack.
    Chunk-matrix packing keeps per-destination data contiguous, so the
    pack IS the fusion: no per-destination re-gather inside the
    exchange.

    ``algos``/``codecs`` follow the grouped-allreduce per-bucket
    convention: algo None resolves flat, hierarchical takes the
    :func:`hierarchical_alltoall_p` two-phase path (invalid forcings
    demote with a one-time WARNING), and the codec applies to the DCN
    leg of hierarchical buckets only — a flat bucket ignores its codec
    (there is no slow-link leg to encode; the ISSUE 13 placement rule,
    not an oversight)."""
    _check_bucket_dtypes(dtypes, buckets)
    n = int(mesh.devices.size)
    if algos is None:
        algos = (None,) * len(buckets)
    algos = tuple(
        validate_algorithm("alltoall", a if a is not None else ALGO_FLAT,
                           n, local_size)
        for a in algos)
    if codecs is None:
        codecs = (comp.CODEC_NONE,) * len(buckets)
    codecs = tuple(codecs)

    def body(*xs):  # per tensor: (1, d0_i, *s_i)
        outs = [None] * len(shapes)
        for b, idxs in enumerate(buckets):
            packed, widths = _a2a_pack([xs[i][0] for i in idxs], n)
            out = _a2a_exchange(packed, axis, n, local_size, algos[b],
                                codecs[b])
            off = 0
            for i, w in zip(idxs, widths):
                outs[i] = out[:, off:off + w].reshape(shapes[i])[None]
                off += w
        return tuple(outs)

    fn = _shmap(body, mesh, axis,
                in_specs=tuple(P(axis) for _ in shapes),
                out_specs=tuple(P(axis) for _ in shapes),
                check_vma=False)
    return jax.jit(fn)


def build_reducescatter(mesh: Mesh, axis: str, op: ReduceOp = ReduceOp.SUM,
                        pad_rows: int = 0):
    """Stacked reduce-scatter: (n, d0, *s) -> (n, ceil(d0/n), *s).

    ``pad_rows`` zero-pads dim 0 inside the program so totals that do not
    divide the world size still reduce exactly (the allgather inverse:
    concatenating every rank's trimmed shard reproduces the full reduced
    tensor). The caller slices the trailing ranks' shards back to their
    real row counts (engine.reducescatter extract)."""
    def body(x):
        v = x[0]
        if pad_rows:
            v = jnp.pad(v, [(0, pad_rows)] + [(0, 0)] * (v.ndim - 1))
        return reducescatter_p(v, axis, op)[None]

    fn = _shmap(body, mesh, axis, in_specs=P(axis), out_specs=P(axis))
    return jax.jit(fn)


def _resolve_reduce_algo(algo: Optional[str], n: int,
                         local_size: int) -> str:
    """Normalize a builder's reduction-algorithm request. ``None`` keeps
    the legacy contract (``local_size > 1`` selects hierarchical, flat
    otherwise); explicit algorithms are validated and demoted — never
    asserted — so non-divisible worlds and invalid forcings compile the
    flat program with a one-time WARNING."""
    if algo is None:
        algo = ALGO_HIERARCHICAL if local_size > 1 else ALGO_FLAT
    return validate_algorithm("allreduce", algo, n, local_size)


def _make_reduce_flat(axis: str, op: ReduceOp, n: int, local_size: int,
                      algo: Optional[str] = None):
    """Flat-buffer reduction closure shared by the fused-bucket builders,
    per algorithm:

    - ``flat``: one whole-world psum (XLA's ring);
    - ``tree``: log2(n) pairwise psum rounds (recursive doubling) — the
      latency-bound small-bucket form;
    - ``hierarchical``: RS/RS/AG/AG ladder over node-local + cross
      replica groups (reference NCCLHierarchicalAllreduce,
      nccl_operations.cc:180-383).

    ``algo=None`` preserves the legacy selection (hierarchical iff
    ``local_size > 1``). Non-SUM/AVERAGE ops always take the flat path —
    tree/hierarchical decompositions only pay for (and are only defined
    over) the additive reductions.
    """
    algo = _resolve_reduce_algo(algo, n, local_size)
    if algo == ALGO_HIERARCHICAL:
        local_groups, cross_groups = slice_groups(n, local_size)
    elif algo == ALGO_TREE:
        rounds = tree_groups(n)

    def _reduce_flat(flat):
        if op not in (ReduceOp.SUM, ReduceOp.AVERAGE) or algo == ALGO_FLAT:
            return allreduce_p(flat, axis, op, 1.0, 1.0)
        if algo == ALGO_TREE:
            out = flat
            for groups in rounds:
                out = lax.psum(out, axis, axis_index_groups=groups)
            if op == ReduceOp.AVERAGE:
                out = out / n
            return out
        pad = (-flat.shape[0]) % n
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        shard = lax.psum_scatter(flat, axis, scatter_dimension=0, tiled=True,
                                 axis_index_groups=local_groups)
        shard = lax.psum_scatter(shard, axis, scatter_dimension=0, tiled=True,
                                 axis_index_groups=cross_groups)
        out = lax.all_gather(shard, axis, axis=0, tiled=True,
                             axis_index_groups=cross_groups)
        out = lax.all_gather(out, axis, axis=0, tiled=True,
                             axis_index_groups=local_groups)
        if pad:
            out = out[:-pad]
        if op == ReduceOp.AVERAGE:
            out = out / n
        return out

    return _reduce_flat


def _resolved_bucket_algos(n: int, local_size: int, algos,
                           n_buckets: int) -> tuple:
    """Per-bucket resolved algorithm list for a grouped reduce builder:
    ``algos=None`` resolves every bucket through the legacy local_size
    rule; explicit entries are validated (demoted, never asserted)."""
    if algos is None:
        algos = (None,) * n_buckets
    return tuple(_resolve_reduce_algo(a, n, local_size) for a in algos)


def _wrap_plain_reducer(fn):
    """Lift a plain ``reduce(flat)`` closure onto the uniform codec-aware
    signature ``reduce(flat, residual) -> (out, new_residual)``."""
    def _reduce(flat, residual=None):
        return fn(flat), None
    return _reduce


def _bucket_reducers(axis: str, op: ReduceOp, n: int, local_size: int,
                     algos, n_buckets: int, codecs=None) -> list:
    """One flat-buffer reduction closure per bucket, memoized per resolved
    (algorithm, codec) pair (buckets sharing both share the closure — and
    the replica-group tables it captures). Every closure has the uniform
    signature ``reduce(flat, residual) -> (out, new_residual)``; plain
    (codec "none") reducers ignore the residual and return None for it."""
    resolved = _resolved_bucket_algos(n, local_size, algos, n_buckets)
    if codecs is None:
        codecs = (comp.CODEC_NONE,) * n_buckets
    cache: dict = {}
    out = []
    for a, c in zip(resolved, codecs):
        key = (a, c)
        if key not in cache:
            if c == comp.CODEC_NONE:
                cache[key] = _wrap_plain_reducer(
                    _make_reduce_flat(axis, op, n, local_size, a))
            else:
                cache[key] = _make_codec_reducer(axis, op, n, local_size,
                                                 a, c)
        out.append(cache[key])
    return out


def build_fused_allreduce(mesh: Mesh, axis: str, op: ReduceOp,
                          shapes, dtype,
                          prescale_factor: float = 1.0,
                          postscale_factor: float = 1.0,
                          local_size: int = 0,
                          algo: Optional[str] = None,
                          codec: str = comp.CODEC_NONE):
    """One-launch fused bucket allreduce: takes the stacked *packed* buffer
    (n, total) and returns one stacked (n, *shape_i) array per bucket member,
    reduced — pack→collective→unpack in a single jitted program (the whole
    point of the reference's fusion buffer, collective_operations.cc:38-82:
    one launch and no per-tensor host round-trips).

    ``local_size > 0`` selects the hierarchical ladder (reference
    NCCLHierarchicalAllreduce nccl_operations.cc:180-383) on the packed
    buffer; 0 = flat psum. ``algo`` (ISSUE 10) overrides that legacy
    rule with an explicit flat/tree/hierarchical choice from
    :func:`choose_algorithm`. ``codec`` (ISSUE 13) encodes the slow leg
    (error-feedback codecs append a residual input after the packed
    buffer and a new-residual output after the pieces).
    """
    n = int(mesh.devices.size)
    sizes = [math.prod(s) for s in shapes]
    resolved = _resolve_reduce_algo(algo, n, local_size)
    (_reduce,) = _bucket_reducers(axis, op, n, local_size, (algo,), 1,
                                  (codec,))
    ef = codec in comp.EF_CODECS

    def body(x, *res):  # x block: (1, total) [+ EF residual]
        flat = x[0]
        if prescale_factor != 1.0:
            flat = flat * prescale_factor
        out, new_res = _reduce(flat, res[0] if ef else None)
        if postscale_factor != 1.0:
            out = out * postscale_factor
        pieces = []
        offset = 0
        for shape, size in zip(shapes, sizes):
            pieces.append(
                lax.dynamic_slice_in_dim(out, offset, size).reshape(shape))
            offset += size
        return tuple(pieces) + ((new_res,) if ef else ())

    fn = _shmap(body, mesh, axis,
                in_specs=(P(axis),) + ((P(),) if ef else ()),
                out_specs=tuple(P() for _ in shapes)
                + ((P(),) if ef else ()),
                check_vma=(resolved == ALGO_FLAT
                           and codec == comp.CODEC_NONE))
    return jax.jit(fn)


def build_codec_allreduce(mesh: Mesh, axis: str, op: ReduceOp, shape,
                          dtype, algo: str, codec: str,
                          prescale_factor: float = 1.0,
                          postscale_factor: float = 1.0,
                          local_size: int = 0):
    """Stacked single-tensor compressed allreduce (the eager
    ``Engine.allreduce`` path when a wire codec is live): flatten, run
    the codec reducer (hierarchical = DCN-leg encoded, otherwise whole
    payload), reshape. Error-feedback codecs take the rank-local
    residual as a second (world-view) input and return the new residual
    after the reduced tensor."""
    n = int(mesh.devices.size)
    (_reduce,) = _bucket_reducers(axis, op, n, local_size, (algo,), 1,
                                  (codec,))
    ef = codec in comp.EF_CODECS

    def body(x, *res):  # x block: (1, *s) [+ EF residual]
        v = x[0]
        flat = v.reshape(-1)
        if prescale_factor != 1.0:
            flat = flat * prescale_factor
        out, new_res = _reduce(flat, res[0] if ef else None)
        if postscale_factor != 1.0:
            out = out * postscale_factor
        out = out.reshape(v.shape)
        return (out, new_res) if ef else out

    fn = _shmap(body, mesh, axis,
                in_specs=(P(axis),) + ((P(),) if ef else ()),
                out_specs=(P(), P()) if ef else P(),
                check_vma=False)
    return jax.jit(fn)


def build_pack_group(buckets):
    """Jitted whole-group pack: all N local tensors in, one flat buffer
    PER BUCKET out — each already shaped (1, total_b), so the caller's
    lift to a stacked global array is pure metadata (no eager reshape
    dispatch per tensor, the r4 eager path's hidden cost: ~2 launches
    per leaf). Shapes/dtypes come from
    the traced arguments; the caller's builder-cache key carries them for
    memoization."""
    def f(*ts):
        outs = []
        for idxs in buckets:
            outs.append(jnp.concatenate(
                [jnp.ravel(ts[i]) for i in idxs])[None])
        return tuple(outs)

    return jax.jit(f)


def _check_bucket_dtypes(dtypes, buckets):
    """Per-bucket dtype uniformity is the ``bucket_by_size`` contract the
    packed-buffer math relies on (a mixed-dtype concat would silently
    promote); assert it here so a hand-rolled bucket list fails loudly."""
    for idxs in buckets:
        kinds = {str(dtypes[i]) for i in idxs}
        if len(kinds) > 1:
            raise ValueError(
                f"fusion bucket {list(idxs)} mixes dtypes {sorted(kinds)}; "
                f"buckets must be dtype-uniform (bucket_by_size contract)")


def build_grouped_allreduce(mesh: Mesh, axis: str, op: ReduceOp,
                            shapes, dtypes, buckets,
                            prescale_factor: float = 1.0,
                            postscale_factor: float = 1.0,
                            local_size: int = 0,
                            pipeline: bool = False,
                            algos: Optional[Sequence[str]] = None,
                            codecs: Optional[Sequence[str]] = None):
    """ONE launch for the whole grouped reduce+unpack: the per-bucket
    packed buffers (from :func:`build_pack_group`, stacked (n, total_b))
    go in, every reduced tensor of the group comes out — one collective
    per bucket inside a single program (XLA's combiner may merge further).
    This is the eager hot path's dispatch-count lever (VERDICT r4 weak
    #1): the whole grouped allreduce is pack(1 dispatch) +
    reduce+unpack(1 dispatch), where the per-bucket form cost 2·n_buckets
    launches. Mirrors the reference's one fused launch per
    cycle (operations.cc:566-616).

    Args:
      shapes/dtypes: per-tensor, in group order.
      buckets: list of index lists partitioning range(len(shapes)),
        same-dtype within a bucket (bucket_by_size output).
      pipeline: issue every bucket's collective back-to-back BEFORE any
        unpack is traced (ISSUE 6 overlap): the serial form interleaves
        bucket i's unpack between bucket i's reduce and bucket i+1's
        reduce, so an in-order scheduler must drain reduce(i) before it
        can issue anything of bucket i+1; the pipelined trace order
        (scale..., reduce..., unpack...) leaves the collectives mutually
        independent and adjacent, which is what XLA's async-collective
        conversion / latency-hiding scheduler overlaps.
      algos: per-bucket algorithm ("flat"/"tree"/"hierarchical") from
        :func:`choose_algorithm` (ISSUE 10); None = the legacy local_size
        rule for every bucket. The small latency-bound bucket of a step
        can lower to the tree form while its big bucket takes the
        hierarchical ladder, in the SAME program.
      codecs: per-bucket wire codec ("none"/"bf16"/"fp8"/"int8", ISSUE
        13); None = "none" everywhere. Error-feedback buckets grow the
        program's I/O: one rank-local residual buffer per EF bucket is
        appended AFTER the packed inputs (world-view lifted, the state-
        leaf convention) and the matching new residuals come back after
        the tensor outputs, in bucket order.
    """
    _check_bucket_dtypes(dtypes, buckets)
    n = int(mesh.devices.size)
    if codecs is None:
        codecs = (comp.CODEC_NONE,) * len(buckets)
    codecs = tuple(codecs)
    reducers = _bucket_reducers(axis, op, n, local_size, algos,
                                len(buckets), codecs)
    resolved = _resolved_bucket_algos(n, local_size, algos, len(buckets))
    ef_buckets = tuple(b for b in range(len(buckets))
                       if codecs[b] in comp.EF_CODECS)
    sizes = [math.prod(s) for s in shapes]

    def body(*args):  # per-bucket blocks (1, total_b) [+ EF residuals]
        packed = args[:len(buckets)]
        residuals = {b: args[len(buckets) + i]
                     for i, b in enumerate(ef_buckets)}
        outs = [None] * len(shapes)
        new_res: dict = {}

        def _reduce(b, flat):
            out, nr = reducers[b](flat, residuals.get(b))
            if b in residuals:
                new_res[b] = nr
            return out

        if pipeline:
            flats = []
            for b in range(len(buckets)):
                flat = packed[b][0]
                if prescale_factor != 1.0:
                    flat = flat * prescale_factor
                flats.append(flat)
            reds = [_reduce(b, f) for b, f in enumerate(flats)]
            if postscale_factor != 1.0:
                reds = [r * postscale_factor for r in reds]
            for b, idxs in enumerate(buckets):
                _unpack_flat(reds[b], shapes, sizes, idxs, outs)
            return tuple(outs) + tuple(new_res[b] for b in ef_buckets)
        for b, idxs in enumerate(buckets):
            flat = packed[b][0]
            if prescale_factor != 1.0:
                flat = flat * prescale_factor
            red = _reduce(b, flat)
            if postscale_factor != 1.0:
                red = red * postscale_factor
            offset = 0
            for i in idxs:
                outs[i] = lax.dynamic_slice_in_dim(
                    red, offset, sizes[i]).reshape(shapes[i])
                offset += sizes[i]
        return tuple(outs) + tuple(new_res[b] for b in ef_buckets)

    fn = _shmap(body, mesh, axis,
                in_specs=tuple(P(axis) for _ in buckets)
                + tuple(P() for _ in ef_buckets),
                out_specs=tuple(P() for _ in shapes)
                + tuple(P() for _ in ef_buckets),
                check_vma=(all(a == ALGO_FLAT for a in resolved)
                           and not any(c != comp.CODEC_NONE
                                       for c in codecs)))
    return jax.jit(fn)


def build_fused_broadcast(mesh: Mesh, axis: str, root_rank: int, shapes,
                          dtype):
    """One-launch fused bucket broadcast: the stacked packed buffer
    (n, total) plus the active bit -> one stacked (*shape_i) array per
    bucket member and the root's active flag, all from a single launch
    (the fusion-buffer treatment applied to broadcast_parameters' init
    storm — N leaves, one collective per dtype bucket, ONE flag read)."""
    sizes = [math.prod(s) for s in shapes]

    def body(x, a):  # x: (1, total), a: (1, 1)
        out = broadcast_p(x[0], axis, root_rank)
        flag = broadcast_p(a[0], axis, root_rank)
        pieces = []
        offset = 0
        for shape, size in zip(shapes, sizes):
            pieces.append(
                lax.dynamic_slice_in_dim(out, offset, size).reshape(shape))
            offset += size
        return tuple(pieces) + (flag,)

    fn = _shmap(body, mesh, axis, in_specs=(P(axis), P(axis)),
                out_specs=tuple(P() for _ in shapes) + (P(),))
    return jax.jit(fn)


def build_pack(shapes, dtype):
    """Jitted pack: N local tensors -> one flat buffer (single dispatch)."""
    def f(*ts):
        return jnp.concatenate([jnp.ravel(t) for t in ts]) if ts \
            else jnp.zeros((0,), dtype)
    return jax.jit(f)


# ---------------------------------------------------------------------------
# ZeRO-1 sharded gradient sync: grouped reduce-scatter / allgather builders
# ---------------------------------------------------------------------------


def shard_spec(total: int, n: int) -> tuple:
    """Shard assignment for a flat bucket of ``total`` elements over ``n``
    ranks: returns ``(padded, shard)`` with ``padded = shard * n`` and
    ``shard = ceil(total / n)`` — rank r owns the contiguous slice
    ``[r*shard, (r+1)*shard)`` of the zero-padded buffer. Padding keeps the
    reduce-scatter/allgather pair exact for bucket totals that do not
    divide the world size (ZeRO-1, Rajbhandari et al. 2020 §5.1)."""
    shard = -(-int(total) // int(n)) if n > 0 else int(total)
    return shard * n, shard


def _rs_flat(flat, axis: str, n: int, op: ReduceOp):
    """Reduce-scatter a flat buffer: pad to divisibility, psum_scatter, and
    return this rank's shard (shape ``(ceil(len/n),)``). Sum/Average only —
    the same op restriction as :func:`reducescatter_p`."""
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(
            f"reducescatter supports Sum and Average, got {op!r}")
    padded, _ = shard_spec(flat.shape[0], n)
    pad = padded - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    shard = lax.psum_scatter(flat, axis, scatter_dimension=0, tiled=True)
    if op == ReduceOp.AVERAGE:
        shard = shard / n
    return shard


def _rs_flat_codec(flat, residual, axis: str, n: int, op: ReduceOp,
                   codec: str):
    """Compressed flat reduce-scatter (the ZeRO-1 gradient leg, ISSUE 13):
    the codec is applied PRE-scatter — each rank encodes its whole padded
    contribution (error-feedback: quantize(flat + residual)) — and the
    exchange is an all-to-all of encoded chunks: rank r still receives
    exactly chunk r of every peer's buffer, so the shard-ownership
    invariant (:func:`shard_spec`: rank r owns contiguous chunk r) is
    untouched; the received contributions are decoded rank-locally with
    their senders' scales and summed in float32. Same shard out as
    :func:`_rs_flat`, 1/ratio of the wire bytes. Returns ``(shard,
    new_residual)``."""
    if op not in (ReduceOp.SUM, ReduceOp.AVERAGE):
        raise ValueError(
            f"reducescatter supports Sum and Average, got {op!r}")
    padded, shard_len = shard_spec(flat.shape[0], n)
    pad = padded - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    payload, scale, new_res = comp.ef_encode(flat, residual, codec)
    chunks = payload.reshape(n, shard_len)
    # row j of the result is rank j's chunk for THIS rank (alltoall_p's
    # split/concat convention) — chunk ownership is positional, exactly
    # the flat ring's
    recv = lax.all_to_all(chunks, axis, split_axis=0, concat_axis=0,
                          tiled=False)
    scales = None
    if scale is not None:
        scales = lax.all_gather(scale, axis, axis=0, tiled=False)
    shard = comp.decode_sum(recv, scales, codec, flat.dtype)
    if op == ReduceOp.AVERAGE:
        shard = shard / n
    return shard, new_res


def _ag_flat(shard, axis: str, total: int, algo: str = ALGO_FLAT,
             n: int = 0, local_size: int = 0):
    """Inverse of :func:`_rs_flat`: all-gather the per-rank shards and trim
    the divisibility padding back off.

    ``algo="hierarchical"`` gathers in two levels — intra-slice (ICI)
    first, then whole slice blocks across slices (DCN) — so the slow
    fabric carries each byte once in contiguous blocks (reference
    MPIHierarchicalAllgather, mpi_operations.cc:178). Because the flat
    shard convention assigns rank r contiguous chunk r and slice rank
    blocks are contiguous, the local gather yields exactly slice c's
    block and the cross gather concatenates blocks in rank order — the
    result is bit-identical to the flat gather."""
    if algo == ALGO_HIERARCHICAL and validate_algorithm(
            "allgather", ALGO_HIERARCHICAL, n, local_size) \
            == ALGO_HIERARCHICAL:
        local_groups, cross_groups = slice_groups(n, local_size)
        full = lax.all_gather(shard, axis, axis=0, tiled=True,
                              axis_index_groups=local_groups)
        full = lax.all_gather(full, axis, axis=0, tiled=True,
                              axis_index_groups=cross_groups)
    else:
        full = lax.all_gather(shard, axis, axis=0, tiled=True)
    if full.shape[0] != total:
        full = full[:total]
    return full


def _unpack_flat(flat, shapes, sizes, idxs, outs):
    offset = 0
    for i in idxs:
        outs[i] = lax.dynamic_slice_in_dim(
            flat, offset, sizes[i]).reshape(shapes[i])
        offset += sizes[i]


def build_grouped_reducescatter(mesh: Mesh, axis: str, op: ReduceOp,
                                shapes, dtypes, buckets,
                                prescale_factor: float = 1.0,
                                postscale_factor: float = 1.0,
                                pipeline: bool = False,
                                algos: Optional[Sequence[str]] = None):
    """ONE launch for a whole grouped reduce-scatter: the per-bucket packed
    buffers (from :func:`build_pack_group`, stacked (n, total_b)) go in, one
    stacked (n, shard_b) array per bucket comes out — rank r's addressable
    slice is its reduced shard of the bucket. The sharded-gradient-sync
    sibling of :func:`build_grouped_allreduce`: same bytes on the wire as
    the allreduce (an allreduce IS reduce-scatter + allgather), but the
    caller keeps only 1/n of the reduced elements, which is what lets the
    optimizer update and its state shrink by the world size (ZeRO-1).
    Bucket totals need not divide n — shards are over the zero-padded
    buffer (:func:`shard_spec`). ``pipeline=True`` traces every bucket's
    scale before any reduce-scatter so the collectives issue back-to-back
    (overlap-ready, ISSUE 6).

    ``algos`` is accepted for selection-layer symmetry (ISSUE 10) but the
    scatter itself is ALWAYS the flat ring: the shard-ownership
    convention (rank r owns contiguous chunk r — what ZeRO-1 state
    shapes, checkpoints, and reshard all key on) is incompatible with a
    two-level scatter's chunk permutation; non-flat entries demote with
    a one-time WARNING (see :func:`validate_algorithm`)."""
    _check_bucket_dtypes(dtypes, buckets)
    n = int(mesh.devices.size)
    if algos is not None:
        for a in algos:
            validate_algorithm("reducescatter", a, n, 0)

    def body(*packed):  # per-bucket blocks (1, total_b)
        outs = []
        if pipeline:
            flats = []
            for b in range(len(buckets)):
                flat = packed[b][0]
                if prescale_factor != 1.0:
                    flat = flat * prescale_factor
                flats.append(flat)
            shards = [_rs_flat(f, axis, n, op) for f in flats]
            if postscale_factor != 1.0:
                shards = [s * postscale_factor for s in shards]
            return tuple(s[None] for s in shards)
        for b, idxs in enumerate(buckets):
            flat = packed[b][0]
            if prescale_factor != 1.0:
                flat = flat * prescale_factor
            shard = _rs_flat(flat, axis, n, op)
            if postscale_factor != 1.0:
                shard = shard * postscale_factor
            outs.append(shard[None])
        return tuple(outs)

    fn = _shmap(body, mesh, axis,
                in_specs=tuple(P(axis) for _ in buckets),
                out_specs=tuple(P(axis) for _ in buckets))
    return jax.jit(fn)


def build_grouped_allgather(mesh: Mesh, axis: str, shapes, dtypes, buckets,
                            pipeline: bool = False,
                            local_size: int = 0,
                            algos: Optional[Sequence[str]] = None):
    """Inverse of :func:`build_grouped_reducescatter` and the return leg of
    the sharded optimizer step: per-bucket stacked shards (n, shard_b) in,
    every tensor of the group out — replicated, unpacked to its natural
    shape, padding trimmed. One all-gather per bucket in a single
    program. ``pipeline=True`` issues every bucket's all-gather before any
    unpack is traced (bucket i's unpack no longer interposes between
    gather i and gather i+1 — overlap-ready, ISSUE 6); this is also the
    program the ZeRO-1 prefetch leg launches under the step's tail.
    ``algos`` selects flat vs the two-level hierarchical gather per
    bucket (ISSUE 10; order-preserving, see :func:`_ag_flat`)."""
    _check_bucket_dtypes(dtypes, buckets)
    n = int(mesh.devices.size)
    if algos is None:
        algos = (ALGO_FLAT,) * len(buckets)
    algos = tuple(validate_algorithm("allgather", a, n, local_size)
                  for a in algos)
    sizes = [math.prod(s) for s in shapes]
    totals = [sum(sizes[i] for i in idxs) for idxs in buckets]

    def body(*shards):  # per-bucket blocks (1, shard_b)
        outs = [None] * len(shapes)
        if pipeline:
            fulls = [_ag_flat(shards[b][0], axis, totals[b], algos[b],
                              n, local_size)
                     for b in range(len(buckets))]
            for b, idxs in enumerate(buckets):
                _unpack_flat(fulls[b], shapes, sizes, idxs, outs)
            return tuple(outs)
        for b, idxs in enumerate(buckets):
            full = _ag_flat(shards[b][0], axis, totals[b], algos[b],
                            n, local_size)
            _unpack_flat(full, shapes, sizes, idxs, outs)
        return tuple(outs)

    # gathered outputs are identical on every rank but not VMA-inferrable
    fn = _shmap(body, mesh, axis,
                in_specs=tuple(P(axis) for _ in buckets),
                out_specs=tuple(P() for _ in shapes),
                check_vma=False)
    return jax.jit(fn)


def _check_state_leaves(state, new_state):
    """Trace-time shape/dtype stability contract shared by the fused and
    split ZeRO-1 step builders."""
    if len(new_state) != len(state):
        raise ValueError(
            f"sharded update changed the state leaf count "
            f"({len(state)} -> {len(new_state)})")
    for old, new in zip(state, new_state):
        if old.shape != new.shape or old.dtype != new.dtype:
            raise ValueError(
                f"sharded update changed a state leaf's shape/dtype "
                f"({old.shape}/{old.dtype} -> {new.shape}/{new.dtype}); "
                f"shard-local state must be shape-stable")


def build_sharded_update(mesh: Mesh, axis: str, op: ReduceOp,
                         shapes, dtypes, buckets,
                         state_shapes, state_dtypes, update,
                         prescale_factor: float = 1.0,
                         postscale_factor: float = 1.0,
                         packed: bool = True,
                         codecs: Optional[Sequence[str]] = None):
    """The FIRST pipeline stage of a split ZeRO-1 step (ISSUE 6 prefetch):
    reduce-scatter every gradient bucket (issued back-to-back, no unpack
    interposing) and run ``update`` on this rank's shards — but do NOT
    all-gather. Outputs are the per-bucket *stacked* updated-parameter
    shards (n, shard_b), exactly what :func:`build_grouped_allgather`
    consumes as its own launch, followed by the new state leaves. Splitting
    the all-gather out lets the engine hold it as a prefetch leg across the
    step boundary: state consumers never wait on the gather, and the
    gather's wire time rides under the step's tail instead of on the
    update's critical path.

    ``packed=True``: inputs are per-bucket packed buffers (n, total_b)
    from :func:`build_pack_group` (engine path). ``packed=False``: inputs
    are the raw gradient tensors in natural shapes presented as world
    views (the staged replay path — same input convention as
    :func:`build_replay_step`).

    ``codecs`` (ISSUE 13) compresses the reduce-scatter legs per bucket
    (:func:`_rs_flat_codec` — pre-scatter encode, rank-local decode,
    shard ownership untouched). Error-feedback buckets append a residual
    input after the state leaves and a new-residual output after the new
    state, in bucket order."""
    if dtypes is not None:
        _check_bucket_dtypes(dtypes, buckets)
    n = int(mesh.devices.size)
    if codecs is None:
        codecs = (comp.CODEC_NONE,) * len(buckets)
    codecs = tuple(codecs)
    ef_buckets = tuple(b for b in range(len(buckets))
                       if codecs[b] in comp.EF_CODECS)

    def body(*args):
        n_in = len(buckets) if packed else len(shapes)
        state = list(args[n_in:n_in + len(state_shapes)])
        residuals = {b: args[n_in + len(state_shapes) + i]
                     for i, b in enumerate(ef_buckets)}
        flats = []
        for b, idxs in enumerate(buckets):
            if packed:
                flat = args[b][0]
            else:
                flat = jnp.concatenate([jnp.ravel(args[i]) for i in idxs])
            if prescale_factor != 1.0:
                flat = flat * prescale_factor
            flats.append(flat)
        # collectives issued back-to-back: mutually independent, the
        # overlap-ready form
        shards = []
        new_res: dict = {}
        for b, f in enumerate(flats):
            if codecs[b] == comp.CODEC_NONE:
                shards.append(_rs_flat(f, axis, n, op))
            else:
                s, nr = _rs_flat_codec(f, residuals.get(b), axis, n, op,
                                       codecs[b])
                if b in residuals:
                    new_res[b] = nr
                shards.append(s)
        if postscale_factor != 1.0:
            shards = [s * postscale_factor for s in shards]
        new_shards, new_state = update(shards, state)
        _check_state_leaves(state, new_state)
        return tuple(s[None] for s in new_shards) + tuple(new_state) \
            + tuple(new_res[b] for b in ef_buckets)

    n_in = len(buckets) if packed else len(shapes)
    in_specs = (tuple(P(axis) for _ in buckets) if packed
                else tuple(P() for _ in shapes))
    fn = _shmap(body, mesh, axis,
                in_specs=in_specs + tuple(P() for _ in state_shapes)
                + tuple(P() for _ in ef_buckets),
                out_specs=tuple(P(axis) for _ in buckets)
                + tuple(P() for _ in state_shapes)
                + tuple(P() for _ in ef_buckets),
                check_vma=False)
    return jax.jit(fn)


def build_sharded_step(mesh: Mesh, axis: str, op: ReduceOp,
                       shapes, dtypes, buckets,
                       state_shapes, state_dtypes, update,
                       prescale_factor: float = 1.0,
                       postscale_factor: float = 1.0,
                       pipeline: bool = False,
                       local_size: int = 0,
                       ag_algos: Optional[Sequence[str]] = None,
                       codecs: Optional[Sequence[str]] = None):
    """ONE launch for a whole ZeRO-1 optimizer step: per-bucket packed
    gradient buffers (stacked (n, total_b)) plus this rank's optimizer-state
    leaves (world-view lifted, genuinely different per rank) go in; the
    program reduce-scatters each bucket, runs ``update`` on the local shards
    only (1/n of the optimizer-update FLOPs), all-gathers the updated
    parameter shards, and unpacks — outputs are the full updated parameter
    tensors (replicated by construction) followed by the new state leaves
    (each rank's own shard-local state).

    ``update(shards, state_leaves) -> (new_param_shards, new_state_leaves)``
    is traced into the program; it must be collective-free and preserve the
    state leaves' shapes/dtypes (asserted at trace time). The wire sequence
    is exactly one reduce-scatter and one all-gather per bucket — the same
    bytes as the fused allreduce, split around the shard-local update.
    ``pipeline=True`` keeps the same wire sequence but traces each phase's
    collectives back-to-back (all reduce-scatters, update, all
    all-gathers, then unpacks) so no unpack interposes between two
    collectives (ISSUE 6 overlap-ready ordering).

    ``ag_algos`` selects flat vs hierarchical for the return all-gather
    per bucket (ISSUE 10); the reduce-scatter leg is always the flat
    ring (shard-ownership invariant, :func:`validate_algorithm`).

    ``codecs`` (ISSUE 13) compresses the GRADIENT reduce-scatter legs
    per bucket (pre-scatter encode, rank-local decode — ownership
    untouched, :func:`_rs_flat_codec`); the parameter all-gather stays
    full precision (every rank must reconstruct bit-identical params).
    Error-feedback buckets append a residual input after the state
    leaves and a new-residual output after the new state.
    """
    _check_bucket_dtypes(dtypes, buckets)
    n = int(mesh.devices.size)
    if ag_algos is None:
        ag_algos = (ALGO_FLAT,) * len(buckets)
    ag_algos = tuple(validate_algorithm("allgather", a, n, local_size)
                     for a in ag_algos)
    if codecs is None:
        codecs = (comp.CODEC_NONE,) * len(buckets)
    codecs = tuple(codecs)
    ef_buckets = tuple(b for b in range(len(buckets))
                       if codecs[b] in comp.EF_CODECS)
    sizes = [math.prod(s) for s in shapes]
    totals = [sum(sizes[i] for i in idxs) for idxs in buckets]

    def body(*args):
        packed = args[:len(buckets)]
        state = list(args[len(buckets):len(buckets) + len(state_shapes)])
        residuals = {b: args[len(buckets) + len(state_shapes) + i]
                     for i, b in enumerate(ef_buckets)}
        new_res: dict = {}

        def _rs(b, flat):
            if codecs[b] == comp.CODEC_NONE:
                return _rs_flat(flat, axis, n, op)
            s, nr = _rs_flat_codec(flat, residuals.get(b), axis, n, op,
                                   codecs[b])
            if b in residuals:
                new_res[b] = nr
            return s

        if pipeline:
            flats = []
            for b in range(len(buckets)):
                flat = packed[b][0]
                if prescale_factor != 1.0:
                    flat = flat * prescale_factor
                flats.append(flat)
            shards = [_rs(b, f) for b, f in enumerate(flats)]
            if postscale_factor != 1.0:
                shards = [s * postscale_factor for s in shards]
        else:
            shards = []
            for b in range(len(buckets)):
                flat = packed[b][0]
                if prescale_factor != 1.0:
                    flat = flat * prescale_factor
                shard = _rs(b, flat)
                if postscale_factor != 1.0:
                    shard = shard * postscale_factor
                shards.append(shard)
        new_shards, new_state = update(shards, state)
        _check_state_leaves(state, new_state)
        outs = [None] * len(shapes)
        if pipeline:
            fulls = [_ag_flat(new_shards[b], axis, totals[b], ag_algos[b],
                              n, local_size)
                     for b in range(len(buckets))]
            for b, idxs in enumerate(buckets):
                _unpack_flat(fulls[b], shapes, sizes, idxs, outs)
        else:
            for b, idxs in enumerate(buckets):
                full = _ag_flat(new_shards[b], axis, totals[b],
                                ag_algos[b], n, local_size)
                _unpack_flat(full, shapes, sizes, idxs, outs)
        return tuple(outs) + tuple(new_state) \
            + tuple(new_res[b] for b in ef_buckets)

    # packed grads arrive stacked; state leaves are world-view claims (each
    # rank's own shard presented as 'replicated'); gathered params are
    # replicated by construction, new state is per-rank — neither is
    # VMA-inferrable, same as the replay builder
    fn = _shmap(body, mesh, axis,
                in_specs=tuple(P(axis) for _ in buckets)
                + tuple(P() for _ in state_shapes)
                + tuple(P() for _ in ef_buckets),
                out_specs=tuple(P() for _ in shapes)
                + tuple(P() for _ in state_shapes)
                + tuple(P() for _ in ef_buckets),
                check_vma=False)
    return jax.jit(fn)


def _seg_algo_spec(field, n_buckets: int):
    """Decode a replay segment's topology field (position 4): a bare int
    is the legacy form — ``local_size``, > 1 meaning hierarchical for
    every bucket — while a ``(local_size, algos)`` tuple carries the
    per-bucket topology-aware selection (ISSUE 10) and a
    ``(local_size, algos, codecs)`` tuple additionally carries the
    per-bucket wire codec (ISSUE 13; both shorter forms mean codec
    "none" everywhere). For "sharded" segments the algo list applies to
    the return all-gather legs (the reduce-scatter is pinned flat) and
    the codec list to the reduce-scatter legs."""
    if isinstance(field, tuple):
        local, algos = int(field[0]), tuple(field[1])
        if len(algos) != n_buckets:
            raise ValueError(
                f"segment algo list has {len(algos)} entries for "
                f"{n_buckets} buckets")
        codecs = (tuple(field[2]) if len(field) > 2
                  else (comp.CODEC_NONE,) * n_buckets)
        if len(codecs) != n_buckets:
            raise ValueError(
                f"segment codec list has {len(codecs)} entries for "
                f"{n_buckets} buckets")
    else:
        local, algos = int(field), (None,) * n_buckets
        codecs = (comp.CODEC_NONE,) * n_buckets
    return local, algos, codecs


def replay_residual_layout(segments, n: int) -> list:
    """Error-feedback residual I/O order for a replay program: one entry
    ``(seg_idx, bucket_idx, elems)`` per EF-codec bucket, in
    segment-major bucket-minor program order. Residual inputs follow the
    step's tensors in this order and the new-residual outputs follow the
    tensor outputs the same way — the engine's replay launch and
    :func:`build_replay_step` both derive the arity from here."""
    out = []
    for si, seg in enumerate(segments):
        cls, code, pre, post, topo_field, shapes, buckets = seg
        if cls == "a2a":
            # the alltoall DCN-leg codec is stateless by design (dispatched
            # tokens have no step-over-step identity for a residual to
            # telescope against) — never a residual row, even for codecs
            # that carry one on reduce segments
            continue
        local, algos, codecs = _seg_algo_spec(topo_field, len(buckets))
        sizes = [math.prod(s) for s in shapes]
        for bi, idxs in enumerate(buckets):
            codec = codecs[bi]
            if codec not in comp.EF_CODECS:
                continue
            total = sum(sizes[i] for i in idxs)
            if cls == "sharded":
                elems = codec_residual_elems("sharded", total, n, local,
                                             None, codec)
            else:
                algo = _resolve_reduce_algo(algos[bi], n, local)
                elems = codec_residual_elems("reduce", total, n, local,
                                             algo, codec)
            out.append((si, bi, elems))
    return out


def build_replay_step(mesh: Mesh, axis: str, segments,
                      sharded_updates=None, pipeline: bool = False):
    """ONE launch for a whole captured eager step (core/replay.py): every
    recorded collective call's pack, reduction/broadcast, and unpack fused
    into a single jitted program — the XLA answer to CUDA-graph capture of
    the steady-state dispatch stream (the reference amortizes the same
    per-op cost with its background fusion cycle, operations.cc:566-616;
    here the whole cycle collapses to one dispatch).

    Inputs are the step's local tensors in recorded order, presented as
    'replicated' world-view arrays (``Backend.world_view``: each rank
    contributes its own shard, a zero-dispatch metadata lift). With
    ``in_specs=P()`` the manual region sees each rank's own value, so the
    per-bucket psum/broadcast reduces genuinely distinct per-rank data —
    this is only sound from shard_map manual code, which is why the lift
    helper is engine-internal.

    Args:
      segments: sequence of ``(cls, code, pre, post, local_size, shapes,
        buckets)`` tuples — ``cls`` is ``"reduce"`` (code = ReduceOp),
        ``"bcast"`` (code = root rank), ``"a2a"`` (an alltoall dispatch
        group: code unused, per-bucket algos/codecs ride the topology
        field exactly as for reduce segments, and the codec applies to
        the hierarchical DCN leg only — stateless, no residual row), or
        ``"sharded"`` (a ZeRO-1 optimizer step: code = ``(op,
        update_key, n_grads)``, ``shapes`` lists the gradient shapes
        followed by the shard-local state-leaf shapes, ``buckets`` index
        into the first ``n_grads`` shapes, and ``update_key`` resolves
        the shard-update closure in ``sharded_updates``); other
        ``shapes``/``buckets`` as before. An ``"a2a"`` segment's inputs
        and outputs ride the same world-view P() claim as everything
        else — each rank's addressable shard is its OWN dispatch/receive
        buffer, which is exactly what the one-device-per-process group
        mesh extracts.
      sharded_updates: mapping update_key -> ``update(shards, state)``
        closure (engine._sharded_updates); required when any segment is
        ``"sharded"``.
      pipeline: the ISSUE 6 overlap restructure. The serial trace order is
        pack(0), reduce(0), unpack(0), pack(1), reduce(1), ... — bucket
        0's unpack *consumes* reduce(0) and sits between it and bucket
        1's collective, so an in-order scheduler serializes the whole
        chain behind each wire leg. ``pipeline=True`` traces the step as
        explicit software-pipeline phases instead: every bucket's pack
        first, then every collective back-to-back (mutually independent —
        nothing traced between two collectives consumes an earlier
        collective's result), then shard-local updates + return
        all-gathers, then every unpack. Same math, same wire bytes; the
        collectives become async-overlappable (XLA's latency-hiding
        scheduler / async collective conversion hides reduce(i) behind
        pack(i+1) and the unpack epilogue).
    """
    n = int(mesh.devices.size)
    n_tensors = sum(len(seg[5]) for seg in segments)
    # error-feedback residual I/O (ISSUE 13): one rank-local residual per
    # EF-codec bucket rides after the step's tensors (world-view lifted)
    # and the new residuals return after the tensor outputs, in
    # replay_residual_layout order
    res_layout = replay_residual_layout(segments, n)
    res_in = {(si, bi): n_tensors + k
              for k, (si, bi, _) in enumerate(res_layout)}

    def body_pipelined(*ts):
        outs = [None] * n_tensors
        new_res: dict = {}
        bases = []
        base = 0
        for seg in segments:
            bases.append(base)
            base += len(seg[5])
        # -- phase 1: every bucket's pack (pre-scaled), no collective yet --
        packs = {}   # (seg_idx, bucket_idx) -> flat (or (n, R) for a2a)
        for si, (cls, code, pre, post, local_size, shapes,
                 buckets) in enumerate(segments):
            for bi, idxs in enumerate(buckets):
                if cls == "a2a":
                    packs[(si, bi)], _ = _a2a_pack(
                        [ts[bases[si] + i] for i in idxs], n)
                    continue
                flat = jnp.concatenate(
                    [jnp.ravel(ts[bases[si] + i]) for i in idxs])
                if cls != "bcast" and pre != 1.0:
                    flat = flat * pre
                packs[(si, bi)] = flat
        # -- phase 2: every collective, issued back-to-back --
        reds = {}    # (seg_idx, bucket_idx) -> reduced flat / shard
        for si, (cls, code, pre, post, topo_field, shapes,
                 buckets) in enumerate(segments):
            local_size, algos, codecs = _seg_algo_spec(topo_field,
                                                       len(buckets))
            if cls == "reduce":
                reducers = _bucket_reducers(axis, ReduceOp(code), n,
                                            local_size, algos,
                                            len(buckets), codecs)
            for bi in range(len(buckets)):
                flat = packs[(si, bi)]
                res = ts[res_in[(si, bi)]] if (si, bi) in res_in else None
                if cls == "sharded":
                    if codecs[bi] == comp.CODEC_NONE:
                        reds[(si, bi)] = _rs_flat(flat, axis, n,
                                                  ReduceOp(code[0]))
                    else:
                        shard, nr = _rs_flat_codec(flat, res, axis, n,
                                                   ReduceOp(code[0]),
                                                   codecs[bi])
                        if (si, bi) in res_in:
                            new_res[(si, bi)] = nr
                        reds[(si, bi)] = shard
                elif cls == "reduce":
                    red, nr = reducers[bi](flat, res)
                    if (si, bi) in res_in:
                        new_res[(si, bi)] = nr
                    reds[(si, bi)] = red
                elif cls == "a2a":
                    reds[(si, bi)] = _a2a_exchange(flat, axis, n,
                                                   local_size, algos[bi],
                                                   codecs[bi])
                else:
                    reds[(si, bi)] = broadcast_p(flat, axis, code)
        # -- phase 3: shard-local updates + return all-gathers --
        for si, (cls, code, pre, post, topo_field, shapes,
                 buckets) in enumerate(segments):
            sizes = [math.prod(s) for s in shapes]
            if cls == "sharded":
                local_size, ag_algos, _codecs = _seg_algo_spec(
                    topo_field, len(buckets))
                op_code, update_key, n_grads = code
                shards = [reds[(si, bi)] for bi in range(len(buckets))]
                if post != 1.0:
                    shards = [s * post for s in shards]
                state = [ts[bases[si] + j]
                         for j in range(n_grads, len(shapes))]
                new_shards, new_state = sharded_updates[update_key](
                    shards, state)
                for bi, idxs in enumerate(buckets):
                    total = sum(sizes[i] for i in idxs)
                    reds[(si, bi)] = _ag_flat(
                        new_shards[bi], axis, total,
                        ag_algos[bi] or ALGO_FLAT, n, local_size)
                for j, leaf in enumerate(new_state):
                    outs[bases[si] + n_grads + j] = leaf
            elif cls == "reduce" and post != 1.0:
                for bi in range(len(buckets)):
                    reds[(si, bi)] = reds[(si, bi)] * post
        # -- phase 4: every unpack (the epilogue nothing waits behind) --
        for si, (cls, code, pre, post, local_size, shapes,
                 buckets) in enumerate(segments):
            sizes = [math.prod(s) for s in shapes]
            for bi, idxs in enumerate(buckets):
                if cls == "a2a":
                    ex = reds[(si, bi)]
                    off = 0
                    for i in idxs:
                        w = sizes[i] // n
                        outs[bases[si] + i] = \
                            ex[:, off:off + w].reshape(shapes[i])
                        off += w
                    continue
                seg_outs = [None] * len(shapes)
                _unpack_flat(reds[(si, bi)], shapes, sizes, idxs, seg_outs)
                for i in idxs:
                    outs[bases[si] + i] = seg_outs[i]
        return tuple(outs) + tuple(new_res[(si, bi)]
                                   for si, bi, _ in res_layout)

    def body(*ts):  # each rank's own local tensors, natural shapes
        outs = [None] * n_tensors
        new_res: dict = {}
        base = 0
        for si, (cls, code, pre, post, topo_field, shapes,
                 buckets) in enumerate(segments):
            sizes = [math.prod(s) for s in shapes]
            local_size, algos, codecs = _seg_algo_spec(topo_field,
                                                       len(buckets))
            if cls == "sharded":
                # rs -> shard-local update -> ag, fused in-stream: the
                # sharded eager step replays as part of the same single
                # launch as every other recorded call
                op_code, update_key, n_grads = code
                op = ReduceOp(op_code)
                state = [ts[base + j] for j in range(n_grads, len(shapes))]
                shards = []
                for bi, idxs in enumerate(buckets):
                    flat = jnp.concatenate(
                        [jnp.ravel(ts[base + i]) for i in idxs])
                    if pre != 1.0:
                        flat = flat * pre
                    if codecs[bi] == comp.CODEC_NONE:
                        shard = _rs_flat(flat, axis, n, op)
                    else:
                        res = (ts[res_in[(si, bi)]]
                               if (si, bi) in res_in else None)
                        shard, nr = _rs_flat_codec(flat, res, axis, n, op,
                                                   codecs[bi])
                        if (si, bi) in res_in:
                            new_res[(si, bi)] = nr
                    if post != 1.0:
                        shard = shard * post
                    shards.append(shard)
                new_shards, new_state = sharded_updates[update_key](
                    shards, state)
                for b, idxs in enumerate(buckets):
                    total = sum(sizes[i] for i in idxs)
                    full = _ag_flat(new_shards[b], axis, total,
                                    algos[b] or ALGO_FLAT, n, local_size)
                    seg_outs = [None] * len(shapes)
                    _unpack_flat(full, shapes, sizes, idxs, seg_outs)
                    for i in idxs:
                        outs[base + i] = seg_outs[i]
                for j, leaf in enumerate(new_state):
                    outs[base + n_grads + j] = leaf
                base += len(shapes)
                continue
            if cls == "a2a":
                for b, idxs in enumerate(buckets):
                    packed, widths = _a2a_pack(
                        [ts[base + i] for i in idxs], n)
                    ex = _a2a_exchange(packed, axis, n, local_size,
                                       algos[b], codecs[b])
                    off = 0
                    for i, w in zip(idxs, widths):
                        outs[base + i] = \
                            ex[:, off:off + w].reshape(shapes[i])
                        off += w
                base += len(shapes)
                continue
            if cls == "reduce":
                reducers = _bucket_reducers(axis, ReduceOp(code), n,
                                            local_size, algos,
                                            len(buckets), codecs)
            for b, idxs in enumerate(buckets):
                flat = jnp.concatenate(
                    [jnp.ravel(ts[base + i]) for i in idxs])
                if cls == "reduce":
                    if pre != 1.0:
                        flat = flat * pre
                    res = (ts[res_in[(si, b)]]
                           if (si, b) in res_in else None)
                    red, nr = reducers[b](flat, res)
                    if (si, b) in res_in:
                        new_res[(si, b)] = nr
                    if post != 1.0:
                        red = red * post
                else:
                    red = broadcast_p(flat, axis, code)
                off = 0
                for i in idxs:
                    outs[base + i] = lax.dynamic_slice_in_dim(
                        red, off, sizes[i]).reshape(shapes[i])
                    off += sizes[i]
            base += len(shapes)
        return tuple(outs) + tuple(new_res[(si, bi)]
                                   for si, bi, _ in res_layout)

    # inputs are claimed-replicated world views (varying in truth) and the
    # outputs are replicated by construction — the VMA checker can infer
    # neither, same as the ladder builders above
    fn = _shmap(body_pipelined if pipeline else body, mesh, axis,
                in_specs=tuple(P() for _ in
                               range(n_tensors + len(res_layout))),
                out_specs=tuple(P() for _ in
                                range(n_tensors + len(res_layout))),
                check_vma=False)
    return jax.jit(fn)


def build_barrier(mesh: Mesh, axis: str):
    """Barrier = tiny psum every rank must join (reference:
    MPIController::Barrier mpi_controller.cc:225)."""
    def body(x):
        return lax.psum(x[0], axis)

    fn = _shmap(body, mesh, axis, in_specs=P(axis), out_specs=P())
    return jax.jit(fn)
