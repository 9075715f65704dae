"""Device-mesh construction for the TPU-native runtime.

The reference's topology model is GLOBAL/LOCAL/CROSS communicators
(horovod/common/common.h:113-117, mpi/mpi_context.cc splits). The TPU-native
equivalent is a ``jax.sharding.Mesh``:

- 1-D ``world`` mesh — the global communicator; every collective defaults here.
- 2-D ``(cross, local)`` mesh — the hierarchical decomposition used by
  NCCLHierarchicalAllreduce (ops/nccl_operations.cc:180-383): on TPU, ``local``
  maps onto the ICI-connected slice and ``cross`` onto the DCN axis between
  slices/hosts.
- N-D training meshes (``data``/``fsdp``/``tensor``/``seq``/``expert``/``pipe``)
  for SPMD parallelism beyond the reference's DP-only surface.
"""

from __future__ import annotations

import hashlib
import logging
import math
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common import env as env_mod

WORLD_AXIS = "world"
CROSS_AXIS = "cross"   # inter-node / DCN axis
LOCAL_AXIS = "local"   # intra-node / ICI axis

logger = logging.getLogger("horovod_tpu")

# Nominal per-chip link bandwidths in GB/s, keyed by ``device_kind`` as jax
# reports it, each with its source — the roofline the bus-bandwidth sweep
# reports achieved bandwidth against, and the ICI:DCN ratio the selection
# layer reads. A TPU kind that is not here is an error (see
# :func:`nominal_link_gbps`): another generation's figures would make every
# roofline share computed from them wrong without a sign.
_NOMINAL_LINK_GBPS = {
    # device_kind: (ici_gbps, dcn_gbps)
    # ICI: Google Cloud "TPU v5e" — 1,600 Gbit/s chip-to-chip interconnect
    # per chip. DCN: no per-chip figure is published; 12.5 GB/s is one
    # 100 Gbit/s host NIC, an assumption nothing on a single host reads.
    "TPU v5 lite": (200.0, 12.5),
    # forced-host-device test worlds: not a device figure — keeps the 1:8
    # fast:slow shape the selection tests are written against
    "cpu": (8.0, 1.0),
}


def nominal_link_gbps(device_kind: str) -> Tuple[float, float]:
    """``(ici_gbps, dcn_gbps)`` for a ``device_kind`` the table knows;
    anything else raises."""
    try:
        return _NOMINAL_LINK_GBPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no nominal link rates for device_kind {device_kind!r}; known: "
            f"{sorted(_NOMINAL_LINK_GBPS)} (the TPU generations this program "
            f"has run on, and the CPU test worlds; GPUs and other "
            f"accelerators are not supported). Add a TPU kind with its "
            f"source to parallel/mesh.py _NOMINAL_LINK_GBPS") from None


@dataclass(frozen=True)
class Topology:
    """First-class fabric descriptor the runtime resolves ONCE and threads
    to every collective builder (ROADMAP item 2; the reference's
    GLOBAL/LOCAL/CROSS communicator split, common.h:113-117, promoted from
    an opt-in env knob to a runtime axis).

    ``local_size`` is the number of ranks on one fast-fabric island (an
    ICI-connected TPU slice, or processes on one host in CPU/GPU test
    worlds); ``size / local_size`` islands talk over the slow fabric
    (DCN). ``choose_algorithm`` (ops/collectives.py) picks
    ring/tree/hierarchical per (bytes, this descriptor).
    """

    size: int
    local_size: int = 1
    platform: str = "cpu"
    source: str = "flat"       # "override" | "slice_attrs" | "process" | "flat"
    ici_gbps: float = _NOMINAL_LINK_GBPS["cpu"][0]
    dcn_gbps: float = _NOMINAL_LINK_GBPS["cpu"][1]

    @property
    def num_slices(self) -> int:
        return max(1, self.size // max(self.local_size, 1))

    @property
    def is_multislice(self) -> bool:
        return self.num_slices > 1 and self.local_size > 1

    @property
    def hierarchical_ok(self) -> bool:
        """Whether a (cross, local) decomposition is non-trivial AND exact:
        more than one rank per island, more than one island, divisible
        world. Non-divisible worlds get the flat fallback (the satellite
        fix for the old hard assert)."""
        return (1 < self.local_size < self.size
                and self.size % self.local_size == 0)

    def local_groups(self) -> List[List[int]]:
        """Rank groups sharing a fast-fabric island (requires
        ``hierarchical_ok``). Delegates to the ONE slice-major layout
        rule (ops.collectives.slice_groups) every two-level builder
        derives its replica groups from — the layout must never fork."""
        from ..ops.collectives import slice_groups
        return slice_groups(self.size, self.local_size)[0]

    def cross_groups(self) -> List[List[int]]:
        """Rank groups spanning islands at the same local index (same
        canonical rule as :meth:`local_groups`)."""
        from ..ops.collectives import slice_groups
        return slice_groups(self.size, self.local_size)[1]

    # -- roofline ----------------------------------------------------------

    def roofline_busbw_gbps(self, kind: str = "allreduce",
                            algo: str = "flat") -> float:
        """Nominal bus-bandwidth ceiling in GB/s for one collective under
        ``algo`` on this fabric (busbw in the NCCL-tests sense: moved
        bytes normalized by the algorithm-independent 2(n-1)/n factor, so
        every algorithm is comparable against the same line).

        - flat ring: paced by the slowest link the ring crosses — DCN
          when the world spans islands, ICI otherwise.
        - hierarchical allreduce: the cross leg carries 1/local_size of
          the payload, so the ceiling is min(ici, dcn * local_size).
        - hierarchical allgather: the cross gather moves whole slice
          blocks (every byte crosses DCN) — DCN-paced like the flat
          multislice ring; its win is hop count, not bandwidth.
        - alltoall: busbw convention is (n-1)/n (each rank keeps its own
          chunk; only n-1 of n chunks move). Flat is paced like the ring
          — DCN when the world spans islands. The hierarchical lowering's
          DCN leg carries only the cross-slice block transpose — each DCN
          link moves (C-1)/C of the payload instead of (n-1)/n across C
          slices, so the ceiling is min(ici, dcn · (n-1)/n ÷ (C-1)/C).
        - tree (recursive doubling): each of the log2(n) rounds moves the
          full payload, so the bandwidth ceiling divides by log2(n) —
          the reason tree is for latency-bound small buckets only.
        """
        n = max(self.size, 1)
        if n <= 1:
            return float("inf")
        if kind == "alltoall":
            if algo == "hierarchical" and self.hierarchical_ok:
                c = self.num_slices
                if c <= 1:
                    return self.ici_gbps
                # normalized by the flat (n-1)/n convention: the DCN leg
                # only moves (C-1)/C, so the effective ceiling scales up
                # by the block-transpose factor ((n-1)/n) / ((C-1)/C)
                factor = ((n - 1) / n) / ((c - 1) / c)
                return min(self.ici_gbps, self.dcn_gbps * factor)
            return self.dcn_gbps if self.is_multislice else self.ici_gbps
        if algo == "hierarchical" and self.hierarchical_ok:
            if kind == "allgather":
                return min(self.ici_gbps, self.dcn_gbps)
            return min(self.ici_gbps, self.dcn_gbps * self.local_size)
        base = self.dcn_gbps if self.is_multislice else self.ici_gbps
        if algo == "tree":
            return base / max(math.log2(n), 1.0)
        return base

    def describe(self) -> dict:
        return {"size": self.size, "local_size": self.local_size,
                "num_slices": self.num_slices, "platform": self.platform,
                "source": self.source, "ici_gbps": self.ici_gbps,
                "dcn_gbps": self.dcn_gbps,
                "hierarchical_ok": self.hierarchical_ok}

    def digest(self) -> str:
        """Stable identity of the fabric SHAPE — the persistence key half
        that decides whether a stored tuning record applies to this world
        (autotune/persistence.py). Deliberately excludes the bandwidth
        numbers: measured link rates vary run to run on the same pod, and
        a record keyed on them would never match again. Excludes
        ``source`` too (override vs probe must not fork the key for the
        same shape)."""
        text = f"{self.size}|{self.local_size}|{self.num_slices}|" \
               f"{self.platform}"
        return hashlib.sha256(text.encode()).hexdigest()

    @property
    def calibrated(self) -> bool:
        """Whether the link table is measured-on-pod (MeasuredTopology)
        rather than the nominal per-generation figures."""
        return False

    # -- mesh integration --------------------------------------------------

    def hierarchical_mesh(self,
                          devices: Optional[Sequence[jax.Device]] = None
                          ) -> Mesh:
        """The 2-D (cross, local) mesh matching this descriptor."""
        return hierarchical_mesh(self.local_size, devices)

    def multislice_mesh(self, dcn_axes: dict, ici_axes: dict,
                        devices: Optional[Sequence[jax.Device]] = None
                        ) -> Mesh:
        """DCN-aware SPMD mesh over this topology (delegates to
        :func:`multislice_mesh`, which uses the hybrid device mesh on real
        multi-slice hardware)."""
        return multislice_mesh(dcn_axes, ici_axes, devices)


@dataclass(frozen=True)
class MeasuredTopology(Topology):
    """A :class:`Topology` whose link table was CALIBRATED by the engine's
    init-time probe (autotune/calibration.py) instead of taken from the
    nominal per-generation constants.

    ``ici_gbps``/``dcn_gbps`` hold the measured figures, so every
    consumer of the base descriptor (roofline helpers, bench sweeps,
    selection) sees calibrated numbers transparently; the nominal values
    stay visible in ``nominal_ici_gbps``/``nominal_dcn_gbps`` so the
    bench can report the nominal-vs-measured delta. ``launch_latency_us``
    is the fitted per-launch α of the α–β cost model, and ``link_model``
    maps each probed algorithm class to its fitted ``(alpha_s,
    beta_bytes_per_s)`` pair — the inputs the derived crossover
    thresholds (autotune/calibration.py) come from.

    ``digest()`` is inherited unchanged: calibration never forks the
    persistence key — two runs on the same fabric shape share tuning
    records even when their probes measured slightly different rates.
    """

    nominal_ici_gbps: float = 0.0
    nominal_dcn_gbps: float = 0.0
    launch_latency_us: float = 0.0
    # (("flat", alpha_s, beta_bytes_per_s), ("hierarchical", ...), ...)
    link_model: Tuple[Tuple[str, float, float], ...] = ()

    @property
    def calibrated(self) -> bool:
        return True

    def fitted(self, algo: str) -> Optional[Tuple[float, float]]:
        """The fitted ``(alpha_s, beta_bytes_per_s)`` pair for one probed
        algorithm class, or None when that class was not probed (e.g.
        hierarchical on a flat world)."""
        for name, alpha, beta in self.link_model:
            if name == algo:
                return (alpha, beta)
        return None

    def describe(self) -> dict:
        d = super().describe()
        d.update({"calibrated": True,
                  "nominal_ici_gbps": self.nominal_ici_gbps,
                  "nominal_dcn_gbps": self.nominal_dcn_gbps,
                  "launch_latency_us": round(self.launch_latency_us, 2),
                  "link_model": {name: {"alpha_us": round(a * 1e6, 2),
                                        "beta_gbps": round(b / 1e9, 3)}
                                 for name, a, b in self.link_model}})
        return d


def measured_topology(base: Topology, ici_gbps: float, dcn_gbps: float,
                      launch_latency_us: float,
                      link_model: Dict[str, Tuple[float, float]]
                      ) -> MeasuredTopology:
    """Overlay measured link rates on a nominal descriptor. The base's
    shape fields carry over unchanged (same ``digest()``); only the
    bandwidth table and the fitted α–β model are new."""
    return MeasuredTopology(
        size=base.size, local_size=base.local_size,
        platform=base.platform, source=base.source,
        ici_gbps=float(ici_gbps), dcn_gbps=float(dcn_gbps),
        nominal_ici_gbps=base.ici_gbps, nominal_dcn_gbps=base.dcn_gbps,
        launch_latency_us=float(launch_latency_us),
        link_model=tuple(sorted(
            (name, float(a), float(b))
            for name, (a, b) in link_model.items())))


def _slice_local_size(devices: Sequence[jax.Device]) -> Tuple[int, str]:
    """(devices per island, detection source) from device attributes:
    ``slice_index`` (real multi-slice TPU pods) first, then
    ``process_index`` (one host = one island in test worlds)."""
    for attr, source in (("slice_index", "slice_attrs"),
                         ("process_index", "process")):
        groups: dict = {}
        missing = False
        for d in devices:
            v = getattr(d, attr, None)
            if v is None:
                missing = True
                break
            groups.setdefault(v, 0)
            groups[v] += 1
        if missing or len(groups) <= 1:
            continue
        sizes = set(groups.values())
        if len(sizes) == 1:       # uniform islands only
            return sizes.pop(), source
    return len(devices), "flat"   # one island: everything is fast fabric


def detect_topology(size: Optional[int] = None,
                    local_size: Optional[int] = None,
                    devices: Optional[Sequence[jax.Device]] = None
                    ) -> Topology:
    """Resolve the :class:`Topology` descriptor for a world.

    Precedence for ``local_size`` (ranks per fast-fabric island):

    1. the ``HOROVOD_TPU_LOCAL_SIZE`` env override — the user's escape
       hatch for fabrics the probes cannot see (and the test hook);
    2. the ``local_size`` argument when > 1 (the engine passes the
       launcher's processes-per-host figure);
    3. device attributes: ``slice_index`` groups on real multi-slice TPU
       pods, ``process_index`` groups elsewhere;
    4. flat (one island).

    A ``local_size`` that does not divide the world falls back to the
    largest divisor <= local_size (the :func:`hierarchical_mesh` rule) —
    never an assert; ``Topology.hierarchical_ok`` reports whether the
    result supports the two-level decomposition.
    """
    override = os.environ.get(env_mod.HOROVOD_TPU_LOCAL_SIZE)
    source = "flat"
    devs: Sequence[jax.Device] = ()
    if devices is not None or size is None:
        devs = list(devices) if devices is not None else list(jax.devices())
        if size is None:
            size = len(devs)
    # the link rates are the device's own, also for a world given by size
    # alone: never another platform's by default
    first = devs[0] if devs else jax.devices()[0]
    platform, kind = first.platform, first.device_kind
    parsed_override = None
    if override:
        try:
            parsed_override = int(override)
        except ValueError:
            logger.warning("HOROVOD_TPU_LOCAL_SIZE=%r is not an int; "
                           "ignoring the override", override)
    if parsed_override is not None:
        local_size, source = parsed_override, "override"
    elif local_size is not None and local_size > 1:
        source = "process"
    else:
        local_size = None
    if local_size is None:
        if devs:
            local_size, source = _slice_local_size(devs)
            if local_size >= size:  # one island
                local_size, source = 1, "flat"
        else:
            local_size = 1
    local_size = max(1, min(int(local_size), int(size)))
    if size % local_size != 0:
        fallback = max(d for d in range(1, local_size + 1)
                       if size % d == 0)
        logger.warning(
            "topology: local_size %d does not divide world size %d; "
            "falling back to local_size=%d (hierarchical collectives "
            "demote to flat when no non-trivial divisor exists)",
            local_size, size, fallback)
        local_size = fallback
    ici, dcn = nominal_link_gbps(kind)
    return Topology(size=int(size), local_size=int(local_size),
                    platform=platform, source=source,
                    ici_gbps=ici, dcn_gbps=dcn)


def world_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D mesh over every device — the GLOBAL communicator."""
    devs = list(devices) if devices is not None else list(jax.devices())
    return Mesh(np.array(devs), (WORLD_AXIS,))


def hierarchical_mesh(local_size: Optional[int] = None,
                      devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """2-D (cross, local) mesh for hierarchical collectives.

    ``local_size`` defaults to the per-process device count (the TPU analog of
    ranks-per-node used by the reference's local communicator split,
    mpi/mpi_context.cc). Falls back to the largest power-of-2-ish divisor when
    the world size is not divisible.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    n = len(devs)
    if local_size is None:
        local_size = max(1, len([d for d in devs if d.process_index == devs[0].process_index]))
    if n % local_size != 0:
        # fall back to the largest divisor of n that is <= local_size
        local_size = max(d for d in range(1, local_size + 1) if n % d == 0)
    cross = n // local_size
    arr = np.array(devs).reshape(cross, local_size)
    return Mesh(arr, (CROSS_AXIS, LOCAL_AXIS))


def training_mesh(axis_sizes: dict,
                  devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """N-D SPMD training mesh, e.g. {'data': 2, 'tensor': 2, 'seq': 2}.

    Any axis given size -1 absorbs the remaining devices. Axis order in the
    dict is the mesh-major order: put the axis that should ride DCN first and
    the most bandwidth-hungry axis (tensor) last so it lands on the
    innermost/fastest ICI ring.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    n = len(devs)
    names = list(axis_sizes.keys())
    sizes = list(axis_sizes.values())
    if sizes.count(-1) > 1:
        raise ValueError("only one axis may be -1")
    known = math.prod(s for s in sizes if s != -1)
    if -1 in sizes:
        if n % known != 0:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[sizes.index(-1)] = n // known
    if math.prod(sizes) != n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {math.prod(sizes)} "
                         f"devices, have {n}")
    arr = np.array(devs).reshape(tuple(sizes))
    return Mesh(arr, tuple(names))


def multislice_mesh(dcn_axes: dict, ici_axes: dict,
                    devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """DCN-aware mesh for multi-slice TPU pods.

    ``dcn_axes`` partition ACROSS slices (put data/pipeline parallelism
    here — DCN is the slow fabric), ``ici_axes`` partition WITHIN a slice
    (tensor/sequence/expert parallelism — the bandwidth-hungry collectives
    ride the ICI torus). This is the standard sharding recipe: lay out the
    mesh so XLA's inserted collectives match fabric bandwidth to
    communication volume.

    On real multi-slice hardware (devices expose ``slice_index``) the
    assignment uses ``mesh_utils.create_hybrid_device_mesh`` so device
    coordinates align with the physical topology; elsewhere (single slice,
    CPU test worlds) it falls back to a slice-major reshape with identical
    axis semantics, so programs compile the same either way.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    names = tuple(dcn_axes.keys()) + tuple(ici_axes.keys())
    dcn_shape = tuple(dcn_axes.values())
    ici_shape = tuple(ici_axes.values())
    multi_slice = len({getattr(d, "slice_index", 0) for d in devs}) > 1
    if multi_slice:
        from jax.experimental import mesh_utils
        # create_hybrid_device_mesh wants equal-length shape tuples whose
        # ELEMENTWISE product is the final mesh shape: DCN axes contribute 1
        # to the ICI shape and vice versa, so the result's dims line up with
        # (dcn_axes..., ici_axes...) names
        full_ici = (1,) * len(dcn_shape) + tuple(ici_shape)
        full_dcn = tuple(dcn_shape) + (1,) * len(ici_shape)
        arr = mesh_utils.create_hybrid_device_mesh(
            full_ici, full_dcn, devices=devs)
        return Mesh(arr, names)
    n = len(devs)
    shape = dcn_shape + ici_shape
    if math.prod(shape) != n:
        raise ValueError(f"mesh {dict(zip(names, shape))} needs "
                         f"{math.prod(shape)} devices, have {n}")
    return Mesh(np.array(devs).reshape(shape), names)


def pp_dp_sp_mesh(n_stages: int, data: int = -1, seq: int = 1,
                  devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """PP × DP × SP composition mesh (ISSUE 16): ``pipe`` outermost —
    stage boundaries are the fewest and most latency-tolerant transfers
    (one point-to-point hop per tick), so pipeline parallelism is the
    axis that should absorb DCN when the world spans slices. ``data``
    (ZeRO-1 gradient sync; -1 absorbs remaining devices) sits in the
    middle, and ``seq`` (ring-attention K/V rotation — the
    bandwidth-hungriest ring) lands innermost on the fastest ICI ring.

    The result is a standard ``training_mesh``: pipeline code runs
    shard_map-manual over ``pipe`` per submesh row, DP gradient sync
    rides the engine over ``data``, and SP attention rotates over
    ``seq`` — see docs/parallelism.md for the composition rules."""
    return training_mesh({"pipe": n_stages, "data": data, "seq": seq},
                         devices)


def pipeline_boundary_edges(topology: Topology, n_stages: int,
                            stage_size: Optional[int] = None
                            ) -> Tuple[bool, ...]:
    """Which pipeline-ring boundaries cross DCN: entry i covers the
    boundary between stage i and stage (i+1) % n_stages. A stage owns
    ``stage_size`` consecutive ranks of the slice-major layout
    (default: size // n_stages — the pp_dp_sp_mesh layout, where each
    stage's DP×SP block is contiguous), and a boundary is DCN iff the
    adjacent stages' blocks start on different islands. Feeds the
    ``(codec, coded_edges)`` boundary-codec argument of
    :func:`horovod_tpu.parallel.pipeline.pipeline_train_step` — only
    DCN-crossing activation hops get the PR 13 wire codec."""
    p = n_stages
    g = stage_size if stage_size else max(1, topology.size // max(1, p))
    ls = max(1, topology.local_size)
    if ls <= 1 or ls >= topology.size:
        return tuple([False] * p)

    def island(s: int) -> int:
        return ((s % p) * g) // ls

    return tuple(island(i) != island(i + 1) for i in range(p))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def sharded_axis0(mesh: Mesh, axis: str = WORLD_AXIS) -> NamedSharding:
    return NamedSharding(mesh, P(axis))
