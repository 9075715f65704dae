"""Process/world state backend.

Plays the role of the reference's control-plane contexts
(mpi/mpi_context.{h,cc}: global/local/cross communicators;
gloo/gloo_context.cc:127-219 rendezvous) on top of the JAX distributed
coordinator. Topology:

- **rank/size** — process-level, like an MPI rank: the id the launcher gave
  the process (``HOROVOD_RANK``), ``jax.process_index`` in a world jax was
  initialised for by someone else; ``jax.process_count``.
- **local_rank/local_size** — position within the host (derived from
  HOROVOD_LOCAL_RANK env set by the launcher, or 0/1).
- **cross_rank/cross_size** — position across hosts at the same local rank
  (controller.h:119-127 accessors).

The backend also owns the *eager group mesh*: a 1-D mesh with exactly one
device per process, over which the eager named-tensor collectives execute. The
full device mesh (every chip) is exposed separately for SPMD training.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common import env as env_mod
from ..common.exceptions import HorovodInternalError
from ..parallel.mesh import WORLD_AXIS


class Backend:
    """World/topology state + array plumbing for eager collectives."""

    def __init__(self):
        self._initialized = False
        self._removed = False
        self._rank = 0
        self._proc_id = None
        self._size = 1
        self._local_rank = 0
        self._local_size = 1
        self._cross_rank = 0
        self._cross_size = 1
        self._group_mesh: Optional[Mesh] = None
        self._group_sharding = None
        self._rep_sharding = None
        self._distributed = False

    # -- lifecycle ---------------------------------------------------------

    def init(self):
        if self._initialized:
            return
        self._removed = False
        self._recoverable = False
        slot = None
        elastic = bool(os.environ.get(env_mod.HOROVOD_ELASTIC))
        if elastic:
            # Elastic worker: identity is (hostname, local_rank); the global
            # rank/size come from the rendezvous *every* init, so a reset
            # (shutdown+init) re-joins the new world — reference
            # gloo_context.cc:157-204 elastic re-init.
            from ..common.exceptions import WorkerRemovedError
            try:
                slot = self._fetch_elastic_slot()
            except WorkerRemovedError:
                # Scaled out before ever joining a world (removal racing the
                # first init). Don't blow up user code that sits outside the
                # @hvd.elastic.run wrapper: become an inert, removed, size-1
                # backend; the run wrapper checks `removed` and exits the
                # training loop cleanly.
                self._removed = True
                self._initialized = True
                return
            os.environ[env_mod.HOROVOD_TPU_NUM_PROCESSES] = str(slot.size)
            os.environ[env_mod.HOROVOD_TPU_PROCESS_ID] = str(slot.rank)
            os.environ[env_mod.HOROVOD_RANK] = str(slot.rank)
        coord = os.environ.get(env_mod.HOROVOD_TPU_COORDINATOR)
        nprocs = os.environ.get(env_mod.HOROVOD_TPU_NUM_PROCESSES)
        proc_id = None
        if coord and nprocs and int(nprocs) > 1:
            proc_id = int(os.environ.get(env_mod.HOROVOD_TPU_PROCESS_ID,
                                         os.environ.get(env_mod.HOROVOD_RANK, "0")))
            bind = None
            if coord == "@rendezvous":
                coord, bind = self._resolve_coordinator(proc_id)
            if elastic:
                # A peer crash must surface as a catchable error on the
                # survivors (reference: HorovodInternalError -> restore +
                # re-init), not a process abort. Recoverable mode stops the
                # coordination client from fatally terminating the process
                # on peer failure and makes shutdown() non-blocking when
                # peers are already gone.
                jax.config.update("jax_enable_recoverability", True)
                self._recoverable = True
            heartbeat = int(os.environ.get(
                env_mod.HOROVOD_TPU_HEARTBEAT_TIMEOUT,
                "10" if elastic else "100"))
            shutdown_t = int(os.environ.get(
                env_mod.HOROVOD_TPU_SHUTDOWN_TIMEOUT,
                "30" if elastic else "300"))
            jax.distributed.initialize(
                coordinator_address=coord, num_processes=int(nprocs),
                process_id=proc_id, coordinator_bind_address=bind,
                heartbeat_timeout_seconds=heartbeat,
                shutdown_timeout_seconds=shutdown_t)
            self._distributed = True
        # The rank is the one the launcher gave this process (HOROVOD_RANK,
        # the reference's contract): the launcher's output prefix, the
        # elastic driver and the failpoints number processes by it. jax's
        # own process index is the PJRT client's; on a TPU host libtpu
        # derives it from the chip's place in the grid, which need not
        # follow the launcher's slots (found on the four-chip v5e host,
        # PR 21). proc_id is the coordination service's numbering, kept for
        # what concerns that service (who hosts it, who leaves it last).
        self._proc_id = proc_id
        self._rank = jax.process_index() if proc_id is None else \
            int(os.environ.get(env_mod.HOROVOD_RANK, proc_id))
        self._size = jax.process_count()
        if slot is not None:
            self._local_rank = slot.local_rank
            self._local_size = slot.local_size
            self._cross_rank = slot.cross_rank
            self._cross_size = slot.cross_size
        else:
            self._local_rank = int(os.environ.get(env_mod.HOROVOD_LOCAL_RANK, "0"))
            self._local_size = int(os.environ.get(env_mod.HOROVOD_LOCAL_SIZE, "1"))
            self._cross_rank = int(os.environ.get(env_mod.HOROVOD_CROSS_RANK,
                                                  str(self._rank // max(self._local_size, 1))))
            self._cross_size = int(os.environ.get(env_mod.HOROVOD_CROSS_SIZE,
                                                  str(max(1, self._size // max(self._local_size, 1)))))
        # One device per process for the eager group mesh. Pick each process's
        # first local device, ordered by rank.
        per_proc = {}
        for d in jax.devices():
            per_proc.setdefault(d.process_index, d)
        if len(per_proc) != self._size:
            raise HorovodInternalError(
                f"expected one device per process ({self._size}), found "
                f"{len(per_proc)}")
        order = self._process_index_of_each_rank() if self._distributed \
            else sorted(per_proc)
        devs = [per_proc[i] for i in order]
        self._group_mesh = Mesh(np.array(devs), (WORLD_AXIS,))
        self._group_sharding = NamedSharding(self._group_mesh, P(WORLD_AXIS))
        self._rep_sharding = NamedSharding(self._group_mesh, P())
        self._initialized = True

    def _process_index_of_each_rank(self):
        """jax's process index of rank 0, 1, ...: every process posts its
        own under its rank in the coordination service's key-value store
        and reads the others' (no device program; the service is new with
        every ``jax.distributed.initialize``, so an elastic re-init posts
        into an empty store)."""
        from jax._src import distributed
        client = distributed.global_state.client
        timeout_ms = 1000 * int(float(os.environ.get(
            env_mod.HOROVOD_GLOO_TIMEOUT_SECONDS, "120")))
        client.key_value_set(f"hvd_tpu/process_index/{self._rank}",
                             str(jax.process_index()))
        order = [int(client.blocking_key_value_get(
            f"hvd_tpu/process_index/{r}", timeout_ms))
            for r in range(self._size)]
        if sorted(order) != list(range(self._size)):
            raise HorovodInternalError(
                f"ranks do not map one-to-one onto jax processes: {order}")
        return order

    def _fetch_elastic_slot(self):
        """Long-poll the elastic rendezvous for this worker's SlotInfo.

        Blocks (404-long-poll) while the driver is rebuilding the world, so a
        resetting worker naturally waits for the new assignment. Raises
        HorovodInternalError if this host was removed from the job
        (reference gloo_context.cc:157-204 throws on removed host)."""
        from ..runner.http_client import read_data_from_kvstore
        from ..runner.hosts import SlotInfo
        rdv_addr = os.environ[env_mod.HOROVOD_GLOO_RENDEZVOUS_ADDR]
        rdv_port = int(os.environ[env_mod.HOROVOD_GLOO_RENDEZVOUS_PORT])
        # A resume legitimately takes up to the driver's elastic timeout
        # (waiting for replacement hosts), which is much longer than the
        # plain gloo rendezvous timeout — don't kill surviving workers first.
        timeout = float(os.environ.get(
            "HOROVOD_ELASTIC_TIMEOUT",
            os.environ.get(env_mod.HOROVOD_GLOO_TIMEOUT_SECONDS, "600")))
        host = os.environ.get(env_mod.HOROVOD_HOSTNAME, "localhost")
        local_rank = os.environ.get(env_mod.HOROVOD_LOCAL_RANK, "0")
        # key carries the world version this process last belonged to so the
        # rendezvous never re-serves the world we are leaving
        last_version = int(os.environ.get("HOROVOD_TPU_WORLD_VERSION", "0"))
        try:
            data = read_data_from_kvstore(rdv_addr, rdv_port, "rank_and_size",
                                          f"{host}:{local_rank}:{last_version}",
                                          timeout=timeout)
        except TimeoutError as e:
            raise HorovodInternalError(
                f"elastic rendezvous did not assign {host}:{local_rank} a "
                f"rank within {timeout}s (job stopped?): {e}")
        text = data.decode()
        version = 0
        if "|" in text:
            version_s, text = text.split("|", 1)
            version = int(version_s)
        slot = SlotInfo.from_response_string(text)
        if slot.rank < 0:
            from ..common.exceptions import WorkerRemovedError
            raise WorkerRemovedError(
                f"slot {host}:{local_rank} was removed from the elastic job")
        os.environ["HOROVOD_TPU_WORLD_VERSION"] = str(version)
        return slot

    def _resolve_coordinator(self, proc_id: int):
        """Resolve the ``@rendezvous`` coordinator sentinel.

        The driver can't pick a race-free port on rank 0's host (reference
        has the same constraint — gloo_context.cc:70-90 solves it with the
        launcher's HTTP KV). Rank 0 binds a free port locally, publishes
        ``host:port`` to the rendezvous KV, and binds the coordination
        service on all interfaces; everyone else long-polls the key.
        Returns (coordinator_address, coordinator_bind_address|None).
        """
        from ..runner.http_client import (put_data_into_kvstore,
                                          read_data_from_kvstore)
        rdv_addr = os.environ[env_mod.HOROVOD_GLOO_RENDEZVOUS_ADDR]
        rdv_port = int(os.environ[env_mod.HOROVOD_GLOO_RENDEZVOUS_PORT])
        timeout = float(os.environ.get(env_mod.HOROVOD_GLOO_TIMEOUT_SECONDS,
                                       "120"))
        # The key carries the world version: during cascaded failures the
        # previous world's rank 0 may publish its (stale) address after the
        # rendezvous cleared the scope for the new world — a versioned key
        # can never satisfy a newer world's read.
        version = os.environ.get("HOROVOD_TPU_WORLD_VERSION", "0")
        key = f"addr.v{version}"
        if proc_id == 0:
            from ..runner.http_server import find_free_port
            port = find_free_port()
            host = os.environ.get(env_mod.HOROVOD_HOSTNAME, "127.0.0.1")
            if host in ("localhost", "::1"):
                host = "127.0.0.1"
            addr = f"{host}:{port}"
            put_data_into_kvstore(rdv_addr, rdv_port, "coordinator", key,
                                  addr.encode(), timeout=timeout)
            # Keep the port reserved only between probe and bind — the same
            # (small) race the reference accepts; binding on 0.0.0.0 makes
            # the advertised hostname irrelevant locally.
            return addr, f"0.0.0.0:{port}"
        addr = read_data_from_kvstore(rdv_addr, rdv_port, "coordinator",
                                      key, timeout=timeout).decode()
        return addr, None

    def _ordered_distributed_shutdown(self):
        """Tear down the JAX distributed client with coordinator-last
        ordering. ("Rank" below is the coordination service's process id,
        ``self._proc_id``: the service lives in process 0 of ITS numbering.)

        Recoverable mode (enabled for elastic worlds) removes the
        coordination service's shutdown barrier, so teardown order becomes a
        race: a non-zero rank whose ShutdownTask RPC finds rank 0's
        in-process coordinator already gone is killed by an absl LOG(FATAL)
        — uncatchable from Python, and the cause of the elastic scale-down
        flake (the removed worker died hard instead of exiting cleanly).
        Order is re-imposed through the launcher's KV, which outlives every
        world: non-zero ranks disconnect first and post a flag; rank 0
        collects the flags (bounded wait — a crashed peer never posts)
        before tearing the service down.

        The KV protocol applies ONLY when recoverability is actually on.
        With the barrier present (static worlds), a non-zero rank's
        ``jax.distributed.shutdown()`` blocks IN the barrier until rank 0
        also enters it — so the flag would only ever be posted after rank 0
        gave up waiting for it, turning every multi-process teardown into a
        full HOROVOD_TPU_SHUTDOWN_ORDER_TIMEOUT stall. There the barrier
        itself is the ordering guarantee (the service outlives every
        client), and all ranks simply meet in it."""
        rdv_addr = os.environ.get(env_mod.HOROVOD_GLOO_RENDEZVOUS_ADDR)
        rdv_port = os.environ.get(env_mod.HOROVOD_GLOO_RENDEZVOUS_PORT)
        if not rdv_addr or not rdv_port or self._size <= 1 \
                or not self._recoverable:
            jax.distributed.shutdown()
            return
        from ..runner.http_client import (put_data_into_kvstore,
                                          read_data_from_kvstore)
        import time as _time
        version = os.environ.get("HOROVOD_TPU_WORLD_VERSION", "0")
        scope = f"shutdown.v{version}"
        if self._proc_id != 0:
            try:
                jax.distributed.shutdown()
            finally:
                try:
                    put_data_into_kvstore(rdv_addr, int(rdv_port), scope,
                                          str(self._proc_id), b"1", timeout=5)
                except Exception:
                    pass
            return
        deadline = _time.monotonic() + float(os.environ.get(
            env_mod.HOROVOD_TPU_SHUTDOWN_ORDER_TIMEOUT, "10"))
        # Poll every pending rank in short rounds instead of blocking the
        # whole budget on the first one: a single dead low-rank peer must
        # not starve the wait for live higher-rank peers (that would
        # reintroduce the teardown race for them).
        pending = set(range(1, self._size))
        while pending and _time.monotonic() < deadline:
            for r in sorted(pending):
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    break
                try:
                    read_data_from_kvstore(rdv_addr, int(rdv_port), scope,
                                           str(r),
                                           timeout=min(1.0, remaining))
                    pending.discard(r)
                except Exception:
                    pass  # not posted yet (or dead peer): try others
        jax.distributed.shutdown()

    def shutdown(self):
        if self._distributed:
            try:
                self._ordered_distributed_shutdown()
            except Exception:
                pass
            self._distributed = False
            # Tear down the XLA backends so a later init() can call
            # jax.distributed.initialize() again with a NEW world — the
            # TPU-native analog of the reference's full C++ core
            # shutdown+re-init on elastic reset (torch/elastic.py:46,
            # gloo_context.cc:157-204). Device arrays die with the backend;
            # elastic state survives because State.save() keeps host copies.
            try:
                import jax._src.xla_bridge as xla_bridge
                xla_bridge._clear_backends()
                jax.clear_caches()
            except Exception as e:
                # A failed teardown makes elastic re-init silently reuse the
                # old world's backend — fail loudly there instead of
                # producing wrong-size meshes later.
                if os.environ.get(env_mod.HOROVOD_ELASTIC):
                    raise HorovodInternalError(
                        f"could not tear down XLA backends for elastic "
                        f"re-init (jax API change?): {e!r}")
                import logging
                logging.getLogger("horovod_tpu").warning(
                    "XLA backend teardown failed: %r", e)
        self._initialized = False
        self._group_mesh = None

    @property
    def initialized(self) -> bool:
        return self._initialized

    @property
    def removed(self) -> bool:
        """True when this worker was scaled out of the elastic job at init
        time and never joined the world (see init())."""
        return self._removed

    # -- topology ----------------------------------------------------------

    def rank(self) -> int:
        return self._rank

    def size(self) -> int:
        return self._size

    def local_rank(self) -> int:
        return self._local_rank

    def local_size(self) -> int:
        return self._local_size

    def cross_rank(self) -> int:
        return self._cross_rank

    def cross_size(self) -> int:
        return self._cross_size

    def is_homogeneous(self) -> bool:
        """Reference: mpi_controller.cc:26-82 homogeneity check. With a JAX
        backend every process addresses the same chip count per host."""
        return self._size % max(self._local_size, 1) == 0

    @property
    def group_mesh(self) -> Mesh:
        return self._group_mesh

    # -- array plumbing ----------------------------------------------------

    def to_global(self, local_value, batched: bool = False) -> jax.Array:
        """Lift this process's tensor to a stacked global array of shape
        (size, *s), sharded one slice per process over the group mesh.

        ``batched=True`` means the value already carries the leading
        (1, ...) block dim (e.g. produced on-device by build_pack_group) —
        the lift is then pure metadata: no eager reshape dispatch, and
        device_put of an on-device array to its own device is a no-op."""
        import jax.numpy as jnp
        x = jnp.asarray(local_value)
        local_dev = self._group_mesh.devices.flat[self._rank]
        shard = jax.device_put(x if batched else x[None], local_dev)
        global_shape = (self._size,) + tuple(shard.shape[1:])
        return jax.make_array_from_single_device_arrays(
            global_shape, self._group_sharding, [shard])

    def world_view(self, local_value) -> jax.Array:
        """Present this process's tensor as a 'replicated' global array over
        the group mesh with NO device dispatch: the array keeps its natural
        shape (no ``x[None]`` reshape launch) and each process contributes
        its own — genuinely different — shard. Only sound as input to a
        ``shard_map`` with ``in_specs=P()``, where the manual region sees
        each rank's own value (the step-replay program's zero-dispatch
        lift); consuming it as a true replicated value would read one
        rank's data as everyone's."""
        import jax.numpy as jnp
        x = jnp.asarray(local_value)
        local_dev = self._group_mesh.devices.flat[self._rank]
        shard = jax.device_put(x, local_dev)  # no-op when already resident
        return jax.make_array_from_single_device_arrays(
            tuple(x.shape), self._rep_sharding, [shard])

    def from_global(self, garr: jax.Array):
        """Extract this process's slice of a stacked (size, *s) result."""
        for s in garr.addressable_shards:
            if s.index[0].start == self._rank or self._size == 1:
                return s.data[0]
        # A missing shard means the array isn't laid out the way this rank
        # believes — reading any other shard would be silent data
        # corruption (ADVICE r1: fail loudly instead).
        raise HorovodInternalError(
            f"rank {self._rank}: no addressable shard for this rank in a "
            f"stacked global array (shape {garr.shape}; "
            f"{len(garr.addressable_shards)} addressable shards) — "
            f"world/mesh mismatch?")

    def from_replicated(self, garr: jax.Array):
        """Extract a replicated (out_specs=P()) result: the addressable shard
        IS the full value — a zero-dispatch read (no eager slice, which would
        cost a device round-trip per tensor)."""
        return garr.addressable_shards[0].data
