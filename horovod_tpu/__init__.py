"""horovod_tpu — a TPU-native distributed deep-learning training framework with
Horovod's capabilities, rebuilt on JAX/XLA/pjit/Pallas over ICI/DCN.

Public API parity with the reference's frontends (horovod/torch/mpi_ops.py,
horovod/tensorflow/__init__.py, horovod/common/basics.py):

    import horovod_tpu as hvd
    hvd.init()
    h = hvd.allreduce_async(grads, name="grads", op=hvd.Average)
    out = hvd.synchronize(h)

plus the TPU-native SPMD surface (``hvd.mesh()``, in-pjit collectives in
``horovod_tpu.ops``, ``distributed_optimizer`` in ``horovod_tpu.optimizer``).
"""

from __future__ import annotations

from typing import Optional, Sequence

# XLA latency-hiding-scheduler knob (HOROVOD_TPU_XLA_LHS=1) must land in
# XLA_FLAGS before anything touches a jax backend (flags are parsed at
# backend init, not at jax import; nothing in this package's import
# initialises a backend).
from .common.env import apply_xla_lhs as _apply_xla_lhs
_apply_xla_lhs()

from .common.reduce_ops import (ReduceOp, Average, Sum, Adasum, Min, Max, Product,
                                handle_average_backwards_compatibility)
from .common.exceptions import (HorovodInternalError, HostsUpdatedInterrupt,
                                DuplicateNameError)
from .core.state import global_state
from .version import __version__


# ---------------------------------------------------------------------------
# Lifecycle (parity: common/basics.py:33-120)
# ---------------------------------------------------------------------------

def init(comm=None):
    """Initialize the runtime. In a multi-process launch (under ``tpurun`` or
    with HOROVOD_TPU_COORDINATOR set) this joins the JAX distributed
    coordinator; standalone it is a size-1 world."""
    global_state().init()


def shutdown():
    global_state().shutdown()


def is_initialized() -> bool:
    return global_state().initialized


def _engine():
    st = global_state()
    if not st.initialized:
        raise ValueError("horovod_tpu has not been initialized; run hvd.init() first.")
    return st.engine


def _backend():
    st = global_state()
    if not st.initialized:
        raise ValueError("horovod_tpu has not been initialized; run hvd.init() first.")
    return st.backend


# ---------------------------------------------------------------------------
# Topology (parity: common/basics.py rank/size/local_rank/...)
# ---------------------------------------------------------------------------

def rank() -> int:
    return _backend().rank()


def size() -> int:
    return _backend().size()


def local_rank() -> int:
    return _backend().local_rank()


def local_size() -> int:
    return _backend().local_size()


def cross_rank() -> int:
    return _backend().cross_rank()


def cross_size() -> int:
    return _backend().cross_size()


def is_homogeneous() -> bool:
    return _backend().is_homogeneous()


def mesh():
    """The eager 1-D world mesh (one device per process)."""
    return _backend().group_mesh


# Build-introspection parity (common/basics.py *_built/_enabled): the TPU build
# has exactly one data plane — XLA collectives.
def mpi_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def nccl_built() -> bool:
    return False


def gloo_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def xla_built() -> bool:
    return True


def xla_enabled() -> bool:
    return True


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


# ---------------------------------------------------------------------------
# Collectives — async (parity: torch/mpi_ops.py allreduce_async/poll/synchronize)
# ---------------------------------------------------------------------------

def allreduce_async(tensor, name: Optional[str] = None, op=None, average=None,
                    prescale_factor: float = 1.0, postscale_factor: float = 1.0):
    op = handle_average_backwards_compatibility(op, average)
    if op == Adasum:
        from .ops.adasum import adasum_allreduce_handle
        return adasum_allreduce_handle(_engine(), tensor, name,
                                       prescale_factor=prescale_factor,
                                       postscale_factor=postscale_factor)
    return _engine().allreduce(tensor, name=name, op=op,
                               prescale_factor=prescale_factor,
                               postscale_factor=postscale_factor)


def allreduce(tensor, name: Optional[str] = None, op=None, average=None,
              prescale_factor: float = 1.0, postscale_factor: float = 1.0):
    return allreduce_async(tensor, name, op, average, prescale_factor,
                           postscale_factor).synchronize()


def grouped_allreduce_async(tensors: Sequence, name: Optional[str] = None, op=None,
                            average=None, prescale_factor: float = 1.0,
                            postscale_factor: float = 1.0):
    op = handle_average_backwards_compatibility(op, average)
    if op == Adasum:
        # Adasum coefficients are per-tensor (adasum.h:338-398), so fusing
        # tensors into one buffer would change the numerics — run per tensor.
        from .ops.adasum import adasum_allreduce_handle
        eng = _engine()
        return [adasum_allreduce_handle(eng, t,
                                        None if name is None else f"{name}.{i}",
                                        prescale_factor=prescale_factor,
                                        postscale_factor=postscale_factor)
                for i, t in enumerate(tensors)]
    return _engine().grouped_allreduce(tensors, name=name, op=op,
                                       prescale_factor=prescale_factor,
                                       postscale_factor=postscale_factor)


def grouped_allreduce(tensors: Sequence, name: Optional[str] = None, op=None,
                      average=None, prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0):
    return [h.synchronize() for h in
            grouped_allreduce_async(tensors, name, op, average, prescale_factor,
                                    postscale_factor)]


def allgather_async(tensor, name: Optional[str] = None):
    return _engine().allgather(tensor, name=name)


def allgather(tensor, name: Optional[str] = None):
    return allgather_async(tensor, name).synchronize()


def broadcast_async(tensor, root_rank: int, name: Optional[str] = None):
    return _engine().broadcast(tensor, root_rank, name=name)


def broadcast(tensor, root_rank: int, name: Optional[str] = None):
    return broadcast_async(tensor, root_rank, name).synchronize()


def alltoall_async(tensor, splits=None, name: Optional[str] = None):
    return _engine().alltoall(tensor, splits=splits, name=name)


def alltoall(tensor, splits=None, name: Optional[str] = None):
    """Without ``splits``: returns just the received tensor, drop-in with the
    reference frontend (torch/mpi_ops.py alltoall). With ``splits``: returns
    ``(tensor, received_splits)`` per operations.cc:951-1002 semantics."""
    out, recv_splits = alltoall_async(tensor, splits, name).synchronize()
    if splits is None:
        return out
    return out, recv_splits


def reducescatter_async(tensor, name: Optional[str] = None, op=None):
    op = ReduceOp.SUM if op is None else ReduceOp(op)
    return _engine().reducescatter(tensor, name=name, op=op)


def reducescatter(tensor, name: Optional[str] = None, op=None):
    return reducescatter_async(tensor, name, op).synchronize()


def barrier():
    _engine().barrier()


def metrics_snapshot() -> dict:
    """Plain nested dict of every registered metric (counters, gauges,
    histograms, event logs) from the process-wide registry
    (``horovod_tpu.metrics``): wire bytes by op kind/dtype, dispatch counts,
    fusion-bucket fill, enqueue→complete latency histograms, replay
    arm/fallback counters, elastic membership events, autotune knobs.

    Works before ``hvd.init()`` (the registry is process-wide); instruments
    populate as subsystems run. ``HOROVOD_TPU_METRICS=0`` disables
    collection (the snapshot is then empty). See docs/observability.md for
    the metric names and the Prometheus ``GET /metrics`` scrape endpoint."""
    from . import metrics as _metrics
    return _metrics.snapshot()


def step_heartbeat(step: Optional[int] = None):
    """SPMD-path liveness signal for the stall inspector: call once per
    (jitted) train step. When a rendezvous KV is present, rank 0 attributes
    hangs to the rank whose heartbeat stopped advancing
    (stall_inspector.h:70-92 cross-rank attribution)."""
    st = global_state()
    if st.stall_inspector is not None:
        st.stall_inspector.record_heartbeat(step)


def poll(handle) -> bool:
    return handle.poll()


def synchronize(handle):
    return handle.synchronize()


def join() -> int:
    """Join op (parity: operations.cc:1004-1040 EnqueueTensorJoin / torch
    join). A rank that is out of data calls ``join()`` and keeps matching the
    other ranks' collectives with zero-tensor substitutes
    (tensor_queue.h:39-41) until every rank has joined; returns the last rank
    to join. Ranks may process different batch counts without hanging:

        while have_data:
            hvd.allreduce(grads, ...)
        last = hvd.join()
    """
    return _engine().join()


# Convenience re-exports
from . import optimizer  # noqa: E402
DistributedOptimizer = optimizer.DistributedOptimizer
DistributedDeltaAdasumOptimizer = optimizer.DistributedDeltaAdasumOptimizer
# the SPMD optax wrapper (hvd.distributed(inner, shard_optimizer=True) is
# the ZeRO-1 optimizer-state-sharded mode, docs/sharded_optimizer.md)
distributed = optimizer.distributed
from .ops.compression import Compression  # noqa: E402
from . import functions as _functions  # noqa: E402
broadcast_parameters = _functions.broadcast_parameters
broadcast_object = _functions.broadcast_object
allgather_object = _functions.allgather_object
allreduce_sparse = _functions.allreduce_sparse
broadcast_optimizer_state = _functions.broadcast_optimizer_state
step_begin = _functions.step_begin
step_end = _functions.step_end
step = _functions.step
from . import metrics  # noqa: E402
from . import faults  # noqa: E402
from . import elastic  # noqa: E402

__all__ = [
    "init", "shutdown", "is_initialized", "rank", "size", "local_rank",
    "local_size", "cross_rank", "cross_size", "is_homogeneous", "mesh",
    "allreduce", "allreduce_async", "grouped_allreduce", "grouped_allreduce_async",
    "allgather", "allgather_async", "broadcast", "broadcast_async",
    "alltoall", "alltoall_async", "reducescatter", "reducescatter_async",
    "barrier", "join", "poll", "synchronize", "step_heartbeat",
    "step_begin", "step_end", "step", "metrics_snapshot", "metrics",
    "faults",
    "broadcast_parameters", "broadcast_object", "allgather_object",
    "allreduce_sparse",
    "broadcast_optimizer_state",
    "DistributedOptimizer", "DistributedDeltaAdasumOptimizer",
    "distributed", "Compression", "optimizer", "elastic",
    "ReduceOp", "Average", "Sum", "Adasum", "Min", "Max", "Product",
    "HorovodInternalError", "HostsUpdatedInterrupt", "DuplicateNameError",
    "__version__",
]
