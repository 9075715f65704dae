"""Join-op tests (reference: operations.cc:1004-1040 EnqueueTensorJoin,
zero-tensor substitution tensor_queue.h:39-41, torch Join tests): ranks
processing different batch counts must train to completion without hanging,
and join() returns the last joining rank.
"""

import os

import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("HVD_TPU_SKIP_MULTIPROC") == "1",
    reason="multi-process tier disabled")


def _mp_env(extra=None):
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_STALL_CHECK_DISABLE": "1",
    }
    env.update(extra or {})
    return env


def _worker_ragged_allreduce():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    rank = hvd.rank()
    n_batches = 3 if rank == 0 else 6   # rank 0 runs out of data first
    results = []
    for b in range(n_batches):
        out = np.asarray(hvd.allreduce(np.ones(4) * (rank + 1),
                                       name=f"b{b}", op=hvd.Sum))
        results.append(float(out[0]))
    last = hvd.join()
    # batches 0-2: both ranks contribute (1 + 2); batches 3-5: rank 0 is
    # joined and substitutes zeros, so only rank 1's tensor lands — the
    # parent test asserts these values
    return (results, last)


def _worker_ragged_grouped():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    rank = hvd.rank()
    n_batches = 2 if rank == 0 else 4
    sums = []
    for b in range(n_batches):
        outs = hvd.grouped_allreduce(
            [np.ones(3) * (rank + 1), np.ones((2, 2)) * (rank + 1)],
            name=f"g{b}", op=hvd.Sum)
        sums.append([float(np.asarray(o).ravel()[0]) for o in outs])
    last = hvd.join()
    return (sums, last)


def _worker_mixed_ops_after_join():
    """Rank 0 joins while rank 1 still runs broadcast + allgather +
    reducescatter — substitutes must match every op kind."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    rank = hvd.rank()
    out = {}
    if rank == 0:
        out["last"] = hvd.join()
        return out
    out["bcast"] = float(np.asarray(
        hvd.broadcast(np.full((3,), 7.0), root_rank=1, name="bc"))[0])
    g = np.asarray(hvd.allgather(np.ones((2, 2)), name="ag"))
    out["gather_rows"] = int(g.shape[0])
    rs = np.asarray(hvd.reducescatter(np.ones((4, 2)), name="rs"))
    out["rs"] = float(rs[0, 0])
    out["last"] = hvd.join()
    return out


def test_single_process_join():
    import horovod_tpu as hvd
    hvd.init()
    assert hvd.join() == 0


@pytest.mark.integration
def test_ragged_batches_allreduce():
    from horovod_tpu.runner import run
    results = run(_worker_ragged_allreduce, np=2, env=_mp_env())
    (r0, last0), (r1, last1) = results
    assert r0 == [3.0] * 3, r0
    assert r1 == [3.0] * 3 + [2.0] * 3, r1
    # rank 1 joined last
    assert last0 == last1 == 1


@pytest.mark.integration
def test_ragged_batches_grouped():
    from horovod_tpu.runner import run
    results = run(_worker_ragged_grouped, np=2, env=_mp_env())
    (s0, last0), (s1, last1) = results
    assert s0 == [[3.0, 3.0]] * 2, s0
    assert s1 == [[3.0, 3.0]] * 2 + [[2.0, 2.0]] * 2, s1
    assert last0 == last1 == 1


@pytest.mark.integration
def test_mixed_ops_under_join():
    from horovod_tpu.runner import run
    results = run(_worker_mixed_ops_after_join, np=2, env=_mp_env())
    r0, r1 = results
    assert r0 == {"last": 1}, r0
    assert r1["bcast"] == 7.0
    assert r1["gather_rows"] == 4      # 2 rows from rank1 + 2 zero rows
    assert r1["rs"] in (1.0,)          # zeros from rank 0 don't change sum
    assert r1["last"] == 1


@pytest.mark.integration
def test_join_with_debug_consistency():
    """The two features compose: substitutes send wildcard rows."""
    from horovod_tpu.runner import run
    results = run(_worker_ragged_allreduce, np=2,
                  env=_mp_env({"HOROVOD_TPU_DEBUG_CONSISTENCY": "1"}))
    assert results[0][0] == [3.0] * 3
    assert results[1][0] == [3.0] * 3 + [2.0] * 3


def _worker_joined_root_broadcast():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import HorovodInternalError
    if hvd.rank() == 0:
        try:
            hvd.join()
            return "no-error"
        except HorovodInternalError as e:
            return "raised" if "no data to broadcast" in str(e) else str(e)
    try:
        hvd.broadcast(np.ones(3), root_rank=0, name="bad")
        return "no-error"
    except HorovodInternalError as e:
        return "raised" if "has already joined" in str(e) else str(e)


@pytest.mark.integration
def test_broadcast_from_joined_root_errors():
    """A joined broadcast root would silently broadcast zeros — both sides
    must error instead (review r2 finding)."""
    from horovod_tpu.runner import run
    results = run(_worker_joined_root_broadcast, np=2, env=_mp_env())
    assert results == ["raised", "raised"], results


def _worker_ragged_grouped_overflow():
    """24 tensors per grouped call: k > _JOIN_META_SLOTS (16), so the
    advertisement spills into the deterministic overflow exchange — the
    joined rank must reconstruct all 24 substitutes from head + overflow."""
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.core.engine import _JOIN_META_SLOTS
    rank = hvd.rank()
    n_tensors = _JOIN_META_SLOTS + 8
    n_batches = 2 if rank == 0 else 4
    sums = []
    for b in range(n_batches):
        outs = hvd.grouped_allreduce(
            [np.ones((2, i + 1)) * (rank + 1) for i in range(n_tensors)],
            name=f"ov{b}", op=hvd.Sum)
        sums.append([float(np.asarray(o).ravel()[0]) for o in outs])
    last = hvd.join()
    return (sums, last, n_tensors)


@pytest.mark.integration
def test_ragged_grouped_metadata_overflow():
    from horovod_tpu.runner import run
    results = run(_worker_ragged_grouped_overflow, np=2, env=_mp_env())
    (s0, last0, n), (s1, last1, _) = results
    assert all(v == 3.0 for batch in s0 for v in batch), s0[:1]
    assert all(v == 3.0 for batch in s1[:2] for v in batch)
    # rank 0 joined: batches 2-3 see only rank 1's ones
    assert all(v == 2.0 for batch in s1[2:] for v in batch), s1[2:][:1]
    assert len(s1[0]) == n
    assert last0 == last1 == 1
