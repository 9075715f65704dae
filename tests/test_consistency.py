"""Debug-mode cross-rank consistency checking (HOROVOD_TPU_DEBUG_CONSISTENCY).

Adversarial 2-process tests mirroring the reference's mismatched-submission
error cases (test/test_torch.py / test_tensorflow.py error grids; coordinator
validation controller.cc:380-623): mismatched shape / dtype / op / name
across ranks must fail fast with a descriptive error on every rank instead
of hanging.
"""

import os

import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("HVD_TPU_SKIP_MULTIPROC") == "1",
    reason="multi-process tier disabled")


def _mp_env():
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_STALL_CHECK_DISABLE": "1",
        "HOROVOD_TPU_DEBUG_CONSISTENCY": "1",
    }


def _worker_shape_mismatch():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import TensorShapeMismatchError
    shape = (4,) if hvd.rank() == 0 else (5,)
    try:
        hvd.allreduce(np.ones(shape), name="t", op=hvd.Sum)
    except TensorShapeMismatchError as e:
        return ("raised", "Mismatched shape" in str(e))
    return ("no-error", None)


def _worker_dtype_mismatch():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import TensorDtypeMismatchError
    dtype = np.float32 if hvd.rank() == 0 else np.int32
    try:
        hvd.allreduce(np.ones(3, dtype=dtype), name="t", op=hvd.Sum)
    except TensorDtypeMismatchError as e:
        return ("raised", "Mismatched dtype" in str(e))
    return ("no-error", None)


def _worker_op_mismatch():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import ConsistencyError
    op = hvd.Sum if hvd.rank() == 0 else hvd.Min
    try:
        hvd.allreduce(np.ones(3), name="t", op=op)
    except ConsistencyError as e:
        return ("raised", "reduce op" in str(e))
    return ("no-error", None)


def _worker_name_mismatch():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import ConsistencyError
    name = "a" if hvd.rank() == 0 else "b"
    try:
        hvd.allreduce(np.ones(3), name=name, op=hvd.Sum)
    except ConsistencyError as e:
        return ("raised", "different tensor name" in str(e))
    return ("no-error", None)


def _worker_matching_ok():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    out = np.asarray(hvd.allreduce(np.ones(3), name="ok", op=hvd.Sum))
    # uneven allgather dim0 is legitimate and must pass the checker
    g = np.asarray(hvd.allgather(
        np.zeros((hvd.rank() + 1, 2), np.float32), name="ag"))
    outs = hvd.grouped_allreduce(
        [np.ones(2), np.ones((2, 2))], name="grp", op=hvd.Average)
    # fused broadcast (r4): matching submissions pass the checker too
    bp = hvd.broadcast_parameters(
        {"a": np.full((2,), float(hvd.rank())),
         "b": np.full((3, 2), float(hvd.rank()))}, root_rank=1)
    return (float(out[0]), g.shape[0], len(outs),
            float(np.asarray(bp["a"])[0]))


def _worker_equal_sizes_violation():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import TensorShapeMismatchError
    eng = hvd._engine()
    d0 = 2 if hvd.rank() == 0 else 3
    try:
        # equal_sizes=True is a caller contract (dim 0 matches everywhere);
        # debug mode validates dim 0 for it (unlike plain allgather, where
        # uneven dim 0 is legitimate)
        eng.allgather(np.ones((d0, 2), np.float32), name="eq",
                      equal_sizes=True).synchronize()
    except TensorShapeMismatchError as e:
        return ("raised", "Mismatched shape" in str(e))
    return ("no-error", None)


def _worker_grouped_broadcast_mismatch():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.common.exceptions import TensorShapeMismatchError
    shape = (2, 2) if hvd.rank() == 0 else (3, 2)
    try:
        hvd.broadcast_parameters({"w": np.ones(shape)}, root_rank=0)
    except TensorShapeMismatchError as e:
        return ("raised", "Mismatched shape" in str(e))
    return ("no-error", None)


@pytest.mark.integration
@pytest.mark.parametrize("worker,desc", [
    (_worker_shape_mismatch, "shape"),
    (_worker_dtype_mismatch, "dtype"),
    (_worker_op_mismatch, "op"),
    (_worker_name_mismatch, "name"),
    (_worker_grouped_broadcast_mismatch, "grouped-broadcast-shape"),
    (_worker_equal_sizes_violation, "equal-sizes-contract"),
])
def test_mismatch_raises_on_every_rank(worker, desc):
    from horovod_tpu.runner import run
    results = run(worker, np=2, env=_mp_env())
    assert results == [("raised", True), ("raised", True)], (desc, results)


@pytest.mark.integration
def test_matching_submissions_pass():
    from horovod_tpu.runner import run
    results = run(_worker_matching_ok, np=2, env=_mp_env())
    assert results == [(2.0, 3, 2, 1.0), (2.0, 3, 2, 1.0)], results
