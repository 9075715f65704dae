"""Test configuration: force an 8-device CPU world before JAX initializes.

This mirrors the reference's keystone test pattern — genuine multi-participant
collectives on one host (SURVEY.md §4: tests run under ``mpirun -np 2``) — via
XLA's host-platform device multiplexing.
"""

import os

# Force CPU even on a machine that has a TPU: unit tests always run on the
# virtual 8-device CPU world.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
# Tests should never pick up a real coordinator config from the environment.
for _v in ("HOROVOD_TPU_COORDINATOR", "HOROVOD_TPU_NUM_PROCESSES",
           "HOROVOD_TPU_PROCESS_ID", "HOROVOD_TIMELINE"):
    os.environ.pop(_v, None)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    import jax
    from horovod_tpu.parallel.mesh import world_mesh
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 forced CPU devices, got {len(devs)}"
    return world_mesh(devs)
