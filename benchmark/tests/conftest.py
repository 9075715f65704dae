"""Tests of the benchmark itself, run by hand:

    python -m pytest benchmark/tests -q

They run on the CPU, on four forced host devices, and never report a
speed. ``tests/`` (tier-1) does not collect them.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(BENCH, "readers"), os.path.dirname(BENCH)]
