"""A traced run by the names the program wrote
(``horovod_tpu/common/scopes.py``), by hand:

    python3 benchmark/run.py --workload <cell> --seed 0 --seconds 10 \
        --trace 1 --out <dir>
    python3 benchmark/scopes.py <dir>

prints the time by scope prefix and the largest operations outside forward,
backward and optimizer, from the record's own summary (xplane.py: ONE
walker reads the profiler's file, and since PR 36 the worker records every
operation's ``op_name`` beside its label). The metrics that read the scopes
(``readers/trace_scopes.py``, a file each in ``layer_metrics/``) are
declared in ``BENCHMARK.json`` and on the traced line; nothing is read by
hand and not declared.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [p for p in (HERE, os.path.join(HERE, "readers"))
                if p not in sys.path]

import files        # noqa: E402
import stats        # noqa: E402
import tracecalc    # noqa: E402
import xplane       # noqa: E402

summarize_file = xplane.summarize_file      # tools/trace_by_operation.py


def scope_metrics() -> list:
    """Names of the metric files that the scope reader reads."""
    folder = os.path.join(HERE, "layer_metrics")
    names = sorted(f[:-5] for f in os.listdir(folder) if f.endswith(".json"))
    return [n for n in names if files.load_json(
        os.path.join(folder, n + ".json"))["reader"] == "trace_scopes"]


def by_prefix(dev: dict, n: int = 30) -> list:
    """[[scope without its last part (the primitive), seconds], ...] by
    self time, largest first."""
    total = {}
    for _, _, self_ns, i in dev["ops"]:
        prefix = dev["scopes"][i].rpartition("/")[0] or "(no op_name)"
        total[prefix] = total.get(prefix, 0.0) + self_ns
    return [[k, t / 1e9] for k, t in sorted(
        total.items(), key=lambda kv: -kv[1])[:n]]


def main(out_dir: str) -> None:
    """Of the first chip of a run kept with ``run.py --out``."""
    traced = files.load_json(os.path.join(out_dir, "record.json"))["traced"]
    dev, steps = traced["trace"]["devices"][0], traced["steps"]
    print(f"by scope, ms a step, first chip ({dev['plane']}):")
    for prefix, seconds in by_prefix(dev):
        print(f"  {seconds * 1e3 / steps:9.3f}  {prefix}")
    spec, _ = files.layer_metric("unscoped_ms_per_step")
    inside = stats.matcher(spec["exclude"])
    rest = {**dev, "ops": [o for o in dev["ops"]
                           if not inside(dev["scopes"][o[3]])]}
    print("largest operations outside forward, backward and optimizer, "
          "ms a step:")
    for group, seconds in tracecalc.top_ops(rest, 8):
        print(f"  {seconds * 1e3 / steps:9.3f}  {group}")


if __name__ == "__main__":
    main(sys.argv[1])
