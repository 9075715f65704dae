#!/usr/bin/env python3
"""A traced run by (scope, operation): device self time, ms a step, of every
operation whose ``op_name`` matches a pattern, and the sum a scope. The
reader is ``benchmark/scopes.py``'s; that file groups by scope PREFIX, which
hides what an operation is (ISSUE 33: ``router/scatter-add`` and
``router/jit(take_along_axis)/gather`` were 8.7 ms a step of the sparse-expert
cell under two prefixes). Needs no jax and no chip.

    python3 benchmark/run.py --workload <cell> --seed 0 --seconds 10 \\
        --trace 1 --out <dir>
    python3 tools/trace_by_operation.py <dir>
    python3 tools/trace_by_operation.py <file.xplane.pb[.gz]> --steps 2 \\
        --scopes attn ffn

``--scopes``: the scopes to keep and to sum by (an operation counts for the
first one its ``op_name`` holds as a whole path part, inside ``jvp(...)`` and
``transpose(...)`` too); by default the routed experts' four and ``embed``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark")
sys.path[:0] = [p for p in (BENCH, os.path.join(BENCH, "readers"))
                if p not in sys.path]

import scopes       # noqa: E402  (benchmark/scopes.py)
import xplane       # noqa: E402

ROUTED = ("router", "moe_dispatch", "experts", "moe_combine", "embed")
# the parts of an op_name that every operation of a scanned layer shares
NOISE = re.compile(r"jit\(train_step\)/|while/body/|closed_call/|checkpoint/")


def by_operation(path: str, steps: int, names) -> dict:
    """``{"busy_ms", "scopes": {scope: ms}, "operations": [[ms, calls a
    step, op_name, label], ...]}`` of the first chip of an xplane file,
    a step; operations largest first."""
    dev = scopes.summarize_file(path)["devices"][0]
    part = {n: re.compile(r"(^|/|\()%s(\)|/|$)" % re.escape(n))
            for n in names}
    total, calls, sums, busy = {}, {}, dict.fromkeys(names, 0.0), 0.0
    for _, _, self_ns, i in dev["ops"]:
        busy += self_ns
        scope = next((n for n in names if part[n].search(dev["scopes"][i])),
                     None)
        if scope is None:
            continue
        key = (NOISE.sub("", dev["scopes"][i]), dev["labels"][i])
        total[key] = total.get(key, 0.0) + self_ns
        calls[key] = calls.get(key, 0) + 1
        sums[scope] += self_ns
    return {"busy_ms": busy / 1e6 / steps,
            "scopes": {n: ns / 1e6 / steps for n, ns in sums.items()},
            "operations": [[ns / 1e6 / steps, calls[key] / steps, *key]
                           for key, ns in sorted(total.items(),
                                                 key=lambda kv: -kv[1])]}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("run", help="a directory kept with run.py --out, or an "
                                "xplane file")
    ap.add_argument("--steps", type=int,
                    help="traced steps (read from record.json of a run)")
    ap.add_argument("--scopes", nargs="+", default=list(ROUTED))
    ap.add_argument("--least", type=float, default=0.02,
                    help="operations under this many ms a step are summed, "
                         "not listed")
    args = ap.parse_args()
    path, steps = args.run, args.steps
    if os.path.isdir(path):
        with open(os.path.join(path, "record.json")) as fh:
            steps = steps or json.load(fh)["traced"]["steps"]
        path = xplane.newest_xplane(os.path.join(path, "trace"))
    if not steps:
        sys.exit("trace_by_operation: --steps is needed with a bare file")
    got = by_operation(path, steps, args.scopes)
    print("busy %.3f ms a step over %d steps" % (got["busy_ms"], steps))
    for ms, calls, name, label in got["operations"]:
        if ms >= args.least:
            print("%8.3f  %5.1f a step  %s | %s" % (ms, calls, name[-120:],
                                                   label[:70]))
    print("by scope:", {n: round(ms, 3) for n, ms in got["scopes"].items()})


if __name__ == "__main__":
    main()
