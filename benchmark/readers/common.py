"""What the readers share: how to walk the worker's record and its trace.

A reader is ``read(ctx, spec) -> float | None``: ``ctx`` is what run.py
made of the worker's record (see run.py ``context``), ``spec`` the
metric's own json from ``layer_metrics/``. A reader that finds nothing to
read returns None, and the metric is left out of the line.
"""

from __future__ import annotations

import stats


def dig(record: dict, path: str):
    """The value at a dotted path; a list on the way is reduced to the
    median over its items; None where the path leads nowhere."""
    value = record
    for i, key in enumerate(path.split(".")):
        if isinstance(value, list):
            rest = ".".join(path.split(".")[i:])
            found = [dig(item, rest) for item in value]
            found = [x for x in found if x is not None]
            return stats.median(found) if found else None
        if not isinstance(value, dict) or key not in value:
            return None
        value = value[key]
    return value


def traced_devices(ctx: dict):
    """(device summary, the host's spans, traced steps) of every chip that
    was traced."""
    traced = ctx["record"].get("traced")
    if not traced:
        return []
    return [(dev, traced["trace"]["spans"], traced["steps"])
            for dev in traced["trace"]["devices"]]
