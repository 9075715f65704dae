"""Device time of the operations whose label (name, opcode, largest
result) matches one of ``match`` and, where the metric's file has
``scope``, whose op_name (the scope the program wrote) matches that pattern
too: regular expressions, in milliseconds a step, averaged over the traced
chips. ``what`` is

- ``self``: the operations' own device time;
- ``span``: the time from each one's start to its end, asynchronous pairs
  counted from the start of ``-start`` to the end of ``-done``;
- ``exposed``: the part of ``span`` during which no other operation runs
  on that chip.
"""

import stats
import tracecalc
from common import traced_devices


def per_step_ms(ctx, spec):
    match = stats.matcher(spec["match"])
    scope = stats.matcher([spec["scope"]]) if "scope" in spec else None
    out = []
    for dev, _, steps in traced_devices(ctx):
        if spec["what"] == "self":
            ns = tracecalc.matched_self_ns(dev, match, scope)
        elif spec["what"] == "span":
            ns = stats.total(tracecalc.matched_intervals(dev, match, scope))
        elif spec["what"] == "exposed":
            ns = stats.total(tracecalc.exposed(dev, match, scope))
        else:
            raise ValueError(f"trace_ops: unknown 'what' {spec['what']!r}")
        out.append(ns / 1e6 / steps)
    return out


def read(ctx, spec):
    found = per_step_ms(ctx, spec)
    return sum(found) / len(found) if found else None
