"""Device time of the operations whose label matches one of ``match``
(regular expressions over name, HLO category and framework scope), in
milliseconds a step, averaged over the traced chips. ``what`` is

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
    out = []
    for dev, _, steps in traced_devices(ctx):
        if spec["what"] == "self":
            ns = tracecalc.matched_self_ns(dev, match)
        elif spec["what"] == "span":
            ns = stats.total(tracecalc.matched_intervals(dev, match))
        elif spec["what"] == "exposed":
            ns = stats.total(tracecalc.exposed(dev, match))
        else:
            raise ValueError(f"trace_ops: unknown 'what' {spec['what']!r}")
        out.append(ns / 1e6 / steps)
    return out


def read(ctx, spec):
    found = per_step_ms(ctx, spec)
    return sum(found) / len(found) if found else None
