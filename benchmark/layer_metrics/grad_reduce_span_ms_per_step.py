"""Time a step during which an asynchronous collective fusion is in
flight, in milliseconds, averaged over the traced chips.

The TPU compiler writes such a collective as two ``fusion kCustom`` on the
``XLA Ops`` line, ``async-collective-start.N`` and ``async-collective-done.N``,
and carries the transfer inside the fusions between them; the line of
asynchronous operations has no event for it, so ``trace_ops``'s ``span``
reads the two fusions alone. Here the k-th start of a number is paired with
the k-th done of that number. 0 where the program has no such fusion (as
``trace_ops`` reads a program without its operations); None where the two
do not pair up (another compiler's numbering): the metric is then left out.
"""

import re

import stats
import tracecalc
from common import traced_devices


def pairs(dev, start, done):
    """[(start's start, done's end), ...] of one chip, or None where a
    number has another count of starts than of dones, or a done that ends
    before its start."""
    by_number = {}
    for begin, dur, _, label in sorted(dev["ops"]):
        name = tracecalc.op_name(dev["labels"][label])
        for kind, found in ((0, start.match(name)), (1, done.match(name))):
            if found:
                by_number.setdefault(found[1], ([], []))[kind].append(
                    (begin, begin + dur))
    out = []
    for starts, dones in by_number.values():
        if len(starts) != len(dones):
            return None
        out += [(s[0], d[1]) for s, d in zip(starts, dones)]
    return out if all(e > s for s, e in out) else None


def read(ctx, spec):
    start, done = re.compile(spec["start"]), re.compile(spec["done"])
    found = []
    for dev, _, steps in traced_devices(ctx):
        paired = pairs(dev, start, done)
        if paired is None:
            return None
        found.append(stats.total(stats.union(paired)) / 1e6 / steps)
    return sum(found) / len(found) if found else None
