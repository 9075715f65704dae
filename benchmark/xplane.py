"""From the profiler's ``.xplane.pb`` to a compact summary that plain
python can reduce (stats.py, tracecalc.py, readers/): nothing but jax reads
the file.

What the v5e's trace looks like (looked at by hand, PR 22; ``python
benchmark/xplane.py <trace dir>`` prints it): one plane per chip,
``/device:TPU:<n>``, with these lines among others:

- ``XLA Ops``: one event per executed HLO operation, nested where an
  operation holds others (a ``while`` and the operations of its body). The
  event's NAME is the operation's whole HLO text (``%fusion.226 = (f32[...],
  ...) fusion(...), kind=kOutput, calls=...``; a Pallas kernel is a
  ``custom-call`` named after the kernel, ``%splash_mha_fwd_residuals.4``);
  there is no category or framework-scope stat to read.
- ``Async XLA Ops``: one event per asynchronous operation, from its
  ``-start`` to its ``-done`` (copies, slices, collectives). They overlap
  the operations of ``XLA Ops`` and are kept apart.
- ``XLA Modules``: one event per executed program (``jit_step(<id>)``).

Host threads are lines of the plane ``/host:CPU``;
``jax.profiler.TraceAnnotation`` spans are events of the python thread's
line, on the same clock as the device lines.

The summary::

    {"devices": [{"plane": "/device:TPU:0",
                  "labels": ["fusion.226 | fusion kOutput | bf16[4,2048,2048]",
                             ...],
                  "ops": [[start_ns, duration_ns, self_ns, label], ...],
                  "async": [[start_ns, duration_ns, label], ...],
                  "modules": [[name, start_ns, duration_ns], ...]}],
     "spans": [[name, start_ns, duration_ns], ...]}

Times are nanoseconds from the earliest event kept. ``self_ns`` is the
event's duration less the events nested in it. A label is the operation's
name, its opcode (with a fusion's kind or a custom call's target) and the
largest array it produces, parsed from the HLO text, so that a metric's
patterns can match any of the three and the breakdown can group under
names that survive recompilation.
"""

from __future__ import annotations

import glob
import math
import os
import re

import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."

_HLO = re.compile(r"^%(?P<name>\S+) = (?P<type>.*?) (?P<op>[a-z][a-z0-9\-]*)\(")
_ARRAY = re.compile(r"([a-z]+\d+[a-z0-9]*)\[([\d,]*)\]")
_KIND = re.compile(r", kind=(k\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def label_of(text: str) -> str:
    """``name | opcode | largest result`` of an event named by HLO text;
    any other name as it is."""
    m = _HLO.match(text)
    if m is None:
        return text[:200]
    op = m["op"]
    extra = _KIND.search(text) if op == "fusion" else (
        _TARGET.search(text) if op == "custom-call" else None)
    if extra:
        op += " " + extra[1]
    arrays = _ARRAY.findall(m["type"])
    big = max(arrays, default=None, key=lambda a: math.prod(
        int(d) for d in a[1].split(",") if d))
    return " | ".join([m["name"], op, f"{big[0]}[{big[1]}]" if big else ""])


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def summarize(profile) -> dict:
    """``profile`` is a ``jax.profiler.ProfileData``."""
    devices, spans = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"plane": plane.name, "labels": [], "ops": [],
                   "async": [], "modules": []}
            index, parsed = {}, {}

            def label(text):
                if text not in parsed:      # a name recurs every step
                    parsed[text] = index.setdefault(label_of(text),
                                                    len(index))
                return parsed[text]

            for line in plane.lines:
                if line.name == OPS_LINE:
                    events = list(line.events)
                    selfs = stats.self_times(
                        [(e.start_ns, e.duration_ns) for e in events])
                    dev["ops"] = [
                        [e.start_ns, e.duration_ns, self_ns, label(e.name)]
                        for e, self_ns in zip(events, selfs)]
                elif line.name == ASYNC_LINE:
                    dev["async"] = [
                        [e.start_ns, e.duration_ns, label(e.name)]
                        for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [[e.name, e.start_ns, e.duration_ns]
                                      for e in line.events]
            dev["labels"] = list(index)
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    starts = [o[0] for d in devices for o in d["ops"] + d["async"]] + \
        [m[1] for d in devices for m in d["modules"]] + [s[1] for s in spans]
    t0 = min(starts, default=0.0)
    for d in devices:
        for o in d["ops"] + d["async"]:
            o[0] -= t0
        for m in d["modules"]:
            m[1] -= t0
    for s in spans:
        s[1] -= t0
    devices.sort(key=lambda d: d["plane"])
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def summarize_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(path))


def describe(profile, top: int = 12) -> str:
    """Planes, lines and the names that take most time on each line: what
    to look at by hand before trusting a matcher."""
    out = []
    for plane in profile.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            by_name = {}
            for e in events:
                n, t = by_name.get(e.name, (0, 0.0))
                by_name[e.name] = (n + 1, t + e.duration_ns)
            out.append(f"  LINE {line.name!r}: {len(events)} events, "
                       f"{len(by_name)} names")
            for name, (n, t) in sorted(by_name.items(),
                                       key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {t / 1e6:10.3f} ms  x{n:<5d} {name[:100]}")
            if events:
                e = events[len(events) // 2]
                shown = [(k, (v[:80] if isinstance(v, str) else v))
                         for k, v in e.stats][:12]
                out.append(f"    stats of {e.name[:60]!r}: {shown}")
                out.append(f"    label of it: {label_of(e.name)!r}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    from jax.profiler import ProfileData
    target = sys.argv[1]
    if os.path.isdir(target):
        target = newest_xplane(target)
    print(describe(ProfileData.from_file(target)))
