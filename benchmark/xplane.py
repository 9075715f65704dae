"""From the profiler's ``.xplane.pb`` to a compact summary that plain
python can reduce (stats.py, tracecalc.py, readers/). ONE walker reads the
file, over the protobuf wire format, thirty lines: it imports neither jax
nor a generated ``xplane_pb2``, so the parent process could read a trace
too (``jax.profiler.ProfileData`` read it until PR 36 and does not show an
event-metadata entry's stats, which is where the ``op_name`` is).

What the v5e's trace looks like (looked at by hand, PR 22 and PR 24;
``python benchmark/xplane.py <trace dir>`` prints it): one plane per chip,
``/device:TPU:<n>``, with these lines among others:

- ``XLA Ops``: one event per executed HLO operation, nested where an
  operation holds others (a ``while`` and the operations of its body). The
  event's NAME (its metadata entry's) is the operation's HLO text up to its
  attributes (``%fusion.226 = (f32[...], ...) fusion(...), kind=kOutput,
  calls=...``; a Pallas kernel is a ``custom-call`` named after the kernel,
  ``%splash_mha_fwd_residuals.4``), with no ``metadata={...}``. The
  ``op_name`` the program wrote (``horovod_tpu/common/scopes.py``; jax adds
  ``jit(...)``, ``jvp(`` and ``transpose(``) is a STAT of that metadata
  entry, named ``tf_op``: the name and a colon,
  ``jit(train_step)/transpose(jvp())/layers/while/body/closed_call/ffn/
  btd,df->btf/dot_general:``. Beside it there: ``hlo_category``, ``flops``,
  ``bytes_accessed``, ``source`` (file:line), ``program_id``. Operations
  that XLA made itself (copies, the parameters' layout changes) carry no
  ``tf_op``: their scope is ``""``.
- ``Async XLA Ops``: one event per asynchronous operation, from its
  ``-start`` to its ``-done`` (copies, slices, collectives). They overlap
  the operations of ``XLA Ops`` and are kept apart.
- ``XLA Modules``: one event per executed program (``jit_step(<id>)``).

Host threads are lines of the plane ``/host:CPU``;
``jax.profiler.TraceAnnotation`` spans are events of the python thread's
line, on the same clock as the device lines. Those the benchmark wrote
(``bench.``) and those the program writes (``hvd.``) are kept.

The summary::

    {"devices": [{"plane": "/device:TPU:0",
                  "labels": ["fusion.226 | fusion kOutput | bf16[4,2048,2048]",
                             ...],
                  "scopes": ["jit(train_step)/jvp()/layers/.../dot_general",
                             ...],
                  "ops": [[start_ns, duration_ns, self_ns, operation], ...],
                  "async": [[start_ns, duration_ns, operation], ...],
                  "modules": [[name, start_ns, duration_ns], ...]}],
     "spans": [[name, start_ns, duration_ns], ...]}

Times are whole nanoseconds (the file's picoseconds cut, as ``ProfileData``
cut them) from the earliest event kept. ``self_ns`` is the event's duration
less the events nested in it. An operation is an index into ``labels`` and
``scopes``, which run in parallel: one (label, scope) pair, since
``fusion.3`` of two programs may share a label and not a scope. A label is
the operation's name, its opcode (with a fusion's kind or a custom call's
target) and the largest array it produces, parsed from the HLO text, so
that a metric's patterns can match any of the three and the breakdown can
group under names that survive recompilation.
"""

from __future__ import annotations

import glob
import gzip
import math
import os
import re

import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIXES = ("bench.", "hvd.")
SCOPE_STAT = "tf_op"

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


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def fields(buf):
    """(field number, value) of every field of one protobuf message: an int
    for a varint, the bytes for anything else."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"wire type {kind} at byte {i}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def planes(data):
    """Of every plane of a serialized XSpace: its name, each event-metadata
    id's name (an operation's HLO text, a span's name) and scope, and its
    lines in the file's order as (name, [(metadata id, start ns, duration
    ns), ...]). Field numbers are xplane.proto's."""
    for number, plane in fields(memoryview(data)):
        if number != 1:                                 # XSpace.planes
            continue
        name, stat_names, metadata, lines = "", {}, [], []
        for n, v in fields(plane):
            if n == 2:                                  # XPlane.name
                name = text(v)
            elif n == 3:                                # .lines
                t0, line, found = 0, "", []
                for m, w in fields(v):
                    if m == 2:                          # XLine.name
                        line = text(w)
                    elif m == 3:                        # .timestamp_ns
                        t0 = w
                    elif m == 4:                        # .events
                        found.append(dict(fields(w)))
                # XEvent: metadata_id, offset_ps, duration_ps
                lines.append((line, [
                    (e[1], t0 + e.get(2, 0) // 1000, e.get(3, 0) // 1000)
                    for e in found]))
            elif n == 4:                                # .event_metadata
                metadata.append(dict(fields(v))[2])
            elif n == 5:                                # .stat_metadata
                entry = dict(fields(dict(fields(v))[2]))
                stat_names[entry.get(1)] = text(entry.get(2, b""))
        names, scopes = {}, {}
        for entry in metadata:
            ident = None
            for n, v in fields(entry):
                if n == 1:                              # XEventMetadata.id
                    ident = v
                elif n == 2:                            # .name
                    names[ident] = text(v)
                elif n == 5:                            # .stats
                    stat = dict(fields(v))
                    if stat_names.get(stat.get(1)) == SCOPE_STAT:
                        scopes[ident] = text(stat.get(5, b"")).rstrip(":")
        yield name, names, scopes, lines


def summarize(data) -> dict:
    """The summary of a serialized XSpace."""
    devices, spans = [], []
    for plane, names, scopes, lines in planes(data):
        if DEVICE_PLANE.match(plane):
            dev = {"plane": plane, "ops": [], "async": [], "modules": []}
            index, seen = {}, {}

            def operation(m):
                if m not in seen:       # a metadata id recurs every step
                    seen[m] = index.setdefault(
                        (label_of(names.get(m, "")), scopes.get(m, "")),
                        len(index))
                return seen[m]

            for line, events in lines:
                if line == OPS_LINE:
                    selfs = stats.self_times([(s, d) for _, s, d in events])
                    dev["ops"] = [[s, d, self_ns, operation(m)]
                                  for (m, s, d), self_ns in zip(events, selfs)]
                elif line == ASYNC_LINE:
                    dev["async"] = [[s, d, operation(m)]
                                    for m, s, d in events]
                elif line == MODULES_LINE:
                    dev["modules"] = [[names.get(m, ""), s, d]
                                      for m, s, d in events]
            dev["labels"] = [k[0] for k in index]
            dev["scopes"] = [k[1] for k in index]
            devices.append(dev)
        elif plane == HOST_PLANE:
            spans += [[names[m], s, d] for _, events in lines
                      for m, s, d in events
                      if m in names and names[m].startswith(SPAN_PREFIXES)]
    starts = [o[0] for d in devices for o in d["ops"] + d["async"]] + \
        [m[1] for d in devices for m in d["modules"]] + [s[1] for s in spans]
    t0 = min(starts, default=0)
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


def read_file(path: str) -> bytes:
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        return f.read()


def summarize_file(path: str) -> dict:
    return summarize(read_file(path))


def describe(data, top: int = 12) -> str:
    """Planes, lines and the names that take most time on each line, with
    the scope of each: what to look at by hand before trusting a matcher."""
    out = []
    for plane, names, scopes, lines in planes(data):
        out.append(f"PLANE {plane!r}: {len(lines)} lines, {len(names)} names, "
                   f"{len(scopes)} with a {SCOPE_STAT}")
        for line, events in lines:
            by_name = {}
            for m, _, d in events:
                n, t = by_name.get(m, (0, 0))
                by_name[m] = (n + 1, t + d)
            out.append(f"  LINE {line!r}: {len(events)} events, "
                       f"{len(by_name)} names")
            for m, (n, t) in sorted(by_name.items(),
                                    key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {t / 1e6:10.3f} ms  x{n:<5d} "
                           f"{label_of(names.get(m, '?'))[:90]}  "
                           f"[{scopes.get(m, '')[-90:]}]")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    target = sys.argv[1]
    if os.path.isdir(target):
        target = newest_xplane(target)
    print(describe(read_file(target)))
