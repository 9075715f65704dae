"""How the recorded traces in ``data/`` are cut from the profiler's files.

    python benchmark/tests/make_fixture.py <file.xplane.pb[.gz], or the \
        directory of a run kept with run.py --out> <out.xplane.pb.gz> \
        <first step> <steps>

Keeps, of the first chip's plane, the events of the lines ``XLA Ops``,
``Async XLA Ops`` and ``XLA Modules`` that lie inside the chosen steps, each
operation's ``tf_op`` stat (its ``op_name``; see xplane.py) on its
event-metadata entry, where the profiler put it, and of the host plane the
spans xplane.py keeps that overlap them. A step runs from one run of the
trace's first program to the next run of the same program (an eager step is
two programs). An operation's name is its HLO text; the operand list, which
is most of it, is cut to ``...`` and the result's layouts (``{2,1,0:T(8,128)
(2,1)}``) go. Nothing else is changed: times are the
chip's, in whole nanoseconds. Of a run's directory the worker's record is
kept beside the trace, ``<out>.record.json``: all of it but the summary of
the whole trace, which the cut stands for (``traced.steps`` is the steps
kept), so that a test reduces what the chip left.

The three recordings older than PR 36 were cut before the one maker: the
two of PR 22 and ``lfm2-...1step`` (PR 34) without ``tf_op``,
``lm-spmd-1chip.scoped.2steps`` (PR 24) without the asynchronous line and
the spans. They stay as they are: the tests hold what they read.
"""

from __future__ import annotations

import gzip
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import xplane   # noqa: E402

LINES = (xplane.OPS_LINE, xplane.ASYNC_LINE, xplane.MODULES_LINE)
_OPERANDS = re.compile(r"^(%\S+ = .*? [a-z][a-z0-9\-]*\()(.*)$", re.S)
_ATTRS = re.compile(r'(, kind=k\w+|, custom_call_target="[^"]+")')
_LAYOUT = re.compile(r"\{[^{}]*\}")


def shorten(name: str) -> str:
    m = _OPERANDS.match(name)
    if m is None:
        return name
    return _LAYOUT.sub("", m[1]) + "...)" + "".join(_ATTRS.findall(m[2]))


def quote(text: str) -> str:
    return text.replace("\\", "\\\\").replace('"', '\\"').replace("\n", " ")


def plane_text(plane_id: int, name: str, names: dict, scopes: dict,
               lines: dict) -> list:
    """``lines``: line name -> [(metadata id, start_ns, duration_ns)]."""
    used = sorted({m for events in lines.values() for m, _, _ in events})
    out = [f'planes {{ id: {plane_id} name: "{name}"',
           f'  stat_metadata {{ key: 1 value {{ id: 1 '
           f'name: "{xplane.SCOPE_STAT}" }} }}']
    for m in used:
        stat = (f' stats {{ metadata_id: 1 str_value: '
                f'"{quote(scopes[m])}:" }}' if m in scopes else "")
        out.append(f'  event_metadata {{ key: {m} value {{ id: {m} '
                   f'name: "{quote(shorten(names[m]))}"{stat} }} }}')
    for k, (line, events) in enumerate(lines.items()):
        out.append(f'  lines {{ id: {k + 1} name: "{line}" timestamp_ns: 0')
        out += [f'    events {{ metadata_id: {m} offset_ps: {start * 1000} '
                f'duration_ps: {dur * 1000} }}' for m, start, dur in events]
        out.append('  }')
    out.append('}')
    return out


def main(src: str, dst: str, first: int, steps: int) -> None:
    from jax.profiler import ProfileData    # text to wire format, no more
    if os.path.isdir(src):
        with open(os.path.join(src, "record.json")) as f:
            record = json.load(f)
        record["traced"] = {**record["traced"], "steps": steps, "trace": None}
        with open(dst.replace(".xplane.pb.gz", ".record.json"), "w") as f:
            json.dump(record, f)
        src = xplane.newest_xplane(os.path.join(src, "trace"))
    found = {plane: (names, scopes, dict(lines)) for plane, names, scopes,
             lines in xplane.planes(xplane.read_file(src))}
    device = min(p for p in found if xplane.DEVICE_PLANE.match(p))
    names, scopes, lines = found[device]
    modules = sorted(lines[xplane.MODULES_LINE], key=lambda e: e[1])
    program = names[modules[0][0]].split("(")[0]
    starts = [i for i, (m, _, _) in enumerate(modules)
              if names[m].split("(")[0] == program] + [len(modules)]
    modules = modules[starts[first]:starts[first + steps]]
    lo, hi = modules[0][1], modules[-1][1] + modules[-1][2]
    host_names, _, host_lines = found[xplane.HOST_PLANE]
    spans = [(m, s, d) for events in host_lines.values() for m, s, d in events
             if host_names.get(m, "").startswith(xplane.SPAN_PREFIXES)
             and s + d >= lo and s <= hi]
    lo_all = min([lo] + [s for _, s, _ in spans])      # offsets are unsigned
    kept = {line: [(m, s - lo_all, d) for m, s, d in lines.get(line, [])
                   if s >= lo and s + d <= hi] for line in LINES}
    text = plane_text(1, device, names, scopes, kept)
    text += plane_text(2, xplane.HOST_PLANE, host_names, {}, {
        "python3": [(m, s - lo_all, d) for m, s, d in spans]})
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(text))
    with gzip.open(dst, "wb", compresslevel=9) as f:
        f.write(blob)
    used = {m for events in kept.values() for m, _, _ in events}
    print(f"{dst}: {os.path.getsize(dst)} bytes; "
          f"{ {n: len(e) for n, e in kept.items()} }, {len(spans)} spans, "
          f"{len(scopes.keys() & used)} of {len(used)} names with a scope, "
          f"programs {[names[m].split('(')[0] for m, _, _ in modules]}, "
          f"{(hi - lo) / 1e6:.3f} ms")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
