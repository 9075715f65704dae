"""How the recorded traces in ``data/`` were cut from the profiler's files.

    python benchmark/tests/make_fixture.py <file.xplane.pb[.gz]> <out.pb.gz> \
        <first program run> <program runs>

Keeps, of the first device plane, the events of the lines ``XLA Ops``,
``Async XLA Ops`` and ``XLA Modules`` that lie inside the chosen runs of
programs (an ``XLA Modules`` event each), and of the host plane the
benchmark-side spans that overlap them. An operation's name is its HLO
text; the operand list, which is most of it, is cut to ``...``. Nothing
else is changed: times are the chip's.
"""

from __future__ import annotations

import gzip
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import xplane   # noqa: E402

LINES = (xplane.OPS_LINE, xplane.ASYNC_LINE, xplane.MODULES_LINE)
_OPERANDS = re.compile(r"^(%\S+ = .*? [a-z][a-z0-9\-]*\()(.*)$", re.S)
_ATTRS = re.compile(r'(, kind=k\w+|, custom_call_target="[^"]+")')


def shorten(name: str) -> str:
    m = _OPERANDS.match(name)
    if m is None:
        return name
    return m[1] + "...)" + "".join(_ATTRS.findall(m[2]))


def quote(text: str) -> str:
    return text.replace("\\", "\\\\").replace('"', '\\"').replace("\n", " ")


def plane_text(plane_id: int, name: str, lines: dict) -> list:
    """``lines``: line name -> [(event name, start_ns, duration_ns)]."""
    meta = {}
    for events in lines.values():
        for event_name, _, _ in events:
            meta.setdefault(event_name, len(meta) + 1)
    out = [f'planes {{ id: {plane_id} name: "{name}"']
    out += [f'  event_metadata {{ key: {i} value {{ id: {i} '
            f'name: "{quote(n)}" }} }}' for n, i in meta.items()]
    for k, (line_name, events) in enumerate(lines.items()):
        out.append(f'  lines {{ id: {k + 1} name: "{line_name}" '
                   f'timestamp_ns: 0')
        out += [f'    events {{ metadata_id: {meta[n]} '
                f'offset_ps: {round(start * 1000)} '
                f'duration_ps: {round(dur * 1000)} }}'
                for n, start, dur in events]
        out.append('  }')
    out.append('}')
    return out


def main(src: str, dst: str, first: int, runs: int) -> None:
    from jax.profiler import ProfileData
    raw = (gzip.open(src) if src.endswith(".gz") else open(src, "rb")).read()
    profile = ProfileData.from_serialized_xspace(raw)
    device = next(p for p in profile.planes
                  if xplane.DEVICE_PLANE.match(p.name))
    kept = {line.name: list(line.events) for line in device.lines
            if line.name in LINES}
    modules = kept[xplane.MODULES_LINE][first:first + runs]
    lo = modules[0].start_ns
    hi = modules[-1].start_ns + modules[-1].duration_ns
    dev_lines = {
        name: [(shorten(e.name), e.start_ns - lo, e.duration_ns)
               for e in events
               if e.start_ns >= lo and e.start_ns + e.duration_ns <= hi]
        for name, events in kept.items()}
    host = next(p for p in profile.planes if p.name == xplane.HOST_PLANE)
    spans = [(e.name, e.start_ns - lo, e.duration_ns)
             for line in host.lines for e in line.events
             if e.name.startswith(xplane.SPAN_PREFIX)
             and e.start_ns + e.duration_ns >= lo and e.start_ns <= hi]
    shift = min([0.0] + [s for _, s, _ in spans])    # offsets are unsigned
    text = plane_text(1, device.name, {
        n: [(x, s - shift, d) for x, s, d in ev]
        for n, ev in dev_lines.items()})
    text += plane_text(2, xplane.HOST_PLANE, {
        "python3": [(x, s - shift, d) for x, s, d in spans]})
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(text))
    with gzip.open(dst, "wb", compresslevel=9) as f:
        f.write(blob)
    print(f"{dst}: {os.path.getsize(dst)} bytes; "
          f"{ {n: len(e) for n, e in dev_lines.items()} }, "
          f"{len(spans)} spans, {(hi - lo) / 1e6:.3f} ms")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
