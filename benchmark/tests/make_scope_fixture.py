"""How the recorded trace in ``data/`` that carries scopes was cut from the
profiler's file (the two older recordings have none: make_fixture.py reads
through ``jax.profiler.ProfileData``, which does not show them).

    python benchmark/tests/make_scope_fixture.py <file.xplane.pb[.gz]> \
        <out.pb.gz> <first program run> <program runs>

Keeps, of the first device plane, the events of the lines ``XLA Ops`` and
``XLA Modules`` that lie inside the chosen runs of programs, each
operation's name cut as make_fixture.py cuts it, and its ``tf_op`` stat
(the ``op_name``; see scopes.py) on its event-metadata entry, where the
profiler put it. Times are the chip's.
"""

from __future__ import annotations

import gzip
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import scopes                                   # noqa: E402
import xplane                                   # noqa: E402
from make_fixture import quote, shorten         # noqa: E402


def main(src: str, dst: str, first: int, runs: int) -> None:
    from jax.profiler import ProfileData
    plane, names, scope_of, lines = next(scopes.device_planes(
        scopes.read_file(src)))
    modules = sorted(lines[xplane.MODULES_LINE], key=lambda e: e[1])
    modules = modules[first:first + runs]
    lo, hi = modules[0][1], modules[-1][1] + modules[-1][2]
    kept = {line: [e for e in lines[line] if e[1] >= lo and e[1] + e[2] <= hi]
            for line in (xplane.OPS_LINE, xplane.MODULES_LINE)}
    used = sorted({e[0] for events in kept.values() for e in events})
    text = [f'planes {{ id: 1 name: "{plane}"',
            f'  stat_metadata {{ key: 1 value {{ id: 1 '
            f'name: "{scopes.SCOPE_STAT}" }} }}']
    for m in used:
        stat = (f' stats {{ metadata_id: 1 str_value: '
                f'"{quote(scope_of[m])}:" }}' if m in scope_of else "")
        text.append(f'  event_metadata {{ key: {m} value {{ id: {m} '
                    f'name: "{quote(shorten(names[m]))}"{stat} }} }}')
    for k, (line, events) in enumerate(kept.items()):
        text.append(f'  lines {{ id: {k + 1} name: "{line}" timestamp_ns: 0')
        text += [f'    events {{ metadata_id: {m} '
                 f'offset_ps: {round((start - lo) * 1000)} '
                 f'duration_ps: {round(dur * 1000)} }}'
                 for m, start, dur in events]
        text.append('  }')
    text.append('}')
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(text))
    with gzip.open(dst, "wb", compresslevel=9) as f:
        f.write(blob)
    print(f"{dst}: {os.path.getsize(dst)} bytes; "
          f"{ {n: len(e) for n, e in kept.items()} }, "
          f"{len(scope_of.keys() & set(used))} of {len(used)} names with a "
          f"scope, {(hi - lo) / 1e6:.3f} ms")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4]))
