"""The device trace by scope: which ``op_name`` each executed operation
carries, read from the profiler's file, and by hand the table of a traced
run by the names the program wrote (``horovod_tpu/common/scopes.py``):

    python3 benchmark/run.py --workload <cell> --seed 0 --seconds 10 \
        --trace 1 --out <dir>
    python3 benchmark/scopes.py <dir>

Where the name is (looked at by hand, PR 24, ``lm-spmd-1chip`` on the v5e
from an empty compile cache). NOT in the event's name: that is the
operation's HLO text up to its attributes, ``%fusion.245 = s32[...]
fusion(... %inputs.1), kind=kLoop, calls=%fused_computation.301``, with no
``metadata={...}``. It is a stat of the event's METADATA entry in the
plane (``XEventMetadata.stats``), named ``tf_op``: the ``op_name`` and a
colon, ``jit(train_step)/transpose(jvp())/layers/while/body/closed_call/
ffn/btd,df->btf/dot_general:``. Beside it there: ``hlo_category``,
``flops``, ``bytes_accessed``, ``source`` (file:line), ``program_id``.
``jax.profiler.ProfileData`` shows an event's own stats (offset, duration)
and not its metadata's, which is why xplane.py found "no framework-scope
stat to read". So this file walks the protobuf wire format itself, thirty
lines, and imports neither jax nor a generated ``xplane_pb2``. Operations
that XLA made itself (copies, the parameters' layout changes) carry no
``tf_op``: their scope is ``""``.

:func:`summarize_file` gives the device entries of xplane.py's summary for
the ``XLA Ops`` line, with ``scopes`` parallel to ``labels``; an operation
is one (label, scope) pair, since ``fusion.3`` of two programs may share
a label and not a scope. ``readers/trace_scopes.py`` reduces them where
the record has them under ``record["traced"]["scoped"]``. The worker does
not put them there yet: worker.py is the benchmark's, a PR that is not a
``benchmark`` PR may only add files, and run.py deletes the profiler's
files before the readers run. Until a ``benchmark`` PR adds that line to
worker.py and the five metrics to ``BENCHMARK.json`` (PERF.md section 7),
the table is made by hand, as above, from a run kept with ``--out``.
"""

from __future__ import annotations

import gzip
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [p for p in (HERE, os.path.join(HERE, "readers"))
                if p not in sys.path]

import files        # noqa: E402
import stats        # noqa: E402
import tracecalc    # noqa: E402
import xplane       # noqa: E402

SCOPE_STAT = "tf_op"


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


def device_planes(data):
    """Of every chip's plane of a serialized XSpace: its name, each
    event-metadata id's name (the operation's HLO text) and scope, and the
    events of each line as (metadata id, start ns, duration ns). Field
    numbers are xplane.proto's."""
    for number, plane in fields(memoryview(data)):
        if number != 1:                                 # XSpace.planes
            continue
        name, stat_names, metadata, lines = "", {}, [], {}
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
                lines[line] = [(e[1], t0 + e.get(2, 0) / 1e3,
                                e.get(3, 0) / 1e3) for e in found]
            elif n == 4:                                # .event_metadata
                metadata.append(dict(fields(v))[2])
            elif n == 5:                                # .stat_metadata
                entry = dict(fields(dict(fields(v))[2]))
                stat_names[entry.get(1)] = text(entry.get(2, b""))
        if not xplane.DEVICE_PLANE.match(name):
            continue
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
    """``{"devices": [{"plane", "labels", "scopes", "ops"}]}`` of a
    serialized XSpace: xplane.py's device entries, ``XLA Ops`` line only,
    each label index standing for one (label, scope) pair."""
    devices = []
    for plane, names, scopes, lines in device_planes(data):
        index, events = {}, lines.get(xplane.OPS_LINE, [])
        selfs = stats.self_times([(s, d) for _, s, d in events])
        ops = [[s, d, self_ns, index.setdefault(
            (xplane.label_of(names[m]), scopes.get(m, "")), len(index))]
            for (m, s, d), self_ns in zip(events, selfs)]
        devices.append({"plane": plane, "labels": [k[0] for k in index],
                        "scopes": [k[1] for k in index], "ops": ops})
    t0 = min((o[0] for d in devices for o in d["ops"]), default=0.0)
    for d in devices:
        for o in d["ops"]:
            o[0] -= t0
    devices.sort(key=lambda d: d["plane"])
    return {"devices": devices}


def read_file(path: str) -> bytes:
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        return f.read()


def summarize_file(path: str) -> dict:
    return summarize(read_file(path))


def scope_metrics() -> list:
    """Names of the metric files that the scope reader reads."""
    folder = os.path.join(HERE, "layer_metrics")
    names = sorted(f[:-5] for f in os.listdir(folder) if f.endswith(".json"))
    return [n for n in names if files.load_json(
        os.path.join(folder, n + ".json"))["reader"] == "trace_scopes"]


def by_prefix(dev: dict, n: int = 30) -> list:
    """[[scope without its last part (the primitive), seconds], ...] by
    self time, largest first."""
    total = {}
    for _, _, self_ns, i in dev["ops"]:
        prefix = dev["scopes"][i].rpartition("/")[0] or "(no op_name)"
        total[prefix] = total.get(prefix, 0.0) + self_ns
    return [[k, t / 1e9] for k, t in sorted(
        total.items(), key=lambda kv: -kv[1])[:n]]


def main(out_dir: str) -> None:
    """The scope metrics of a run kept with ``run.py --out``, what their sum
    leaves of the chip's busy time, and where the time is by scope."""
    record = files.load_json(os.path.join(out_dir, "record.json"))
    traced = record["traced"]
    traced["scoped"] = summarize_file(
        xplane.newest_xplane(os.path.join(out_dir, "trace")))["devices"]
    ctx = {"record": record, "notes": []}
    for name in scope_metrics():
        spec, read = files.layer_metric(name)
        print(f"{name}: {read(ctx, spec)}")
    for note in ctx["notes"]:
        print(note)
    dev, steps = traced["scoped"][0], traced["steps"]
    print(f"by scope, ms a step, first chip ({dev['plane']}):")
    for prefix, seconds in by_prefix(dev):
        print(f"  {seconds * 1e3 / steps:9.3f}  {prefix}")
    spec, _ = files.layer_metric("unscoped_ms_per_step")
    inside = stats.matcher(spec["exclude"])
    rest = {**dev, "ops": [o for o in dev["ops"]
                           if not inside(dev["scopes"][o[3]])]}
    print("largest operations outside forward, backward and optimizer, "
          "ms a step:")
    for group, seconds in tracecalc.top_ops(rest, 8):
        print(f"  {seconds * 1e3 / steps:9.3f}  {group}")


if __name__ == "__main__":
    main(sys.argv[1])
