"""Timeline writer tests (parity: reference test/test_timeline.py asserts the
produced Chrome-trace JSON is valid and contains the expected event phases).

The writer is one Python thread behind a queue.
"""

import json
import os

from horovod_tpu.timeline import _MAX_TIDS, _OVERFLOW_TIDS, Timeline


def _exercise(tl: Timeline):
    tl.record_enqueue("grad.0", "allreduce", 4096)
    tl.record_activity("grad.0", "XLA_ALLREDUCE", 120.0)
    tl.record_done("grad.0")
    tl.record_counter("hvd_tpu_wire_bytes_per_sec",
                      {"bytes_per_sec": 123.5})
    tl.mark_cycle()
    tl.stop()


def _load_events(path):
    with open(path) as f:
        events = json.load(f)
    assert isinstance(events, list)
    return events


def test_python_writer(tmp_path):
    p = str(tmp_path / "timeline.json")
    tl = Timeline(p, mark_cycles=True)
    tl.start()
    _exercise(tl)
    events = _load_events(p)
    phases = [e["ph"] for e in events]
    assert "B" in phases and "E" in phases and "X" in phases and "i" in phases
    b = next(e for e in events if e["ph"] == "B")
    assert b["name"] == "ALLREDUCE"
    assert b["args"]["tensor"] == "grad.0"
    assert b["args"]["bytes"] == 4096
    c = next(e for e in events if e["ph"] == "C")
    assert c["name"] == "hvd_tpu_wire_bytes_per_sec"
    assert c["args"]["bytes_per_sec"] == 123.5


def test_tid_overflow_hashes_onto_reserved_pool(tmp_path):
    """ISSUE 3 satellite: past _MAX_TIDS distinct names, new names must hash
    onto the reserved overflow tid pool (stable per name) instead of
    collapsing onto tid 0 — a >4096-name trace still parses with balanced
    B/E per tid."""
    p = str(tmp_path / "big.json")
    tl = Timeline(p)
    tl.start()
    n = _MAX_TIDS + 300
    for i in range(n):
        tl.record_enqueue(f"tensor.{i}", "allreduce", 1)
        tl.record_done(f"tensor.{i}")
    tl.stop()
    events = _load_events(p)
    assert len(events) == 2 * n          # the full trace parsed
    per_tid = {}
    for e in events:
        per_tid.setdefault(e["tid"], []).append(e["ph"])
    for tid, phases in per_tid.items():
        assert phases.count("B") == phases.count("E"), tid
    overflow = [t for t in per_tid if t > _MAX_TIDS]
    assert overflow, "no overflow tids recorded"
    assert all(t <= _MAX_TIDS + _OVERFLOW_TIDS for t in overflow)
    # nothing fell onto tid 0 (the old corruption mode)
    assert 0 not in per_tid


def test_record_done_without_enqueue_is_dropped(tmp_path):
    """ISSUE 5 satellite: a done for a name that was never enqueued used
    to emit an unbalanced "E" event — it must be guarded (debug-log +
    drop) so merged traces never contain dangling ends."""
    p = str(tmp_path / "guard.json")
    tl = Timeline(p)
    tl.start()
    tl.record_done("never.enqueued")          # stray: dropped
    tl.record_enqueue("real", "allreduce", 8)
    tl.record_done("real")
    tl.record_done("real")                    # double-done: dropped too
    tl.stop()
    events = _load_events(p)
    assert [e["ph"] for e in events] == ["B", "E"]


def test_pid_and_correlation_tagging(tmp_path):
    """The Python writer stamps the configured pid (the rank) and tags
    spans with the engine's cross-rank correlation id, so a local timeline
    joins against the merged /trace."""
    p = str(tmp_path / "corr.json")
    tl = Timeline(p, pid=7)
    tl.start()
    tl.record_enqueue("g", "allreduce", 8, corr="g#0#1")
    tl.record_done("g")
    tl.stop()
    b, e = _load_events(p)
    assert b["pid"] == e["pid"] == 7
    assert b["args"]["corr"] == "g#0#1"
    assert e["args"]["corr"] == "g#0#1"


def test_file_is_valid_while_writer_is_live(tmp_path):
    """Write-then-seal: the file parses as complete JSON after every
    flushed event, not only after a clean stop."""
    import time
    p = str(tmp_path / "live.json")
    tl = Timeline(p)
    tl.start()
    try:
        tl.record_enqueue("a", "allreduce", 1)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            try:
                if _load_events(p):
                    break
            except (ValueError, FileNotFoundError):
                pass
            time.sleep(0.02)
        events = _load_events(p)
        assert [e["ph"] for e in events] == ["B"]
    finally:
        tl.stop()


def test_writer_killed_mid_stream_leaves_loadable_file(tmp_path):
    """ISSUE 5 satellite regression: a rank killed mid-stream (os._exit —
    no atexit, no writer stop) must leave a timeline every complete event
    of which is recoverable. With write-then-seal the last flushed state
    is even plain-json.load()-able; the tolerant loader covers the
    partial-buffer tail case."""
    import subprocess
    import sys
    p = str(tmp_path / "killed.json")
    script = f"""
import os, time
from horovod_tpu.timeline import Timeline
tl = Timeline({p!r})
tl.start()
for i in range(50):
    tl.record_enqueue(f"t{{i}}", "allreduce", 64)
    tl.record_done(f"t{{i}}")
time.sleep(0.5)        # let the writer drain + flush
os._exit(1)            # crash: no stop(), no atexit
"""
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    from horovod_tpu.trace import load_trace_file
    events = load_trace_file(p)
    assert len(events) == 100, f"recovered {len(events)} of 100 events"
    phases = [e["ph"] for e in events]
    assert phases.count("B") == phases.count("E") == 50
    # ...and the crash-tolerant format is ALSO plain valid JSON up to the
    # last flushed seal
    assert isinstance(json.load(open(p)), list)
