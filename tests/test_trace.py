"""Cross-rank collective tracing (ISSUE 5): correlation-id stamping, the
trace ring + KV segments, clock-beacon alignment, the merged ``GET /trace``
cluster timeline, the straggler report, and the flight recorder.

The np=2 integration test at the bottom is the acceptance path: two real
worker processes run traced steps with a delay failpoint on rank 1, the
merged ``/trace`` must be valid Chrome-trace JSON with per-rank pids and
cross-rank-joinable correlation ids, and ``tools/trace_report.py`` must
name rank 1 as the straggler with skew on the injected delay's order of
magnitude.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from horovod_tpu import faults
from horovod_tpu import trace as trace_mod
from horovod_tpu.trace import (TraceRecorder, clock_offset, collective_skew,
                               load_trace_events, make_corr, merge_segments,
                               observe_skew, parse_corr, publish_segment,
                               render_cluster_trace)

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def _exercise(rec: TraceRecorder, names=("g0", "g1"), rounds=3,
              world_version=0, t_shift=0.0):
    """Drive one recorder through ``rounds`` steps of named collectives."""
    for _ in range(rounds):
        rec.record_step(begin=True)
        for n in names:
            rec.record_enqueue(n, "allreduce", 64, world_version)
            rec.record_dispatch(n, "XLA_DISPATCH", 0.001)
            rec.record_done(n)
        rec.record_step(begin=False)


# ---------------------------------------------------------------------------
# recorder
# ---------------------------------------------------------------------------

class TestRecorder:
    def test_correlation_ids_are_deterministic(self):
        """Two ranks submitting the same named collectives in the same
        order mint the SAME ids — the joinability invariant."""
        a, b = TraceRecorder(rank=0), TraceRecorder(rank=1)
        ids_a = [a.record_enqueue("x", "allreduce", 8, 3) for _ in range(4)]
        ids_b = [b.record_enqueue("x", "allreduce", 8, 3) for _ in range(4)]
        assert ids_a == ids_b == [make_corr("x", 3, i + 1) for i in range(4)]
        assert parse_corr(ids_a[-1]) == ("x", 3, 4)
        # names with the separator char still round-trip (rsplit)
        assert parse_corr(make_corr("a#b", 1, 2)) == ("a#b", 1, 2)

    def test_live_corr_and_done_guard(self):
        rec = TraceRecorder(rank=0)
        corr = rec.record_enqueue("t", "broadcast", 4, 0)
        assert rec.live_corr("t") == corr
        rec.record_done("t")
        assert rec.live_corr("t") is None
        before = len(rec.segment()["events"])
        rec.record_done("t")            # second done: dropped, no event
        rec.record_done("never")        # never enqueued: dropped
        assert len(rec.segment()["events"]) == before

    def test_ring_is_bounded_and_counts_drops(self):
        rec = TraceRecorder(rank=0, capacity=32)
        for i in range(100):
            rec.record_enqueue(f"n{i}", "allreduce", 1, 0)
        seg = rec.segment()
        assert len(seg["events"]) == 32
        assert seg["dropped"] == 68

    def test_segment_byte_cap_drops_oldest(self):
        rec = TraceRecorder(rank=0, capacity=512)
        for i in range(512):
            rec.record_enqueue(f"tensor.name.{i:04d}", "allreduce", 1, 0)
        seg = rec.segment(max_bytes=8192)
        assert len(json.dumps(seg)) <= 8192
        assert seg["events"], "cap dropped everything"
        # the survivors are the NEWEST events
        assert seg["events"][-1]["n"] == "tensor.name.0511"
        assert seg["dropped"] >= 512 - len(seg["events"])


# ---------------------------------------------------------------------------
# merger + clock alignment
# ---------------------------------------------------------------------------

class TestMerger:
    def test_pid_remap_and_balance(self):
        segs = {}
        for r in (0, 1):
            rec = TraceRecorder(rank=r)
            _exercise(rec)
            now = time.monotonic()
            rec.add_beacon(now, 1e6 + now, 0.001)
            segs[r] = rec.segment()
        events = merge_segments(segs)
        assert {e["pid"] for e in events} == {0, 1}
        labels = [e["args"]["name"] for e in events
                  if e.get("ph") == "M" and e["name"] == "process_name"]
        assert labels == ["rank 0", "rank 1"]
        for pid in (0, 1):
            per_tid = {}
            for e in events:
                if e["pid"] == pid and e.get("ph") in ("B", "E"):
                    per_tid.setdefault(e["tid"], []).append(e["ph"])
            assert per_tid, "no spans for pid"
            for phases in per_tid.values():
                assert phases.count("B") == phases.count("E")

    def test_clock_alignment_recovers_injected_offset(self):
        """Rank 1's beacons claim its monotonic clock runs 100s behind the
        server clock relative to rank 0's: after alignment, simultaneous
        events land at the same wall time and skew reflects only the real
        arrival gap."""
        OFFSET = 100.0
        segs = {}
        base = time.monotonic()
        for r, (clock_shift, late) in enumerate([(0.0, 0.0),
                                                 (-OFFSET, 0.010)]):
            rec = TraceRecorder(rank=r)
            # arrival at base+late on the shared (true) clock, recorded on
            # a rank-local monotonic clock shifted by clock_shift
            with _frozen_monotonic(base + late + clock_shift):
                rec.record_enqueue("g", "allreduce", 8, 0)
            rec.add_beacon(base + clock_shift, 5000.0 + base, 0.002)
            segs[r] = rec.segment()
        sk = collective_skew(segs)
        (corr, ent), = sk.items()
        assert ent["last_rank"] == 1
        assert abs(ent["skew"] - 0.010) < 0.005, ent

    def test_min_rtt_beacon_wins(self):
        noisy = (10.0, 1000.0, 0.5)           # wildly wrong, high rtt
        good = (10.0, 500.0, 0.001)
        # the beacon's local ts is already the request midpoint, so the
        # offset is a plain difference (rtt only selects the beacon)
        assert clock_offset([noisy, good]) == 500.0 - 10.0
        assert clock_offset([]) is None

    def test_truncated_ring_seals_open_spans(self):
        """A rank that died mid-collective (enqueue recorded, done never)
        must still merge into a BALANCED trace."""
        rec = TraceRecorder(rank=2)
        rec.record_enqueue("hung", "allreduce", 8, 0)
        events = merge_segments({2: rec.segment()})
        bs = [e for e in events if e.get("ph") == "B"]
        es = [e for e in events if e.get("ph") == "E"]
        assert len(bs) == len(es) == 1
        assert es[0]["args"]["truncated"] is True
        # ...and a dangling done (ring evicted the begin) is dropped
        rec2 = TraceRecorder(rank=3)
        rec2._live["ghost"] = "ghost#0#1"     # simulate pre-ring enqueue
        rec2.record_done("ghost")
        events2 = merge_segments({3: rec2.segment()})
        assert not [e for e in events2 if e.get("ph") in ("B", "E")]

    def test_render_skips_garbage_payloads_and_observes_skew(self):
        from horovod_tpu.metrics import Registry
        segs = {}
        for r in (0, 1):
            rec = TraceRecorder(rank=r)
            now = time.monotonic()
            with _frozen_monotonic(now + 0.02 * r):
                rec.record_enqueue("s", "broadcast", 8, 0)
            rec.add_beacon(now, 100.0 + now, 0.001)
            segs[str(r)] = json.dumps(rec.segment()).encode()
        segs["9"] = b"not json at all"
        segs["8"] = b'{"no": "events"}'
        reg = Registry(enabled=True)
        body = render_cluster_trace(segs, reg=reg)
        obj = json.loads(body)
        assert obj["otherData"]["ranks"] == [0, 1]
        assert obj["otherData"]["straggler_rank"] == 1
        hist = reg.histogram("hvd_tpu_collective_skew_seconds")
        snap = hist._snap()
        assert snap and snap[0][1]["count"] == 1
        assert reg.gauge("hvd_tpu_straggler_rank").value() == 1.0

    def test_unaligned_rank_is_excluded_from_skew(self):
        """A rank without clock beacons lives in a private monotonic
        domain: it still renders (labeled unaligned) but must NOT
        participate in skew — comparing raw monotonic against
        beacon-aligned wall time would yield epoch-scale garbage and a
        bogus straggler verdict."""
        now = time.monotonic()
        segs = {}
        for r in (0, 1):
            rec = TraceRecorder(rank=r)
            rec.record_enqueue("u", "allreduce", 8, 0)
            if r == 0:
                rec.add_beacon(now, 1.7e9 + now, 0.001)   # epoch-aligned
            segs[r] = rec.segment()                        # rank 1: none
        assert collective_skew(segs) == {}
        events = merge_segments(segs)
        labels = {e["pid"]: e["args"]["name"] for e in events
                  if e.get("ph") == "M" and e["name"] == "process_name"}
        assert labels == {0: "rank 0", 1: "rank 1 (unaligned)"}
        obj = json.loads(render_cluster_trace(
            {str(r): json.dumps(s) for r, s in segs.items()}))
        assert obj["otherData"]["straggler_rank"] is None
        assert obj["otherData"]["ranks"] == [0, 1]

    def test_straggler_verdict_without_registry(self):
        """The headline straggler answer never depends on the metrics
        registry being enabled (HOROVOD_TPU_METRICS=0 + tracing on is a
        supported combination)."""
        from horovod_tpu.metrics import Registry
        segs = {}
        now = time.monotonic()
        for r in (0, 1):
            rec = TraceRecorder(rank=r)
            with _frozen_monotonic(now + 0.02 * r):
                rec.record_enqueue("s", "broadcast", 8, 0)
            rec.add_beacon(now, 100.0 + now, 0.001)
            segs[str(r)] = json.dumps(rec.segment()).encode()
        for reg in (None, Registry(enabled=False)):
            obj = json.loads(render_cluster_trace(segs, reg=reg))
            assert obj["otherData"]["straggler_rank"] == 1

    def test_repeat_scrapes_observe_each_collective_once(self):
        """Segments are ring snapshots: a watermark keeps repeat /trace
        scrapes from re-observing the same collectives, so the histogram
        count scales with collectives, not scrape frequency."""
        from horovod_tpu.metrics import Registry
        segs = {}
        now = time.monotonic()
        for r in (0, 1):
            rec = TraceRecorder(rank=r)
            with _frozen_monotonic(now + 0.01 * r):
                rec.record_enqueue("w", "allreduce", 8, 0)
                rec.record_enqueue("w", "allreduce", 8, 0)
            rec.add_beacon(now, 100.0 + now, 0.001)
            segs[str(r)] = json.dumps(rec.segment()).encode()
        reg = Registry(enabled=True)
        watermark = {}
        for _ in range(3):
            render_cluster_trace(segs, reg=reg, watermark=watermark)
        hist = reg.histogram("hvd_tpu_collective_skew_seconds")
        ((_, agg),) = hist._snap()
        assert agg["count"] == 2, agg
        assert watermark == {"w": (0, 2)}


class TestTolerantLoader:
    def test_object_array_and_truncated_forms(self):
        events = [{"ph": "B", "ts": 1.0, "pid": 0, "tid": 1},
                  {"ph": "E", "ts": 2.0, "pid": 0, "tid": 1}]
        assert load_trace_events(json.dumps({"traceEvents": events})) \
            == events
        assert load_trace_events(json.dumps(events)) == events
        text = json.dumps(events)
        # chop mid-second-event: the complete prefix is recovered
        cut = text.index('{"ph": "E"') + 5
        assert load_trace_events(text[:cut]) == events[:1]
        # newline-delimited events
        nd = "\n".join(json.dumps(e) for e in events)
        assert load_trace_events(nd) == events
        assert load_trace_events("") == []


# ---------------------------------------------------------------------------
# publication: /clock beacons, trace/<rank> segments, GET /trace
# ---------------------------------------------------------------------------

@pytest.fixture
def kv_server():
    from horovod_tpu.runner.http_server import KVStoreServer
    server = KVStoreServer(("127.0.0.1", 0))
    server.start()
    yield server
    faults.disarm()
    server.stop()


class TestEndpoint:
    def test_fetch_server_clock_beacon(self, kv_server):
        from horovod_tpu.runner.http_client import fetch_server_clock
        t0 = time.time()
        mono, server_ts, rtt = fetch_server_clock("127.0.0.1",
                                                  kv_server.port)
        assert abs(server_ts - t0) < 5.0
        assert 0 <= rtt < 5.0
        assert abs(mono - time.monotonic()) < 5.0

    def test_get_trace_merges_published_segments(self, kv_server):
        for r in (0, 1):
            rec = TraceRecorder(rank=r)
            _exercise(rec)
            mono, ts, rtt = __import__(
                "horovod_tpu.runner.http_client", fromlist=["x"]
            ).fetch_server_clock("127.0.0.1", kv_server.port)
            rec.add_beacon(mono, ts, rtt)
            publish_segment(("127.0.0.1", kv_server.port), r, rec.segment())
        from horovod_tpu.runner.http_client import read_data_from_kvstore
        body = read_data_from_kvstore("127.0.0.1", kv_server.port,
                                      "trace", "", timeout=5)
        obj = json.loads(body)
        assert obj["otherData"]["ranks"] == [0, 1]
        corrs0 = {e["args"]["corr"] for e in obj["traceEvents"]
                  if e.get("ph") == "B" and e["pid"] == 0}
        corrs1 = {e["args"]["corr"] for e in obj["traceEvents"]
                  if e.get("ph") == "B" and e["pid"] == 1}
        assert corrs0 == corrs1 and corrs0
        assert obj["otherData"]["collectives_correlated"] == len(corrs0)

    def test_get_trace_with_nothing_published(self, kv_server):
        """An empty /trace is a valid empty trace, not an error."""
        from horovod_tpu.runner.http_client import read_data_from_kvstore
        obj = json.loads(read_data_from_kvstore(
            "127.0.0.1", kv_server.port, "trace", "", timeout=5))
        assert obj["traceEvents"] == []
        assert obj["otherData"]["ranks"] == []

    def test_clear_scope_drops_stale_segments(self, kv_server):
        """The elastic driver clears trace/<rank> on world activation so a
        merged trace never mixes two worlds' rank numberings."""
        rec = TraceRecorder(rank=0)
        rec.record_enqueue("old", "allreduce", 8, 0)
        publish_segment(("127.0.0.1", kv_server.port), 0, rec.segment())
        kv_server.clear_scope("trace")
        from horovod_tpu.runner.http_client import read_data_from_kvstore
        obj = json.loads(read_data_from_kvstore(
            "127.0.0.1", kv_server.port, "trace", "", timeout=5))
        assert obj["otherData"]["ranks"] == []


@pytest.mark.chaos
class TestPublishChaos:
    """ISSUE 5 satellite: a dropped trace publish degrades the merged
    trace gracefully instead of failing the /trace endpoint."""

    @pytest.fixture(autouse=True)
    def _disarm(self):
        faults.disarm()
        yield
        faults.disarm()

    def test_dropped_publish_degrades_gracefully(self, kv_server):
        kv = ("127.0.0.1", kv_server.port)
        rec0, rec1 = TraceRecorder(rank=0), TraceRecorder(rank=1)
        _exercise(rec0)
        _exercise(rec1)
        publish_segment(kv, 0, rec0.segment())
        faults.arm("trace.publish=*drop()")      # rank 1's publish vanishes
        publish_segment(kv, 1, rec1.segment())
        assert faults.hits("trace.publish") == 1
        faults.disarm()
        from horovod_tpu.runner.http_client import read_data_from_kvstore
        obj = json.loads(read_data_from_kvstore(
            "127.0.0.1", kv_server.port, "trace", "", timeout=5))
        # rank 1 is simply absent; the trace stays valid and rank 0 rich
        assert obj["otherData"]["ranks"] == [0]
        assert any(e.get("ph") == "B" for e in obj["traceEvents"])

    def test_publisher_counts_failures(self, tmp_path):
        """A publisher pointed at a dead server swallows + counts."""
        from horovod_tpu.metrics import registry
        from horovod_tpu.trace import TracePublisher
        reg = registry()
        before = reg.counter("hvd_tpu_trace_publish_failures_total").total()
        pub = TracePublisher(TraceRecorder(rank=0), ("127.0.0.1", 1),
                             rank=0, interval=60)
        pub.tick()                                # no thread needed
        assert reg.counter(
            "hvd_tpu_trace_publish_failures_total").total() == before + 1


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_dump_is_valid_single_rank_chrome_trace(self, tmp_path):
        rec = TraceRecorder(rank=1)
        _exercise(rec)
        rec.record_enqueue("hung.op", "allreduce", 8, 0)   # open at dump
        path = rec.dump(str(tmp_path / "sub" / "flight.json"))
        with open(path) as f:
            obj = json.load(f)
        assert obj["otherData"]["flight_recorder"] is True
        assert obj["otherData"]["rank"] == 1
        evs = obj["traceEvents"]
        assert {e["pid"] for e in evs if "pid" in e} == {1}
        # the hung op's span is sealed, flagged truncated
        sealed = [e for e in evs if e.get("ph") == "E"
                  and e.get("args", {}).get("truncated")]
        assert len(sealed) == 1
        sys.path.insert(0, TOOLS)
        try:
            import trace_report
            assert trace_report.check_events(evs) == []
        finally:
            sys.path.remove(TOOLS)


# ---------------------------------------------------------------------------
# engine wiring: correlation stamping + HOROVOD_TPU_TRACE=0 no-op contract
# ---------------------------------------------------------------------------

class TestEngineWiring:
    def test_engine_records_all_three_phases(self, monkeypatch):
        import numpy as np
        import horovod_tpu as hvd
        hvd.shutdown()
        monkeypatch.setenv("HOROVOD_TPU_TRACE", "1")
        hvd.init()
        try:
            gs = hvd.global_state()
            assert gs.trace_recorder is not None
            assert gs.engine.trace is gs.trace_recorder
            hvd.allreduce(np.ones(4, np.float32), name="wired.a",
                          op=hvd.Sum)
            evs = gs.trace_recorder.segment()["events"]
            phases = {e["p"] for e in evs if e.get("n") == "wired.a"}
            assert phases == {"enq", "dis", "done"}
            enq = next(e for e in evs
                       if e.get("n") == "wired.a" and e["p"] == "enq")
            name, wv, seq = parse_corr(enq["c"])
            assert (name, seq) == ("wired.a", 1)
            assert wv == gs.engine.world_version
        finally:
            hvd.shutdown()

    def test_trace_disabled_leaves_engine_hook_none(self, monkeypatch):
        """HOROVOD_TPU_TRACE=0: engine.trace stays None — the dispatch hot
        path pays one is-None check per site and takes no new lock (the
        HOROVOD_TPU_METRICS=0 discipline)."""
        import numpy as np
        import horovod_tpu as hvd
        hvd.shutdown()
        monkeypatch.setenv("HOROVOD_TPU_TRACE", "0")
        hvd.init()
        try:
            gs = hvd.global_state()
            assert gs.engine.trace is None
            assert gs.trace_recorder is None
            assert gs.trace_publisher is None
            # the hot path still works end to end
            out = np.asarray(hvd.allreduce(np.ones(2, np.float32),
                                           name="off.a", op=hvd.Sum))
            assert out[0] == hvd.size()
        finally:
            hvd.shutdown()


# ---------------------------------------------------------------------------
# tools/trace_report.py (report + --check, the tier-1 lint pattern)
# ---------------------------------------------------------------------------

class TestTraceReport:
    def _merged(self, tmp_path, late_rank=1, late=0.02):
        segs = {}
        base = time.monotonic()
        for r in (0, 1):
            rec = TraceRecorder(rank=r)
            shift = late if r == late_rank else 0.0
            for i in range(5):
                rec.record_step(begin=True)
                with _frozen_monotonic(base + i * 0.1 + shift):
                    rec.record_enqueue("g0", "allreduce", 64, 0)
                rec.record_dispatch("g0", "XLA_DISPATCH", 0.004)
                rec.record_done("g0")
                rec.record_step(begin=False)
            rec.add_beacon(base, 777.0 + base, 0.0)
            segs[r] = rec.segment()
        path = tmp_path / "merged.json"
        path.write_bytes(render_cluster_trace(
            {str(k): json.dumps(v) for k, v in segs.items()}))
        return str(path)

    # NOTE (ISSUE 7): the clean-merged-trace --check wiring moved to the
    # unified parametrized suite in tests/test_check.py (tools/check.py's
    # trace_schema lint builds a live 2-rank merged trace and runs
    # check_events on it); only the error-path test stays here.

    def test_check_catches_violations(self, tmp_path):
        bad = [{"ph": "E", "ts": 1.0, "pid": 0, "tid": 3},      # dangling
               {"ph": "B", "ts": 2.0, "pid": 0, "tid": 4,
                "args": {"corr": "missing-separators"}},        # malformed
               {"ph": "??", "ts": 3.0, "pid": 0}]               # bad phase
        p = tmp_path / "bad.json"
        p.write_text(json.dumps(bad))
        proc = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "trace_report.py"),
             str(p), "--check"], capture_output=True, text=True)
        assert proc.returncode == 1
        assert "dangling E" in proc.stdout
        assert "malformed correlation id" in proc.stdout
        assert "unclosed B" in proc.stdout

    def test_report_names_straggler_and_breaks_down_steps(self, tmp_path):
        sys.path.insert(0, TOOLS)
        try:
            import trace_report
            events = load_trace_events(
                open(self._merged(tmp_path, late_rank=1, late=0.02)).read())
            rep = trace_report.analyze(events)
        finally:
            sys.path.remove(TOOLS)
        assert rep["ranks"] == [0, 1]
        assert rep["top_straggler"] == 1
        s = rep["skew_by_kind"]["ALLREDUCE"]
        assert s["count"] == 5
        assert 0.01e6 < s["mean_us"] < 0.04e6
        # wire-vs-gap: 4ms dispatch per step recorded on both ranks
        for pid in (0, 1):
            w = rep["wire_vs_gap"][pid]
            assert w["steps"] == 5
            assert w["wire_us"] > 0
        cp = rep["critical_path"]
        assert cp["wait_by_rank"].get(1, 0) == pytest.approx(
            5 * 0.02e6, rel=0.3)

    def test_cli_report_runs(self, tmp_path):
        path = self._merged(tmp_path)
        proc = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "trace_report.py"), path],
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr
        assert "top stragglers" in proc.stdout
        assert "critical-path estimate" in proc.stdout


# ---------------------------------------------------------------------------
# np=2 end-to-end acceptance
# ---------------------------------------------------------------------------

def _worker_traced_job():
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.runner.http_client import read_data_from_kvstore

    gs = hvd.global_state()
    eng = hvd._engine()
    rank = hvd.rank()
    for step in range(4):
        eng.step_begin()
        hvd.allreduce(np.ones(4, np.float32), name="e2e.g0", op=hvd.Sum)
        hvd.allreduce(np.ones(8, np.float32), name="e2e.g1", op=hvd.Sum)
        eng.step_end()
    # deterministic publish (beacon + segment) before the fetch
    assert gs.trace_publisher is not None, "publisher not wired to the KV"
    gs.trace_publisher.tick()
    hvd.barrier()                      # both ranks have published
    body = None
    if rank == 0:
        import os
        addr = os.environ["HOROVOD_GLOO_RENDEZVOUS_ADDR"]
        port = int(os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"])
        body = read_data_from_kvstore(addr, port, "trace", "",
                                      timeout=10).decode()
    hvd.barrier()
    return {"rank": rank, "trace": body}


@pytest.mark.integration
def test_two_process_merged_trace_and_straggler_attribution():
    """Acceptance: np=2, rank 1 delayed 50 ms at every enqueue via the
    fault-injection subsystem. The merged /trace must be valid Chrome-trace
    JSON with per-rank pids, every collective joinable across ranks by
    correlation id exactly once per phase, and the report must attribute
    the delay to rank 1 with skew on its order of magnitude."""
    from horovod_tpu.runner import run
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_STALL_CHECK_DISABLE": "1",
        # replay off: every collective takes the normal enqueue path, so
        # the per-phase correlation assertion below is exact
        "HOROVOD_TPU_STEP_REPLAY": "0",
        "HOROVOD_TPU_FAULTS": "engine.enqueue@1=*delay(0.05)",
    }
    r0, r1 = run(_worker_traced_job, np=2, env=env)
    assert r1["trace"] is None
    obj = json.loads(r0["trace"])          # valid JSON — or this raises
    events = obj["traceEvents"]
    assert obj["otherData"]["ranks"] == [0, 1]

    # schema self-check over the real merged trace
    sys.path.insert(0, TOOLS)
    try:
        import trace_report
        assert trace_report.check_events(events) == []
        rep = trace_report.analyze(events)
    finally:
        sys.path.remove(TOOLS)

    # every e2e.* collective joinable: same corr ids on both pids, exactly
    # once per phase per rank
    per_pid = {0: {}, 1: {}}
    for e in events:
        if e.get("ph") not in ("B", "E"):
            continue
        corr = e.get("args", {}).get("corr")
        if not corr or not corr.startswith("e2e."):
            continue
        per_pid[e["pid"]].setdefault(corr, []).append(e["ph"])
    assert per_pid[0] and set(per_pid[0]) == set(per_pid[1])
    assert len(per_pid[0]) == 8            # 2 tensors x 4 steps
    for pid in (0, 1):
        for corr, phases in per_pid[pid].items():
            assert sorted(phases) == ["B", "E"], (pid, corr, phases)

    # straggler attribution: rank 1, skew on the 50 ms order of magnitude
    assert rep["top_straggler"] == 1
    skews = [ent for k, ent in trace_report.arrival_skew(events).items()
             if k.startswith("e2e.")]
    assert skews
    mean_skew_s = sum(e["skew_us"] for e in skews) / len(skews) / 1e6
    assert 0.005 < mean_skew_s < 1.0, mean_skew_s
    # the skew also rode the server's registry: the driver-side scrape in
    # otherData carries the straggler verdict
    assert obj["otherData"]["straggler_rank"] == 1


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

class _frozen_monotonic:
    """Context manager pinning ``trace``'s view of ``time.monotonic`` to a
    fixed value (synthesizing cross-rank arrival orders deterministically).
    The real ``time`` module is restored on exit."""

    def __init__(self, value: float):
        self.value = value

    def __enter__(self):
        self._orig = trace_mod.time

        class _T:
            monotonic = staticmethod(lambda v=self.value: v)

        trace_mod.time = _T
        return self

    def __exit__(self, *exc):
        trace_mod.time = self._orig
        return False
