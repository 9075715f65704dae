"""Step-health layer tests (ISSUE 20): detector math (rolling
median/MAD baselines, warmup gate, edge-triggered classification),
flight-dump rate limiting, HBM sampler degradation, the one-branch
disabled mode, a perf-marked overhead smoke, and the np=2 acceptance —
a delay failpoint armed on rank 1 mid-run must surface as a
``straggler_drift`` anomaly naming rank 1, write a flight dump, and
show up in the Prometheus scrape."""

import json
import os
import re
import time
import urllib.request

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import metrics as hmetrics
from horovod_tpu.observability import (AnomalyDetector, FlightDumper,
                                       HBMSampler, RollingBaseline,
                                       StepDigest, StepHealthMonitor)


@pytest.fixture
def isolated_registry():
    """Swap in a fresh process registry: instruments these tests bump
    (anomaly counters, HBM gauges) must not leak into the KV server's
    merged scrape that the health-report tests read."""
    with hmetrics._registry_lock:
        saved = hmetrics._registry
        hmetrics._registry = hmetrics.Registry()
    try:
        yield
    finally:
        with hmetrics._registry_lock:
            hmetrics._registry = saved


def _digest(step, wall, wait=0.0, dispatches=2, wire=1024.0, fallbacks=0):
    return StepDigest(
        step=step, wall_s=wall, dispatches=dispatches, wire_bytes=wire,
        wire_by_link={"flat": wire}, collective_wait_s=wait,
        wait_by_kind={"allreduce": wait}, replay_replayed=0,
        replay_fallbacks=fallbacks, replay_armed=False, prefetch_hits=0,
        bucket_fill_pct=0.0, compression_saved=0.0)


def _warm(det, n=12, wall=0.010, wait=0.004, **kw):
    """Feed n baseline digests with deterministic jitter so the MAD is
    small but nonzero."""
    for i in range(n):
        det.observe(_digest(i, wall + 1e-4 * (i % 3),
                            wait=wait + 1e-4 * (i % 2), **kw))
    return n


# ---------------------------------------------------------------------------
# RollingBaseline: median/MAD math and the warmup gate
# ---------------------------------------------------------------------------

class TestRollingBaseline:
    def test_median_mad_match_numpy(self):
        rng = np.random.RandomState(7)
        vals = list(rng.uniform(1.0, 5.0, size=40))
        base = RollingBaseline(window=64, warmup=4)
        for v in vals:
            base.update(v)
        assert base.median == pytest.approx(np.median(vals))
        assert base.mad == pytest.approx(
            np.median(np.abs(np.asarray(vals) - np.median(vals))))

    def test_window_bounds_history(self):
        base = RollingBaseline(window=8, warmup=2)
        for v in range(100):
            base.update(float(v))
        # only the last 8 samples (92..99) remain
        assert base.median == pytest.approx(np.median(range(92, 100)))
        assert len(base) == 8

    def test_warmup_gate(self):
        base = RollingBaseline(window=16, warmup=6)
        for i in range(5):
            base.update(1.0)
            assert not base.ready
            # a wild outlier scores 0.0 until the gate opens
            assert base.deviation(100.0) == 0.0
        base.update(1.0)
        assert base.ready
        assert base.deviation(100.0) > 0.0

    def test_floor_prevents_hair_trigger(self):
        # perfectly constant baseline -> MAD 0; the floor keeps the
        # deviation finite and proportional
        base = RollingBaseline(window=16, warmup=4, floor=0.5)
        for _ in range(8):
            base.update(10.0)
        assert base.mad == 0.0
        assert base.deviation(11.0) == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# AnomalyDetector: classification rules, edge triggering
# ---------------------------------------------------------------------------

class TestAnomalyDetector:
    def test_no_anomalies_during_warmup(self):
        det = AnomalyDetector(window=32, warmup=8)
        for i in range(7):
            # wild values, but the gate is closed
            assert det.observe(_digest(i, 0.010 * (i + 1),
                                       wire=1024.0 * (i + 1))) == []

    def test_spike_with_flat_wait_is_straggler_drift(self):
        det = AnomalyDetector(window=32, warmup=8)
        n = _warm(det)
        out = det.observe(_digest(n, wall=0.100, wait=0.004), rank=1)
        classes = {a.cls for a in out}
        assert "step_time_spike" in classes
        assert "straggler_drift" in classes
        drift = next(a for a in out if a.cls == "straggler_drift")
        assert "rank 1 is the straggler" in drift.detail
        assert "local to rank 1" in drift.detail

    def test_spike_with_spiking_wait_is_straggler_wait(self):
        det = AnomalyDetector(window=32, warmup=8)
        n = _warm(det)
        out = det.observe(_digest(n, wall=0.100, wait=0.090), rank=0)
        classes = {a.cls for a in out}
        assert "step_time_spike" in classes
        assert "straggler_wait" in classes
        assert "straggler_drift" not in classes

    def test_spike_is_edge_triggered(self):
        det = AnomalyDetector(window=32, warmup=8)
        n = _warm(det)
        first = det.observe(_digest(n, wall=0.100, wait=0.004))
        assert any(a.cls == "step_time_spike" for a in first)
        # staying in the spike regime emits nothing new
        again = det.observe(_digest(n + 1, wall=0.100, wait=0.004))
        assert not any(a.cls == "step_time_spike" for a in again)

    def test_sustained_regression_fires_once_per_episode(self):
        det = AnomalyDetector(window=64, warmup=8, sustain=3)
        n = _warm(det)
        seen = []
        for i in range(6):
            seen += det.observe(_digest(n + i, wall=0.013, wait=0.004))
        sustained = [a for a in seen if a.cls == "sustained_regression"]
        assert len(sustained) == 1
        assert "consecutive steps above baseline" in sustained[0].detail

    def test_dispatch_change_names_replay_fallback(self):
        det = AnomalyDetector(window=32, warmup=8)
        n = _warm(det)
        out = det.observe(_digest(n, wall=0.010, wait=0.004,
                                  dispatches=9, fallbacks=1))
        change = [a for a in out if a.cls == "dispatch_change"]
        assert len(change) == 1
        assert "replay fell back to eager dispatch" in change[0].detail
        # regime persists -> edge-triggered, no repeat
        for i in range(3):
            more = det.observe(_digest(n + 1 + i, wall=0.010, wait=0.004,
                                       dispatches=9, fallbacks=0))
            assert not any(a.cls == "dispatch_change" for a in more)

    def test_wire_shift(self):
        det = AnomalyDetector(window=32, warmup=8)
        n = _warm(det)
        out = det.observe(_digest(n, wall=0.010, wait=0.004, wire=65536.0))
        assert any(a.cls == "wire_shift" for a in out)


# ---------------------------------------------------------------------------
# FlightDumper: rate limit, swallowed dump failures
# ---------------------------------------------------------------------------

class TestFlightDumper:
    def test_rate_limit(self, isolated_registry):
        calls = []

        def dump():
            calls.append(1)
            return "/tmp/flight.json"

        fd = FlightDumper(dump, min_interval=3600.0)
        assert fd(trigger="step_time_spike") == "/tmp/flight.json"
        # a storm of triggers inside the interval is one dump
        for _ in range(10):
            assert fd(trigger="step_time_spike") is None
        assert len(calls) == 1

    def test_zero_interval_always_dumps(self, isolated_registry):
        calls = []
        fd = FlightDumper(lambda: calls.append(1) or "/x", min_interval=0.0)
        fd()
        fd()
        assert len(calls) == 2

    def test_dump_failure_is_swallowed(self, isolated_registry):
        def bad():
            raise OSError("disk full")

        fd = FlightDumper(bad, min_interval=0.0)
        assert fd(trigger="manual") is None  # no raise


# ---------------------------------------------------------------------------
# HBMSampler: graceful degradation off-device
# ---------------------------------------------------------------------------

class TestHBMSampler:
    def test_unsupported_platform_disables_after_first_sample(self, isolated_registry):
        probes = []

        def stats():
            probes.append(1)
            return None  # CPU-style: no memory_stats

        s = HBMSampler(stats_fn=stats)
        assert s.sample() is None
        assert s.sample() is None
        assert len(probes) == 1  # detected once, never probed again
        assert s.last() == (None, None)

    def test_watermark_tracks_last_sample(self, isolated_registry):
        s = HBMSampler(stats_fn=lambda: {
            "bytes_in_use": 1 << 30, "peak_bytes_in_use": 2 << 30,
            "bytes_limit": 16 << 30})
        out = s.sample()
        assert out["bytes_in_use"] == 1 << 30
        assert s.last() == (1 << 30, 2 << 30)

    def test_reserved_bytes_count_as_taken(self, isolated_registry):
        """A loaded program's temporaries are ``bytes_reserved`` on the TPU
        runtime: the gauge publishes them and the digest's watermark adds
        them, while ``peak_bytes_in_use`` is passed on as reported."""
        s = HBMSampler(stats_fn=lambda: {
            "bytes_in_use": 8 << 30, "bytes_reserved": 3 << 30,
            "peak_bytes_in_use": 8 << 30, "bytes_limit": 16 << 30})
        s.sample()
        assert s.last() == (11 << 30, 8 << 30)
        by_kind = {labels["kind"]: v for labels, v in
                   hmetrics.registry().gauge("hvd_tpu_hbm_bytes")._snap()}
        assert by_kind == {"in_use": float(8 << 30),
                           "reserved": float(3 << 30),
                           "peak": float(8 << 30),
                           "limit": float(16 << 30)}

    def test_raising_stats_fn_degrades(self, isolated_registry):
        def boom():
            raise NotImplementedError("no memory_stats on this runtime")

        s = HBMSampler(stats_fn=boom)
        assert s.sample() is None
        assert s.sample() is None  # disabled, not retried


# ---------------------------------------------------------------------------
# Disabled mode: exactly one branch on the step path
# ---------------------------------------------------------------------------

class TestDisabledMode:
    def test_step_health_0_leaves_engine_health_none(self, monkeypatch):
        from horovod_tpu.core.state import global_state
        monkeypatch.setenv("HOROVOD_TPU_STEP_HEALTH", "0")
        hvd.shutdown()
        hvd.init()
        try:
            gs = global_state()
            assert gs.engine.health is None
            assert gs.step_health is None
            # steps still work, no digests anywhere
            with hvd.step():
                hvd.allreduce(np.ones(2, np.float32), name="shd.off",
                              op=hvd.Sum)
        finally:
            hvd.shutdown()
        monkeypatch.delenv("HOROVOD_TPU_STEP_HEALTH")
        hvd.init()
        try:
            assert global_state().engine.health is not None
        finally:
            hvd.shutdown()

    def test_step_path_has_exactly_one_health_branch(self):
        """The acceptance bar: disabled mode adds exactly one is-None
        check to the step path (the PR 3 engine.trace discipline)."""
        import horovod_tpu.core.engine as engine_mod
        import inspect
        src = inspect.getsource(engine_mod)
        assert len(re.findall(r"self\.health is not None", src)) == 1
        assert not re.findall(r"self\.health\b", inspect.getsource(
            engine_mod.Engine.step_begin))


# ---------------------------------------------------------------------------
# Perf smoke: digest + detector overhead per step
# ---------------------------------------------------------------------------

class _FakeEngine:
    def __init__(self):
        self.dispatch_count = 0
        self.step_index = 0


@pytest.mark.perf
def test_step_health_overhead_under_one_percent(isolated_registry):
    """ISSUE 20 acceptance: on_step_end (digest assembly + baseline
    update + classification) costs < 1% of a 10 ms reference step."""
    eng = _FakeEngine()
    mon = StepHealthMonitor(eng, rank=0)
    costs = []
    for _ in range(300):
        eng.dispatch_count += 3
        eng.step_index += 1
        t0 = time.perf_counter()
        mon.on_step_end()
        costs.append(time.perf_counter() - t0)
    costs.sort()
    median = costs[len(costs) // 2]
    assert median < 100e-6, f"median on_step_end cost {median * 1e6:.1f} us"
    assert len(mon.recent()) == 300


# ---------------------------------------------------------------------------
# health_report --format=json against a live 2-rank scrape
# ---------------------------------------------------------------------------

def _load_tool(name):
    import importlib.util
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(repo, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rank_snap(rank, anomalies=0.0):
    snap = {
        "enabled": True,
        "counters": {
            "hvd_tpu_steps_total": {"help": "s", "values": [[{}, 50.0]]},
        },
        "gauges": {
            "hvd_tpu_hbm_bytes": {"help": "h", "values": [
                [{"kind": "in_use"}, 4.0e9], [{"kind": "reserved"}, 3.0e9],
                [{"kind": "peak"}, 6.0e9], [{"kind": "limit"}, 16.0e9]]},
        },
        "histograms": {
            "hvd_tpu_step_seconds": {"help": "st", "values": [
                [{}, {"sum": 0.55, "count": 50,
                      "buckets": [[0.008, 10], [0.016, 45],
                                  ["+Inf", 50]]}]]},
        },
        "events": {},
    }
    if anomalies:
        snap["counters"]["hvd_tpu_step_anomalies_total"] = {
            "help": "a",
            "values": [[{"class": "straggler_drift"}, anomalies]]}
    return snap


class TestHealthReportJSON:
    """ISSUE 20 satellite: ``--format=json`` emits the check.py-shaped
    verdict and exits nonzero when any section is red.

    Every test takes ``isolated_registry``: the in-process KV server
    merges the server process's OWN registry into ``GET /metrics``, so
    without isolation the hundreds of tests that ran earlier in the
    suite leak real step histograms and anomaly counters into the
    scrape and flip the verdict."""

    def _serve(self, snaps):
        from horovod_tpu.metrics import publish_snapshot
        from horovod_tpu.runner.http_server import KVStoreServer
        server = KVStoreServer(("127.0.0.1", 0))
        server.start()
        for rank, snap in enumerate(snaps):
            publish_snapshot(("127.0.0.1", server.port), rank, snap)
        return server

    def test_green_cluster_exits_zero(self, capsys, isolated_registry):
        health = _load_tool("health_report")
        server = self._serve([_rank_snap(0), _rank_snap(1)])
        try:
            rc = health.main(["--url", f"http://127.0.0.1:{server.port}",
                              "--format=json"])
        finally:
            server.stop()
        verdict = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert verdict["ok"] is True
        sh = verdict["checks"]["step_health"]
        assert sh["ok"] is True
        assert sh["stats"]["steps_observed"] == 100
        assert sh["stats"]["step_time_p50_ms"] is not None
        # limit less in_use less reserved (a loaded program's temporaries)
        assert sh["stats"]["hbm_min_headroom_bytes"] == pytest.approx(9.0e9)

    def test_anomalies_turn_step_health_red(self, capsys, isolated_registry):
        health = _load_tool("health_report")
        server = self._serve([_rank_snap(0),
                              _rank_snap(1, anomalies=3.0)])
        try:
            rc = health.main(["--url", f"http://127.0.0.1:{server.port}",
                              "--format=json"])
        finally:
            server.stop()
        verdict = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert verdict["ok"] is False
        sh = verdict["checks"]["step_health"]
        assert sh["ok"] is False
        assert any("straggler_drift" in e for e in sh["errors"])

    def test_text_mode_renders_slo_section(self, capsys, isolated_registry):
        health = _load_tool("health_report")
        server = self._serve([_rank_snap(0), _rank_snap(1)])
        try:
            rc = health.main(["--url", f"http://127.0.0.1:{server.port}"])
        finally:
            server.stop()
        out = capsys.readouterr().out
        assert rc == 0
        assert "step health / SLO" in out


# ---------------------------------------------------------------------------
# np=2 acceptance: delay failpoint on rank 1 -> straggler_drift names
# rank 1, flight dump on disk, anomaly counter in the scrape
# ---------------------------------------------------------------------------

def _worker_step_health():
    import os
    import urllib.request
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu import faults
    from horovod_tpu import metrics as hmetrics
    from horovod_tpu.core.state import global_state

    rank = hvd.rank()
    warm = 14

    def one_step(i):
        with hvd.step():
            out = hvd.allreduce(np.ones(64, np.float32),
                                name=f"sh.b{i}", op=hvd.Sum)
        return out

    for i in range(warm):
        one_step(i)
    # mid-run: rank 1 goes slow — an existing delay failpoint at the
    # enqueue seam, rank-local (the sleep runs BEFORE the handle's
    # enqueue timestamp, so rank 1's own collective wait stays flat)
    if rank == 1:
        faults.arm("engine.enqueue=3*delay(0.25)")
    for i in range(warm, warm + 6):
        one_step(i)
    faults.disarm()

    mon = global_state().step_health
    anomalies = mon.recent_anomalies()
    dump_path = os.path.join(os.environ["HOROVOD_TPU_TRACE_DUMP_DIR"],
                             f"hvd_tpu_flight_rank{rank}.json")

    snap = hvd.metrics_snapshot()
    addr = os.environ["HOROVOD_GLOO_RENDEZVOUS_ADDR"]
    port = int(os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"])
    hmetrics.publish_snapshot((addr, port), rank, snap)
    # poll the KV for every rank's publish — NOT a barrier (a collective
    # here would advance counters past the returned snapshot)
    from horovod_tpu.runner.http_client import read_data_from_kvstore
    for r in range(hvd.size()):
        read_data_from_kvstore(addr, port, "metrics", str(r), timeout=30)
    text = None
    if rank == 0:
        with urllib.request.urlopen(f"http://{addr}:{port}/metrics",
                                    timeout=15) as resp:
            text = resp.read().decode()
    return {
        "rank": rank,
        "classes": sorted({a.cls for a in anomalies}),
        "details": [a.detail for a in anomalies],
        "anomaly_count": mon.anomaly_count,
        "digests": len(mon.recent()),
        "dump_exists": os.path.exists(dump_path),
        "text": text,
    }


@pytest.mark.integration
@pytest.mark.skipif(os.environ.get("HVD_TPU_SKIP_MULTIPROC") == "1",
                    reason="multi-process tier disabled")
def test_two_rank_straggler_anomaly_end_to_end(tmp_path):
    """ISSUE 20 acceptance: a delay failpoint armed on rank 1 mid-run
    produces a straggler-drift anomaly that names rank 1, an automatic
    flight dump on disk, and an anomaly counter visible in the
    Prometheus scrape."""
    from horovod_tpu.runner import run
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_STALL_CHECK_DISABLE": "1",
        "HOROVOD_TPU_METRICS_INTERVAL": "3600",
        # keep every op on the eager path so the enqueue failpoint and
        # the per-op latency histogram stay live under the delay
        "HOROVOD_TPU_STEP_REPLAY": "0",
        "HOROVOD_TPU_TRACE_DUMP_DIR": str(tmp_path),
    }
    results = run(_worker_step_health, np=2, env=env)
    r0 = next(r for r in results if r["rank"] == 0)
    r1 = next(r for r in results if r["rank"] == 1)
    assert r0["digests"] == 20 and r1["digests"] == 20

    # the delayed rank detects ITSELF: step time spiked while its own
    # collective wait stayed flat
    assert "straggler_drift" in r1["classes"], r1
    assert any("rank 1 is the straggler" in d for d in r1["details"]), r1
    # the healthy rank saw its wait spike (waiting on rank 1)
    assert "step_time_spike" in r0["classes"], r0

    # automatic flight dump (rate-limited) on the anomalous rank
    assert r1["dump_exists"], "anomaly produced no flight dump"
    dump = tmp_path / "hvd_tpu_flight_rank1.json"
    with open(dump) as f:
        assert json.load(f)["otherData"]["flight_recorder"] is True

    # anomaly counter rides the normal publish -> scrape path
    assert r0["text"], "rank 0 scraped nothing"
    anom_lines = [ln for ln in r0["text"].splitlines()
                  if ln.startswith("hvd_tpu_step_anomalies_total{")]
    assert anom_lines, "scrape carries no step anomaly counter"
    assert any('class="straggler_drift"' in ln for ln in anom_lines), \
        anom_lines
