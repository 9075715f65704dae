"""Metrics registry + cluster telemetry tests (ISSUE 3): registry
semantics (counter monotonicity, log2 histogram bucketing,
snapshot-is-copy), Prometheus text rendering, the KVStoreServer
``GET /metrics`` aggregation round-trip, the metric-namespace lint tool,
and an np=2 end-to-end scrape whose numbers reconcile with each worker's
``hvd.metrics_snapshot()``."""

import importlib.util
import json
import os
import re
import subprocess
import sys
import urllib.request

import numpy as np
import pytest

from horovod_tpu import metrics as hmetrics
from horovod_tpu.metrics import (METRIC_SPECS, Registry, _NOOP,
                                 render_prometheus,
                                 render_prometheus_cluster)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL_PAIR_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _parse_prom(text):
    """Minimal Prometheus text parser: returns (samples, type_lines) where
    samples is a list of (name, labels_dict, value). Any malformed line
    fails the parse (the 'Prometheus-parseable' acceptance bar)."""
    samples, type_lines = [], []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) >= 3 and parts[1] == "TYPE":
                type_lines.append((parts[2], parts[3]))
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        name, labelstr, val = m.groups()
        labels = dict(_LABEL_PAIR_RE.findall(labelstr)) if labelstr else {}
        v = float("inf") if val == "+Inf" else float(val)
        samples.append((name, labels, v))
    return samples, type_lines


def _tot(snap, name, section="counters"):
    ent = snap.get(section, {}).get(name)
    if not ent:
        return 0.0
    return sum(v for _, v in ent["values"])


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_monotonic(self):
        reg = Registry()
        c = reg.counter("hvd_tpu_test_a_total", help="h")
        c.inc()
        c.inc(4.0)
        assert c.value() == 5.0
        with pytest.raises(ValueError):
            c.inc(-1.0)
        assert c.value() == 5.0

    def test_counter_labels_independent(self):
        reg = Registry()
        c = reg.counter("hvd_tpu_test_b_total", help="h")
        c.inc(3, kind="allreduce", dtype="float32")
        c.inc(5, kind="allgather", dtype="float32")
        assert c.value(kind="allreduce", dtype="float32") == 3
        assert c.value(kind="allgather", dtype="float32") == 5
        assert c.total() == 8

    def test_gauge(self):
        reg = Registry()
        g = reg.gauge("hvd_tpu_test_g", help="h")
        g.set(7.0)
        g.set(2.5)
        assert g.value() == 2.5
        g.inc(0.5)
        assert g.value() == 3.0

    def test_histogram_log2_bucketing(self):
        reg = Registry()
        h = reg.histogram("hvd_tpu_test_h_seconds", help="h",
                          min_exp=-3, max_exp=3)
        assert h.bounds == [0.125, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0]
        for v in (0.3, 5.0, 100.0):
            h.observe(v, kind="x")
        [(labels, ent)] = h._snap()
        assert labels == {"kind": "x"}
        assert ent["count"] == 3
        assert ent["sum"] == pytest.approx(105.3)
        buckets = dict((str(le), c) for le, c in ent["buckets"])
        # 0.3 -> le=0.5; 5.0 -> le=8; 100 -> only +Inf (cumulative counts)
        assert buckets["0.5"] == 1
        assert buckets["8.0"] == 2
        assert buckets["+Inf"] == 3

    def test_snapshot_is_copy(self):
        reg = Registry()
        c = reg.counter("hvd_tpu_test_c_total", help="h")
        c.inc(2, kind="k")
        snap = reg.snapshot()
        snap["counters"]["hvd_tpu_test_c_total"]["values"][0][1] = 999
        snap["counters"]["hvd_tpu_test_c_total"]["values"][0][0]["kind"] = "x"
        fresh = reg.snapshot()
        assert fresh["counters"]["hvd_tpu_test_c_total"]["values"] == \
            [[{"kind": "k"}, 2.0]]

    def test_name_and_help_validation(self):
        reg = Registry()
        with pytest.raises(ValueError, match="must match"):
            reg.counter("bad-name", help="h")
        with pytest.raises(ValueError, match="must match"):
            reg.counter("not_hvd_prefixed_total", help="h")
        with pytest.raises(ValueError, match="help"):
            reg.counter("hvd_tpu_undeclared_total")   # no spec, no help
        # declared names resolve their help from METRIC_SPECS
        c = reg.counter("hvd_tpu_dispatches_total")
        assert c.help == METRIC_SPECS["hvd_tpu_dispatches_total"][1]

    def test_type_clash_rejected(self):
        reg = Registry()
        reg.counter("hvd_tpu_test_d_total", help="h")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("hvd_tpu_test_d_total", help="h")

    def test_event_log(self):
        reg = Registry()
        ev = reg.event_log("hvd_tpu_test_events", help="h", maxlen=4)
        for i in range(6):
            ev.append("join", f"rank{i}")
        ev.append("leave", "rank0")
        snap = ev._snap()
        assert len(snap["log"]) == 4            # bounded
        assert snap["log"][-1][0] == 7          # monotonic seq survives trim
        counts = {tuple(sorted(l.items())): v for l, v in snap["counts"]}
        assert counts[(("kind", "join"),)] == 6.0

    def test_disabled_registry_is_noop(self):
        reg = Registry(enabled=False)
        c = reg.counter("hvd_tpu_test_e_total", help="h")
        assert c is _NOOP
        c.inc(5)                                 # lock-free no-op
        assert c.total() == 0.0
        snap = reg.snapshot()
        assert snap["enabled"] is False and snap["counters"] == {}


# ---------------------------------------------------------------------------
# Prometheus rendering
# ---------------------------------------------------------------------------

class TestPrometheusRender:
    def _sample_registry(self):
        reg = Registry()
        c = reg.counter("hvd_tpu_wire_bytes_total")
        c.inc(1024, kind="allreduce", dtype="float32")
        c.inc(64, kind="allgather", dtype="int32")
        reg.gauge("hvd_tpu_fusion_bucket_fill_pct").set(42.5)
        h = reg.histogram("hvd_tpu_op_latency_seconds", min_exp=-3,
                          max_exp=3)
        h.observe(0.3, kind="allreduce")
        reg.event_log("hvd_tpu_elastic_events").append("rank_join", "h:0")
        return reg

    def test_render_single(self):
        text = render_prometheus(self._sample_registry().snapshot())
        samples, type_lines = _parse_prom(text)
        by = {}
        for name, labels, v in samples:
            by.setdefault(name, []).append((labels, v))
        assert ({"kind": "allreduce", "dtype": "float32"}, 1024.0) \
            in by["hvd_tpu_wire_bytes_total"]
        assert by["hvd_tpu_fusion_bucket_fill_pct"] == [({}, 42.5)]
        assert any(l.get("le") == "+Inf" and v == 1.0
                   for l, v in by["hvd_tpu_op_latency_seconds_bucket"])
        assert by["hvd_tpu_op_latency_seconds_count"] == \
            [({"kind": "allreduce"}, 1.0)]
        assert by["hvd_tpu_elastic_events_total"] == \
            [({"kind": "rank_join"}, 1.0)]
        kinds = dict(type_lines)
        assert kinds["hvd_tpu_op_latency_seconds"] == "histogram"
        assert kinds["hvd_tpu_wire_bytes_total"] == "counter"

    def test_render_cluster_per_rank_labels(self):
        s0 = self._sample_registry().snapshot()
        reg1 = self._sample_registry()
        reg1.counter("hvd_tpu_wire_bytes_total").inc(
            512, kind="allreduce", dtype="float32")
        s1 = reg1.snapshot()
        text = render_prometheus_cluster({"0": s0, "1": s1})
        samples, type_lines = _parse_prom(text)
        # exactly one TYPE line per family even with two ranks
        names = [n for n, _ in type_lines]
        assert len(names) == len(set(names))
        wire = {l["rank"]: v for n, l, v in samples
                if n == "hvd_tpu_wire_bytes_total"
                and l.get("kind") == "allreduce"}
        assert wire == {"0": 1024.0, "1": 1536.0}

    def test_label_escaping(self):
        reg = Registry()
        reg.counter("hvd_tpu_test_esc_total", help="h").inc(
            1, reason='divergence "op #" \\ mid\nstep')
        text = render_prometheus(reg.snapshot())
        samples, _ = _parse_prom(text)
        [(name, labels, v)] = samples
        assert labels["reason"].startswith("divergence")


# ---------------------------------------------------------------------------
# KVStoreServer GET /metrics round-trip
# ---------------------------------------------------------------------------

import contextlib


@contextlib.contextmanager
def _isolated_registry():
    """Swap the process-global registry for a fresh empty one.

    The scrape endpoint merges the *server process's own* registry into
    the response under rank="driver" — by design (elastic telemetry on
    the launcher). In-process tests share one interpreter, so whatever
    counters earlier test files left in the global registry (engine wire
    bytes from test_stall/test_trace/test_chaos runs) would leak into
    these exact-value assertions. This was a real ORDER DEPENDENCE:
    TestScrapeEndpoint failed whenever registry-touching suites ran
    first (reproduced at PR 7 HEAD with `pytest tests/test_stall.py
    tests/test_trace.py tests/test_metrics.py::TestScrapeEndpoint`)."""
    with hmetrics._registry_lock:
        saved = hmetrics._registry
        hmetrics._registry = Registry()
    try:
        yield
    finally:
        with hmetrics._registry_lock:
            hmetrics._registry = saved


class TestScrapeEndpoint:
    @pytest.fixture(autouse=True)
    def _fresh_registry(self):
        with _isolated_registry():
            yield

    def test_scrape_isolated_from_polluted_process_registry(self):
        """Regression for the order dependence itself, in the
        non-alphabetical order: pollute the process registry the way an
        earlier engine/stall/trace suite does, THEN run the round-trip
        under the isolation this class now applies — the driver merge
        must not leak the polluted series into the assertions."""
        polluted = hmetrics.registry()   # the real global (fixture-swapped
        # to a fresh one, so this test's pollution is itself contained)
        polluted.counter("hvd_tpu_wire_bytes_total").inc(
            320.0, kind="allreduce", dtype="float32")
        polluted.counter("hvd_tpu_dispatches_total").inc(12)
        with _isolated_registry():
            self.test_kvstore_metrics_roundtrip()
            self.test_metrics_scrape_empty_store()

    def test_kvstore_metrics_roundtrip(self):
        from horovod_tpu.runner.http_server import KVStoreServer
        server = KVStoreServer(("127.0.0.1", 0))
        port = server.start()
        try:
            for rank in (0, 1):
                reg = Registry()
                reg.counter("hvd_tpu_wire_bytes_total").inc(
                    100 * (rank + 1), kind="allreduce", dtype="float32")
                reg.counter("hvd_tpu_dispatches_total").inc(7 + rank)
                hmetrics.publish_snapshot(("127.0.0.1", port), rank,
                                          reg.snapshot())
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
                ctype = resp.headers.get("Content-Type", "")
                text = resp.read().decode()
            assert "text/plain" in ctype and "0.0.4" in ctype
            samples, type_lines = _parse_prom(text)
            names = [n for n, _ in type_lines]
            assert len(names) == len(set(names))
            wire = {l["rank"]: v for n, l, v in samples
                    if n == "hvd_tpu_wire_bytes_total"}
            assert wire == {"0": 100.0, "1": 200.0}
            disp = {l["rank"]: v for n, l, v in samples
                    if n == "hvd_tpu_dispatches_total"}
            assert disp == {"0": 7.0, "1": 8.0}
        finally:
            server.stop()

    def test_metrics_scrape_empty_store(self):
        from horovod_tpu.runner.http_server import KVStoreServer
        server = KVStoreServer(("127.0.0.1", 0))
        port = server.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
                text = resp.read().decode()
            samples, _ = _parse_prom(text)     # parseable, just empty
            # ISSUE 18: the server accounts its own KV traffic, so the
            # driver merge may surface hvd_tpu_kv_request{s,_bytes}_total
            # (including this very scrape) — no OTHER telemetry allowed
            # from an empty store.
            extras = [s for s in samples
                      if not s[0].startswith("hvd_tpu_kv_request")]
            assert extras == []
        finally:
            server.stop()

    def test_rendezvous_server_inherits_metrics_route(self):
        from horovod_tpu.runner.http_server import RendezvousServer
        server = RendezvousServer(("127.0.0.1", 0))
        port = server.start()
        try:
            server.init([])
            reg = Registry()
            reg.counter("hvd_tpu_dispatches_total").inc(3)
            hmetrics.publish_snapshot(("127.0.0.1", port), 0, reg.snapshot())
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
                samples, _ = _parse_prom(resp.read().decode())
            assert ("hvd_tpu_dispatches_total", {"rank": "0"}, 3.0) \
                in samples
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# tools/check_metric_names.py (CI lint)
# ---------------------------------------------------------------------------

def _load_checker():
    spec = importlib.util.spec_from_file_location(
        "check_metric_names",
        os.path.join(REPO, "tools", "check_metric_names.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestMetricNameLint:
    # NOTE (ISSUE 7): the clean-tree wiring (declared specs lint-clean +
    # CLI exit 0) moved to the unified parametrized suite in
    # tests/test_check.py (tools/check.py runs every lint); only the
    # error-path unit test stays here next to the registry it exercises.

    def test_bad_specs_flagged(self):
        checker = _load_checker()
        errs = checker.validate_specs({
            "Bad-Name": ("counter", "h"),
            "hvd_tpu_no_help_total": ("counter", ""),
            "hvd_tpu_wrong_type": ("meter", "h"),
            "hvd_tpu_counter_without_suffix": ("counter", "h"),
        })
        joined = "\n".join(errs)
        assert "Bad-Name: does not match" in joined
        assert "hvd_tpu_no_help_total: missing help" in joined
        assert "unknown metric type 'meter'" in joined
        assert "hvd_tpu_counter_without_suffix: counters must end" in joined


# ---------------------------------------------------------------------------
# live engine instrumentation (size-1 in-process world)
# ---------------------------------------------------------------------------

class TestLiveInstrumentation:
    def test_engine_populates_registry(self):
        import horovod_tpu as hvd
        hvd.init()
        base = hvd.metrics_snapshot()
        assert base["enabled"] is True
        hvd.allreduce(np.ones(16, np.float32), name="met.ar", op=hvd.Sum)
        hvd.grouped_allreduce(
            [np.ones(4, np.float32), np.ones((2, 3), np.float32)],
            name="met.g", op=hvd.Sum)
        snap = hvd.metrics_snapshot()
        # wire bytes: 64 (allreduce) + 16 + 24 (grouped)
        assert _tot(snap, "hvd_tpu_wire_bytes_total") \
            - _tot(base, "hvd_tpu_wire_bytes_total") == 104.0
        assert _tot(snap, "hvd_tpu_dispatches_total") \
            > _tot(base, "hvd_tpu_dispatches_total")
        kinds = {tuple(sorted(l.items()))
                 for l, _ in snap["counters"]["hvd_tpu_wire_bytes_total"]
                 ["values"]}
        # every wire series carries the fabric-link label (ISSUE 10);
        # a size-1 world moves everything over link="flat"
        assert (("dtype", "float32"), ("kind", "allreduce"),
                ("link", "flat")) in kinds
        # the sync allreduce retires through synchronize -> latency observed
        lat = snap["histograms"]["hvd_tpu_op_latency_seconds"]["values"]
        assert any(l.get("kind") == "allreduce" and ent["count"] >= 1
                   for l, ent in lat)
        # bucket accounting moved with the grouped call
        assert _tot(snap, "hvd_tpu_fusion_buckets_total") \
            - _tot(base, "hvd_tpu_fusion_buckets_total") >= 1

    def test_jsonl_emitter(self, tmp_path, monkeypatch):
        import horovod_tpu as hvd
        hvd.shutdown()
        path = str(tmp_path / "metrics.jsonl")
        monkeypatch.setenv("HOROVOD_TPU_METRICS_FILE", path)
        monkeypatch.setenv("HOROVOD_TPU_METRICS_INTERVAL", "3600")
        hvd.init()
        hvd.allreduce(np.ones(4, np.float32), name="emit.ar", op=hvd.Sum)
        hvd.shutdown()                   # final flush writes one record
        with open(path) as f:
            lines = [json.loads(l) for l in f if l.strip()]
        assert lines, "emitter wrote nothing"
        rec = lines[-1]
        assert rec["rank"] == 0
        assert "hvd_tpu_wire_bytes_total" in rec["metrics"]["counters"]

    def test_metrics_disabled_no_dispatch_bookkeeping(self, monkeypatch):
        import horovod_tpu as hvd
        from horovod_tpu import metrics
        hvd.shutdown()
        monkeypatch.setenv("HOROVOD_TPU_METRICS", "0")
        metrics._reset_registry_for_tests()
        try:
            hvd.init()
            eng = hvd._engine()
            assert eng._m_enabled is False
            assert eng._m_dispatches is _NOOP
            hvd.allreduce(np.ones(4, np.float32), name="dis.ar", op=hvd.Sum)
            snap = hvd.metrics_snapshot()
            assert snap["enabled"] is False and snap["counters"] == {}
        finally:
            hvd.shutdown()
            monkeypatch.setenv("HOROVOD_TPU_METRICS", "1")
            metrics._reset_registry_for_tests()


# ---------------------------------------------------------------------------
# np=2: publish -> aggregate -> scrape, numbers reconcile with snapshots
# ---------------------------------------------------------------------------

def _worker_metrics_scrape():
    import os
    import urllib.request
    import numpy as np
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu import metrics as hmetrics

    rank = hvd.rank()
    # six identical replay-bracketed steps: arm at streak 3 (default
    # warmup), replay the tail -> armed/replayed counters move
    for i in range(6):
        with hvd.step():
            hs = hvd.grouped_allreduce_async(
                [np.ones(8, np.float32), np.ones((2, 2), np.float32)],
                name=f"mg{i}", op=hvd.Sum)
        for h in hs:
            h.synchronize()
    # one divergent step (plain allreduce doesn't match the armed grouped
    # stream) -> a replay fallback
    with hvd.step():
        hvd.allreduce(np.ones(4, np.float32), name="mdiv", op=hvd.Sum)
    hvd.barrier()
    snap = hvd.metrics_snapshot()
    addr = os.environ["HOROVOD_GLOO_RENDEZVOUS_ADDR"]
    port = int(os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"])
    hmetrics.publish_snapshot((addr, port), rank, snap)
    # wait for every rank's publish by polling the KV — NOT a barrier: a
    # collective here would advance the counters after the snapshot, and
    # the emitter's shutdown final-flush republish would then diverge from
    # the snapshot this worker returns (scrape reconciliation would race)
    from horovod_tpu.runner.http_client import read_data_from_kvstore
    for r in range(hvd.size()):
        read_data_from_kvstore(addr, port, "metrics", str(r), timeout=30)
    text, ctype = None, None
    if rank == 0:
        with urllib.request.urlopen(f"http://{addr}:{port}/metrics",
                                    timeout=15) as resp:
            ctype = resp.headers.get("Content-Type", "")
            text = resp.read().decode()

    def tot(name):
        ent = snap["counters"].get(name, {"values": []})
        return sum(v for _, v in ent["values"])

    return {"rank": rank,
            "wire": tot("hvd_tpu_wire_bytes_total"),
            "disp": tot("hvd_tpu_dispatches_total"),
            "armed": tot("hvd_tpu_replay_armed_total"),
            "replayed": tot("hvd_tpu_replay_replayed_steps_total"),
            "fallbacks": tot("hvd_tpu_replay_fallbacks_total"),
            "text": text, "ctype": ctype}


@pytest.mark.integration
@pytest.mark.skipif(os.environ.get("HVD_TPU_SKIP_MULTIPROC") == "1",
                    reason="multi-process tier disabled")
def test_two_rank_scrape_reconciles_with_snapshots():
    """ISSUE 3 acceptance: a two-rank run scraped via GET /metrics on the
    rendezvous server returns Prometheus-parseable text whose per-rank
    wire-byte/dispatch/replay counters equal each worker's own
    hvd.metrics_snapshot()."""
    from horovod_tpu.runner import run
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "HOROVOD_STALL_CHECK_DISABLE": "1",
        # periodic emitter must not overwrite the deterministic publish
        "HOROVOD_TPU_METRICS_INTERVAL": "3600",
    }
    results = run(_worker_metrics_scrape, np=2, env=env)
    r0 = next(r for r in results if r["rank"] == 0)
    assert r0["text"], "rank 0 scraped nothing"
    assert "text/plain" in r0["ctype"]
    samples, type_lines = _parse_prom(r0["text"])
    names = [n for n, _ in type_lines]
    assert len(names) == len(set(names)), "duplicate TYPE lines"
    for r in results:
        rk = str(r["rank"])

        def scraped(name):
            return sum(v for n, l, v in samples
                       if n == name and l.get("rank") == rk)

        assert r["wire"] > 0
        assert scraped("hvd_tpu_wire_bytes_total") == \
            pytest.approx(r["wire"]), rk
        assert scraped("hvd_tpu_dispatches_total") == \
            pytest.approx(r["disp"]), rk
        assert r["armed"] >= 1 and r["replayed"] >= 1, r
        assert scraped("hvd_tpu_replay_armed_total") == \
            pytest.approx(r["armed"]), rk
        assert r["fallbacks"] >= 1, r
        assert scraped("hvd_tpu_replay_fallbacks_total") == \
            pytest.approx(r["fallbacks"]), rk
