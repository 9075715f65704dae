"""Topology layer + collective algorithm selection (ISSUE 10).

Unit surface: the :class:`~horovod_tpu.parallel.mesh.Topology`
descriptor (detection, the HOROVOD_TPU_LOCAL_SIZE override,
non-divisible fallback), the pure selection rules
(``ops.collectives.choose_algorithm`` / ``validate_algorithm``), the
per-link wire attribution (``link_split`` + the engine's link-labeled
accounting) and the trace/report link breakdown. Compiled-program structure per selected algorithm lives in
tests/test_compiled_structure.py; real np=2 forced-algorithm parity in
tests/test_multiprocess.py.
"""

import logging

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.common.env import HOROVOD_TPU_LOCAL_SIZE
from horovod_tpu.ops import collectives as C
from horovod_tpu.parallel.mesh import Topology, detect_topology


def _topo(size, local, platform="tpu"):
    return Topology(size=size, local_size=local, platform=platform,
                    source="override")


# ---------------------------------------------------------------------------
# Topology descriptor + detection
# ---------------------------------------------------------------------------

class TestTopology:
    def test_hierarchical_ok_requires_nontrivial_exact_factorization(self):
        assert _topo(8, 4).hierarchical_ok
        assert not _topo(8, 1).hierarchical_ok   # flat
        assert not _topo(8, 8).hierarchical_ok   # one island
        assert not _topo(6, 4).hierarchical_ok   # non-divisible
        assert not _topo(1, 1).hierarchical_ok

    def test_groups_are_contiguous_slice_major(self):
        t = _topo(8, 4)
        assert t.local_groups() == [[0, 1, 2, 3], [4, 5, 6, 7]]
        assert t.cross_groups() == [[0, 4], [1, 5], [2, 6], [3, 7]]
        assert t.num_slices == 2 and t.is_multislice

    def test_roofline_shapes(self):
        t = _topo(8, 4)
        flat = t.roofline_busbw_gbps("allreduce", "flat")
        hier = t.roofline_busbw_gbps("allreduce", "hierarchical")
        tree = t.roofline_busbw_gbps("allreduce", "tree")
        # multislice flat ring is paced by DCN; the hierarchical ladder
        # recovers up to local_size x of it (capped by ICI); tree divides
        # by log2(n)
        assert flat == t.dcn_gbps
        assert hier == min(t.ici_gbps, t.dcn_gbps * 4)
        assert hier > flat
        # hierarchical ALLGATHER is DCN-paced (whole slice blocks cross;
        # the win is hop count, not bandwidth) — no local_size recovery
        assert t.roofline_busbw_gbps("allgather", "hierarchical") \
            == min(t.ici_gbps, t.dcn_gbps)
        # tree rounds each move the full payload: base fabric / log2(n)
        assert tree == pytest.approx(t.dcn_gbps / 3)
        single = _topo(8, 1)
        assert single.roofline_busbw_gbps("allreduce", "flat") \
            == single.ici_gbps
        assert single.roofline_busbw_gbps("allreduce", "tree") \
            == pytest.approx(single.ici_gbps / 3)

    def test_detect_env_override_wins(self, monkeypatch):
        monkeypatch.setenv(HOROVOD_TPU_LOCAL_SIZE, "4")
        t = detect_topology(size=8, local_size=2)
        assert t.local_size == 4 and t.source == "override"
        assert t.hierarchical_ok

    def test_detect_launcher_local_size(self, monkeypatch):
        monkeypatch.delenv(HOROVOD_TPU_LOCAL_SIZE, raising=False)
        t = detect_topology(size=8, local_size=2)
        assert t.local_size == 2 and t.source == "process"

    def test_detect_nondivisible_falls_back_to_divisor(self, monkeypatch,
                                                       caplog):
        monkeypatch.setenv(HOROVOD_TPU_LOCAL_SIZE, "4")
        with caplog.at_level(logging.WARNING, logger="horovod_tpu"):
            t = detect_topology(size=6)
        # largest divisor of 6 that is <= 4
        assert t.local_size == 3
        assert t.hierarchical_ok
        assert any("does not divide" in r.message for r in caplog.records)

    def test_detect_from_devices_flat_cpu_world(self, monkeypatch):
        monkeypatch.delenv(HOROVOD_TPU_LOCAL_SIZE, raising=False)
        # the 8 forced-CPU devices share one process: one island -> flat
        t = detect_topology(devices=jax.devices())
        assert t.size == len(jax.devices())
        assert t.local_size == 1 and t.source == "flat"
        assert t.platform == "cpu"

    def test_detect_slice_attrs(self, monkeypatch):
        monkeypatch.delenv(HOROVOD_TPU_LOCAL_SIZE, raising=False)

        class FakeDev:
            platform = "tpu"
            device_kind = "TPU v5 lite"

            def __init__(self, slice_index, process_index):
                self.slice_index = slice_index
                self.process_index = process_index

        devs = [FakeDev(i // 4, 0) for i in range(8)]
        t = detect_topology(devices=devs)
        assert t.local_size == 4 and t.source == "slice_attrs"
        assert t.platform == "tpu" and t.hierarchical_ok
        # v5e: 1,600 Gbit/s chip-to-chip (Google Cloud "TPU v5e")
        assert t.ici_gbps == 200.0

    def test_unknown_tpu_kind_is_an_error(self, monkeypatch):
        monkeypatch.delenv(HOROVOD_TPU_LOCAL_SIZE, raising=False)

        class FakeDev:
            platform = "tpu"
            device_kind = "TPU v9"
            process_index = 0

        with pytest.raises(ValueError, match="TPU v9"):
            detect_topology(devices=[FakeDev()])

    def test_world_given_by_size_takes_the_devices_own_rates(self,
                                                             monkeypatch):
        """No devices passed: the rates are those of the device jax has,
        not the CPU row by default."""
        monkeypatch.delenv(HOROVOD_TPU_LOCAL_SIZE, raising=False)

        class FakeDev:
            platform = "tpu"
            device_kind = "TPU v5 lite"

        monkeypatch.setattr(jax, "devices", lambda: [FakeDev()])
        t = detect_topology(size=4, local_size=2)
        assert (t.platform, t.ici_gbps, t.dcn_gbps) == ("tpu", 200.0, 12.5)
        FakeDev.device_kind = "TPU v9"
        with pytest.raises(ValueError, match="not supported"):
            detect_topology(size=4)


# ---------------------------------------------------------------------------
# selection rules
# ---------------------------------------------------------------------------

class TestChooseAlgorithm:
    def test_auto_small_reduction_is_tree(self):
        t = _topo(8, 4)
        assert C.choose_algorithm("allreduce", 64 * 1024, t) == "tree"

    def test_auto_large_reduction_is_hierarchical_on_multislice(self):
        t = _topo(8, 4)
        assert C.choose_algorithm("allreduce", 8 * 1024 ** 2, t) \
            == "hierarchical"
        assert C.choose_algorithm("allgather", 8 * 1024 ** 2, t) \
            == "hierarchical"

    def test_auto_large_reduction_is_flat_on_single_slice(self):
        t = _topo(8, 1)
        assert C.choose_algorithm("allreduce", 8 * 1024 ** 2, t) == "flat"

    def test_auto_never_trees_tiny_worlds_or_non_pow2(self):
        assert C.choose_algorithm("allreduce", 1024, _topo(2, 1)) == "flat"
        assert C.choose_algorithm("allreduce", 1024, _topo(6, 1)) == "flat"

    def test_reducescatter_is_always_flat(self):
        t = _topo(8, 4)
        assert C.choose_algorithm("reducescatter", 8 * 1024 ** 2, t) \
            == "flat"
        assert C.validate_algorithm("reducescatter", "hierarchical", 8, 4) \
            == "flat"

    def test_forced_invalid_demotes_never_raises(self):
        # tree on a non-power-of-2 world
        assert C.choose_algorithm("allreduce", 10, _topo(6, 1),
                                  force="tree") == "flat"
        # hierarchical with no exact factorization (the old assert site)
        assert C.choose_algorithm("allreduce", 10, _topo(6, 4),
                                  force="hierarchical") == "flat"
        # unknown name
        assert C.validate_algorithm("allreduce", "quantum", 8, 4) == "flat"

    def test_forced_valid_sticks_at_any_size(self):
        t = _topo(8, 4)
        assert C.choose_algorithm("allreduce", 8 * 1024 ** 2, t,
                                  force="tree") == "tree"
        assert C.choose_algorithm("allreduce", 16, t,
                                  force="hierarchical") == "hierarchical"
        assert C.choose_algorithm("allreduce", 16, t, force="flat") == "flat"

    def test_tree_threshold_knob_moves_the_boundary(self):
        t = _topo(8, 1)
        assert C.choose_algorithm("allreduce", 1024, t,
                                  tree_threshold_bytes=512) == "flat"
        assert C.choose_algorithm("allreduce", 1024, t,
                                  tree_threshold_bytes=2048) == "tree"

    def test_size_one_world_is_flat(self):
        assert C.choose_algorithm("allreduce", 1024, _topo(1, 1)) == "flat"

    def test_tree_groups_structure(self):
        rounds = C.tree_groups(8)
        assert rounds[0] == [[0, 1], [2, 3], [4, 5], [6, 7]]
        assert rounds[1] == [[0, 2], [1, 3], [4, 6], [5, 7]]
        assert rounds[2] == [[0, 4], [1, 5], [2, 6], [3, 7]]


# ---------------------------------------------------------------------------
# per-link wire attribution
# ---------------------------------------------------------------------------

class TestLinkSplit:
    def test_flat_and_tree_ride_link_flat(self):
        assert C.link_split("flat", 1000, 4) == {"flat": 1000}
        assert C.link_split("tree", 1000, 4) == {"flat": 1000}

    def test_hierarchical_splits_preserving_totals(self):
        split = C.link_split("hierarchical", 1000, 4)
        assert split["dcn"] == 250           # the 1/local_size cross leg
        assert split["ici"] == 750
        assert sum(split.values()) == 1000

    def test_hierarchical_allgather_attributes_payload_to_dcn(self):
        # the cross gather moves whole slice blocks: every byte crosses
        # DCN — no 1/local_size reduction to claim (that is allreduce's)
        assert C.link_split("hierarchical", 1000, 4, kind="allgather") \
            == {"dcn": 1000}

    def test_engine_wire_counter_carries_link_labels(self):
        """The acceptance surface: the metrics snapshot shows the ici/dcn
        wire split when a hierarchical bucket is accounted."""
        import horovod_tpu as hvd
        from horovod_tpu import metrics as hvd_metrics
        hvd.init()
        eng = hvd._engine()
        x = jnp.ones((256,), jnp.float32)  # 1024 bytes
        links = [C.link_split("hierarchical", x.nbytes, 4)]
        base = hvd_metrics.snapshot()
        eng._m_account("grouped_allreduce", [x], links)
        snap = hvd_metrics.snapshot()

        def val(s, **labels):
            want = tuple(sorted(labels.items()))
            for l, v in s["counters"].get("hvd_tpu_wire_bytes_total",
                                          {"values": []})["values"]:
                if tuple(sorted(l.items())) == want:
                    return v
            return 0.0

        labels = dict(kind="grouped_allreduce", dtype="float32")
        assert val(snap, link="ici", **labels) \
            - val(base, link="ici", **labels) == 768.0
        assert val(snap, link="dcn", **labels) \
            - val(base, link="dcn", **labels) == 256.0

    def test_engine_selection_counter_and_flat_link_on_size1(self):
        """A size-1 world moves every byte over link="flat" and never
        splits (selection inactive)."""
        import horovod_tpu as hvd
        from horovod_tpu import metrics as hvd_metrics
        hvd.init()
        base = hvd_metrics.snapshot()
        hvd.allreduce(np.ones(16, np.float32), name="topo.ar", op=hvd.Sum)
        snap = hvd_metrics.snapshot()
        rows = {tuple(sorted(l.items()))
                for l, _ in snap["counters"]["hvd_tpu_wire_bytes_total"]
                ["values"]}
        assert (("dtype", "float32"), ("kind", "allreduce"),
                ("link", "flat")) in rows


# ---------------------------------------------------------------------------
# trace + report link breakdown
# ---------------------------------------------------------------------------

class TestTraceLinkBreakdown:
    def test_link_bytes_rides_the_merged_trace_and_report(self):
        from horovod_tpu.trace import TraceRecorder, merge_segments
        import tools.trace_report as tr
        recs = {}
        for r in range(2):
            rec = TraceRecorder(rank=r)
            rec.record_enqueue("grad.0", "grouped_allreduce", 1000, 0,
                              link_bytes={"ici": 750, "dcn": 250})
            rec.record_done("grad.0")
            rec.record_enqueue("b.0", "broadcast", 64, 0)
            rec.record_done("b.0")
            recs[r] = rec.segment()
        events = merge_segments(recs)
        # schema lint stays green with the new args key
        assert tr.check_events(events) == []
        links = tr.wire_by_link(events)
        assert links["GROUPED_ALLREDUCE"] == {"ici": 1500, "dcn": 500}
        assert "BROADCAST" not in links  # no stamp -> no row
        rep = tr.analyze(events)
        assert rep["wire_by_link"]["GROUPED_ALLREDUCE"]["dcn"] == 500
        assert rep["skew_by_kind"]["GROUPED_ALLREDUCE"][
            "wire_bytes_by_link"] == {"ici": 1500, "dcn": 500}


# ---------------------------------------------------------------------------
# replay re-arms when selection knobs move
# ---------------------------------------------------------------------------

def test_replay_rearms_on_collective_algo_knob_move():
    """A live move of the algorithm knob (env force or the autotune
    categorical) must rebuild armed replay programs — eager warmup and
    the armed program always resolve the same schedule."""
    import horovod_tpu as hvd
    hvd.init()
    eng = hvd._engine()
    prev = (eng.config.step_replay_warmup, eng.config.collective_algo)
    eng.config.step_replay_warmup = 2
    eng.replay.invalidate_all("test isolation")
    tensors = [jnp.ones((8,), jnp.float32) for _ in range(3)]
    try:
        for i in range(3):
            eng.step_begin()
            hvd.grouped_allreduce(list(tensors), name=f"ra.{i}", op=hvd.Sum)
            eng.step_end()
        assert eng.replay.replayed_steps >= 1
        armed = [e["armed"] for e in eng.replay._seen.values()
                 if e.get("armed")]
        assert armed and armed[0].algo_sig[0] == "auto"
        eng.config.collective_algo = "flat"
        eng.step_begin()
        hvd.grouped_allreduce(list(tensors), name="ra.3", op=hvd.Sum)
        eng.step_end()
        rearmed = [e["armed"] for e in eng.replay._seen.values()
                   if e.get("armed")]
        assert rearmed and rearmed[0].algo_sig[0] == "flat"
    finally:
        (eng.config.step_replay_warmup, eng.config.collective_algo) = prev
        eng.replay.invalidate_all("test isolation")
