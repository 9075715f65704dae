"""Measured performance model + persistent fleet autotune (ISSUE 14).

Covers the three tentpole layers on the CPU test world:

- **calibration** — the α–β fit, the derived ring/tree and
  flat/hierarchical crossovers, the MeasuredTopology overlay, and the
  probe-disabled fallback to nominal tables;
- **joint search** — string-valued categoricals (the PR 10
  boolean-over-string encoding retired), the tree-threshold numeric dim,
  and calibrated-prediction seeding;
- **persistence** — tuning-record round trip keyed by (model signature,
  topology digest), stale-digest rejection, nearest-key priors for
  elastic N→M resizes, and the engine-level warm start that reaches the
  stored knob vector in <= 1 autotune cycle.

The real multi-rank probe determinism case lives in
tests/test_multiprocess.py (np=2, probing on); the in-process probe
smoke here is ``perf``-marked per the tier-1 convention.
"""

import json
import math
import os
import sys
import time

import numpy as np
import pytest

from horovod_tpu.autotune.calibration import (
    A2A_CLASS_FLAT, A2A_CLASS_HIER, HIER_THRESHOLD_MAX,
    TREE_THRESHOLD_MAX, TREE_THRESHOLD_MIN,
    derived_alltoall_threshold_bytes, derived_hier_threshold_bytes,
    derived_thresholds, derived_tree_threshold_bytes, fit_alpha_beta,
    fit_measured_topology)
from horovod_tpu.autotune.parameter_manager import ParameterManager
from horovod_tpu.autotune.persistence import (TuningStore, kv_key,
                                              record_filename)
from horovod_tpu.parallel.mesh import (MeasuredTopology, Topology,
                                       measured_topology)

MB = 1024 * 1024
TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


# ---------------------------------------------------------------------------
# calibration: α–β fit + derived crossovers
# ---------------------------------------------------------------------------

class TestCalibrationFit:
    def test_fit_recovers_known_model(self):
        alpha, beta = 2e-4, 5e9
        sizes = [64e3, 512e3, 4e6]
        times = [alpha + s / beta for s in sizes]
        a, b = fit_alpha_beta(sizes, times)
        assert a == pytest.approx(alpha, rel=1e-6)
        assert b == pytest.approx(beta, rel=1e-6)

    def test_fit_degenerate_slope_degrades_to_latency_only(self):
        # pure noise where bigger messages measured FASTER: the bandwidth
        # term must drop out (inf), never go negative
        a, b = fit_alpha_beta([1e5, 1e6], [2e-3, 1e-3])
        assert b == float("inf")
        assert a >= 0.0

    def test_tree_threshold_grows_with_latency(self):
        lo = derived_tree_threshold_bytes(1e-5, 1e9, 8)
        hi = derived_tree_threshold_bytes(1e-3, 1e9, 8)
        assert hi > lo
        assert TREE_THRESHOLD_MIN <= lo <= hi <= TREE_THRESHOLD_MAX

    def test_tree_threshold_floor_below_4_ranks(self):
        # n=2: tree and flat are the same exchange, auto never offers it
        assert derived_tree_threshold_bytes(1e-3, 1e9, 2) == \
            TREE_THRESHOLD_MIN

    def test_hier_threshold_zero_when_ladder_never_slower(self):
        assert derived_hier_threshold_bytes((2e-4, 1e9), (1e-4, 4e9)) == 0

    def test_hier_threshold_caps_when_no_bandwidth_win(self):
        # ladder costs extra launches and measured NO bandwidth gain:
        # selection should keep flat for every realistic bucket
        assert derived_hier_threshold_bytes((1e-4, 1e9), (4e-4, 1e9)) == \
            HIER_THRESHOLD_MAX

    def test_hier_threshold_crossover_math(self):
        flat, hier = (1e-4, 1e9), (3e-4, 4e9)
        s = derived_hier_threshold_bytes(flat, hier)
        # at the crossover both cost models agree
        t_flat = flat[0] + s / flat[1]
        t_hier = hier[0] + s / hier[1]
        assert t_flat == pytest.approx(t_hier, rel=1e-3)


class TestMeasuredTopology:
    def _base(self, size=8, local=4):
        return Topology(size=size, local_size=local, platform="cpu")

    def test_overlay_preserves_shape_and_digest(self):
        base = self._base()
        m = measured_topology(base, 6.5, 0.8, 15.0,
                              {"flat": (1e-4, 1e9),
                               "hierarchical": (3e-4, 4e9)})
        assert isinstance(m, MeasuredTopology)
        assert (m.size, m.local_size, m.num_slices) == (8, 4, 2)
        assert m.hierarchical_ok
        assert m.calibrated and not base.calibrated
        # calibration must never fork the persistence key
        assert m.digest() == base.digest()
        assert m.ici_gbps == 6.5 and m.dcn_gbps == 0.8
        assert m.nominal_ici_gbps == base.ici_gbps
        assert m.fitted("flat") == (1e-4, 1e9)
        assert m.fitted("tree") is None
        d = m.describe()
        assert d["calibrated"] and "link_model" in d

    def test_digest_tracks_shape_not_measurement(self):
        a = self._base(8, 4)
        assert a.digest() != self._base(8, 2).digest()
        assert a.digest() != self._base(4, 4).digest()
        assert a.digest() != Topology(size=8, local_size=4,
                                      platform="tpu").digest()
        # bandwidths and detection source do not key records
        b = Topology(size=8, local_size=4, platform="cpu",
                     source="override", ici_gbps=99.0, dcn_gbps=9.0)
        assert a.digest() == b.digest()

    def test_fit_measured_topology_flat_world(self):
        base = Topology(size=4, local_size=1, platform="cpu")
        beta = 2e9
        agreed = {"flat": [1e-4 + s / beta
                           for s in (64e3, 512e3, 4e6)]}
        m = fit_measured_topology(base, agreed, bands=(64e3, 512e3, 4e6))
        # flat world: the ring measures ICI; busbw convention 2(n-1)/n
        assert m.ici_gbps == pytest.approx(
            2 * 3 / 4 * beta / 1e9, rel=1e-3)
        assert m.launch_latency_us > 0
        tree_thr, hier_thr = derived_thresholds(m)
        assert TREE_THRESHOLD_MIN <= tree_thr <= TREE_THRESHOLD_MAX
        assert hier_thr == 0     # ladder unprobed -> nominal behavior

    def test_fit_measured_topology_multislice(self):
        base = Topology(size=8, local_size=4, platform="cpu")
        agreed = {
            "flat": [1e-4 + s / 1e9 for s in (64e3, 512e3, 4e6)],
            "hierarchical": [3e-4 + s / 3e9 for s in (64e3, 512e3, 4e6)],
        }
        m = fit_measured_topology(base, agreed, bands=(64e3, 512e3, 4e6))
        assert m.is_multislice and m.calibrated
        # the flat ring is DCN-paced on multislice fabrics
        assert m.dcn_gbps == pytest.approx(2 * 7 / 8 * 1e9 / 1e9,
                                           rel=1e-3)
        tree_thr, hier_thr = derived_thresholds(m)
        # ladder costs extra α but wins bandwidth: finite crossover
        assert 0 < hier_thr < HIER_THRESHOLD_MAX

    def test_choose_algorithm_respects_hier_threshold(self):
        from horovod_tpu.ops import collectives as C
        topo = Topology(size=6, local_size=3, platform="cpu")
        below = C.choose_algorithm("allreduce", 1 * MB, topo,
                                   tree_threshold_bytes=0,
                                   hier_threshold_bytes=2 * MB)
        above = C.choose_algorithm("allreduce", 4 * MB, topo,
                                   tree_threshold_bytes=0,
                                   hier_threshold_bytes=2 * MB)
        assert below == C.ALGO_FLAT
        assert above == C.ALGO_HIERARCHICAL
        # default 0 keeps the nominal always-hierarchical behavior
        assert C.choose_algorithm("allreduce", 1 * MB, topo,
                                  tree_threshold_bytes=0) == \
            C.ALGO_HIERARCHICAL


class TestAlltoallCalibrationBand:
    """ISSUE 17: the alltoall band fits its own α–β rows and derives a
    measured flat-vs-hierarchical dispatch crossover."""

    BANDS = (64e3, 512e3, 4e6)

    def test_a2a_rows_fit_and_derive_finite_crossover(self):
        base = Topology(size=8, local_size=4, platform="cpu")
        agreed = {
            "flat": [1e-4 + s / 1e9 for s in self.BANDS],
            "hierarchical": [3e-4 + s / 3e9 for s in self.BANDS],
            A2A_CLASS_FLAT: [1e-4 + s / 2e9 for s in self.BANDS],
            A2A_CLASS_HIER: [4e-4 + s / 8e9 for s in self.BANDS],
        }
        m = fit_measured_topology(base, agreed, bands=self.BANDS)
        # the extra classes ride the same fit: rows present and sane
        a_f, b_f = m.fitted(A2A_CLASS_FLAT)
        a_h, b_h = m.fitted(A2A_CLASS_HIER)
        assert a_f == pytest.approx(1e-4, rel=1e-3)
        assert b_f == pytest.approx(2e9, rel=1e-3)
        assert a_h == pytest.approx(4e-4, rel=1e-3)
        assert b_h == pytest.approx(8e9, rel=1e-3)
        thr = derived_alltoall_threshold_bytes(m)
        assert thr is not None and 0 < thr < HIER_THRESHOLD_MAX
        # crossover: flat and hier cost curves meet exactly there
        assert a_f + thr / b_f == pytest.approx(a_h + thr / b_h,
                                                rel=1e-3)
        # the alltoall band never perturbs the allreduce crossovers
        tree_thr, hier_thr = derived_thresholds(m)
        assert 0 < hier_thr < HIER_THRESHOLD_MAX

    def test_unprobed_band_returns_none(self):
        base = Topology(size=8, local_size=4, platform="cpu")
        agreed = {
            "flat": [1e-4 + s / 1e9 for s in self.BANDS],
            A2A_CLASS_FLAT: [1e-4 + s / 2e9 for s in self.BANDS],
        }
        m = fit_measured_topology(base, agreed, bands=self.BANDS)
        # hierarchical leg unprobed (single slice, or probe vetoed):
        # no measured crossover — the nominal default stays in force
        assert derived_alltoall_threshold_bytes(m) is None
        assert derived_alltoall_threshold_bytes(
            fit_measured_topology(base,
                                  {"flat": agreed["flat"]},
                                  bands=self.BANDS)) is None

    def test_busbw_convention_alltoall(self):
        from horovod_tpu.autotune.calibration import _busbw_factor
        assert _busbw_factor("alltoall", 8) == pytest.approx(7 / 8)
        assert _busbw_factor("allgather", 8) == pytest.approx(7 / 8)
        assert _busbw_factor("allreduce", 8) == pytest.approx(2 * 7 / 8)


# ---------------------------------------------------------------------------
# joint search: string categoricals, tree-threshold dim, seeding
# ---------------------------------------------------------------------------

def _pm(**kw):
    kw.setdefault("warmup_samples", 0)
    kw.setdefault("steps_per_sample", 1)
    kw.setdefault("max_samples", 4)
    return ParameterManager(**kw)


def _drive_to_convergence(pm, nbytes=4 * MB, limit=200):
    for _ in range(limit):
        if not pm.active:
            return
        if pm._step_start is not None:
            pm._step_start -= 0.01
        pm.step_mark(nbytes)
    raise AssertionError("tuner did not converge")


class TestStringCategoricals:
    CHOICES = ("off", "interleave", "staged")

    def test_string_choices_decode_evenly(self):
        pm = _pm(categorical=[("overlap_pipeline", self.CHOICES)],
                 categorical_initial={"overlap_pipeline": "staged"})
        assert pm.tunes("overlap_pipeline")
        assert pm.categorical_choices("overlap_pipeline") == self.CHOICES
        assert pm.categorical_value("overlap_pipeline") == "staged"
        i = pm._cat_offset
        for u, want in ((0.0, "off"), (0.4, "interleave"),
                        (0.99, "staged"), (1.0, "staged")):
            pm._current[i] = u
            assert pm.categorical_value("overlap_pipeline") == want

    def test_boolean_backcompat(self):
        pm = _pm(categorical=["step_replay"],
                 categorical_initial={"step_replay": False})
        assert pm.categorical_value("step_replay") is False
        pm._current[pm._cat_offset] = 0.9
        assert pm.categorical_value("step_replay") is True

    def test_unknown_initial_lands_on_first_choice(self):
        pm = _pm(categorical=[("collective_algo", ("auto", "flat"))],
                 categorical_initial={"collective_algo": "bogus"})
        assert pm.categorical_value("collective_algo") == "auto"

    def test_encode_round_trips_choices(self):
        pm = _pm(categorical=[("collective_algo",
                               ("auto", "flat", "tree", "hierarchical"))],
                 tune_tree_threshold=True)
        for choice in ("auto", "flat", "tree", "hierarchical"):
            pm._current = pm.encode(
                fusion_threshold_bytes=8 * MB,
                tree_threshold_bytes=512 * 1024,
                categorical_values={"collective_algo": choice})
            assert pm.categorical_value("collective_algo") == choice
            assert pm.fusion_threshold_bytes == 8 * MB
            assert pm.tree_threshold_bytes == 512 * 1024

    def test_fewer_than_two_choices_rejected(self):
        with pytest.raises(ValueError):
            _pm(categorical=[("bad", ("only",))])

    def test_log_columns_carry_string_values(self, tmp_path):
        log = str(tmp_path / "t.csv")
        pm = _pm(categorical=[("collective_algo", ("auto", "flat")),
                              "step_replay"],
                 categorical_initial={"collective_algo": "auto",
                                      "step_replay": True},
                 log_path=log, max_samples=3)
        _drive_to_convergence(pm)
        lines = open(log).read().strip().splitlines()
        assert lines[0].endswith(
            "collective_algo,step_replay,score_bytes_per_sec")
        # value columns: a string for the choice knob, 0/1 for the bool
        row = lines[1].split(",")
        assert row[-3] in ("auto", "flat")
        assert row[-2] in ("0", "1")

    def test_knob_values_snapshot(self):
        pm = _pm(categorical=[("compression", ("none", "int8"))],
                 categorical_initial={"compression": "int8"},
                 tune_tree_threshold=True,
                 initial_tree_threshold=128 * 1024)
        vals = pm.knob_values()
        assert vals["compression"] == "int8"
        assert vals["tree_threshold_bytes"] == 128 * 1024
        assert "fusion_threshold_bytes" in vals


class TestTreeThresholdDimension:
    def test_dim_present_and_bounded(self):
        pm = _pm(tune_tree_threshold=True, initial_tree_threshold=1)
        lo, hi = ParameterManager.TREE_THRESHOLD_BOUNDS
        assert pm.tunes_tree_threshold
        assert pm.tree_threshold_bytes == lo       # clamped up
        assert len(pm._bounds) == 3
        assert pm.space()["numeric"][-1] == "tree_threshold_bytes"

    def test_absent_by_default(self):
        pm = _pm()
        assert not pm.tunes_tree_threshold
        with pytest.raises(ValueError):
            pm.tree_threshold_bytes


class TestMixedSpaceOptimizer:
    def test_suggestions_land_on_slot_centers(self):
        from horovod_tpu.autotune.bayesian_optimization import \
            BayesianOptimizer
        opt = BayesianOptimizer([(0.0, 10.0), (0.0, 1.0), (0.0, 1.0)],
                                seed=3,
                                categorical_slots={1: 2, 2: 3})
        centers2 = {(i + 0.5) / 2 for i in range(2)}
        centers3 = {(i + 0.5) / 3 for i in range(3)}
        for i in range(8):
            x = opt.suggest()
            assert float(x[1]) in centers2, x
            assert float(x[2]) in centers3, x
            opt.register(x, float(-(x[0] - 7.0) ** 2))
        # numeric dim still continuous (not snapped)
        assert 0.0 <= x[0] <= 10.0

    def test_pm_wires_slots_for_every_categorical(self):
        pm = _pm(categorical=["step_replay",
                              ("collective_algo", ("auto", "flat",
                                                   "tree"))],
                 tune_tree_threshold=True)
        assert pm._opt.categorical_slots == {3: 2, 4: 3}


class TestSeedSuggestions:
    def test_seeds_explored_before_random(self):
        pm = _pm(max_samples=10)
        seed1 = pm.encode(fusion_threshold_bytes=2 * MB)
        seed2 = pm.encode(fusion_threshold_bytes=128 * MB)
        pm._seed_suggestions.extend([seed1, seed2])
        # first sample moves to seed1, second to seed2
        pm._step_start = time.perf_counter() - 0.01
        pm.step_mark(4 * MB)
        assert pm.fusion_threshold_bytes == 2 * MB
        pm._step_start -= 0.01
        pm.step_mark(4 * MB)
        assert pm.fusion_threshold_bytes == 128 * MB


# ---------------------------------------------------------------------------
# persistence: record round trip, stale rejection, nearest key
# ---------------------------------------------------------------------------

def _converged_store(tmp_path, topo, model_sig="m" * 64, **pm_kw):
    pm = _pm(categorical=[("collective_algo", ("auto", "flat"))],
             tune_tree_threshold=True, **pm_kw)
    store = TuningStore(str(tmp_path), topo, rank=0)
    pm.attach_persistence(store)
    pm._model_sig = model_sig
    _drive_to_convergence(pm)
    return pm, store


class TestTuningStore:
    TOPO = Topology(size=2, local_size=1, platform="cpu")

    def test_round_trip_exact(self, tmp_path):
        pm, store = _converged_store(tmp_path, self.TOPO)
        path = tmp_path / record_filename("m" * 64, self.TOPO.digest())
        assert path.exists()
        rec = json.loads(path.read_text())
        assert rec["topo_digest"] == self.TOPO.digest()
        assert rec["knobs"] == pm.knob_values()
        got = store.lookup("m" * 64, pm.space())
        assert got is not None and got[1] is True
        assert got[0]["best_x"] == rec["best_x"]

    def test_stale_topo_digest_rejected(self, tmp_path):
        pm, _ = _converged_store(tmp_path, self.TOPO)
        path = tmp_path / record_filename("m" * 64, self.TOPO.digest())
        rec = json.loads(path.read_text())
        rec["topo_digest"] = "0" * 64     # stale: some other fabric
        path.write_text(json.dumps(rec))
        store = TuningStore(str(tmp_path), self.TOPO, rank=0)
        assert store.lookup("m" * 64, pm.space()) is None

    def test_model_sig_mismatch_rejected(self, tmp_path):
        pm, store = _converged_store(tmp_path, self.TOPO)
        # same leading filename chars, different full digest inside
        other = "m" * 16 + "x" * 48
        assert store.lookup(other, pm.space()) is None

    def test_changed_space_rejected(self, tmp_path):
        pm, store = _converged_store(tmp_path, self.TOPO)
        space = pm.space()
        space["categorical"].append(["new_knob", [False, True]])
        assert store.lookup("m" * 64, space) is None

    def test_unknown_version_rejected(self, tmp_path):
        pm, store = _converged_store(tmp_path, self.TOPO)
        path = tmp_path / record_filename("m" * 64, self.TOPO.digest())
        rec = json.loads(path.read_text())
        rec["version"] = 999
        path.write_text(json.dumps(rec))
        assert store.lookup("m" * 64, pm.space()) is None

    def test_nearest_key_prefers_closest_world(self, tmp_path):
        space = None
        for size, local in ((2, 1), (8, 2)):
            topo = Topology(size=size, local_size=local, platform="cpu")
            pm, _ = _converged_store(tmp_path, topo)
            space = pm.space()
        # live world np=4: nearest stored world by log2 distance is 2
        # (|log2(4/2)|=1 == |log2(8/4)|... both 1 -> local_size tiebreak
        # favors neither; larger world wins ties) — use np=3 so the
        # distance is unambiguous: |log2(3/2)|=0.58 < |log2(8/3)|=1.4
        live = Topology(size=3, local_size=1, platform="cpu")
        store = TuningStore(str(tmp_path), live, rank=0)
        got = store.lookup("m" * 64, space)
        assert got is not None
        rec, exact = got
        assert exact is False
        assert rec["topology"]["size"] == 2

    def test_nearest_requires_same_platform(self, tmp_path):
        pm, _ = _converged_store(tmp_path, self.TOPO)
        live = Topology(size=4, local_size=1, platform="tpu")
        store = TuningStore(str(tmp_path), live, rank=0)
        assert store.lookup("m" * 64, pm.space()) is None

    def test_corrupt_file_is_a_miss(self, tmp_path):
        pm, store = _converged_store(tmp_path, self.TOPO)
        path = tmp_path / record_filename("m" * 64, self.TOPO.digest())
        path.write_text("{not json")
        assert store.lookup("m" * 64, pm.space()) is None

    def test_non_root_never_writes(self, tmp_path):
        store = TuningStore(str(tmp_path / "sub"), self.TOPO, rank=1)
        assert store.save({"model_sig": "m" * 64}) is None
        assert not (tmp_path / "sub").exists()

    def test_kv_round_trip(self, tmp_path):
        from horovod_tpu.runner.http_server import KVStoreServer
        server = KVStoreServer()
        port = server.start()
        try:
            kv = ("127.0.0.1", port)
            topo = self.TOPO
            pm = _pm(tune_tree_threshold=True)
            # KV-only store (no directory): save publishes, lookup reads
            store = TuningStore(None, topo, rank=0, kv=kv, kv_timeout=5.0)
            pm.attach_persistence(store)
            pm._model_sig = "k" * 64
            _drive_to_convergence(pm)
            fresh = TuningStore(None, topo, rank=0, kv=kv, kv_timeout=5.0)
            got = fresh.lookup("k" * 64, pm.space())
            assert got is not None and got[1] is True
            assert got[0]["knobs"] == pm.knob_values()
        finally:
            server.stop()


class TestWarmStart:
    TOPO = Topology(size=2, local_size=1, platform="cpu")

    def _space_kw(self):
        return dict(categorical=[("collective_algo", ("auto", "flat"))],
                    tune_tree_threshold=True)

    def test_exact_warm_start_converges_in_one_cycle(self, tmp_path):
        pm, _ = _converged_store(tmp_path, self.TOPO)
        stored_samples = pm.n_samples_taken
        fresh = _pm(warmup_samples=3, **self._space_kw())
        fresh.attach_persistence(TuningStore(str(tmp_path), self.TOPO,
                                             rank=0))
        fresh.maybe_warm_start("m" * 64)
        assert fresh.warm_start_kind == "exact"
        # the stored winner is adopted immediately...
        assert fresh.knob_values() == pm.knob_values()
        assert fresh.active
        # ...and ONE sample confirms convergence (warmup waived): the
        # acceptance bound, asserted by the samples counter
        fresh._step_start = time.perf_counter() - 0.01
        fresh.step_mark(4 * MB)
        fresh._step_start -= 0.01
        fresh.step_mark(4 * MB)
        assert not fresh.active
        assert fresh.n_samples_taken - stored_samples <= 1
        assert fresh.knob_values() == pm.knob_values()

    def test_nearest_key_seeds_but_retunes(self, tmp_path):
        pm, _ = _converged_store(tmp_path, self.TOPO)
        live = Topology(size=4, local_size=1, platform="cpu")
        fresh = _pm(**self._space_kw())
        fresh.attach_persistence(TuningStore(str(tmp_path), live, rank=0))
        fresh.maybe_warm_start("m" * 64)
        assert fresh.warm_start_kind == "nearest"
        assert fresh.active
        assert fresh.n_samples_taken == 0    # no foreign scores replayed
        assert fresh.knob_values() == pm.knob_values()

    def test_dimension_mismatch_ignored(self, tmp_path):
        pm, _ = _converged_store(tmp_path, self.TOPO)
        path = tmp_path / record_filename("m" * 64, self.TOPO.digest())
        rec = json.loads(path.read_text())
        rec["best_x"] = rec["best_x"][:-1]    # space says 5 dims, x has 4
        path.write_text(json.dumps(rec))
        fresh = _pm(**self._space_kw())
        fresh.attach_persistence(TuningStore(str(tmp_path), self.TOPO,
                                             rank=0))
        fresh.maybe_warm_start("m" * 64)
        assert fresh.warm_start_kind == "none"

    def test_miss_leaves_cold_start(self, tmp_path):
        fresh = _pm(**self._space_kw())
        fresh.attach_persistence(TuningStore(str(tmp_path), self.TOPO,
                                             rank=0))
        fresh.maybe_warm_start("q" * 64)
        assert fresh.warm_start_kind == "none"
        assert fresh.active


# ---------------------------------------------------------------------------
# engine integration: warm-start round trip, fallback, model signature
# ---------------------------------------------------------------------------

def _autotune_env(tmp_path, extra=None):
    env = {"HOROVOD_AUTOTUNE": "1",
           "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "0",
           "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "1",
           "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": "3",
           "HOROVOD_TPU_TUNE_PERSIST_DIR": str(tmp_path)}
    env.update(extra or {})
    return env


class TestEngineIntegration:
    def _drive(self, hvd, shapes=((64, 64),), steps=12, tag="wf"):
        from horovod_tpu.core.state import global_state
        pm = global_state().parameter_manager
        grads = [np.ones(s, np.float32) for s in shapes]
        for i in range(steps):
            hs = hvd.grouped_allreduce_async(grads, name=f"{tag}{i}")
            for h in hs:
                hvd.synchronize(h)
            if pm is not None and not pm.active:
                break
        return pm

    def _with_env(self, env, fn):
        import horovod_tpu as hvd
        old = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        try:
            hvd.shutdown()
            hvd.init()
            return fn(hvd)
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            hvd.shutdown()
            hvd.init()

    def test_warm_start_round_trip_through_engine(self, tmp_path):
        """tune → persist → fresh engine loads by digest → skips
        exploration (the acceptance criterion end to end)."""
        env = _autotune_env(tmp_path)

        def first_run(hvd):
            from horovod_tpu.core.state import global_state
            pm = self._drive(hvd)
            assert not pm.active, "tuner should have converged"
            eng = global_state().engine
            assert eng.model_signature() is not None
            return (pm.n_samples_taken, pm.knob_values(),
                    eng.model_signature())

        stored_samples, knobs, sig = self._with_env(env, first_run)
        files = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
        assert len(files) == 1

        def second_run(hvd):
            from horovod_tpu.core.state import global_state
            pm = self._drive(hvd, steps=4)
            assert global_state().engine.model_signature() == sig
            return (pm.warm_start_kind, pm.n_samples_taken, pm.active,
                    pm.knob_values())

        kind, samples, active, knobs2 = self._with_env(env, second_run)
        assert kind == "exact"
        assert not active
        # <= 1 new sample past the persisted record: exploration skipped
        assert samples - stored_samples <= 1
        assert knobs2 == knobs

    def test_different_model_is_a_miss(self, tmp_path):
        env = _autotune_env(tmp_path)
        self._with_env(env, lambda hvd: self._drive(hvd))

        def second_run(hvd):
            pm = self._drive(hvd, shapes=((16, 16), (32,)), steps=3,
                             tag="other")
            return pm.warm_start_kind

        assert self._with_env(env, second_run) == "none"

    def test_probe_disabled_falls_back_to_nominal(self):
        """HOROVOD_TPU_CALIBRATE unset: the engine keeps the nominal
        tables and selection still works — the documented fallback."""
        import horovod_tpu as hvd
        from horovod_tpu.core.state import global_state
        hvd.init()
        eng = global_state().engine
        assert eng.topology.calibrated is False
        assert eng.config.hier_threshold_bytes == 0
        out = np.asarray(hvd.allreduce(np.ones(4, np.float32),
                                       name="nom.a", op=hvd.Sum))
        assert out[0] == hvd.size()

    def test_calibrate_on_single_rank_world_is_noop(self):
        """size<=1: the probe is skipped ("world too small"), nominal
        tables stay, init succeeds."""
        import horovod_tpu as hvd
        env = {"HOROVOD_TPU_CALIBRATE": "1"}

        def check(hvd):
            from horovod_tpu.core.state import global_state
            eng = global_state().engine
            assert eng.topology.calibrated is False
            return True

        assert self._with_env(env, check)

    def test_model_signature_is_shape_stable(self):
        import horovod_tpu as hvd
        from horovod_tpu.core.state import global_state
        hvd.shutdown()
        hvd.init()
        try:
            eng = global_state().engine
            assert eng.model_signature() is None
            grads = [np.ones((8, 8), np.float32), np.ones(3, np.float32)]
            for h in hvd.grouped_allreduce_async(grads, name="sig0"):
                hvd.synchronize(h)
            sig = eng.model_signature()
            assert sig is not None
            # later steps with the same layout never move the signature
            for h in hvd.grouped_allreduce_async(grads, name="sig1"):
                hvd.synchronize(h)
            assert eng.model_signature() == sig
        finally:
            hvd.shutdown()
            hvd.init()


# ---------------------------------------------------------------------------
# in-process probe smoke (perf-marked: builds + runs the real probe
# programs on the 8-device CPU world, no timing assertions)
# ---------------------------------------------------------------------------

class _ProbeWorld:
    """Just enough engine surface for probe_link_times/agree_times: an
    8-device single-process world where 'to_global' replicates the
    payload across the device mesh (each device plays one rank)."""

    def __init__(self, local_size=1):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from horovod_tpu.parallel.mesh import detect_topology
        devs = jax.devices()
        self._n = len(devs)
        self._mesh = Mesh(np.array(devs), ("world",))
        self._sh = NamedSharding(self._mesh, P("world"))
        self._jnp = jnp
        self.topology = detect_topology(size=self._n,
                                        local_size=local_size,
                                        devices=devs)
        self.backend = self

    @property
    def group_mesh(self):
        return self._mesh

    def size(self):
        return self._n

    def to_global(self, x):
        import jax
        return jax.device_put(
            self._jnp.broadcast_to(x, (self._n,) + tuple(x.shape)),
            self._sh)

    def _hierarchical_ok(self):
        return self.topology.hierarchical_ok

    def _exchange_sizes(self, vec):
        return np.asarray(vec)[None]     # one "rank"


@pytest.mark.perf
def test_probe_fits_real_programs():
    """The real probe: build + run the per-class probe programs on the
    8-device world (local_size=4 so flat, tree AND hierarchical classes
    all execute), fit, derive — structure only, no timing assertions."""
    from horovod_tpu.autotune.calibration import (agree_times,
                                                  fit_measured_topology,
                                                  probe_link_times)
    world = _ProbeWorld(local_size=4)
    assert world.topology.hierarchical_ok
    bands = (16 * 1024, 64 * 1024, 256 * 1024)
    local = probe_link_times(world, bands=bands)
    assert set(local) == {"flat", "tree", "hierarchical",
                          A2A_CLASS_FLAT, A2A_CLASS_HIER}
    assert all(len(v) == len(bands) and all(t > 0 for t in v)
               for v in local.values())
    agreed = agree_times(world, local)
    # one participant: the cross-rank median is the local reading,
    # modulo the int-nanosecond exchange grid
    for k in local:
        assert np.allclose(agreed[k], local[k], atol=1e-6)
    m = fit_measured_topology(world.topology, agreed, bands=bands)
    assert m.calibrated
    assert m.ici_gbps > 0 and m.dcn_gbps > 0
    tree_thr, hier_thr = derived_thresholds(m)
    assert TREE_THRESHOLD_MIN <= tree_thr <= TREE_THRESHOLD_MAX
    assert 0 <= hier_thr <= HIER_THRESHOLD_MAX
    # both alltoall legs probed on this world: a measured crossover
    a2a_thr = derived_alltoall_threshold_bytes(m)
    assert a2a_thr is not None
    assert 0 <= a2a_thr <= HIER_THRESHOLD_MAX


# ---------------------------------------------------------------------------
# gap attribution (ISSUE 14 satellite): live 2-rank trace -> four sinks
# ---------------------------------------------------------------------------

class TestGapAttribution:
    def _live_two_rank_events(self, late=0.02):
        """A genuine 2-rank merged trace built from real TraceRecorders
        (the test_trace pattern): 5 steps, one correlated collective per
        step, rank 1 arriving ``late`` seconds behind rank 0."""
        import contextlib
        import time as _t
        from unittest import mock
        from horovod_tpu.trace import TraceRecorder, merge_segments

        @contextlib.contextmanager
        def _frozen(at):
            real = _t.monotonic
            with mock.patch.object(_t, "monotonic", lambda: at):
                yield
            assert _t.monotonic is real

        segs = {}
        base = _t.monotonic()
        for r in (0, 1):
            rec = TraceRecorder(rank=r)
            shift = late if r == 1 else 0.0
            for i in range(5):
                with _frozen(base + i * 0.1 + shift):
                    rec.record_step(begin=True)
                    rec.record_enqueue("g0", "allreduce", 64, 0)
                with _frozen(base + i * 0.1 + shift + 0.004):
                    rec.record_dispatch("g0", "XLA_DISPATCH", 0.004)
                with _frozen(base + i * 0.1 + max(shift, late) + 0.03):
                    rec.record_done("g0")
                with _frozen(base + i * 0.1 + shift + 0.08):
                    rec.record_step(begin=False)
            rec.add_beacon(base, 777.0 + base, 0.0)
            segs[r] = rec.segment()
        return merge_segments(segs)

    def test_four_sinks_partition_step_time(self):
        sys.path.insert(0, TOOLS)
        try:
            import trace_report
            events = self._live_two_rank_events()
            gaps = trace_report.gap_attribution(events)
        finally:
            sys.path.remove(TOOLS)
        assert set(gaps) == {0, 1}
        for pid, row in gaps.items():
            assert row["steps"] == 5
            total = (row["compute_us"] + row["dispatch_us"]
                     + row["wire_us"] + row["straggler_wait_us"])
            assert total == pytest.approx(row["total_us"], rel=1e-6)
            assert row["dispatch_us"] > 0
            assert set(row["pct"]) == {"compute", "dispatch", "wire",
                                       "straggler_wait"}
        # rank 0 arrived first every step: the straggler wait is ITS
        # time lost to rank 1 (5 steps x ~20 ms); rank 1 never waits
        assert gaps[0]["straggler_wait_us"] == pytest.approx(
            5 * 0.02e6, rel=0.2)
        assert gaps[1]["straggler_wait_us"] == 0.0

    def test_report_renders_gap_section(self, tmp_path, capsys):
        from horovod_tpu.trace import render_cluster_trace
        sys.path.insert(0, TOOLS)
        try:
            import trace_report
            events = self._live_two_rank_events()
            path = tmp_path / "merged.json"
            path.write_text(json.dumps({"traceEvents": events}))
            rc = trace_report.main([str(path)])
            out = capsys.readouterr().out
        finally:
            sys.path.remove(TOOLS)
        assert rc == 0
        assert "gap attribution" in out
        assert "compute=" in out and "straggler=" in out

    def test_analyze_includes_gap_attribution(self):
        sys.path.insert(0, TOOLS)
        try:
            import trace_report
            rep = trace_report.analyze(self._live_two_rank_events())
        finally:
            sys.path.remove(TOOLS)
        assert "gap_attribution" in rep
        assert rep["gap_attribution"][0]["pct"]["compute"] >= 0


# ---------------------------------------------------------------------------
# knob provenance (ISSUE 14 satellite)
# ---------------------------------------------------------------------------

class TestKnobProvenance:
    def test_config_records_env_vs_default(self, monkeypatch):
        from horovod_tpu.common.env import Config
        monkeypatch.setenv("HOROVOD_TPU_TREE_THRESHOLD_BYTES", "8192")
        cfg = Config.from_env()
        assert cfg.provenance["tree_threshold_bytes"] == "env-forced"
        assert cfg.provenance["fusion_threshold_bytes"] == "default"

    def test_calibration_sets_provenance(self, tmp_path):
        """engine._apply_calibration flips tree_threshold provenance to
        'calibrated' (unit-level: drive the config mutation the way the
        engine does, via derived thresholds on a measured overlay)."""
        from horovod_tpu.common.env import Config
        cfg = Config.from_env()
        assert cfg.provenance["tree_threshold_bytes"] == "default"
        base = Topology(size=8, local_size=4, platform="cpu")
        m = measured_topology(base, 6.0, 0.8, 10.0,
                              {"flat": (1e-4, 1e9),
                               "hierarchical": (3e-4, 4e9)})
        tree_thr, hier_thr = derived_thresholds(m)
        if cfg.provenance.get("tree_threshold_bytes") != "env-forced":
            cfg.tree_threshold_bytes = tree_thr
            cfg.provenance["tree_threshold_bytes"] = "calibrated"
        cfg.hier_threshold_bytes = hier_thr
        assert cfg.provenance["tree_threshold_bytes"] == "calibrated"
        assert cfg.tree_threshold_bytes == tree_thr
