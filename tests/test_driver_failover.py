"""Survivable elastic driver (ISSUE 19): journal replay, standby
election restriction, promotion resume, and the ``get_slot_state`` /
``wait_for_world`` resize-interleaving regressions.

All in-process: a replicated ElasticRendezvousServer pair (PR 12 fabric)
with FixedHosts discovery and mock workers — no subprocesses, no JAX.
The subprocess SIGKILL chaos case lives in tests/test_chaos.py.
"""

import time

import pytest

from horovod_tpu import faults
from horovod_tpu.elastic.discovery import FixedHosts
from horovod_tpu.elastic.driver import ElasticDriver
from horovod_tpu.elastic.failover import (DriverJournal, DriverStandby,
                                          SCOPE_DRIVER)
from horovod_tpu.elastic.registration import READY
from horovod_tpu.elastic.rendezvous import ElasticRendezvousServer
from horovod_tpu.metrics import registry
from horovod_tpu.runner.replication import ReplicationConfig

from test_elastic_driver import MockWorkers, wait_until


@pytest.fixture(autouse=True)
def _disarm():
    faults.disarm()
    yield
    faults.disarm()


@pytest.fixture(autouse=True)
def _fast_driver_lease(monkeypatch):
    monkeypatch.setenv("HOROVOD_TPU_DRIVER_LEASE_TIMEOUT", "0.6")
    monkeypatch.setenv("HOROVOD_TPU_DRIVER_LEASE_INTERVAL", "0.1")


def _replicated_pair():
    """Primary+standby ElasticRendezvousServer pair. The KV lease is slow
    (manual promotion) so tests control exactly when the replica tier
    fails over."""
    from horovod_tpu.runner.http_server import find_free_port
    p1, p2 = find_free_port(), find_free_port()
    a = ElasticRendezvousServer(("127.0.0.1", p1))
    b = ElasticRendezvousServer(("127.0.0.1", p2))
    a.start()
    b.start()
    reps = [f"127.0.0.1:{p1}", f"127.0.0.1:{p2}"]
    cfg = ReplicationConfig(lease_timeout=60, lease_interval=0.1)
    a.enable_replication(reps[0], reps, role="primary", config=cfg)
    b.enable_replication(reps[1], reps, role="standby", config=cfg)
    return a, b


def _primary_driver(server, hosts, min_np=2, max_np=4):
    disc = FixedHosts(hosts)
    driver = ElasticDriver(server, disc, min_np=min_np, max_np=max_np,
                           timeout=5.0)
    server.set_driver(driver)
    driver.attach_journal(DriverJournal(server))
    workers = MockWorkers(driver)
    return driver, disc, workers


def _shadow(server):
    return DriverJournal.replay(
        server.snapshot(SCOPE_DRIVER).get(SCOPE_DRIVER, {}))


def _mid_resize(driver, disc, standby_server, new_hosts):
    """Grow discovery and wait until the standby's replicated journal
    holds the pending resize — the half-activated snapshot every
    failover test starts from."""
    disc.set(new_hosts)
    assert wait_until(driver.resume_needed, timeout=5)
    assert wait_until(
        lambda: set(_shadow(standby_server).hosts) == set(new_hosts) and
        _shadow(standby_server).head == driver._journal.head(), timeout=5)


def _promote(standby, reason="lease-expiry", timeout=5.0):
    """Promote once the dead driver's lease goes stale: the standby's
    FIRST lease observation timestamps 'now' (conservative: assume fresh
    until proven stale), so a one-shot promote() defers — retry past the
    driver lease timeout like the monitor loop does."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        d = standby.promote(reason=reason)
        if d is not None:
            return d
        time.sleep(0.1)
    return None


class TestJournalReplay:
    def test_replay_reconstructs_mid_resize_state_bitwise(self):
        """The standby's local journal replays into exactly the dead
        driver's HostManager + world + registry state, frozen
        mid-resize."""
        a, b = _replicated_pair()
        driver, disc, workers = _primary_driver(a, {"h1": 2})
        try:
            driver.start(2, workers.create)
            driver.record_worker_exit("h1", 1, exit_code=1)
            driver.record_worker_exit("h1", 1, exit_code=1)  # strike 2
            _mid_resize(driver, disc, b, {"h1": 2, "h2": 2})
            # the resize's notify entry is journalled after its hosts
            # entry: a shadow taken between the two reads notify None
            assert wait_until(
                lambda: _shadow(b).notify == driver._last_notify and
                _shadow(b).head == driver._journal.head(), timeout=5)

            # replay from the STANDBY's locally-replicated store
            shadow = _shadow(b)
            with driver._lock:
                assert shadow.version == driver._world_version
                assert shadow.assignments == [
                    s.to_response_string() for s in driver._assignments]
                assert sorted(tuple(s) for s in shadow.started) == \
                    sorted(driver._started_slots)
                assert shadow.results == {
                    k: c for k, (_, c) in driver._results.items()}
                assert {k: v["count"]
                        for k, v in shadow.strikes.items()} == \
                    {k: v["count"]
                     for k, v in driver._slot_strikes.items()}
                assert shadow.pending and driver._pending_resume
                assert shadow.notify == driver._last_notify
            current, order, blacklist = driver.host_manager.state()
            assert shadow.hosts == current
            assert shadow.order == order
            assert set(shadow.blacklist) == blacklist
            assert shadow.head == driver._journal.head()
        finally:
            driver.stop()
            driver.join()
            a.stop()
            b.stop()

    def test_result_and_blacklist_replay(self):
        """Worker exits and blacklists survive replay: a clean exit clears
        strikes, a blacklisted host leaves membership."""
        a, b = _replicated_pair()
        driver, disc, workers = _primary_driver(a, {"h1": 2, "h2": 2},
                                                min_np=2)
        try:
            driver.start(4, workers.create)
            driver.record_worker_exit("h1", 0, exit_code=0)
            disc.set({"h1": 2})            # h2 vanishes from discovery
            driver.record_worker_exit("h2", 0, exit_code=1)
            assert wait_until(
                lambda: driver.host_manager.is_blacklisted("h2"))
            assert wait_until(
                lambda: _shadow(b).head == driver._journal.head())
            shadow = _shadow(b)
            assert shadow.results["h1:0"] == 0
            assert "h1:0" not in shadow.strikes
            assert "h2" in shadow.blacklist
            assert "h2" not in shadow.hosts
        finally:
            driver.stop()
            driver.join()
            a.stop()
            b.stop()

    def test_dropped_journal_write_is_nonfatal(self):
        """driver.journal=drop() loses the entry with a WARNING; the
        driver keeps running and later appends still land."""
        a, b = _replicated_pair()
        driver, disc, workers = _primary_driver(a, {"h1": 2})
        try:
            driver.start(2, workers.create)
            head_before = driver._journal.head()
            faults.arm("driver.journal=1*drop()")
            assert driver._journal.append("pending", pending=True) is False
            assert driver._journal.append("pending", pending=True) is True
            assert driver._journal.head() > head_before
            assert registry().counter(
                "hvd_tpu_driver_journal_writes_total").value(
                    kind="pending") >= 1
        finally:
            driver.stop()
            driver.join()
            a.stop()
            b.stop()


class TestStandbyElection:
    def test_standby_defers_to_live_driver(self):
        """The election restriction: while the live driver's journal lease
        keeps refreshing, promote() declines; once the driver dies and the
        lease goes stale, promotion proceeds."""
        a, b = _replicated_pair()
        driver, disc, workers = _primary_driver(a, {"h1": 2})
        standby = DriverStandby(b, FixedHosts({"h1": 2}), min_np=2,
                                max_np=4, timeout=5.0,
                                create_worker_fn=MockWorkers(None).create)
        try:
            driver.start(2, workers.create)   # discovery loop heartbeats
            assert wait_until(lambda: standby.journal_head() > 0)
            time.sleep(0.3)                   # a lease tick has landed
            assert standby.promote(reason="manual") is None
            assert standby.driver is None
            # driver dies: heartbeats stop, lease goes stale
            driver.stop()
            driver.join()
            b.replication.promote("test")
            assert wait_until(
                lambda: standby.promote(reason="lease-expiry") is not None,
                timeout=5)
            assert standby.driver is not None
            assert standby.last_promotion_epoch() >= 1
        finally:
            standby.stop()
            driver.stop()
            driver.join()
            a.stop()
            b.stop()

    def test_promotion_resumes_half_activated_world(self):
        """Promotion over a mid-resize snapshot: the restored driver
        serves the journaled world version, re-runs the resume when the
        old world's survivors re-rendezvous, and launches the new host's
        workers through the standby's create_worker_fn — no fleet
        restart."""
        a, b = _replicated_pair()
        driver, disc, workers = _primary_driver(a, {"h1": 2})
        standby_workers = MockWorkers(None)
        standby = DriverStandby(b, FixedHosts({"h1": 2, "h2": 2}),
                                min_np=2, max_np=4, timeout=5.0,
                                create_worker_fn=standby_workers.create)
        try:
            driver.start(2, workers.create)
            v1 = driver.world_version
            _mid_resize(driver, disc, b, {"h1": 2, "h2": 2})
            # the driver dies mid-resize (stop heartbeats + discovery)
            driver.stop()
            driver.join()
            b.replication.promote("driver-failover")
            promoted = _promote(standby)
            assert promoted is not None
            assert promoted.world_version == v1
            assert promoted.resume_needed()
            # survivors of the old world re-rendezvous against the
            # promoted driver; the registry barrier fires the resume
            promoted.record_ready("h1", 0)
            promoted.record_ready("h1", 1)
            assert wait_until(lambda: promoted.world_version == v1 + 1,
                              timeout=10)
            assert wait_until(lambda: not promoted.resume_needed())
            assert promoted.world_size() == 4
            assert wait_until(
                lambda: ("h2", 0) in standby_workers.started_keys() and
                        ("h2", 1) in standby_workers.started_keys())
            # only the NEW slots started processes — survivors kept theirs
            assert ("h1", 0) not in standby_workers.started_keys()
            reg = registry()
            assert reg.counter(
                "hvd_tpu_driver_promotions_total").value() >= 1
            assert reg.counter(
                "hvd_tpu_driver_failovers_total").value() >= 1
            assert reg.counter(
                "hvd_tpu_elastic_recoveries_total").value(
                    kind="driver_failover") >= 1
        finally:
            standby.stop()
            driver.stop()
            driver.join()
            a.stop()
            b.stop()

    def test_promotion_seeds_registry_with_journaled_results(self):
        """Workers that already exited 0 under the dead driver must not
        block the promoted driver's completion: their monitors died with
        the old process, so the journaled results seed the registry and
        the finish check."""
        a, b = _replicated_pair()
        driver, disc, workers = _primary_driver(a, {"h1": 2}, max_np=2)
        standby = DriverStandby(b, FixedHosts({"h1": 2}), min_np=2,
                                max_np=2, timeout=5.0,
                                create_worker_fn=MockWorkers(None).create)
        try:
            driver.start(2, workers.create)
            driver.record_worker_exit("h1", 0, exit_code=0)
            driver.record_worker_exit("h1", 1, exit_code=0)
            assert wait_until(driver.finished)
            driver.stop()
            driver.join()
            b.replication.promote("test")
            promoted = _promote(standby)
            assert promoted is not None
            # all journaled results were exit 0 ⇒ finished immediately
            assert wait_until(promoted.finished, timeout=5)
            assert promoted.error_message is None
        finally:
            standby.stop()
            driver.stop()
            driver.join()
            a.stop()
            b.stop()


class TestResizeInterleavingRegressions:
    def test_get_slot_state_pending_on_mid_scan_version_bump(self):
        """ISSUE 19 race fix: a reentrant resume (registry barrier fired
        on this thread, RLock re-entered) swapping the world between
        get_slot_state's version read and its slot scan must yield
        'pending', never a slot of the PRIOR world."""
        server = ElasticRendezvousServer()
        server.start()
        driver = ElasticDriver(server, FixedHosts({"h1": 2}), min_np=2,
                               timeout=5.0)
        server.set_driver(driver)
        workers = MockWorkers(driver)
        try:
            driver.start(2, workers.create)

            class _SwappingList(list):
                """Simulates the reentrant world swap mid-scan."""
                fired = False

                def __iter__(self):
                    it = super().__iter__()
                    if not _SwappingList.fired:
                        _SwappingList.fired = True
                        with driver._lock:       # reentrant on this thread
                            driver._world_version += 1
                            driver._assignments = []
                    return it

            with driver._lock:
                driver._assignments = _SwappingList(driver._assignments)
            state, slot, version = driver.get_slot_state("h1", 0)
            assert state == "pending"
            assert slot is None
            assert version == driver.world_version
        finally:
            driver.stop()
            driver.join()
            server.stop()

    def test_wait_for_world_rechecks_after_off_lock_count(self):
        """A resize landing between the off-lock registry count and the
        return must not satisfy wait_for_world with the PRIOR world's
        readiness."""
        server = ElasticRendezvousServer()
        server.start()
        driver = ElasticDriver(server, FixedHosts({"h1": 2}), min_np=2,
                               timeout=5.0)
        server.set_driver(driver)
        workers = MockWorkers(driver)
        try:
            driver.start(2, workers.create)
            driver.record_ready("h1", 0)
            driver.record_ready("h1", 1)
            assert driver.wait_for_world(1, timeout=5)

            orig_count = driver._registry.count

            def _count_then_resize(state):
                c = orig_count(state)
                if state == READY:
                    with driver._lock:   # a resize lands in the window
                        driver._pending_resume = True
                return c

            driver._registry.count = _count_then_resize
            assert driver.wait_for_world(1, timeout=0.8) is False
        finally:
            driver.stop()
            driver.join()
            server.stop()


class TestDiscoveryHardening:
    def test_failing_discovery_serves_last_known_good(self):
        """A discovery source that starts failing must not kill the
        driver: the manager retries, then serves the last-known-good
        snapshot as NO_UPDATE with the failure counted."""
        from horovod_tpu.elastic.discovery import (HostManager,
                                                   HostUpdateResult)

        class _Flaky(FixedHosts):
            def __init__(self, hosts):
                super().__init__(hosts)
                self.broken = False

            def find_available_hosts_and_slots(self):
                if self.broken:
                    raise RuntimeError("discovery script exploded")
                return super().find_available_hosts_and_slots()

        disc = _Flaky({"h1": 2, "h2": 2})
        hm = HostManager(disc)
        assert hm.update_available_hosts() == HostUpdateResult.ADDED
        before = registry().counter(
            "hvd_tpu_discovery_failures_total").value()
        disc.broken = True
        assert hm.update_available_hosts() == HostUpdateResult.NO_UPDATE
        # last-known-good membership still served
        assert [h.hostname for h in hm.current_hosts()] == ["h1", "h2"]
        assert hm.available_slots() == 4
        assert registry().counter(
            "hvd_tpu_discovery_failures_total").value() == before + 1
        # recovery: the next successful probe resumes normal updates
        disc.broken = False
        disc.set({"h1": 2})
        assert hm.update_available_hosts() == HostUpdateResult.REMOVED

    def test_driver_discovery_failpoint_retried(self):
        """driver.discovery=drop() fails one probe attempt; the bounded
        retry inside the manager absorbs it without surfacing a failure."""
        from horovod_tpu.elastic.discovery import (HostManager,
                                                   HostUpdateResult)
        disc = FixedHosts({"h1": 2})
        hm = HostManager(disc)
        faults.arm("driver.discovery=1*drop()")
        assert hm.update_available_hosts() == HostUpdateResult.ADDED
        assert [h.hostname for h in hm.current_hosts()] == ["h1"]
