"""Multi-model fleet contract (ISSUE 8 acceptance): SLO policy types,
priority-aging in the batcher, per-name roll-vs-eviction locking, metrics
label hygiene, warm-pool LRU eviction with zero-recompile re-admission
(persistent AOT cache), SLO shed ordering (lowest priority first),
controller rebalancing that keeps in-flight requests answered, the
`/fleet` + fleet-aware `/readyz` HTTP surface, and a slow 64-model
long-tail soak."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.monitor.registry import MetricsRegistry
from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.serving import (ContinuousBatcher, FleetPolicy,
                                        LatencySLO, ModelFleet,
                                        ModelRegistry, RejectedError,
                                        Replica, ServingMetrics, SLOTracker)
from deeplearning4j_tpu.train.updaters import Sgd


def _net(seed=0, n_in=8, n_out=3, hidden=16):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(1e-1))
            .list([DenseLayer(n_out=hidden, activation="relu"),
                   OutputLayer(n_out=n_out, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _x(n=2, n_in=8, seed=0):
    return np.random.RandomState(seed).randn(n, n_in).astype(np.float32)


def _fleet(tmp_path, **kw):
    kw.setdefault("max_resident", 2)
    kw.setdefault("max_batch", 4)
    kw.setdefault("batch_timeout_ms", 1.0)
    kw.setdefault("cache_dir", str(tmp_path / "exec-cache"))
    return ModelFleet(**kw)


# ---------------------------------------------------------------------------
# SLO policy types
# ---------------------------------------------------------------------------

def test_latency_slo_and_policy_validation():
    slo = LatencySLO(target_p99_ms=50.0, priority=3)
    assert slo.request_deadline_ms() == 200.0          # 4x target default
    assert LatencySLO(target_p99_ms=50.0,
                      deadline_ms=75.0).request_deadline_ms() == 75.0
    with pytest.raises(ValueError, match="target_p99_ms"):
        LatencySLO(target_p99_ms=0.0)
    with pytest.raises(ValueError, match="deadline_ms"):
        LatencySLO(deadline_ms=-1.0)
    with pytest.raises(ValueError, match="mode"):
        FleetPolicy(mode="panic")
    with pytest.raises(ValueError, match="breach_after"):
        FleetPolicy(breach_after=0)


def test_slo_tracker_hysteresis_both_directions():
    t = SLOTracker(LatencySLO(target_p99_ms=100.0), breach_after=3,
                   clear_after=2)
    assert not t.observe(500.0) and not t.observe(500.0)   # 2 < breach_after
    assert t.observe(500.0)                                # 3rd flips
    assert t.breaches_total == 1
    assert t.observe(50.0)                                 # 1 good: still on
    assert not t.observe(50.0)                             # 2nd clears
    assert not t.observe(float("nan"))    # empty window counts healthy
    t.observe(500.0), t.observe(500.0), t.observe(500.0)
    assert t.breached and t.breaches_total == 2            # onsets counted


# ---------------------------------------------------------------------------
# satellite: batcher priority aging
# ---------------------------------------------------------------------------

def test_effective_priority_ages_near_deadline():
    b = ContinuousBatcher(lambda g, xs: xs, aging_fraction=0.5,
                          aging_bump=1 << 20)
    try:
        now = time.monotonic()
        from deeplearning4j_tpu.serving.batcher import _Request
        from concurrent.futures import Future
        fresh = _Request(x=np.zeros((1, 2)), future=Future(), group=("g",),
                         priority=0, enqueued=now, deadline=now + 1.0)
        assert b._effective_priority(fresh, now) == 0      # full budget left
        # less than half the budget remains -> escalates above priority 5
        aged = _Request(x=np.zeros((1, 2)), future=Future(), group=("g",),
                        priority=0, enqueued=now - 0.6, deadline=now + 0.4)
        assert b._effective_priority(aged, now) > 5
        nodl = _Request(x=np.zeros((1, 2)), future=Future(), group=("g",),
                        priority=2, enqueued=now, deadline=None)
        assert b._effective_priority(nodl, now) == 2       # no deadline: flat
    finally:
        b.shutdown(drain=False)


def test_aging_prevents_priority_starvation():
    """A low-priority near-deadline request dispatches ahead of a steady
    high-priority stream instead of starving straight past its deadline."""
    gate = threading.Event()
    order = []

    def dispatch(group, xs):
        gate.wait(timeout=5.0)
        order.append(group[0])
        return xs

    b = ContinuousBatcher(dispatch, max_batch=1, batch_timeout_ms=0.5,
                          aging_fraction=1.0)    # escalate immediately
    try:
        b.submit(np.zeros((1, 2)), group=("hi",), priority=5)  # blocks worker
        time.sleep(0.05)
        lo = b.submit(np.zeros((1, 2)), group=("lo",), priority=0,
                      deadline_ms=2000.0)
        his = [b.submit(np.zeros((1, 2)), group=("hi",), priority=5)
               for _ in range(4)]
        gate.set()
        lo.result(timeout=5.0)
        for f in his:
            f.result(timeout=5.0)
        # the aged lo request seeded the first post-gate dispatch
        assert order[1] == "lo", order
    finally:
        b.shutdown(drain=False)


def test_shed_decisions_counted_per_priority_class():
    gate = threading.Event()
    reg = MetricsRegistry()
    m = ServingMetrics(registry_=reg, server_label="s", model_label="m")
    b = ContinuousBatcher(lambda g, xs: (gate.wait(5.0), xs)[1],
                          max_batch=1, max_queue=2, metrics=m)
    try:
        b.submit(np.zeros((1, 2)), priority=7)             # occupies worker
        time.sleep(0.05)
        b.submit(np.zeros((1, 2)), priority=7, deadline_ms=1.0)
        b.submit(np.zeros((1, 2)), priority=3)
        with pytest.raises(RejectedError):                 # queue full
            b.submit(np.zeros((1, 2)), priority=1)
        time.sleep(0.05)                # let the p7 deadline lapse in queue
        gate.set()
        deadline = time.monotonic() + 5.0
        while ("expired:p7" not in m.sheds_by_priority()
               and time.monotonic() < deadline):
            time.sleep(0.01)
        sheds = m.sheds_by_priority()
        assert sheds.get("rejected:p1") == 1
        assert sheds.get("expired:p7") == 1
        assert m.snapshot()["sheds"] == sheds
    finally:
        gate.set()
        b.shutdown(drain=False)


# ---------------------------------------------------------------------------
# satellite: registry roll-vs-eviction lock
# ---------------------------------------------------------------------------

def test_name_lock_serializes_roll_against_eviction():
    reg = ModelRegistry()
    reg.register("m", _net(seed=1))
    assert reg.name_lock("m") is reg.name_lock("m")        # stable per name
    assert reg.name_lock("m") is not reg.name_lock("other")
    rolled = threading.Event()

    def roll():
        reg.register("m", _net(seed=2))                    # takes name lock
        rolled.set()

    with reg.name_lock("m"):       # simulated eviction drain/drop window
        t = threading.Thread(target=roll, daemon=True)
        t.start()
        time.sleep(0.15)
        assert not rolled.is_set()          # roll waits for the eviction
    t.join(timeout=5.0)
    assert rolled.is_set() and reg.get("m").version == 2
    # other names are unaffected by a held lock
    with reg.name_lock("m"):
        reg.register("other", _net(seed=3))


# ---------------------------------------------------------------------------
# satellite: metrics label hygiene
# ---------------------------------------------------------------------------

def test_metrics_label_pair_and_family_dedupe():
    reg = MetricsRegistry()
    a = ServingMetrics(registry_=reg, server_label="m0/r1", model_label="m0")
    assert a._base_labels == {"server": "m0/r1", "model": "m0"}
    a.submitted.inc(3)
    # same label pair (a warm re-admission rebuilding the server) reuses
    # the SAME series: no duplicate family member, counters accumulate
    b = ServingMetrics(registry_=reg, server_label="m0/r1", model_label="m0")
    assert b.submitted is a.submitted
    b.submitted.inc()
    assert a.submitted.value == 4
    # a different replica is a distinct series in the same family
    c = ServingMetrics(registry_=reg, server_label="m0/r2", model_label="m0")
    assert c.submitted is not a.submitted
    assert dict(c.submitted.labels)["model"] == "m0"
    # without model_label the series omits the label (back-compat)
    d = ServingMetrics(registry_=reg, server_label="solo")
    assert "model" not in dict(d.submitted.labels)


# ---------------------------------------------------------------------------
# fleet: deploy + route
# ---------------------------------------------------------------------------

def test_fleet_deploy_route_and_errors(tmp_path):
    with _fleet(tmp_path) as fleet:
        fleet.deploy("a", _net(seed=1), slo=LatencySLO(priority=1))
        fleet.deploy("b", _net(seed=2, n_out=5))
        assert fleet.output("a", _x()).shape == (2, 3)
        assert fleet.output("b", _x()).shape == (2, 5)
        assert fleet.member("a").requests == 1
        with pytest.raises(ValueError, match="already deployed"):
            fleet.deploy("a", _net())
        with pytest.raises(ValueError, match="exactly one"):
            fleet.deploy("c")
        with pytest.raises(KeyError, match="no model"):
            fleet.output("missing", _x())
        st = fleet.fleet_stats()
        assert set(st["models"]) == {"a", "b"}
        assert st["capacity"]["max_resident"] == 2
        assert st["models"]["a"]["priority"] == 1


def test_warm_pool_lru_eviction_and_zero_recompile_readmission(tmp_path):
    with _fleet(tmp_path, max_resident=2) as fleet:
        # distinct architectures -> distinct AOT fingerprints
        for i, width in enumerate((8, 12, 20)):
            fleet.deploy(f"m{i}", _net(seed=i, hidden=width))
        fleet.output("m0", _x())
        fleet.output("m1", _x())
        assert fleet.pool.resident_names() == ["m0", "m1"]
        first = fleet.member("m0").last_admission_fresh_compiles
        assert first and first > 0                   # cold start compiles
        fleet.output("m2", _x())                     # evicts LRU = m0
        m0 = fleet.member("m0")
        assert m0.state == "cold" and m0.evictions == 1
        assert fleet.pool.resident_names() == ["m1", "m2"]
        # evicted params went back to host numpy (device memory released)
        entry = fleet.registry.entries("m0")[0]
        import jax
        for leaf in jax.tree_util.tree_leaves(entry.model.params_):
            assert isinstance(leaf, np.ndarray)
        # re-admission: executables deserialize from the persistent AOT
        # cache — ZERO fresh XLA compiles
        before = dict(fleet.cache.stats)
        y = fleet.output("m0", _x())
        assert y.shape == (2, 3)
        assert fleet.member("m0").state == "resident"
        assert fleet.member("m0").admissions == 2
        assert fleet.member("m0").last_admission_fresh_compiles == 0
        assert fleet.cache.stats["compiles"] == before["compiles"]
        assert fleet.cache.stats["disk_hits"] > before["disk_hits"]
        assert fleet.pool.resident_names() == ["m2", "m0"]   # m1 was LRU


def test_eviction_drains_inflight_requests(tmp_path):
    with _fleet(tmp_path) as fleet:
        fleet.deploy("m", _net(seed=4))
        futs = [fleet.submit("m", _x(seed=i)) for i in range(6)]
        assert fleet.evict("m") is True              # drain -> drop
        for f in futs:
            assert f.result(timeout=10.0).shape == (2, 3)
        assert fleet.member("m").state == "cold"
        assert fleet.evict("m") is False             # already cold: no-op


def test_capacity_exhaustion_and_slice_pressure(tmp_path):
    with _fleet(tmp_path, max_resident=2, n_slices=1) as fleet:
        fleet.deploy("a", _net(seed=1))
        fleet.deploy("b", _net(seed=2))
        fleet.output("a", _x())
        # only 1 slice: admitting b evicts a even though max_resident=2
        fleet.output("b", _x())
        assert fleet.pool.resident_names() == ["b"]
        assert fleet.member("a").state == "cold"
    with _fleet(tmp_path, max_resident=2, n_slices=1) as fleet:
        fleet.deploy("wide", _net(seed=3), replicas=2)   # needs 2 slices
        with pytest.raises(RejectedError, match="capacity"):
            fleet.output("wide", _x())


def test_preferred_slice_affinity_on_readmission(tmp_path):
    with _fleet(tmp_path, max_resident=3, n_slices=4) as fleet:
        for i in range(3):
            fleet.deploy(f"m{i}", _net(seed=i))
            fleet.output(f"m{i}", _x())              # m0->s0, m1->s1, m2->s2
        assert fleet.member("m2").group.replicas[0].slice.index == 2
        fleet.evict("m0")
        fleet.evict("m2")                            # free slices: {0, 2, 3}
        fleet.output("m2", _x())
        # affinity: m2 returns to slice 2 (its persistent-cache home on a
        # device-pinned fleet), not the lowest free slice 0
        assert fleet.member("m2").group.replicas[0].slice.index == 2


# ---------------------------------------------------------------------------
# fleet: SLO shed ordering
# ---------------------------------------------------------------------------

def _force_breach(member):
    for _ in range(member.tracker.breach_after):
        member.tracker.observe(member.slo.target_p99_ms * 100.0)
    assert member.tracker.breached


def test_shed_ordering_low_priority_first(tmp_path):
    with _fleet(tmp_path) as fleet:
        fleet.deploy("lo", _net(seed=1), slo=LatencySLO(priority=0))
        hi = fleet.deploy("hi", _net(seed=2),
                          slo=LatencySLO(priority=10), warm=True)
        fleet.output("lo", _x())
        _force_breach(hi)                    # hi under sustained pressure
        assert fleet.router.shed_level() == 10
        # lower-priority traffic sheds first ...
        with pytest.raises(RejectedError, match="shed"):
            fleet.submit("lo", _x())
        assert fleet.member("lo").sheds == 1
        # ... while the highest-priority member keeps being served
        assert fleet.output("hi", _x()).shape == (2, 3)
        assert fleet.member("hi").sheds == 0
        # breach clears -> low-priority traffic flows again
        for _ in range(fleet.policy.clear_after):
            hi.tracker.observe(1.0)
        assert fleet.router.shed_level() is None
        assert fleet.output("lo", _x()).shape == (2, 3)


def test_self_shed_probes_so_breach_can_clear(tmp_path):
    with _fleet(tmp_path) as fleet:
        lo = fleet.deploy("lo", _net(seed=1), slo=LatencySLO(priority=0),
                          warm=True)
        fleet.deploy("hi", _net(seed=2), slo=LatencySLO(priority=10))
        _force_breach(lo)        # lo breached, outranked by hi -> self-shed
        n = 2 * fleet.router.probe_every
        served = sheds = 0
        for i in range(n):
            try:
                fleet.output("lo", _x(seed=i))
                served += 1
            except RejectedError:
                sheds += 1
        # most traffic sheds, but probe admissions keep samples flowing
        assert served == 2 and sheds == n - 2
        assert fleet.member("lo").sheds == sheds


def test_deprioritize_mode_admits_at_floor(tmp_path):
    with _fleet(tmp_path,
                policy=FleetPolicy(mode="deprioritize")) as fleet:
        fleet.deploy("lo", _net(seed=1), slo=LatencySLO(priority=0))
        hi = fleet.deploy("hi", _net(seed=2),
                          slo=LatencySLO(priority=10), warm=True)
        _force_breach(hi)
        # deprioritized, not refused: the request still answers
        assert fleet.output("lo", _x()).shape == (2, 3)
        assert fleet.member("lo").deprioritized == 1
        assert fleet.member("lo").sheds == 0


# ---------------------------------------------------------------------------
# fleet: replica dispatch health
# ---------------------------------------------------------------------------

def test_replica_health_state_machine():
    import types
    r = Replica("m", types.SimpleNamespace(), types.SimpleNamespace(index=0))
    assert r.healthy
    assert not r.record_failure(3) and not r.record_failure(3)
    assert r.record_failure(3)              # third consecutive: flips
    assert not r.healthy
    assert not r.record_failure(3)          # already down: no re-flip
    assert r.failures == 4
    assert r.record_success()               # probe passed: clears
    assert r.healthy and r.consecutive_failures == 0
    assert not r.record_success()           # steady state: no event
    # a success between failures resets the consecutive count
    r.record_failure(3), r.record_success(), r.record_failure(3)
    assert r.healthy and r.consecutive_failures == 1


def test_flaky_replica_marked_unhealthy_probed_and_readmitted(tmp_path):
    from deeplearning4j_tpu.utils import chaos
    with _fleet(tmp_path, max_resident=2, n_slices=2) as fleet:
        m = fleet.deploy("m", _net(seed=1), replicas=2, warm=True)
        assert len(m.group.replicas) == 2
        good, bad = m.group.replicas
        failovers_before = fleet.instruments.failovers.value
        flaky = chaos.FlakyDispatch(bad.server.cache.run, times=10_000)
        bad.server.cache.run = flaky
        # drive traffic: a request the router hands the flaky replica
        # FAILS OVER to the healthy one — the client never sees the
        # ChaosError — while unhealthy_after consecutive dispatch
        # failures open the replica's breaker
        for i in range(32):
            fleet.output("m", _x(seed=i), timeout=10)
            if not bad.healthy:
                break
        deadline = time.monotonic() + 5     # observer runs on done-callback
        while bad.healthy and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not bad.healthy and good.healthy
        assert bad.consecutive_failures >= fleet.policy.unhealthy_after
        assert fleet.instruments.replica_unhealthy.value >= 1
        assert fleet.instruments.failovers.value > failovers_before
        # routing now avoids it except for probe admissions: over two full
        # probe windows, exactly 2 picks land on the sick replica
        picks = [fleet.router.pick(m)
                 for _ in range(2 * fleet.router.probe_every)]
        assert picks.count(bad) == 2
        assert all(r is good for r in picks if r is not bad)
        # while the probe keeps failing it stays out of rotation — and
        # EVERY request is still served, the failed probes included:
        # they re-route to the healthy replica instead of surfacing
        for i in range(2 * fleet.router.probe_every):
            fleet.output("m", _x(seed=i), timeout=10)
        assert not bad.healthy
        # the server recovers: the next probe succeeds and the replica
        # re-enters normal rotation
        bad.server.cache.run = flaky.fn
        for i in range(4 * fleet.router.probe_every):
            fleet.output("m", _x(seed=i), timeout=10)
            if bad.healthy:
                break
        deadline = time.monotonic() + 5
        while not bad.healthy and time.monotonic() < deadline:
            time.sleep(0.01)
        assert bad.healthy and bad.probes >= 1
        assert fleet.instruments.replica_probes.value >= 1
        assert bad in [fleet.router.pick(m) for _ in range(4)]


# ---------------------------------------------------------------------------
# fleet: controller rebalancing
# ---------------------------------------------------------------------------

def test_controller_grows_pressured_member(tmp_path):
    with _fleet(tmp_path, max_resident=2, n_slices=3) as fleet:
        m = fleet.deploy("m", _net(seed=1), warm=True)
        assert len(m.group.replicas) == 1
        _force_breach(m)
        rec = fleet.controller.reconcile()
        assert [a["action"] for a in rec["actions"]] == ["grow"]
        assert len(m.group.replicas) == 2
        assert fleet.fleet_stats()["recent_actions"]
        # both replicas serve (least-loaded routing spreads the stream)
        for i in range(8):
            assert fleet.output("m", _x(seed=i)).shape == (2, 3)


def test_controller_reclaims_idle_donor_slice(tmp_path):
    policy = FleetPolicy(shrink_idle_after_s=0.0)
    with _fleet(tmp_path, max_resident=2, n_slices=2,
                policy=policy) as fleet:
        donor = fleet.deploy("donor", _net(seed=1), warm=True)
        needy = fleet.deploy("needy", _net(seed=2),
                             slo=LatencySLO(priority=5), warm=True)
        # grow one replica onto the donor's... no free slice exists, so
        # the controller must first drain the idle donor's spare. Give the
        # donor a second replica to donate:
        fleet.controller.reconcile()     # no pressure: nothing happens
        assert len(donor.group.replicas) == 1
        _force_breach(needy)
        rec = fleet.controller.reconcile()
        # donor has only its floor replica -> nothing reclaimable
        assert rec["actions"] == []
        assert len(needy.group.replicas) == 1


def test_rebalance_keeps_inflight_answered(tmp_path):
    policy = FleetPolicy(shrink_idle_after_s=0.0)
    with _fleet(tmp_path, max_resident=1, n_slices=2,
                policy=policy) as fleet:
        m = fleet.deploy("m", _net(seed=1), warm=True)
        _force_breach(m)
        fleet.controller.reconcile()                 # grow to 2 replicas
        assert len(m.group.replicas) == 2
        futs = [fleet.submit("m", _x(seed=i)) for i in range(12)]
        for _ in range(fleet.policy.clear_after):    # breach clears
            m.tracker.observe(1.0)
        # shrink engages once the member is idle; the leaving replica is
        # pulled from routing FIRST, then drained — nothing is dropped
        rec, deadline = None, time.monotonic() + 10.0
        while time.monotonic() < deadline:
            m.last_used = time.monotonic() - 1.0     # "idle" for shrink
            rec = fleet.controller.reconcile()
            if rec["actions"]:
                break
            time.sleep(0.02)
        assert rec and [a["action"] for a in rec["actions"]] == ["shrink"]
        assert len(m.group.replicas) == 1
        for f in futs:                 # every in-flight request answered
            assert f.result(timeout=10.0).shape == (2, 3)


# ---------------------------------------------------------------------------
# fleet: mesh-pinned slices
# ---------------------------------------------------------------------------

def test_mesh_slice_replica_groups(tmp_path):
    import jax
    devices = jax.devices()
    if len(devices) < 4:
        pytest.skip("needs >= 4 devices (conftest provides 8 virtual CPUs)")
    with _fleet(tmp_path, max_resident=2, devices=devices,
                slice_size=2) as fleet:
        assert len(fleet._slices) == len(devices) // 2
        fleet.deploy("m", _net(seed=1), warm=True)
        replica = fleet.member("m").group.replicas[0]
        assert replica.server.cache.mesh is not None
        assert len(replica.slice.devices) == 2
        y = fleet.output("m", _x(n=4))
        assert y.shape == (4, 3)
        st = fleet.fleet_stats()
        assert st["capacity"]["slice_size"] == 2
    with pytest.raises(ValueError, match="slice_size"):
        ModelFleet(devices=devices, slice_size=len(devices) + 1)


# ---------------------------------------------------------------------------
# fleet: rolls, schedules, readiness
# ---------------------------------------------------------------------------

def test_roll_is_zero_downtime_and_warms_new_version(tmp_path):
    with _fleet(tmp_path) as fleet:
        fleet.deploy("m", _net(seed=1, n_out=3), warm=True)
        futs = [fleet.submit("m", _x(seed=i)) for i in range(4)]
        entry = fleet.roll("m", _net(seed=2, n_out=5))
        assert entry.version == 2
        for f in futs:       # in-flight stay on the version they resolved
            assert f.result(timeout=10.0).shape[1] in (3, 5)
        assert fleet.output("m", _x()).shape == (2, 5)   # new submits: v2
        # roll on a cold member just registers (admission picks it up)
        fleet.deploy("cold", _net(seed=3))
        assert fleet.roll("cold", _net(seed=4)).version == 2


def test_schedule_applies_on_admission(tmp_path):
    from deeplearning4j_tpu.compile import Schedule
    with _fleet(tmp_path, max_batch=16) as fleet:
        Schedule(buckets=[4, 16]).apply(fleet)       # fleet default hook
        assert fleet.default_schedule is not None
        fleet.deploy("m", _net(seed=1), warm=True)
        replica = fleet.member("m").group.replicas[0]
        assert replica.server.cache.buckets == [4, 16]
        # a per-model schedule wins over the fleet default
        fleet.deploy("n", _net(seed=2), schedule=Schedule(buckets=[8, 16]),
                     warm=True)
        assert fleet.member("n").group.replicas[0] \
            .server.cache.buckets == [8, 16]


def test_fleet_readyz_cold_members_do_not_block(tmp_path):
    fleet = _fleet(tmp_path)
    assert not fleet.readyz()["ready"]               # nothing deployed
    fleet.deploy("m", _net(seed=1))                  # cold but routable
    assert fleet.readyz() == {"ready": True, "reasons": []}
    fleet.output("m", _x())
    assert fleet.readyz()["ready"]
    fleet.shutdown()
    assert not fleet.readyz()["ready"]
    with pytest.raises(RejectedError, match="shut down"):
        fleet.submit("m", _x())


def test_fleet_http_endpoints(tmp_path):
    from deeplearning4j_tpu.ui.server import UIServer
    with _fleet(tmp_path) as fleet:
        ui = UIServer()                  # fresh instance, not the singleton
        ui.attach_fleet(fleet)
        port = ui.start(port=0)
        try:
            # fleet not ready (no models) -> aggregate /readyz is 503
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/readyz", timeout=5)
            assert ei.value.code == 503
            fleet.deploy("m", _net(seed=1), slo=LatencySLO(priority=2),
                         warm=True)
            fleet.output("m", _x())
            r = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/readyz", timeout=5)
            assert json.loads(r.read())["ready"] is True
            r = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/fleet", timeout=5)
            payload = json.loads(r.read())
            assert isinstance(payload, list) and len(payload) == 1
            st = payload[0]
            assert st["resident"] == ["m"]
            assert st["models"]["m"]["state"] == "resident"
            assert st["models"]["m"]["priority"] == 2
            assert st["aot_cache"]["compiles"] > 0
            r = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=5)
            assert json.loads(r.read())["fleets"] == 1
        finally:
            ui.stop()


def test_fleet_instruments_record_admissions(tmp_path):
    reg = MetricsRegistry()
    with _fleet(tmp_path, registry_=reg) as fleet:
        fleet.deploy("a", _net(seed=1, hidden=8))
        fleet.deploy("b", _net(seed=2, hidden=12))
        fleet.deploy("c", _net(seed=3, hidden=20))
        # The default budget is 4x the SLO's p99 target, from submit: a
        # cold admission (placement + compile) under six test workers on a
        # shared CPU can outlast it before the first dispatch.  This test
        # counts admissions, not latency, so the budget is one that cannot
        # run out.
        for name in ("a", "b", "c", "a"):            # c evicts a; a re-admits
            fleet.output(name, _x(), deadline_ms=600_000.0)
        cold = reg.get("fleet_admissions_total", {"warm": "false"})
        warm = reg.get("fleet_admissions_total", {"warm": "true"})
        assert cold.value == 3 and warm.value == 1
        assert reg.get("fleet_evictions_total").value >= 2
        assert reg.get("fleet_models").value == 3
        assert reg.get("fleet_models_resident").value == 2
        assert reg.get("fleet_requests_total", {"model": "a"}).value == 2


# ---------------------------------------------------------------------------
# quantized re-admission (ISSUE 10)
# ---------------------------------------------------------------------------

def test_fleet_quantize_rolls_and_shrinks_residency(tmp_path):
    """`fleet.quantize(name)` rolls a QuantizedModel in as the next
    version and demotes the f32 predecessor to host — warm-pool memory
    accounting drops to the int8 bytes while outputs stay equivalent."""
    from deeplearning4j_tpu.quant import QuantizedModel
    with _fleet(tmp_path) as fleet:
        fleet.deploy("m", _net(hidden=128))
        before_out = fleet.output("m", _x())
        before_bytes = fleet.resident_bytes()
        entry = fleet.quantize("m")
        assert entry.source == "quant" and entry.version == 2
        assert isinstance(entry.model, QuantizedModel)
        assert fleet.registry.versions("m") == [1, 2]
        after_bytes = fleet.resident_bytes()
        assert after_bytes < before_bytes / 2, (before_bytes, after_bytes)
        after_out = fleet.output("m", _x())          # served by v2 (int8)
        np.testing.assert_allclose(after_out, before_out,
                                   rtol=5e-2, atol=5e-3)
        assert np.argmax(after_out, -1).tolist() == \
            np.argmax(before_out, -1).tolist()


# ---------------------------------------------------------------------------
# slow: long-tail soak
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_long_tail_soak_64_models(tmp_path):
    """64 models through a 4-model warm pool: every request answers with
    the right shape, the pool never exceeds capacity, and the second sweep
    is compile-free (pure persistent-cache deserialization)."""
    n_models, rounds = 64, 2
    with _fleet(tmp_path, max_resident=4, n_slices=8,
                max_batch=4) as fleet:
        rng = np.random.RandomState(0)
        for i in range(n_models):
            fleet.deploy(f"m{i:02d}", _net(seed=i, n_out=3 + i % 3))
        compiles_after_first = None
        for r in range(rounds):
            order = rng.permutation(n_models)
            for i in order:
                y = fleet.output(f"m{i:02d}", _x(seed=i))
                assert y.shape == (2, 3 + i % 3)
                assert len(fleet.pool.resident()) <= 4
            if r == 0:
                compiles_after_first = fleet.cache.stats["compiles"]
        # second sweep: every re-admission warm, zero fresh compiles
        assert fleet.cache.stats["compiles"] == compiles_after_first
        st = fleet.fleet_stats()
        assert len(st["models"]) == n_models
        evictions = sum(m["evictions"] for m in st["models"].values())
        assert evictions >= n_models - 4             # the tail churned
