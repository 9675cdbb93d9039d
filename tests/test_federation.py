"""Cross-host fleet federation contract (ISSUE 14 acceptance): the
generation-fenced membership protocol (crash / partition / straggler
host, slow-host negative control), cross-host failover with deadline
budget carry, stale-dispatch fencing (counted, never delivered),
replicated-snapshot warm re-placement incl. corruption fallback to an
older generation, JOIN re-admission with the snapshot offered back, the
federation degraded ladder, `HostChaos` units, and the arrival-rate
forecaster.  One real multi-process run (`mh_worker_federation.py`) and
the full kill-and-partition gate (`test_federation_gate`) ride the slow
lane."""
import json
import os
import subprocess
import sys
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

from deeplearning4j_tpu.monitor.forecast import (ArrivalRateForecaster,
                                                 HoltForecaster)
from deeplearning4j_tpu.monitor.registry import MetricsRegistry
from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.serving import (DeadlineExceededError,
                                        FederationPolicy, FederationRouter,
                                        HostAgent, HostLostError,
                                        LatencySLO, ModelFleet,
                                        RejectedError, SnapshotCorruptError,
                                        select_snapshot)
from deeplearning4j_tpu.serving.federation import _rendezvous
from deeplearning4j_tpu.train.updaters import Sgd
from deeplearning4j_tpu.utils.chaos import HostChaos

HERE = os.path.dirname(os.path.abspath(__file__))


def _net(seed=0, n_in=8, n_out=3, hidden=16):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(1e-1))
            .list([DenseLayer(n_out=hidden, activation="relu"),
                   OutputLayer(n_out=n_out, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _x(n=2, n_in=8, seed=0):
    return np.random.RandomState(seed).randn(n, n_in).astype(np.float32)


def _policy(**kw):
    kw.setdefault("heartbeat_interval_s", 0.05)
    kw.setdefault("failure_deadline_s", 0.4)
    kw.setdefault("straggler_deadline_s", 2.0)
    kw.setdefault("ghost_linger_s", 3.0)
    return FederationPolicy(**kw)


def _host_fleet(tmp_path, host_id, models=(("m", 5),)):
    d = tmp_path / host_id
    d.mkdir(exist_ok=True)
    fleet = ModelFleet(max_resident=2, n_slices=2, max_batch=4,
                       batch_timeout_ms=1.0,
                       cache_dir=str(tmp_path / "exec-cache"),
                       snapshot_path=str(d / "snapshot.json"),
                       host_id=host_id)
    for name, prio in models:
        fleet.deploy(name, _net(seed=hash(name) % 97),
                     slo=LatencySLO(target_p99_ms=2000.0, priority=prio),
                     warm=True)
    return fleet


@contextmanager
def _federation(tmp_path, hosts=("h1", "h2"), policy=None,
                models=(("m", 5),), replicate=True, reg=None):
    """Router + one in-process HostAgent-wrapped fleet per host id; all
    hosts share one AOT cache dir (the warm re-placement substrate)."""
    policy = policy if policy is not None else _policy()
    reg = reg if reg is not None else MetricsRegistry()
    router = FederationRouter(policy,
                              replicas_dir=str(tmp_path / "router-replicas"),
                              registry_=reg)
    fleets, agents = {}, {}
    try:
        port = router.start(0)
        for h in hosts:
            fleets[h] = _host_fleet(tmp_path, h, models=models)
            agents[h] = HostAgent(
                h, fleets[h], ("127.0.0.1", port), policy=policy,
                replicas_dir=str(tmp_path / h / "replicas"),
                registry_=reg).start()
        if replicate:
            for h in hosts:
                fleets[h].save_snapshot()
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                if set(router.federation_stats()["replicas"]) >= set(hosts):
                    break
                time.sleep(0.02)
            else:
                raise RuntimeError("snapshot replication never completed")
        yield router, fleets, agents
    finally:
        for a in agents.values():
            try:
                a.close()
            except Exception:
                pass
        router.shutdown()
        for f in fleets.values():
            try:
                f.shutdown()
            except Exception:
                pass


def _wait(cond, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def _events(router, kind):
    return [e for e in list(router.events) if e["event"] == kind]


# ---------------------------------------------------------------------------
# Membership: join, serve, introspection
# ---------------------------------------------------------------------------

def test_membership_join_and_serve(tmp_path):
    with _federation(tmp_path) as (router, fleets, agents):
        assert router.hosts() == ["h1", "h2"]
        assert router.generation == 2            # one bump per admission
        for a in agents.values():
            assert a.generation == router.generation \
                or a.generation == 1             # h1 joined at gen 1
        y = router.output("m", _x(), deadline_ms=30_000.0, timeout=60)
        assert y.shape == (2, 3)
        stats = router.federation_stats()
        assert set(stats["hosts"]) == {"h1", "h2"}
        assert stats["hosts"]["h1"]["models"] == ["m"]
        hz = router.healthz()
        assert hz["ok"] and hz["hosts"] == 2
        assert hz["degraded_mode"] == "full"
        # instruments: membership gauges track the live view
        assert router.instruments.hosts.value == 2
        assert router.instruments.generation.value == 2


def test_unknown_model_and_shutdown_reject(tmp_path):
    with _federation(tmp_path, replicate=False) as (router, _, _a):
        # an unknown model still routes (hosts may admit lazily) but the
        # host classifies it as a CLIENT error — surfaced as ValueError,
        # never a failover storm
        with pytest.raises(ValueError):
            router.output("ghost-model", _x(), deadline_ms=5_000.0,
                          timeout=60)
        saved = router
    with pytest.raises(RejectedError):
        saved.submit("m", _x())                  # shut-down router rejects


# ---------------------------------------------------------------------------
# Failure taxonomy: crash / partition / straggler / slow control
# ---------------------------------------------------------------------------

def test_crash_eviction_failover_and_warm_replacement(tmp_path):
    with _federation(tmp_path) as (router, fleets, agents):
        HostChaos(mode="kill").fire(agents["h1"])
        _wait(lambda: _events(router, "evict"), msg="crash eviction")
        ev = _events(router, "evict")[0]
        assert ev["host"] == "h1" and ev["cause"] == "crash"
        assert router.hosts() == ["h2"]
        # h1's models are warm-re-placed on the survivor from the
        # replicated snapshot: zero fresh compiles (shared AOT cache)
        _wait(lambda: _events(router, "replaced"), msg="re-placement")
        rep = _events(router, "replaced")[0]
        assert rep["host"] == "h1" and rep["on"] == "h2"
        assert rep["warm"] and rep["fresh_compiles"] == 0
        assert router.output("m", _x(), deadline_ms=30_000.0,
                             timeout=60).shape == (2, 3)
        assert router.instruments.evictions("crash").value == 1
        assert router.instruments._replacements[True].value == 1


def test_partition_eviction_stale_fence_and_rejoin(tmp_path):
    with _federation(tmp_path) as (router, fleets, agents):
        victim = _rendezvous(["h1", "h2"], "m")  # the host serving "m"
        agent = agents[victim]
        gen0 = router.generation
        # an in-flight request is mid-dispatch on the victim when the
        # partition hits: its reply is deferred, the router must fail it
        # over to the survivor — and fence the deferred reply on heal
        chaos = HostChaos(mode="partition", at_dispatch=0, duration_s=1.2)
        chaos.arm(agent)
        fut = router.submit("m", _x(), deadline_ms=30_000.0)
        assert fut.result(timeout=60).shape == (2, 3)   # settled via failover
        _wait(lambda: _events(router, "evict"), msg="partition eviction")
        ev = _events(router, "evict")[0]
        assert ev["host"] == victim and ev["cause"] == "partition"
        # detection is heartbeat-driven: bounded by the failure deadline
        # (+ generous scheduler slack)
        assert ev["detection_ms"] <= 5_000.0
        # heal: the deferred stale reply arrives at the OLD generation —
        # fenced and counted, never delivered
        _wait(lambda: router.instruments.stale_dispatch.value >= 1,
              msg="stale reply fenced")
        assert _events(router, "stale-fenced")
        # the healed host auto-rejoins at a bumped generation
        _wait(lambda: victim in router.hosts() and agent.rejoins >= 1,
              msg="auto-rejoin")
        assert router.generation > gen0 + 1      # evict bump + rejoin bump
        _wait(lambda: agent.generation == router.generation,
              msg="agent caught up")
        assert router.output("m", _x(), deadline_ms=30_000.0,
                             timeout=60).shape == (2, 3)
        chaos.restore()


def test_straggler_eviction_via_hang(tmp_path):
    policy = _policy(straggler_deadline_s=0.6, failure_deadline_s=5.0)
    with _federation(tmp_path, policy=policy) as (router, fleets, agents):
        victim = _rendezvous(["h1", "h2"], "m")
        chaos = HostChaos(mode="hang", at_dispatch=0, duration_s=3.0)
        chaos.arm(agents[victim])
        # heartbeats keep flowing — only the straggler detector can see
        # this fault; the stuck request must still settle via failover
        fut = router.submit("m", _x(), deadline_ms=30_000.0)
        assert fut.result(timeout=60).shape == (2, 3)
        _wait(lambda: _events(router, "evict"), msg="straggler eviction")
        ev = _events(router, "evict")[0]
        assert ev["host"] == victim and ev["cause"] == "straggler"
        chaos.restore()


def test_slow_host_is_not_evicted(tmp_path):
    """Negative control: a uniformly slow host stays under every failure
    deadline — chaos fires, nothing is evicted."""
    with _federation(tmp_path) as (router, fleets, agents):
        chaos = HostChaos(mode="slow", at_dispatch=0, delay_s=0.03)
        chaos.arm(agents["h1"])
        chaos2 = HostChaos(mode="slow", at_dispatch=0, delay_s=0.03)
        chaos2.arm(agents["h2"])
        for i in range(8):
            assert router.output("m", _x(seed=i), deadline_ms=30_000.0,
                                 timeout=60).shape == (2, 3)
        assert chaos.fired or chaos2.fired
        time.sleep(0.6)                          # several failure deadlines
        assert router.hosts() == ["h1", "h2"]
        assert not _events(router, "evict")
        chaos.restore()
        chaos2.restore()


# ---------------------------------------------------------------------------
# Cross-host failover: budget carry, exhaustion, HostLostError
# ---------------------------------------------------------------------------

def test_failover_carries_remaining_deadline_budget(tmp_path):
    with _federation(tmp_path) as (router, fleets, agents):
        victim = _rendezvous(["h1", "h2"], "m")
        survivor = "h2" if victim == "h1" else "h1"
        seen = []
        orig = fleets[survivor].submit

        def spy(name, x, **kw):
            seen.append(kw.get("deadline_ms"))
            return orig(name, x, **kw)

        fleets[survivor].submit = spy
        # a PARTITION (not a crash): the victim goes silent but its
        # socket stays connected, so the dispatch genuinely lands on it
        # and only the heartbeat deadline can trigger the failover
        agents[victim].partition(True)
        t0 = time.monotonic()
        fut = router.submit("m", _x(), priority=5, deadline_ms=8_000.0)
        assert fut.result(timeout=60).shape == (2, 3)
        assert router.instruments.cross_host_failovers.value >= 1
        # the re-dispatch carried the REMAINING budget, not a fresh one
        assert seen and seen[-1] is not None
        elapsed_ms = (time.monotonic() - t0) * 1000.0
        assert seen[-1] < 8_000.0
        assert seen[-1] >= 8_000.0 - elapsed_ms - 1_000.0
        fleets[survivor].submit = orig
        agents[victim].partition(False)


def test_failover_budget_exhaustion_is_deadline_exceeded(tmp_path):
    with _federation(tmp_path) as (router, fleets, agents):
        victim = _rendezvous(["h1", "h2"], "m")
        agents[victim].partition(True)
        # a budget far smaller than the failure deadline: by the time the
        # silence is detected and the orphan fails over, it is exhausted
        fut = router.submit("m", _x(), deadline_ms=30.0)
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=60)
        agents[victim].partition(False)


def test_failover_cap_is_host_lost(tmp_path):
    policy = _policy(max_failovers=0)
    with _federation(tmp_path, policy=policy) as (router, fleets, agents):
        victim = _rendezvous(["h1", "h2"], "m")
        agents[victim].partition(True)
        fut = router.submit("m", _x(), deadline_ms=30_000.0)
        with pytest.raises(HostLostError):
            fut.result(timeout=60)
        agents[victim].partition(False)


# ---------------------------------------------------------------------------
# Replicated snapshots: on-disk copies, corruption fallback, restore paths
# ---------------------------------------------------------------------------

def test_snapshot_replication_router_and_peers(tmp_path):
    with _federation(tmp_path) as (router, fleets, agents):
        stats = router.federation_stats()
        assert set(stats["replicas"]) == {"h1", "h2"}
        router_files = os.listdir(str(tmp_path / "router-replicas"))
        assert any(f.startswith("h1-gen") for f in router_files)
        assert any(f.startswith("h2-gen") for f in router_files)
        # peer forwarding: each host also holds its PEER's copy, so the
        # fleet survives losing the router and a host together
        _wait(lambda: os.path.isdir(str(tmp_path / "h2" / "replicas"))
              and any(f.startswith("h1-gen") for f in
                      os.listdir(str(tmp_path / "h2" / "replicas"))),
              msg="peer replica of h1 on h2")


def test_select_snapshot_prefers_highest_intact_generation(tmp_path):
    fleet = _host_fleet(tmp_path, "hA")
    try:
        snap = fleet.snapshotter
        copies = []
        for gen in (1, 2, 3):
            snap.generation = gen
            p = snap.save()
            dst = str(tmp_path / f"copy-gen{gen}.json")
            with open(p) as f, open(dst, "w") as g:
                g.write(f.read())
            copies.append(dst)
        # newest copy is torn mid-write: fall back to generation 2
        with open(copies[2], "w") as f:
            f.write('{"format": 1, "fleet": {"trunc')
        path, payload = select_snapshot(copies)
        assert path == copies[1]
        assert payload["generation"] == 2
        assert payload["host_id"] == "hA"
        # every copy rotten -> explicit SnapshotCorruptError
        for p in copies:
            with open(p, "w") as f:
                f.write("garbage")
        with pytest.raises(SnapshotCorruptError):
            select_snapshot(copies)
    finally:
        fleet.shutdown()


def test_restore_snapshot_from_replicated_paths(tmp_path):
    fleet = _host_fleet(tmp_path, "hA")
    fleet.output("m", _x(), deadline_ms=30_000.0, timeout=60)
    fleet.snapshotter.generation = 4
    path = fleet.save_snapshot()
    fleet.shutdown()
    fleet2 = _host_fleet(tmp_path, "hB")
    try:
        restore = fleet2.restore_snapshot(paths=[path])
        assert restore["fresh_compiles"] == 0    # shared AOT cache: warm
        assert fleet2.pool.resident_names() == ["m"]
    finally:
        fleet2.shutdown()


def test_snapshot_header_stamp_and_age_clamped_under_skew(tmp_path):
    fleet = _host_fleet(tmp_path, "hA")
    try:
        snap = fleet.snapshotter
        assert snap.host_id == "hA"
        snap.generation = 7
        p = snap.save()
        with open(p) as f:
            payload = json.load(f)
        assert payload["host_id"] == "hA"
        assert payload["generation"] == 7
        assert snap.age_s() >= 0.0
        # a replica stamped by a skew-AHEAD clock (saved_at in the
        # future): a fresh snapshotter seeds its age from the file and
        # must clamp at zero, never report negative
        payload["saved_at"] = time.time() + 3_600.0   # header not crc'd
        with open(p, "w") as f:
            json.dump(payload, f)
        from deeplearning4j_tpu.serving.resilience import FleetSnapshotter
        snap2 = FleetSnapshotter(fleet, p, host_id="hA")
        assert snap2.age_s() == 0.0
    finally:
        fleet.shutdown()


# ---------------------------------------------------------------------------
# JOIN re-admission: relaunched host, snapshot offered back, parked joiners
# ---------------------------------------------------------------------------

def test_relaunched_host_readmitted_with_snapshot(tmp_path):
    with _federation(tmp_path) as (router, fleets, agents):
        HostChaos(mode="kill").fire(agents["h1"])
        _wait(lambda: _events(router, "replaced"), msg="re-placement")
        gen0 = router.generation
        # relaunch: same host id, a FRESH fleet process (cold members,
        # same shared cache).  WELCOME offers the replicated snapshot
        # back, so the relaunched host re-admits warm.
        fleet_b = ModelFleet(max_resident=2, n_slices=2, max_batch=4,
                             batch_timeout_ms=1.0,
                             cache_dir=str(tmp_path / "exec-cache"),
                             snapshot_path=str(tmp_path / "h1b.json"),
                             host_id="h1")
        fleet_b.deploy("m", _net(seed=hash("m") % 97),
                       slo=LatencySLO(target_p99_ms=2000.0, priority=5))
        agent_b = HostAgent("h1", fleet_b, ("127.0.0.1", router.port),
                            policy=router.policy)
        try:
            agent_b.start(timeout=15.0)
            assert router.generation > gen0      # re-admitted at a bump
            assert agent_b.generation == router.generation
            join = [e for e in _events(router, "join")
                    if e["host"] == "h1" and e.get("rejoin")]
            assert join, "rejoin JOIN not recorded"
            # the WELCOME snapshot restored its preferred placements warm
            assert agent_b.restored is not None
            assert agent_b.restored["fresh_compiles"] == 0
            assert fleet_b.pool.resident_names() == ["m"]
            assert sorted(router.hosts()) == ["h1", "h2"]
        finally:
            agent_b.close()
            fleet_b.shutdown()


def test_auto_admit_false_parks_joiners(tmp_path):
    policy = _policy(auto_admit=False)
    reg = MetricsRegistry()
    router = FederationRouter(policy, registry_=reg)
    fleet = _host_fleet(tmp_path, "h1")
    agent = HostAgent("h1", fleet, ("127.0.0.1", 0), policy=policy,
                      registry_=reg)
    try:
        agent.address = ("127.0.0.1", router.start(0))
        errors = []

        def run():
            try:
                agent.start(timeout=30.0)
            except Exception as e:               # pragma: no cover
                errors.append(e)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        _wait(lambda: router._joiners, msg="parked joiner")
        assert router.hosts() == []              # parked, NOT admitted
        assert router.admit_joiners() == 1
        t.join(timeout=30.0)
        assert not errors
        assert router.hosts() == ["h1"]
        assert agent.generation == router.generation
    finally:
        agent.close()
        router.shutdown()
        fleet.shutdown()


# ---------------------------------------------------------------------------
# Federation degraded ladder
# ---------------------------------------------------------------------------

def test_federation_ladder_sheds_low_priority_under_host_pressure(tmp_path):
    policy = _policy(ladder_down_after=2, ladder_up_after=2)
    models = (("hi", 10), ("lo", 0))
    with _federation(tmp_path, policy=policy, models=models,
                     replicate=False) as (router, fleets, agents):
        # no replicated snapshot -> the lost host CANNOT be re-placed;
        # capacity stays short and the ladder walks down to shed_floor
        agents["h1"].crash()
        _wait(lambda: router.ladder.shed_floor(), timeout=15.0,
              msg="ladder reached shed floor")
        with pytest.raises(RejectedError):
            router.submit("lo", _x(), priority=0, deadline_ms=5_000.0)
        y = router.output("hi", _x(), priority=10, deadline_ms=30_000.0,
                          timeout=60)
        assert y.shape == (2, 3)                 # top class still served
        skipped = _events(router, "replace-skipped")
        assert skipped and skipped[0]["reason"] == "no snapshot"


# ---------------------------------------------------------------------------
# HostChaos units
# ---------------------------------------------------------------------------

def test_host_chaos_validates_mode():
    with pytest.raises(ValueError):
        HostChaos(mode="meteor")


def test_host_chaos_marker_is_one_shot(tmp_path):
    marker = str(tmp_path / "fired")

    class StubAgent:
        def __init__(self):
            self.slowed = []

        def slow(self, d):
            self.slowed.append(d)

    stub = StubAgent()
    chaos = HostChaos(mode="slow", delay_s=0.01, marker=marker)
    assert chaos.armed()
    chaos.fire(stub)
    assert stub.slowed == [0.01]
    assert os.path.exists(marker)
    with open(marker) as f:
        assert f.read().startswith("slow@")
    # a relaunched process re-arming against the same marker stays inert
    chaos2 = HostChaos(mode="slow", delay_s=0.01, marker=marker)
    assert not chaos2.armed()


def test_host_chaos_arm_wraps_and_restore_unwraps(tmp_path):
    class StubAgent:
        def __init__(self):
            self.requests = []

        def _on_request(self, gen, msg, raw):
            self.requests.append(msg)
            return "handled"

        def slow(self, d):
            self.delay = d

    stub = StubAgent()
    chaos = HostChaos(mode="slow", at_dispatch=1, delay_s=0.02)
    chaos.arm(stub)
    with pytest.raises(RuntimeError):
        chaos.arm(stub)                          # double-arm refused
    assert stub._on_request(3, {"id": 1}, b"") == "handled"
    assert not chaos.fired                       # at_dispatch not reached
    assert stub._on_request(3, {"id": 2}, b"") == "handled"
    assert chaos.fired and stub.delay == 0.02    # fired AND passed through
    chaos.restore()
    assert stub.delay == 0.0                     # slow-mode delay cleared
    assert len(stub.requests) == 2


# ---------------------------------------------------------------------------
# Arrival-rate forecaster
# ---------------------------------------------------------------------------

def test_holt_forecaster_ewma_and_trend():
    with pytest.raises(ValueError):
        HoltForecaster(alpha=0.0)
    with pytest.raises(ValueError):
        HoltForecaster(beta=1.5)
    # beta=0: plain EWMA, trend pinned at zero
    ewma = HoltForecaster(alpha=0.5, beta=0.0)
    assert ewma.forecast() == 0.0                # no data yet
    ewma.observe(0.0)
    ewma.observe(10.0)
    assert ewma.forecast() == pytest.approx(5.0)
    assert ewma.trend == 0.0
    # a steady upward series: Holt extrapolates ABOVE the last level
    holt = HoltForecaster(alpha=0.5, beta=0.3)
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        holt.observe(v)
    assert holt.forecast(1.0) > holt.level
    assert holt.forecast(5.0) > holt.forecast(1.0)
    # a declining series extrapolates negative — floored at zero
    down = HoltForecaster(alpha=0.5, beta=0.5)
    for v in (10.0, 8.0, 6.0, 4.0, 2.0, 0.0):
        down.observe(v)
    assert down.trend < 0.0
    assert down.forecast(5.0) == 0.0


def test_arrival_rate_forecaster_ticks_from_registry_counters():
    reg = MetricsRegistry()
    c_a = reg.counter("fleet_requests_total", labels={"model": "a"})
    fc = ArrivalRateForecaster(registry_=reg, alpha=1.0, beta=0.0,
                               horizon_s=10.0)
    c_a.inc(100)                                 # historical traffic
    assert fc.tick(now=100.0) == {}              # first sighting: baseline
    c_a.inc(20)                                  # 20 req in 2 s -> 10 req/s
    out = fc.tick(now=102.0)
    assert out["a"] == pytest.approx(10.0)
    # published as a gauge the scrape endpoint exports
    children = reg.children("fleet_arrival_forecast")
    assert [(lbl["model"], g.value) for lbl, g in children] \
        == [("a", pytest.approx(10.0))]
    assert fc.forecasts() == {"a": pytest.approx(10.0)}
    # a model appearing later baselines without a burst misread
    c_b = reg.counter("fleet_requests_total", labels={"model": "b"})
    c_b.inc(1_000_000)
    out = fc.tick(now=104.0)
    assert "b" not in out                        # baselined, not a burst
    c_b.inc(10)
    out = fc.tick(now=105.0)
    assert out["b"] == pytest.approx(10.0)
    # idle model decays toward zero, never below
    out = fc.tick(now=106.0)
    assert out["a"] == 0.0


# ---------------------------------------------------------------------------
# Multi-process: a real host process hard-killed mid-flood (slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_multiprocess_host_kill_warm_replacement(tmp_path):
    """Three REAL host processes join an in-process router; the one that
    owns model "m" hard-kills itself (`os._exit(9)`) mid-flood.  The
    router must evict it (cause crash), settle every accepted request,
    warm-re-place its model on a survivor, and the survivors must report
    the bumped generation on shutdown."""
    policy = FederationPolicy(heartbeat_interval_s=0.1,
                              failure_deadline_s=0.8,
                              straggler_deadline_s=5.0)
    reg = MetricsRegistry()
    router = FederationRouter(
        policy, replicas_dir=str(tmp_path / "router-replicas"),
        registry_=reg)
    port = router.start(0)
    hosts = ["h1", "h2", "h3"]
    victim = _rendezvous(hosts, "m")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(HERE)
    procs = {}
    try:
        for h in hosts:
            kill_after = "2" if h == victim else "-1"
            procs[h] = subprocess.Popen(
                [sys.executable,
                 os.path.join(HERE, "mh_worker_federation.py"),
                 h, str(port), str(tmp_path), kill_after],
                cwd=root, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)
        _wait(lambda: all(
            os.path.exists(str(tmp_path / f"{h}.ready")) for h in hosts),
            timeout=180.0, msg="all hosts ready")
        _wait(lambda: set(router.federation_stats()["replicas"])
              >= set(hosts), timeout=30.0, msg="snapshot replication")
        served = failed = 0
        for i in range(200):
            try:
                fut = router.submit("m", _x(seed=i), priority=5,
                                    deadline_ms=20_000.0)
            except RejectedError:
                continue
            if fut.exception(timeout=60) is None:
                served += 1
            else:
                failed += 1
            if _events(router, "replaced"):
                break
            time.sleep(0.02)
        assert failed == 0                       # zero lost accepted
        assert served > 0
        ev = _events(router, "evict")
        assert ev and ev[0]["host"] == victim and ev[0]["cause"] == "crash"
        rep = _events(router, "replaced")
        assert rep and rep[0]["host"] == victim
        assert rep[0]["warm"] and rep[0]["fresh_compiles"] == 0
        assert os.path.exists(str(tmp_path / f"{victim}.killed"))
        # wind down the survivors; they report the bumped generation
        # (as of BEFORE their own graceful leaves bump it further)
        gen_at_stop = router.generation
        assert gen_at_stop >= len(hosts) + 1     # 3 joins + >=1 eviction
        with open(str(tmp_path / "stop"), "w") as f:
            f.write("stop")
        survivors = [h for h in hosts if h != victim]
        for h in survivors:
            assert procs[h].wait(timeout=120) == 0, \
                procs[h].stdout.read()[-2000:]
        assert procs[victim].wait(timeout=120) == 9   # os._exit(9)
        for h in survivors:
            with open(str(tmp_path / f"{h}.done")) as f:
                done = json.load(f)
            # at least the post-eviction generation; a peer's own leave
            # REFORM may already have bumped it by the time done is cut
            assert done["generation"] >= gen_at_stop
            assert not done["evicted"]
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        router.shutdown()


# ---------------------------------------------------------------------------
# The federation gate (slow lane): a host kill and a partition under flood,
# then the killed host relaunched
# ---------------------------------------------------------------------------

def kill_partition_and_relaunch(work_dir):
    """Cross-host fleet federation under injected host failure.

    Three in-process hosts, each a full `ModelFleet` (hi + lo members,
    all sharing one persistent AOT cache dir) behind a `HostAgent`,
    fronted by one `FederationRouter`.  Hi/lo client threads flood the
    router; mid-flood `HostChaos` KILLS the hi-affinity host (EOF ->
    cause ``crash``) and PARTITIONS a second host for a window (silence
    -> cause ``partition``; the replies it flushes on heal are
    generation-fenced and counted).  The router must evict both, fail
    over every orphaned in-flight request inside its deadline budget,
    and warm-re-place each dead host's models on a survivor from the
    replicated snapshot.  The partitioned host auto-rejoins on heal; the
    killed host is relaunched as a NEW agent with the same host id and
    must be re-admitted at a bumped generation.

    Returns what the gate asserts on: lost accepted requests, malformed
    replies delivered, hi p99 against its SLO, the evictions and
    re-placements, the fenced stale dispatches, the rejoin and relaunch
    generations, and the final membership."""
    from concurrent.futures import ThreadPoolExecutor

    n_in = 16
    n_out = 4
    hi_slo_ms = 2500.0
    deadline_ms = 8000.0
    flood = 40                              # requests per client thread
    clients = 2                             # threads per priority class
    host_ids = ["h1", "h2", "h3"]

    cache_dir = os.path.join(work_dir, "exec-cache")   # SHARED across hosts
    policy = FederationPolicy(heartbeat_interval_s=0.1,
                              failure_deadline_s=0.8,
                              straggler_deadline_s=6.0,
                              max_failovers=3, affinity_slack=4,
                              ghost_linger_s=8.0)

    def build_fleet(host_id):
        d = os.path.join(work_dir, host_id)
        os.makedirs(d, exist_ok=True)
        fleet = ModelFleet(max_resident=2, n_slices=4, max_batch=8,
                           batch_timeout_ms=1.0, cache_dir=cache_dir,
                           snapshot_path=os.path.join(d, "snapshot.json"),
                           snapshot_interval_s=0.2, host_id=host_id,
                           observe_every=4)
        fleet.deploy("hi", _net(1001, n_in=n_in, n_out=n_out, hidden=32),
                     slo=LatencySLO(target_p99_ms=hi_slo_ms, priority=10),
                     warm=True)
        fleet.deploy("lo", _net(1002, n_in=n_in, n_out=n_out, hidden=32),
                     slo=LatencySLO(target_p99_ms=1000.0, priority=0),
                     warm=True)
        return fleet

    router = FederationRouter(
        policy, replicas_dir=os.path.join(work_dir, "router-replicas"))
    os.makedirs(router.replicas_dir, exist_ok=True)
    fleets, agents = {}, {}
    try:
        port = router.start(0)
        for h in host_ids:
            fleets[h] = build_fleet(h)
            agents[h] = HostAgent(
                h, fleets[h], ("127.0.0.1", port), policy=policy,
                replicas_dir=os.path.join(work_dir, h, "replicas")).start()
        x0 = np.random.RandomState(0).rand(2, n_in).astype(np.float32)
        for name in ("hi", "lo"):           # warm the cross-host path
            router.output(name, x0, deadline_ms=60_000.0, timeout=120)
        for h in host_ids:                  # replicate a snapshot of each
            fleets[h].save_snapshot()       # host's topology to the router
        _wait(lambda: set(router.federation_stats()["replicas"])
              >= set(host_ids), timeout=10.0, msg="snapshot replication")

        # the hi-affinity host takes the kill (it is guaranteed traffic);
        # the lo-affinity host among the SURVIVORS takes the partition,
        # so its post-kill lo dispatches trip the chaos wrapper
        kill_host = _rendezvous(host_ids, "hi")
        part_host = _rendezvous([h for h in host_ids if h != kill_host],
                                "lo")
        kill = HostChaos(mode="kill", at_dispatch=0)
        part = HostChaos(mode="partition", at_dispatch=0, duration_s=1.5)
        armed = {"kill": threading.Event(), "part": threading.Event()}
        progress = threading.Lock()
        submitted = [0]
        total = flood * clients * 2

        def client(spec):
            name, prio, seed = spec
            rs = np.random.RandomState(seed)
            failed = bad = 0
            lat = []
            for _ in range(flood):
                with progress:
                    submitted[0] += 1
                    n = submitted[0]
                if n == total // 4 and not kill.fired:
                    kill.arm(agents[kill_host])
                    armed["kill"].set()
                if n == total // 2 and not part.fired:
                    part.arm(agents[part_host])
                    armed["part"].set()
                x = rs.rand(2, n_in).astype(np.float32)
                t0 = time.perf_counter()
                try:
                    f = router.submit(name, x, priority=prio,
                                      deadline_ms=deadline_ms)
                except RejectedError:
                    continue
                # accepted: this future MUST resolve — a killed or
                # partitioned host has to fail over, not lose it
                if f.exception(timeout=60) is not None:
                    failed += 1
                elif f.result().shape != (2, n_out):
                    bad += 1        # a stale reply delivered would land here
                else:
                    lat.append((time.perf_counter() - t0) * 1000.0)
            return name, failed, bad, lat

        specs = [("hi", 10, 100 + i) for i in range(clients)] \
            + [("lo", 0, 200 + i) for i in range(clients)]
        with ThreadPoolExecutor(len(specs)) as ex:
            results = list(ex.map(client, specs))
        assert armed["kill"].wait(10) and armed["part"].wait(10), \
            "chaos never armed"

        # ---- sustain + recovery: the flood can outrun the failure
        # detector, so keep traffic flowing (still SLO-gated: sustain
        # hi latencies count toward p99) until BOTH faults have fired,
        # both evictions are replaced, and the partitioned host is back
        sustain_failed = 0
        hi_lat = []
        rs = np.random.RandomState(999)
        recover_deadline = time.monotonic() + 45.0
        while time.monotonic() < recover_deadline:
            replaced = {e["host"] for e in _events(router, "replaced")}
            if kill.fired and part.fired \
                    and {kill_host, part_host} <= replaced \
                    and part_host in router.hosts() \
                    and agents[part_host].generation == router.generation:
                break
            for name, prio in (("hi", 10), ("lo", 0)):
                x = rs.rand(2, n_in).astype(np.float32)
                ts = time.perf_counter()
                try:
                    f = router.submit(name, x, priority=prio,
                                      deadline_ms=deadline_ms)
                except RejectedError:
                    continue
                if f.exception(timeout=60) is not None:
                    sustain_failed += 1
                elif name == "hi":
                    hi_lat.append((time.perf_counter() - ts) * 1000.0)
            time.sleep(0.02)
        else:
            raise RuntimeError(
                "federation never recovered: "
                f"kill.fired={kill.fired} part.fired={part.fired} "
                f"events={list(router.events)[-12:]}")
        evictions = _events(router, "evict")
        replacements = _events(router, "replaced")
        stale_fenced = int(router.instruments.stale_dispatch.value)

        # ---- relaunch the killed host: same id, NEW agent, bumped gen ----
        gen_before = router.generation
        relaunched = HostAgent(
            kill_host, fleets[kill_host], ("127.0.0.1", port),
            policy=policy,
            replicas_dir=os.path.join(work_dir, kill_host, "replicas"))
        relaunched.start(timeout=15.0)
        old_agent, agents[kill_host] = agents[kill_host], relaunched
        old_agent.close()
        for name in ("hi", "lo"):           # full membership serves again
            router.output(name, x0, deadline_ms=60_000.0, timeout=120)

        for name, _, _, lat in results:
            if name == "hi":
                hi_lat.extend(lat)
        hi_lat.sort()
        hi_p99 = hi_lat[min(len(hi_lat) - 1,
                            int(len(hi_lat) * 0.99))] if hi_lat else -1.0

        return {
            "hi_slo_ms": hi_slo_ms,
            "hi_p99_ms": hi_p99,
            "bad_replies": sum(r[2] for r in results),
            "lost_accepted": sum(r[1] for r in results) + sustain_failed,
            "kill_host": kill_host,
            "part_host": part_host,
            "evictions": evictions,
            "replacements": replacements,
            "stale_fenced": stale_fenced,
            "part_host_rejoins": agents[part_host].rejoins,
            "relaunch_generation_before": gen_before,
            "relaunch_generation_after": router.generation,
            "relaunch_agent_generation": relaunched.generation,
            "final_hosts": router.hosts(),
            "final_healthz": router.healthz(),
        }
    finally:
        for a in agents.values():
            try:
                a.close()
            except Exception:
                pass
        router.shutdown()
        for f in fleets.values():
            try:
                f.shutdown()
            except Exception:
                pass


@pytest.mark.slow
def test_federation_gate(tmp_path):
    """Zero lost accepted requests through a host kill + a host partition,
    zero stale replies delivered to clients (fenced AND counted instead),
    hi-priority p99 within SLO, both evictions warm-re-placed within
    bound, the partitioned host auto-rejoined, the killed host re-admitted
    at a bumped generation."""
    r = kill_partition_and_relaunch(str(tmp_path))
    causes = {e["cause"] for e in r["evictions"]}
    assert r["lost_accepted"] == 0
    assert r["bad_replies"] == 0
    assert r["hi_p99_ms"] <= r["hi_slo_ms"]
    assert {"crash", "partition"} <= causes
    assert {r["kill_host"], r["part_host"]} <= {
        p["host"] for p in r["replacements"]}
    assert all(p["warm"] and p["fresh_compiles"] == 0
               for p in r["replacements"])
    assert all(e["detection_ms"] <= 5_000.0 for e in r["evictions"])
    assert all(p["replace_ms"] <= 10_000.0 for p in r["replacements"])
    assert r["stale_fenced"] >= 1
    assert r["part_host_rejoins"] >= 1
    assert r["relaunch_generation_after"] > r["relaunch_generation_before"]
    assert r["relaunch_agent_generation"] == r["relaunch_generation_after"]
    assert sorted(r["final_hosts"]) == ["h1", "h2", "h3"]
    assert r["final_healthz"]["ok"]
