"""Self-healing serving fleet demo (docs/robustness.md): the serving
side of fault tolerance — `serving/resilience.py` composed by
`serving.ModelFleet`.

Shows the whole failure story end to end:
 1. a 2-replica member under client flood, with an int8 standby
    registered for the degraded-mode ladder,
 2. one replica KILLED mid-flood (`utils.chaos.ReplicaChaos`) — every
    accepted request still answers: the dispatch fails over to the
    healthy replica and the victim's circuit breaker opens,
 3. the reconcile tick heals: routing-first teardown, bounded drain,
    respawn on the SAME slice through the persistent AOT cache with
    zero fresh compiles,
 4. the degraded ladder steps full -> hedges_off -> quantized under
    sustained pressure (routing flips to the int8 standby, zero
    compiles) and recovers with hysteresis, all visible on /healthz,
 5. a crc-guarded topology snapshot, then a "restarted" fleet process
    rebuilding its pre-crash shape with zero cold compiles.
"""
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np                                         # noqa: E402


def _net(seed=7, hidden=32):
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.train.updaters import Sgd
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(1e-1))
            .list([DenseLayer(n_out=hidden, activation="relu"),
                   OutputLayer(n_out=10, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(16)).build())
    return MultiLayerNetwork(conf).init()


def main():
    import tempfile

    from deeplearning4j_tpu.serving import (LatencySLO, ModelFleet,
                                            FleetPolicy)
    from deeplearning4j_tpu.utils.chaos import ReplicaChaos

    work = tempfile.mkdtemp(prefix="self-healing-fleet-")
    cache_dir = os.path.join(work, "exec-cache")
    snap_path = os.path.join(work, "topology.json")
    rng = np.random.RandomState(0)

    def build():
        return ModelFleet(
            max_resident=2, n_slices=2, max_batch=8, batch_timeout_ms=1.0,
            cache_dir=cache_dir, snapshot_path=snap_path,
            policy=FleetPolicy(drain_timeout_s=1.0))

    # 1. two replicas + an int8 standby for the ladder's quantized level
    fleet = build()
    m = fleet.deploy("ranker", _net(),
                     slo=LatencySLO(target_p99_ms=200.0, priority=10),
                     replicas=2, warm=True)
    fleet.prepare_quantized("ranker")
    print(f"deployed 'ranker' x2 replicas on slices "
          f"{[r.slice.index for r in m.group.replicas]}, "
          f"f32 v{m.serving_version} serving, "
          f"int8 v{m.quantized_version} standing by")

    # 2. kill one replica mid-flood: the client sees ZERO failures
    victim = m.group.replicas[0]
    victim_slice = victim.slice.index
    ReplicaChaos(mode="kill", at_dispatch=0).arm(victim)
    futs = [fleet.submit("ranker", rng.rand(2, 16).astype(np.float32),
                         deadline_ms=5000.0) for _ in range(32)]
    failed = sum(1 for f in futs if f.exception(timeout=30) is not None)
    print(f"replica killed mid-flood: {len(futs) - failed}/{len(futs)} "
          f"served, {failed} failed "
          f"(failovers: {fleet.instruments.failovers.value}, "
          f"victim breaker: {victim.breaker.state})")
    assert failed == 0 and victim.poisoned

    # 3. the reconcile tick respawns it — same slice, zero compiles
    rec = fleet.controller.reconcile()
    act = next(a for a in rec["actions"] if a["action"] == "respawn")
    print(f"healed: respawned on slice {act['slice']} "
          f"(cause={act['cause']}, fresh_compiles="
          f"{act['fresh_compiles']}, {act['respawn_ms']:.0f} ms)")
    assert act["slice"] == victim_slice and act["fresh_compiles"] == 0
    assert all(r.healthy for r in m.group.snapshot())

    # 4. sustained pressure walks the degraded ladder down, one named
    #    level per flip; at 'quantized' the SAME submit serves int8
    for _ in range(2 * fleet.ladder.down_after):
        fleet.ladder.observe(True)
    assert fleet.healthz()["degraded_mode"] == "quantized"
    before = fleet.cache.stats["compiles"]
    fleet.output("ranker", rng.rand(2, 16).astype(np.float32))
    print(f"ladder at '{fleet.ladder.name}': routing flipped to int8 "
          f"v{fleet._route_version(m)} "
          f"({fleet.cache.stats['compiles'] - before} fresh compiles)")
    for _ in range(2 * fleet.ladder.up_after):
        fleet.ladder.observe(False)                 # hysteresis recovery
    print(f"pressure cleared: ladder recovered to '{fleet.ladder.name}' "
          f"after {len(fleet.ladder.transitions)} audited transitions")

    # 5. snapshot, "crash", rebuild to the pre-crash topology
    fleet.save_snapshot()
    shape_before = sorted(r.slice.index for r in m.group.snapshot())
    fleet.shutdown()

    fleet2 = build()                                # the restarted process
    fleet2.deploy("ranker", _net(),
                  slo=LatencySLO(target_p99_ms=200.0, priority=10))
    report = fleet2.restore_snapshot()
    m2 = fleet2.member("ranker")
    print(f"restored from snapshot: members {report['restored']}, "
          f"replicas back on slices "
          f"{sorted(r.slice.index for r in m2.group.snapshot())} "
          f"(fresh compiles: {report['fresh_compiles']})")
    assert report["fresh_compiles"] == 0
    assert sorted(r.slice.index
                  for r in m2.group.snapshot()) == shape_before
    fleet2.output("ranker", rng.rand(2, 16).astype(np.float32))
    fleet2.shutdown()
    print("fleet drained and shut down")


if __name__ == "__main__":
    main()
