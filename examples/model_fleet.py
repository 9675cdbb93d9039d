"""Multi-model serving fleet demo (docs/serving.md): a long tail of
models through `serving.ModelFleet` — SLO-aware routing, warm-pool LRU
eviction backed by the persistent AOT executable cache, and shed ordering
under overload.

Shows the fleet surface end to end:
 1. deploy 8 models into a 3-slot warm pool — each with a
    `LatencySLO(target_p99_ms, priority)`,
 2. sweep the long tail twice: the first pass pays the compiles, the
    second re-admits every evicted model from the persistent cache with
    ZERO fresh compiles,
 3. force sustained SLO pressure on the high-priority model and watch the
    router shed low-priority traffic first,
 4. the `/fleet` topology endpoint and fleet-aware `/readyz`.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np                                         # noqa: E402


def _net(seed, hidden):
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
                                            RejectedError)

    cache_dir = tempfile.mkdtemp(prefix="fleet-exec-cache-")
    fleet = ModelFleet(max_resident=3, max_batch=8, batch_timeout_ms=2.0,
                       cache_dir=cache_dir)

    # 1. a long tail of low-priority models plus one high-priority ranker
    for i in range(7):
        fleet.deploy(f"tail-{i}", _net(seed=i, hidden=24 + 8 * i),
                     slo=LatencySLO(target_p99_ms=200.0, priority=0))
    ranker = fleet.deploy("ranker", _net(seed=99, hidden=64),
                          slo=LatencySLO(target_p99_ms=20.0, priority=10),
                          warm=True)
    print(f"deployed 8 models into a 3-slot warm pool "
          f"(resident: {fleet.pool.resident_names()})")

    # 2. sweep the tail twice — second pass is pure cache deserialization
    rng = np.random.RandomState(0)
    for sweep in range(2):
        before = fleet.cache.stats["compiles"]
        for i in rng.permutation(7):
            x = rng.rand(2, 16).astype(np.float32)
            assert fleet.output(f"tail-{i}", x).shape == (2, 10)
        fresh = fleet.cache.stats["compiles"] - before
        print(f"sweep {sweep}: {fresh} fresh compiles, "
              f"{fleet.cache.stats['disk_hits']} cumulative disk hits, "
              f"resident now {fleet.pool.resident_names()}")
    assert fleet.member("tail-0").last_admission_fresh_compiles == 0

    # 3. sustained breach on the ranker -> lower priority sheds FIRST
    for _ in range(fleet.policy.breach_after):
        ranker.tracker.observe(10_000.0)      # simulate sustained pressure
    shed = 0
    for i in range(4):
        try:
            fleet.output("tail-0", rng.rand(2, 16).astype(np.float32))
        except RejectedError:
            shed += 1
    y = fleet.output("ranker", rng.rand(2, 16).astype(np.float32))
    print(f"under pressure: {shed}/4 low-priority requests shed, "
          f"ranker still served (shape {y.shape})")
    for _ in range(fleet.policy.clear_after):
        ranker.tracker.observe(1.0)           # pressure clears

    # 4. topology endpoint + fleet-aware readiness
    import json
    import urllib.request

    from deeplearning4j_tpu.ui.server import UIServer
    ui = UIServer().attach_fleet(fleet)
    port = ui.start(0)
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/fleet", timeout=10) as r:
        topo = json.loads(r.read())[0]
    print(f"/fleet: {len(topo['models'])} models, resident "
          f"{topo['resident']}, slices free "
          f"{topo['capacity']['slices_free']}, warm admissions "
          f"{sum(1 for m in topo['models'].values() if m['state'] != 'cold' and m['last_admission_fresh_compiles'] == 0)}")
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/readyz", timeout=10) as r:
        print(f"/readyz: {json.loads(r.read())['ready']} "
              "(cold tail models do not block readiness)")
    ui.stop()

    fleet.shutdown()
    print("fleet drained and shut down")


if __name__ == "__main__":
    main()
