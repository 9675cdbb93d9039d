"""Pod arbiter: preemption-safe slice handoffs between an elastic
training gang and a serving fleet (docs/robustness.md, "Pod arbiter").

One pod, two workloads.  The `SliceArbiter` owns the pod's DeviceSlice
inventory and moves slices between a training gang and a `ModelFleet` as
a two-phase, journaled state machine:

  1. serving pressure rises -> `to_serving()`: the gang commits a
     BLOCKING checkpoint, shrinks at that exact step (survivors
     bitwise-rewind), and the freed slice is leased to the fleet;
  2. pressure fades -> `to_training()`: the fleet drains the slice's
     replicas under a deadline and the gang re-admits the slice at a
     bumped generation;
  3. a crash mid-handoff (here: simulated right after the phase-1
     journal write) is recovered by a relaunched arbiter replaying the
     journal — the slice ends single-owned, the handoff completes.

Runs on CPU in a few seconds: python examples/train_serve_arbiter.py
"""
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import tempfile                                            # noqa: E402

import numpy as np                                         # noqa: E402

from deeplearning4j_tpu.monitor.registry import MetricsRegistry  # noqa: E402
from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.serving import ModelFleet
from deeplearning4j_tpu.serving.slo import ArbiterPolicy
from deeplearning4j_tpu.train.arbiter import LocalElasticGang, SliceArbiter
from deeplearning4j_tpu.train.resilience import CheckpointManager
from deeplearning4j_tpu.train.updaters import Sgd

workdir = tempfile.mkdtemp(prefix="pod-arbiter-")
journal = os.path.join(workdir, "journal.json")

# ---- the training side: a model + real checkpoint manager ----
conf = (NeuralNetConfiguration.builder().seed(42).updater(Sgd(0.1))
        .list([DenseLayer(n_out=32, activation="relu"),
               OutputLayer(n_out=3, loss="mcxent", activation="softmax")])
        .set_input_type(InputType.feed_forward(8)).build())
net = MultiLayerNetwork(conf).init()
rng = np.random.RandomState(0)
x = rng.randn(32, 8).astype(np.float32)
y = np.eye(3, dtype=np.float32)[(x[:, 0] > 0).astype(int)]
net.fit(x, y)

manager = CheckpointManager(os.path.join(workdir, "ckpt"), keep_last=20)
gang = LocalElasticGang(net, manager, slices=[0, 1, 2])

# ---- the serving side: a fleet sharing the pod ----
fleet = ModelFleet(max_resident=2, n_slices=1,
                   cache_dir=os.path.join(workdir, "exec-cache"),
                   registry_=MetricsRegistry())
fleet.deploy("classifier", model=net, input_shape=(8,), warm=True)

# ---- the arbiter over both ----
policy = ArbiterPolicy(grant_at_forecast=1.5, return_below_forecast=0.5,
                       min_training_slices=1, drain_timeout_s=2.0)
arb = SliceArbiter(journal, training=gang, fleet=fleet, policy=policy)
fleet.attach_arbiter(arb)                   # growth consults the leases
print(f"lease table: {arb.owners()}")

# 1. the morning spike: pressure over the grant threshold moves a slice
out = arb.maybe_rebalance(pressure=2.0)
print(f"to_serving : slice {out['slice']} -> fleet index "
      f"{arb.fleet_index_of(out['slice'])} "
      f"(gang checkpointed at step {out['resume_step']}, "
      f"world {gang.world}, generation {gang.generation})")
preds = fleet.submit("classifier", x[:4]).result(timeout=30)
print(f"serving on the grown fleet: predictions {preds.shape}")

# 2. the evening lull: pressure under the return threshold reclaims it
out = arb.maybe_rebalance(pressure=0.1)
print(f"to_training: slice {out['slice']} back "
      f"(drained {out['released']['drained'] or 'nothing routed'}, "
      f"gang world {gang.world}, generation {gang.generation})")

# 3. crash mid-handoff: die right after the phase-1 journal write …
class _CrashAfterPhase1(Exception):
    pass


class _Chaos:
    fired = False

    def on_journal(self, direction, phase):
        if not self.fired and phase == "shrink":
            self.fired = True
            raise _CrashAfterPhase1()       # stands in for os._exit(9)


arb.chaos = _Chaos()
try:
    arb.to_serving()
except _CrashAfterPhase1:
    print("arbiter 'crashed' after the phase-1 journal write "
          "(intent durable, nothing executed)")

# … and relaunch over the SAME journal: the constructor replays it
arb2 = SliceArbiter(journal, training=gang, fleet=fleet, policy=policy)
fleet.attach_arbiter(arb2)
rec = arb2.recovered
print(f"relaunched arbiter replayed the handoff: slice {rec['slice']} "
      f"-> {rec['outcome']} (journal replays: "
      f"{arb2.describe()['replays']})")
assert rec["outcome"] == "replayed"
assert arb2.owners()[rec["slice"]] == "serving"
assert rec["slice"] not in gang.held_slices()        # single-owned

fleet.shutdown()
print(f"final lease table: {arb2.owners()}")
print("done.")
