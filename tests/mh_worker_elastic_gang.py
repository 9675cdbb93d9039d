"""Elastic-gang training worker (spawned by test_elastic via
ElasticLocalRunner.run_elastic — NOT a pytest file).

Each process trains the SAME seeded MLN under `ElasticTrainer` with
`HierarchicalGradientSharing(elastic=True)` (heartbeat / deadline / join
knobs resolve from the supervisor's `DL4J_TPU_*` env).  The data stream
is one deterministic GLOBAL batch per step seeded by (epoch, step) only;
each member trains on the strided shard of its LIVE gang rank, so a
reformation re-shards the same stream at the new world size — the
property the bitwise kill-and-resume parity test relies on.

A `PeerKiller` hook (argv-armed) injects the chaos on exactly one rank;
the marker file keeps a relaunched replacement from re-firing.  Only the
coordinator WRITES checkpoints; peers share the directory read-only and
rewind from it on every reformation.

argv: out_dir steps_per_epoch epochs kill_rank kill_step [kill_mode]
  kill_rank -1 disables chaos; kill_mode: kill | hang | partition | slow
"""
import json
import os
import sys

import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import DataSetIterator
from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.parallel.hierarchical import (
    HierarchicalGradientSharing)
from deeplearning4j_tpu.parallel.multihost import ENV_CKPT, ENV_PID
from deeplearning4j_tpu.parallel.transport import (GangEvictedError,
                                                   PeerUnreachableError)
from deeplearning4j_tpu.train.resilience import (CheckpointManager,
                                                 ElasticTrainer)
from deeplearning4j_tpu.train.updaters import Sgd
from deeplearning4j_tpu.utils.chaos import PeerKiller

out_dir = sys.argv[1]
steps_per_epoch = int(sys.argv[2])
epochs = int(sys.argv[3])
kill_rank = int(sys.argv[4])
kill_step = int(sys.argv[5])
kill_mode = sys.argv[6] if len(sys.argv) > 6 else "kill"

rank = int(os.environ.get(ENV_PID, "0"))
policy = os.environ.get("DL4J_TPU_ELASTIC_POLICY", "shrink")
ckpt_dir = os.environ[ENV_CKPT]

N_IN, N_OUT, GLOBAL_BATCH = 16, 3, 12

conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.1))
        .list([DenseLayer(n_out=32, activation="tanh"),
               OutputLayer(n_out=N_OUT, loss="mcxent",
                           activation="softmax")])
        .set_input_type(InputType.feed_forward(N_IN)).build())
net = MultiLayerNetwork(conf).init()
net.set_gradient_sharing(HierarchicalGradientSharing(
    threshold=5e-3, elastic=True))


class GangShardIterator(DataSetIterator):
    """Deterministic global stream, live-rank strided shards (see module
    docstring).  Rank/world are read per batch, NOT captured at
    construction — that is what lets the same iterator keep feeding a
    reformed gang."""

    def __init__(self, model, steps: int):
        self.model = model
        self.steps = int(steps)

    def __iter__(self):
        for i in range(self.steps):
            seed = 1000 + int(self.model.epoch) * self.steps + i
            rng = np.random.RandomState(seed)
            xg = rng.randn(GLOBAL_BATCH, N_IN).astype(np.float32)
            labels = ((xg[:, 0] > 0).astype(int)
                      + (xg[:, 1] > 0).astype(int))
            yg = np.eye(N_OUT, dtype=np.float32)[labels]
            sharing = self.model.gradient_sharing
            r, w = sharing.rank, sharing.world
            yield DataSet(xg[r::w], yg[r::w])

    def __len__(self):
        return self.steps

    def batch_size(self) -> int:
        return GLOBAL_BATCH


# coordinator writes every step; keep_last is high because the parity
# comparator reruns from the exact reform step, which retention must not
# have pruned by the end of the run
manager = CheckpointManager(ckpt_dir, keep_last=200,
                            save_every_steps=1 if rank == 0 else None)
hooks = []
if kill_rank >= 0:
    hooks.append(PeerKiller(kill_rank, kill_step, mode=kill_mode,
                            duration_s=6.0,
                            marker=os.path.join(out_dir, "killed_once")))
trainer = ElasticTrainer(net, manager, policy=policy, rejoin_wait_s=60.0,
                         hooks=hooks, save_initial=(rank == 0))
data = GangShardIterator(net, steps_per_epoch)
try:
    trainer.fit(data, epochs=epochs)
except (GangEvictedError, PeerUnreachableError) as e:
    print(f"rank {rank}: left the gang: {e}", flush=True)
    net.set_gradient_sharing(None)
    sys.exit(7)

stats = net.gradient_sharing.stats()
np.savez(os.path.join(out_dir, f"final_{rank}.npz"),
         params=np.asarray(net.params()),
         iteration=np.int64(net.iteration),
         score=np.float64(net.score()))
with open(os.path.join(out_dir, f"elastic_{rank}.json"), "w") as f:
    json.dump({"stats": stats, "reformations": trainer.reformations}, f)
net.set_gradient_sharing(None)           # close the gang sockets
print(f"rank {rank}: done at iteration {net.iteration} "
      f"(world={stats['world']}, generation={stats['generation']})",
      flush=True)
