"""Elastic gang survival — heartbeats, generation-fenced re-formation,
checkpoint-coordinated resume (docs/robustness.md).

This script is both supervisor and worker.  Run it plain and it launches
a 3-process gang (`ElasticLocalRunner.run_elastic`: real OS processes
coupled only by the elastic TCP gradient mesh) and kills rank 2 mid-run
with a `chaos.PeerKiller` hook.  The survivors detect the death within
the failure deadline, re-form at world 2 under a new membership
generation (in-flight frames from the dead generation are fenced, never
summed into a gradient), rewind to the coordinated checkpoint, and keep
training.  The supervisor relaunches a replacement with
`DL4J_TPU_JOIN=1`; under the `block` rejoin policy the coordinator
admits it and the gang finishes back at world 3 — every member with
identical parameters.

    python examples/elastic_gang_training.py
"""
import json
import os
import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np                                         # noqa: E402

STEPS, N_IN, N_OUT, GLOBAL_BATCH = 20, 16, 3, 12
KILL_RANK, KILL_STEP = 2, 6


def worker():
    """One gang member: train on the strided shard of a deterministic
    global stream, sharded by the member's LIVE gang rank — a
    reformation re-shards the same stream at the new world size."""
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import DataSetIterator
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.parallel import HierarchicalGradientSharing
    from deeplearning4j_tpu.parallel.multihost import ENV_CKPT, ENV_PID
    from deeplearning4j_tpu.train.resilience import (CheckpointManager,
                                                     ElasticTrainer)
    from deeplearning4j_tpu.train.updaters import Sgd
    from deeplearning4j_tpu.utils.chaos import PeerKiller

    out_dir = sys.argv[1]
    rank = int(os.environ[ENV_PID])
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.1))
            .list([DenseLayer(n_out=32, activation="tanh"),
                   OutputLayer(n_out=N_OUT, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(N_IN)).build())
    net = MultiLayerNetwork(conf).init()
    # heartbeat / deadline / join knobs resolve from the supervisor's env
    net.set_gradient_sharing(HierarchicalGradientSharing(
        threshold=5e-3, elastic=True))

    class GangShardIterator(DataSetIterator):
        def __iter__(self):
            for i in range(STEPS):
                rng = np.random.RandomState(1000 + i)
                xg = rng.randn(GLOBAL_BATCH, N_IN).astype(np.float32)
                labels = ((xg[:, 0] > 0).astype(int)
                          + (xg[:, 1] > 0).astype(int))
                yg = np.eye(N_OUT, dtype=np.float32)[labels]
                sharing = net.gradient_sharing
                r, w = sharing.rank, sharing.world
                yield DataSet(xg[r::w], yg[r::w])

        def __len__(self):
            return STEPS

        def batch_size(self):
            return GLOBAL_BATCH

    # only the coordinator writes checkpoints; peers rewind from the
    # same directory on every reformation
    manager = CheckpointManager(
        os.environ[ENV_CKPT], keep_last=50,
        save_every_steps=1 if rank == 0 else None)
    killer = PeerKiller(KILL_RANK, KILL_STEP, mode="kill",
                        marker=os.path.join(out_dir, "killed_once"))
    trainer = ElasticTrainer(
        net, manager, hooks=[killer], rejoin_wait_s=60.0,
        policy=os.environ.get("DL4J_TPU_ELASTIC_POLICY", "shrink"),
        save_initial=(rank == 0))
    trainer.fit(GangShardIterator(), epochs=1)

    stats = net.gradient_sharing.stats()
    for rf in trainer.reformations:
        detect = (f" (detected in {rf['detection_ms']:.1f} ms)"
                  if rf["detection_ms"] is not None else "")
        print(f"rank {rank}: reformed ({rf['cause']}) -> generation "
              f"{rf['generation']}, world {rf['world']}, resumed from "
              f"step {rf['resume_step']}{detect}", flush=True)
    np.savez(os.path.join(out_dir, f"final_{rank}.npz"),
             params=np.asarray(net.params()))
    net.set_gradient_sharing(None)      # close the gang sockets
    print(f"rank {rank}: done at iteration {net.iteration} "
          f"(world={stats['world']}, generation={stats['generation']}, "
          f"loss={net.score():.4f})", flush=True)


def supervisor():
    from deeplearning4j_tpu.parallel.multihost import ElasticLocalRunner
    me = os.path.abspath(__file__)
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "out")
        os.makedirs(out)
        print(f"--- launching 3-process elastic gang (rank {KILL_RANK} "
              f"dies at step {KILL_STEP}) ---")
        results = ElasticLocalRunner(
            num_processes=3, backoff_base_s=0.2).run_elastic(
                me, [out], timeout=300.0,
                checkpoint_dir=os.path.join(td, "ckpt"),
                policy="block", heartbeat_s=0.1, failure_deadline_s=2.0,
                relaunch=True, max_replacements=1)
        for label in sorted(results):
            rc, output = results[label]
            tail = [ln for ln in output.strip().splitlines()
                    if "rank" in ln][-2:]
            status = "ok" if rc == 0 else f"exit {rc}"
            print(f"[{label}] {status}")
            for ln in tail:
                print(f"    {ln}")
        finals = [np.load(os.path.join(out, f"final_{r}.npz"))["params"]
                  for r in range(3)]
        same = all(np.array_equal(finals[0], f) for f in finals[1:])
        print(f"\n=> all 3 members finished with "
              f"{'IDENTICAL' if same else 'DIVERGED'} parameters after "
              "kill -> shrink -> rejoin")


if __name__ == "__main__":
    if os.environ.get("DL4J_TPU_PROCESS_ID") is not None:
        worker()
    else:
        supervisor()
