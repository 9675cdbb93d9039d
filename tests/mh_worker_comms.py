"""Hierarchical gradient-sharing TRAINING worker (spawned by test_comms
via LocalLauncher — NOT a pytest file).

Each rank builds the SAME small MLP, enables hierarchical gradient
sharing (config resolved from the launcher's `DL4J_TPU_*` env), and
trains on its own shard of one deterministic global data stream: the
compiled grad half reduces over the local mesh (ICI role), the host-side
exchange combines across ranks over TCP (DCN role), the compiled apply
half updates.  Mode "compressed" uses the threshold codec with
error-feedback residuals; "dense" ships raw f32 — the A/B baseline.

Per-rank outputs for the driver: the loss curve + final first-layer
weights (replica-consistency proof) as npz, and the exchange stats
(bytes on wire, compression ratio) as json."""
import json
import os
import sys

import numpy as np

from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.parallel.hierarchical import (
    HierarchicalGradientSharing)
from deeplearning4j_tpu.parallel.multihost import ENV_NPROC, ENV_PID
from deeplearning4j_tpu.train.updaters import Sgd

out_dir = sys.argv[1]
mode = sys.argv[2]                       # "compressed" | "dense"
steps = int(sys.argv[3])
batch = int(sys.argv[4])                 # per-rank rows per step
rank = int(os.environ[ENV_PID])
world = int(os.environ[ENV_NPROC])

n_in = 16
conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.1))
        .list([DenseLayer(n_out=32, activation="tanh"),
               OutputLayer(n_out=3, loss="mcxent", activation="softmax")])
        .set_input_type(InputType.feed_forward(n_in)).build())
net = MultiLayerNetwork(conf).init()
net.set_gradient_sharing(HierarchicalGradientSharing(
    threshold=5e-3, compressed=(mode == "compressed")))

# one deterministic global stream, identical on every rank; each rank
# trains on its strided shard — plain data parallelism across "hosts"
rng = np.random.RandomState(0)
losses = []
for _ in range(steps):
    xg = rng.randn(world * batch, n_in).astype(np.float32)
    labels = (xg[:, 0] > 0).astype(int) + (xg[:, 1] > 0).astype(int)
    yg = np.eye(3, dtype=np.float32)[labels]
    net.fit(xg[rank::world], yg[rank::world])
    losses.append(net.score())

stats = net.gradient_sharing.stats()
np.savez(os.path.join(out_dir, f"curve_{mode}_{rank}.npz"),
         losses=np.asarray(losses, np.float64),
         w0=np.asarray(net.params_["layer_0"]["W"]))
with open(os.path.join(out_dir, f"stats_{mode}_{rank}.json"), "w") as f:
    json.dump(stats, f)
net.set_gradient_sharing(None)           # close the mesh sockets
print(f"rank {rank}/{world}: {mode} x{steps} steps, "
      f"final loss {losses[-1]:.4f}", flush=True)
