"""Hierarchical compressed cross-host gradient all-reduce (the Aeron
threshold GradientSharing role at DCN scale — SURVEY.md §3.4).

This script is both driver and worker.  Run it plain and it launches a
simulated 2-host gang (`LocalLauncher`: real OS processes, each with its
own XLA CPU client, coupled ONLY by the TCP gradient mesh), once with
the dense f32 wire and once with threshold-compressed int streams, then
compares bytes-on-wire and final loss.  Inside a launched worker (the
launcher env is set) it trains with `HierarchicalGradientSharing`:
the compiled grad half reduces over the local devices (ICI role), the
host-side exchange combines across processes (DCN role, error-feedback
residuals), the compiled apply half updates.

    python examples/multihost_compressed.py
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

STEPS, BATCH, N_IN = 80, 32, 16


def worker():
    """One simulated host: train on this rank's shard of a shared
    deterministic stream, exchanging gradients over the TCP mesh."""
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.parallel import HierarchicalGradientSharing
    from deeplearning4j_tpu.parallel.multihost import ENV_NPROC, ENV_PID
    from deeplearning4j_tpu.train.updaters import Sgd

    out_dir, mode = sys.argv[1], sys.argv[2]
    rank = int(os.environ[ENV_PID])
    world = int(os.environ[ENV_NPROC])
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(0.1))
            .list([DenseLayer(n_out=32, activation="tanh"),
                   OutputLayer(n_out=3, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(N_IN)).build())
    net = MultiLayerNetwork(conf).init()
    # rank/world/port resolve from the env the launcher exported
    net.set_gradient_sharing(HierarchicalGradientSharing(
        threshold=5e-3, compressed=(mode == "compressed")))

    rng = np.random.RandomState(0)      # same stream on every rank
    for _ in range(STEPS):
        x = rng.randn(world * BATCH, N_IN).astype(np.float32)
        labels = (x[:, 0] > 0).astype(int) + (x[:, 1] > 0).astype(int)
        y = np.eye(3, dtype=np.float32)[labels]
        net.fit(x[rank::world], y[rank::world])   # this rank's shard

    stats = net.gradient_sharing.stats()
    stats["final_loss"] = net.score()
    with open(os.path.join(out_dir, f"{mode}_{rank}.json"), "w") as f:
        json.dump(stats, f)
    net.set_gradient_sharing(None)      # close the mesh sockets
    print(f"rank {rank}/{world} [{mode}]: final loss "
          f"{stats['final_loss']:.4f}, wire bytes "
          f"{stats['bytes_sent_total'] + stats['bytes_received_total']}")


def driver():
    from deeplearning4j_tpu.parallel.multihost import (LocalLauncher,
                                                       free_port)
    me = os.path.abspath(__file__)
    results = {}
    with tempfile.TemporaryDirectory() as td:
        for mode in ("dense", "compressed"):
            print(f"--- launching 2-host gang ({mode} wire) ---")
            LocalLauncher(num_processes=2, devices_per_process=2).run(
                me, [td, mode], timeout=300.0, gradient_port=free_port())
            stats = []
            for r in range(2):
                with open(os.path.join(td, f"{mode}_{r}.json")) as f:
                    stats.append(json.load(f))
            results[mode] = {
                "wire_bytes": sum(s["bytes_sent_total"]
                                  + s["bytes_received_total"]
                                  for s in stats),
                "final_loss": float(np.mean([s["final_loss"]
                                             for s in stats]))}
    d, c = results["dense"], results["compressed"]
    print(f"\ndense:      {d['wire_bytes']:>9} bytes on wire, "
          f"final loss {d['final_loss']:.4f}")
    print(f"compressed: {c['wire_bytes']:>9} bytes on wire, "
          f"final loss {c['final_loss']:.4f}")
    print(f"=> {d['wire_bytes'] / c['wire_bytes']:.1f}x fewer cross-host "
          f"bytes, loss delta "
          f"{abs(c['final_loss'] - d['final_loss']) / d['final_loss']:.2%}")


if __name__ == "__main__":
    if os.environ.get("DL4J_TPU_PROCESS_ID") is not None:
        worker()
    else:
        driver()
