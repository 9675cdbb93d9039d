"""Data-parallel SPMD training with ParallelWrapper (reference
dl4j-examples `MultiGpuLenetMnistExample.java` — ParallelWrapper over
GPUs; here one jitted step sharded over a jax device mesh).

Run with real chips, or simulate a mesh on CPU:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/data_parallel_training.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import jax
import numpy as np

from deeplearning4j_tpu.data import SyntheticMnist
from deeplearning4j_tpu.parallel import ParallelWrapper
from deeplearning4j_tpu.zoo import LeNet


def main():
    print(f"devices: {jax.devices()}")
    net = LeNet(n_classes=10).init_model()

    pw = (ParallelWrapper.builder(net)
          .workers(len(jax.devices()))
          .training_mode("SHARED_GRADIENTS")   # every mode = sync all-reduce
          .build())

    # global batch 64 → 64/n_devices per device, gradients all-reduced
    # over ICI by XLA inside the one compiled step
    it = SyntheticMnist(64, n_batches=20, seed=0)
    pw.fit(it, epochs=2)
    print(f"loss after DP training: {net.score():.4f}")

    # fused SPMD dispatch: k data-parallel steps (per-step all-reduce
    # inside) in ONE compiled dispatch — the r5 host-latency lever
    ds = next(iter(SyntheticMnist(64, n_batches=1, seed=2)))
    xs = np.broadcast_to(np.asarray(ds.features),
                         (4,) + np.asarray(ds.features).shape).copy()
    ys = np.broadcast_to(np.asarray(ds.labels),
                         (4,) + np.asarray(ds.labels).shape).copy()
    losses = pw.fit_steps(xs, ys)
    print(f"fused block of {len(losses)} DP steps in one dispatch, "
          f"loss -> {float(losses[-1]):.4f}")

    # the trained params live sharded/replicated on the mesh; normal
    # single-host inference just works
    x = next(iter(SyntheticMnist(8, n_batches=1, seed=1))).features
    print("predictions:", np.asarray(net.output(x)).argmax(1))


if __name__ == "__main__":
    main()
