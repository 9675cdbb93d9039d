"""Composed 3D parallelism: dp x tp x pp in ONE jitted train step.

The reference composes its distribution mechanisms per job (Spark
orchestration + per-node ParallelWrapper + Aeron gradient sharing,
`dl4j-spark-parameterserver`); the TPU-native form is one mesh with
three axes and one compiled step:

- 'data'  — batch sharding + gradient psum (DP)
- 'model' — Megatron sequence-parallel tensor parallelism for the MLP
            (all_gather before the column-parallel matmul, psum_scatter
            after the row-parallel one) with RING ATTENTION over the
            same axis for the long-context path
- 'pipe'  — GPipe microbatch pipeline via a scan of compute + ppermute

Run on real chips, or simulate the mesh on CPU:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/composed_3d_parallelism.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import os                                                  # noqa: E402
if os.environ.get("JAX_PLATFORMS") == "cpu":
    # a 1-device CPU run would degenerate the whole point of this
    # example — force the virtual 8-way mesh before jax initializes
    if "device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import jax                                                 # noqa: E402
import jax.numpy as jnp                                    # noqa: E402
import numpy as np                                         # noqa: E402

from deeplearning4j_tpu.parallel.composed import (         # noqa: E402
    composed_oracle, composed_train_step, init_stage_params)
from deeplearning4j_tpu.parallel.mesh import make_mesh     # noqa: E402


def main():
    n = len(jax.devices())
    if n >= 8:
        axes = {"data": n // 4, "model": 2, "pipe": 2}
    elif n >= 4:
        axes = {"data": 1, "model": 2, "pipe": 2}
    else:
        axes = {"data": 1, "model": 1, "pipe": max(1, n)}
    used = int(np.prod(list(axes.values())))
    mesh = make_mesh(axes, jax.devices()[:used])
    print(f"mesh: {axes} over {used} device(s)")

    S, D, H, FF = axes["pipe"], 16, 4, 32
    T = 8 * axes["model"]
    B = 4 * S * axes["data"]
    rng = np.random.RandomState(0)
    params = init_stage_params(rng, S, D, H, FF)
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32) * 0.5)
    y = jnp.asarray(rng.randn(B, T, D).astype(np.float32) * 0.5)

    step = composed_train_step(mesh, H, lr=0.1)
    losses = []
    p = params
    for i in range(10):
        p, loss = step(p, x, y)
        losses.append(float(loss))
    print("losses:", " ".join(f"{v:.4f}" for v in losses))
    assert losses[-1] < losses[0], "training did not reduce the loss"

    # sanity: the sharded step's first loss equals single-device math
    oracle = float(jnp.mean((composed_oracle(params, x, H) - y) ** 2))
    assert abs(losses[0] - oracle) < 1e-3 * max(1.0, oracle)
    print(f"matches single-device oracle (first loss {oracle:.4f}) — ok")


if __name__ == "__main__":
    main()
