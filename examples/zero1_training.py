"""ZeRO-1 optimizer-state sharding with ParallelWrapper (Xu et al.,
arXiv:2004.13336 — "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training").

Plain data parallelism replicates the Adam moments (2x the params!) and
the weight update on every replica.  `optimizer_sharding(True)` makes the
one compiled step reduce-scatter the gradients over the data axis, run
the optimizer on each replica's 1/N shard, and all-gather the updated
params — same math, ~N× less optimizer-state HBM per replica.

Run with real chips, or simulate a mesh on CPU:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/zero1_training.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import os                                                  # noqa: E402
if os.environ.get("JAX_PLATFORMS") == "cpu":
    # a 1-device run would degenerate the sharding — force a virtual
    # 4-way mesh before jax initializes
    if "device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import jax                                                 # noqa: E402
import numpy as np                                         # noqa: E402

from deeplearning4j_tpu.monitor import set_enabled        # noqa: E402
from deeplearning4j_tpu.monitor.registry import registry  # noqa: E402
from deeplearning4j_tpu.nn import (                       # noqa: E402
    DenseLayer, InputType, MultiLayerNetwork, NeuralNetConfiguration,
    OutputLayer)
from deeplearning4j_tpu.parallel import ParallelWrapper   # noqa: E402
from deeplearning4j_tpu.train.updaters import Adam        # noqa: E402


def make_net(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-3))
            .list([DenseLayer(n_out=512, activation="relu"),
                   DenseLayer(n_out=512, activation="relu"),
                   OutputLayer(n_out=10, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(128)).build())
    return MultiLayerNetwork(conf).init()


def main():
    set_enabled(True)
    print(f"devices: {jax.devices()}")
    rng = np.random.RandomState(0)
    x = rng.randn(64, 128).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, 64)]

    # --- A: plain data parallelism (optimizer state replicated) ---------
    net_a = make_net()
    pw_a = ParallelWrapper.builder(net_a).build()
    for _ in range(5):
        pw_a.fit(x, y)

    # --- B: ZeRO-1 — same math, sharded weight update -------------------
    net_b = make_net()
    pw_b = (ParallelWrapper.builder(net_b)
            .optimizer_sharding(True)       # the one-line opt-in
            .build())
    for _ in range(5):
        pw_b.fit(x, y)

    # parity: with_sharding_constraint is value-preserving, so the two
    # trajectories are identical
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b)))),
        net_a.params_, net_b.params_)
    max_diff = max(jax.tree_util.tree_leaves(diffs))
    print(f"max param diff after 5 Adam steps: {max_diff:.2e}")
    assert max_diff < 1e-5

    # the HBM headline: per-replica optimizer-state bytes, from the
    # telemetry gauge pair the wrapper records at placement
    repl = registry().get("training_opt_state_bytes", {"sharded": "false"})
    shrd = registry().get("training_opt_state_bytes", {"sharded": "true"})
    print(f"optimizer state per replica: {int(repl.value):,} B replicated "
          f"-> {int(shrd.value):,} B sharded "
          f"({repl.value / shrd.value:.1f}x smaller)")

    # composes with the fused k-step dispatch (collectives stay inside
    # the compiled scan body) — and zero1= can toggle it per call
    xs = np.broadcast_to(x, (4,) + x.shape).copy()
    ys = np.broadcast_to(y, (4,) + y.shape).copy()
    losses = pw_b.fit_steps(xs, ys, zero1=True)
    print(f"fused block of {len(losses)} sharded-update steps in one "
          f"dispatch, loss -> {float(losses[-1]):.4f}")

    # before portable checkpoints, drop back to true-shape moments
    pw_b.optimizer_sharding(False)
    print("sharding disabled; moments back at true shapes for save()")


if __name__ == "__main__":
    main()
