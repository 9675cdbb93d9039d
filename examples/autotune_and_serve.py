"""Persistent AOT executable cache + schedule autotuner — warm restarts.

XLA compilation dominates cold-start: every process that builds the same
model pays the same multi-second `jit` stall before its first step.  The
`deeplearning4j_tpu.compile` package removes the repeat payments:

1. `PersistentExecutableCache` — serialized compiled executables on disk,
   keyed by (jax/backend version, topology, model program, arg shapes).
   A restarted process deserializes instead of recompiling: same math,
   no compile before the first step.
2. `ScheduleAutotuner` — measures steps/sec over a small config space
   (fused_steps, prefetch depth, donation, ZeRO-1) and persists the
   winning `Schedule`; later runs `load_schedule()` and start tuned.

This example trains cold, "restarts" (fresh model objects, same cache
dir), and shows the warm path does zero compiles while producing
bit-identical scores; then it autotunes a schedule, saves it, and brings
up a ModelServer-style serving cache warm from the same directory.
"""
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import tempfile
import time

import numpy as np

from deeplearning4j_tpu.compile import (PersistentExecutableCache,
                                        ScheduleAutotuner, load_schedule,
                                        save_schedule)
from deeplearning4j_tpu.data import ArrayDataSetIterator
from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.serving import BucketedCompileCache
from deeplearning4j_tpu.train import Adam


def make_net(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .list([DenseLayer(n_out=64, activation="relu"),
                   OutputLayer(n_out=4, loss="mcxent", activation="softmax")])
            .set_input_type(InputType.feed_forward(16)).build())
    return MultiLayerNetwork(conf).init()


def make_data(n=512, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 16).astype(np.float32)
    y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, n)]
    return x, y


def train(cache_dir, steps=8):
    """One 'process': build the model, route its step through the cache."""
    cache = PersistentExecutableCache(cache_dir)
    net = make_net().set_executable_cache(cache)
    x, y = make_data()
    t0 = time.perf_counter()
    net.fit(x[:64], y[:64])                  # pays (or skips) the compile
    t_first = time.perf_counter() - t0
    for i in range(1, steps):
        net.fit(x[64 * (i % 8):64 * (i % 8) + 64],
                y[64 * (i % 8):64 * (i % 8) + 64])
    return net, cache, t_first


def main():
    workdir = tempfile.mkdtemp(prefix="dl4j-aot-example-")

    # ---- 1) cold process: compiles once, stores the executable ----------
    net1, c1, t_cold = train(workdir)
    print(f"cold : first step {t_cold * 1e3:7.1f} ms   "
          f"compiles={c1.stats['compiles']} stores={c1.stats['stores']}")

    # ---- 2) 'restart': fresh objects, same directory -> zero compiles ---
    net2, c2, t_warm = train(workdir)
    print(f"warm : first step {t_warm * 1e3:7.1f} ms   "
          f"compiles={c2.stats['compiles']} disk_hits={c2.stats['disk_hits']}")
    assert c2.stats["compiles"] == 0, "warm restart must not compile"
    assert float(net1.score()) == float(net2.score()), "bitwise parity"
    print(f"       identical scores ({net2.score():.6f}), "
          f"{t_cold / max(t_warm, 1e-9):.1f}x faster to first step")

    # ---- 3) autotune a schedule and persist it --------------------------
    x, y = make_data(1024)

    def measure(schedule):
        net = make_net().set_executable_cache(PersistentExecutableCache(workdir))
        schedule.apply(net)
        it = ArrayDataSetIterator(x, y, batch_size=64)
        net.fit(it, fused_steps=schedule.fused_steps)   # compile excluded...
        it.reset()
        t0 = time.perf_counter()
        net.fit(it, fused_steps=schedule.fused_steps)   # ...time steady state
        steps = (len(x) // 64) / max(time.perf_counter() - t0, 1e-9)
        return steps

    best = ScheduleAutotuner(
        measure, space={"fused_steps": [1, 8], "prefetch_depth": [2],
                        "donation": [True]},
        refine_rounds=0).search()
    path = save_schedule(best, workdir, name="example")
    print(f"tuned: fused_steps={best.fused_steps} -> "
          f"{best.steps_per_sec:.0f} steps/s "
          f"(baseline {best.meta['baseline_steps_per_sec']:.0f}); "
          f"saved {os.path.basename(path)}")

    # a later process starts tuned instead of re-searching
    loaded = load_schedule(workdir, name="example")
    assert loaded is not None and loaded.fused_steps == best.fused_steps

    # ---- 4) serving comes up warm from the same directory ---------------
    scache = BucketedCompileCache(max_batch=16, persistent=workdir)
    scache.warmup("mlp:v1", make_net(), trailing=(16,), dtype=np.float32,
                  parallel=True)
    out = scache.run("mlp:v1", make_net(seed=9), make_data(5)[0])
    print(f"serve: warmed buckets {scache.buckets}, "
          f"compiles={scache.persistent.stats['compiles']} "
          f"disk_hits={scache.persistent.stats['disk_hits']}, "
          f"served {out.shape[0]} rows")
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
