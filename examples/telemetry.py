"""Unified telemetry — one registry across training, pipeline and serving.

Runs an instrumented end-to-end slice of the framework (docs/observability.md):

1. train an MLP through the async `DevicePrefetchIterator` pipeline —
   step timing, compile events, prefetch depth and producer wait record
   into the process-wide `monitor.MetricsRegistry` as a side effect;
2. serve the trained net from a `ModelServer` — its `ServingMetrics` is a
   view over the SAME registry, labeled `server="sN"`;
3. wrap a custom section in `span(...)` (nested spans record as
   "parent/child" and forward into `jax.profiler.TraceAnnotation`);
4. start the `UIServer` and scrape `GET /metrics` — the Prometheus text a
   real scraper would ingest — then print the interesting series.

Backend-agnostic; run on CPU with `JAX_PLATFORMS=cpu python
examples/telemetry.py`.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import urllib.request                                      # noqa: E402

import numpy as np                                         # noqa: E402

from deeplearning4j_tpu.data import DataSet                # noqa: E402
from deeplearning4j_tpu.data.iterators import (            # noqa: E402
    ListDataSetIterator)
from deeplearning4j_tpu.data.pipeline import (             # noqa: E402
    DevicePrefetchIterator)
from deeplearning4j_tpu.monitor import registry, span      # noqa: E402
from deeplearning4j_tpu.nn import (DenseLayer, InputType,  # noqa: E402
                                   MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.serving import ModelServer         # noqa: E402
from deeplearning4j_tpu.ui.server import UIServer          # noqa: E402


def main():
    rng = np.random.RandomState(0)

    # -- 1. instrumented training through the prefetch pipeline ----------
    conf = (NeuralNetConfiguration.builder().seed(7)
            .list([DenseLayer(n_out=32, activation="relu"),
                   OutputLayer(n_out=3, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(8)).build())
    net = MultiLayerNetwork(conf).init()
    batches = [DataSet(rng.rand(16, 8).astype(np.float32),
                       np.eye(3, dtype=np.float32)[rng.randint(0, 3, 16)])
               for _ in range(16)]

    with span("example", section="train"):     # custom nested span
        pf = DevicePrefetchIterator(ListDataSetIterator(batches), depth=2)
        try:
            net.fit(pf, epochs=3)              # fit wraps each epoch in
        finally:                               # span("fit_epoch") itself
            pf.close()

    # -- 2. serving against the same registry ----------------------------
    server = ModelServer(max_batch=16, batch_timeout_ms=2.0)
    ui = UIServer()
    try:
        server.deploy("mlp", net)
        for _ in range(20):
            server.output("mlp", rng.rand(4, 8).astype(np.float32))

        # -- 3. scrape /metrics like Prometheus would ---------------------
        port = ui.start(port=0)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            text = r.read().decode()
    finally:
        ui.stop()
        server.shutdown()

    print("== /metrics (selected series) ==")
    for line in text.splitlines():
        if line.startswith(("training_", "pipeline_", "serving_latency",
                            "serving_queue", "span_ms")) \
                and "quantile" not in line:
            print(" ", line)

    # -- 4. the same numbers, host-side ----------------------------------
    snap = registry().snapshot()
    lbl = {"model": "MultiLayerNetwork"}
    steps = registry().get("training_steps_total", lbl)
    compiles = registry().get("training_compiles_total", lbl)
    print(f"\nsteps trained: {steps.value}")
    print(f"compiles: {compiles.value}")
    span_keys = [k for k in snap["histograms"] if k.startswith("span_ms")]
    print(f"span series: {span_keys}")


if __name__ == "__main__":
    main()
