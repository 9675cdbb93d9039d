"""Async end-to-end training input pipeline — prefetch, on-device
normalization, sync-free loop.

The compiled train step leaves three host-side stalls in the steady-state
loop (docs/performance.md):

1. batches are ETL'd and normalized on host, serialized with compute;
2. every `fit` pays one host dispatch, and host `np.stack` copies pay
   again on the fused path;
3. listeners that read `score()` force a device sync every iteration.

This example composes the three fixes from `deeplearning4j_tpu.data.pipeline`:
`DevicePrefetchIterator` (producer-thread ETL + depth-bounded device
staging), `net.set_normalizer(...)` (the fitted normalizer replayed as a
jitted on-device prologue, bitwise identical to the host transform), and
`fit(..., fused_steps=k)` over pre-staged device batches (stacked inside
the compiled dispatch).  Score collection stays lazy (`score_array()`)
until read.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import time

import numpy as np

from deeplearning4j_tpu.data import (DataSet, DataSetIterator,
                                     DevicePrefetchIterator,
                                     NormalizerStandardize)
from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.train import Adam
from deeplearning4j_tpu.train.listeners import CollectScoresListener


class SyntheticEtlIterator(DataSetIterator):
    """Materializes each batch from raw float64 rows on demand — the
    per-batch host cost a record-reader/augmentation pipeline pays.  With
    `DevicePrefetchIterator` this work runs in the producer thread,
    overlapped with the previous steps' compute."""

    def __init__(self, raw_x, raw_y, batch):
        self.raw_x, self.raw_y, self._batch = raw_x, raw_y, batch

    def __iter__(self):
        for i in range(0, len(self.raw_x), self._batch):
            x = (self.raw_x[i:i + self._batch]).astype(np.float32)
            y = np.eye(4, dtype=np.float32)[self.raw_y[i:i + self._batch]]
            yield DataSet(x, y)

    def reset(self):
        pass

    def batch_size(self):
        return self._batch

    def __len__(self):
        return (len(self.raw_x) + self._batch - 1) // self._batch


def make_net(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .list([DenseLayer(n_out=64, activation="relu"),
                   DenseLayer(n_out=64, activation="relu"),
                   OutputLayer(n_out=4, loss="mcxent", activation="softmax")])
            .set_input_type(InputType.feed_forward(16)).build())
    return MultiLayerNetwork(conf).init()


def main():
    rng = np.random.RandomState(0)
    raw_x = rng.rand(4096, 16) * 50.0          # raw float64 "records"
    raw_y = rng.randint(0, 4, 4096)
    iterator = SyntheticEtlIterator(raw_x, raw_y, batch=128)

    # fit the normalizer on host ONCE; training replays it on device
    nz = NormalizerStandardize().fit(iterator)

    net = make_net()
    net.set_normalizer(nz)                     # on-device prologue
    collect = CollectScoresListener()          # lazy: no per-iter sync
    net.listeners = [collect]

    pf = DevicePrefetchIterator(iterator, depth=2)   # double-buffer H2D
    try:
        t0 = time.perf_counter()
        net.fit(pf, epochs=3, fused_steps=8)   # streaming fused epochs
        final = float(net.score())             # the ONE blocking read
        dt = time.perf_counter() - t0
    finally:
        pf.close()                             # joins the producer thread

    scores = collect.scores                    # coercion happens here
    print(f"3 epochs x {len(iterator)} batches in {dt:.2f}s "
          f"(prefetch depth 2, fused_steps=8)")
    print(f"score: {scores[0]:.4f} -> {final:.4f}, "
          f"{len(scores)} collected without per-iteration syncs")
    assert final < scores[0]
    assert pf.active_producers() == 0


if __name__ == "__main__":
    main()
