"""Keras import → pretrained-artifact conversion → dynamic-batching
serving (round-3 surface; reference analogs: `KerasModelImport`,
`ZooModel.initPretrained`, `ParallelInference` with ObservablesProvider).

Builds a Bidirectional-LSTM sequence classifier in TF-Keras with random
weights, saves the H5, then:
 1. imports it (predictions match TF),
 2. converts it to a model-zip pretrained artifact via the converter CLI
    machinery,
 3. serves it behind `DynamicBatchingInference`, with concurrent clients
    whose requests are aggregated into batched dispatches.
"""
import os
import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

os.environ.setdefault("CUDA_VISIBLE_DEVICES", "-1")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import numpy as np                                         # noqa: E402


def main():
    import tensorflow as tf
    from deeplearning4j_tpu.modelimport import KerasModelImport
    from deeplearning4j_tpu.nn import MultiLayerNetwork
    from deeplearning4j_tpu.parallel import (DynamicBatchingInference,
                                             ParallelInference, make_mesh)
    from deeplearning4j_tpu.zoo.convert import convert

    tf.keras.utils.set_random_seed(0)
    km = tf.keras.Sequential([
        tf.keras.layers.Input((12, 5)),
        tf.keras.layers.Bidirectional(
            tf.keras.layers.LSTM(16, return_sequences=True)),
        tf.keras.layers.TimeDistributed(
            tf.keras.layers.Dense(8, activation="tanh")),
        tf.keras.layers.Flatten(),
        tf.keras.layers.Dense(3, activation="softmax")])

    with tempfile.TemporaryDirectory() as tmp:
        h5 = os.path.join(tmp, "model.h5")
        km.save(h5)

        # 1. import: predictions must match TF
        net = KerasModelImport.import_keras_sequential_model_and_weights(h5)
        x = np.random.RandomState(0).randn(6, 12, 5).astype(np.float32)
        ours = np.asarray(net.output(x))
        theirs = km.predict(x, verbose=0)
        np.testing.assert_allclose(ours, theirs, rtol=1e-3, atol=1e-4)
        print(f"import ok: max|Δ| vs TF = {np.abs(ours - theirs).max():.2e}")

        # 2. convert to the pretrained artifact (model zip)
        artifact = os.path.join(tmp, "model.zip")
        print(convert(h5, artifact, "zip"))
        served_net = MultiLayerNetwork.load(artifact, False)

        # 3. serve with dynamic request batching
        pi = ParallelInference(served_net, mesh=make_mesh())
        dyn = DynamicBatchingInference(pi, max_batch=32, timeout_ms=100.0)
        from concurrent.futures import ThreadPoolExecutor
        reqs = [np.random.RandomState(i).randn(n, 12, 5).astype(np.float32)
                for i, n in enumerate((1, 3, 2, 4, 1, 5))]
        with ThreadPoolExecutor(max_workers=6) as ex:
            outs = list(ex.map(dyn.output, reqs))
        dyn.shutdown()
        for r, o in zip(reqs, outs):
            assert o.shape == (r.shape[0], 3)
        print(f"served {len(reqs)} concurrent requests "
              f"({sum(r.shape[0] for r in reqs)} rows) through dynamic "
              "batching — shapes and routing correct")


if __name__ == "__main__":
    main()
