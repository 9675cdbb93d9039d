"""CSV → TransformProcess → normalizer → classifier (the DataVec
pipeline; reference dl4j-examples `IrisClassifier.java` / datavec
examples)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np

from deeplearning4j_tpu.data import (CSVRecordReader,
                                     RecordReaderDataSetIterator)
from deeplearning4j_tpu.data.normalizers import NormalizerStandardize
from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.train.evaluation import Evaluation
from deeplearning4j_tpu.train.updaters import Adam


def iris_csv(n=150, seed=0):
    """Generate an iris-like CSV in-memory (no downloads): 3 separable
    clusters over 4 features."""
    rng = np.random.RandomState(seed)
    rows = ["sl,sw,pl,pw,species"]
    centers = np.array([[5.0, 3.4, 1.5, 0.2], [5.9, 2.8, 4.3, 1.3],
                        [6.6, 3.0, 5.6, 2.0]])
    for i in range(n):
        c = i % 3
        v = centers[c] + rng.randn(4) * 0.25
        rows.append(",".join(f"{x:.2f}" for x in v) + f",{c}")
    return "\n".join(rows)


def main():
    reader = CSVRecordReader(text=iris_csv(), skip_lines=1)
    it = RecordReaderDataSetIterator(reader, batch_size=30, label_index=4,
                                     num_classes=3)

    normalizer = NormalizerStandardize()
    normalizer.fit(it)
    it.set_pre_processor(normalizer)

    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(5e-2))
            .list([DenseLayer(n_out=16, activation="relu"),
                   OutputLayer(n_out=3, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    net.fit(it, epochs=30)

    ev = net.evaluate(it, Evaluation())
    print(ev.stats())
    assert ev.accuracy() > 0.9


if __name__ == "__main__":
    main()
