"""Transfer learning: train a base net, freeze its features, replace the
head for a new task, fine-tune (reference dl4j-examples
`EditLastLayerOthersFrozen.java` + `TransferLearningHelper`)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.nn.transferlearning import (TransferLearning,
                                                    TransferLearningHelper)
from deeplearning4j_tpu.train.updaters import Adam


def data(n, n_classes, seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 8).astype(np.float32)
    labels = (x[:, :n_classes].argmax(1))
    return x, np.eye(n_classes, dtype=np.float32)[labels]


def main():
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-2))
            .list([DenseLayer(n_out=32, activation="relu"),
                   DenseLayer(n_out=16, activation="relu"),
                   OutputLayer(n_out=4, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(8)).build())
    base = MultiLayerNetwork(conf).init()
    x, y = data(256, 4, seed=0)
    for _ in range(30):
        base.fit(x, y)
    print(f"base task loss: {base.score():.4f}")

    # freeze layers 0-1, swap the 4-way head for a 2-way one
    derived = (TransferLearning.builder(base)
               .set_feature_extractor(1)
               .remove_output_layer()
               .add_layer(OutputLayer(n_out=2, loss="mcxent",
                                      activation="softmax"))
               .build())
    x2, y2 = data(256, 2, seed=1)
    for _ in range(30):
        derived.fit(x2, y2)
    print(f"fine-tuned new-task loss: {derived.score():.4f}")

    # helper: featurize once through the frozen trunk, then train the head
    # on cached features (fast path for repeated epochs; original 4-class
    # head, so original-task labels)
    helper = TransferLearningHelper(base, frozen_till=1)
    feats = helper.featurize(DataSet(x, y))
    helper.fit_featurized(feats)
    print("featurize-then-fit path OK")


if __name__ == "__main__":
    main()
