"""LeNet on MNIST — the canonical first example (reference
dl4j-examples `LeNetMNIST.java`).

Uses the real MNIST IDX files when MNIST_DIR points at them; otherwise
the deterministic synthetic stand-in (zero-egress environments)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np

from deeplearning4j_tpu.data import MnistDataSetIterator, SyntheticMnist
from deeplearning4j_tpu.train.evaluation import Evaluation
from deeplearning4j_tpu.zoo import LeNet


def make_iterators(batch=64):
    try:
        return (MnistDataSetIterator(batch, train=True),
                MnistDataSetIterator(batch, train=False))
    except FileNotFoundError:
        print("MNIST_DIR not set — using synthetic MNIST")
        return (SyntheticMnist(batch, n_batches=20, seed=0),
                SyntheticMnist(batch, n_batches=5, seed=1))


def main():
    train_it, test_it = make_iterators()
    net = LeNet(n_classes=10).init_model()
    print(f"LeNet: {net.num_params():,} params")

    net.fit(train_it, epochs=2)
    print(f"final train batch loss: {net.score():.4f}")

    ev = net.evaluate(test_it, Evaluation())
    print(ev.stats())

    # checkpoint round-trip with exact resume (updater state included)
    net.save("/tmp/lenet.zip")
    from deeplearning4j_tpu.nn import MultiLayerNetwork
    restored = MultiLayerNetwork.load("/tmp/lenet.zip")
    x = next(iter(test_it)).features
    np.testing.assert_allclose(np.asarray(net.output(x)),
                               np.asarray(restored.output(x)), atol=1e-6)
    print("checkpoint round-trip: outputs identical")


if __name__ == "__main__":
    main()
