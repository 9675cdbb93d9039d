"""SameDiff-equivalent graph engine: declare a custom graph, train it,
use control flow, round-trip through serialization (reference
samediff-examples)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np

from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
from deeplearning4j_tpu.train.updaters import Adam


def main():
    sd = SameDiff.create()
    x = sd.placeholder("input", shape=(-1, 4))
    y = sd.placeholder("label", shape=(-1, 3))
    w0 = sd.var("w0", "XAVIER", 4, 32)
    b0 = sd.var("b0", np.zeros(32, np.float32))
    w1 = sd.var("w1", "XAVIER", 32, 3)
    h = sd.nn.tanh(sd.nn.linear(x, w0, b0))
    logits = sd.op("matmul", h, w1, name="logits")
    sd.nn.softmax(logits, name="out")
    sd.loss.softmax_cross_entropy(y, logits, name="loss")
    sd.set_loss_variables("loss")
    sd.set_training_config(TrainingConfig(
        updater=Adam(1e-2),
        data_set_feature_mapping=["input"],
        data_set_label_mapping=["label"]))

    rng = np.random.RandomState(0)
    xs = rng.randn(128, 4).astype(np.float32)
    labels = (xs[:, 0] > 0).astype(int) + (xs[:, 1] > 0).astype(int)
    ys = np.eye(3, dtype=np.float32)[labels]
    for _ in range(60):
        sd.fit(xs, ys)
    print(f"loss: {sd.score():.4f}")
    acc = (np.asarray(sd.output({'input': xs}, 'out')['out']).argmax(1)
           == labels).mean()
    print(f"train accuracy: {acc:.2f}")

    # control flow: scan a running sum over a sequence inside the graph
    sd2 = SameDiff.create()
    seq = sd2.placeholder("seq", shape=(8,))
    total, partials = sd2.scan(
        lambda s, carry, t: (s.op("add", carry, t),) * 2,
        sd2.constant("z", np.float32(0.0)), seq, name="running")
    out = sd2.output({"seq": np.arange(8, dtype=np.float32)}, total)
    print(f"scan sum(0..7) = {float(np.asarray(out[total.name])):.0f}")

    # serialization round-trip
    sd.save("/tmp/samediff_model.zip")
    sd3 = SameDiff.load("/tmp/samediff_model.zip")
    a = np.asarray(sd.output({"input": xs[:4]}, "out")["out"])
    b = np.asarray(sd3.output({"input": xs[:4]}, "out")["out"])
    np.testing.assert_allclose(a, b, atol=1e-6)
    print("serialization round-trip: outputs identical")


if __name__ == "__main__":
    main()
