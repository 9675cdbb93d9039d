"""Custom layers via the SameDiffLayer escape hatch + CapsNet (reference
samediff-layer examples and the CapsNet config classes)."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import dataclasses                                         # noqa: E402

import jax                                                 # noqa: E402
import numpy as np                                         # noqa: E402

from deeplearning4j_tpu.nn import (CapsuleLayer,           # noqa: E402
                                   CapsuleStrengthLayer, InputType,
                                   LossLayer, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer,
                                   PrimaryCapsules, SameDiffLayer,
                                   register_layer)
from deeplearning4j_tpu.train.updaters import Adam         # noqa: E402


@register_layer
@dataclasses.dataclass(kw_only=True)
class GatedDense(SameDiffLayer):
    """out = (xW + b) * sigmoid(xG): declare params, write the forward in
    plain jnp — the whole escape-hatch contract."""

    n_out: int = 0

    def define_parameters(self, input_type):
        f = input_type.shape[-1]
        return {"W": (f, self.n_out), "G": (f, self.n_out),
                "b": ((self.n_out,), "ZERO")}

    def define_layer(self, params, x, mask=None):
        return (x @ params["W"] + params["b"]) * jax.nn.sigmoid(
            x @ params["G"])

    def get_output_type(self, input_type):
        return InputType.feed_forward(self.n_out)


def main():
    rng = np.random.RandomState(0)

    # --- custom gated layer in a standard network ---
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-2))
            .list([GatedDense(n_out=24),
                   OutputLayer(n_out=2, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(8)).build())
    net = MultiLayerNetwork(conf).init()
    x = rng.randn(64, 8).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]
    for _ in range(40):
        net.fit(x, y)
    print(f"gated-dense custom layer loss: {net.score():.4f}")
    # registered subclasses serialize like built-ins
    net.save("/tmp/gated.zip")
    print("saved/loadable:", bool(MultiLayerNetwork.load("/tmp/gated.zip")))

    # --- CapsNet: primary capsules -> dynamic routing -> lengths ---
    caps_conf = (NeuralNetConfiguration.builder().seed(0)
                 .updater(Adam(3e-3))
                 .list([PrimaryCapsules(capsules=4, capsule_dim=4,
                                        kernel_size=5, stride=2),
                        CapsuleLayer(capsules=3, capsule_dim=8,
                                     routings=3),
                        CapsuleStrengthLayer(),
                        LossLayer(loss="mcxent", activation="softmax")])
                 .set_input_type(InputType.convolutional(12, 12, 1))
                 .build())
    caps = MultiLayerNetwork(caps_conf).init()
    labels = rng.randint(0, 3, 48)
    imgs = np.zeros((48, 12, 12, 1), np.float32)
    for i, c in enumerate(labels):        # class = bright quadrant
        r, col = divmod(c, 2)
        imgs[i, r * 6:(r + 1) * 6, col * 6:(col + 1) * 6] = 1.0
    yc = np.eye(3, dtype=np.float32)[labels]
    for _ in range(50):
        caps.fit(imgs, yc)
    acc = (np.asarray(caps.output(imgs)).argmax(1) == labels).mean()
    print(f"capsnet quadrant task accuracy: {acc:.2f}")


if __name__ == "__main__":
    main()
