"""Finite-difference gradient checks (reference: GradientCheckTests family,
SURVEY.md §4 — central differences vs backprop in double precision)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn import (
    BatchNormalizationLayer, ConvolutionLayer, DenseLayer, InputType,
    MultiLayerNetwork, NeuralNetConfiguration, OutputLayer, SubsamplingLayer)
from deeplearning4j_tpu.train import Sgd
from deeplearning4j_tpu.train.gradientcheck import check_gradients


def build_net(layers, input_type, seed=7):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(0.1)).weight_init("XAVIER")
            .dtype("float64")
            .list(layers).set_input_type(input_type).build())
    return MultiLayerNetwork(conf).init()


def score_fn_for(net, x, y, jit=True):
    x = jnp.asarray(x, jnp.float64)
    y = jnp.asarray(y, jnp.float64)

    def score(params):
        return net._loss(params, net.state_, x, y, None)[0]

    # one compiled program for the check's many evaluations
    return jax.jit(score) if jit else score


def test_mlp_gradients():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 4))
    y = np.eye(3)[rng.integers(0, 3, 8)]
    net = build_net([
        DenseLayer(n_out=6, activation="tanh"),
        OutputLayer(n_out=3, loss="mcxent", activation="softmax"),
    ], InputType.feed_forward(4))
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=None, verbose=True)


def test_mlp_gradients_with_l1_l2():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(8, 4))
    y = np.eye(2)[rng.integers(0, 2, 8)]
    conf = (NeuralNetConfiguration.builder()
            .seed(3).updater(Sgd(0.1)).weight_init("XAVIER")
            .l1(0.01).l2(0.02).dtype("float64")
            .list([DenseLayer(n_out=5, activation="sigmoid"),
                   OutputLayer(n_out=2, loss="mcxent", activation="softmax")])
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=None, verbose=True)


def test_cnn_gradients():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(4, 6, 6, 2))
    y = np.eye(2)[rng.integers(0, 2, 4)]
    net = build_net([
        ConvolutionLayer(n_out=3, kernel_size=3, activation="tanh",
                         weight_init="XAVIER"),
        SubsamplingLayer(kernel_size=2, stride=2),
        OutputLayer(n_out=2, loss="mcxent", activation="softmax"),
    ], InputType.convolutional(6, 6, 2))
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=32, verbose=True)


def test_batchnorm_gradients():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(8, 5))
    y = np.eye(2)[rng.integers(0, 2, 8)]
    net = build_net([
        DenseLayer(n_out=6, activation="identity"),
        BatchNormalizationLayer(),
        OutputLayer(n_out=2, loss="mcxent", activation="softmax"),
    ], InputType.feed_forward(5))
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=None, verbose=True)


@pytest.mark.parametrize("loss,act", [
    ("mse", "identity"), ("l2", "identity"), ("l1", "tanh"),
    ("xent", "sigmoid"), ("negativeloglikelihood", "softmax"),
])
def test_loss_gradients(loss, act):
    rng = np.random.default_rng(5)
    x = rng.normal(size=(6, 3))
    if loss in ("xent",):
        y = (rng.random((6, 2)) > 0.5).astype(np.float64)
    elif loss == "negativeloglikelihood":
        y = np.eye(2)[rng.integers(0, 2, 6)]
    else:
        y = rng.normal(size=(6, 2))
    net = build_net([
        DenseLayer(n_out=4, activation="tanh"),
        OutputLayer(n_out=2, loss=loss, activation=act),
    ], InputType.feed_forward(3))
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=None, verbose=True)


# ---------------------------------------------------------------------------
# Extended-layer gradient checks (conv3d, locally-connected, PReLU, center
# loss, separable conv) — the GradientCheckTests family widened
# ---------------------------------------------------------------------------

def test_conv3d_gradients():
    from deeplearning4j_tpu.nn import Convolution3DLayer, Subsampling3DLayer
    rng = np.random.default_rng(10)
    x = rng.normal(size=(2, 4, 4, 4, 2))
    y = np.eye(2)[rng.integers(0, 2, 2)]
    net = build_net([
        Convolution3DLayer(n_out=3, kernel_size=2, convolution_mode="Same",
                           activation="tanh"),
        Subsampling3DLayer(pooling_type="AVG", kernel_size=2, stride=2),
        OutputLayer(n_out=2, loss="mcxent", activation="softmax"),
    ], InputType.convolutional3d(4, 4, 4, 2))
    # eagerly: the 3-D pool's generic `reduce_window` has no reverse-mode
    # rule under `jit`
    assert check_gradients(score_fn_for(net, x, y, jit=False), net.params_,
                           max_params_per_leaf=20)


def test_locally_connected_gradients():
    from deeplearning4j_tpu.nn import LocallyConnected2DLayer
    rng = np.random.default_rng(11)
    x = rng.normal(size=(3, 5, 5, 2))
    y = np.eye(2)[rng.integers(0, 2, 3)]
    net = build_net([
        LocallyConnected2DLayer(n_out=3, kernel_size=2, activation="tanh"),
        OutputLayer(n_out=2, loss="mcxent", activation="softmax"),
    ], InputType.convolutional(5, 5, 2))
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=20)


def test_prelu_gradients():
    from deeplearning4j_tpu.nn import PReLULayer
    rng = np.random.default_rng(12)
    x = rng.normal(size=(6, 4))
    y = np.eye(2)[rng.integers(0, 2, 6)]
    net = build_net([
        DenseLayer(n_out=5, activation="identity"),
        PReLULayer(alpha_init=0.3),
        OutputLayer(n_out=2, loss="mcxent", activation="softmax"),
    ], InputType.feed_forward(4))
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=None)


def test_center_loss_gradients():
    from deeplearning4j_tpu.nn import CenterLossOutputLayer
    rng = np.random.default_rng(13)
    x = rng.normal(size=(6, 4))
    y = np.eye(3)[rng.integers(0, 3, 6)]
    net = build_net([
        DenseLayer(n_out=5, activation="tanh"),
        CenterLossOutputLayer(n_out=3, lambda_=0.3),
    ], InputType.feed_forward(4))
    # seed centers off zero so their gradient is informative
    net.params_["layer_1"]["centers"] = jnp.asarray(
        rng.normal(size=(3, 5)) * 0.1)
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=None)


def test_separable_conv_gradients():
    from deeplearning4j_tpu.nn import SeparableConvolution2DLayer
    rng = np.random.default_rng(14)
    x = rng.normal(size=(2, 5, 5, 2))
    y = np.eye(2)[rng.integers(0, 2, 2)]
    net = build_net([
        SeparableConvolution2DLayer(n_out=3, kernel_size=3,
                                    convolution_mode="Same",
                                    activation="tanh"),
        OutputLayer(n_out=2, loss="mcxent", activation="softmax"),
    ], InputType.convolutional(5, 5, 2))
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=20)


def test_capsnet_gradients():
    """CapsNet stack gradient check: dynamic routing is a fixed-iteration
    unrolled loop differentiated end-to-end."""
    from deeplearning4j_tpu.nn import (CapsuleLayer, CapsuleStrengthLayer,
                                       LossLayer, PrimaryCapsules)
    rng = np.random.default_rng(5)
    x = rng.random((4, 8, 8, 1))
    y = np.eye(2)[rng.integers(0, 2, 4)]
    net = build_net([
        PrimaryCapsules(capsules=2, capsule_dim=4, kernel_size=5, stride=2),
        CapsuleLayer(capsules=2, capsule_dim=4, routings=2),
        CapsuleStrengthLayer(),
        LossLayer(loss="mcxent", activation="softmax"),
    ], InputType.convolutional(8, 8, 1))
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=12, verbose=True)


def test_samediff_custom_layer_gradients():
    import dataclasses

    from deeplearning4j_tpu.nn import SameDiffLayer, register_layer

    @register_layer
    @dataclasses.dataclass(kw_only=True)
    class _Bilinear(SameDiffLayer):
        n_out: int = 0

        def define_parameters(self, input_type):
            f = input_type.shape[-1]
            return {"W": (f, self.n_out), "U": (f, self.n_out)}

        def define_layer(self, params, x, mask=None):
            return jnp.tanh(x @ params["W"]) * (x @ params["U"])

        def get_output_type(self, input_type):
            return InputType.feed_forward(self.n_out)

    rng = np.random.default_rng(6)
    x = rng.normal(size=(6, 3))
    y = np.eye(2)[rng.integers(0, 2, 6)]
    net = build_net([
        _Bilinear(n_out=5),
        OutputLayer(n_out=2, loss="mcxent", activation="softmax"),
    ], InputType.feed_forward(3))
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=None, verbose=True)


def test_shape_op_layers_gradients():
    """Reshape/Permute/RepeatVector/Flatten/TimeDistributed path: the
    shape pipeline is param-free but must route gradients exactly through
    to surrounding layers (round-3 layers, reference KerasReshape etc.)."""
    from deeplearning4j_tpu.nn import (FlattenLayer, PermuteLayer,
                                       RepeatVectorLayer, ReshapeLayer,
                                       TimeDistributed)
    rng = np.random.default_rng(5)
    x = rng.normal(size=(4, 6))
    y = np.eye(2)[rng.integers(0, 2, 4)]
    net = build_net([
        DenseLayer(n_out=8, activation="tanh"),
        RepeatVectorLayer(n=4),             # [B,4,8]
        TimeDistributed(underlying=DenseLayer(n_out=6, activation="tanh")),
        PermuteLayer(dims=(2, 1)),          # [B,6,4]
        ReshapeLayer(target_shape=(3, 8)),  # [B,3,8]
        FlattenLayer(),                     # [B,24]
        OutputLayer(n_out=2, loss="mcxent", activation="softmax"),
    ], InputType.feed_forward(6))
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=None, verbose=True)


def test_bidirectional_return_last_gradients():
    from deeplearning4j_tpu.nn import Bidirectional, LSTM
    rng = np.random.default_rng(6)
    x = rng.normal(size=(3, 5, 4))
    y = np.eye(2)[rng.integers(0, 2, 3)]
    net = build_net([
        Bidirectional(fwd=LSTM(n_out=3), mode="CONCAT", return_last=True),
        OutputLayer(n_out=2, loss="mcxent", activation="softmax"),
    ], InputType.recurrent(4, 5))
    assert check_gradients(score_fn_for(net, x, y), net.params_,
                           max_params_per_leaf=8, verbose=True)
