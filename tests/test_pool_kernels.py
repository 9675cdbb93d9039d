"""Taps max-pool backward vs XLA select-and-scatter (ops/pool_kernels.py).

Reference role: cuDNN PoolingBackward in CudnnSubsamplingHelper; here the
taps VJP is the TPU-shaped alternative, adopted only on measurement
(it has never been timed on the chip)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from deeplearning4j_tpu.ops.pool_kernels import (POOL_BWD_TAPS,
                                                 max_pool2d_taps)


def _xla_pool(x, kernel, stride, padding):
    pad = padding
    if not isinstance(pad, str):
        pad = ((0, 0), tuple(pad[0]), tuple(pad[1]), (0, 0))
    return lax.reduce_window(x, -jnp.inf, lax.max,
                             (1,) + tuple(kernel) + (1,),
                             (1,) + tuple(stride) + (1,), pad)


CONFIGS = [
    ((3, 3), (2, 2), "SAME", (2, 13, 13, 4)),      # resnet stem shape class
    ((2, 2), (2, 2), "VALID", (2, 12, 12, 3)),
    ((3, 3), (1, 1), "SAME", (1, 9, 9, 2)),
    ((3, 2), (2, 3), "VALID", (2, 11, 10, 3)),     # odd kernel/stride mix
    ((3, 3), (2, 2), ((0, 1), (1, 0)), (1, 10, 10, 2)),  # explicit asym
    ((2, 2), (2, 2), "VALID", (1, 13, 13, 1)),     # cropped VALID tail
]


@pytest.mark.parametrize("kernel,stride,padding,shape", CONFIGS)
def test_taps_forward_matches_xla(kernel, stride, padding, shape):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(*shape).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(max_pool2d_taps(x, kernel, stride, padding)),
        np.asarray(_xla_pool(x, kernel, stride, padding)))


@pytest.mark.parametrize("kernel,stride,padding,shape", CONFIGS)
def test_taps_grad_matches_xla_on_distinct_values(kernel, stride, padding,
                                                  shape):
    """With no exact ties (continuous random values), the taps VJP must
    equal XLA's select-and-scatter gradient exactly."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.rand(*shape).astype(np.float32))
    t = _xla_pool(x, kernel, stride, padding) * 0.7

    g_taps = jax.grad(
        lambda a: jnp.sum((max_pool2d_taps(a, kernel, stride, padding)
                           - t) ** 2))(x)
    g_xla = jax.grad(
        lambda a: jnp.sum((_xla_pool(a, kernel, stride, padding)
                           - t) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g_taps), np.asarray(g_xla),
                               rtol=1e-6, atol=1e-6)


def test_taps_grad_splits_ties_conservatively():
    """On a constant input every window ties everywhere; the taps VJP
    splits dy evenly — sum(dx) must still equal sum(dy) (a valid
    subgradient), where select-and-scatter gives all to the first max."""
    x = jnp.ones((1, 8, 8, 1), jnp.float32)
    y, vjp = jax.vjp(
        lambda a: max_pool2d_taps(a, (2, 2), (2, 2), "VALID"), x)
    dy = jnp.full_like(y, 3.0)
    (dx,) = vjp(dy)
    assert np.isclose(float(jnp.sum(dx)), float(jnp.sum(dy)))
    # even split: each of the 4 window positions gets dy/4
    np.testing.assert_allclose(np.asarray(dx), 0.75)


def test_layer_routes_through_flag():
    """SubsamplingLayer takes the taps path only when the flag is on, and
    training results stay consistent (no ties in random data)."""
    from deeplearning4j_tpu.nn import (ConvolutionLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer,
                                       SubsamplingLayer)
    from deeplearning4j_tpu.train import Adam

    def build():
        conf = (NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-2))
                .list([ConvolutionLayer(n_out=4, kernel_size=3,
                                        convolution_mode="Same"),
                       SubsamplingLayer(kernel_size=3, stride=2,
                                        convolution_mode="Same"),
                       OutputLayer(n_out=2, loss="mcxent",
                                   activation="softmax")])
                .set_input_type(InputType.convolutional(12, 12, 2)).build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.RandomState(0)
    x = rng.rand(8, 12, 12, 2).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.randint(0, 2, 8)]

    a = build()
    for _ in range(3):
        a.fit(x, y)
    old = dict(POOL_BWD_TAPS)
    try:
        POOL_BWD_TAPS["enabled"] = True
        b = build()
        for _ in range(3):
            b.fit(x, y)
    finally:
        POOL_BWD_TAPS.clear()
        POOL_BWD_TAPS.update(old)
    np.testing.assert_allclose(np.asarray(a.params()),
                               np.asarray(b.params()), rtol=2e-5,
                               atol=1e-6)
