"""Pallas conv wgrad prototype — interpret-mode correctness vs the XLA
autodiff reference (the on-chip A/B of 2026-07-31 is quoted in
`ops/conv_kernels.py` and PERF.md)."""
import numpy as np
import pytest

import jax.numpy as jnp

from deeplearning4j_tpu.ops.conv_kernels import (conv3x3_wgrad_tpu,
                                                 conv3x3_wgrad_xla)

rs = np.random.RandomState(0)


@pytest.mark.parametrize("B,H,W,Ci,Co", [
    (2, 8, 8, 8, 16),       # even rows, bh=8
    (1, 7, 7, 16, 8),       # odd rows, bh=7 (the ResNet 7x7 tail shape)
    (2, 14, 14, 8, 8),      # bh=14
])
def test_wgrad_matches_xla(B, H, W, Ci, Co):
    x = jnp.asarray(rs.randn(B, H, W, Ci).astype(np.float32) * 0.5)
    dy = jnp.asarray(rs.randn(B, H, W, Co).astype(np.float32) * 0.5)
    got = np.asarray(conv3x3_wgrad_tpu(x, dy, interpret=True))
    want = np.asarray(conv3x3_wgrad_xla(x, dy))
    assert got.shape == (3, 3, Ci, Co)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_wgrad_bf16_inputs_accumulate_f32():
    x = jnp.asarray(rs.randn(2, 8, 8, 8).astype(np.float32))
    dy = jnp.asarray(rs.randn(2, 8, 8, 8).astype(np.float32))
    got = np.asarray(conv3x3_wgrad_tpu(x.astype(jnp.bfloat16),
                                       dy.astype(jnp.bfloat16),
                                       interpret=True))
    want = np.asarray(conv3x3_wgrad_xla(x, dy))
    assert got.dtype == np.float32
    # bf16 INPUT rounding (not accumulation — that is f32) bounds the
    # agreement: ~0.4% relative on dW values of magnitude ~10
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=0.12)


def test_wgrad_rejects_mismatched_shapes():
    x = jnp.zeros((1, 8, 8, 4))
    dy = jnp.zeros((1, 4, 8, 4))
    with pytest.raises(ValueError, match="mismatches"):
        conv3x3_wgrad_tpu(x, dy, interpret=True)


# ---- dgrad (conv-backward-data) ----
from deeplearning4j_tpu.ops.conv_kernels import (conv3x3_dgrad_tpu,  # noqa: E402
                                                 conv3x3_dgrad_xla)


@pytest.mark.parametrize("B,H,W,Ci,Co", [
    (2, 8, 8, 8, 16),       # even rows, bh=8
    (1, 7, 7, 16, 8),       # odd rows, bh=7 (the ResNet 7x7 tail shape)
    (2, 14, 14, 8, 8),      # bh=14
])
def test_dgrad_matches_xla(B, H, W, Ci, Co):
    dy = jnp.asarray(rs.randn(B, H, W, Co).astype(np.float32) * 0.5)
    w = jnp.asarray(rs.randn(3, 3, Ci, Co).astype(np.float32) * 0.5)
    got = np.asarray(conv3x3_dgrad_tpu(dy, w, interpret=True))
    want = np.asarray(conv3x3_dgrad_xla(dy, w))
    assert got.shape == (B, H, W, Ci)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_dgrad_bf16_inputs_accumulate_f32():
    dy = jnp.asarray(rs.randn(2, 8, 8, 8).astype(np.float32))
    w = jnp.asarray(rs.randn(3, 3, 8, 8).astype(np.float32) * 0.3)
    got = np.asarray(conv3x3_dgrad_tpu(dy.astype(jnp.bfloat16),
                                       w.astype(jnp.bfloat16),
                                       interpret=True))
    want = np.asarray(conv3x3_dgrad_xla(dy, w))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=0.12)


def test_dgrad_rejects_bad_filter():
    dy = jnp.zeros((1, 8, 8, 4))
    w = jnp.zeros((5, 5, 4, 4))
    with pytest.raises(ValueError, match="not \\[3, 3"):
        conv3x3_dgrad_tpu(dy, w, interpret=True)


# ---- measured-dispatch adoption hook ----
from deeplearning4j_tpu.ops.conv_kernels import (CONV_BWD_PALLAS,  # noqa: E402
                                                 conv3x3_same)


def test_conv_bwd_pallas_hook_grads_match_xla():
    """With the adoption flags on (interpret mode), the conv2d op's
    backward runs the Pallas wgrad+dgrad kernels and must produce the
    same gradients as the XLA path — the train-step-level contract the
    on-chip A/B (playbook stage 8) assumes."""
    import jax
    from deeplearning4j_tpu.autodiff.ops import OP_TABLE

    x = jnp.asarray(rs.randn(2, 8, 8, 4).astype(np.float32) * 0.5)
    w = jnp.asarray(rs.randn(3, 3, 4, 8).astype(np.float32) * 0.3)
    tgt = jnp.asarray(rs.randn(2, 8, 8, 8).astype(np.float32))

    def loss(x_, w_):
        y = OP_TABLE["conv2d"](x_, w_)
        return jnp.sum((y - tgt) ** 2)

    gx_ref, gw_ref = jax.grad(loss, (0, 1))(x, w)

    old = dict(CONV_BWD_PALLAS)
    try:
        CONV_BWD_PALLAS.update(wgrad=True, dgrad=True, interpret=True)
        out_hook = OP_TABLE["conv2d"](x, w)
        # forward identical (same XLA conv)
        np.testing.assert_allclose(
            np.asarray(out_hook),
            np.asarray(conv3x3_same(x, w)), rtol=1e-6)
        gx, gw = jax.grad(loss, (0, 1))(x, w)
    finally:
        CONV_BWD_PALLAS.clear()
        CONV_BWD_PALLAS.update(old)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               rtol=1e-4, atol=1e-4)
    # flags off again: hook must not engage (plain path, bias works)
    y = OP_TABLE["conv2d"](x, w, jnp.zeros(8, jnp.float32))
    assert y.shape == (2, 8, 8, 8)


def test_conv_layer_hook_training_matches_xla():
    """Layer-level contract: a small conv net trains identically with the
    Pallas backward hook on (interpret) and off."""
    import jax
    from deeplearning4j_tpu.nn import (ConvolutionLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration,
                                       OutputLayer)
    from deeplearning4j_tpu.train import Sgd

    def build():
        conf = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1))
                .list([ConvolutionLayer(n_out=4, kernel_size=3,
                                        convolution_mode="Same",
                                        has_bias=False,
                                        activation="relu"),
                       OutputLayer(n_out=3, loss="mcxent",
                                   activation="softmax")])
                .set_input_type(InputType.convolutional(6, 6, 2)).build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.RandomState(0)
    x = rng.rand(4, 6, 6, 2).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 4)]

    net_a = build()
    net_a.fit(x, y)
    ref = np.asarray(net_a.params())

    old = dict(CONV_BWD_PALLAS)
    try:
        CONV_BWD_PALLAS.update(wgrad=True, dgrad=True, interpret=True)
        net_b = build()
        net_b.fit(x, y)
        got = np.asarray(net_b.params())
    finally:
        CONV_BWD_PALLAS.clear()
        CONV_BWD_PALLAS.update(old)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
