"""Feed-forward and convolutional layer zoo.

Covers the reference's `deeplearning4j-nn/.../nn/conf/layers/*.java` configs
and `nn/layers/**` implementations: Dense, Output, Loss, Activation, Dropout,
Embedding(+Sequence), Convolution2D (+1D/Depthwise/Separable/Deconv),
Subsampling (pooling), BatchNormalization, LocalResponseNormalization,
GlobalPooling, Upsampling, ZeroPadding, ElementWiseMultiplication.

TPU notes: convs run NHWC/HWIO via `lax.conv_general_dilated` so XLA tiles
them directly onto the MXU; pooling is `lax.reduce_window`; batch-norm in
training mode computes batch statistics inline (XLA fuses the whole
normalize-scale-shift chain into neighbouring ops — the role cuDNN's fused
batchnorm plays in the reference's platform helpers,
`libnd4j/include/ops/declarable/platform/cudnn/batchnorm.cu`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.core import InputType, Layer, PyTree
from deeplearning4j_tpu.ops.initializers import init_weights
from deeplearning4j_tpu.ops.losses import get_loss


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return int(v[0]), int(v[1])
    return int(v), int(v)


# ---------------------------------------------------------------------------
# Dense / Output / Loss
# ---------------------------------------------------------------------------

@dataclasses.dataclass(kw_only=True)
class DenseLayer(Layer):
    """Fully-connected layer (reference `DenseLayer` /
    `nn/layers/feedforward/dense/DenseLayer.java`).  Non-2D inputs are
    auto-flattened, subsuming `CnnToFeedForwardPreProcessor`."""

    n_out: int = 0
    has_bias: bool = True
    STOCHASTIC: bool = True  # input dropout

    def initialize(self, rng, input_type, dtype=jnp.float32):
        n_in = input_type.flat_size() if input_type.kind != "recurrent" else input_type.shape[-1]
        params = {"W": init_weights(rng, (n_in, self.n_out), self.winit(), dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        out_type = (InputType.recurrent(self.n_out, input_type.shape[0])
                    if input_type.kind == "recurrent"
                    else InputType.feed_forward(self.n_out))
        return params, {}, out_type

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_input_dropout(x, train, rng)
        if x.ndim > 2 and not self._is_recurrent_input(x):
            x = x.reshape(x.shape[0], -1)
        y = self._fused_dense(x, params)
        if y is not None:
            return y, state
        y = x @ params["W"]
        if self.has_bias:
            y = y + params["b"]
        return self.act_fn()(y), state

    def _fused_dense(self, x, params):
        """Route through the Pallas fused bias+activation tile when the
        kernel tier takes the call (TPU/GPU, or forced mode); None keeps
        the plain XLA lowering — the CPU/tier-1 path, bit-identical to
        before the tier existed."""
        act = self.activation if self.activation is not None else "identity"
        if not isinstance(act, str):
            return None
        from deeplearning4j_tpu.ops import pallas as tier
        b = params.get("b") if self.has_bias else None
        if tier.dispatch.resolve("fused_dense", x, params["W"], bias=b,
                                 activation=act) != "pallas":
            return None
        rows = 1
        for d in x.shape[:-1]:
            rows *= int(d)
        sc = tier.shape_class(m=rows, k=int(x.shape[-1]),
                              n=int(params["W"].shape[-1]))
        return tier.matmul.fused_dense(
            x, params["W"], bias=b, activation=act,
            tile=tier.dispatch.get_tile("fused_dense", sc),
            interpret=tier.dispatch.interpret_mode())

    def _is_recurrent_input(self, x):
        # [batch, time, features] passes through time-distributed.
        return x.ndim == 3


@dataclasses.dataclass(kw_only=True)
class OutputLayer(DenseLayer):
    """Dense + loss head (reference `OutputLayer`).  The loss consumes raw
    pre-activations for logit-fused losses (MCXENT/XENT) — the stable path —
    while `activate()` still applies the configured activation for
    `output()` calls."""

    loss: Any = "mcxent"

    def loss_fn(self):
        return get_loss(self.loss)

    def compute_loss(self, params, state, x, labels, *, train=True, rng=None,
                     mask=None):
        from deeplearning4j_tpu.ops.losses import apply_loss
        x = self.maybe_input_dropout(x, train, rng)
        if x.ndim > 2 and not self._is_recurrent_input(x):
            x = x.reshape(x.shape[0], -1)
        pre = x @ params["W"]
        if self.has_bias:
            pre = pre + params["b"]
        # loss math (softmax/log) in >= f32: upcasts bf16 logits, leaves
        # f64 gradient-check nets untouched
        pre = pre.astype(jnp.promote_types(pre.dtype, jnp.float32))
        return apply_loss(self.loss, self.act_fn(), pre, labels, mask)


@dataclasses.dataclass(kw_only=True)
class LossLayer(Layer):
    """Loss-only head, no params (reference `LossLayer`)."""

    loss: Any = "mcxent"
    REGULARIZABLE: Tuple[str, ...] = ()

    def initialize(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.act_fn()(x), state

    def compute_loss(self, params, state, x, labels, *, train=True, rng=None,
                     mask=None):
        from deeplearning4j_tpu.ops.losses import apply_loss
        return apply_loss(self.loss, self.act_fn(), x, labels, mask)


@dataclasses.dataclass(kw_only=True)
class ActivationLayer(Layer):
    """Standalone activation (reference `ActivationLayer`).

    `activation_args` parameterizes named activations (e.g. leakyrelu's
    alpha) while keeping the config JSON-serializable — the IActivation-
    with-hyperparameters case that a bare name can't carry."""

    activation_args: Optional[Dict[str, Any]] = None
    REGULARIZABLE: Tuple[str, ...] = ()

    def initialize(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        fn = self.act_fn()
        if self.activation_args:
            return fn(x, **self.activation_args), state
        return fn(x), state


@dataclasses.dataclass(kw_only=True)
class DropoutLayer(Layer):
    """Standalone dropout (reference `DropoutLayer`); `dropout` is the
    RETAIN probability per reference semantics."""

    dropout: Optional[float] = 0.5
    REGULARIZABLE: Tuple[str, ...] = ()
    STOCHASTIC: bool = True

    def initialize(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        return self.maybe_input_dropout(x, train, rng), state


@dataclasses.dataclass(kw_only=True)
class ElementWiseMultiplicationLayer(Layer):
    """Per-feature learned scale + bias (reference
    `ElementWiseMultiplicationLayer`)."""

    STOCHASTIC: bool = True

    def initialize(self, rng, input_type, dtype=jnp.float32):
        n = input_type.flat_size()
        params = {"W": jnp.ones((n,), dtype), "b": jnp.full((n,), self.bias_init, dtype)}
        return params, {}, input_type

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_input_dropout(x, train, rng)
        return self.act_fn()(x * params["W"] + params["b"]), state


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

@dataclasses.dataclass(kw_only=True)
class EmbeddingLayer(Layer):
    """Index -> vector lookup (reference `EmbeddingLayer`): input is a
    [batch] or [batch, 1] int array.  On TPU this is a gather — XLA lowers it
    natively, replacing the reference's embedding-as-onehot-matmul fallback."""

    n_in: int = 0   # vocab size
    n_out: int = 0
    has_bias: bool = False

    def initialize(self, rng, input_type, dtype=jnp.float32):
        params = {"W": init_weights(rng, (self.n_in, self.n_out), self.winit(), dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return params, {}, InputType.feed_forward(self.n_out)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        y = params["W"][idx]
        if self.has_bias:
            y = y + params["b"]
        return self.act_fn()(y), state


@dataclasses.dataclass(kw_only=True)
class EmbeddingSequenceLayer(Layer):
    """Sequence of indices -> [batch, time, n_out] (reference
    `EmbeddingSequenceLayer`)."""

    n_in: int = 0
    n_out: int = 0
    has_bias: bool = False

    def initialize(self, rng, input_type, dtype=jnp.float32):
        params = {"W": init_weights(rng, (self.n_in, self.n_out), self.winit(), dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        t = input_type.shape[0] if input_type.kind == "recurrent" else None
        return params, {}, InputType.recurrent(self.n_out, t)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        y = params["W"][x.astype(jnp.int32)]
        if self.has_bias:
            y = y + params["b"]
        return self.act_fn()(y), state


# ---------------------------------------------------------------------------
# Convolutions (NHWC / HWIO)
# ---------------------------------------------------------------------------

def _padding_2d(mode: str, padding) -> Any:
    """ConvolutionMode (Same|Truncate|Strict) + explicit padding -> the lax
    padding argument. Shared by every 2-D conv/pool layer."""
    if (mode or "Truncate").lower() == "same":
        return "SAME"
    ph, pw = _pair(padding)
    return [(ph, ph), (pw, pw)]


@dataclasses.dataclass(kw_only=True)
class ConvolutionLayer(Layer):
    """2-D convolution (reference `ConvolutionLayer` → libnd4j conv2d op +
    cuDNN platform helper).  NHWC input, HWIO kernel — the layout XLA maps
    straight onto the MXU."""

    n_out: int = 0
    kernel_size: Any = (3, 3)
    stride: Any = (1, 1)
    padding: Any = (0, 0)
    dilation: Any = (1, 1)
    convolution_mode: str = "Truncate"  # Same | Truncate | Strict
    has_bias: bool = True

    def _spatial(self, in_hw):
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        dh, dw = _pair(self.dilation)
        ph, pw = _pair(self.padding)
        if self.convolution_mode.lower() == "same":
            oh = -(-in_hw[0] // sh)
            ow = -(-in_hw[1] // sw)
        else:
            eff_kh = (kh - 1) * dh + 1
            eff_kw = (kw - 1) * dw + 1
            oh = (in_hw[0] + 2 * ph - eff_kh) // sh + 1
            ow = (in_hw[1] + 2 * pw - eff_kw) // sw + 1
        return oh, ow

    def initialize(self, rng, input_type, dtype=jnp.float32):
        h, w, c = input_type.shape
        kh, kw = _pair(self.kernel_size)
        params = {"W": init_weights(rng, (kh, kw, c, self.n_out), self.winit("RELU"), dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        oh, ow = self._spatial((h, w))
        return params, {}, InputType.convolutional(oh, ow, self.n_out)

    def _padding_arg(self):
        return _padding_2d(self.convolution_mode, self.padding)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_input_dropout(x, train, rng)
        pad = self._padding_arg()
        from deeplearning4j_tpu.ops.conv_kernels import (conv3x3_eligible,
                                                         conv3x3_same)
        # Pallas conv-backward adoption hook (default off; bias is added
        # AFTER the conv here, so the conv itself qualifies) — see
        # ops/conv_kernels.CONV_BWD_PALLAS + playbook stage 8
        if conv3x3_eligible(x.shape, params["W"].shape, None,
                            _pair(self.stride), pad,
                            _pair(self.dilation)):
            y = conv3x3_same(x, params["W"])
        else:
            y = lax.conv_general_dilated(
                x, params["W"],
                window_strides=_pair(self.stride),
                padding=pad,
                rhs_dilation=_pair(self.dilation),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
        if self.has_bias:
            y = y + params["b"]
        return self.act_fn()(y), state


@dataclasses.dataclass(kw_only=True)
class Convolution1DLayer(Layer):
    """1-D conv over [batch, time, features] (reference `Convolution1DLayer`)."""

    n_out: int = 0
    kernel_size: int = 3
    stride: int = 1
    padding: int = 0
    dilation: int = 1
    convolution_mode: str = "Same"
    has_bias: bool = True

    def initialize(self, rng, input_type, dtype=jnp.float32):
        f = input_type.shape[-1]
        k = int(self.kernel_size)
        params = {"W": init_weights(rng, (k, f, self.n_out), self.winit("RELU"), dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        t = input_type.shape[0]
        if t is not None:
            if self.convolution_mode.lower() == "same":
                t = -(-t // int(self.stride))
            else:
                eff_k = (k - 1) * int(self.dilation) + 1
                t = (t + 2 * int(self.padding) - eff_k) // int(self.stride) + 1
        return params, {}, InputType.recurrent(self.n_out, t)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_input_dropout(x, train, rng)
        pad = ("SAME" if self.convolution_mode.lower() == "same"
               else [(int(self.padding),) * 2])
        y = lax.conv_general_dilated(
            x, params["W"],
            window_strides=(int(self.stride),),
            padding=pad,
            rhs_dilation=(int(self.dilation),),
            dimension_numbers=("NWC", "WIO", "NWC"),
        )
        if self.has_bias:
            y = y + params["b"]
        return self.act_fn()(y), state


@dataclasses.dataclass(kw_only=True)
class DepthwiseConvolution2DLayer(Layer):
    """Depthwise conv (reference `DepthwiseConvolution2D`)."""

    depth_multiplier: int = 1
    kernel_size: Any = (3, 3)
    stride: Any = (1, 1)
    padding: Any = (0, 0)
    convolution_mode: str = "Truncate"
    has_bias: bool = True

    def initialize(self, rng, input_type, dtype=jnp.float32):
        h, w, c = input_type.shape
        kh, kw = _pair(self.kernel_size)
        n_out = c * self.depth_multiplier
        params = {"W": init_weights(rng, (kh, kw, 1, n_out), self.winit("RELU"), dtype)}
        if self.has_bias:
            params["b"] = jnp.full((n_out,), self.bias_init, dtype)
        helper = ConvolutionLayer(n_out=n_out, kernel_size=self.kernel_size,
                                  stride=self.stride, padding=self.padding,
                                  convolution_mode=self.convolution_mode)
        oh, ow = helper._spatial((h, w))
        return params, {}, InputType.convolutional(oh, ow, n_out)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_input_dropout(x, train, rng)
        c = x.shape[-1]
        y = lax.conv_general_dilated(
            x, params["W"],
            window_strides=_pair(self.stride),
            padding=_padding_2d(self.convolution_mode, self.padding),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=c,
        )
        if self.has_bias:
            y = y + params["b"]
        return self.act_fn()(y), state


@dataclasses.dataclass(kw_only=True)
class SeparableConvolution2DLayer(Layer):
    """Depthwise-separable conv (reference `SeparableConvolution2D`)."""

    n_out: int = 0
    depth_multiplier: int = 1
    kernel_size: Any = (3, 3)
    stride: Any = (1, 1)
    padding: Any = (0, 0)
    convolution_mode: str = "Truncate"
    has_bias: bool = True
    REGULARIZABLE: Tuple[str, ...] = ("W_depth", "W_point")

    def initialize(self, rng, input_type, dtype=jnp.float32):
        h, w, c = input_type.shape
        kh, kw = _pair(self.kernel_size)
        k1, k2 = jax.random.split(rng)
        mid = c * self.depth_multiplier
        params = {
            "W_depth": init_weights(k1, (kh, kw, 1, mid), self.winit("RELU"), dtype),
            "W_point": init_weights(k2, (1, 1, mid, self.n_out), self.winit("RELU"), dtype),
        }
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        helper = ConvolutionLayer(n_out=self.n_out, kernel_size=self.kernel_size,
                                  stride=self.stride, padding=self.padding,
                                  convolution_mode=self.convolution_mode)
        oh, ow = helper._spatial((h, w))
        return params, {}, InputType.convolutional(oh, ow, self.n_out)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_input_dropout(x, train, rng)
        c = x.shape[-1]
        y = lax.conv_general_dilated(
            x, params["W_depth"], window_strides=_pair(self.stride),
            padding=_padding_2d(self.convolution_mode, self.padding),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=c)
        y = lax.conv_general_dilated(
            y, params["W_point"], window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.has_bias:
            y = y + params["b"]
        return self.act_fn()(y), state


@dataclasses.dataclass(kw_only=True)
class Deconvolution2DLayer(Layer):
    """Transposed conv (reference `Deconvolution2D`)."""

    n_out: int = 0
    kernel_size: Any = (2, 2)
    stride: Any = (2, 2)
    padding: Any = (0, 0)
    convolution_mode: str = "Truncate"
    has_bias: bool = True

    def initialize(self, rng, input_type, dtype=jnp.float32):
        h, w, c = input_type.shape
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        params = {"W": init_weights(rng, (kh, kw, c, self.n_out), self.winit("RELU"), dtype)}
        if self.has_bias:
            params["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        if self.convolution_mode.lower() == "same":
            oh, ow = h * sh, w * sw
        else:
            oh = sh * (h - 1) + kh - 2 * ph
            ow = sw * (w - 1) + kw - 2 * pw
        return params, {}, InputType.convolutional(oh, ow, self.n_out)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_input_dropout(x, train, rng)
        if self.convolution_mode.lower() == "same":
            pad = "SAME"
        else:
            # lax.conv_transpose explicit pads apply to the lhs-dilated
            # input; reference-style deconv padding p maps to (k-1-p) so the
            # output is s*(h-1) + k - 2p, matching the reference shape fn.
            kh, kw = _pair(self.kernel_size)
            ph, pw = _pair(self.padding)
            pad = [(kh - 1 - ph, kh - 1 - ph), (kw - 1 - pw, kw - 1 - pw)]
        # gradient-form transposed conv (TF/Keras/reference convention):
        # lax.conv_transpose slides the kernel in correlation orientation,
        # spatially flipped relative to the gradient form — flip here.
        # Without this, Conv2DTranspose imports are spatially mirrored
        # (caught by the op-validation sweep; the old conformance test's
        # deconv fed an avg-pool, which is flip-invariant).
        y = lax.conv_transpose(
            x, jnp.flip(params["W"], (0, 1)), strides=_pair(self.stride),
            padding=pad, dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.has_bias:
            y = y + params["b"]
        return self.act_fn()(y), state


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------

@dataclasses.dataclass(kw_only=True)
class SubsamplingLayer(Layer):
    """Spatial pooling (reference `SubsamplingLayer`): MAX | AVG | SUM |
    PNORM over NHWC windows via `lax.reduce_window`."""

    pooling_type: str = "MAX"
    kernel_size: Any = (2, 2)
    stride: Any = (2, 2)
    padding: Any = (0, 0)
    convolution_mode: str = "Truncate"
    pnorm: int = 2
    REGULARIZABLE: Tuple[str, ...] = ()

    def initialize(self, rng, input_type, dtype=jnp.float32):
        h, w, c = input_type.shape
        helper = ConvolutionLayer(n_out=c, kernel_size=self.kernel_size,
                                  stride=self.stride, padding=self.padding,
                                  convolution_mode=self.convolution_mode)
        oh, ow = helper._spatial((h, w))
        return {}, {}, InputType.convolutional(oh, ow, c)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        pad2 = _padding_2d(self.convolution_mode, self.padding)
        pad = pad2
        if pad != "SAME":
            pad = ((0, 0), tuple(pad[0]), tuple(pad[1]), (0, 0))
        dims = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        pt = self.pooling_type.upper()
        if pt == "MAX":
            from deeplearning4j_tpu.ops.pool_kernels import max_pool2d
            p2 = pad2 if isinstance(pad2, str) \
                else (tuple(pad2[0]), tuple(pad2[1]))
            y = max_pool2d(x, (kh, kw), (sh, sw), p2)
        elif pt in ("AVG", "AVERAGE"):
            s = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
            ones = jnp.ones_like(x)
            cnt = lax.reduce_window(ones, 0.0, lax.add, dims, strides, pad)
            y = s / cnt
        elif pt == "SUM":
            y = lax.reduce_window(x, 0.0, lax.add, dims, strides, pad)
        elif pt == "PNORM":
            p = float(self.pnorm)
            s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, dims, strides, pad)
            y = s ** (1.0 / p)
        else:
            raise ValueError(f"Unknown pooling type {self.pooling_type}")
        return y, state


@dataclasses.dataclass(kw_only=True)
class GlobalPoolingLayer(Layer):
    """Global pooling over spatial or time dims (reference
    `GlobalPoolingLayer`), with mask support for variable-length sequences."""

    pooling_type: str = "MAX"
    pnorm: int = 2
    REGULARIZABLE: Tuple[str, ...] = ()

    def initialize(self, rng, input_type, dtype=jnp.float32):
        if input_type.kind == "convolutional":
            c = input_type.shape[-1]
            return {}, {}, InputType.feed_forward(c)
        if input_type.kind == "recurrent":
            return {}, {}, InputType.feed_forward(input_type.shape[-1])
        return {}, {}, input_type

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        axes = tuple(range(1, x.ndim - 1))
        pt = self.pooling_type.upper()
        if mask is not None and x.ndim == 3:
            m = mask[..., None].astype(x.dtype)
            if pt == "MAX":
                y = jnp.max(jnp.where(m > 0, x, -jnp.inf), axis=1)
            elif pt in ("AVG", "AVERAGE"):
                y = jnp.sum(x * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
            elif pt == "SUM":
                y = jnp.sum(x * m, axis=1)
            else:
                p = float(self.pnorm)
                y = jnp.sum((jnp.abs(x) ** p) * m, axis=1) ** (1.0 / p)
            return y, state
        if pt == "MAX":
            y = jnp.max(x, axis=axes)
        elif pt in ("AVG", "AVERAGE"):
            y = jnp.mean(x, axis=axes)
        elif pt == "SUM":
            y = jnp.sum(x, axis=axes)
        elif pt == "PNORM":
            p = float(self.pnorm)
            y = jnp.sum(jnp.abs(x) ** p, axis=axes) ** (1.0 / p)
        else:
            raise ValueError(f"Unknown pooling type {self.pooling_type}")
        return y, state


@dataclasses.dataclass(kw_only=True)
class Upsampling2DLayer(Layer):
    """Nearest-neighbour upsampling (reference `Upsampling2D`)."""

    size: Any = (2, 2)
    REGULARIZABLE: Tuple[str, ...] = ()

    def initialize(self, rng, input_type, dtype=jnp.float32):
        h, w, c = input_type.shape
        sh, sw = _pair(self.size)
        return {}, {}, InputType.convolutional(h * sh, w * sw, c)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        sh, sw = _pair(self.size)
        y = jnp.repeat(jnp.repeat(x, sh, axis=1), sw, axis=2)
        return y, state


@dataclasses.dataclass(kw_only=True)
class ZeroPaddingLayer(Layer):
    """Spatial zero padding (reference `ZeroPaddingLayer`).  `padding`
    accepts an int, a symmetric (ph, pw) pair, or per-side
    ((top, bottom), (left, right)) — the Keras ZeroPadding2D forms."""

    padding: Any = (1, 1)
    REGULARIZABLE: Tuple[str, ...] = ()

    def _sides(self):
        ph, pw = _pair(self.padding) if not (
            isinstance(self.padding, (tuple, list))
            and len(self.padding) == 2
            and isinstance(self.padding[0], (tuple, list))) else self.padding
        top, bot = _pair(ph)
        left, right = _pair(pw)
        return (int(top), int(bot)), (int(left), int(right))

    def initialize(self, rng, input_type, dtype=jnp.float32):
        h, w, c = input_type.shape
        (t, b), (le, r) = self._sides()
        return {}, {}, InputType.convolutional(h + t + b, w + le + r, c)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        (t, b), (le, r) = self._sides()
        return jnp.pad(x, ((0, 0), (t, b), (le, r), (0, 0))), state


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

@dataclasses.dataclass(kw_only=True)
class BatchNormalizationLayer(Layer):
    """Batch normalization (reference `BatchNormalization` layer; running
    stats follow the reference's `decay` convention:
    running = decay * running + (1-decay) * batch)."""

    decay: float = 0.9
    eps: float = 1e-5
    lock_gamma_beta: bool = False
    REGULARIZABLE: Tuple[str, ...] = ()
    HAS_STATE: bool = True

    def initialize(self, rng, input_type, dtype=jnp.float32):
        c = input_type.shape[-1]
        params = {} if self.lock_gamma_beta else {
            "gamma": jnp.ones((c,), dtype), "beta": jnp.zeros((c,), dtype)}
        state = {"mean": jnp.zeros((c,), dtype), "var": jnp.ones((c,), dtype)}
        return params, state, input_type

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        axes = tuple(range(x.ndim - 1))
        # statistics in >= f32 even under a bf16 compute_dtype
        # (mixed-precision invariant: normalizer math accumulates f32;
        # f64 nets keep f64); running stats keep their stored dtype so
        # state shapes/dtypes are step-stable
        xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
        if train:
            # one-pass moments (E[xs], E[xs^2]): both reductions read the
            # activation once and fuse into a single multi-output kernel —
            # jnp.var's centered form would re-read x after computing the
            # mean, doubling BN's HBM traffic (measured ~5ms/step of
            # reduce_sum on ResNet-50 b64 before this change).  Shifting by
            # the running mean keeps E[xs]^2 << E[xs^2] so the f32
            # subtraction doesn't cancel catastrophically on large-mean
            # activations (shifted-moments trick; the shift is a per-channel
            # constant that fuses into the same kernel).
            shift = state["mean"].astype(xf.dtype)
            xs = xf - shift
            m1 = jnp.mean(xs, axis=axes)
            mean = m1 + shift
            var = jnp.maximum(jnp.mean(xs * xs, axis=axes) - m1 * m1, 0.0)
            new_state = {
                "mean": (self.decay * state["mean"]
                         + (1 - self.decay) * mean.astype(state["mean"].dtype)),
                "var": (self.decay * state["var"]
                        + (1 - self.decay) * var.astype(state["var"].dtype)),
            }
        else:
            mean, var = (state["mean"].astype(jnp.float32),
                         state["var"].astype(jnp.float32))
            new_state = state
        y = ((xf - mean) / jnp.sqrt(var + self.eps)).astype(x.dtype)
        if not self.lock_gamma_beta:
            y = y * params["gamma"] + params["beta"]
        return self.act_fn()(y), new_state


@dataclasses.dataclass(kw_only=True)
class LocalResponseNormalizationLayer(Layer):
    """LRN across channels (reference `LocalResponseNormalization`)."""

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75
    REGULARIZABLE: Tuple[str, ...] = ()

    def initialize(self, rng, input_type, dtype=jnp.float32):
        return {}, {}, input_type

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        half = self.n // 2
        sq = x * x
        # sum over a window of `n` adjacent channels (last axis, NHWC)
        padded = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(half, half)])
        window = sum(
            lax.slice_in_dim(padded, i, i + x.shape[-1], axis=x.ndim - 1)
            for i in range(self.n)
        )
        return x / (self.k + self.alpha * window) ** self.beta, state


@dataclasses.dataclass(kw_only=True)
class LayerNormalizationLayer(Layer):
    """Layer norm over the feature axis (capability-exceeding addition used
    by the BERT/attention stack; the reference only has `LayerNorm` as a
    SameDiff op, `libnd4j .../generic/nn/layer_norm.cpp`)."""

    eps: float = 1e-5
    REGULARIZABLE: Tuple[str, ...] = ()

    def initialize(self, rng, input_type, dtype=jnp.float32):
        c = input_type.shape[-1]
        return {"gamma": jnp.ones((c,), dtype), "beta": jnp.zeros((c,), dtype)}, {}, input_type

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        # measured dispatch: Pallas fused LayerNorm on TPU, jnp otherwise
        from deeplearning4j_tpu.ops.norm_kernels import fused_layer_norm
        return fused_layer_norm(x, params["gamma"], params["beta"],
                                self.eps), state
