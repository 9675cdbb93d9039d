"""Int8 weight-quantization primitives for the inference path.

The cuDNN case (PAPERS.md, arXiv 1410.0759) is that inference throughput
lives in low-precision primitives; TVM (arXiv 1802.04799) adds that
quantized programs must be first-class *compiled artifacts*.  These
kernels supply the math half of that contract for `quant/` (the artifact
half lives in `compile/fingerprint.py` + the persistent executable cache):

- `QTensor`: a pytree-registered (int8 values, f32 per-channel scales)
  pair.  Because it is a pytree node, the quantized leaves flow through
  `jit` / `device_put` / `tree_map` / fingerprint `tree_spec` untouched —
  the int8 buffer is what sits in device memory, which is exactly what
  the fleet's residency accounting measures.
- `quantize_tensor` / `dequantize`: per-channel symmetric int8 with the
  scale on the *output* axis, so `x @ W ≈ (x @ W_q) * scale[None, :]` is
  an identity up to rounding — the dequantize happens AFTER the matmul,
  inside the jitted program, in the accumulating dtype (guide: Patterns —
  Quantization Kernels).
- `quantized_matmul`: the dense/attention-projection hot path.  The MXU
  consumes the int8 weights cast to the accumulating dtype (bf16 under
  mixed precision, f32 otherwise); nothing in the compiled program ever
  silently widens back to f32 when a bf16 compute dtype is configured.
- `quantized_matmul_static`: optional static activation quantization —
  int8×int8 with an int32 accumulator using calibration-derived input
  scales (`quant/calibrate.py`), the full low-bit MXU path.

TPU tiling note (pallas guide): int8 tiles are (32, 128), so quantized
weight matrices keep their trailing dim a multiple of 128 where the model
allows; XLA handles ragged shapes with padding, correctness never depends
on it.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INT8_MAX = 127.0


@jax.tree_util.register_pytree_node_class
class QTensor:
    """Quantized tensor: int8 (or bf16-fallback) values + per-channel
    scales along `axis`.  Pytree children are (q, scale) so the pair
    travels as two ordinary leaves; `axis` is static aux data."""

    def __init__(self, q, scale, axis: int = -1):
        self.q = q
        self.scale = scale
        self.axis = int(axis)

    # ---- pytree protocol ----
    def tree_flatten(self):
        return (self.q, self.scale), (self.axis,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        q, scale = children
        return cls(q, scale, axis=aux[0])

    # ---- array-ish surface ----
    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self.q.shape)

    @property
    def ndim(self) -> int:
        return self.q.ndim

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def nbytes(self) -> int:
        return int(getattr(self.q, "nbytes", 0)
                   + getattr(self.scale, "nbytes", 0))

    def __repr__(self):
        return (f"QTensor(shape={self.shape}, dtype={self.q.dtype}, "
                f"axis={self.axis})")


def _scale_shape(shape: Tuple[int, ...], axis: int) -> Tuple[int, ...]:
    """Broadcast shape of the per-channel scale vector: 1 everywhere but
    `axis`."""
    out = [1] * len(shape)
    out[axis] = shape[axis]
    return tuple(out)


def quantize_tensor(w, axis: int = -1, dtype=jnp.int8) -> QTensor:
    """Symmetric per-channel int8 quantization: one scale per slice along
    `axis` (for a dense W of [n_in, n_out], axis=-1 is per-output-channel,
    making post-matmul dequantization exact).  All-zero channels get
    scale 1 so dequantization stays finite."""
    w = np.asarray(w)
    nd = w.ndim
    axis = axis % nd if nd else 0
    reduce_axes = tuple(i for i in range(nd) if i != axis)
    amax = np.abs(w).max(axis=reduce_axes, keepdims=True) if nd else \
        np.abs(w)
    scale = amax / INT8_MAX
    scale = np.where(scale == 0.0, 1.0, scale).astype(np.float32)
    q = np.clip(np.rint(w / scale), -INT8_MAX, INT8_MAX).astype(np.int8)
    return QTensor(jnp.asarray(q), jnp.asarray(scale), axis=axis)


def dequantize(qt: QTensor, dtype=jnp.float32):
    """Reconstruct a dense tensor in `dtype` — inside a trace this is the
    in-program dequantize; the int8 buffer stays the resident one."""
    return (qt.q.astype(dtype) * qt.scale.astype(dtype)).astype(dtype)


def quantization_error(w, axis: int = -1) -> float:
    """Mean |w - dequant(quant(w))| / mean |w| — the relative information
    loss an int8 round trip costs this tensor (the bf16-fallback signal)."""
    w = np.asarray(w, np.float64)
    qt = quantize_tensor(w, axis=axis)
    deq = np.asarray(qt.q, np.float64) * np.asarray(qt.scale, np.float64)
    denom = float(np.abs(w).mean()) or 1.0
    return float(np.abs(w - deq).mean()) / denom


def range_hostility(w, axis: int = -1) -> float:
    """max / mean of |w| within the worst channel.  int8 resolves ~1/127
    of a channel's max; once the channel's typical magnitude falls below
    one quantization step (hostility > ~127) most of its mass rounds to
    zero — the range-hostile case `quant/ptq.py` sends to bf16 instead."""
    w = np.asarray(w, np.float64)
    nd = w.ndim
    axis = axis % nd if nd else 0
    reduce_axes = tuple(i for i in range(nd) if i != axis)
    aw = np.abs(w)
    amax = aw.max(axis=reduce_axes)
    amean = aw.mean(axis=reduce_axes)
    ratio = amax / np.where(amean == 0.0, 1.0, amean)
    return float(ratio.max()) if ratio.size else 0.0


def dequant_epilogue(y, scale, bias=None, out_dtype=None):
    """Shared int8→float epilogue: widen the int32 accumulator to f32,
    multiply by the (already combined) per-channel scale row, add the
    optional bias — all in f32 — then cast.  Both the jnp reference
    contraction and the Pallas int8 tile call this same function, so the
    two paths agree bit-for-bit on scales for any tiling."""
    y = y.astype(jnp.float32) * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if out_dtype is not None:
        y = y.astype(out_dtype)
    return y


def _tier_resolve(kernel, *args, **kwargs):
    """Ask the fused-kernel tier which implementation this call gets
    (imported here: `ops.pallas.matmul` imports this module)."""
    from deeplearning4j_tpu.ops import pallas as tier
    return tier.dispatch.resolve(kernel, *args, **kwargs), tier


def _matmul_shape_class(x, n_out: int):
    from deeplearning4j_tpu.ops.pallas.tiles import shape_class
    rows = 1
    for d in x.shape[:-1]:
        rows *= int(d)
    return shape_class(m=rows, k=int(x.shape[-1]), n=int(n_out))


def _quantized_matmul_ref(x, qt: QTensor, acc_dtype=None):
    acc = jnp.dtype(acc_dtype) if acc_dtype is not None else x.dtype
    x = x.astype(acc)
    y = jax.lax.dot_general(
        x, qt.q.astype(acc),
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=acc)
    return y * qt.scale.astype(acc).reshape((1,) * (y.ndim - 1) + (-1,))


def quantized_matmul(x, qt: QTensor, acc_dtype=None):
    """x @ dequant(W) computed as (x @ W_q) * scale — the matmul consumes
    the int8 weights cast to the accumulating dtype and the per-output-
    channel scales apply to the product, so no f32 copy of W ever exists
    in the program.  `acc_dtype` defaults to x's dtype (bf16 under mixed
    precision).  Exact (up to rounding of W) only for axis == last dim.

    On TPU/GPU (or under a forced `pallas` dispatch mode) this routes to
    the weight-only Pallas tile, which widens W one K-block at a time in
    VMEM instead of streaming a dequantized copy from HBM."""
    if qt.axis != qt.ndim - 1:
        raise ValueError(
            f"quantized_matmul needs per-output-channel scales "
            f"(axis={qt.ndim - 1}), got axis={qt.axis}")
    impl, tier = _tier_resolve("q_matmul", x, qt.q, qt.scale)
    if impl == "pallas":
        sc = _matmul_shape_class(x, qt.shape[-1])
        return tier.matmul.q_matmul(
            x, qt.q, qt.scale, acc_dtype=acc_dtype,
            tile=tier.dispatch.get_tile("q_matmul", sc),
            interpret=tier.dispatch.interpret_mode())
    return _quantized_matmul_ref(x, qt, acc_dtype=acc_dtype)


def quantize_activation(x, scale):
    """Static activation quantization with a calibration-derived scale:
    clip+round to int8 inside the program (guide: stochastic rounding is
    for training; inference uses round-to-nearest)."""
    return jnp.clip(jnp.round(x / scale), -INT8_MAX, INT8_MAX
                    ).astype(jnp.int8)


def quantized_matmul_static(x, qt: QTensor, x_scale,
                            acc_dtype=jnp.float32):
    """Full low-bit path: int8 activations (static calibration scale) ×
    int8 weights with an int32 accumulator, dequantized once at the end
    by `x_scale * w_scale` — the MXU int8 mode the guide's quantization
    pattern targets."""
    if qt.axis != qt.ndim - 1:
        raise ValueError("static quantized matmul needs axis == last dim")
    xq = quantize_activation(x, x_scale)
    acc = jnp.dtype(acc_dtype)
    impl, tier = _tier_resolve("int8_matmul", xq, qt.q, qt.scale, x_scale)
    if impl == "pallas":
        sc = _matmul_shape_class(xq, qt.shape[-1])
        return tier.matmul.int8_matmul(
            xq, qt.q, qt.scale, x_scale=x_scale, out_dtype=acc,
            tile=tier.dispatch.get_tile("int8_matmul", sc),
            interpret=tier.dispatch.interpret_mode())
    y = jax.lax.dot_general(
        xq, qt.q,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    scale = (jnp.asarray(x_scale, jnp.float32)
             * qt.scale.astype(jnp.float32).reshape(
                 (1,) * (y.ndim - 1) + (-1,)))
    return dequant_epilogue(y, scale, out_dtype=acc)


def quantized_dense(x, qt: QTensor, b: Optional[jax.Array] = None,
                    acc_dtype=None):
    """Dense-layer hot path: quantized matmul + bias in the accumulating
    dtype (activation application stays with the calling layer).  When
    the Pallas tier takes the call, the bias add is fused into the tile's
    epilogue."""
    if qt.axis != qt.ndim - 1:
        raise ValueError(
            f"quantized_dense needs per-output-channel scales "
            f"(axis={qt.ndim - 1}), got axis={qt.axis}")
    impl, tier = _tier_resolve("q_matmul", x, qt.q, qt.scale, bias=b)
    if impl == "pallas":
        sc = _matmul_shape_class(x, qt.shape[-1])
        return tier.matmul.q_matmul(
            x, qt.q, qt.scale, bias=b, acc_dtype=acc_dtype,
            tile=tier.dispatch.get_tile("q_matmul", sc),
            interpret=tier.dispatch.interpret_mode())
    y = _quantized_matmul_ref(x, qt, acc_dtype=acc_dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y
