"""Fused matmul-family Pallas kernels: int8 tiles and dense epilogues.

One generic blockwise kernel serves three public entry points:

- :func:`int8_matmul` — the full low-bit path: int8×int8→int32 stays on
  the MXU for every K block, and the per-output-channel dequant (plus
  optional bias) is fused into the epilogue of the *last* K step.  Because
  the integer contraction is exact (associative, no rounding) and the f32
  epilogue is shared with the jnp reference
  (`quant_kernels.dequant_epilogue`), Pallas and reference agree
  *bit-for-bit* for any tiling — which is what the conformance suite pins.
- :func:`q_matmul` — weight-only quantization: int8 weights are widened
  to the compute dtype inside the kernel (per K block, in VMEM) instead
  of materializing a dequantized copy of W in HBM first.
- :func:`fused_dense` — float matmul with bias + activation fused into
  the epilogue (the cuDNN-style fused primitive), differentiable via a
  ``custom_vjp`` whose backward is the reference lowering's VJP.

Zero-padding to block multiples is exact for matmul (padded rows/cols
contribute zeros to the accumulator and are sliced off), so ragged shapes
need no masking here.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas.tiles import DEFAULT_TILES, TileConfig
from deeplearning4j_tpu.ops.quant_kernels import dequant_epilogue


#: Epilogue activations.  Both the kernel epilogue and the reference call
#: these same functions, so conformance is a pure tiling question — except
#: "gelu", for which the kernel calls `_gelu_kernel` below.
EPILOGUE_ACTIVATIONS: Dict[str, Any] = {
    "identity": lambda y: y,
    "linear": lambda y: y,
    "relu": lambda y: jnp.maximum(y, 0.0),
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    # exact erf form, matching ops.activations.gelu
    "gelu": lambda y: jax.nn.gelu(y, approximate=False),
}


def _gelu_kernel(y):
    """Exact-form gelu for the kernel epilogue.  Mosaic (jax 0.9.0) lowers
    neither `erf` nor `erfc` ("NotImplementedError: Unimplemented primitive
    in Pallas TPU lowering for KernelType.TC: erfc"), so erf is evaluated
    by Abramowitz & Stegun 7.1.26 (|error| <= 1.5e-7, f32 round-off) from
    ops Mosaic does lower; the reference keeps `jax.nn.gelu`."""
    z = jnp.abs(y) * 0.7071067811865476
    t = 1.0 / (1.0 + 0.3275911 * z)
    poly = t * (0.254829592 + t * (-0.284496736 + t * (
        1.421413741 + t * (-1.453152027 + t * 1.061405429))))
    erf_abs = 1.0 - poly * jnp.exp(-z * z)
    return 0.5 * y * (1.0 + jnp.where(y < 0.0, -erf_abs, erf_abs))


_KERNEL_ACTIVATIONS = {**EPILOGUE_ACTIVATIONS, "gelu": _gelu_kernel}

_FLOAT_DTYPES = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublane(dtype) -> int:
    d = jnp.dtype(dtype)
    if d == jnp.dtype(jnp.int8):
        return 32
    if d == jnp.dtype(jnp.bfloat16):
        return 16
    return 8


def _block_sizes(M: int, K: int, N: int, tile: TileConfig, x_dtype):
    """Clamp the tile to the problem, honouring TPU tiling minima:
    bm is a sublane dim (multiple of the operand's sublane count), bk and
    bn are lane dims (multiples of 128) unless they cover the whole dim."""
    bm = min(tile.block_m, _round_up(M, _sublane(x_dtype)))
    bk = min(tile.block_k, _round_up(K, 128))
    bn = min(tile.block_n, _round_up(N, 128))
    return bm, bk, bn


def _matmul_kernel(x_ref, w_ref, *rest, nk, acc_dtype, compute_dtype,
                   has_scale, has_bias, activation):
    refs = list(rest)
    scale_ref = refs.pop(0) if has_scale else None
    bias_ref = refs.pop(0) if has_bias else None
    out_ref, acc_sc = refs

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    xb = x_ref[...]
    wb = w_ref[...]
    if compute_dtype is not None:
        xb = xb.astype(compute_dtype)
        wb = wb.astype(compute_dtype)
    acc_sc[...] += jnp.dot(xb, wb, preferred_element_type=acc_dtype)

    @pl.when(k == nk - 1)
    def _finalize():
        y = acc_sc[...]
        if has_scale:
            y = dequant_epilogue(y, scale_ref[...],
                                 bias=bias_ref[...] if has_bias else None)
        else:
            y = y.astype(jnp.float32)
            if has_bias:
                y = y + bias_ref[...].astype(jnp.float32)
        if activation is not None:
            y = _KERNEL_ACTIVATIONS[activation](y)
        out_ref[...] = y.astype(out_ref.dtype)


def _tiled_matmul(x2, w, *, scale=None, bias=None, activation=None,
                  acc_dtype, compute_dtype, out_dtype,
                  tile: TileConfig, interpret: bool):
    """Grid (M/bm, N/bn, K/bk) with K innermost; VMEM accumulator scratch
    persists across the K steps of one (i, j) output block."""
    M, K = x2.shape
    N = w.shape[1]
    bm, bk, bn = _block_sizes(M, K, N, tile, x2.dtype)
    Mp, Kp, Np = _round_up(M, bm), _round_up(K, bk), _round_up(N, bn)

    xp = jnp.pad(x2, ((0, Mp - M), (0, Kp - K))) if (Mp, Kp) != (M, K) else x2
    wp = jnp.pad(w, ((0, Kp - K), (0, Np - N))) if (Kp, Np) != (K, N) else w

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    inputs = [xp, wp]
    if scale is not None:
        sp = jnp.pad(scale, ((0, 0), (0, Np - N)))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        inputs.append(sp)
    if bias is not None:
        bp = jnp.pad(bias.reshape(1, N).astype(jnp.float32),
                     ((0, 0), (0, Np - N)))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        inputs.append(bp)

    kernel = functools.partial(
        _matmul_kernel,
        nk=Kp // bk,
        acc_dtype=acc_dtype,
        compute_dtype=compute_dtype,
        has_scale=scale is not None,
        has_bias=bias is not None,
        activation=activation,
    )
    out = pl.pallas_call(
        kernel,
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.dtype(out_dtype)),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        # the accumulator is carried across K only
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*inputs)
    if (Mp, Np) != (M, N):
        out = out[:M, :N]
    return out


def _combined_scale(w_scale, x_scale, N: int):
    """Normalize per-channel weight scales (and an optional scalar
    activation scale) into the single (1, N) f32 row the epilogue
    multiplies by.  Shared by Pallas and reference so the f32 math — and
    therefore the output bits — are identical."""
    scale = jnp.asarray(w_scale, jnp.float32).reshape(1, N)
    if x_scale is not None:
        scale = jnp.asarray(x_scale, jnp.float32) * scale
    return scale


def _leading_flatten(x):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


# ---------------------------------------------------------------------------
# int8 × int8 → int32 (static activation quantization)
# ---------------------------------------------------------------------------

def int8_matmul(xq, wq, w_scale, x_scale=None, bias=None,
                out_dtype=jnp.float32, tile: Optional[TileConfig] = None,
                interpret: bool = False):
    """int8 activations × int8 weights with an int32 MXU accumulator and
    the dequant epilogue fused into the last K step.  Bitwise-equal to
    :func:`int8_matmul_reference` under any tiling."""
    tile = tile or DEFAULT_TILES["int8_matmul"]
    x2, lead = _leading_flatten(xq)
    N = wq.shape[1]
    y = _tiled_matmul(
        x2, wq,
        scale=_combined_scale(w_scale, x_scale, N),
        bias=bias,
        acc_dtype=jnp.int32, compute_dtype=None, out_dtype=out_dtype,
        tile=tile, interpret=interpret)
    return y.reshape(lead + (N,))


def int8_matmul_reference(xq, wq, w_scale, x_scale=None, bias=None,
                          out_dtype=jnp.float32):
    """Definition of correctness: whole-array int8→int32 contraction,
    then the shared dequant epilogue."""
    y = jax.lax.dot_general(
        xq, wq,
        dimension_numbers=(((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    scale = _combined_scale(w_scale, x_scale, wq.shape[1])
    return dequant_epilogue(y, scale, bias=bias, out_dtype=out_dtype)


def int8_supports(xq, wq, w_scale, x_scale=None, bias=None, **kw) -> bool:
    return (
        getattr(xq, "ndim", 0) >= 2 and getattr(wq, "ndim", 0) == 2
        and jnp.dtype(xq.dtype) == jnp.dtype(jnp.int8)
        and jnp.dtype(wq.dtype) == jnp.dtype(jnp.int8)
        and (x_scale is None or jnp.ndim(x_scale) == 0)
    )


def int8_profitable(xq, wq, *args, **kw) -> bool:
    return wq.shape[0] >= 256 and wq.shape[1] >= 256


# ---------------------------------------------------------------------------
# weight-only int8 (float activations)
# ---------------------------------------------------------------------------

def q_matmul(x, wq, w_scale, bias=None, acc_dtype=None,
             tile: Optional[TileConfig] = None, interpret: bool = False):
    """Weight-only path: int8 weights widen to the compute dtype inside
    the kernel, one K block at a time in VMEM — no dequantized copy of W
    in HBM.  Accumulates in f32 for stability; output in ``acc_dtype``
    (default: x's dtype, matching ``quantized_matmul``)."""
    tile = tile or DEFAULT_TILES["q_matmul"]
    acc = jnp.dtype(acc_dtype) if acc_dtype is not None else x.dtype
    x2, lead = _leading_flatten(x)
    N = wq.shape[1]
    y = _tiled_matmul(
        x2, wq,
        scale=_combined_scale(w_scale, None, N),
        bias=bias,
        acc_dtype=jnp.float32, compute_dtype=jnp.dtype(acc),
        out_dtype=acc, tile=tile, interpret=interpret)
    return y.reshape(lead + (N,))


def q_matmul_reference(x, wq, w_scale, bias=None, acc_dtype=None):
    """Mirrors `quant_kernels.quantized_matmul` (+ optional bias)."""
    acc = jnp.dtype(acc_dtype) if acc_dtype is not None else x.dtype
    y = jax.lax.dot_general(
        x.astype(acc), wq.astype(acc),
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=acc)
    y = y * jnp.asarray(w_scale, acc).reshape((1,) * (y.ndim - 1) + (-1,))
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def q_supports(x, wq, w_scale, bias=None, **kw) -> bool:
    return (
        getattr(x, "ndim", 0) >= 2 and getattr(wq, "ndim", 0) == 2
        and jnp.dtype(x.dtype) in _FLOAT_DTYPES
        and jnp.dtype(wq.dtype) == jnp.dtype(jnp.int8)
    )


def q_profitable(x, wq, *args, **kw) -> bool:
    return wq.shape[0] >= 256 and wq.shape[1] >= 256


# ---------------------------------------------------------------------------
# fused dense (matmul + bias + activation epilogue), differentiable
# ---------------------------------------------------------------------------

def fused_dense_reference(x, w, bias=None, activation=None):
    """f32-accumulated dense with the same epilogue functions the kernel
    applies; output in x's dtype."""
    y = jax.lax.dot_general(
        x, w,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if activation is not None:
        y = EPILOGUE_ACTIVATIONS[activation](y)
    return y.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_dense_p(x, w, b, activation, tile, interpret):
    x2, lead = _leading_flatten(x)
    N = w.shape[1]
    y = _tiled_matmul(
        x2, w, bias=b, activation=activation,
        acc_dtype=jnp.float32, compute_dtype=None, out_dtype=x.dtype,
        tile=tile, interpret=interpret)
    return y.reshape(lead + (N,))


def _fused_dense_fwd(x, w, b, activation, tile, interpret):
    return _fused_dense_p(x, w, b, activation, tile, interpret), (x, w, b)


def _fused_dense_bwd(activation, tile, interpret, res, g):
    x, w, b = res
    _, vjp = jax.vjp(
        lambda x_, w_, b_: fused_dense_reference(x_, w_, b_, activation),
        x, w, b)
    return vjp(g)


_fused_dense_p.defvjp(_fused_dense_fwd, _fused_dense_bwd)


def fused_dense(x, w, bias=None, activation=None,
                tile: Optional[TileConfig] = None, interpret: bool = False):
    """Dense layer forward with bias + activation fused into the matmul
    epilogue.  Differentiable: the backward pass is the reference
    lowering's VJP (recomputed — flash-style, no epilogue residuals)."""
    tile = tile or DEFAULT_TILES["fused_dense"]
    b = bias if bias is not None else jnp.zeros((w.shape[1],), x.dtype)
    return _fused_dense_p(x, w, b, activation, tile, bool(interpret))


def dense_supports(x, w, bias=None, activation=None, **kw) -> bool:
    return (
        getattr(x, "ndim", 0) >= 2 and getattr(w, "ndim", 0) == 2
        and jnp.dtype(x.dtype) in _FLOAT_DTYPES
        and jnp.dtype(w.dtype) == jnp.dtype(x.dtype)
        and (bias is None or jnp.dtype(bias.dtype) in _FLOAT_DTYPES)
        and (activation is None or activation in EPILOGUE_ACTIVATIONS)
    )


def dense_profitable(x, w, *args, **kw) -> bool:
    rows = 1
    for d in x.shape[:-1]:
        rows *= int(d)
    return rows >= 128 and w.shape[0] >= 128 and w.shape[1] >= 128
