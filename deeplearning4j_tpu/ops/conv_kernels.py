"""Pallas conv-backward-filter (wgrad) prototype.

ResNet-50's conv backward is over half of its train step (`PERF_LEDGER.jsonl`,
`conv_backward_ms_per_step`); the prescribed experiment is a Pallas
wgrad (or dgrad) kernel for the 3x3 stride-1 SAME shapes, A/B'd against
XLA's lowering ON CHIP — a measured win adopts it, a measured loss gets a
committed negative-result table (measured on v5e, 2026-07-31: wgrad
0.93-1.20x and dgrad 0.95-1.24x in isolation; PERF.md).

Formulation: for a 3x3 stride-1 SAME conv,

    dW[i, j, ci, co] = sum_{b, oh, ow} x_pad[b, oh+i, ow+j, ci]
                                     * dy[b, oh, ow, co]

i.e. NINE [Ci, K] x [K, Co] matmuls over the same K = B*H*W reduction,
each with a shifted view of x.  XLA lowers this as one big filter-grad
conv; the kernel instead keeps an x row-stripe resident in VMEM and
reuses it for all nine taps (the data-reuse XLA's tiling does not get
credit for at these shapes).

Halo handling: Pallas blocked indexing cannot express overlapping row
blocks, so the three row shifts are materialized OUTSIDE the kernel as
three row-aligned views of the padded input (x_pad[:, i:i+H] for
i in 0,1,2) — each partitions cleanly into row stripes; the two column
shifts stay inside the stripe because the full padded width is loaded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wgrad_kernel(xt_ref, xm_ref, xb_ref, dy_ref, out_ref, *, bh, W, Ci,
                  Co):
    step = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    dy = dy_ref[0].reshape(bh * W, Co).astype(jnp.float32)
    for i, xs_ref in enumerate((xt_ref, xm_ref, xb_ref)):
        xs = xs_ref[0]                          # [bh, W+2, Ci]
        for j in range(3):
            xij = xs[:, j:j + W, :].reshape(bh * W, Ci).astype(
                jnp.float32)
            acc = jax.lax.dot_general(
                xij, dy, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out_ref[i * 3 + j] += acc


def conv3x3_wgrad_tpu(x, dy, block_rows: int = 0,
                      interpret: bool = False):
    """Filter gradient of a 3x3 stride-1 SAME NHWC conv.

    x: [B, H, W, Ci] activations, dy: [B, H, W, Co] output cotangent
    -> dw [3, 3, Ci, Co] float32.
    """
    B, H, W, Ci = x.shape
    Co = dy.shape[-1]
    if dy.shape[:3] != (B, H, W):
        raise ValueError(f"dy {dy.shape} mismatches x {x.shape}")
    bh = block_rows or max(d for d in (1, 2, 4, 7, 8, 14, 16, 28, 32)
                           if H % d == 0)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    # three row-shifted, stripe-partitionable views (see module docstring)
    xt = xp[:, 0:H]
    xm = xp[:, 1:H + 1]
    xb = xp[:, 2:H + 2]
    grid = (B, H // bh)

    x_spec = pl.BlockSpec((1, bh, W + 2, Ci),
                          lambda b, i: (b, i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_wgrad_kernel, bh=bh, W=W, Ci=Ci, Co=Co),
        grid=grid,
        in_specs=[x_spec, x_spec, x_spec,
                  pl.BlockSpec((1, bh, W, Co), lambda b, i: (b, i, 0, 0))],
        out_specs=pl.BlockSpec((9, Ci, Co), lambda b, i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((9, Ci, Co), jnp.float32),
        interpret=interpret,
    )(xt, xm, xb, dy)
    return out.reshape(3, 3, Ci, Co)


def conv3x3_wgrad_xla(x, dy):
    """XLA reference: filter grad via autodiff of the forward conv."""
    w0 = jnp.zeros((3, 3, x.shape[-1], dy.shape[-1]), jnp.float32)

    def loss(w):
        y = jax.lax.conv_general_dilated(
            x.astype(jnp.float32), w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.sum(y * dy.astype(jnp.float32))

    return jax.grad(loss)(w0)


# ---------------------------------------------------------------------------
# dgrad: conv-backward-data (VERDICT r4 #5 — wgrad alone cannot close the
# 13.2 ms conv backward; dgrad is the other half).
#
# For a 3x3 stride-1 SAME conv, dx = SAME-conv(dy, Wt) where
# Wt[i, j, co, ci] = W[2-i, 2-j, ci, co] (spatial rot180 + channel
# transpose).  Same shifted-view trick as wgrad: the three row shifts of
# the padded dy are materialized as stripe-partitionable views outside
# the kernel; inside, each stripe does NINE [bh*W, Co] x [Co, Ci]
# matmuls against the pre-flipped filter taps and accumulates in f32 —
# the dy stripe stays resident in VMEM across all nine taps.
# ---------------------------------------------------------------------------

def _dgrad_kernel(dyt_ref, dym_ref, dyb_ref, wf_ref, out_ref, *, bh, W,
                  Ci, Co):
    wf = wf_ref[...]                             # [9, Co, Ci]
    acc = jnp.zeros((bh * W, Ci), jnp.float32)
    for i, ds_ref in enumerate((dyt_ref, dym_ref, dyb_ref)):
        ds = ds_ref[0]                           # [bh, W+2, Co]
        for j in range(3):
            dij = ds[:, j:j + W, :].reshape(bh * W, Co).astype(
                jnp.float32)
            acc += jax.lax.dot_general(
                dij, wf[i * 3 + j], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    out_ref[0] = acc.reshape(bh, W, Ci)


def conv3x3_dgrad_tpu(dy, w, block_rows: int = 0,
                      interpret: bool = False):
    """Input gradient of a 3x3 stride-1 SAME NHWC conv.

    dy: [B, H, W, Co] output cotangent, w: [3, 3, Ci, Co] filter
    -> dx [B, H, W, Ci] float32.
    """
    B, H, W, Co = dy.shape
    Ci = w.shape[2]
    if w.shape != (3, 3, Ci, Co):
        raise ValueError(f"w {w.shape} is not [3, 3, Ci, {Co}]")
    bh = block_rows or max(d for d in (1, 2, 4, 7, 8, 14, 16, 28, 32)
                           if H % d == 0)
    dyp = jnp.pad(dy, ((0, 0), (1, 1), (1, 1), (0, 0)))
    dyt = dyp[:, 0:H]
    dym = dyp[:, 1:H + 1]
    dyb = dyp[:, 2:H + 2]
    # rot180 + channel transpose, one tap per row: wf[i*3+j] = Wt[i, j]
    wf = jnp.flip(w, (0, 1)).transpose(0, 1, 3, 2).reshape(9, Co, Ci)
    grid = (B, H // bh)

    dy_spec = pl.BlockSpec((1, bh, W + 2, Co),
                           lambda b, i: (b, i, 0, 0))
    return pl.pallas_call(
        functools.partial(_dgrad_kernel, bh=bh, W=W, Ci=Ci, Co=Co),
        grid=grid,
        in_specs=[dy_spec, dy_spec, dy_spec,
                  pl.BlockSpec((9, Co, Ci), lambda b, i: (0, 0, 0))],
        out_specs=pl.BlockSpec((1, bh, W, Ci),
                               lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, Ci), jnp.float32),
        interpret=interpret,
    )(dyt, dym, dyb, wf)


def conv3x3_dgrad_xla(dy, w):
    """XLA reference: input grad via autodiff of the forward conv."""
    B, H, W, Co = dy.shape
    x0 = jnp.zeros((B, H, W, w.shape[2]), jnp.float32)

    def loss(x):
        y = jax.lax.conv_general_dilated(
            x, w.astype(jnp.float32), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.sum(y * dy.astype(jnp.float32))

    return jax.grad(loss)(x0)


# ---------------------------------------------------------------------------
# Measured-dispatch adoption hook (the flash/fused-LN pattern): a
# custom_vjp 3x3-s1-SAME conv whose BACKWARD routes to the Pallas
# wgrad/dgrad kernels when the corresponding flag is on.  Default off:
# measured on v5e, 2026-07-31, the full ResNet-50 step went from 35.6 ms
# (XLA) to 45.4 (wgrad), 44.3 (dgrad) and 47.3 ms (both) — the custom_vjp
# boundary breaks XLA's conv+BN+relu fusion.  The
# DL4J_TPU_CONV_BWD_PALLAS env var turns the flags on.
# ---------------------------------------------------------------------------

import os as _os

CONV_BWD_PALLAS = {
    "wgrad": "w" in _os.environ.get("DL4J_TPU_CONV_BWD_PALLAS", ""),
    "dgrad": "d" in _os.environ.get("DL4J_TPU_CONV_BWD_PALLAS", ""),
    #: interpret-mode for tests on CPU
    "interpret": False,
}


def conv3x3_eligible(x_shape, w_shape, b, stride, padding, dilation) -> bool:
    """The shapes this hook covers: 3x3, stride 1, SAME, no dilation,
    NHWC, bias-free (the ResNet body conv)."""
    return (any(CONV_BWD_PALLAS[k] for k in ("wgrad", "dgrad"))
            and b is None
            and tuple(stride) == (1, 1) and tuple(dilation) == (1, 1)
            and padding == "SAME"
            and len(w_shape) == 4 and w_shape[:2] == (3, 3)
            and len(x_shape) == 4)


@jax.custom_vjp
def conv3x3_same(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _c33_fwd(x, w):
    return conv3x3_same(x, w), (x, w)


def _c33_bwd(res, dy):
    x, w = res
    itp = CONV_BWD_PALLAS["interpret"]
    # XLA's own cotangents for whichever side stays on the XLA path —
    # the unused one is dead-code-eliminated under jit
    _, pullback = jax.vjp(
        lambda x_, w_: jax.lax.conv_general_dilated(
            x_, w_, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")), x, w)
    dx_xla, dw_xla = pullback(dy)
    dx = (conv3x3_dgrad_tpu(dy, w, interpret=itp).astype(x.dtype)
          if CONV_BWD_PALLAS["dgrad"] else dx_xla)
    dw = (conv3x3_wgrad_tpu(x, dy, interpret=itp).astype(w.dtype)
          if CONV_BWD_PALLAS["wgrad"] else dw_xla)
    return dx, dw


conv3x3_same.defvjp(_c33_fwd, _c33_bwd)


# ---------------------------------------------------------------------------
# Quantized inference conv (quant/ subsystem hot path)
# ---------------------------------------------------------------------------

def quantized_conv2d(x, qt, stride=(1, 1), padding="SAME",
                     dilation=(1, 1), acc_dtype=None,
                     feature_group_count=1):
    """NHWC/HWIO conv against int8 weights with per-output-channel scales:
    the conv consumes `qt.q` cast to the accumulating dtype and the scales
    apply to the product — `conv(x, dequant(W)) == conv(x, W_q) * s[co]`
    exactly, because each output channel is a sum over one channel's
    weights only.  The int8 HWIO buffer is what stays device-resident;
    no f32 copy of the filter exists in the compiled program."""
    if qt.axis != qt.ndim - 1:
        raise ValueError(
            f"quantized_conv2d needs per-output-channel scales "
            f"(axis={qt.ndim - 1}), got axis={qt.axis}")
    acc = jnp.dtype(acc_dtype) if acc_dtype is not None else x.dtype
    y = jax.lax.conv_general_dilated(
        x.astype(acc), qt.q.astype(acc),
        tuple(stride), padding, rhs_dilation=tuple(dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=feature_group_count,
        preferred_element_type=acc)
    return y * qt.scale.astype(acc).reshape(1, 1, 1, -1)
