"""Taps-based max-pool backward — the select-and-scatter replacement.

XLA lowers max-pool's gradient to `select-and-scatter`, a serial
window-walk, one of the ten longest device ops of ResNet-50's step
(`PERF_LEDGER.jsonl`: `resnet50_train_b256`) — the same per-window scan shape
the reference delegates to cuDNN's `PoolingBackward`
(`deeplearning4j-cuda/.../CudnnSubsamplingHelper.java` role).

The TPU-shaped alternative: recompute the max match on the OUTPUT grid
with kh*kw shifted strided views (the same tap machinery as
`conv_kernels`' wgrad), then accumulate `dy * [x == y] / ties` back into
the input with kh*kw strided `.at[].add` slices — pure elementwise +
slicing that XLA fuses, no serial scatter.

Semantics note: ties split the gradient evenly (a valid subgradient that
preserves sum(dx) == sum(dy)); XLA's select-and-scatter gives the whole
gradient to the FIRST max in window order.  The two differ only on exact
float ties (e.g. multiple relu zeros in one window), so adoption is
flag-gated (`POOL_BWD_TAPS`) and decided on measurement, like
CONV_BWD_PALLAS.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

# measured adoption only (never timed on the chip); the env override
# mirrors CONV_BWD_PALLAS's discipline in conv_kernels.py
import os as _os

POOL_BWD_TAPS = {
    "enabled": _os.environ.get("DL4J_TPU_POOL_BWD_TAPS", "") == "1",
}


def _resolve_pad(padding, H, W, kernel, stride, Ho, Wo):
    """Per-dim (lo, hi) pads matching lax.reduce_window's semantics."""
    kh, kw = kernel
    sh, sw = stride
    if padding == "VALID":
        return (0, 0), (0, 0)
    if padding == "SAME":
        th = max((Ho - 1) * sh + kh - H, 0)
        tw = max((Wo - 1) * sw + kw - W, 0)
        return (th // 2, th - th // 2), (tw // 2, tw - tw // 2)
    (plh, phh), (plw, phw) = padding
    return (plh, phh), (plw, phw)


def _pool_fwd_raw(x, kernel, stride, padding):
    pad = padding
    if not isinstance(pad, str):
        pad = ((0, 0), tuple(pad[0]), tuple(pad[1]), (0, 0))
    return lax.reduce_window(x, -jnp.inf, lax.max,
                             (1,) + tuple(kernel) + (1,),
                             (1,) + tuple(stride) + (1,), pad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def max_pool2d_taps(x, kernel, stride, padding="VALID"):
    """NHWC max pool whose VJP avoids select-and-scatter (see module
    docstring).  `padding`: "SAME" | "VALID" | ((lo,hi),(lo,hi))."""
    return _pool_fwd_raw(x, kernel, stride, padding)


def _fwd(x, kernel, stride, padding):
    y = _pool_fwd_raw(x, kernel, stride, padding)
    return y, (x, y)


def _bwd(kernel, stride, padding, resid, dy):
    x, y = resid
    B, H, W, C = x.shape
    kh, kw = kernel
    sh, sw = stride
    Ho, Wo = y.shape[1], y.shape[2]
    (plh, _), (plw, _) = _resolve_pad(padding, H, W, kernel, stride, Ho, Wo)
    Lh = (Ho - 1) * sh + kh            # padded window coverage
    Lw = (Wo - 1) * sw + kw
    xp = jnp.pad(x.astype(jnp.float32),
                 ((0, 0), (plh, max(Lh - H - plh, 0)),
                  (plw, max(Lw - W - plw, 0)), (0, 0)),
                 constant_values=-jnp.inf)[:, :Lh, :Lw, :]
    yf = y.astype(jnp.float32)

    taps, ties = [], 0.
    for ti in range(kh):
        for tj in range(kw):
            v = xp[:, ti:ti + (Ho - 1) * sh + 1:sh,
                   tj:tj + (Wo - 1) * sw + 1:sw, :]
            eq = (v == yf).astype(jnp.float32)
            taps.append(eq)
            ties = ties + eq
    scale = dy.astype(jnp.float32) / ties

    dxp = jnp.zeros((B, Lh, Lw, C), jnp.float32)
    i = 0
    for ti in range(kh):
        for tj in range(kw):
            dxp = dxp.at[:, ti:ti + (Ho - 1) * sh + 1:sh,
                         tj:tj + (Wo - 1) * sw + 1:sw, :].add(
                taps[i] * scale)
            i += 1
    dx = dxp[:, plh:plh + H, plw:plw + W, :]
    if dx.shape[1] < H or dx.shape[2] < W:     # VALID with cropped tail
        dx = jnp.pad(dx, ((0, 0), (0, H - dx.shape[1]),
                          (0, W - dx.shape[2]), (0, 0)))
    return (dx.astype(x.dtype),)


max_pool2d_taps.defvjp(_fwd, _bwd)


def max_pool2d(x, kernel, stride, padding="VALID"):
    """Dispatcher: taps VJP when POOL_BWD_TAPS['enabled'], else the
    XLA reduce_window path (select-and-scatter backward)."""
    if POOL_BWD_TAPS["enabled"]:
        return max_pool2d_taps(x, tuple(kernel), tuple(stride), padding)
    return _pool_fwd_raw(x, kernel, stride, padding)
