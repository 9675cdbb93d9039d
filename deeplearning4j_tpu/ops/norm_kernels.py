"""Pallas fused LayerNorm — the platform-helper pattern beyond attention.

Reference analog: `libnd4j/include/ops/declarable/platform/cudnn/**` —
vendor-tuned kernels behind a dispatch check.  XLA already fuses layer-norm
chains well; this kernel exists for the long-sequence transformer path
where keeping the (mean, rstd) statistics in VMEM between forward and
backward avoids an HBM round-trip, and as the second instance (after
`attention_kernels.fused_attention`) of the measured-dispatch pattern:
`fused_layer_norm` uses the Pallas kernel only when shapes tile cleanly on
TPU, else the plain jnp composition.

custom_vjp wires the Pallas backward; gradients match the jnp reference
(tests run the kernel in interpret mode on CPU)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def layer_norm_reference(x, gain, bias=None, eps: float = 1e-5):
    """The canonical jnp layer norm over the last axis (the plain impl the
    registry op and the Pallas kernel both validate against — standalone so
    the op can dispatch here without a circular import)."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps) * gain
    return y if bias is None else y + bias


def rms_norm(x, gain, eps: float = 1e-6):
    """RMSNorm over the last axis (Zhang & Sennrich 2019): `x / sqrt(mean(x^2)
    + eps) * gain`, no mean removed and no bias.  Computed in float32 whatever
    the input's dtype, returned in it.  Plain jnp: XLA fuses it into its
    neighbours, and no cell has shown a kernel to be worth having."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def gated_rms_norm(y, z, gain, groups: int, eps: float = 1e-5):
    """Mamba-2's gated RMSNorm (`norm_before_gate=False`): `h = y *
    SiLU(z)`, then each of `groups` equal groups of the last axis divided
    by its root mean square (`eps` added), times `gain` [channels].  For
    `y`, `z` [..., channels]; float32 throughout, returned in float32."""
    h = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = h.reshape(*h.shape[:-1], groups, h.shape[-1] // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(h.shape) * gain.astype(jnp.float32)


# -- forward kernel ---------------------------------------------------------

def _ln_fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    # Mosaic constraint (found on real v5e, not representable in interpret
    # mode): one kernel may not mix 2D and 1D outputs — the stats are
    # therefore (blk, 1) blocks (full lane cover exempts the 128-divisibility
    # rule), squeezed by the caller.
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd * g_ref[...] + b_ref[...]
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _ln_bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref,
                   dx_ref, dg_ref, db_ref):
    # dg/db partials: a (1, F) block violates Mosaic's 8-sublane rule, so
    # each grid step broadcasts its partial over an (8, F) block; the caller
    # reads sublane 0 of each.
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    g = g_ref[...]
    mean = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (x - mean) * rstd
    dg_ref[...] = jnp.broadcast_to(
        jnp.sum(dy * xhat, axis=0)[None, None, :], dg_ref.shape)
    db_ref[...] = jnp.broadcast_to(
        jnp.sum(dy, axis=0)[None, None, :], db_ref.shape)
    wdy = dy * g
    c1 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    c2 = jnp.mean(wdy, axis=-1, keepdims=True)
    dx = (wdy - xhat * c1 - c2) * rstd
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _rows_of(x):
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    return rows


def layer_norm_tpu(x, gain, bias=None, eps: float = 1e-5,
                   block_rows: int = 256, interpret: bool = False):
    """Pallas layer norm over the last axis.  x: [..., F]."""
    F = x.shape[-1]
    bias_ = jnp.zeros((F,), jnp.float32) if bias is None else bias
    rows = _rows_of(x)
    x2 = x.reshape(rows, F)
    blk = min(block_rows, rows)
    if rows % blk:
        raise ValueError(f"rows {rows} not divisible by block {blk}")
    grid = (rows // blk,)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((blk, F), lambda i: (i, 0)),
                  pl.BlockSpec((F,), lambda i: (0,)),
                  pl.BlockSpec((F,), lambda i: (0,))],
        out_specs=[pl.BlockSpec((blk, F), lambda i: (i, 0)),
                   pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                   pl.BlockSpec((blk, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, F), x.dtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=interpret,
    )(x2, gain.astype(jnp.float32), bias_.astype(jnp.float32))
    return y.reshape(x.shape), mean[:, 0], rstd[:, 0]


def layer_norm_bwd_tpu(x, gain, mean, rstd, dy, block_rows: int = 256,
                       interpret: bool = False):
    F = x.shape[-1]
    rows = _rows_of(x)
    x2 = x.reshape(rows, F)
    dy2 = dy.reshape(rows, F)
    blk = min(block_rows, rows)
    if rows % blk:
        raise ValueError(f"rows {rows} not divisible by block {blk}")
    grid = (rows // blk,)
    dx, dg_part, db_part = pl.pallas_call(
        _ln_bwd_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((blk, F), lambda i: (i, 0)),
                  pl.BlockSpec((F,), lambda i: (0,)),
                  pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                  pl.BlockSpec((blk, 1), lambda i: (i, 0)),
                  pl.BlockSpec((blk, F), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((blk, F), lambda i: (i, 0)),
                   pl.BlockSpec((1, 8, F), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 8, F), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, F), x.dtype),
                   jax.ShapeDtypeStruct((grid[0], 8, F), jnp.float32),
                   jax.ShapeDtypeStruct((grid[0], 8, F), jnp.float32)],
        interpret=interpret,
    )(x2, gain.astype(jnp.float32), mean[:, None], rstd[:, None], dy2)
    return (dx.reshape(x.shape), dg_part[:, 0].sum(0).astype(gain.dtype),
            db_part[:, 0].sum(0))


# -- custom_vjp dispatcher --------------------------------------------------

# Measured on v5e-1, 2026-07-31: fused LN
# fwd+bwd beats XLA's fused chain 1.07x at 8k rows and 1.06x at 64k rows
# (D=768 BERT shapes).  Below ~1k rows dispatch overhead dominates.
_LN_MIN_ROWS = 1024


def _can_tile(x, block_rows: int = 256) -> bool:
    """Kernel-lowering feasibility (also the interpret-mode gate)."""
    rows = _rows_of(x)
    return rows % min(block_rows, rows) == 0 and x.shape[-1] % 128 == 0


def _worth_it(x) -> bool:
    """Dispatch heuristic: big enough to beat XLA's fused chain."""
    return _rows_of(x) >= _LN_MIN_ROWS


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ln(x, gain, bias, eps, interpret):
    y, _, _ = layer_norm_tpu(x, gain, bias, eps, interpret=interpret)
    return y


def _fused_ln_fwd(x, gain, bias, eps, interpret):
    y, mean, rstd = layer_norm_tpu(x, gain, bias, eps, interpret=interpret)
    return y, (x, gain, bias, mean, rstd)


def _fused_ln_bwd(eps, interpret, res, dy):
    x, gain, bias, mean, rstd = res
    dx, dg, db = layer_norm_bwd_tpu(x, gain, mean, rstd, dy,
                                    interpret=interpret)
    return dx, dg, db.astype(bias.dtype)


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def fused_layer_norm(x, gain, bias=None, eps: float = 1e-5,
                     interpret: Optional[bool] = None):
    """Measured-dispatch layer norm (the `fused_attention` pattern): Pallas
    kernel when on TPU (or interpret=True) and shapes tile; jnp reference
    otherwise."""
    if interpret is None:
        on_tpu = jax.default_backend() == "tpu"
        if not on_tpu or not _can_tile(x) or not _worth_it(x):
            return layer_norm_reference(x, gain, bias, eps)
        interpret = False
    elif not _can_tile(x):        # interpret mode: correctness gate only
        return layer_norm_reference(x, gain, bias, eps)
    bias_arg = jnp.zeros((x.shape[-1],), jnp.float32) if bias is None \
        else bias
    return _fused_ln(x, gain, bias_arg, eps, interpret)
