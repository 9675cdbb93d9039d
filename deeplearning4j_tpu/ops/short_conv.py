"""The gated short convolution of the LFM2 family (`lfm2` / `lfm2_moe`
configs: `layer_types` "conv", `conv_L_cache` the kernel's length).

A token mixer whose cost is linear in the sequence: two gates around a
depthwise causal convolution over a few positions, between two products.

    [B, C, X] = split3(u W_in)            W_in  [H, 3H], each part [T, H]
    z = B * X
    c_t = sum_j k_j * z_{t-(L-1)+j}       k [L, H]; z before position 0 is 0
    y = (C * c) W_out                     W_out [H, H]

No activation and no bias in it (`causal_depthwise_conv` takes an optional
bias for the convolution of the Mamba-2 mixer, `zoo/decoder.py`, which has
one).  The in-projection is ONE product
of width 3H.  Between the two products the mix reads B, C and X once and
writes one [T, H] array: L shifted multiply-adds a channel, which XLA fuses
into one elementwise pass forward and one backward — it is bound by memory
bandwidth, and autodiff's gradient of it is again shifted multiply-adds
(checked against a hand-written loop in `tests/test_decoder_hybrid.py`).
The mix computes in float32 whatever the products' dtype.

Named scopes `in_proj`, `mix`, `out_proj` mark the three parts' device ops;
the caller names the layer (`zoo/decoder.py`: `short_conv`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_depthwise_conv(z, kernel, bias=None):
    """`c[..., t, :] = sum_j kernel[j] * z[..., t - (L-1) + j, :] + bias` for
    `z` [..., T, C], `kernel` [L, C] and `bias` [C] or None (no bias, the
    gated short convolution's case): every channel its own L taps, the last
    tap on the position itself, positions before 0 zero."""
    taps, t = kernel.shape[0], z.shape[-2]
    padded = jnp.pad(z, [(0, 0)] * (z.ndim - 2) + [(taps - 1, 0), (0, 0)])
    c = sum(kernel[j] * padded[..., j:j + t, :] for j in range(taps))
    return c if bias is None else c + bias


def gated_short_conv(u, w_in, kernel, w_out):
    """`(C * causal_conv(B * X)) W_out` with `[B, C, X] = split3(u W_in)`,
    for `u` [..., T, H]; `w_in` [H, 3H], `kernel` [L, H], `w_out` [H, H]."""
    with jax.named_scope("in_proj"):
        bcx = u @ w_in
    with jax.named_scope("mix"):
        b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
        mixed = c * causal_depthwise_conv(b * x, kernel.astype(jnp.float32))
        mixed = mixed.astype(u.dtype)
    with jax.named_scope("out_proj"):
        return mixed @ w_out
