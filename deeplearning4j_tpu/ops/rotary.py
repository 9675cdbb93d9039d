"""Rotary position embedding (Su et al. 2021, arXiv:2104.09864).

Two forms, which differ in which two elements of the last axis make pair
`i`, the pair that is rotated by the angle `pos * base**(-2i/d)`:

- interleaved (`rotary_interleaved`): `(x[2i], x[2i+1])`, as `deepseek_v3`
  configs say with `rope_interleave`;
- half-split (`rotary_half_split`): `(x[i], x[i + d/2])`, the `rotate_half`
  of most public decoders.

A checkpoint trained under one is wrong under the other, so the form is part
of a model's definition.  They are the same rotation on a permuted axis:
half-split of `x` is interleaved of `x` with `[0, d/2, 1, d/2 + 1, ...]`
gathered, scattered back.
"""
from __future__ import annotations

import jax.numpy as jnp


def rotary_angles(positions, dim: int, base: float = 10000.0):
    """Angles [..., dim/2] in float32 for integer `positions` [...]."""
    inv_freq = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return positions.astype(jnp.float32)[..., None] * inv_freq


def rotary_interleaved(x, positions, base: float = 10000.0):
    """Rotate `x` [..., T, heads, d] by `positions` [T] (or [..., T]): pair
    i of every head, `(x[..., 2i], x[..., 2i+1])`, turns by `pos *
    base**(-2i/d)`.  Angles, sines and the rotation in float32; returned in
    `x`'s dtype."""
    d = x.shape[-1]
    ang = rotary_angles(positions, d, base)[..., None, :]   # over the heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rotary_half_split(x, positions, base: float = 10000.0):
    """Rotate `x` [..., T, heads, d] by `positions` [T] (or [..., T]): pair
    i of every head, `(x[..., i], x[..., i + d/2])`, turns by `pos *
    base**(-2i/d)`.  Angles, sines and the rotation in float32; returned in
    `x`'s dtype."""
    d = x.shape[-1]
    ang = rotary_angles(positions, d, base)[..., None, :]   # over the heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., :d // 2], xf[..., d // 2:]
    out = jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.astype(x.dtype)
