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

`rotary_pairs` is the rotation itself with the axis left to the caller: the
two members of every pair arrive as two arrays, whole lanes each, and the
turn is four multiplies and two adds over them — no `[..., d/2, 2]` array,
no stack, no lane that changes place.  A caller whose `x` comes from a
product gets the two arrays for nothing by taking the product's weight
columns apart (`w[..., 0::2]`, `w[..., 1::2]`: interleaved; `w[..., :d/2]`,
`w[..., d/2:]`: half-split), and may lay the results side by side in any
order, provided queries and keys share it: rotary lanes enter nothing but
`q . k`, a sum over the same products in whatever order they stand.
`zoo/decoder.py`'s latent attention does that (PERF.md, PR 36: on the chip
the pair-stack of `rotary_interleaved` cost five passes over the queries,
through arrays with a minor dimension of 2); `rotary_interleaved` stays the
definition, and the tests hold `rotary_pairs` to it.

`rotary_sections` is the half-split form over several position streams
(multimodal rotary: `mrope_section` of a public config, e.g. [16, 24, 24] of
a head's 64 frequencies): frequency `i` takes its position from the stream
whose section holds `i`, the sections laid end to end in the order given
(the chunked layout).  Text gives every stream the token's index, and the
result is then `rotary_half_split`'s, bit for bit.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


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


def rotary_pairs(a, b, positions, base: float = 10000.0):
    """Pair i is `(a[..., i], b[..., i])`, `a` and `b` [..., T, d/2]: both
    members turned by `pos * base**(-2i/d)`, returned as two arrays.
    `positions` [T], or any shape that broadcasts against `a`'s leading axes
    (`[B, 1, T]` for `a` [B, heads, T, d/2]).  Angles, sines and the
    rotation in float32; returned in `a`'s dtype."""
    ang = rotary_angles(positions, 2 * a.shape[-1], base)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    af, bf = a.astype(jnp.float32), b.astype(jnp.float32)
    return ((af * cos - bf * sin).astype(a.dtype),
            (af * sin + bf * cos).astype(a.dtype))


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


def rotary_sections(x, positions, sections, base: float = 10000.0):
    """`rotary_half_split` of `x` [..., T, heads, d] whose frequency `i`
    turns by `positions[j] * base**(-2i/d)`, `j` the section that holds `i`:
    `positions` [streams, T] (or [streams, ..., T]), `sections` the number
    of frequencies each stream drives, `d/2` in all."""
    d = x.shape[-1]
    if sum(sections) != d // 2 or len(sections) != positions.shape[0]:
        raise ValueError(
            f"sections {tuple(sections)} over {positions.shape[0]} position "
            f"streams do not make the {d // 2} frequencies of a head of {d}")
    ang = rotary_angles(positions, d, base)           # [streams, ..., T, d/2]
    stream = np.repeat(np.arange(len(sections)), sections)
    ang = sum(jnp.where(stream == j, ang[j], 0.0)
              for j in range(len(sections)))[..., None, :]   # over the heads
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    a, b = xf[..., :d // 2], xf[..., d // 2:]
    out = jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.astype(x.dtype)
