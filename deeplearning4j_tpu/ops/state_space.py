"""The state-space dual (SSD) of Mamba-2 (Dao & Gu, arXiv:2405.21060), chunk
by chunk, on the train path: the token mixer of Nemotron-H's `M` layers
(arXiv:2504.03624).

A head carries a state `S` [N, P] through the sequence.  For token t with
input `x_t` [P], step `dt_t` > 0, the head's scalar decay rate `A` < 0 and
the vectors `B_t`, `C_t` [N] of the head's GROUP (heads `a` read group
`a // (heads / groups)`):

    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T
    y_t = S_t^T C_t + D x_t

`ssd_recurrent` is that recurrence, token by token: the definition.  `ssd`
computes the same thing `chunk` tokens at a time.  Inside a chunk, with `a_t
= dt_t A`, `c_t` its cumulative sum from the chunk's start (inclusive), `X_t
= dt_t x_t` and `S` the state before the chunk:

    Y     = (C B^T o L) X + exp(c) (C S) + D x      L[t, s] = exp(c_t - c_s),
                                                   s <= t, else 0
    S'    = exp(c_last) S + (B o exp(c_last - c))^T X

`C B^T` is one [chunk, chunk] product a group, shared by its heads; every
exponent is a difference `c_t - c_s` with `s <= t` or `c_t` itself, so none
is positive.  The states cross the chunks in a `lax.scan` over the chunks,
carrying [heads, N, P] in float32.

Numerics: the decays, the cumulative sums, `dt` and the state in float32;
the products take their operands in `x`'s dtype and sum in float32; `y` is
float32.  The chunk length changes no number beyond rounding.

`ssd` has a `custom_vjp` whose only residuals are its inputs: the backward
computes the chunked form again and takes autodiff's VJP of it.  Both run
under `jax.named_scope("ssd_scan")`, so every device op of the scan, forward
and backward, carries that scope.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 128         # Nemotron-H's `chunk_size`
_HI = jax.lax.Precision.HIGHEST


def ssd_recurrent(x, dt, A, B, C, D):
    """`y` [b, T, h, P] float32 token by token, for `x` [b, T, h, P], `dt`
    [b, T, h] (after its softplus), `A` [h] (negative), `B`, `C` [b, T, g,
    N] and `D` [h]; the state before the first token is zero."""
    b, _, h, p = x.shape
    g, n = B.shape[2:]
    f32 = jnp.float32
    of_head = jnp.arange(h) // (h // g)

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs                    # [b, h, P], [b, h], ..
        b_t, c_t = b_t[:, of_head], c_t[:, of_head]         # [b, h, N]
        s = (jnp.exp(dt_t * A)[..., None, None] * s
             + (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :])
        return s, jnp.einsum("bhn,bhnp->bhp", c_t, s, precision=_HI)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (x, dt, B, C))
    _, y = jax.lax.scan(step, jnp.zeros((b, h, n, p), f32), xs)
    return (jnp.moveaxis(y, 0, 1)
            + D.astype(f32)[:, None] * x.astype(f32))


def _chunked(x, dt, A, B, C, D, chunk: int):
    """`ssd_recurrent`'s `y`, `chunk` tokens at a time (module docstring);
    T a multiple of `chunk`."""
    b, T, h, p = x.shape
    g, n = B.shape[2:]
    r, nc = h // g, T // chunk
    f32, dtype = jnp.float32, x.dtype
    # [b, chunks, chunk, groups, heads of the group, ...]
    xc = x.reshape(b, nc, chunk, g, r, p)
    dtc = dt.astype(f32).reshape(b, nc, chunk, g, r)
    Bc = B.astype(dtype).reshape(b, nc, chunk, g, n)
    Cc = C.astype(dtype).reshape(b, nc, chunk, g, n)
    cum = jnp.cumsum(dtc * A.astype(f32).reshape(g, r), axis=2)
    X = (xc * dtc[..., None]).astype(dtype)
    # inside the chunks
    cb = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc, preferred_element_type=f32)
    ct = cum.transpose(0, 1, 3, 4, 2)                       # [b, c, g, r, l]
    pos = jnp.arange(chunk)
    causal = pos[:, None] >= pos[None, :]
    decay = jnp.exp(jnp.where(causal, ct[..., :, None] - ct[..., None, :],
                              -jnp.inf))
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp",
                   (cb[:, :, :, None] * decay).astype(dtype), X,
                   preferred_element_type=f32)
    # what each chunk writes into the state, and the state across chunks
    to_end = jnp.exp(cum[:, :, -1:] - cum)                  # [b, c, l, g, r]
    written = jnp.einsum("bclgn,bclgrp->bcgrnp", Bc,
                         (X * to_end[..., None]).astype(dtype),
                         preferred_element_type=f32)
    kept = jnp.exp(cum[:, :, -1])                           # [b, c, g, r]

    def across(s, xs):
        kept_c, written_c = xs
        return kept_c[..., None, None] * s + written_c, s

    _, before = jax.lax.scan(
        across, jnp.zeros((b, g, r, n, p), f32),
        (jnp.moveaxis(kept, 1, 0), jnp.moveaxis(written, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                  # [b, c, g, r, n, p]
    y = y + jnp.einsum("bclgn,bcgrnp->bclgrp", Cc, before.astype(dtype),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y + D.astype(f32).reshape(g, r)[:, :, None] * xc.astype(f32)
    return y.reshape(b, T, h, p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd(x, dt, A, B, C, D, chunk):
    with jax.named_scope("ssd_scan"):
        return _chunked(x, dt, A, B, C, D, chunk)


def _ssd_fwd(x, dt, A, B, C, D, chunk):
    return _ssd(x, dt, A, B, C, D, chunk), (x, dt, A, B, C, D)


def _ssd_bwd(chunk, res, dy):
    with jax.named_scope("ssd_scan"):
        _, vjp = jax.vjp(functools.partial(_chunked, chunk=chunk), *res)
        return vjp(dy)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, A, B, C, D, chunk: int = CHUNK):
    """`ssd_recurrent`'s `y` [b, T, h, P] (float32) computed `chunk` tokens
    at a time.  `x`, `B` and `C` enter the products in `x`'s dtype; `dt`
    [b, T, h], `A` and `D` [h] are read in float32.  T need not be a
    multiple of `chunk`: the tail is padded with tokens whose `dt` is 0, so
    they neither decay the state nor write to it."""
    T = x.shape[1]
    if x.shape[2] % B.shape[2]:
        raise ValueError(f"{x.shape[2]} heads are no multiple of "
                         f"{B.shape[2]} groups")
    chunk = min(chunk, -(-T // 8) * 8)
    pad = -T % chunk

    def padded(a):
        return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)) \
            if pad else a

    y = _ssd(*map(padded, (x, dt.astype(jnp.float32))), A, padded(B),
             padded(C), D, chunk)
    return y[:, :T]
