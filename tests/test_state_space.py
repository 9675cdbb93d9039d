"""`ops/state_space.py`: the chunked state-space dual against its token-by-
token definition in float32 — forward and every gradient, several chunk
lengths and a length that is no multiple of the chunk, heads sharing a
group's B and C, the D skip, and the scope its ops carry."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.state_space import ssd, ssd_recurrent

F32 = jnp.float32


def _inputs(seed=0, b=2, T=40, h=8, p=4, g=2, n=6):
    """x, dt (after softplus: (0.01, 0.3)), A (-(0.5, 4)), B, C, D."""
    r = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(r.normal(size=shape), F32)

    return (normal(b, T, h, p), jnp.asarray(r.uniform(0.01, 0.3, (b, T, h)),
                                            F32),
            -jnp.asarray(r.uniform(0.5, 4.0, h), F32), normal(b, T, g, n),
            normal(b, T, g, n), normal(h))


def _close(got, want, tol=1e-5):
    """Largest difference within `tol` of the largest magnitude."""
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


_recurrent = jax.jit(ssd_recurrent)


@pytest.mark.parametrize("T,chunk", [(40, 8), (40, 16), (40, 40), (37, 8),
                                     (37, 16), (64, 128)])
def test_chunks_against_the_recurrence(T, chunk):
    """Any chunk length, and a T that is no multiple of it (the tail padded
    with tokens that neither decay nor write), gives the recurrence's y."""
    args = _inputs(T=T)
    got = jax.jit(ssd, static_argnums=6)(*args, chunk)
    assert got.shape == args[0].shape and got.dtype == F32
    _close(got, _recurrent(*args))


@pytest.mark.parametrize("chunk", [8, 16])
def test_gradients_against_the_recurrences(chunk):
    """d/dx, d/ddt, d/dA, d/dB, d/dC and d/dD of a weighted sum of y, the
    custom VJP against autodiff of the token-by-token definition, over 37
    tokens (a padded tail)."""
    args = _inputs(seed=1, T=37)
    ct = jnp.asarray(np.random.default_rng(2).normal(size=args[0].shape), F32)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(ssd(*a, chunk=chunk) * ct),
                           argnums=range(6)))(*args)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(ssd_recurrent(*a) * ct),
                            argnums=range(6)))(*args)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        _close(g, w)


def test_heads_read_their_groups_b_and_c():
    """Eight heads over one group read that group's B and C: the same as
    eight groups that each repeat it; heads 0..3 and 4..7 over two groups
    read group 0 and group 1."""
    x, dt, A, B, C, D = _inputs(seed=3, h=8, g=1)
    one = jax.jit(ssd)(x, dt, A, B, C, D)
    _close(one, jax.jit(ssd)(x, dt, A, jnp.repeat(B, 8, 2),
                             jnp.repeat(C, 8, 2), D))
    x, dt, A, B, C, D = _inputs(seed=4, h=8, g=2)
    two = jax.jit(ssd)(x, dt, A, B, C, D)
    for grp, heads in ((0, slice(0, 4)), (1, slice(4, 8))):
        alone = jax.jit(ssd)(x[:, :, heads], dt[:, :, heads], A[heads],
                             B[:, :, grp:grp + 1], C[:, :, grp:grp + 1],
                             D[heads])
        _close(two[:, :, heads], alone)


def test_the_d_skip_and_a_state_that_forgets():
    """With C zero only the skip is left, `D x`; with a decay so strong
    that nothing carries over a token, y is `dt (C . B) x + D x` token by
    token."""
    x, dt, A, B, C, D = _inputs(seed=5)
    np.testing.assert_allclose(
        jax.jit(ssd)(x, dt, A, B, jnp.zeros_like(C), D),
        D[:, None] * x, rtol=1e-6, atol=1e-7)
    forget = jnp.full_like(A, -1e4)
    got = jax.jit(ssd)(x, dt, forget, B, C, D)
    cb = jnp.einsum("btgn,btgn->btg", C, B)
    want = (jnp.repeat(cb, 4, 2) * dt)[..., None] * x + D[:, None] * x
    _close(got, want)


def test_heads_that_are_no_multiple_of_the_groups_are_refused():
    x, dt, A, B, C, D = _inputs(h=6, g=4)
    with pytest.raises(ValueError, match="6 heads"):
        ssd(x, dt, A, B, C, D)


def test_products_take_the_inputs_dtype_and_the_scan_its_scope():
    """bfloat16 x, B, C: y stays float32 and within bfloat16's rounding of
    the float32 recurrence; the ops of the forward and of the backward
    (the custom VJP's) carry `ssd_scan`."""
    x, dt, A, B, C, D = _inputs(seed=6)
    bf = [a.astype(jnp.bfloat16) for a in (x, B, C)]
    got = jax.jit(ssd)(bf[0], dt, A, bf[1], bf[2], D)
    assert got.dtype == F32
    want = _recurrent(bf[0].astype(F32), dt, A, bf[1].astype(F32),
                      bf[2].astype(F32), D)
    _close(got, want, tol=3e-2)
    text = jax.jit(jax.grad(lambda x: jnp.sum(ssd(x, dt, A, B, C, D)))).lower(
        x).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    assert sum("ssd_scan" in n for n in names) >= len(names) // 2
