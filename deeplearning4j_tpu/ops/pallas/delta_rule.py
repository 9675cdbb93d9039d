"""The chunkwise delta rule's recurrence ACROSS chunks, chunk after chunk.

`ops/linear_attention.py` computes everything that lies inside one chunk of
`C` tokens for all chunks at once; what is left is the state `S` [dk, dv] a
head carries from chunk to chunk.  For chunk n, with `S` the state before it
(held here transposed, `St = S^T` [dv, dk], so that the decay scales lanes):

    V   = u - w S                    the chunk's values less what S predicts
    O   = qg S + Aqk V               its outputs, the scale folded into both
    S'  = Diag(gc) S + kd^T V        the state after it

`w`, `qg`, `kd` [C, dk], `u` [C, dv], `Aqk` [C, C] (lower triangle) and the
decay over the whole chunk `gc` [dk] come from the chunk's own tokens.  Here
the grid is (head block, chunk), the chunk axis sequential, the state held in
VMEM (float32, a [dv, dk] tile a head) across it; every block of the chunk's
arrays is read once and `O` written once.

`across_chunks` runs it forward and hands out the state before every chunk as
well (`states` [BH, N, dv, dk]: the backward pass needs them) and the last
one; `across_chunks_bwd` walks the chunks in reverse with the gradient of the
state in VMEM:

    dV   = Aqk^T dO + kd dS'         (dS' the gradient of the state after)
    dw   = -dV S^T,  du = dV,  dqg = dO S^T,  dAqk = dO V^T,  dkd = V dS'^T
    dgc  = sum over values of S * dS'
    dS   = Diag(gc) dS' + qg^T dO - w^T dV

and hands out the gradient of the first state too.  Every product is float32
at full precision (`#tpu.contract_precision<fp32>` under Mosaic): the state
runs through every chunk of a sequence.

The `*_reference` twins are the definitions: the same equations in
`jax.numpy` under `lax.scan`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HEADS = 8                      # heads a grid step at most
_VMEM_LIMIT = 32 << 20
_NN = (((1,), (0,)), ((), ()))                 # a [m, c], b [c, n] -> [m, n]
_NT = (((1,), (1,)), ((), ()))                 # a [m, c], b [n, c] -> [m, n]
_TN = (((0,), (0,)), ((), ()))                 # a [c, m], b [c, n] -> [m, n]


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _heads(bh: int) -> int:
    """Heads a grid step: the most, up to `_HEADS`, that divide `bh`."""
    return max(h for h in range(1, min(_HEADS, bh) + 1) if bh % h == 0)


# ---------------------------------------------------------------------------
# definitions
# ---------------------------------------------------------------------------

def across_chunks_reference(w, u, qg, kd, gc, aqk, s0):
    """`(o [BH, T, dv], states [BH, N, dv, dk], last [BH, dv, dk])` of
    `w`, `qg`, `kd` [BH, T, dk], `u` [BH, T, dv], `gc` [BH, N, 1, dk],
    `aqk` [BH, T, C] and the first state `s0` [BH, dv, dk] (transposed
    states, float32 all)."""
    BH, T, _ = w.shape
    C = aqk.shape[-1]
    N = T // C

    def by_chunk(a):
        return jnp.moveaxis(a.reshape(BH, N, C, a.shape[-1]), 1, 0)

    def step(s, xs):
        w_, u_, qg_, kd_, gc_, a_ = xs
        v = u_ - jnp.einsum("bck,bvk->bcv", w_, s, precision="highest")
        o = (jnp.einsum("bck,bvk->bcv", qg_, s, precision="highest")
             + jnp.einsum("bct,btv->bcv", a_, v, precision="highest"))
        s_next = s * gc_ + jnp.einsum("bcv,bck->bvk", v, kd_,
                                      precision="highest")
        return s_next, (o, s)

    last, (o, states) = jax.lax.scan(
        step, s0, (by_chunk(w), by_chunk(u), by_chunk(qg), by_chunk(kd),
                   jnp.moveaxis(gc, 1, 0), by_chunk(aqk)))
    return (jnp.moveaxis(o, 0, 1).reshape(BH, T, -1),
            jnp.moveaxis(states, 0, 1), last)


def across_chunks_bwd_reference(do, w, u, qg, kd, gc, aqk, states, d_last):
    """`(dw, du, dqg, dkd, dgc, daqk, ds0)` of `across_chunks` for the
    cotangents `do` [BH, T, dv] and `d_last` [BH, dv, dk], given the states
    before every chunk (`across_chunks`' second result)."""
    BH, T, _ = w.shape
    C = aqk.shape[-1]
    N = T // C
    hi = "highest"

    def by_chunk(a):
        return jnp.moveaxis(a.reshape(BH, N, C, a.shape[-1]), 1, 0)

    def step(ds, xs):
        do_, w_, u_, qg_, kd_, gc_, a_, s = xs
        v = u_ - jnp.einsum("bck,bvk->bcv", w_, s, precision=hi)
        dv = (jnp.einsum("btc,btv->bcv", a_, do_, precision=hi)
              + jnp.einsum("bck,bvk->bcv", kd_, ds, precision=hi))
        grads = (-jnp.einsum("bcv,bvk->bck", dv, s, precision=hi), dv,
                 jnp.einsum("bcv,bvk->bck", do_, s, precision=hi),
                 jnp.einsum("bcv,bvk->bck", v, ds, precision=hi),
                 jnp.sum(s * ds, axis=1, keepdims=True),
                 jnp.einsum("bcv,btv->bct", do_, v, precision=hi))
        ds = (ds * gc_ + jnp.einsum("bcv,bck->bvk", do_, qg_, precision=hi)
              - jnp.einsum("bcv,bck->bvk", dv, w_, precision=hi))
        return ds, grads

    xs = (by_chunk(do), by_chunk(w), by_chunk(u), by_chunk(qg), by_chunk(kd),
          jnp.moveaxis(gc, 1, 0), by_chunk(aqk), jnp.moveaxis(states, 1, 0))
    ds0, (dw, du, dqg, dkd, dgc, daqk) = jax.lax.scan(step, d_last, xs,
                                                       reverse=True)
    flat = lambda a: jnp.moveaxis(a, 0, 1).reshape(BH, T, -1)  # noqa: E731
    return (flat(dw), flat(du), flat(dqg), flat(dkd),
            jnp.moveaxis(dgc, 0, 1), flat(daqk), ds0)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(w_ref, u_ref, qg_ref, kd_ref, gc_ref, a_ref, s0_ref,
                o_ref, st_ref, last_ref, s_sc, *, heads):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        s_sc[...] = s0_ref[...]

    for h in range(heads):
        s = s_sc[h]                                        # [dv, dk]
        st_ref[h, 0] = s
        v = u_ref[h] - _dot(w_ref[h], s, _NT)              # [C, dv]
        o_ref[h] = _dot(qg_ref[h], s, _NT) + _dot(a_ref[h], v, _NN)
        s_sc[h] = s * gc_ref[h, 0] + _dot(v, kd_ref[h], _TN)

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        last_ref[...] = s_sc[...]


def _bwd_kernel(do_ref, w_ref, u_ref, qg_ref, kd_ref, gc_ref, a_ref, st_ref,
                dl_ref, dw_ref, du_ref, dqg_ref, dkd_ref, dgc_ref, da_ref,
                ds0_ref, ds_sc, *, heads):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        ds_sc[...] = dl_ref[...]

    for h in range(heads):
        s, ds, do = st_ref[h, 0], ds_sc[h], do_ref[h]
        w = w_ref[h]
        v = u_ref[h] - _dot(w, s, _NT)                     # [C, dv]
        dv = _dot(a_ref[h], do, _TN) + _dot(kd_ref[h], ds, _NT)
        du_ref[h] = dv
        dw_ref[h] = -_dot(dv, s, _NN)
        dqg_ref[h] = _dot(do, s, _NN)
        dkd_ref[h] = _dot(v, ds, _NN)
        dgc_ref[h, 0] = jnp.sum(s * ds, axis=0, keepdims=True)
        da_ref[h] = _dot(do, v, _NT)
        ds_sc[h] = (ds * gc_ref[h, 0] + _dot(do, qg_ref[h], _TN)
                    - _dot(dv, w, _TN))

    @pl.when(n == pl.num_programs(1) - 1)
    def _():
        ds0_ref[...] = ds_sc[...]


def _specs(BH, T, C, dk, dv, reverse: bool):
    """Block specs of the chunk's arrays, its decay, its states, a head
    block's whole state; chunk `n` of the grid is chunk N-1-n in reverse."""
    N = T // C
    hb = _heads(BH)
    at = (lambda n: N - 1 - n) if reverse else (lambda n: n)
    rows = lambda d: pl.BlockSpec((hb, C, d),  # noqa: E731
                                  lambda i, n: (i, at(n), 0))
    per_chunk = lambda d, e: pl.BlockSpec(  # noqa: E731
        (hb, 1, d, e), lambda i, n: (i, at(n), 0, 0))
    whole = pl.BlockSpec((hb, dv, dk), lambda i, n: (i, 0, 0))
    return hb, (BH // hb, N), rows, per_chunk, whole


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                vmem_limit_bytes=_VMEM_LIMIT)


def across_chunks(w, u, qg, kd, gc, aqk, s0, interpret=False):
    """`across_chunks_reference` as one Mosaic kernel."""
    BH, T, dk = w.shape
    dv, C = u.shape[-1], aqk.shape[-1]
    hb, grid, rows, per_chunk, whole = _specs(BH, T, C, dk, dv, False)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=hb),
        grid=grid,
        in_specs=[rows(dk), rows(dv), rows(dk), rows(dk), per_chunk(1, dk),
                  rows(C), whole],
        out_specs=[rows(dv), per_chunk(dv, dk), whole],
        out_shape=[jax.ShapeDtypeStruct((BH, T, dv), f32),
                   jax.ShapeDtypeStruct((BH, T // C, dv, dk), f32),
                   jax.ShapeDtypeStruct((BH, dv, dk), f32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), f32)],
        compiler_params=_params(),
        interpret=interpret,
    )(w, u, qg, kd, gc, aqk, s0)


def across_chunks_bwd(do, w, u, qg, kd, gc, aqk, states, d_last,
                      interpret=False):
    """`across_chunks_bwd_reference` as one Mosaic kernel, chunks in
    reverse."""
    BH, T, dk = w.shape
    dv, C = u.shape[-1], aqk.shape[-1]
    hb, grid, rows, per_chunk, whole = _specs(BH, T, C, dk, dv, True)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_bwd_kernel, heads=hb),
        grid=grid,
        in_specs=[rows(dv), rows(dk), rows(dv), rows(dk), rows(dk),
                  per_chunk(1, dk), rows(C), per_chunk(dv, dk), whole],
        out_specs=[rows(dk), rows(dv), rows(dk), rows(dk), per_chunk(1, dk),
                   rows(C), whole],
        out_shape=[jax.ShapeDtypeStruct((BH, T, dk), f32),
                   jax.ShapeDtypeStruct((BH, T, dv), f32),
                   jax.ShapeDtypeStruct((BH, T, dk), f32),
                   jax.ShapeDtypeStruct((BH, T, dk), f32),
                   jax.ShapeDtypeStruct((BH, T // C, 1, dk), f32),
                   jax.ShapeDtypeStruct((BH, T, C), f32),
                   jax.ShapeDtypeStruct((BH, dv, dk), f32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), f32)],
        compiler_params=_params(),
        interpret=interpret,
    )(do, w, u, qg, kd, gc, aqk, states, d_last)
