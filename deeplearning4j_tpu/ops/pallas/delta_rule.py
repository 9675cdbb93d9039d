"""The chunkwise delta rule's Mosaic kernels: the recurrence ACROSS chunks,
chunk after chunk, and what lies INSIDE each chunk.

Inside a chunk of `C` tokens everything comes from the chunk's own tokens
(`within_chunks` below); what is left is the state `S` [dk, dv] a head
carries from chunk to chunk.  For chunk n, with `S` the state before it
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

INSIDE the chunks, `within_chunks` computes what `ops/linear_attention.py`'s
`_within_chunks` (the definition) computes, one chunk of a block of heads a
grid step (grid (head block, chunk), both parallel), everything in VMEM and
float32 at full precision.  For a chunk's q, k, g [C, dk], v [C, dv] and
beta [C], with sub-blocks of `sub` tokens starting at r_b = b sub:

    sums   = M g          M [(2 + nb) C, C] of 0/1: the rows of
                          G_t = sum_{j<=t} g_j,
                          loc_t = sum_{r<j<=t} g_j (r: t's sub-block start),
                          te_t = sum_{j>t} g_j,
                          E_b[i] = sum_{i<j<=r_b} g_j (i < r_b), b = 1..nb-1
    S_b    = P g_b        P [sub^2, sub]: (t, i) -> sum_{i<j<=t} g_j, the
                          [sub, sub, dk] tile of sub-block b as sub^2 rows
    A_X[t, i] = (X_t e^loc_t) . (k_i e^E_b[i])        i in an earlier block
              = sum_c X_t[c] k_i[c] e^S_b[(t,i), c]   i in t's sub-block
                                                      (X = q for A_qk, k)
    Aqk    = scale A_q (i <= t),  L = Diag(beta) A_k (i < t)
    T      = (I + L)^-1           doubling: T <- T - T L_s T, L_s the part
                                  of L between the two halves of each 2s
                                  block, s = 1 (T = I - L_1), 2, 4, ...;
                                  two heads' L as one block-diagonal
                                  [2C, 2C], which fills the MXU
    u = T (beta v),  w = T (beta k e^G),  qg = scale q e^G,  kd = k e^te,
    gc = e^(sum of g)

Every exponent is a sum over the tokens it spans (a 0/1 product), none a
difference of two sums, and none positive.  A product of a 0/1 matrix (M,
P, a one-hot) takes the float32 operand as three bfloat16 parts, one pass
each: exact, where full precision would make six.  The (t, i) pairs of a
sub-block are rows of [sub^2, dk] arrays: a row of X or k is taken to its
pairs by a broadcast, a pair's value back to [sub, C] by a one-hot
product.

`within_chunks_bwd` recomputes all of that from q, k, v, g, beta and takes
the cotangents dw, du, dqg, dkd, dgc, daqk:

    du', dw'  = T^T du, T^T dw                 the solve transposed (upper)
    dL     = -(du' u^T + dw' w^T)  (i < t)
    dbeta  = rowsum(du' v + dw' k e^G + dL A_k)
    dv     = beta du',  dA_k = beta dL,  dA_q = scale daqk (i <= t)
    dq     = scale dqg e^G + dXl_q e^loc + (intra),   dk likewise with
             beta dw' e^G + dkd e^te + dcols e^E (i < r_b) + (intra)
    cross (row block b >= 1): dXl_b = dA_X,b cols_b,
             dcols_b = sum_X dA_X,b^T Xl_b,  dE_b = dcols_b * cols_b
    intra: dp_X(t,i) = dA_X[t, i];  dX_t += sum_i dp_X k_i e^S,
             dk_i += sum_i (dp_q q_t + dp_k k_t) e^S,
             dS = (dp_q q_t + dp_k k_t) k_i e^S
    dg     = M^T [dG; dloc; dte; dE_1..] + P^T dS_b (per block)
             + dgc e^(sum g) on every row,
    dG = (beta dw' k + scale dqg q) e^G,  dloc = sum_X dXl_X * Xl_X,
    dte = dkd * kd

so each decay's gradient is gathered over the very tokens whose sum the
forward took.
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


def _dot01(a, x, dims):
    """`a` (entries 0 or 1) times float32 `x`, exactly as `_dot` but in
    three single passes: `x` as the sum of three bfloat16 parts."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    a = a.astype(bf16)
    hi = x.astype(bf16)
    rest = x - hi.astype(f32)
    mid = rest.astype(bf16)
    lo = (rest - mid.astype(f32)).astype(bf16)
    return sum(jax.lax.dot_general(a, part, dims, preferred_element_type=f32)
               for part in (hi, mid, lo))


_GROUP = 2      # heads whose chunk solves share one block-diagonal inverse


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


# ---------------------------------------------------------------------------
# inside the chunks
# ---------------------------------------------------------------------------

def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _sub(C: int) -> int:
    """Tokens a sub-block: `_within_chunks`' rule (16, halved until it
    divides C)."""
    sub = min(16, C)
    while C % sub:
        sub //= 2
    return sub


def _sums_matrix(C: int, sub: int):
    """M [(2 + nb) C, C]: the 0/1 rows of G, loc, te and E_1..E_{nb-1}
    (module docstring)."""
    t, j = _iota((C, C), 0), _iota((C, C), 1)
    s = sub.bit_length() - 1
    mats = [j <= t,
            ((t >> s) == (j >> s)) & (j <= t) & ((j & (sub - 1)) != 0),
            j > t]
    mats += [(t < r) & (j > t) & (j <= r) for r in range(sub, C, sub)]
    return jnp.concatenate([m.astype(jnp.float32) for m in mats], axis=0)


def _pairs(sub: int):
    """P [sub^2, sub] (the span of pair (t, i) = t sub + i: i < j <= t) and
    the one-hots E_t, E_i [sub, sub^2] of a pair's t and i."""
    f32 = jnp.float32
    s = sub.bit_length() - 1
    p, j = _iota((sub * sub, sub), 0), _iota((sub * sub, sub), 1)
    spans = (((p & (sub - 1)) < j) & (j <= (p >> s))).astype(f32)
    row, p = _iota((sub, sub * sub), 0), _iota((sub, sub * sub), 1)
    return spans, (row == (p >> s)).astype(f32), \
        (row == (p & (sub - 1))).astype(f32)


def _pair_cols(sub: int, C: int, r: int):
    """[sub^2, 2C]: pair (t, i)'s column r + i of a [sub, C] row block, in
    each of two such blocks side by side."""
    p, c = _iota((sub * sub, 2 * C), 0), _iota((sub * sub, 2 * C), 1)
    return ((c & (C - 1) if C & (C - 1) == 0 else c % C)
            == r + (p & (sub - 1))).astype(jnp.float32)


def _by_t(x, sub):
    """[sub, n] -> [sub^2, n]: row t to every pair (t, .)."""
    return jnp.broadcast_to(x[:, None, :], (sub, sub, x.shape[-1])).reshape(
        sub * sub, x.shape[-1])


def _by_i(x, sub):
    """[sub, n] -> [sub^2, n]: row i to every pair (., i)."""
    return jnp.broadcast_to(x[None], (sub, sub, x.shape[-1])).reshape(
        sub * sub, x.shape[-1])


def _diagonal(x, axis):
    """The diagonal of a [C, C] broadcast of the row (axis 1) or column
    (axis 0) `x`, as a column or a row: a row turned, exactly."""
    C = max(x.shape)
    t, i = _iota((C, C), 0), _iota((C, C), 1)
    return jnp.sum(jnp.where(t == i, jnp.broadcast_to(x, (C, C)), 0.0),
                   axis=axis, keepdims=True)


def _block_diag(ms):
    """[n C, n C] with the [C, C] matrices `ms` on its diagonal."""
    if len(ms) == 1:
        return ms[0]
    zero = jnp.zeros_like(ms[0])
    return jnp.concatenate([
        jnp.concatenate([m if j == i else zero for j in range(len(ms))],
                        axis=1) for i, m in enumerate(ms)], axis=0)


def _unit_lower_inverse(lm, C: int):
    """(I + lm)^-1 of a strictly lower `lm` [n, n], block diagonal in blocks
    of C (a power of two, or n), by doubling: the first level is I - lm's
    pairs (2t, 2t + 1), each later one two products."""
    n = lm.shape[0]
    t, i = _iota((n, n), 0), _iota((n, n), 1)

    def part(h):            # within blocks of 2^(h+1), across their halves
        return ((t >> (h + 1)) == (i >> (h + 1))) & ((t >> h) != (i >> h))

    inv = (t == i).astype(jnp.float32) - jnp.where(part(0), lm, 0.0)
    s = 2
    while s < C:
        h = s.bit_length() - 1
        inv = inv - _dot(inv, _dot(jnp.where(part(h), lm, 0.0), inv, _NN),
                         _NN)
        s *= 2
    return inv


def _inside(q, k, g, beta, scale):
    """What the forward and backward kernels share of one head's chunk:
    the exponentials, the cross-block columns, each sub-block's pair
    arrays, Aqk, A_k masked, and L = Diag(beta) A_k."""
    C, dk = q.shape
    sub = _sub(C)
    nb = C // sub
    m = _sums_matrix(C, sub)
    sums = _dot01(m, g, _NN)
    eG, eloc, ete = (jnp.exp(sums[a * C:(a + 1) * C]) for a in range(3))
    row = _iota((C, dk), 0)
    ee = [None] + [jnp.exp(sums[(2 + b) * C:(3 + b) * C])
                   for b in range(1, nb)]
    cols = [None] + [jnp.where(row < b * sub, k * ee[b], 0.0)
                     for b in range(1, nb)]
    ql, kl = q * eloc, k * eloc
    spans, et, ei = _pairs(sub)
    blocks, aq, ak = [], [], []
    for b in range(nb):
        sl = slice(b * sub, (b + 1) * sub)
        d = jnp.exp(_dot01(spans, g[sl], _NN))           # [sub^2, dk]
        qt, kt, ki = _by_t(q[sl], sub), _by_t(k[sl], sub), _by_i(k[sl], sub)
        cc = _pair_cols(sub, C, b * sub)
        kid = ki * d
        lane = _iota(cc.shape, 1)
        a = _dot01(et, jnp.where(lane < C,
                                 jnp.sum(qt * kid, axis=1, keepdims=True),
                                 jnp.sum(kt * kid, axis=1, keepdims=True))
                   * cc, _NN)                            # [sub, 2C]
        a_q, a_k = a[:, :C], a[:, C:]
        if b:
            a = _dot(jnp.concatenate([ql[sl], kl[sl]], axis=0), cols[b], _NT)
            a_q, a_k = a_q + a[:sub], a_k + a[sub:]
        aq.append(a_q)
        ak.append(a_k)
        blocks.append((qt, kt, ki, d, cc[:, :C]))
    t, i = _iota((C, C), 0), _iota((C, C), 1)
    aqk = jnp.where(t >= i, jnp.concatenate(aq, axis=0), 0.0) * scale
    akk = jnp.where(t > i, jnp.concatenate(ak, axis=0), 0.0)
    return dict(sub=sub, nb=nb, m=m, eG=eG, eloc=eloc, ete=ete, ee=ee,
                cols=cols, ql=ql, kl=kl, spans=spans, et=et, ei=ei,
                blocks=blocks, aqk=aqk, akk=akk, lm=beta * akk)


def _each_group(heads: int, C: int, body):
    """`body(hs)` for the grid step's heads in groups `hs` whose T is one
    block-diagonal inverse: pairs where C is a power of two and the heads
    pair up (a 128 x 128 product fills the MXU as a 64 x 64 one does not),
    else one at a time; a loop, so that the body is traced once."""
    n = _GROUP if C & (C - 1) == 0 and heads % _GROUP == 0 else 1

    def step(group, carry):
        body([group * n + j for j in range(n)])
        return carry

    jax.lax.fori_loop(0, heads // n, step, 0)


def _within_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, w_ref, u_ref,
                   qg_ref, kd_ref, gc_ref, a_ref, *, heads, scale):
    C, dv = v_ref.shape[1:]

    def group(hs):
        betas = [_diagonal(b_ref[h], 1) for h in hs]     # [C, 1]
        xs = [_inside(q_ref[h], k_ref[h], g_ref[h], beta, scale)
              for h, beta in zip(hs, betas)]
        inv = _unit_lower_inverse(_block_diag([x["lm"] for x in xs]), C)
        x_all = _dot(inv, jnp.concatenate([
            beta * jnp.concatenate([v_ref[h], k_ref[h] * x["eG"]], axis=1)
            for h, x, beta in zip(hs, xs, betas)], axis=0), _NN)  # [u | w]
        for j, (h, x) in enumerate(zip(hs, xs)):
            rows = slice(j * C, (j + 1) * C)
            u_ref[h] = x_all[rows, :dv]
            w_ref[h] = x_all[rows, dv:]
            qg_ref[h] = q_ref[h] * x["eG"] * scale
            kd_ref[h] = k_ref[h] * x["ete"]
            gc_ref[h] = jnp.exp(jnp.sum(g_ref[h], axis=0, keepdims=True))
            a_ref[h] = x["aqk"]

    _each_group(heads, C, group)


def _within_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, dw_ref, du_ref,
                       dqg_ref, dkd_ref, dgc_ref, da_ref, dq_ref, dk_ref,
                       dv_ref, dg_ref, db_ref, *, heads, scale):
    C, dv = v_ref.shape[1:]

    def group(hs):
        betas = [_diagonal(b_ref[h], 1) for h in hs]     # [C, 1]
        xs = [_inside(q_ref[h], k_ref[h], g_ref[h], beta, scale)
              for h, beta in zip(hs, betas)]
        inv = _unit_lower_inverse(_block_diag([x["lm"] for x in xs]), C)
        r = [jnp.concatenate([v_ref[h], k_ref[h] * x["eG"]], axis=1)
             for h, x in zip(hs, xs)]                   # [v | k e^G]
        x_all = _dot(inv, jnp.concatenate(
            [beta * r_ for beta, r_ in zip(betas, r)], axis=0), _NN)
        dx_all = _dot(inv, jnp.concatenate(             # T^T [du | dw]
            [jnp.concatenate([du_ref[h], dw_ref[h]], axis=1) for h in hs],
            axis=0), _TN)
        for j, (h, x) in enumerate(zip(hs, xs)):
            rows = slice(j * C, (j + 1) * C)
            _within_bwd_head(q_ref, k_ref, v_ref, g_ref, dqg_ref, dkd_ref,
                             dgc_ref, da_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                             db_ref, h, betas[j], x, r[j], x_all[rows],
                             dx_all[rows], scale)

    _each_group(heads, C, group)


def _within_bwd_head(q_ref, k_ref, v_ref, g_ref, dqg_ref, dkd_ref, dgc_ref,
                     da_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, h, beta,
                     x, r, x_h, dx, scale):
    """One head's gradients, given its [u | w] and T^T [du | dw]."""
    q, k, g = q_ref[h], k_ref[h], g_ref[h]
    C, width = q.shape
    dv = v_ref.shape[-1]
    sub, nb, eG = x["sub"], x["nb"], x["eG"]
    t, i = _iota((C, C), 0), _iota((C, C), 1)
    dl = jnp.where(t > i, -_dot(dx, x_h, _NT), 0.0)
    db_ref[h] = _diagonal(jnp.sum(dx * r, axis=1, keepdims=True)
                          + jnp.sum(dl * x["akk"], axis=1, keepdims=True), 0)
    dv_ref[h] = beta * dx[:, :dv]
    dw = dx[:, dv:]
    dqg, dkd = dqg_ref[h], dkd_ref[h]
    dq = scale * dqg * eG
    dk = beta * dw * eG + dkd * x["ete"]
    dG = (beta * dw * k + scale * dqg * q) * eG
    dte = dkd * k * x["ete"]
    da_q = jnp.where(t >= i, da_ref[h], 0.0) * scale
    da_k = beta * dl
    zero = jnp.zeros((sub, width), jnp.float32)
    dql, dkl, dE, dq_in, dk_in, dg_in = [zero], [zero], [], [], [], []
    row = _iota((C, width), 0)
    for b in range(nb):
        sl = slice(b * sub, (b + 1) * sub)
        dq_b, dk_b = da_q[sl], da_k[sl]                  # [sub, C]
        both = jnp.concatenate([dq_b, dk_b], axis=0)      # [2 sub, C]
        if b:
            cols = x["cols"][b]
            dl_b = _dot(both, cols, _NN)
            dql.append(dl_b[:sub])
            dkl.append(dl_b[sub:])
            dcols = _dot(both, jnp.concatenate(
                [x["ql"][sl], x["kl"][sl]], axis=0), _TN)  # [C, dk]
            dk = dk + jnp.where(row < b * sub, dcols * x["ee"][b], 0.0)
            dE.append(dcols * cols)
        qt, kt, ki, d, cc = x["blocks"][b]
        dp_q = jnp.sum(_by_t(dq_b, sub) * cc, axis=1, keepdims=True)
        dp_k = jnp.sum(_by_t(dk_b, sub) * cc, axis=1, keepdims=True)
        kid = ki * d
        dx_in = _dot01(x["et"], jnp.concatenate([dp_q * kid, dp_k * kid],
                                                axis=1), _NN)
        dq_in.append(dx_in[:, :width])
        dki = (dp_q * qt + dp_k * kt) * d
        dk_in.append(dx_in[:, width:] + _dot01(x["ei"], dki, _NN))
        dg_in.append(_dot01(x["spans"], dki * ki, _TN))
    dql, dkl = jnp.concatenate(dql, axis=0), jnp.concatenate(dkl, axis=0)
    dq_ref[h] = dq + dql * x["eloc"] + jnp.concatenate(dq_in, axis=0)
    dk_ref[h] = dk + dkl * x["eloc"] + jnp.concatenate(dk_in, axis=0)
    dloc = dql * x["ql"] + dkl * x["kl"]
    dsums = jnp.concatenate([dG, dloc, dte] + dE, axis=0)
    dgc = dgc_ref[h] * jnp.exp(jnp.sum(g, axis=0, keepdims=True))
    dg_ref[h] = (_dot01(x["m"], dsums, _TN) + jnp.concatenate(dg_in, axis=0)
                 + dgc)


def _within_specs(BH, N, C):
    """Head block, grid, and the block spec of a [BH, N, C, e] array's
    chunk for a block of heads."""
    hb = _heads(BH)

    def block(rows, e):
        return pl.BlockSpec((hb, pl.Squeezed(), rows, e),
                            lambda i, n: (i, n, 0, 0))
    return hb, (BH // hb, N), block


def _within_params():
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"),
                                vmem_limit_bytes=_VMEM_LIMIT)


def within_chunks(q, k, v, g, beta, scale, interpret=False):
    """`_within_chunks` of `ops/linear_attention.py` as one Mosaic kernel:
    `q`, `k`, `g` [BH, N, C, dk], `v` [BH, N, C, dv], `beta` [BH, N, C]
    -> `(w, u, qg, kd, gc [BH, N, dk], aqk [BH, N, C, C])`."""
    BH, N, C, dk = q.shape
    dv = v.shape[-1]
    hb, grid, block = _within_specs(BH, N, C)
    f32 = jnp.float32
    shape = lambda *s: jax.ShapeDtypeStruct((BH, N) + s, f32)  # noqa: E731
    w, u, qg, kd, gc, aqk = pl.pallas_call(
        functools.partial(_within_kernel, heads=hb, scale=scale),
        grid=grid,
        in_specs=[block(C, dk), block(C, dk), block(C, dv), block(C, dk),
                  block(1, C)],
        out_specs=[block(C, dk), block(C, dv), block(C, dk), block(C, dk),
                   block(1, dk), block(C, C)],
        out_shape=[shape(C, dk), shape(C, dv), shape(C, dk), shape(C, dk),
                   shape(1, dk), shape(C, C)],
        compiler_params=_within_params(),
        interpret=interpret,
    )(q, k, v, g, beta[:, :, None])
    return w, u, qg, kd, gc.reshape(BH, N, dk), aqk


def within_chunks_bwd(q, k, v, g, beta, dw, du, dqg, dkd, dgc, daqk, scale,
                      interpret=False):
    """The VJP of `within_chunks` as one Mosaic kernel on the same grid:
    `(dq, dk, dv, dg, dbeta)` for the cotangents of its six results."""
    BH, N, C, dk = q.shape
    dv = v.shape[-1]
    hb, grid, block = _within_specs(BH, N, C)
    f32 = jnp.float32
    shape = lambda *s: jax.ShapeDtypeStruct((BH, N) + s, f32)  # noqa: E731
    dq, dk_, dv_, dg, db = pl.pallas_call(
        functools.partial(_within_bwd_kernel, heads=hb, scale=scale),
        grid=grid,
        in_specs=[block(C, dk), block(C, dk), block(C, dv), block(C, dk),
                  block(1, C), block(C, dk), block(C, dv), block(C, dk),
                  block(C, dk), block(1, dk), block(C, C)],
        out_specs=[block(C, dk), block(C, dk), block(C, dv), block(C, dk),
                   block(1, C)],
        out_shape=[shape(C, dk), shape(C, dk), shape(C, dv), shape(C, dk),
                   shape(1, C)],
        compiler_params=_within_params(),
        interpret=interpret,
    )(q, k, v, g, beta[:, :, None], dw, du, dqg, dkd,
      dgc.reshape(BH, N, 1, dk), daqk)
    return dq, dk_, dv_, dg, db.reshape(BH, N, C)
