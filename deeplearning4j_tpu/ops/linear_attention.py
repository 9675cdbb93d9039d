"""The gated delta rule with a decay per channel, chunk by chunk, on the train
path: the token mixer of Kimi delta attention (KDA; Kimi Linear,
arXiv:2510.26692), which Solar Open 2 puts in 3 of every 4 layers.

A head carries a state `S` [dk, dv] through the sequence.  For token t with
key `k_t` (L2-normed), value `v_t`, query `q_t` (L2-normed), a log decay a
channel `g_t` [dk] (<= 0) and a step `beta_t` (in (0, 2] where negative
eigenvalues are allowed):

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = scale * S_t^T q_t                      (scale = dk^-1/2)

`delta_rule_recurrent` is that recurrence, token by token: the definition.
`chunk_delta_rule` computes the same thing `C` (`CHUNK`) tokens at a time.
Inside a chunk, with `G` the decay's cumulative sum a channel from the
chunk's start (inclusive) and `S` the state before the chunk:

    A_kk[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])     i < t
    A_qk[t, i] = sum_c q_t[c] k_i[c] exp(G_t[c] - G_i[c])     i <= t
    (I + Diag(beta) A_kk) [u | w] = Diag(beta) [V | K exp(G)]  forward
                                                  substitution (unit lower)
    V_new = u - w S
    O     = scale (q exp(G)) S + scale A_qk V_new
    S'    = Diag(exp G_C) S + (K exp(G_C - G))^T V_new

Every exponent there is a DIFFERENCE `G_t - G_i` with `i <= t`, so none is
positive: `exp(-G)` alone is never formed (it overflows where the decay is
strong: 16 x softplus(.) a token at the init's largest `A`).  A_kk and A_qk
take the chunk in sub-blocks of `_SUB`: a pair in two different sub-blocks
factors through the later block's first position r, `exp(G_t - G_r) *
exp(G_r - G_i)`, both factors <= 1, and is a product over dk; a pair inside
one sub-block takes the decay summed over the tokens between them, a channel
([sub, sub, dk] a sub-block).  Every decay is a sum over the tokens it
spans, accumulated from its short end, never a difference of two long sums:
a gradient that cancels to a small one keeps its precision.
`_within_chunks` is that, every chunk of every head at once in batched
`jax.numpy` (float32, full precision): the definition.  Where the tier takes
the kernels, `ops/pallas/delta_rule.py`'s `within_chunks` computes the same
one chunk of a block of heads a grid step in VMEM, and `within_chunks_bwd`
its VJP from the same inputs; either pair sits behind a `custom_vjp` whose
only residuals are the inputs (the reference pair's backward is autodiff's
VJP of `_within_chunks`, the forward computed again).  What crosses
chunks — `V_new`, `O`, `S'` — is `ops/pallas/delta_rule.py`'s recurrence
over the grid (head, chunk), a Mosaic kernel where the tier takes one, with
a `custom_vjp` whose backward is the reverse recurrence over chunks (a
kernel of the same shape).

Names: the caller puts the whole under `jax.named_scope("delta_rule")`
(`zoo/decoder.py`); nothing is saved for the backward pass by name.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 64          # tokens a chunk (the kernel's grid walks T / CHUNK)
_SUB = 16           # A_kk and A_qk: exact differences inside this many
_HI = jax.lax.Precision.HIGHEST


def l2_normalize(x, eps: float = 1e-6):
    """`x / sqrt(sum x^2 + eps)` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


# ---------------------------------------------------------------------------
# the definition
# ---------------------------------------------------------------------------

def delta_rule_recurrent(q, k, v, g, beta, initial_state=None, scale=None):
    """`(o [B, H, T, dv], S [B, H, dk, dv])` token by token, float32, for
    `q`, `k`, `g` [B, H, T, dk], `v` [B, H, T, dv], `beta` [B, H, T]; the
    state before the first token is `initial_state` (zero by default)."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    scale = dk ** -0.5 if scale is None else scale
    f32 = jnp.float32
    s0 = (jnp.zeros((B, H, dk, dv), f32) if initial_state is None
          else initial_state.astype(f32))

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None]
        pred = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=_HI)
        s = s + (b_t[..., None, None] * k_t[..., :, None]
                 * (v_t - pred)[..., None, :])
        return s, scale * jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=_HI)

    xs = tuple(jnp.moveaxis(a.astype(f32), 2, 0) for a in (q, k, v, g, beta))
    s, o = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 2), s


# ---------------------------------------------------------------------------
# inside the chunks
# ---------------------------------------------------------------------------

def _within_chunks(q, k, v, g, beta, scale: float):
    """`(w, u, qg, kd, gc, aqk)` of every chunk at once: `q`, `k`, `g`
    [..., C, dk], `v` [..., C, dv], `beta` [..., C] (float32); results
    [..., C, dk], [..., C, dv], [..., C, dk], [..., C, dk], [..., dk],
    [..., C, C]."""
    lead, (C, dk), dv = q.shape[:-2], q.shape[-2:], v.shape[-1]
    sub = min(_SUB, C)
    while C % sub:
        sub //= 2
    nb = C // sub
    G = jnp.cumsum(g, axis=-2)
    pos = jnp.arange(C)
    # the decay from each sub-block's first position on, and from each
    # position to the chunk's end, as sums of the g AFTER the first: a
    # difference of two cumulative sums where both ends are one token
    # would cancel large gradients to leave a small one
    start = (pos % sub == 0)[:, None]
    local = jnp.cumsum(jnp.where(start, 0.0, g).reshape(*lead, nb, sub, dk),
                       axis=-2)                        # G_t - G_r
    after = jnp.concatenate([g[..., 1:, :], jnp.zeros_like(g[..., :1, :])],
                            axis=-2)                   # g_{t+1}

    def rev_cumsum(a):
        return jnp.flip(jnp.cumsum(jnp.flip(a, -2), -2), -2)

    to_end = rev_cumsum(after)                         # G_{C-1} - G_t
    rows = jnp.stack([q, k])                           # A_qk's and A_kk's
    # pairs across sub-blocks, through the row's block start r:
    # exp(G_t - G_r) (t >= r) on the rows, exp(G_r - G_i) (i < r) on the
    # keys, the latter summed backwards from r
    before = (pos[None, :] < (jnp.arange(nb) * sub)[:, None])[..., None]
    cols = k[..., None, :, :] * jnp.where(before, jnp.exp(rev_cumsum(
        jnp.where(before, after[..., None, :, :], 0.0))), 0.0)
    A = jnp.einsum("x...bsd,...bcd->x...bsc",
                   rows.reshape(2, *lead, nb, sub, dk) * jnp.exp(local),
                   cols, precision=_HI)
    # pairs inside a sub-block: the decay between them a channel, summed
    # over the tokens i < j <= t themselves (not a difference of two sums,
    # which would leave a few ulps of a long sum in a short one), 1 on the
    # diagonal
    spans = ((pos[:sub, None, None] >= pos[None, None, :sub])
             & (pos[None, :sub, None] < pos[None, None, :sub]))
    below = (pos[:sub, None] > pos[None, :sub])[..., None]
    decay = jnp.where(below, jnp.exp(jnp.einsum(
        "tij,...bjd->...btid", spans.astype(g.dtype),
        g.reshape(*lead, nb, sub, dk), precision=_HI)),
        (pos[:sub, None] == pos[None, :sub])[..., None].astype(g.dtype))
    diag = jnp.sum(rows.reshape(2, *lead, nb, sub, 1, dk)
                   * k.reshape(*lead, nb, 1, sub, dk) * decay, axis=-1)
    A = (A.reshape(2, *lead, nb, sub, nb, sub)
         + jnp.einsum("x...bts,bc->x...btcs", diag, jnp.eye(nb, dtype=A.dtype))
         ).reshape(2, *lead, C, C)
    lower = pos[:, None] > pos[None, :]
    aqk = jnp.where(lower | (pos[:, None] == pos[None, :]), A[0], 0.0) * scale
    akk = jnp.where(lower, A[1], 0.0)
    eG = jnp.exp(G)
    x = jax.lax.linalg.triangular_solve(
        beta[..., None] * akk,
        beta[..., None] * jnp.concatenate([v, k * eG], axis=-1),
        left_side=True, lower=True, unit_diagonal=True)
    return (x[..., dv:], x[..., :dv], q * eG * scale,
            k * jnp.exp(to_end), eG[..., -1, :], aqk)


# ---------------------------------------------------------------------------
# the tier's kernels: the insides and the recurrence across chunks, each
# with its VJP
# ---------------------------------------------------------------------------

def _within_chunks_bwd(q, k, v, g, beta, dw, du, dqg, dkd, dgc, daqk,
                       scale: float):
    """`(dq, dk, dv, dg, dbeta)`: autodiff's VJP of `_within_chunks`, the
    forward computed again."""
    _, vjp = jax.vjp(functools.partial(_within_chunks, scale=scale),
                     q, k, v, g, beta)
    return vjp((dw, du, dqg, dkd, dgc, daqk))


def _tier(x):
    """`((inside, inside_bwd), (across, across_bwd))` as the tier resolves
    them by one answer: `ops/pallas/delta_rule.py`'s four Mosaic kernels,
    or `_within_chunks` with its VJP and the recurrence's definitions."""
    from deeplearning4j_tpu.ops import pallas as tier
    mod = tier.delta_rule
    if tier.dispatch.resolve("delta_rule", x) != "pallas":
        return ((_within_chunks, _within_chunks_bwd),
                (mod.across_chunks_reference, mod.across_chunks_bwd_reference))
    interpret = tier.dispatch.interpret_mode()

    def kernel(fn):
        return functools.partial(fn, interpret=interpret)
    return ((kernel(mod.within_chunks), kernel(mod.within_chunks_bwd)),
            (kernel(mod.across_chunks), kernel(mod.across_chunks_bwd)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _within(kernels, scale, q, k, v, g, beta):
    return kernels[0](q, k, v, g, beta, scale)


def _within_fwd(kernels, scale, q, k, v, g, beta):
    return kernels[0](q, k, v, g, beta, scale), (q, k, v, g, beta)


def _within_bwd(kernels, scale, res, cts):
    return kernels[1](*res, *cts, scale)


_within.defvjp(_within_fwd, _within_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _across(kernels, w, u, qg, kd, gc, aqk, s0):
    o, _, last = kernels[0](w, u, qg, kd, gc, aqk, s0)
    return o, last


def _across_fwd(kernels, w, u, qg, kd, gc, aqk, s0):
    o, states, last = kernels[0](w, u, qg, kd, gc, aqk, s0)
    return (o, last), (w, u, qg, kd, gc, aqk, states)


def _across_bwd(kernels, res, cts):
    do, d_last = cts
    return kernels[1](do, *res, d_last)


_across.defvjp(_across_fwd, _across_bwd)


def chunk_delta_rule(q, k, v, g, beta, initial_state=None, scale=None,
                     chunk: int = CHUNK):
    """`delta_rule_recurrent`'s `(o, S)` computed `chunk` tokens at a time
    (module docstring); T need not be a multiple of `chunk` (the tail is
    padded with tokens that neither decay nor write)."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    scale = dk ** -0.5 if scale is None else scale
    f32 = jnp.float32
    chunk = min(chunk, -(-T // 8) * 8)
    N = -(-T // chunk)
    pad = N * chunk - T
    BH = B * H

    def split(a):
        a = a.astype(f32)
        if pad:
            a = jnp.pad(a, [(0, 0), (0, 0), (0, pad)]
                        + [(0, 0)] * (a.ndim - 3))
        return a.reshape(BH, N, chunk, *a.shape[3:])

    # the insides' only residuals are their inputs: the backward computes
    # them again, in VMEM where the tier takes the kernels
    inside, across = _tier(q)
    w, u, qg, kd, gc, aqk = _within(
        inside, scale, split(q), split(k), split(v), split(g), split(beta))
    flat = lambda a: a.reshape(BH, N * chunk, a.shape[-1])  # noqa: E731
    w, u, qg, kd, aqk = map(flat, (w, u, qg, kd, aqk))
    s0 = (jnp.zeros((BH, dv, dk), f32) if initial_state is None else
          jnp.swapaxes(initial_state.astype(f32), -1, -2).reshape(BH, dv, dk))
    o, last = _across(across, w, u, qg, kd, gc.reshape(BH, N, 1, dk), aqk,
                      s0)
    return (o.reshape(B, H, N * chunk, dv)[:, :, :T],
            jnp.swapaxes(last.reshape(B, H, dv, dk), -1, -2))
