"""Learned sparse attention's index: which keys each query may see.

DeepSeek-V3.2's lightning indexer (DeepSeek-V3.2-Exp report;
arXiv:2512.02556), on the train path.  Beside a layer's attention heads
stands a second, small attention: `n` index heads of `d` dims over ONE key
head.  Its score of a causal pair is

    I[t, s] = sum_j w[t, j] * relu(q_idx[t, j] . k_idx[s])        (float32)

(the caller folds the constant scale into `w`), and query `t` attends the
`min(t + 1, topk)` keys `s <= t` of largest `I[t, s]`, ties to the lower
`s` — one set for all of the layer's heads, EXACT: a threshold from a
sample, `approx_max_k` or a choice of key blocks is another model.

`sparse_index` finds the sets and hands them out as an
`attention_kernels.Selection` (one bit a pair) that the flash kernels take
as an operand.  Exact selection without a sort: a float's bits, the sign
folded, order as unsigned integers, so the k-th largest of a row is built
bit by bit from the top — 32 passes of compare-and-count over the row, each
one fusion — and the pairs above it are kept, with as many of its ties,
lowest key first, as make up the count (a cumulative sum that runs only
in a chunk that has such ties to split).

`index_loss` is the indexer's own objective (the sparse training stage):
`mean_t KL(pbar[t, .] || softmax_{s in S_t} I[t, s])`, `pbar` the main
heads' probabilities summed over the heads and divided by their number, on
the selected pairs, a constant.  It reaches the indexer's parameters alone
and nothing differentiates through the choice of `S_t`.  Its gradient with
respect to `I` is `(softmax - pbar) / rows` on the selected pairs, so loss
and gradients come from ONE pass: the forward rule takes the gradients of
`q_idx`, `k_idx` and `w` too and names them `INDEX_GRADS`, the backward rule
scales them (as `zoo/bert.py`'s head takes its gradients in the forward); a
block under `jax.checkpoint(policy=save_only_these_names(..., INDEX_GRADS,
SELECTION))` then neither selects nor scores a second time.

Everything dense in (query, key) is computed `_CHUNK` queries at a time: a
[T, S] float32 array is 1 GB at 16,384 tokens.  The per-head stacks
([heads, chunk, S]) never reach HBM where the tier takes the Mosaic kernels
of `ops/pallas/sparse_index.py` (a TPU, or the tier forced to `pallas`): the
index heads are summed in the tile, so are the main heads' probabilities,
and the scores' three gradients come from one recomputation of a tile's
dots.  Elsewhere the same three functions are plain `jax.numpy`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.ops.attention_kernels import (Selection, _pack_bits,
                                                      _unpack_bits)

# What a caller's `jax.checkpoint` policy saves of this module's results.
SELECTION = "sparse_selection"
INDEX_GRADS = "index_loss_grads"

_CHUNK = 1024       # queries a pass: six dense [chunk, S] float32 arrays


def _chunk(T: int) -> int:
    """Queries a pass: `_CHUNK`, halved until it divides T; whole words of
    a `Selection`."""
    c = min(_CHUNK, T)
    while T % c:
        c //= 2
    if c % 32:
        raise ValueError(f"{T} queries are no whole chunks of 32-bit words")
    return c


# ---------------------------------------------------------------------------
# the three dense pieces: Mosaic kernels where the tier takes them
# ---------------------------------------------------------------------------

def _dense(q_idx, k_idx, chunk: int):
    """`(index_scores, index_scores_bwd, head_summed_probs)` of
    `ops/pallas/sparse_index.py` as the tier resolves them for `chunk` of
    these queries at a time: the kernels, or their plain definitions."""
    from deeplearning4j_tpu.ops import pallas as tier
    mod = tier.sparse_index
    B, n, _, d = q_idx.shape
    if tier.dispatch.resolve(
            "sparse_index", jax.ShapeDtypeStruct((B, n, chunk, d),
                                                 q_idx.dtype),
            k_idx) != "pallas":
        return (mod.index_scores_reference, mod.index_scores_bwd_reference,
                mod.head_summed_probs_reference)
    return tuple(functools.partial(
        f, interpret=tier.dispatch.interpret_mode()) for f in (
        mod.index_scores, mod.index_scores_bwd, mod.head_summed_probs))


# ---------------------------------------------------------------------------
# exact top-k a query, as a keep-mask
# ---------------------------------------------------------------------------

def _ordered_bits(x):
    """float32 -> uint32 that order as the floats do (-0.0 as +0.0), and no
    finite float maps to 0."""
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(1 << 31)


def select_keys(scores, q_offset, topk: int):
    """bool [B, C, S]: for query `q_offset + i` the `min(position + 1,
    topk)` keys at or before it of largest `scores[b, i]`, ties to the lower
    key."""
    B, C, S = scores.shape
    t = q_offset + jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] <= t[:, None]
    u = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
    want = jnp.minimum(t + 1, topk)                      # [C], >= 1

    def count(keep):
        return jnp.sum(keep, axis=-1, dtype=jnp.int32)

    def bit(i, kth):       # the k-th largest of a row, from its top bit down
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(u >= cand[..., None]) >= want, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((B, C), jnp.uint32))
    above = u > kth[..., None]
    tie = (u == kth[..., None]) & valid
    need = want - count(above)          # of the ties, the lowest keys first
    return above | jax.lax.cond(
        jnp.all(count(tie) == need), lambda: tie,
        lambda: tie & (jnp.cumsum(tie, axis=-1, dtype=jnp.int32)
                       <= need[..., None]))


def _chunks(a, axis: int, chunk: int):
    """`a` with `axis` cut into chunks, the chunks' axis first."""
    shape = a.shape
    a = a.reshape(*shape[:axis], shape[axis] // chunk, chunk,
                  *shape[axis + 1:])
    return jnp.moveaxis(a, axis, 0)


def _unchunk(a, axis: int):
    """The inverse of `_chunks` on a stacked result."""
    a = jnp.moveaxis(a, 0, axis)
    return a.reshape(*a.shape[:axis], -1, *a.shape[axis + 2:])


def sparse_index(q_idx, k_idx, w, topk: int):
    """The selection of every query of a sequence: `(Selection, selected)`
    for `q_idx` [B, n, T, d], `k_idx` [B, T, d], `w` [B, T, n] (float32
    with the scale folded in); `selected` is the number of selected pairs
    (float32: 31.5M a sequence of 16,384 tokens).  No gradient: the caller
    stops it on the way in.  The two packed arrays carry the name
    `SELECTION`."""
    B, _, T, _ = q_idx.shape
    C = _chunk(T)
    scores, _, _ = _dense(q_idx, k_idx, C)

    def one(xs):
        q_c, w_c, q_offset = xs
        keep = select_keys(scores(q_c, k_idx, w_c, q_offset), q_offset, topk)
        return (_pack_bits(keep, 1), _pack_bits(keep, 2),
                jnp.sum(keep, dtype=jnp.float32))

    by_query, by_key, selected = jax.lax.map(
        one, (_chunks(q_idx, 2, C), _chunks(w, 1, C),
              jnp.arange(0, T, C, dtype=jnp.int32)))
    selection = Selection(
        checkpoint_name(_unchunk(by_query, 1), SELECTION),
        checkpoint_name(_unchunk(by_key, 1).transpose(0, 2, 1), SELECTION))
    return selection, jnp.sum(selected)


# ---------------------------------------------------------------------------
# the indexer's loss
# ---------------------------------------------------------------------------

def _loss_and_grads(q_idx, k_idx, w, by_query, q, k, lse, scale):
    """`(loss, (d q_idx, d k_idx, d w))` of `index_loss`, a chunk of
    queries at a time."""
    B, _, T, _ = q_idx.shape
    H = q.shape[1]
    C = _chunk(T)
    index_scores, index_scores_bwd, head_summed_probs = _dense(q_idx, k_idx,
                                                                C)

    def one(dk_sum, xs):
        q_c, w_c, words, qm_c, lse_c, q_offset = xs
        keep = _unpack_bits(words, 1)                       # [B, C, S]
        scores = index_scores(q_c, k_idx, w_c, q_offset)
        lse_idx = jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf),
                                   axis=-1, keepdims=True)
        log_p = jnp.where(keep, scores - lse_idx, 0.0)
        pbar = jnp.where(
            keep, head_summed_probs(qm_c, k, lse_c, scale, q_offset) / H,
            0.0)
        kl = jnp.sum(jnp.where(
            pbar > 0, pbar * (jnp.log(jnp.where(pbar > 0, pbar, 1.0))
                              - log_p), 0.0))
        d_scores = (jnp.where(keep, jnp.exp(log_p), 0.0) - pbar) / (B * T)
        dq_c, dk_c, dw_c = index_scores_bwd(d_scores, q_c, k_idx, w_c,
                                            q_offset)
        return dk_sum + dk_c.astype(jnp.float32), (kl, dq_c, dw_c)

    dk, (kl, dq, dw) = jax.lax.scan(
        one, jnp.zeros(k_idx.shape, jnp.float32),
        (_chunks(q_idx, 2, C), _chunks(w, 1, C),
         _chunks(by_query, 1, C // 32), _chunks(q, 2, C),
         _chunks(lse, 2, C), jnp.arange(0, T, C, dtype=jnp.int32)))
    return jnp.sum(kl) / (B * T), (
        _unchunk(dq, 2), dk.astype(k_idx.dtype), _unchunk(dw, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def index_loss(q_idx, k_idx, w, by_query, q, k, lse, scale):
    """`mean over sequences and queries of KL(pbar[t, .] || softmax_{s in
    S_t} I[t, s])` (float32): `q_idx` [B, n, T, d], `k_idx` [B, T, d], `w`
    [B, T, n] as `sparse_index` takes them, `by_query` of its `Selection`;
    `q` [B, H, T, D], `k` [B, Hk, T, D] and `lse` [B, H, T] the main heads'
    queries, keys and logsumexp over the selected pairs (`fused_attention(...,
    return_lse=True)`), `scale` their scores' scale: constants, as the
    selection is.  The gradient reaches `q_idx`, `k_idx` and `w`."""
    return _loss_and_grads(q_idx, k_idx, w, by_query, q, k, lse, scale)[0]


def _il_fwd(q_idx, k_idx, w, by_query, q, k, lse, scale):
    loss, grads = _loss_and_grads(q_idx, k_idx, w, by_query, q, k, lse,
                                  scale)
    return loss, tuple(checkpoint_name(g, INDEX_GRADS) for g in grads)


def _il_bwd(scale, grads, g):
    return (*(g.astype(d.dtype) * d for d in grads), None, None, None, None)


index_loss.defvjp(_il_fwd, _il_bwd)
