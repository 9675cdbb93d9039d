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
one fusion over a chunk's scores in VMEM — and the pairs above it are kept,
with as many of its ties, lowest key first, as make up the count.  Ties AT
the threshold are the rule, not the exception: under training whole
stretches of a row score exactly 0 (every head's ReLU shut), and half the
chunks of a step have a row whose threshold is shared by more keys than it
may keep.  Such a chunk finds, per row, the LAST tie it keeps the same way
it found the threshold: the key's number bit by bit, `log2 S` passes of
compare-and-count (a cumulative sum over the row does the same and moves a
[chunk, S] int32 array five times).  A chunk without such a row keeps `u
>= kth`.  Either way the test and the packing along the queries are one
pass over the scores' bits in VMEM that writes words — no boolean or
widened [chunk, S] array reaches HBM — and the packing along the keys is
ONE kernel a layer that turns the words (`pack_by_key`).  Which branch a
chunk took is decided on the device from the scores themselves;
`sparse_index` counts the chunks that searched (`tie_split_chunks`).

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
the scores' three gradients come from one recomputation of a tile's dots,
and the selection's words are turned in the tile.  The loss's kernels
unpack the chunk's words in the tile too: the scores' pass keeps each
row's logsumexp over its selected pairs, and the probabilities' last head
step makes the KL term and the scores' cotangent, so only two [chunk, S]
float32 arrays, the scores and their cotangent, go from one kernel to the
next.  Elsewhere the same five functions are plain `jax.numpy`
(`pack_by_key` alone too, where a sequence's words come in no whole tiles:
`pack_by_key_supports`).
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

_CHUNK = 1024       # queries a pass: the selection's scores and bits, the
                    # loss's scores and cotangent, [chunk, S] each


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
# the four dense pieces: Mosaic kernels where the tier takes them
# ---------------------------------------------------------------------------

def _dense(q_idx, k_idx, chunk: int):
    """`(index_scores, pack_by_key, index_scores_lse, kl_and_cotangent,
    index_scores_bwd)` of `ops/pallas/sparse_index.py` — the selection's
    two, then the loss's three — as the tier resolves them for `chunk` of
    these queries at a time (`pack_by_key` for the sequence, and only where
    its words come in whole tiles): the kernels, or their plain
    definitions."""
    from deeplearning4j_tpu.ops import pallas as tier
    mod = tier.sparse_index
    B, n, T, d = q_idx.shape
    if tier.dispatch.resolve(
            "sparse_index", jax.ShapeDtypeStruct((B, n, chunk, d),
                                                 q_idx.dtype),
            k_idx) != "pallas":
        return (mod.index_scores_reference, mod.pack_by_key_reference,
                mod.index_scores_lse_reference,
                mod.kl_and_cotangent_reference,
                mod.index_scores_bwd_reference)
    kernels = [mod.index_scores, mod.pack_by_key, mod.index_scores_lse,
               mod.kl_and_cotangent, mod.index_scores_bwd]
    kernels = [functools.partial(f, interpret=tier.dispatch.interpret_mode())
               for f in kernels]
    if not mod.pack_by_key_supports(T, k_idx.shape[1]):
        kernels[1] = mod.pack_by_key_reference
    return tuple(kernels)


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


def _count(keep):
    return jnp.sum(keep, axis=-1, dtype=jnp.int32)


def _threshold(scores, q_offset, topk: int):
    """`(u, kth, want)` of a chunk's `scores` [B, C, S]: `u` (uint32) their
    ordered bits, 0 where the key lies after query `q_offset + i`; `want`
    [C] = `min(position + 1, topk)`, the keys a query keeps; `kth` [B, C]
    the `want`-th largest of a row of `u` (not 0: `want` keys lie at or
    before the query)."""
    B, C, S = scores.shape
    t = q_offset + jnp.arange(C, dtype=jnp.int32)
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] <= t[:, None]
    u = jnp.where(valid, _ordered_bits(scores), jnp.uint32(0))
    want = jnp.minimum(t + 1, topk)                      # [C], >= 1

    def bit(i, kth):       # the k-th largest of a row, from its top bit down
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(_count(u >= cand[..., None]) >= want, cand, kth)

    return u, jax.lax.fori_loop(0, 32, bit,
                                jnp.zeros((B, C), jnp.uint32)), want


def _ties(u, kth, want):
    """`(need, split)`: `need` [B, C], how many of the keys AT its threshold
    a row keeps, `want - count(u > kth)`, and `split`, whether any row of
    the chunk has more such keys than that."""
    need = want - _count(u > kth[..., None])
    return need, jnp.any(_count(u == kth[..., None]) != need)


def _cut(u, kth, need):
    """[B, C]: the last key a row keeps of those AT its threshold, the
    `need`-th of them from the left: the key's number bit by bit from the
    top, one compare-and-count over the row a bit, as the threshold was
    found (`log2 S` passes, where a cumulative sum moves a [chunk, S] int32
    array five times)."""
    S = u.shape[-1]
    under = jnp.arange(S, dtype=jnp.int32)
    tie = u == kth[..., None]

    def bit(i, cut):    # the largest `cut` with fewer than `need` ties under
        cand = cut | (jnp.int32(1) << ((S - 1).bit_length() - 1 - i))
        return jnp.where(
            _count(tie & (under < cand[..., None])) < need, cand, cut)

    return jax.lax.fori_loop(0, (S - 1).bit_length(), bit,
                             jnp.zeros(kth.shape, jnp.int32))


def _keep_to(u, kth, cut):
    """bool like `u` [..., S]: the keys above a row's threshold and those
    at it up to key `cut`."""
    under = jnp.arange(u.shape[-1], dtype=jnp.int32)
    return (u > kth[..., None]) | ((u == kth[..., None])
                                   & (under <= cut[..., None]))


def _selected_words(scores, q_offset, topk: int):
    """`(by_query, split)` of a chunk's `scores` [B, C, S]: int32 [B, C/32,
    S], bit r of word i set where query `q_offset + 32 i + r` keeps the key
    — the `min(position + 1, topk)` keys at or before it of largest score,
    ties to the lower key — and whether the chunk had ties to split.  Test,
    shift and sum are ONE pass over `u`, written word by word."""
    B, C, _ = scores.shape
    u, kth, want = _threshold(scores, q_offset, topk)
    need, split = _ties(u, kth, want)
    word = lambda a: a.reshape(B, C // 32, 32, *a.shape[2:])
    # (the barrier keeps the packing in the branches: moved out of them by
    # XLA, each hands over 16 MB of booleans and 64 MB of shifts)
    pack = lambda keep: jax.lax.optimization_barrier(
        _pack_bits(keep, 2).reshape(B, C // 32, -1))
    return jax.lax.cond(
        split,
        lambda: pack(_keep_to(word(u), word(kth), word(_cut(u, kth, need)))),
        lambda: pack(word(u) >= word(kth)[..., None])), split


def select_keys(scores, q_offset, topk: int):
    """bool [B, C, S]: `_selected_words`' bits, a pair each."""
    return _unpack_bits(_selected_words(scores, q_offset, topk)[0], 1)


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
    """The selection of every query of a sequence: `(Selection, selected,
    tie_split_chunks)` for `q_idx` [B, n, T, d], `k_idx` [B, T, d], `w` [B,
    T, n] (float32 with the scale folded in); `selected` is the number of
    selected pairs (float32: 31.5M a sequence of 16,384 tokens),
    `tie_split_chunks` (int32) the chunks in which some query's threshold
    was shared by more keys than it could keep.  No gradient: the caller
    stops it on the way in.  The two packed arrays carry the name
    `SELECTION`."""
    T = q_idx.shape[2]
    C = _chunk(T)
    scores, pack_by_key, *_ = _dense(q_idx, k_idx, C)

    def one(xs):
        q_c, w_c, q_offset = xs
        by_query, split = _selected_words(
            scores(q_c, k_idx, w_c, q_offset), q_offset, topk)
        return (by_query, jnp.sum(jax.lax.population_count(by_query),
                                  dtype=jnp.float32),
                split.astype(jnp.int32))

    by_query, selected, split = jax.lax.map(
        one, (_chunks(q_idx, 2, C), _chunks(w, 1, C),
              jnp.arange(0, T, C, dtype=jnp.int32)))
    by_query = _unchunk(by_query, 1)
    selection = Selection(checkpoint_name(by_query, SELECTION),
                          checkpoint_name(pack_by_key(by_query), SELECTION))
    return selection, jnp.sum(selected), jnp.sum(split, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# the indexer's loss
# ---------------------------------------------------------------------------

def _loss_and_grads(q_idx, k_idx, w, by_query, q, k, lse, scale):
    """`(loss, (d q_idx, d k_idx, d w))` of `index_loss`, a chunk of
    queries at a time: the scores with each row's logsumexp over its
    selected pairs, the KL a row with the scores' cotangent, the three
    gradients."""
    B, _, T, _ = q_idx.shape
    C = _chunk(T)
    _, _, scores_lse, kl_and_cotangent, index_scores_bwd = _dense(
        q_idx, k_idx, C)

    def one(dk_sum, xs):
        q_c, w_c, qm_c, lse_c, q_offset = xs
        words = jax.lax.dynamic_slice_in_dim(by_query, q_offset // 32,
                                             C // 32, 1)
        scores, lse_idx = scores_lse(q_c, k_idx, w_c, words, q_offset)
        d_scores, kl = kl_and_cotangent(qm_c, k, lse_c, scale, scores, words,
                                        lse_idx, B * T, q_offset)
        dq_c, dk_c, dw_c = index_scores_bwd(d_scores, q_c, k_idx, w_c,
                                            q_offset)
        return dk_sum + dk_c.astype(jnp.float32), (jnp.sum(kl), dq_c, dw_c)

    dk, (kl, dq, dw) = jax.lax.scan(
        one, jnp.zeros(k_idx.shape, jnp.float32),
        (_chunks(q_idx, 2, C), _chunks(w, 1, C), _chunks(q, 2, C),
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
