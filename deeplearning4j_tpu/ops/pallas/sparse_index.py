"""The dense pieces of learned sparse attention's index, tile by tile.

`ops/sparse_index.py` scores every causal (query, key) pair with a small
second attention (`n` index heads of `d` dims over ONE key head), and for its
loss needs the main heads' probabilities summed over the heads, pair by pair.
Written in XLA each is a stack of per-head [queries, keys] arrays — 16 or 32
of them, 17 to 34 GB a layer at 16,384 tokens — reduced by the next op.
Here the heads are summed in the tile and only the [queries, keys] float32
result reaches HBM:

- `index_scores`: `I[t, s] = sum_j w[t, j] relu(q_idx[t, j] . k_idx[s])`;
- `index_scores_bwd`: its three gradients from one recomputation of a
  tile's dots (dQ resident across a query block's key blocks, dK written a
  (query block, key block) and summed outside, dW a lane a head).

For the indexer's loss two kernels also take the chunk's words of the
selection, unpacked in the tile along its sublanes, so that the loss's
per-pair arithmetic never leaves VMEM:

- `index_scores_lse`: the scores and, over the key blocks (the grid's
  innermost axis), a running max and sum a row over its selected pairs,
  written as the row's logsumexp [B, C] along the lanes;
- `kl_and_cotangent`: `sum_a exp(q[a] . k[a // group] * scale - lse[a])`
  over the heads, the grid's innermost axis, in the tile; the last head's
  step reads the tile's scores and words and turns the sum into the KL
  term (summed a row, [B, C] along the lanes, resident across the key
  blocks) and the scores' cotangent `(p - pbar) / rows`, written where
  the sum was.

Between the loss's kernels only the scores and the cotangent, [C, S]
float32 each, pass through HBM; XLA adds the rows' KL and the chunks' dK.

Each takes `q_offset`, the first query's position (an int32 scalar, known
on the device only: the caller walks a sequence in chunks), and skips the
tiles that lie wholly above the diagonal, which it leaves zero (a tile on
the diagonal is computed whole: the caller masks, as a selection holds
causal pairs only) or, `index_scores_lse`'s scores, does not write.

The fifth piece works on the selection's bits, so that no boolean or
widened [queries, keys] array stands between the top-k and the flash
kernels:

- `pack_by_key`: an `attention_kernels.Selection`'s second array from its
  first, 32 queries to a word in, 32 keys to a word out: a tile of words is
  unpacked along its sublanes (as the flash kernels do), turned (one 32-bit
  transpose, as the flash backward's `ds.T`) and packed along its sublanes
  again.  Once a sequence; 2 x 33.5 MB move at 16,384 tokens.

The `*_reference` twins are the definitions (plain jnp, every pair
computed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.attention_kernels import (NEG_INF, _pack_bits,
                                                      _unpack_bits)

_BLOCK_Q, _BLOCK_K = 512, 1024
_VMEM_LIMIT = 64 << 20
# the loss's three kernels need under 27 MB: a call that claims more makes
# XLA move the selection's 32 MB of words, which it keeps in VMEM across the
# loss's loop over chunks, out to HBM and back around every chunk
_LOSS_VMEM_LIMIT = 32 << 20
_NT = (((1,), (1,)), ((), ()))                 # a [m, c], b [n, c] -> [m, n]
_TN = (((0,), (0,)), ((), ()))                 # a [c, m], b [c, n] -> [m, n]


# ---------------------------------------------------------------------------
# definitions
# ---------------------------------------------------------------------------

def index_scores_reference(q_idx, k_idx, w, q_offset=None):
    """`I` [B, C, S] float32 of `q_idx` [B, n, C, d], `k_idx` [B, S, d] and
    `w` [B, C, n] (float32, the scale folded in)."""
    dots = jnp.einsum("bnqd,bkd->bnqk", q_idx, k_idx,
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bqn,bnqk->bqk", w.astype(jnp.float32),
                      jnp.maximum(dots, 0.0))


def index_scores_lse_reference(q_idx, k_idx, w, by_query, q_offset=None):
    """`(I, lse_idx)`: `index_scores_reference` and the logsumexp [B, C] of
    each row's selected scores, `by_query` [B, C/32, S] the chunk's words
    of a `Selection`."""
    scores = index_scores_reference(q_idx, k_idx, w)
    return scores, jax.nn.logsumexp(
        jnp.where(_unpack_bits(by_query, 1), scores, -jnp.inf), axis=-1)


def index_scores_bwd_reference(d_scores, q_idx, k_idx, w, q_offset=None):
    """`(d q_idx, d k_idx, d w)` of `index_scores` for the cotangent
    `d_scores` [B, C, S] (zero above the diagonal)."""
    return jax.vjp(index_scores_reference, q_idx, k_idx, w)[1](d_scores)


def head_summed_probs_reference(q, k, lse, scale, q_offset=None):
    """`sum_a exp(q[a] . k[a // group] * scale - lse[a])` [B, C, S] float32
    of `q` [B, H, C, D], `k` [B, Hk, S, D], `lse` [B, H, C]."""
    k = jnp.repeat(k, q.shape[1] // k.shape[1], axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    return jnp.sum(jnp.exp(s - lse[..., None]), axis=1)


def kl_and_cotangent_reference(q, k, lse, scale, scores, by_query, lse_idx,
                               rows: int, q_offset=None):
    """`(d_scores, kl)` of the indexer's loss on a chunk: `pbar` the main
    heads' probabilities (`head_summed_probs_reference` over their number)
    and `log_p` the index's log-softmax, both on the selected pairs only;
    `kl` [B, C] each row's `sum pbar (log pbar - log_p)`, and `d_scores`
    [B, C, S] = `(p - pbar) / rows`, zero off the selection."""
    keep = _unpack_bits(by_query, 1)
    log_p = jnp.where(keep, scores - lse_idx[..., None], 0.0)
    pbar = jnp.where(
        keep, head_summed_probs_reference(q, k, lse, scale) / q.shape[1], 0.0)
    kl = jnp.sum(jnp.where(
        pbar > 0, pbar * (jnp.log(jnp.where(pbar > 0, pbar, 1.0)) - log_p),
        0.0), axis=-1)
    return (jnp.where(keep, jnp.exp(log_p), 0.0) - pbar) / rows, kl


def pack_by_key_reference(by_query):
    """`by_key` [B, S/32, T] of an `attention_kernels.Selection` from its
    `by_query` [B, T/32, S] (int32 both): bit r of `by_key[b, j, t]` is
    bit `t % 32` of `by_query[b, t // 32, 32 j + r]`."""
    return _pack_bits(_unpack_bits(by_query, 1), 2).transpose(0, 2, 1)


def index_supports(q_idx, k_idx, *args, **kw) -> bool:
    """Whole tiles: the chunk's queries and the keys in blocks that are
    multiples of the sublane and lane tiling (or the whole side)."""
    C, S = q_idx.shape[2], k_idx.shape[1]
    return (q_idx.ndim == 4 and k_idx.ndim == 3 and C % 8 == 0
            and (S % 128 == 0 or S <= _BLOCK_K))


def pack_by_key_supports(T: int, S: int) -> bool:
    """Whether `pack_by_key` takes a selection of `T` queries over `S`
    keys: a block's 32nd is a tile's rows on one side and its length its
    lanes on the other, so both blocks are multiples of 32 x 8 (or the
    whole side)."""
    return T % 32 == 0 and S % 32 == 0 and all(
        b == side or b % 256 == 0
        for b, side in zip(_blocks(T, S), (T, S)))


def _blocks(C: int, S: int):
    bq, bk = min(_BLOCK_Q, C), min(_BLOCK_K, S)
    while C % bq:
        bq //= 2
    while S % bk:
        bk //= 2
    return bq, bk


def _word_blocks(C: int, S: int):
    """`_blocks` for a kernel that also reads the chunk's words [C/32, S]: a
    query block of whole sublane tiles of words (256 queries), else the
    chunk's whole side."""
    bq, bk = _blocks(C, S)
    return (bq if bq % 256 == 0 else C), bk


def _to_lanes(col):
    """[n, 1] -> [1, n]: a value a row, laid along the lanes."""
    return jnp.broadcast_to(col, (col.shape[0], 128)).T[:1]


def _to_rows(row):
    """[1, n] -> [n, 1], the inverse of `_to_lanes`."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]


def _live(off_ref, i, j, bq, bk):
    """Whether tile (i, j) holds a pair at or under the diagonal."""
    return j * bk <= off_ref[0] + i * bq + (bq - 1)


def _last_key_block(off, i, bq, bk):
    """The last key block a query block sees: the index maps clamp to it, so
    a tile above the diagonal fetches nothing new."""
    return (off[0] + i * bq + (bq - 1)) // bk


def _offset(q_offset):
    return jnp.asarray(0 if q_offset is None else q_offset,
                       jnp.int32).reshape(1)


# ---------------------------------------------------------------------------
# index scores
# ---------------------------------------------------------------------------

def _scores_kernel(off_ref, q_ref, k_ref, w_ref, *rest, heads, bq, bk,
                   with_lse):
    """The tile's scores; `with_lse`: the tile's words come last of the
    inputs, and a running (max, sum) a row over its selected pairs, across
    the key blocks, ends as the row's logsumexp."""
    if with_lse:
        words_ref, o_ref, lse_ref, m_sc, l_sc = rest
    else:
        o_ref, = rest
    i, j = pl.program_id(1), pl.program_id(2)
    live = _live(off_ref, i, j, bq, bk)

    if with_lse:
        @pl.when(j == 0)
        def _():
            m_sc[...] = jnp.full_like(m_sc, NEG_INF)
            l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(live)
    def _():
        k = k_ref[0]                                       # [bk, d]
        w = w_ref[0]                                       # [bq, n] f32
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(heads):
            dots = jax.lax.dot_general(
                q_ref[0, h], k, _NT, preferred_element_type=jnp.float32)
            acc = acc + w[:, h:h + 1] * jnp.maximum(dots, 0.0)
        o_ref[0] = acc
        if with_lse:
            keep = _unpack_bits(words_ref[0])              # [bq, bk]
            m = m_sc[...]
            m_new = jnp.maximum(m, jnp.max(jnp.where(keep, acc, NEG_INF),
                                           axis=1, keepdims=True))
            p = jnp.where(keep, jnp.exp(acc - m_new), 0.0)
            l_sc[...] = (jnp.exp(m - m_new) * l_sc[...]
                         + jnp.sum(p, axis=1, keepdims=True))
            m_sc[...] = m_new

    if with_lse:
        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            lse_ref[0] = _to_lanes(m_sc[...] + jnp.log(l_sc[...]))
    else:
        @pl.when(jnp.logical_not(live))
        def _():
            o_ref[0] = jnp.zeros((bq, bk), jnp.float32)


def index_scores(q_idx, k_idx, w, q_offset=None, interpret=False):
    """`index_scores_reference` in the tiles that reach under the diagonal
    of queries `q_offset ..`, zero in the others."""
    return _index_scores(q_idx, k_idx, w, None, q_offset, interpret)


def index_scores_lse(q_idx, k_idx, w, by_query, q_offset=None,
                     interpret=False):
    """`index_scores_lse_reference` for a selection of causal pairs: the
    scores in the tiles that reach under the diagonal, the others NOT
    written (their values are undefined; no kernel here reads them), and
    each row's logsumexp over its selected pairs (at least one a row)."""
    return _index_scores(q_idx, k_idx, w, by_query, q_offset, interpret)


def _index_scores(q_idx, k_idx, w, by_query, q_offset, interpret):
    B, n, C, d = q_idx.shape
    S = k_idx.shape[1]
    with_lse = by_query is not None
    bq, bk = (_word_blocks if with_lse else _blocks)(C, S)
    last = lambda j, i, off: jnp.minimum(j, _last_key_block(off, i, bq, bk))
    in_specs = [
        pl.BlockSpec((1, n, bq, d), lambda b, i, j, off: (b, 0, i, 0)),
        pl.BlockSpec((1, bk, d), lambda b, i, j, off: (b, last(j, i, off), 0)),
        pl.BlockSpec((1, bq, n), lambda b, i, j, off: (b, i, 0)),
    ]
    inputs = [_offset(q_offset), q_idx, k_idx, w.astype(jnp.float32)]
    out_specs = pl.BlockSpec((1, bq, bk), lambda b, i, j, off: (b, i, j))
    out_shape = jax.ShapeDtypeStruct((B, C, S), jnp.float32)
    scratch = []
    if with_lse:
        in_specs.append(pl.BlockSpec((1, bq // 32, bk), lambda b, i, j, off: (
            b, i, last(j, i, off))))
        inputs.append(by_query)
        # a tile above the diagonal maps to the last live one: not written
        out_specs = [pl.BlockSpec((1, bq, bk), lambda b, i, j, off: (
                         b, i, last(j, i, off))),
                     pl.BlockSpec((1, 1, bq), lambda b, i, j, off: (b, 0, i))]
        out_shape = [out_shape, jax.ShapeDtypeStruct((B, 1, C), jnp.float32)]
        scratch = [pltpu.VMEM((bq, 1), jnp.float32)] * 2
    out = pl.pallas_call(
        functools.partial(_scores_kernel, heads=n, bq=bq, bk=bk,
                          with_lse=with_lse),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, C // bq, S // bk),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=(
            _LOSS_VMEM_LIMIT if with_lse else _VMEM_LIMIT)),
        interpret=interpret,
    )(*inputs)
    return (out[0], out[1].reshape(B, C)) if with_lse else out


# ---------------------------------------------------------------------------
# their gradients
# ---------------------------------------------------------------------------

def _scores_bwd_kernel(off_ref, g_ref, q_ref, k_ref, w_ref, dq_ref, dk_ref,
                       dw_ref, dq_sc, dw_sc, *, heads, bq, bk):
    i, j = pl.program_id(1), pl.program_id(2)
    live = _live(off_ref, i, j, bq, bk)

    @pl.when(j == 0)
    def _():
        dq_sc[...] = jnp.zeros_like(dq_sc)
        dw_sc[...] = jnp.zeros_like(dw_sc)

    @pl.when(live)
    def _():
        g = g_ref[0]                                       # [bq, bk] f32
        k = k_ref[0]                                       # [bk, d]
        w = w_ref[0]                                       # [bq, n] f32
        lane = jax.lax.broadcasted_iota(jnp.int32, dw_sc.shape, 1)
        dk = jnp.zeros(dk_ref.shape[2:], jnp.float32)
        for h in range(heads):
            q = q_ref[0, h]                                # [bq, d]
            dots = jax.lax.dot_general(
                q, k, _NT, preferred_element_type=jnp.float32)
            # d I / d dots = w where the head fired
            gh = jnp.where(dots > 0, g * w[:, h:h + 1], 0.0).astype(k.dtype)
            dq_sc[h] += jnp.dot(gh, k, preferred_element_type=jnp.float32)
            dk = dk + jax.lax.dot_general(
                gh, q, _TN, preferred_element_type=jnp.float32)
            dw_h = jnp.sum(g * jnp.maximum(dots, 0.0), axis=1, keepdims=True)
            dw_sc[...] += jnp.where(lane == h, dw_h, 0.0)
        dk_ref[0, 0] = dk

    @pl.when(jnp.logical_not(live))
    def _():
        dk_ref[0, 0] = jnp.zeros(dk_ref.shape[2:], jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)
        dw_ref[0] = dw_sc[...]


def index_scores_bwd(d_scores, q_idx, k_idx, w, q_offset=None,
                     interpret=False):
    """`index_scores_bwd_reference` for a cotangent that is zero above the
    diagonal: `(d q_idx, d k_idx, d w)`, the key's in float32."""
    B, n, C, d = q_idx.shape
    S = k_idx.shape[1]
    bq, bk = _blocks(C, S)
    lanes = max(128, n)
    keys = lambda b, i, j, off: (
        b, jnp.minimum(j, _last_key_block(off, i, bq, bk)), 0)
    q_rows = lambda b, i, j, off: (b, 0, i, 0)
    dq, dk, dw = pl.pallas_call(
        functools.partial(_scores_bwd_kernel, heads=n, bq=bq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, C // bq, S // bk),
            in_specs=[
                pl.BlockSpec((1, bq, bk), lambda b, i, j, off: (
                    b, i, jnp.minimum(j, _last_key_block(off, i, bq, bk)))),
                pl.BlockSpec((1, n, bq, d), q_rows),
                pl.BlockSpec((1, bk, d), keys),
                pl.BlockSpec((1, bq, n), lambda b, i, j, off: (b, i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, n, bq, d), q_rows),
                # a (query block, key block) its own: summed outside
                pl.BlockSpec((1, 1, bk, d), lambda b, i, j, off: (b, i, j, 0)),
                pl.BlockSpec((1, bq, lanes), lambda b, i, j, off: (b, i, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((n, bq, d), jnp.float32),
                            pltpu.VMEM((bq, lanes), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((B, n, C, d), q_idx.dtype),
            jax.ShapeDtypeStruct((B, C // bq, S, d), jnp.float32),
            jax.ShapeDtypeStruct((B, C, lanes), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_LOSS_VMEM_LIMIT),
        interpret=interpret,
    )(_offset(q_offset), d_scores.astype(jnp.float32), q_idx, k_idx,
      w.astype(jnp.float32))
    return dq, jnp.sum(dk, axis=1), dw[..., :n].astype(w.dtype)


# ---------------------------------------------------------------------------
# the main heads' probabilities, summed over the heads
# ---------------------------------------------------------------------------

def _probs_kernel(off_ref, q_ref, k_ref, lse_ref, scores_ref, words_ref,
                  lse_idx_ref, o_ref, kl_ref, kl_sc, *, scale, bq, bk, heads,
                  rows):
    """The heads' probabilities summed in the output's tile; the last
    head's step turns the sum into the loss's cotangent and adds the tile's
    KL to its rows'."""
    i, j, a = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    live = _live(off_ref, i, j, bq, bk)

    @pl.when(a == 0)
    def _():
        o_ref[0] = jnp.zeros((bq, bk), jnp.float32)

    @pl.when(jnp.logical_and(j == 0, a == 0))
    def _():
        kl_sc[...] = jnp.zeros_like(kl_sc)

    @pl.when(live)
    def _():
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], _NT,
            preferred_element_type=jnp.float32) * scale
        o_ref[0] += jnp.exp(s - lse_ref[0, 0])            # [bq, 1] -> lanes

    @pl.when(jnp.logical_and(live, a == heads - 1))
    def _():
        # the divisions as products with reciprocals (fewer vector ops a
        # pair; the same floats where the divisor is a power of two), and
        # no select where `pbar` is 0: `log_p` is finite, so the term is 0
        keep = _unpack_bits(words_ref[0])                  # [bq, bk]
        pbar = jnp.where(keep, o_ref[0] * (1.0 / heads), 0.0)
        log_p = jnp.where(keep, scores_ref[0] - _to_rows(lse_idx_ref[0]),
                          0.0)
        kl_sc[...] += jnp.sum(
            pbar * (jnp.log(jnp.where(pbar > 0, pbar, 1.0)) - log_p),
            axis=1, keepdims=True)
        o_ref[0] = ((jnp.where(keep, jnp.exp(log_p), 0.0) - pbar)
                    * (1.0 / rows))

    @pl.when(jnp.logical_and(j == pl.num_programs(2) - 1, a == heads - 1))
    def _():
        kl_ref[0] = _to_lanes(kl_sc[...])


def kl_and_cotangent(q, k, lse, scale, scores, by_query, lse_idx, rows: int,
                     q_offset=None, interpret=False):
    """`kl_and_cotangent_reference` for a selection of causal pairs, from
    `index_scores_lse`'s two results, in the tiles that reach under the
    diagonal of queries `q_offset ..`, zero in the others.  The heads are
    the grid's innermost axis: the tile's sum stays in VMEM while they
    pass, and the last head's step reads the tile's scores and words
    (fetched once a tile) and writes the cotangent where the sum was."""
    B, H, C, D = q.shape
    Hk, S = k.shape[1], k.shape[2]
    group = H // Hk
    bq, bk = _word_blocks(C, S)
    q_rows = lambda b, i, j, a, off: (b, a, i, 0)
    last = lambda j, i, off: jnp.minimum(j, _last_key_block(off, i, bq, bk))
    tile = lambda b, i, j, a, off: (b, i, last(j, i, off))
    by_row = lambda b, i, j, a, off: (b, 0, i)
    d_scores, kl = pl.pallas_call(
        functools.partial(_probs_kernel, scale=scale, bq=bq, bk=bk, heads=H,
                          rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, C // bq, S // bk, H),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D), q_rows),
                pl.BlockSpec((1, 1, bk, D), lambda b, i, j, a, off: (
                    b, a // group, last(j, i, off), 0)),
                pl.BlockSpec((1, 1, bq, 1), q_rows),
                pl.BlockSpec((1, bq, bk), tile),
                pl.BlockSpec((1, bq // 32, bk), tile),
                pl.BlockSpec((1, 1, bq), by_row),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, bk), lambda b, i, j, a, off: (b, i, j)),
                pl.BlockSpec((1, 1, bq), by_row),
            ],
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, C, S), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_LOSS_VMEM_LIMIT),
        interpret=interpret,
    )(_offset(q_offset), q, k, lse.astype(jnp.float32)[..., None], scores,
      by_query, lse_idx.reshape(B, 1, C))
    return d_scores, kl.reshape(B, C)


# ---------------------------------------------------------------------------
# the selection's bits, turned
# ---------------------------------------------------------------------------

def _by_key_kernel(words_ref, o_ref, *, bq, bk):
    i, j = pl.program_id(1), pl.program_id(2)
    live = j * bk <= i * bq + (bq - 1)

    @pl.when(live)
    def _():
        keep = _unpack_bits(words_ref[0]).astype(jnp.int32).T   # [bk, bq]
        r = jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0) & 31
        o_ref[0] = jnp.sum(
            jnp.left_shift(keep, r).reshape(bk // 32, 32, bq), axis=1)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[0] = jnp.zeros((bk // 32, bq), jnp.int32)


def pack_by_key(by_query, interpret=False):
    """`pack_by_key_reference` of a selection that holds causal pairs only:
    a tile wholly above the diagonal reads nothing new and writes zero
    words."""
    B, words, S = by_query.shape
    T = 32 * words
    bq, bk = _blocks(T, S)
    return pl.pallas_call(
        functools.partial(_by_key_kernel, bq=bq, bk=bk),
        grid=(B, T // bq, S // bk),
        in_specs=[pl.BlockSpec((1, bq // 32, bk), lambda b, i, j: (
            b, i, jnp.minimum(j, (i * bq + (bq - 1)) // bk)))],
        out_specs=pl.BlockSpec((1, bk // 32, bq), lambda b, i, j: (b, j, i)),
        out_shape=jax.ShapeDtypeStruct((B, S // 32, T), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(by_query)
