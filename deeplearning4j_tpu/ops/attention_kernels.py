"""Fused attention kernels.

Replaces the reference's `dotProductAttention`/`multiHeadDotProductAttention`
declarable ops (`libnd4j .../generic/nn/dot_product_attention.cpp` — naive
materialized [T,T] scores) with flash-attention-style computation, the role
cuDNN fused attention plays for the reference's platform helpers:

- `mha_reference`: naive jnp (ground truth for tests; O(T^2) memory).
- `blockwise_attention`: online-softmax `lax.scan` over KV blocks — O(T)
  memory, XLA-fusable everywhere (CPU tests, any accelerator), and the
  building block ring attention reuses across chips.
- `flash_attention_tpu` + `flash_attention_bwd_tpu`: Pallas TPU kernels
  over a grid (batch*heads, live tile): the second axis walks a
  `tile_schedule` — the (query block, key block) tiles of which the mask
  keeps a pair, listed in numpy while the call is traced and handed to the
  kernel as scalar-prefetch operands that its index maps read — with
  online-softmax state in VMEM scratch; the forward saves per-row
  logsumexp and the backward is ONE kernel, key block outer and q blocks
  inner, that recomputes P from the logsumexp once a tile and takes dV, dK
  (tile scratch) and dQ (resident in VMEM across the KV blocks) from the
  same pass — no [T,T] materialization in either direction, no partial dQ
  in HBM.  A tile the mask leaves empty is no grid step; a tile it keeps
  whole runs without mask arithmetic.
- `fused_attention`: measured dispatcher — XLA-fused naive path for short
  sequences (fastest on v5e below ~2k), Pallas kernels for long unmasked
  tiling shapes, blockwise scan for the rest; differentiable everywhere.

Layouts: [B, H, T, D] (heads separated — the TPU-native layout; the nn/
attention layers reshape from [B, T, F]).  `q` and `k` share the key width
D; `v` (and the output) may be narrower or wider, [B, H, S, Dv] — latent
attention's expanded form has keys of 192 and values of 128.  The default
scale is over the key width.

Grouped-query heads: `k` and `v` may lie over fewer heads than `q`, [B, Hk,
S, ...] with H a multiple of Hk; query head `h` attends key-value head
`h // (H // Hk)`, and dK, dV are sums over a group's query heads.  The
Mosaic kernels never hold keys or values repeated in HBM: the forward
addresses their blocks by `head // group`, the backward folds a group's
query heads into one grid row so that dK and dV accumulate over them in the
kernel's scratch; the two XLA paths repeat them, where XLA pleases.

Masks: none, `causal`, a [B, S] keep-mask over key positions, or
`block_diffusion=(L, B)`, the mask of block-diffusion training (BD3-LM,
arXiv:2503.09573, section 3): the last L rows are a clean sequence in blocks
of B tokens and the T - L rows before them — L of them, or none — its noisy
copy (T == S).  A clean row sees the clean rows of its own and earlier
blocks; a noisy row sees the noisy rows of its own block and the clean rows
of earlier blocks; no clean row sees a noisy one.  With no noisy rows that
is attention causal over blocks and bidirectional inside one.  Every branch
builds it from row and column numbers, and no [T, S] array reaches HBM.

A `selection` is the one mask that is DATA: which keys each query may see,
chosen on the device (`ops/sparse_index.py`: learned sparse attention), the
same for every head, a subset of the causal pairs.  It travels as a
`Selection`, one bit a pair, packed twice — 32 queries to a word
(`by_query` [B, T/32, S], what a forward tile, queries by keys, unpacks
along its sublanes) and 32 keys to a word (`by_key` [B, S/32, T], the same
for the backward's tiles, keys by queries) — 2 x 33.5 MB at 16,384 tokens
where one byte a pair is 268 MB.  The kernels walk `causal`'s schedule (a
tile above the diagonal holds no selected pair) and mask every live tile by
its bits; the two XLA paths unpack it to a [B, T, S] keep-mask.

Which tiles of a mask the Mosaic kernels visit is said in ONE place,
`tile_kinds` (numpy, from T, S, the tile, the mask and a span's offset):
empty, partial or full, for `causal` and the block mask alike (at 512 x
1024: 48 of a head's 128 tiles live and 24 of them full under the block mask
on 2 x 4,096 rows; 20 of 32 and 12 causal at 4,096; 72 of 128 and 56 at
8,192).  `tile_schedule` lists the live ones in the forward's or the
backward's order with the flags the accumulators need and its own count,
`(tiles, live, full)`; the kernels hold no liveness test.  With a padding
`mask` every live tile is partial (the bias is added everywhere).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_NO_LIMIT = 1 << 30


def _block_of(pos, B: int):
    """`pos // B` for non-negative int32 positions (a shift where it can
    be: Mosaic's vector unit has no integer division to spare)."""
    if B & (B - 1) == 0:
        return jnp.right_shift(pos, B.bit_length() - 1)
    return jax.lax.div(pos, jnp.int32(B))


def block_diffusion_keep(rows, cols, T: int, L: int, B: int):
    """Whether row `rows` may attend column `cols` (int32 arrays that
    broadcast) under `block_diffusion=(L, B)` over T rows: see the module
    docstring.  With `d` = the row's block less the column's: noisy on noisy
    `d == 0`, noisy on clean `d >= 1`, clean on clean `d >= 0`."""
    o = T - L                               # the noisy rows come first
    qn, kn = rows < o, cols < o
    d = (_block_of(rows - jnp.where(qn, 0, o), B)
         - _block_of(cols - jnp.where(kn, 0, o), B))
    return jnp.where(kn, qn & (d == 0), jnp.where(qn, d >= 1, d >= 0))


def _check_block_diffusion(T: int, S: int, block_diffusion):
    L, B = block_diffusion
    if T != S or T not in (L, 2 * L) or L % B:
        raise ValueError(
            f"block_diffusion=({L}, {B}) is over {L} or {2 * L} rows and "
            f"columns in blocks of {B}, not [{T}, {S}]")


def _bd_quadrant(q0, k0, o: int, where):
    """A tile of the block-diffusion mask whose first row is `q0` and first
    column `k0` (no tile lies across `o`, the first clean row): `(qp, kp,
    dmin, dmax)` — the first row's and column's position in its own half,
    and the kept pairs are those with `dmin <= block(row) - block(column) <=
    dmax` (none where a clean row meets noisy columns).  Scalars and
    `jnp.where` inside a kernel, numpy arrays and `np.where` in
    `tile_kinds`."""
    qn, kn = q0 < o, k0 < o
    qp = q0 - where(qn, 0, o)
    kp = k0 - where(kn, 0, o)
    dmin = where(qn & ~kn, 1, 0)
    dmax = where(kn, where(qn, 0, -1), _NO_LIMIT)
    return qp, kp, dmin, dmax


def _bd_keep_tile(qp, kp, dmin, dmax, bq: int, bk: int, B: int,
                  keys_first: bool = False):
    """The kept pairs of that tile, [bq, bk] ([bk, bq] with `keys_first`),
    from iota: blocks along each side, one subtraction over the tile."""
    if keys_first:
        q_shape, k_shape = (1, bq), (bk, 1)
    else:
        q_shape, k_shape = (bq, 1), (1, bk)
    q_blk = _block_of(qp + jax.lax.broadcasted_iota(
        jnp.int32, q_shape, 1 if keys_first else 0), B)
    k_blk = _block_of(kp + jax.lax.broadcasted_iota(
        jnp.int32, k_shape, 0 if keys_first else 1), B)
    d = q_blk - k_blk
    return (d >= dmin) & (d <= dmax)


def _over_query_heads(q, k, v):
    """`k` and `v` [B, Hk, ...] repeated over the query's H heads, head `h`
    from `h // (H // Hk)`; themselves where H == Hk.  Autodiff sums a
    group's gradients."""
    group = q.shape[1] // k.shape[1]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


class Selection(NamedTuple):
    """Which (query, key) pairs attention keeps, one bit a pair, the same
    for every head (module docstring).  Bit `r` of `by_query[b, i, s]` is
    pair `(32 i + r, s)`; bit `r` of `by_key[b, j, t]` is pair `(t, 32 j +
    r)`.  int32 both."""
    by_query: jax.Array     # [B, T/32, S]
    by_key: jax.Array       # [B, S/32, T]


def _pack_bits(keep, axis: int):
    """bool [...] -> int32 with `axis` cut to a 32nd: bit r of word i is
    element `32 i + r` along it."""
    shape = keep.shape
    keep = keep.reshape(*shape[:axis], shape[axis] // 32, 32,
                        *shape[axis + 1:])
    r = jnp.arange(32, dtype=jnp.uint32).reshape(
        (32,) + (1,) * (len(shape) - axis - 1))
    return jax.lax.bitcast_convert_type(
        jnp.sum(keep.astype(jnp.uint32) << r, axis=axis + 1,
                dtype=jnp.uint32), jnp.int32)


def _unpack_bits(words, axis: int = -2):
    """The inverse of `_pack_bits`: int32 [..., n, m] -> bool [..., 32 n, m]
    (`axis` -2, the one the kernels use on a tile), from a sublane
    broadcast, a shift by the row's number in its word and a test."""
    axis %= words.ndim
    shape = words.shape
    n = shape[axis]
    bits = jnp.broadcast_to(
        jnp.expand_dims(words, axis + 1),
        (*shape[:axis], n, 32, *shape[axis + 1:])).reshape(
        *shape[:axis], 32 * n, *shape[axis + 1:])
    r = jax.lax.broadcasted_iota(jnp.int32, bits.shape, axis) & 31
    return ((bits >> r) & 1) != 0


def pack_selection(keep) -> Selection:
    """`Selection` of a boolean keep-mask [B, T, S] (T and S multiples of
    32)."""
    return Selection(_pack_bits(keep, 1),
                     _pack_bits(keep, 2).transpose(0, 2, 1))


def unpack_selection(selection: Selection):
    """The boolean keep-mask [B, T, S] of a `Selection`."""
    return _unpack_bits(selection.by_query, 1)


def _keep(mask):
    """A keep-mask over keys [B, S] or over pairs [B, T, S], against scores
    [B, H, T, S]."""
    return (mask[:, None, None, :] if mask.ndim == 2 else mask[:, None]) > 0


def mha_reference(q, k, v, mask=None, causal=False, scale=None,
                  block_diffusion=None, return_lse=False):
    """Naive attention (ground truth).  mask: [B, S] of 1/0 over KV
    positions, or [B, T, S] over (query, key) pairs; `block_diffusion`: (L,
    B), the module docstring's mask.  With `return_lse` also the rows'
    logsumexp [B, H, T] (float32)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    k, v = _over_query_heads(q, k, v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        T, S = q.shape[2], k.shape[2]
        qi = jnp.arange(T)[:, None]
        ki = jnp.arange(S)[None, :]
        scores = jnp.where(qi >= ki, scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(_keep(mask), scores, NEG_INF)
    if block_diffusion is not None:
        T, S = q.shape[2], k.shape[2]
        _check_block_diffusion(T, S, block_diffusion)
        keep = block_diffusion_keep(
            jnp.arange(T, dtype=jnp.int32)[:, None],
            jnp.arange(S, dtype=jnp.int32)[None, :], T, *block_diffusion)
        scores = jnp.where(keep, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    if return_lse:
        return out, jax.nn.logsumexp(scores.astype(jnp.float32), axis=-1)
    return out


def _blockwise_fwd(q, k, v, mask, causal, scale, block_k,
                   block_diffusion=None):
    """Online-softmax scan over KV blocks; returns (out, (m, l))."""
    k, v = _over_query_heads(q, k, v)
    B, H, T, D = q.shape
    S, Dv = k.shape[2], v.shape[3]
    nblocks = S // block_k
    qs = q * scale

    kb = k.reshape(B, H, nblocks, block_k, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, nblocks, block_k, Dv).transpose(2, 0, 1, 3, 4)
    if mask is None:
        mb = jnp.ones((nblocks, B, block_k), q.dtype)
    elif mask.ndim == 2:
        mb = mask.reshape(B, nblocks, block_k).transpose(1, 0, 2)
    else:                                   # over pairs: [B, T, S]
        mb = mask.reshape(B, T, nblocks, block_k).transpose(2, 0, 1, 3)

    def step(carry, blk):
        acc, m, l, j = carry
        kj, vj, mj = blk
        # online-softmax statistics in f32 regardless of input dtype
        # (matches the Pallas kernel; bf16 accumulation across blocks
        # degrades the softmax normalizer)
        s = jnp.einsum("bhqd,bhkd->bhqk", qs, kj,
                       preferred_element_type=jnp.float32)  # [B,H,T,bk]
        s = jnp.where(_keep(mj), s, NEG_INF)
        if causal:
            qi = jnp.arange(T)[:, None]
            ki = j * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where(qi >= ki, s, NEG_INF)
        if block_diffusion is not None:
            qi = jnp.arange(T, dtype=jnp.int32)[:, None]
            ki = jnp.asarray(j * block_k, jnp.int32) + jnp.arange(
                block_k, dtype=jnp.int32)[None, :]
            s = jnp.where(block_diffusion_keep(qi, ki, T, *block_diffusion),
                          s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1)
        acc_new = corr[..., None] * acc + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        return (acc_new, m_new, l_new, j + 1), None

    acc0 = jnp.zeros((B, H, T, Dv), jnp.float32)
    m0 = jnp.full((B, H, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, T), jnp.float32)
    (acc, m, l, _), _ = jax.lax.scan(step, (acc0, m0, l0, 0), (kb, vb, mb))
    return (acc / l[..., None]).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def blockwise_attention(q, k, v, mask=None, causal=False, scale=None,
                        block_k=128, block_diffusion=None):
    """O(T)-memory attention via lax.scan (the 'flash' recurrence in pure
    JAX).  Differentiable with recompute-based backward."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    bk = min(block_k, k.shape[2])
    if k.shape[2] % bk:
        return mha_reference(q, k, v, mask, causal, scale, block_diffusion)
    if block_diffusion is not None:
        _check_block_diffusion(q.shape[2], k.shape[2], block_diffusion)
    return _blockwise_fwd(q, k, v, mask, causal, scale, bk, block_diffusion)


def _bw_fwd(q, k, v, mask, causal, scale, block_k, block_diffusion=None):
    out = blockwise_attention(q, k, v, mask, causal, scale, block_k,
                              block_diffusion)
    return out, (q, k, v, mask)


def _bw_bwd(causal, scale, block_k, block_diffusion, res, g):
    """Flash-style backward: recompute attention under jax.grad of the
    scan — XLA rematerializes blockwise, never storing [T,T]."""
    q, k, v, mask = res

    def f(q_, k_, v_):
        if scale is None:
            s = q_.shape[-1] ** -0.5
        else:
            s = scale
        bk = min(block_k, k_.shape[2])
        if k_.shape[2] % bk:
            out = mha_reference(q_, k_, v_, mask, causal, s, block_diffusion)
        else:
            out = _blockwise_fwd(q_, k_, v_, mask, causal, s, bk,
                                 block_diffusion)
        return jnp.sum(out * g)

    dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    return dq, dk, dv, None


blockwise_attention.defvjp(_bw_fwd, _bw_bwd)


# ---------------------------------------------------------------------------
# The tile schedule: which tiles of the mask the kernels visit, and in what
# order.  Built in numpy while a call is traced, from what is static there.
# ---------------------------------------------------------------------------

# bits of `TileSchedule.flags`
PARTIAL, Q_FIRST, Q_LAST, K_FIRST, K_LAST = 1, 2, 4, 8, 16


class TileSchedule(NamedTuple):
    """The live tiles of one kernel call in the order its grid walks them:
    int32 arrays of one length that the kernels take as scalar-prefetch
    operands."""
    q: np.ndarray       # the tile's query block
    k: np.ndarray       # its key block
    flags: np.ndarray   # PARTIAL: the mask cuts the tile (else every pair is
    #                     kept); Q_FIRST / Q_LAST: the walk's first / last
    #                     tile of that query block; K_FIRST / K_LAST: of that
    #                     key block
    counts: tuple       # (tiles, live, full) of one head's [rows, S]
    keys_seen: tuple    # the key blocks that hold a live tile, ascending

    @property
    def kinds(self):
        """(some tile is partial, some tile is full)."""
        partial = (self.flags & PARTIAL) != 0
        return bool(partial.any()), bool((~partial).any())


def tile_kinds(T: int, S: int, bq: int, bk: int, causal=False,
               block_diffusion=None, q_offset=0, rows=None):
    """int8 [rows // bq, S // bk]: 0 where the mask keeps no pair of the
    [bq, bk] tile, 1 where it keeps some, 2 where it keeps all.  The queries
    are `rows` rows from `q_offset` on (a span) of a [T, S] problem; the
    mask is `causal`, `block_diffusion=(L, B)` or neither.  Both masks keep
    a pair by `d` = block(row) - block(column) (blocks of 1 for causal), and
    `d` takes every value between its least and its most over a tile: so the
    tile's two far corners decide."""
    rows = T - q_offset if rows is None else rows
    q0 = q_offset + bq * np.arange(rows // bq)[:, None]
    k0 = bk * np.arange(S // bk)[None, :]
    if block_diffusion is not None:
        L, B = block_diffusion
        qp, kp, dmin, dmax = _bd_quadrant(q0, k0, T - L, np.where)
    elif causal:
        qp, kp, dmin, dmax, B = q0, k0, 0, _NO_LIMIT, 1
    else:
        return np.full((rows // bq, S // bk), 2, np.int8)
    most = (qp + bq - 1) // B - kp // B
    least = qp // B - (kp + bk - 1) // B
    live = np.maximum(least, dmin) <= np.minimum(most, dmax)
    full = (least >= dmin) & (most <= dmax)
    return live.astype(np.int8) + full


def _ends(a):
    """(first, last) occurrence of each value of `a`, as boolean masks."""
    first, last = np.zeros(len(a), bool), np.zeros(len(a), bool)
    first[np.unique(a, return_index=True)[1]] = True
    last[len(a) - 1 - np.unique(a[::-1], return_index=True)[1]] = True
    return first, last


@functools.lru_cache(maxsize=None)
def tile_schedule(T: int, S: int, bq: int, bk: int, causal=False,
                  block_diffusion=None, masked=False, q_offset=0, rows=None,
                  group=1, keys_outer=False) -> TileSchedule:
    """The live tiles of `tile_kinds(...)`, the one place that says which
    tiles a kernel visits and which of them need the mask.  `masked`: a key
    padding mask rides along, so every live tile is partial.  Forward order
    (`keys_outer` false): query block outer, key blocks ascending.  Backward
    order: key block outer, then the `group` query heads of a key-value
    head, then query blocks; `q` there counts blocks of the group's rows,
    head after head.  Every query block holds a live tile (asserted: both
    masks keep a row's own block), a key block need not (`keys_seen`)."""
    kinds = tile_kinds(T, S, bq, bk, causal, block_diffusion, q_offset, rows)
    if masked:
        kinds = np.minimum(kinds, 1)
    nq, nk = kinds.shape
    assert (kinds > 0).any(axis=1).all(), "a query block with no live tile"
    if keys_outer:
        k, head, q = np.nonzero(
            np.broadcast_to(kinds.T[:, None, :], (nk, group, nq)))
    else:
        q, k = np.nonzero(kinds)
        head = 0
    partial = kinds[q, k] == 1
    q = head * nq + q
    (q_first, q_last), (k_first, k_last) = _ends(q), _ends(k)
    flags = (PARTIAL * partial + Q_FIRST * q_first + Q_LAST * q_last
             + K_FIRST * k_first + K_LAST * k_last)
    arrays = [np.asarray(a, np.int32) for a in (q, k, flags)]
    for a in arrays:
        a.setflags(write=False)
    return TileSchedule(
        *arrays, (nq * nk, int((kinds > 0).sum()), int((kinds == 2).sum())),
        tuple(int(j) for j in np.flatnonzero((kinds > 0).any(axis=0))))


def _by_kind(flags, kinds, tile):
    """`tile(partial)` as this grid step's tile asks; where the schedule
    holds one kind only (`TileSchedule.kinds`), without asking."""
    partial, full = kinds
    if partial and full:
        pl.when((flags & PARTIAL) != 0)(lambda: tile(True))
        pl.when((flags & PARTIAL) == 0)(lambda: tile(False))
    else:
        tile(partial)


# ---------------------------------------------------------------------------
# Pallas TPU kernels
# ---------------------------------------------------------------------------

def _flash_kernel(qb_ref, kb_ref, flags_ref, q_ref, k_ref, v_ref, *rest,
                  block_q: int, block_k: int, kinds, causal: bool,
                  scale: float, has_mask: bool, block_diffusion=None,
                  has_selection: bool = False):
    """Grid (batch*head, live tile): step `t` is tile (`qb_ref[t]`,
    `kb_ref[t]`) of a forward `TileSchedule` — a query block's live tiles
    one after another, key blocks ascending — so a tile the mask leaves
    empty costs no grid step.  Pallas pipelines the KV block fetches
    (double-buffered HBM→VMEM) while online-softmax state lives in VMEM
    scratch from a query block's first tile to its last.  A full tile takes
    no mask arithmetic; a partial one builds `causal`'s or
    ``block_diffusion``'s ((noisy rows, block length)) kept pairs from iota.
    Emits per-row logsumexp for the backward kernel.  With ``has_mask`` an
    additive f32 bias block [1, 1, bk] (0 keep / NEG_INF drop over KV
    positions) precedes the outputs and every tile is partial.  With
    ``has_selection`` the tile's bits of `Selection.by_query`, [1, bq/32,
    bk], come last of the inputs: every tile is partial and the bits are
    its whole mask (a selection holds causal pairs only)."""
    rest = list(rest)
    bias_ref = rest.pop(0) if has_mask else None
    sel_ref = rest.pop(0) if has_selection else None
    o_ref, lse_ref, acc_sc, m_sc, l_sc = rest
    t = pl.program_id(1)
    qi, j, flags = qb_ref[t], kb_ref[t], flags_ref[t]

    @pl.when((flags & Q_FIRST) != 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    def tile(partial):
        q = q_ref[0]                                       # [bq, D]
        kj = k_ref[0]                                      # [bk, D]
        vj = v_ref[0]
        s = jnp.dot(q, kj.T, preferred_element_type=jnp.float32) * scale
        if has_mask:
            s = s + bias_ref[0]                            # [1,bk] → rows
        if partial and block_diffusion is not None:
            o, B = block_diffusion
            s = jnp.where(_bd_keep_tile(
                *_bd_quadrant(qi * block_q, j * block_k, o, jnp.where),
                block_q, block_k, B), s, NEG_INF)
        if has_selection:
            s = jnp.where(_unpack_bits(sel_ref[0]), s, NEG_INF)
        elif partial and causal:
            rows = (qi * block_q
                    + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 0))
            cols = (j * block_k
                    + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1))
            s = jnp.where(rows >= cols, s, NEG_INF)
        m = m_sc[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_sc[...] = corr * l_sc[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = corr * acc_sc[...] + jnp.dot(
            p.astype(vj.dtype), vj, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    _by_kind(flags, kinds, tile)

    @pl.when((flags & Q_LAST) != 0)
    def _finalize():
        l = l_sc[...]
        o_ref[0] = (acc_sc[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_sc[...] + jnp.log(l)                # [bq, 1]


def _mask_bias3(mask, B, S):
    """[B, S] 1/0 keep-mask → additive f32 bias [B, 1, S] for the kernels."""
    return jnp.where(mask.reshape(B, S) > 0, 0.0, NEG_INF).astype(
        jnp.float32).reshape(B, 1, S)


def _bd_static(T, S, bq, bk, block_diffusion) -> dict:
    """The kernels' `block_diffusion` keyword, (noisy rows, block length),
    checked against the tiles; none where the mask is not asked for."""
    if block_diffusion is None:
        return {}
    _check_block_diffusion(T, S, block_diffusion)
    L, B = block_diffusion
    if L % bq or L % bk:
        raise ValueError(f"tiles of {bq} x {bk} lie across the first clean "
                         f"row of block_diffusion=({L}, {B})")
    return {"block_diffusion": (T - L, B)}


def _check_selection(selection, causal, mask, block_diffusion,
                     block: int) -> bool:
    """Whether a kernel call carries a selection; one that does is causal,
    has no other mask and unpacks whole words along `block`."""
    if selection is None:
        return False
    if not causal or mask is not None or block_diffusion is not None:
        raise ValueError("a selection holds causal pairs: it goes with "
                         "`causal` and no other mask")
    if block % 32:
        raise ValueError(f"a block of {block} is no whole 32-bit words of "
                         f"a selection")
    return True


def flash_attention_tpu(q, k, v, causal=False, scale=None,
                        block_q=256, block_k=256, interpret=False,
                        return_lse=False, mask=None, block_diffusion=None,
                        selection: Optional[Selection] = None):
    """Pallas flash-attention forward.  q [B, H, T, D], k [B, Hk, S, D],
    v [B, Hk, S, Dv] -> [B, H, T, Dv]; T and S divisible by the block sizes
    (dispatcher checks), H a multiple of Hk (a key-value head's blocks are
    fetched for each of its query heads, from where they lie).  The grid's
    second axis walks `tile_schedule`'s live tiles.  With ``return_lse``
    also returns the row logsumexp [B*H, T] (f32) for the backward kernel.
    ``mask``: optional [B, S] 1/0 keep-mask over KV positions
    (padding/segment mask), shared across heads.  ``block_diffusion``:
    (L, B) of the module docstring's mask; the blocks divide L, so that no
    tile lies across the first clean row.  ``selection``: a `Selection` of
    causal pairs (with ``causal``, whose schedule the grid walks); the query
    block is whole words of it."""
    B, H, T, D = q.shape
    S, Dv = k.shape[2], v.shape[3]
    if scale is None:
        scale = D ** -0.5
    bq = min(block_q, T)
    bk = min(block_k, S)
    bd = _bd_static(T, S, bq, bk, block_diffusion)
    has_selection = _check_selection(selection, causal, mask,
                                     block_diffusion, bq)
    Hk = k.shape[1]
    group = H // Hk
    # grid row b = batch * H + head reads the row b // group of k and v
    # folded to [B * Hk, S, .]: the heads of a group are neighbours
    kv_row = (lambda b: b) if group == 1 else (lambda b: b // group)
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * Hk, S, D)
    vf = v.reshape(B * Hk, S, Dv)
    has_mask = mask is not None
    sched = tile_schedule(T, S, bq, bk, causal, block_diffusion,
                          has_mask or has_selection)
    kernel = functools.partial(_flash_kernel, block_q=bq, block_k=bk,
                               kinds=sched.kinds, causal=causal, scale=scale,
                               has_mask=has_mask,
                               has_selection=has_selection, **bd)
    # the index maps read step t's blocks from the schedule (in SMEM)
    q_rows = lambda b, t, qb, kb, _: (b, qb[t], 0)
    k_rows = lambda b, t, qb, kb, _: (kv_row(b), kb[t], 0)
    in_specs = [
        pl.BlockSpec((1, bq, D), q_rows),
        pl.BlockSpec((1, bk, D), k_rows),
        pl.BlockSpec((1, bk, Dv), k_rows),
    ]
    inputs = [qf, kf, vf]
    if has_mask:
        # bias [B, 1, S]: per-batch, shared across the H heads folded into
        # grid dim 0 — the index map divides the head out
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), lambda b, t, qb, kb, _, H=H: (b // H, 0, kb[t])))
        inputs.append(_mask_bias3(mask, B, S))
    if has_selection:       # one for all heads: the index map divides them out
        in_specs.append(pl.BlockSpec(
            (1, bq // 32, bk),
            lambda b, t, qb, kb, _, H=H: (b // H, qb[t], kb[t])))
        inputs.append(selection.by_query)
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B * H, len(sched.q)),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, bq, Dv), q_rows),
                # lse rides a trailing singleton lane dim — (1, bq, 1)
                # blocks satisfy the TPU (8, 128)-or-full tiling rule
                pl.BlockSpec((1, bq, 1), q_rows),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, Dv), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
        ],
        interpret=interpret,
    )(sched.q, sched.k, sched.flags, *inputs)
    out = out.reshape(B, H, T, Dv)
    return (out, lse.reshape(B * H, T)) if return_lse else out


# VMEM of the one backward kernel.  `_BWD_VMEM_LIMIT` is what the call states
# to Mosaic (`vmem_limit_bytes`; a v5e has 128 MiB): at kanana's 4096 x 192
# a tile of 512 x 1024 takes all of the 16 MiB default and 1024 x 1024 takes
# 20.  `_BWD_DQ_VMEM` of it is the room of the resident dQ: the f32
# accumulator [span, D] and the double-buffered output block [span, D] (6
# MiB at 4096 x 192 in bfloat16); the tile's temporaries, operands and dK/dV
# share the rest.
_BWD_VMEM_LIMIT = 48 << 20
_BWD_DQ_VMEM = 16 << 20

_NT = (((1,), (1,)), ((), ()))                 # a [m, c], b [n, c] -> [m, n]


def _flash_bwd_kernel(qb_ref, kb_ref, flags_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, *rest, block_q: int, block_k: int,
                      kinds, head_blocks: int, q_offset: int, causal: bool,
                      scale: float, has_mask: bool, block_diffusion=None,
                      has_selection: bool = False):
    """dQ, dK and dV over grid (batch*key-value head, live tile): step `t`
    is tile (`qb_ref[t]`, `kb_ref[t]`) of a backward `TileSchedule` — a key
    block's live tiles one after another, the q blocks innermost.  A tile
    recomputes P from the saved logsumexp and computes dP and dS once; from
    them dV += P^T dO and dK += dS^T Q into the kv block's scratch (zeroed
    at the block's first tile, written at its last) and dQ += dS K into the
    rows of a [span, D] scratch that stays in VMEM across the key blocks
    (zeroed at a q block's first visit, written at its last): five products,
    no [T, T] array and no partial dQ in HBM.  The tile is held
    keys-by-queries ([bk, bq]), so that dV and dK are plain products, dQ's
    is the one transposed operand and the row statistics lie along lanes
    ([1, bq]).  ``q_offset`` is the first query's position (a span of a
    longer sequence).  The q blocks of a grid row are ``head_blocks`` blocks
    of each query head of a group, head after head: a block's positions
    start anew with each head, and dK/dV sum over all of them.  Full and
    partial tiles, ``block_diffusion``: as in the forward kernel;
    ``has_selection``: the tile's bits of `Selection.by_key`, [1, bk/32,
    bq]."""
    rest = list(rest)
    bias_ref = rest.pop(0) if has_mask else None
    sel_ref = rest.pop(0) if has_selection else None
    dq_ref, dk_ref, dv_ref, dq_sc, dk_sc, dv_sc = rest
    t = pl.program_id(1)
    i, j, flags = qb_ref[t], kb_ref[t], flags_ref[t]
    rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)

    @pl.when((flags & K_FIRST) != 0)
    def _init_dkv():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when((flags & Q_FIRST) != 0)
    def _init_dq():
        dq_sc[rows, :] = jnp.zeros((block_q, dq_sc.shape[1]), jnp.float32)

    def tile(partial):
        q = q_ref[0]                                       # [bq, D]
        do = do_ref[0]                                     # [bq, Dv]
        kj = k_ref[0]                                      # [bk, D]
        vj = v_ref[0]                                      # [bk, Dv]
        s = jax.lax.dot_general(
            kj, q, _NT, preferred_element_type=jnp.float32) * scale
        if has_mask:
            s = s + bias_ref[0]                            # [bk, 1] → cols
        # the block's first query position: blocks start anew with a head
        q0 = q_offset + i % head_blocks * block_q
        if partial and block_diffusion is not None:
            o, B = block_diffusion
            s = jnp.where(_bd_keep_tile(
                *_bd_quadrant(q0, j * block_k, o, jnp.where),
                block_q, block_k, B, keys_first=True), s, NEG_INF)
        if has_selection:
            s = jnp.where(_unpack_bits(sel_ref[0]), s, NEG_INF)
        elif partial and causal:
            keys = (j * block_k
                    + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_k, block_q), 0))
            queries = (q0
                       + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_k, block_q), 1))
            s = jnp.where(queries >= keys, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])                     # [bk, bq] f32
        dv_sc[...] += jnp.dot(p.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            vj, do, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0, 0])).astype(q.dtype)
        dk_sc[...] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
        dq_sc[rows, :] += jnp.dot(ds.T, kj,
                                  preferred_element_type=jnp.float32)

    _by_kind(flags, kinds, tile)

    @pl.when((flags & K_LAST) != 0)
    def _write_dkv():
        dk_ref[0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when((flags & Q_LAST) != 0)
    def _write_dq():
        dq_ref[0, rows, :] = (dq_sc[rows, :] * scale).astype(dq_ref.dtype)


def _bwd_plan(T, S, D, Dv, itemsize, block_q, block_k, group=1):
    """(bq, bk, span) of the backward kernel, from the shapes alone: the
    asked blocks, the larger halved until the tile's four f32 [bk, bq]
    temporaries, the double-buffered operands and dK/dV's accumulators and
    output blocks fit `_BWD_VMEM_LIMIT` less `_BWD_DQ_VMEM`; and the most
    query rows (whole blocks) of each of a group's `group` heads whose
    resident dQ fits `_BWD_DQ_VMEM`."""
    bq, bk = min(block_q, T), min(block_k, S)

    def tile_bytes(bq, bk):
        return (16 * bq * bk + 2 * itemsize * (bq + bk) * (D + Dv)
                + (4 + 2 * itemsize) * bk * (D + Dv))

    while tile_bytes(bq, bk) > _BWD_VMEM_LIMIT - _BWD_DQ_VMEM:
        if bk >= bq and bk % 256 == 0:
            bk //= 2
        elif bq % 256 == 0:
            bq //= 2
        else:
            break
    rows = _BWD_DQ_VMEM // (D * (4 + 2 * itemsize)) // group
    return bq, bk, min(T, max(bq, rows // bq * bq))


def _sum_over_spans(parts, seen, bk, dtype):
    """dK or dV [B*Hk, S, .] from the spans' `parts`, of which span `s`
    wrote the key blocks `seen[s]` alone: their sum under each span's static
    mask of the rows it wrote — a select, so that what an unwritten block
    holds never reaches the sum, and keys that no query sees get zeros
    (causal with S > T).  (Slicing the written blocks out and concatenating
    the runs reads half the bytes, but XLA makes three passes of it: 0.67 ms
    a step slower on SDAR's cell than this one fusion, PERF.md, PR 34.)"""
    nkv = parts[0].shape[1] // bk
    if len(parts) == 1 and len(seen[0]) == nkv:
        return parts[0]
    wrote = [np.repeat(np.isin(np.arange(nkv), blocks), bk)[None, :, None]
             for blocks in seen]
    return functools.reduce(jnp.add, (
        part if mask.all() else jnp.where(mask, part, 0)
        for part, mask in zip(parts, wrote))).astype(dtype)


def flash_attention_bwd_tpu(q, k, v, out, lse, g, causal=False, scale=None,
                            block_q=256, block_k=256, interpret=False,
                            mask=None, block_diffusion=None,
                            selection: Optional[Selection] = None):
    """Pallas flash-attention backward: delta precomputed on-device, then
    ONE kernel (`_flash_bwd_kernel`) that computes the scores, P, dP and dS
    of a tile once and takes dQ, dK and dV from them — no [T, T] array, no
    second pass over the scores.  ``block_q``/``block_k`` are the forward's
    blocks; `_bwd_plan` keeps them where they fit the VMEM the call states
    (`_BWD_VMEM_LIMIT`, 48 MiB) and halves them where they do not.  dQ of a
    whole (batch, head) stays in VMEM while its kv blocks pass, within a
    budget of 16 MiB (`_BWD_DQ_VMEM`: 10,922 rows at keys of 192 in
    bfloat16); a longer sequence is cut into spans of queries that fit, one
    call each over the key blocks its `tile_schedule` holds a live tile of,
    and dK/dV are summed in float32 over the spans that saw a block (a span
    writes no other block of its dK/dV, and `_sum_over_spans` masks the
    rest out).
    Grouped-query heads (k, v [B, Hk, S, .]): a grid row is a KEY-VALUE head
    and its q blocks are those of the group's query heads, head after head,
    so dK and dV accumulate over the group in the kernel's scratch and dQ of
    the whole group is resident (32,768 rows of 64: the whole budget at `[1,
    32, 8192, 64]` over 8).  Measured there against per-head dK/dV written
    in float32 and summed after: 9.68 against 10.37 ms (PERF.md, PR 31)."""
    B, H, T, D = q.shape
    S, Dv = k.shape[2], v.shape[3]
    Hk = k.shape[1]
    G = H // Hk
    if scale is None:
        scale = D ** -0.5
    bq, bk, span = _bwd_plan(T, S, D, Dv, q.dtype.itemsize, block_q, block_k,
                             G)
    bd = _bd_static(T, S, bq, bk, block_diffusion)
    has_selection = _check_selection(selection, causal, mask,
                                     block_diffusion, bk)
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * Hk, S, D)
    vf = v.reshape(B * Hk, S, Dv)
    gf = g.reshape(B * H, T, Dv)
    # delta_i = rowsum(dO_i * O_i) — cheap elementwise reduce, XLA-fused
    delta = jnp.sum(gf.astype(jnp.float32)
                    * out.reshape(B * H, T, Dv).astype(jnp.float32), axis=-1)
    lse = lse.reshape(B * H, T)
    has_mask = mask is not None
    extra_in, extra_specs = [], []
    if has_mask:
        # bias [B, S, 1] along the tile's rows: per-batch, shared across the
        # heads folded into grid dim 0 — the index map divides the head out
        extra_in = [_mask_bias3(mask, B, S).reshape(B, S, 1)]
        extra_specs = [pl.BlockSpec(
            (1, bk, 1), lambda b, t, qb, kb, _, H=Hk: (b // H, kb[t], 0))]

    def one_span(t0, t1, part_dtype=None):
        """The kernel over queries [t0, t1) and the keys they see: `(dq, dk,
        dv), key blocks seen`; dK and dV in ``part_dtype`` where they are
        one span's part of a sum."""
        n = t1 - t0
        nq = n // bq                    # q blocks of one head
        sched = tile_schedule(T, S, bq, bk, causal, block_diffusion,
                              has_mask or has_selection, t0, n, G, True)
        sel_in, sel_specs = [], []
        if has_selection:   # the whole array: the map finds the span's and
            sel_in = [selection.by_key]         # the head's query block
            sel_specs = [pl.BlockSpec(
                (1, bk // 32, bq), lambda b, t, qb, kb, _: (
                    b // Hk, kb[t], t0 // bq + qb[t] % nq))]

        def rows(a):        # [B*H, T, .] -> the span's rows of a group,
            a = a[:, t0:t1]                         # head after head
            return a if G == 1 else a.reshape(B * Hk, G * n, a.shape[-1])

        def stat(a):                    # [B*H, T] -> [1, bq] blocks
            return a[:, t0:t1].reshape(B * Hk, G * nq, 1, bq)

        kernel = functools.partial(
            _flash_bwd_kernel, block_q=bq, block_k=bk, kinds=sched.kinds,
            head_blocks=nq, q_offset=t0, causal=causal, scale=scale,
            has_mask=has_mask, has_selection=has_selection, **bd)
        q_rows = lambda b, t, qb, kb, _: (b, qb[t], 0)
        k_rows = lambda b, t, qb, kb, _: (b, kb[t], 0)
        stat_spec = pl.BlockSpec((1, 1, 1, bq),
                                 lambda b, t, qb, kb, _: (b, qb[t], 0, 0))
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(B * Hk, len(sched.q)),
                in_specs=[
                    pl.BlockSpec((1, bq, D), q_rows),
                    pl.BlockSpec((1, bk, D), k_rows),
                    pl.BlockSpec((1, bk, Dv), k_rows),
                    pl.BlockSpec((1, bq, Dv), q_rows),
                    stat_spec, stat_spec,
                ] + extra_specs + sel_specs,
                out_specs=[
                    # dQ: one block a (batch, key-value head), written back
                    # when it ends
                    pl.BlockSpec((1, G * n, D),
                                 lambda b, t, qb, kb, _: (b, 0, 0)),
                    pl.BlockSpec((1, bk, D), k_rows),
                    pl.BlockSpec((1, bk, Dv), k_rows),
                ],
                scratch_shapes=[
                    pltpu.VMEM((G * n, D), jnp.float32),
                    pltpu.VMEM((bk, D), jnp.float32),
                    pltpu.VMEM((bk, Dv), jnp.float32),
                ]),
            out_shape=[
                jax.ShapeDtypeStruct((B * Hk, G * n, D), q.dtype),
                jax.ShapeDtypeStruct((B * Hk, S, D), part_dtype or k.dtype),
                jax.ShapeDtypeStruct((B * Hk, S, Dv), part_dtype or v.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_BWD_VMEM_LIMIT),
            interpret=interpret,
        )(sched.q, sched.k, sched.flags, rows(qf), kf, vf, rows(gf),
          stat(lse), stat(delta), *extra_in, *sel_in), sched.keys_seen

    spans = range(0, T, span)
    parts, seen = zip(*(
        one_span(t0, min(t0 + span, T), jnp.float32 if len(spans) > 1 else None)
        for t0 in spans))
    dq = parts[0][0] if len(spans) == 1 else jnp.concatenate(
        [part[0].reshape(B * H, -1, D) for part in parts], axis=1)
    dk = _sum_over_spans([part[1] for part in parts], seen, bk, k.dtype)
    dv = _sum_over_spans([part[2] for part in parts], seen, bk, v.dtype)
    return (dq.reshape(B, H, T, D), dk.reshape(B, Hk, S, D),
            dv.reshape(B, Hk, S, Dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_attention_diff(q, k, v, mask, causal, scale, block_q=256,
                          block_k=256, interpret=False, block_diffusion=None):
    return flash_attention_tpu(q, k, v, causal, scale, block_q, block_k,
                               mask=mask, interpret=interpret,
                               block_diffusion=block_diffusion)


# What `_fa_fwd` names for a caller's `jax.checkpoint` policy.
FLASH_OUT = "flash_attention_out"
FLASH_LSE = "flash_attention_lse"


def _fa_fwd(q, k, v, mask, causal, scale, block_q, block_k,
            interpret=False, block_diffusion=None):
    """The forward kernel, once; residuals for `_fa_bwd`.

    `out` [B, H, T, Dv] and the row logsumexp [B*H, T] (float32) are the two
    residuals the backward kernel needs that a caller cannot rebuild without
    running this kernel again, so they carry the names `FLASH_OUT` and
    `FLASH_LSE`: a block under `jax.checkpoint(policy=
    save_only_these_names(FLASH_OUT, FLASH_LSE))` keeps them and its
    backward pass holds no second forward kernel (`zoo/decoder.py`).  These
    two and not q/k/v: at kanana's shapes they are 68 MB a layer against
    268 MB, and q/k/v come back from the projections at the MXU's rate
    (saving them too did not fit 16 GB: PERF.md, PR 27 and PR 28).  The
    logsumexp is named in its [B*H, T] form; the kernel's [B*H, T, 1] pads
    to 128 lanes in HBM.  The named `out` is both the primal result and the
    residual.  Without such a policy a name is an identity."""
    out, lse = flash_attention_tpu(q, k, v, causal, scale, block_q, block_k,
                                   return_lse=True, mask=mask,
                                   interpret=interpret,
                                   block_diffusion=block_diffusion)
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, mask, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, interpret, block_diffusion, res,
            g):
    q, k, v, mask, out, lse = res
    dq, dk, dv = flash_attention_bwd_tpu(q, k, v, out, lse, g, causal, scale,
                                         block_q, block_k, mask=mask,
                                         interpret=interpret,
                                         block_diffusion=block_diffusion)
    return dq, dk, dv, None


_flash_attention_diff.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_selected_diff(q, k, v, by_query, by_key, scale, block_q=256,
                         block_k=256, interpret=False):
    """Causal flash attention over a `Selection`'s pairs: `(out, logsumexp
    [B, H, T])`.  The logsumexp is handed out for a reader that stops the
    gradient (the index loss of `ops/sparse_index.py`): its cotangent is
    dropped."""
    return _fs_fwd(q, k, v, by_query, by_key, scale, block_q, block_k,
                   interpret)[0]


def _fs_fwd(q, k, v, by_query, by_key, scale, block_q, block_k, interpret):
    """As `_fa_fwd`, the same two names; the selection is an input, so a
    block under such a policy keeps it only if its maker names it."""
    B, H, T, _ = q.shape
    out, lse = flash_attention_tpu(
        q, k, v, True, scale, block_q, block_k, return_lse=True,
        interpret=interpret, selection=Selection(by_query, by_key))
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return (out, lse.reshape(B, H, T)), (q, k, v, by_query, by_key, out, lse)


def _fs_bwd(scale, block_q, block_k, interpret, res, g):
    q, k, v, by_query, by_key, out, lse = res
    dq, dk, dv = flash_attention_bwd_tpu(
        q, k, v, out, lse, g[0], True, scale, block_q, block_k,
        interpret=interpret, selection=Selection(by_query, by_key))
    return dq, dk, dv, None, None


_flash_selected_diff.defvjp(_fs_fwd, _fs_bwd)


def _pick_block(x: int, prefer: int) -> Optional[int]:
    for b in (prefer, 512, 256, 128):
        if b <= prefer and x % b == 0:
            return b
    return None


# Empirical v5e-1 policy (fwd+bwd, bf16, D=64), measured on v5e,
# 2026-07-31: XLA's attention fusion wins at
# seq 1024 (flash 0.78x), parity at 2048 (0.998x), flash ahead at 4096
# (1.03x) and increasingly beyond — and flash is the only O(T)-memory
# option once [T,T] scores stop fitting HBM.
_FLASH_MIN_SEQ = 2048
_XLA_SCORE_BYTES_MAX = 2 << 30   # beyond ~2GB of scores, never take XLA path


def fused_attention(q, k, v, mask=None, causal=False, scale=None,
                    block_diffusion=None,
                    selection: Optional[Selection] = None,
                    return_lse: bool = False):
    """Dispatcher (the platform-helper pattern — cuDNN-attention role):

    - kernel tier (`ops/pallas/dispatch`): Pallas flash kernels (fwd +
      one-kernel recomputing bwd, O(T) memory) with TileConfig-driven
      blocks and masked-tail padding for ragged shapes, on TPU/GPU when
      the measured heuristics say flash wins (long seq, lane-multiple D),
      or whenever the tier is forced to `pallas`.
    - short seq / small scores → XLA-fused naive path (measured fastest
      on v5e below ~2k).
    - the rest → blockwise scan (O(T) memory).

    `block_diffusion=(L, B)`: the mask of block-diffusion training over L
    or 2L rows (module docstring), in every branch; not with `mask` or
    `causal`.  `selection`: a `Selection` of causal pairs, with `causal`
    and no other mask, in every branch (the XLA paths unpack it to a [B, T,
    S] keep-mask); `return_lse` then also gives the rows' logsumexp [B, H,
    T] in float32, for a reader that stops the gradient.  Differentiable
    everywhere."""
    from deeplearning4j_tpu.ops import pallas as _tier
    B, H, T, D = q.shape
    S = k.shape[2]
    if selection is not None:
        return _selected_attention(q, k, v, selection, causal, mask,
                                   block_diffusion, scale, return_lse)
    if return_lse:
        raise ValueError("the logsumexp is handed out with a selection")
    masks = {} if block_diffusion is None else {
        "block_diffusion": tuple(int(n) for n in block_diffusion)}
    if masks and (causal or mask is not None):
        raise ValueError("block_diffusion is a mask of its own: neither "
                         "`causal` nor `mask` goes with it")
    if _tier.dispatch.resolve("attention", q, k, v, mask=mask,
                              causal=causal, **masks) == "pallas":
        sc = _tier.shape_class(t=T, s=S, d=D)
        return _tier.attention.flash_attention(
            q, k, v, mask=mask, causal=causal, scale=scale,
            tile=_tier.dispatch.get_tile("attention", sc),
            interpret=_tier.dispatch.interpret_mode(), **masks)
    score_bytes = B * H * T * S * q.dtype.itemsize
    if score_bytes <= _XLA_SCORE_BYTES_MAX:
        return mha_reference(q, k, v, mask, causal, scale, **masks)
    return blockwise_attention(q, k, v, mask, causal, scale, **masks)


def _selected_attention(q, k, v, selection, causal, mask, block_diffusion,
                        scale, return_lse):
    """`fused_attention` over a `Selection`: the flash kernels where the
    tier takes them, else the naive path on the unpacked mask (the CPU, short
    sequences), the logsumexp from the same scores."""
    from deeplearning4j_tpu.ops import pallas as _tier
    _check_selection(selection, causal, mask, block_diffusion, 32)
    T, S, D = q.shape[2], k.shape[2], q.shape[3]
    if _tier.dispatch.resolve("attention", q, k, v, causal=True,
                              selection=selection) == "pallas":
        out, lse = _tier.attention.flash_attention(
            q, k, v, causal=True, scale=scale,
            tile=_tier.dispatch.get_tile(
                "attention", _tier.shape_class(t=T, s=S, d=D)),
            interpret=_tier.dispatch.interpret_mode(), selection=selection)
    else:
        out, lse = mha_reference(q, k, v, unpack_selection(selection),
                                 scale=scale, return_lse=True)
    return (out, jax.lax.stop_gradient(lse)) if return_lse else out


# ---------------------------------------------------------------------------
# Quantized inference projections (quant/ subsystem hot path)
# ---------------------------------------------------------------------------

def quantized_projection(x, qt, b=None, acc_dtype=None):
    """[B, T, F] @ int8 [F, O] projection with per-output-channel scales —
    the q/k/v/out projections are where an attention block's weight bytes
    live, so they are what quantization shrinks; the [T, T] score math
    keeps the accumulating dtype untouched.  Dequantization (the scale
    multiply) happens after the matmul, inside the jitted program."""
    from deeplearning4j_tpu.ops.quant_kernels import quantized_matmul
    y = quantized_matmul(x, qt, acc_dtype=acc_dtype)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def quantized_mha(x, w_qkv, w_out, n_heads: int, b_qkv=None, b_out=None,
                  mask=None, causal=False, acc_dtype=None):
    """Self-attention with all four projections served from int8 weights
    (`w_qkv`: QTensor [F, 3F']; `w_out`: QTensor [F', F_out]) and the
    score/softmax/value math in the accumulating dtype via
    `fused_attention` — the quantized counterpart of the nn attention
    layers' forward for serving."""
    B, T, _ = x.shape
    qkv = quantized_projection(x, w_qkv, b=b_qkv, acc_dtype=acc_dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    d = q.shape[-1] // n_heads

    def heads(a):          # [B, T, H*D] -> [B, H, T, D]
        return a.reshape(B, T, n_heads, d).transpose(0, 2, 1, 3)

    o = fused_attention(heads(q), heads(k), heads(v), mask=mask,
                        causal=causal)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, n_heads * d)
    return quantized_projection(o, w_out, b=b_out, acc_dtype=acc_dtype)
