"""Flash attention entry point for the fused-kernel tier.

Thin, tile-aware wrapper over the blockwise online-softmax kernels in
``ops/attention_kernels.py`` (forward + the one backward kernel that takes
dQ, dK and dV from a single pass over the recomputed scores, via
``_flash_attention_diff``).  What the tier adds on top:

- tiling comes from a :class:`TileConfig` (``block_q``/``block_kv``)
  instead of the fixed ``_pick_block`` ladder, so the autotuner's
  persisted winners take effect here (the backward starts from the same
  blocks and halves them only where its VMEM budget asks:
  ``attention_kernels._bwd_plan``);
- ragged / non-multiple-of-tile shapes are handled by zero-padding T and
  S up to block multiples with the padded KV positions knocked out via
  the additive [B, S] mask (a masked tail), then slicing the padded query
  rows back off — exact, because masked positions contribute
  ``exp(-1e30)``-scale weights and padded query rows are discarded;
- a ``reference`` lowering (plain ``mha_reference``) that is the
  definition of correctness for the conformance suite.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from deeplearning4j_tpu.ops.pallas.tiles import DEFAULT_TILES, TileConfig


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _q_sublane(dtype) -> int:
    return 16 if jnp.dtype(dtype) == jnp.dtype(jnp.bfloat16) else 8


def _dividing(block: int, n: int) -> int:
    """The asked block, halved until it divides `n` rows."""
    b = min(block, n)
    while n % b:
        b //= 2
    return b


def flash_attention(q, k, v, mask=None, causal: bool = False, scale=None,
                    tile: Optional[TileConfig] = None,
                    interpret: bool = False, block_diffusion=None,
                    selection=None):
    """Flash attention of q [B, H, T, D], k [B, Hk, S, D], v [B, Hk, S, Dv]
    -> [B, H, T, Dv] with TileConfig-driven blocks and masked-tail padding
    for ragged T/S (the padding is along T and S only, so it holds for any
    value width and any number of key-value heads).  Under
    `block_diffusion=(L, B)` the blocks divide L — no tile lies across the
    first clean row — and nothing is padded.  With a `selection`
    (`attention_kernels.Selection`, causal pairs) the blocks divide T and
    S, nothing is padded either, and the result is `(out, logsumexp [B, H,
    T])`.  Differentiable."""
    import deeplearning4j_tpu.ops.attention_kernels as ak

    tile = tile or DEFAULT_TILES["attention"]
    B, H, T, D = q.shape
    S = k.shape[2]
    if selection is not None:
        return ak._flash_selected_diff(
            q, k, v, selection.by_query, selection.by_key, scale,
            _dividing(tile.block_q, T), _dividing(tile.block_kv, S),
            interpret)
    if block_diffusion is not None:
        L = block_diffusion[0]
        return ak._flash_attention_diff(
            q, k, v, None, False, scale, _dividing(tile.block_q, L),
            _dividing(tile.block_kv, L), interpret, block_diffusion)
    bq = min(tile.block_q, _round_up(T, _q_sublane(q.dtype)))
    bk = min(tile.block_kv, _round_up(S, 128))
    Tp, Sp = _round_up(T, bq), _round_up(S, bk)

    if (Tp, Sp) == (T, S):
        args = (q, k, v, mask, causal, scale, bq, bk)
    else:
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        keep = jnp.ones((B, S), q.dtype) if mask is None else mask
        maskp = jnp.pad(keep.astype(q.dtype), ((0, 0), (0, Sp - S)))
        args = (qp, kp, vp, maskp, causal, scale, bq, bk)
    if interpret:
        args = args + (True,)
    out = ak._flash_attention_diff(*args)
    if Tp != T:
        out = out[:, :, :T, :]
    return out


def attention_reference(q, k, v, mask=None, causal: bool = False,
                        scale=None, block_diffusion=None, selection=None):
    import deeplearning4j_tpu.ops.attention_kernels as ak

    if selection is not None:
        return ak.mha_reference(q, k, v, ak.unpack_selection(selection),
                                scale=scale, return_lse=True)
    return ak.mha_reference(q, k, v, mask=mask, causal=causal, scale=scale,
                            block_diffusion=block_diffusion)


def attention_supports(q, k, v, mask=None, causal: bool = False,
                       block_diffusion=None, selection=None, **kw) -> bool:
    """Hard constraints only — forced-pallas mode must work on the small
    shapes the conformance suite uses."""
    if getattr(q, "ndim", 0) != 4:
        return False
    if jnp.dtype(q.dtype) not in (jnp.dtype(jnp.float32),
                                  jnp.dtype(jnp.bfloat16)):
        return False
    if k.dtype != q.dtype or v.dtype != q.dtype:
        return False
    # q and k share the batch and the key width; v may have a width of its
    # own but lies over the same batch, heads and positions as k; the
    # query's heads are a multiple of k's (grouped-query heads: query head h
    # attends key-value head h // group), equal where every head has its own
    if getattr(k, "ndim", 0) != 4 or getattr(v, "ndim", 0) != 4 \
            or k.shape[3] != q.shape[3] or v.shape[:3] != k.shape[:3] \
            or k.shape[0] != q.shape[0] or k.shape[1] == 0 \
            or q.shape[1] % k.shape[1]:
        return False
    if mask is not None:
        B, _, _, _ = q.shape
        S = k.shape[2]
        if getattr(mask, "ndim", 0) != 2 or mask.shape != (B, S):
            return False
    if selection is not None:
        # causal pairs in whole 32-bit words along both sides: a block that
        # divides T or S is then whole words too
        if mask is not None or not causal or block_diffusion is not None \
                or q.shape[2] % 32 or k.shape[2] % 32:
            return False
    if block_diffusion is not None:
        # the clean rows alone or both copies, in whole blocks; the tiles
        # are whole sublanes of L (`flash_attention` halves them to fit)
        L, blk = block_diffusion
        if mask is not None or causal or q.shape[2] != k.shape[2] \
                or q.shape[2] not in (L, 2 * L) or L % blk \
                or L % _q_sublane(q.dtype):
            return False
    return True


def attention_profitable(q, k, v, mask=None, causal: bool = False,
                         **kw) -> bool:
    """Auto-mode perf heuristics: mirror the measured v5e policy the old
    dispatcher encoded (flash wins from ~2k sequence, the key width — the
    scores' contraction — a multiple of 64; the value width is free)."""
    import deeplearning4j_tpu.ops.attention_kernels as ak

    T, S, key_width = q.shape[2], k.shape[2], k.shape[3]
    return key_width % 64 == 0 and max(T, S) >= ak._FLASH_MIN_SEQ
