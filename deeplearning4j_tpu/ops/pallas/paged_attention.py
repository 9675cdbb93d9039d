"""Paged-KV decode attention for the fused-kernel tier.

The autoregressive decode hot loop is one query token per sequence
attending over that sequence's whole history.  Storing the history
contiguously forces a per-sequence max-length reservation; instead the
serving layer (``serving/decode.py``) keeps KV in fixed-size *pages*
shared by every sequence, and each sequence owns a *block table* — the
ordered list of page indices that make up its history (the vLLM design,
applied to the TPU tier).  This module is the attention that reads that
layout, shipped under the PR-13 two-implementation contract:

- :func:`paged_attention_reference` — a pure-jnp gather over the block
  tables followed by masked softmax.  It IS the spec; the conformance
  suite pins the Pallas kernel against it on CPU.
- :func:`paged_attention` — a Pallas kernel whose grid walks
  ``(batch, page)`` with the block tables and sequence lengths in
  scalar-prefetch memory, so each grid step DMAs exactly one page, all
  heads of it (``pl.BlockSpec`` index maps read the block table to find
  it), and folds it head by head into a running online softmax held in
  VMEM scratch.  No
  per-sequence padding to a max length ever materializes.

Int8 KV pages ride through the PR-10 quantization seam: pages may be
``int8`` with per-(token, head) f32 scales produced by
``quant_kernels.quantize_tensor(axis=0)`` over rows of D; both
implementations widen with the identical ``q * scale`` dequant
(:func:`dequant_rows`), so int8 conformance is a pure rounding question,
never a tiling one.

Layout contract (shared with ``serving.decode.PagedKVCache``):

- ``q``            [B, H, D]         one decode token per sequence
- ``k_pages``      [P, page, H, D]   f32/bf16, or int8 with scales
- ``v_pages``      [P, page, H, D]
- ``k_scales``     [P, page, H]      f32 (int8 pages only)
- ``v_scales``     [P, page, H]
- ``block_tables`` [B, max_pages]    int32; slots past a sequence's last
                                     page MUST hold a valid index (0) so
                                     the skipped DMAs stay in bounds
- ``seq_lens``     [B]               int32, >= 1

The TileConfig enters at cache-construction time: ``block_kv`` is the
page size the serving layer allocates (the autotuner's knob), so the
kernel's KV tile is the page itself and the grid follows the block table.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops.pallas.tiles import DEFAULT_TILES, TileConfig


#: Matches ops.attention_kernels.NEG_INF — masked logits, not -jnp.inf,
#: so fully-masked tails stay NaN-free.
NEG_INF = -1e30

_KV_DTYPES = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def dequant_rows(x, scales, dtype=jnp.float32):
    """Widen int8 KV rows with their per-(token, head) scales: the exact
    inverse of ``quantize_tensor(rows, axis=0)``.  Shared by the kernel
    body and the reference so both dequantize identically.

    ``x`` [..., D] int8 (or float — then this is a plain cast),
    ``scales`` [...] broadcast over D.
    """
    x = x.astype(dtype)
    if scales is not None:
        x = x * scales.astype(dtype)[..., None]
    return x


# ---------------------------------------------------------------------------
# Reference — the spec
# ---------------------------------------------------------------------------


def paged_attention_reference(q, k_pages, v_pages, block_tables, seq_lens,
                              scale=None, k_scales=None, v_scales=None,
                              **_ignored):
    """Gather each sequence's pages per its block table, run masked
    attention over the reconstructed history.  Pure jnp; f32 math."""
    B, H, D = q.shape
    page = k_pages.shape[1]
    sm = (1.0 / math.sqrt(D)) if scale is None else float(scale)
    k = dequant_rows(k_pages, k_scales)           # [P, page, H, D] f32
    v = dequant_rows(v_pages, v_scales)
    max_pages = block_tables.shape[1]
    L = max_pages * page
    kg = k[block_tables].reshape(B, L, H, D).transpose(0, 2, 1, 3)
    vg = v[block_tables].reshape(B, L, H, D).transpose(0, 2, 1, 3)
    s = jnp.einsum("bhd,bhld->bhl", q.astype(jnp.float32), kg) * sm
    pos = jnp.arange(L)[None, None, :]            # [1, 1, L]
    valid = pos < seq_lens.astype(jnp.int32)[:, None, None]
    s = jnp.where(valid, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhl,bhld->bhd", w, vg)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _paged_kernel(bt_ref, sl_ref, q_ref, k_ref, v_ref, *rest,
                  sm_scale, quantized):
    if quantized:
        ks_ref, vs_ref, out_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        out_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)
    page, H = k_ref.shape[1], k_ref.shape[2]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    seq_len = sl_ref[b]
    start = p * page

    @pl.when(start < seq_len)
    def _accumulate():
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        live = start + idx < seq_len                       # (1, page)
        for h in range(H):             # static: one page serves every head
            qv = q_ref[0, h:h + 1, :].astype(jnp.float32)  # (1, D)
            kb = dequant_rows(k_ref[0, :, h, :],           # (page, D)
                              ks_ref[0, :, h] if quantized else None)
            vb = dequant_rows(v_ref[0, :, h, :],
                              vs_ref[0, :, h] if quantized else None)
            s = jnp.dot(qv, kb.T,
                        preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(live, s, NEG_INF)                # (1, page)
            m_prev = m_ref[h:h + 1, :]                     # (1, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            w = jnp.exp(s - m_new)                         # (1, page)
            acc_ref[h:h + 1, :] = acc_ref[h:h + 1, :] * corr + jnp.dot(
                w, vb, preferred_element_type=jnp.float32)
            l_ref[h:h + 1, :] = (l_ref[h:h + 1, :] * corr
                                 + jnp.sum(w, axis=-1, keepdims=True))
            m_ref[h:h + 1, :] = m_new

    @pl.when(p == n_pages - 1)
    def _finalize():
        norm = jnp.maximum(l_ref[...], 1e-37)              # seq_len >= 1
        out_ref[0] = (acc_ref[...] / norm).astype(out_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    scale=None, k_scales=None, v_scales=None,
                    tile: Optional[TileConfig] = None,
                    interpret: bool = False):
    """Paged-KV decode attention: one query token per sequence against a
    block-table-addressed page pool.  Output [B, H, D] in q's dtype."""
    tile = tile or DEFAULT_TILES["paged_attention"]
    B, H, D = q.shape
    P, page, _, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    sm = (1.0 / math.sqrt(D)) if scale is None else float(scale)
    quantized = k_scales is not None
    block_tables = block_tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)

    # Blocks span all H heads: Mosaic wants the last two block dims equal
    # to the array's (or multiples of the 8x128 tile), and H is second-minor
    # in q [B, H, D], the pages [P, page, H, D] and the scales [P, page, H].
    def page_map(b, p, bt, sl):
        return (bt[b, p], 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, H, D), lambda b, p, bt, sl: (b, 0, 0)),
        pl.BlockSpec((1, page, H, D), page_map),
        pl.BlockSpec((1, page, H, D), page_map),
    ]
    args = [q, k_pages, v_pages]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, page, H), lambda b, p, bt, sl: (bt[b, p], 0, 0)),
            pl.BlockSpec((1, page, H), lambda b, p, bt, sl: (bt[b, p], 0, 0)),
        ]
        args += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, D), lambda b, p, bt, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, D), jnp.float32),   # online-softmax accumulator
            pltpu.VMEM((H, 1), jnp.float32),   # running max
            pltpu.VMEM((H, 1), jnp.float32),   # running normalizer
        ],
    )
    kernel = functools.partial(_paged_kernel, sm_scale=sm,
                               quantized=quantized)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )(block_tables, seq_lens, *args)


# ---------------------------------------------------------------------------
# Dispatch predicates
# ---------------------------------------------------------------------------


def paged_supports(q, k_pages, v_pages, block_tables, seq_lens,
                   scale=None, k_scales=None, v_scales=None,
                   **kw) -> bool:
    """Hard constraints only — forced-pallas mode must work on the small
    shapes the conformance suite uses."""
    if getattr(q, "ndim", 0) != 3 or getattr(k_pages, "ndim", 0) != 4:
        return False
    if jnp.dtype(q.dtype) not in _KV_DTYPES:
        return False
    if k_pages.shape != v_pages.shape:
        return False
    B, H, D = q.shape
    if k_pages.shape[2] != H or k_pages.shape[3] != D:
        return False
    if jnp.dtype(k_pages.dtype) == jnp.dtype(jnp.int8):
        if k_scales is None or v_scales is None:
            return False
        if k_scales.shape != k_pages.shape[:3]:
            return False
    elif jnp.dtype(k_pages.dtype) != jnp.dtype(q.dtype):
        return False
    if getattr(block_tables, "ndim", 0) != 2 or block_tables.shape[0] != B:
        return False
    if getattr(seq_lens, "ndim", 0) != 1 or seq_lens.shape[0] != B:
        return False
    return True


def paged_profitable(q, k_pages, v_pages, block_tables, seq_lens,
                     **kw) -> bool:
    """Auto-mode heuristics: the gather kernel pays off once a sequence's
    reconstructed history is long enough that XLA's dense gather path
    would materialize a large padded [B, L, H, D] intermediate."""
    D = q.shape[2]
    page = k_pages.shape[1]
    return D % 64 == 0 and block_tables.shape[1] * page >= 1024
