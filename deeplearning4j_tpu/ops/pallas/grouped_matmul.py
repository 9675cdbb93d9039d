"""Grouped matrix product for the fused-kernel tier: the experts' products
of an expert layer (`ops/moe.py`).

`lhs` [M, K] holds rows sorted by group, `group_sizes` [G] says how many
rows each group has (they may sum to fewer than M: the rows that follow
belong to no group), `rhs` [G, K, N] one matrix a group:

    out[r] = lhs[r] @ rhs[g(r)]   for rows of a group,   0 for the rest.

The number of rows a group gets is data, not shape, so nothing here
compiles again when the routing changes, and no row is dropped.

- Pallas: the grouped kernels jax ships (`jax.experimental.pallas.ops.tpu
  .megablox`): `gmm` walks the row tiles each group touches (a grid whose
  length is data), `tgmm` is the weights' gradient.  They leave the rows of
  no group unwritten; `grouped_matmul` zeroes them, in the forward and in
  both gradients, so whatever the unwritten memory held never reaches a sum.
- reference: `jax.lax.ragged_dot`, the definition of correctness.
"""
from __future__ import annotations

import importlib
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.pallas.tiles import DEFAULT_TILES, TileConfig


def grouped_matmul_reference(lhs, rhs, group_sizes):
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=lhs.dtype)


def _backend():
    # the package's `__init__` shadows the module `gmm` with the function
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _tiling(tile: TileConfig, m: int, k: int, n: int):
    return (min(tile.block_m, m), min(tile.block_k, k), min(tile.block_n, n))


def _zero_tail(out, group_sizes):
    rows = jax.lax.broadcasted_iota(jnp.int32, (out.shape[0], 1), 0)
    return jnp.where(rows < jnp.sum(group_sizes), out, 0)


def _forward(lhs, rhs, group_sizes, tile: TileConfig, interpret: bool):
    m, k = lhs.shape
    out = _backend().gmm(lhs, rhs, group_sizes, lhs.dtype,
                         _tiling(tile, m, k, rhs.shape[2]),
                         interpret=interpret)
    return _zero_tail(out, group_sizes)


_gmm = jax.custom_vjp(_forward, nondiff_argnums=(3, 4))


def _gmm_fwd(lhs, rhs, group_sizes, tile, interpret):
    return (_forward(lhs, rhs, group_sizes, tile, interpret),
            (lhs, rhs, group_sizes))


def _gmm_bwd(tile, interpret, res, g):
    lhs, rhs, group_sizes = res
    m, k = lhs.shape
    n = rhs.shape[2]
    mb = _backend()
    d_lhs = mb.gmm(g, rhs, group_sizes, lhs.dtype, _tiling(tile, m, n, k),
                   transpose_rhs=True, interpret=interpret)
    d_rhs = mb.tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                    _tiling(tile, m, k, n), num_actual_groups=rhs.shape[0],
                    interpret=interpret)
    return _zero_tail(d_lhs, group_sizes), d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes, tile: Optional[TileConfig] = None,
                   interpret: bool = False):
    """Pallas lowering; the rows are padded up to a whole row tile (the
    padding belongs to no group).  Differentiable in `lhs` and `rhs`."""
    tile = tile or DEFAULT_TILES["grouped_matmul"]
    m = lhs.shape[0]
    bm = min(tile.block_m, m)
    pad = -m % bm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _gmm(lhs, rhs, group_sizes.astype(jnp.int32), tile, interpret)
    return out[:m] if pad else out


def grouped_supports(lhs, rhs, group_sizes, **kw) -> bool:
    """Hard constraints: 2-D rows, one [K, N] matrix a group, one float
    dtype, and K and N that the kernels' 128-wide tiles divide."""
    if getattr(lhs, "ndim", 0) != 2 or getattr(rhs, "ndim", 0) != 3:
        return False
    if lhs.dtype != rhs.dtype or jnp.dtype(lhs.dtype) not in (
            jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    if rhs.shape[0] != group_sizes.shape[0] or rhs.shape[1] != lhs.shape[1]:
        return False
    return lhs.shape[1] % 128 == 0 and rhs.shape[2] % 128 == 0


def grouped_profitable(lhs, rhs, group_sizes, **kw) -> bool:
    """On the chip the reference computes every group over every row tile;
    the kernel is the only lowering whose work follows the routing."""
    return True
