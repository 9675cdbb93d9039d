"""Hand-fused Pallas kernel tier (cuDNN-style primitive catalog).

Every kernel here ships two implementations — a Pallas TPU/GPU kernel
parameterized by a :class:`TileConfig` and a pure-jnp reference that is
the definition of correctness — selected by ``dispatch``: Pallas on
accelerators, reference on CPU, so tier-1 stays green under
``JAX_PLATFORMS=cpu``.  Tile schedules are searched by
``compile/autotune.py``'s ``TileAutotuner``, persisted per device kind +
shape class, and folded into AOT cache keys via
``compile/fingerprint.kernel_tier_fingerprint``.

Importing this package registers the kernel set; call sites go through
``dispatch.resolve`` and never import kernel modules directly.
"""
from deeplearning4j_tpu.ops.pallas import (attention, delta_rule, dispatch,
                                           grouped_matmul, matmul,
                                           paged_attention, sparse_index,
                                           tiles)
from deeplearning4j_tpu.ops.pallas.tiles import (  # noqa: F401
    DEFAULT_TILES,
    TILE_FORMAT,
    TILE_GRID_DIMS,
    TILE_SPACES,
    TileConfig,
    shape_class,
)

dispatch.register(
    "attention",
    pallas_fn=attention.flash_attention,
    reference_fn=attention.attention_reference,
    supports=attention.attention_supports,
    profitable=attention.attention_profitable,
)
dispatch.register(
    "paged_attention",
    pallas_fn=paged_attention.paged_attention,
    reference_fn=paged_attention.paged_attention_reference,
    supports=paged_attention.paged_supports,
    profitable=paged_attention.paged_profitable,
)
dispatch.register(
    "int8_matmul",
    pallas_fn=matmul.int8_matmul,
    reference_fn=matmul.int8_matmul_reference,
    supports=matmul.int8_supports,
    profitable=matmul.int8_profitable,
)
dispatch.register(
    "q_matmul",
    pallas_fn=matmul.q_matmul,
    reference_fn=matmul.q_matmul_reference,
    supports=matmul.q_supports,
    profitable=matmul.q_profitable,
)
dispatch.register(
    "fused_dense",
    pallas_fn=matmul.fused_dense,
    reference_fn=matmul.fused_dense_reference,
    supports=matmul.dense_supports,
    profitable=matmul.dense_profitable,
)
dispatch.register(
    "grouped_matmul",
    pallas_fn=grouped_matmul.grouped_matmul,
    reference_fn=grouped_matmul.grouped_matmul_reference,
    supports=grouped_matmul.grouped_supports,
    profitable=grouped_matmul.grouped_profitable,
)
# one answer for the tier's kernels: `ops/sparse_index.py` asks once a call
# (`pack_by_key` alone also asks `pack_by_key_supports` for its words' tiles)
dispatch.register(
    "sparse_index",
    pallas_fn=sparse_index.index_scores,
    reference_fn=sparse_index.index_scores_reference,
    supports=sparse_index.index_supports,
)
# the delta rule of `ops/linear_attention.py`: the chunks' insides and the
# recurrence across them, forward and backward, by one answer
dispatch.register(
    "delta_rule",
    pallas_fn=delta_rule.across_chunks,
    reference_fn=delta_rule.across_chunks_reference,
)

__all__ = [
    "attention",
    "delta_rule",
    "dispatch",
    "grouped_matmul",
    "matmul",
    "paged_attention",
    "sparse_index",
    "tiles",
    "TileConfig",
    "DEFAULT_TILES",
    "TILE_SPACES",
    "TILE_GRID_DIMS",
    "TILE_FORMAT",
    "shape_class",
]
