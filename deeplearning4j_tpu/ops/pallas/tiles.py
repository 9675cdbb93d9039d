"""Tile schedules for the hand-fused Pallas kernel tier.

A :class:`TileConfig` is the unit the autotuner searches over and the unit
the tile store persists: one frozen record of the block sizes a kernel is
launched with.  Kernels read only the fields they care about (attention uses
``block_q``/``block_kv``, matmul-family kernels use ``block_m``/``block_n``/
``block_k``), so a single config type can describe every kernel in the tier
and round-trip through one JSON table.

Shape classes bucket concrete operand shapes into pow2 classes so a tuned
tile generalises across nearby shapes instead of being keyed to one exact
problem size (the TVM-style "schedule per workload class" idea, mirrored
from the step-level ``ScheduleAutotuner``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Tuple

TILE_FORMAT = "deeplearning4j_tpu.tiles.v1"


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """Block sizes for one fused-kernel launch.

    Attention kernels consume ``block_q``/``block_kv``; matmul-family
    kernels consume ``block_m``/``block_n``/``block_k``.  Unused fields are
    carried along untouched so one config can be stored per kernel name.
    """

    block_q: int = 512
    block_kv: int = 1024
    block_m: int = 256
    block_n: int = 256
    block_k: int = 512

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, obj: Dict[str, Any]) -> "TileConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: int(v) for k, v in obj.items() if k in fields})

    def config_key(self) -> str:
        return (
            f"q{self.block_q}-kv{self.block_kv}-"
            f"m{self.block_m}-n{self.block_n}-k{self.block_k}"
        )

    def replace(self, **kw: int) -> "TileConfig":
        return dataclasses.replace(self, **kw)


#: Baseline tile per kernel.  The attention defaults mirror the block sizes
#: the pre-tier dispatcher picked (``_pick_block(T, 512)`` / ``(S, 1024)``),
#: so enabling the tier with no autotuning is behaviour-preserving.
DEFAULT_TILES: Dict[str, TileConfig] = {
    "attention": TileConfig(block_q=512, block_kv=1024),
    "int8_matmul": TileConfig(block_m=256, block_n=256, block_k=512),
    "q_matmul": TileConfig(block_m=256, block_n=256, block_k=512),
    "fused_dense": TileConfig(block_m=256, block_n=256, block_k=512),
    # decode attention: block_kv IS the KV page size the serving layer
    # allocates (one page per grid step), block_q is the single decode row
    "paged_attention": TileConfig(block_q=1, block_kv=16),
    # grouped (per-expert) products: whole expert widths fit one tile, so a
    # grid step is a few microseconds of MXU work; 256 rows because an
    # expert sees a few hundred rows a step
    "grouped_matmul": TileConfig(block_m=256, block_n=1024, block_k=1024),
}

#: Candidate values per tile dimension, per kernel.  Kept deliberately
#: small: the tile search is grid+greedy over these, and every entry is a
#: real compile+measure on hardware.
TILE_SPACES: Dict[str, Dict[str, List[int]]] = {
    "attention": {
        "block_q": [128, 256, 512],
        "block_kv": [256, 512, 1024, 2048],
    },
    "int8_matmul": {
        "block_m": [128, 256, 512],
        "block_n": [128, 256, 512],
        "block_k": [256, 512, 1024],
    },
    "q_matmul": {
        "block_m": [128, 256, 512],
        "block_n": [128, 256, 512],
        "block_k": [256, 512, 1024],
    },
    "fused_dense": {
        "block_m": [128, 256, 512],
        "block_n": [128, 256, 512],
        "block_k": [256, 512, 1024],
    },
    "paged_attention": {
        "block_kv": [8, 16, 32, 64, 128],
    },
    "grouped_matmul": {
        "block_m": [128, 256, 512],
        "block_n": [256, 512, 1024],
        "block_k": [256, 512, 1024],
    },
}

#: Dimensions swept by the coarse grid stage (the rest are greedy-refined).
TILE_GRID_DIMS: Dict[str, Tuple[str, ...]] = {
    "attention": ("block_q", "block_kv"),
    "int8_matmul": ("block_m", "block_n"),
    "q_matmul": ("block_m", "block_n"),
    "fused_dense": ("block_m", "block_n"),
    "paged_attention": ("block_kv",),
    "grouped_matmul": ("block_m", "block_n"),
}


def _pow2_bucket(n: int) -> int:
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


def shape_class(**dims: int) -> str:
    """Bucket concrete dims into a pow2 shape-class key, e.g. ``k512-m128-n1024``.

    Keys are sorted so call sites can pass dims in any order.
    """
    items = sorted(dims.items())
    return "-".join(f"{k}{_pow2_bucket(v)}" for k, v in items)


def iter_space(space: Dict[str, Iterable[int]]) -> List[Dict[str, int]]:
    """Cartesian product of a {dim: candidates} space as override dicts."""
    combos: List[Dict[str, int]] = [{}]
    for dim in sorted(space):
        combos = [
            {**combo, dim: int(v)} for combo in combos for v in space[dim]
        ]
    return combos
