"""Single dispatch layer for the fused-kernel tier.

Every kernel in ``ops/pallas`` ships two implementations: a Pallas kernel
parameterized by a :class:`~deeplearning4j_tpu.ops.pallas.tiles.TileConfig`
and a pure-jnp reference that is the definition of correctness.  Call sites
ask this module which implementation to run; the answer depends on what
it can observe — the platform, the operands' dtypes and shapes — through:

* the kernel's registration — a kernel registered with ``pallas_fn=None``
  is reference-only, whatever the mode,
* the dispatch mode — ``auto`` (Pallas on TPU/GPU when the kernel's
  support *and* profitability predicates pass, reference everywhere else),
  ``pallas`` (force Pallas wherever the hard support predicate allows;
  on CPU the kernel runs in interpret mode, which is how the conformance
  suite pins ``pallas == reference``), or ``reference`` (force the jnp
  lowering),
* the kernel's own predicates, registered alongside its implementations.

Once the answer is ``pallas`` the call site runs the kernel and a failure
in it propagates: nothing here or at the call sites catches it.

The mode comes from ``DL4J_TPU_KERNEL_TIER`` or :func:`set_dispatch_mode`.
The module also owns the in-process tile table (installed by the autotuner
or loaded from the persisted store) and exposes
:func:`kernel_tier_fingerprint` so ``compile/fingerprint.py`` can fold the
tier configuration into AOT cache keys — a tile change can never collide
with a stale executable.
"""
from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax

from deeplearning4j_tpu.ops.pallas.tiles import DEFAULT_TILES, TileConfig

_MODES = ("auto", "pallas", "reference")

_lock = threading.Lock()


def _env_mode() -> str:
    mode = os.environ.get("DL4J_TPU_KERNEL_TIER", "auto")
    if mode not in _MODES:
        raise ValueError(
            f"DL4J_TPU_KERNEL_TIER={mode!r}; want one of {_MODES}")
    return mode


_mode: str = _env_mode()


def on_accelerator() -> bool:
    return jax.default_backend() in ("tpu", "gpu")


def interpret_mode() -> bool:
    """Whether a forced-Pallas kernel must run under ``interpret=True``."""
    return not on_accelerator()


def dispatch_mode() -> str:
    return _mode


def set_dispatch_mode(mode: str) -> str:
    """Set the tier mode; returns the previous mode (for try/finally)."""
    global _mode
    if mode not in _MODES:
        raise ValueError(f"unknown kernel-tier mode {mode!r}; want one of {_MODES}")
    with _lock:
        prev, _mode = _mode, mode
    return prev


@dataclass
class KernelSpec:
    name: str
    pallas_fn: Optional[Callable[..., Any]]
    reference_fn: Callable[..., Any]
    #: hard correctness constraints — gate both auto and forced-pallas modes
    supports: Optional[Callable[..., bool]] = None
    #: perf heuristics — gate auto mode only, so forced mode stays testable
    #: on shapes too small to be profitable
    profitable: Optional[Callable[..., bool]] = None


_registry: Dict[str, KernelSpec] = {}
_tiles: Dict[str, TileConfig] = {}
#: KV-cache storage dtype of the decode engine ("f32" / "bf16" / "int8").
#: Part of program identity: an int8-KV decode step traces a different
#: program (in-kernel dequant) than an f32-KV one, so the fingerprint
#: must split them or the AOT cache would serve a stale executable.
_kv_dtype: str = "f32"


def register(
    name: str,
    pallas_fn: Optional[Callable[..., Any]],
    reference_fn: Callable[..., Any],
    supports: Optional[Callable[..., bool]] = None,
    profitable: Optional[Callable[..., bool]] = None,
) -> None:
    _registry[name] = KernelSpec(name, pallas_fn, reference_fn, supports, profitable)


def kernels() -> Dict[str, KernelSpec]:
    return dict(_registry)


def resolve(name: str, *args: Any, **kwargs: Any) -> str:
    """Pick ``"pallas"`` or ``"reference"`` for one call and record it."""
    spec = _registry.get(name)
    impl = "reference"
    if spec is not None and spec.pallas_fn is not None:
        mode = _mode
        if mode != "reference":
            ok = spec.supports is None or bool(spec.supports(*args, **kwargs))
            if ok and mode == "auto":
                ok = on_accelerator() and (
                    spec.profitable is None or bool(spec.profitable(*args, **kwargs))
                )
            if ok:
                impl = "pallas"
    _record(name, impl)
    return impl


def _record(name: str, impl: str) -> None:
    try:
        from deeplearning4j_tpu.monitor.instrument import ops_instruments

        ops_instruments().record_dispatch(name, impl)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Tile table
# ---------------------------------------------------------------------------


def set_tile(kernel: str, cfg: TileConfig, shape_class: Optional[str] = None) -> None:
    key = f"{kernel}/{shape_class}" if shape_class else kernel
    with _lock:
        _tiles[key] = cfg


def get_tile(kernel: str, shape_class: Optional[str] = None) -> TileConfig:
    """Most specific installed tile: shape-class entry > kernel-wide > default."""
    if shape_class is not None:
        cfg = _tiles.get(f"{kernel}/{shape_class}")
        if cfg is not None:
            return cfg
    cfg = _tiles.get(kernel)
    if cfg is not None:
        return cfg
    return DEFAULT_TILES.get(kernel, TileConfig())


def install_tile_table(table: Dict[str, TileConfig]) -> None:
    with _lock:
        _tiles.update(table)


def tile_table() -> Dict[str, TileConfig]:
    return dict(_tiles)


def clear_tiles() -> None:
    with _lock:
        _tiles.clear()


def set_kv_dtype(dtype: str) -> str:
    """Install the decode KV-cache dtype ("f32"/"bf16"/"int8") into the
    tier fingerprint; returns the previous value (for try/finally)."""
    global _kv_dtype
    with _lock:
        prev, _kv_dtype = _kv_dtype, str(dtype)
    return prev


def kv_dtype() -> str:
    return _kv_dtype


def reset() -> None:
    """Test hook: restore env-derived mode and drop installed tiles."""
    global _mode, _kv_dtype
    with _lock:
        _mode = _env_mode()
        _tiles.clear()
        _kv_dtype = "f32"


def kernel_tier_fingerprint() -> Dict[str, Any]:
    """Stable description of the tier config, folded into AOT cache keys.

    Distinguishes reference programs from Pallas-default programs from
    autotuned-tile programs: any change in mode, any installed tile, or
    the decode KV-cache dtype changes the fingerprint
    (an f32-KV and an int8-KV decode program never share an AOT entry).
    """
    return {
        "mode": _mode,
        "tiles": {k: cfg.to_json() for k, cfg in sorted(_tiles.items())},
        "kv_dtype": _kv_dtype,
    }
