"""AOT-cached jitted step functions for the training hot loops.

`jax.jit` keeps its executable cache in-process: a preempted
FaultTolerantTrainer restart, an elastic re-launch, or plain `python
train.py` again re-traces and re-compiles the donated train step from
scratch — routinely the longest stall in a restart.  `step_function()`
wraps a step body so that first-call compilation goes through a
`PersistentExecutableCache`: the lowered program is compiled once per
(model fingerprint, argument signature) *ever* and deserialized on every
later process start.

Dispatch cost: the wrapper keys its in-memory table on the argument
signature.  Hashing the full argument pytree every step would walk
hundreds of parameter leaves, so callers split the signature —
`dynamic_argnums` names the arguments whose shapes/dtypes can change
between calls (the data batch, masks); everything else (params, state,
opt state, rng, counters) is hashed once on first call and assumed
stable, which holds because every step-shape-changing event in this
codebase (set_normalizer, zero1 toggles, graph mutation) rebuilds the
step function anyway.  A signature the table has never seen falls through
to the same lower→compile→persist path, exactly like `jax.jit` retracing.

When no cache is configured the wrapper *is* `jax.jit` (same object,
zero overhead), so the persistent layer stays strictly opt-in.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from deeplearning4j_tpu.compile.fingerprint import (args_signature,
                                                    signature_json)
from deeplearning4j_tpu.compile.persistent import PersistentExecutableCache


class AotStepFunction:
    """Callable wrapping `jax.jit(body, donate_argnums=...)` with a
    persistent executable tier.  Exposes `_cache_size()` (count of actual
    trace+compile events, NOT disk hits) so monitor's compile detection
    keeps reporting real compiles."""

    def __init__(self, body: Callable, *, donate_argnums: Tuple[int, ...],
                 key_base: Callable[[], Dict[str, Any]],
                 cache: PersistentExecutableCache,
                 dynamic_argnums: Sequence[int] = ()):
        import jax
        self._jit = jax.jit(body, donate_argnums=tuple(donate_argnums))
        self._cache = cache
        self._key_base = key_base
        self._dynamic = tuple(dynamic_argnums)
        self._static_sig = None          # signature of the stable args
        self._table: Dict[Any, Any] = {}  # full sig -> executable
        self._n_compiles = 0
        self._donate = tuple(donate_argnums)

    def _split_sig(self, args) -> Tuple[Any, Any]:
        dyn = tuple(args[i] for i in self._dynamic if i < len(args))
        dyn_sig = args_signature(dyn)
        if self._static_sig is None:
            static = tuple(a for i, a in enumerate(args)
                           if i not in self._dynamic)
            self._static_sig = args_signature(static)
        return self._static_sig, dyn_sig

    def __call__(self, *args):
        static_sig, dyn_sig = self._split_sig(args)
        sig = (static_sig, dyn_sig)
        fn = self._table.get(sig)
        if fn is None:
            parts = dict(self._key_base())
            parts["donate_argnums"] = list(self._donate)
            parts["dynamic_argnums"] = list(self._dynamic)
            parts["static_args"] = signature_json(static_sig)
            parts["dynamic_args"] = signature_json(dyn_sig)
            fn, source = self._cache.get_or_compile(
                parts, lambda: self._jit.lower(*args).compile())
            if source == "compiled":
                self._n_compiles += 1
            self._table[sig] = fn
        return fn(*args)

    def trace(self, *args):
        """`jax.jit(body).trace`: what `monitor.note_step` asks of a step
        function."""
        return self._jit.trace(*args)

    def _cache_size(self) -> int:
        """Actual compile events (monitor.check_compile contract); a disk
        hit deserializes without compiling and does not count."""
        return self._n_compiles

    @property
    def executables(self) -> Dict[Any, Any]:
        return self._table


def step_function(body: Callable, *, donate_argnums: Tuple[int, ...] = (),
                  key_base: Optional[Callable[[], Dict[str, Any]]] = None,
                  cache: Optional[PersistentExecutableCache] = None,
                  dynamic_argnums: Sequence[int] = ()):
    """The step-builder entry point: returns plain `jax.jit(body, ...)`
    when no persistent cache is in play, otherwise an `AotStepFunction`
    bridging compilation through the cache.  `key_base` is a zero-arg
    callable (evaluated lazily, at first dispatch) producing the model/
    config fingerprint parts of the disk key."""
    import jax
    if cache is None or key_base is None:
        return jax.jit(body, donate_argnums=tuple(donate_argnums))
    return AotStepFunction(body, donate_argnums=tuple(donate_argnums),
                           key_base=key_base, cache=cache,
                           dynamic_argnums=dynamic_argnums)
