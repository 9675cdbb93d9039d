"""Buffer-lifetime sanitizers — the TPU equivalent of the reference's
workspace-misuse validation (SURVEY.md §5.2: `LayerWorkspaceMgr` asserts
arrays come from the expected workspace; `NotReleasedWorkspaceException`).

Under XLA the corresponding failure class is *donation misuse*: every
compiled train step donates its params/state/opt-state buffers
(`donate_argnums`), so any alias of those arrays held elsewhere — a second
network sharing transplanted params, a stored "best model" snapshot, a
listener keeping a reference — becomes a deleted buffer after the next
`fit()`.  jax's own error ("Array has been deleted") carries no context
about *which* model/leaf was hit or why.  These helpers give the named,
early error the reference's workspace validation gave.

Used by transfer learning and early stopping (the two donation-aliasing
bug sites fixed in round 2) and available as a public guard.
"""
from __future__ import annotations

from typing import Any, Iterable, Tuple

import jax


class BufferValidationError(RuntimeError):
    """Raised when a pytree holds deleted (donated-away) or cross-shared
    device buffers (reference analogue: NotReleasedWorkspaceException)."""


def _leaves_with_paths(tree: Any) -> Iterable[Tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        yield jax.tree_util.keystr(path), leaf


def assert_live(tree: Any, context: str = "pytree") -> None:
    """Raise BufferValidationError naming every deleted leaf in `tree`.

    A leaf is deleted when a jitted step donated its buffer (XLA reused the
    HBM) while this reference survived — the use-after-donation race the
    reference guards against with workspace validation.
    """
    dead = [p for p, leaf in _leaves_with_paths(tree)
            if isinstance(leaf, jax.Array) and leaf.is_deleted()]
    if dead:
        raise BufferValidationError(
            f"{context}: {len(dead)} leaf buffer(s) were donated to a "
            f"compiled step and deleted: {dead[:5]}"
            f"{' …' if len(dead) > 5 else ''}. Copy leaves before sharing "
            "them across networks (jax.tree_util.tree_map(jnp.copy, ...)) "
            "or re-load from a checkpoint.")


def _buffer_ids(tree: Any) -> dict:
    out = {}
    for p, leaf in _leaves_with_paths(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            try:
                out[leaf.unsafe_buffer_pointer()] = p
            except Exception:   # sharded/committed arrays: fall back to id
                out[id(leaf)] = p
    return out


def assert_disjoint(tree_a: Any, tree_b: Any,
                    context: str = "trees") -> None:
    """Raise if two pytrees share any device buffer.

    Donation makes silent sharing fatal: when one network's step donates a
    buffer the other network still references, the second network dies on
    its next use.  Transfer learning / model-saver code paths must deep-copy
    (the round-1 bug class); this guard catches regressions.
    """
    ids_a = _buffer_ids(tree_a)
    shared = [(pa, ids_a[ptr]) for ptr, pa in _buffer_ids(tree_b).items()
              if ptr in ids_a]
    if shared:
        pairs = ", ".join(f"{b}≡{a}" for b, a in shared[:5])
        raise BufferValidationError(
            f"{context}: {len(shared)} device buffer(s) shared between the "
            f"two trees ({pairs}{' …' if len(shared) > 5 else ''}); a "
            "donating train step on either side will delete the other's "
            "params. Deep-copy on transplant.")


def validate_network(net: Any, context: str = None) -> None:
    """Check a MultiLayerNetwork / ComputationGraph / SameDiff-like object's
    device state (params_, state_, opt_state_ / variables_) for deleted
    buffers."""
    name = context or type(net).__name__
    for attr in ("params_", "state_", "opt_state_", "variables_"):
        tree = getattr(net, attr, None)
        if tree is not None:
            assert_live(tree, f"{name}.{attr}")
