"""Compressed gradient exchange for DCN/multi-slice hops.

Reference: `EncodedGradientsAccumulator` + Aeron publish/receive
(SURVEY.md §3.4): async threshold-quantized deltas between nodes.  On TPU
the intra-slice path is XLA all-reduce over ICI (never compressed); this
module keeps the reference's compression capability for the slow
cross-slice/DCN hop, as a HOST-side exchange: encode locally (C++ codec),
ship the sparse stream over whatever transport links slices (the launcher's
job), decode+apply remotely.  Synchronous-apply semantics — the async
staleness of the reference is deliberately dropped (north star).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import numpy as np

from deeplearning4j_tpu.native_ops import ThresholdCodec


class CompressedGradientExchange:
    """Per-leaf threshold codecs over a gradient pytree."""

    def __init__(self, params_template, threshold: float = 1e-3,
                 adaptive_target_density: float = 1e-2):
        leaves, self._treedef = jax.tree_util.tree_flatten(params_template)
        self._shapes = [np.shape(l) for l in leaves]
        self.codecs: List[ThresholdCodec] = [
            ThresholdCodec(int(np.prod(s) or 1), threshold) for s in
            self._shapes]
        self.target_density = adaptive_target_density

    def encode(self, grads) -> List[np.ndarray]:
        """Pytree -> list of sparse int32 streams (residuals carried).

        Adaptive threshold (the ResidualPostProcessor role) adjusts AFTER
        each encode from the emitted stream's density — no second scan of
        the gradient."""
        leaves = jax.tree_util.tree_leaves(grads)
        out = []
        self._used_thresholds = []
        for codec, leaf in zip(self.codecs, leaves):
            self._used_thresholds.append(codec.threshold)
            stream = codec.encode(np.asarray(leaf))
            out.append(stream)
            d = len(stream) / codec.size
            if d > 2 * self.target_density:
                codec.threshold *= 1.2
            elif d < self.target_density / 2 and codec.threshold > 1e-6:
                codec.threshold /= 1.2
        return out

    def thresholds(self) -> List[float]:
        """Thresholds USED by the most recent encode (what decode needs)."""
        return getattr(self, "_used_thresholds",
                       [c.threshold for c in self.codecs])

    def decode(self, streams: List[np.ndarray],
               thresholds: Optional[List[float]] = None):
        """Sparse streams -> dense gradient pytree.  `thresholds` defaults
        to the most recent encode's ONLY when None — an explicit (possibly
        empty, for a zero-leaf tree) list is honored as given, and the
        per-call threshold never mutates codec state, so a decode of peer
        streams can run concurrently with the next local encode."""
        if thresholds is None:
            thresholds = self.thresholds()
        dense = []
        for codec, enc, shape, thr in zip(self.codecs, streams,
                                          self._shapes, thresholds):
            dense.append(codec.decode(enc, threshold=thr).reshape(shape))
        return jax.tree_util.tree_unflatten(self._treedef, dense)

    def compression_ratio(self, streams: List[np.ndarray]) -> float:
        dense_bytes = sum(4 * int(np.prod(s) or 1) for s in self._shapes)
        sparse_bytes = sum(4 * (len(s) + 1) for s in streams)
        return dense_bytes / max(sparse_bytes, 1)

    # ---- error-feedback residual management (elastic gang support) ----
    def residuals(self) -> List[np.ndarray]:
        """Per-leaf error-feedback residuals (live views, not copies)."""
        return [c.residual for c in self.codecs]

    def residual_norm(self) -> float:
        """Total l2 mass currently parked in error-feedback residuals —
        the gradient signal a membership change would strand."""
        return float(np.sqrt(sum(float(np.dot(c.residual, c.residual))
                                 for c in self.codecs)))

    def reset_residuals(self) -> None:
        """Zero the error-feedback state.  Used when a gang reformation
        rewinds to a checkpoint: the parked residual was accumulated from
        steps the rewind discards, so flushing it would double-count
        gradient mass the resumed run will recompute."""
        for c in self.codecs:
            c.residual[:] = 0.0

    def take_residuals(self) -> List[np.ndarray]:
        """Detach and return the residuals, zeroing the codec state.  A
        forward (non-rewind) membership change carries these into the
        next exchange via `flush_into` so no gradient mass is silently
        lost."""
        out = [c.residual.copy() for c in self.codecs]
        self.reset_residuals()
        return out

    def flush_into(self, residuals: List[np.ndarray]) -> None:
        """Add previously taken residuals into this exchange's codecs so
        the next encode emits them (shape-checked leafwise)."""
        for c, r in zip(self.codecs, residuals):
            if r.shape != c.residual.shape:
                raise ValueError(
                    f"residual shape {r.shape} != codec {c.residual.shape}")
            c.residual += r.astype(np.float32, copy=False)


def allreduce_compressed(exchange: CompressedGradientExchange,
                         transport, grads):
    """Sum a gradient pytree across ranks through the compressed path:
    encode locally (residuals carried), all-gather the sparse streams over
    `transport` (a `transport.TcpGradientMesh`), decode every rank's stream,
    sum dense.  This is the reference's EncodedGradientsAccumulator
    apply-peer-updates loop made synchronous (SURVEY.md §3.4 north star)."""
    from deeplearning4j_tpu.parallel.transport import (pack_streams,
                                                       unpack_streams)
    streams = exchange.encode(grads)
    payload = pack_streams(streams, exchange.thresholds())
    total = None
    for peer_payload in transport.allgather(payload):
        peer_streams, peer_thr = unpack_streams(peer_payload)
        dense = exchange.decode(peer_streams, peer_thr)
        total = dense if total is None else jax.tree_util.tree_map(
            lambda a, b: a + b, total, dense)
    return total


def allreduce_dense(transport, grads):
    """Sum a gradient pytree across ranks shipping FULL-PRECISION f32
    leaves — the uncompressed baseline the threshold path is compared
    against.  Same star all-gather, no codec, no residuals; bytes on wire
    scale with the dense parameter count."""
    from deeplearning4j_tpu.parallel.transport import (pack_dense,
                                                       unpack_dense)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    payload = pack_dense([np.asarray(l) for l in leaves])
    total = None
    for peer_payload in transport.allgather(payload):
        peer = unpack_dense(peer_payload)
        total = peer if total is None else [a + b
                                            for a, b in zip(total, peer)]
    return jax.tree_util.tree_unflatten(treedef, total)
