"""Async end-to-end training input pipeline.

The compiled train step (`jax.jit` + donation + `lax.scan`) leaves three
host-side stalls in the steady-state loop, and this module removes all
three (once the step is compiled, the remaining wins are overlapping data
movement with compute and eliminating host round-trips):

1. **Device prefetch** — :class:`DevicePrefetchIterator` double/triple-
   buffers batches onto device with `jax.device_put` *ahead* of compute
   (bounded depth = backpressure; clean shutdown), layered on
   :class:`~deeplearning4j_tpu.data.iterators.AsyncDataSetIterator` so
   host ETL runs in a producer thread while staged transfers are in
   flight.
2. **On-device normalization** — :class:`DeviceNormalizer` replays a
   fitted host normalizer (`NormalizerStandardize` / `NormalizerMinMaxScaler`
   / `ImagePreProcessingScaler`) as a pure-jnp prologue folded into the
   jitted step body (`MultiLayerNetwork.set_normalizer`), so host ETL
   stops copying every batch through float64 statistics math.
3. **Device-staged fused blocks** — :func:`device_blocks` feeds
   `fit(iterator, fused_steps=k)` with `[k, batch, ...]` blocks stacked
   *on device* (`jnp.stack` over pre-staged per-batch arrays) instead of
   the old per-block host `np.stack` copy.

Everything here is backend-agnostic: on CPU the same code path runs; on
TPU `device_put` overlaps the H2D DMA with the previous step's compute.
"""
from __future__ import annotations

import collections
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.data.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.data.iterators import (AsyncDataSetIterator,
                                               DataSetIterator)
from deeplearning4j_tpu.data.normalizers import (ImagePreProcessingScaler,
                                                 NormalizerMinMaxScaler,
                                                 NormalizerStandardize)

Placement = Callable[[np.ndarray], jax.Array]


class ProducerError(RuntimeError):
    """The ETL producer thread failed.  Re-raised on the CONSUMER side of
    `DevicePrefetchIterator` with batch-position context and the original
    exception chained (`__cause__`) — a producer crash must fail the
    training loop loudly, never masquerade as a clean end of epoch."""


# ---------------------------------------------------------------------------
# On-device normalization
# ---------------------------------------------------------------------------

def _sub_div(shift, scale):
    """`(x - shift) / scale` with the stats fenced behind an
    `optimization_barrier` so they stay runtime values.  This is the one
    affine form XLA cannot re-round: a *constant* divisor is rewritten to
    multiply-by-reciprocal (the barrier blocks that), and mul+add pairs
    are FMA-contracted by CPU codegen (barriers do NOT survive to codegen,
    so the host normalizers canonicalize to this same sub/div form via
    `affine_stats()` instead — see data/normalizers.py)."""
    sh = jnp.asarray(np.asarray(shift, np.float32))
    sc = jnp.asarray(np.asarray(scale, np.float32))

    def apply(x):
        s0, s1 = lax.optimization_barrier((sh, sc))
        return (x.astype(jnp.float32) - s0) / s1
    return apply


class DeviceNormalizer:
    """A fitted host normalizer re-expressed as pure jnp ops.

    Instances are closed over by the jitted step body, so the statistics
    become on-device constants of the compiled executable and the apply
    runs fused with the forward pass — the host never touches the batch.
    The op order/dtypes mirror the host `transform` exactly so results are
    bitwise identical (asserted in tests/test_input_pipeline.py).
    """

    def __init__(self, apply_features, apply_labels=None):
        self._features = apply_features
        self._labels = apply_labels

    def apply_features(self, x):
        return self._features(x)

    def apply_labels(self, y):
        return y if (self._labels is None or y is None) else self._labels(y)

    @staticmethod
    def from_host(nz) -> "DeviceNormalizer":
        """Build from a *fitted* host normalizer; raises TypeError for
        kinds with no pure per-batch form (e.g. MultiNormalizer — compose
        per-input DeviceNormalizers instead).

        Every supported kind reduces to one `(x - shift) / scale` with f32
        stats shared bit-for-bit with the host `transform` (standardize
        already has that shape; minmax/image expose it via
        `affine_stats()`), so host and device outputs agree bitwise — see
        `_sub_div` for why this is the only rounding-stable affine form."""
        if isinstance(nz, DeviceNormalizer):
            return nz
        if isinstance(nz, NormalizerStandardize):
            if nz.mean is None:
                raise ValueError("normalizer is not fitted (call fit first)")
            feats = _sub_div(nz.mean, nz.std)
            labels = None
            if nz.fit_labels and nz.label_mean is not None:
                labels = _sub_div(nz.label_mean, nz.label_std)
            return DeviceNormalizer(feats, labels)
        if isinstance(nz, NormalizerMinMaxScaler):
            if nz.data_min is None:
                raise ValueError("normalizer is not fitted (call fit first)")
            shift, scale = nz.affine_stats()
            if scale is None:
                const = jnp.float32(nz.min_range)
                return DeviceNormalizer(
                    lambda x: jnp.full_like(x.astype(jnp.float32), const))
            return DeviceNormalizer(_sub_div(shift, scale))
        if isinstance(nz, ImagePreProcessingScaler):
            shift, scale = nz.affine_stats()
            if scale is None:
                const = jnp.float32(nz.a)
                return DeviceNormalizer(
                    lambda x: jnp.full_like(x.astype(jnp.float32), const))
            return DeviceNormalizer(_sub_div(shift, scale))
        raise TypeError(
            f"no on-device form for {type(nz).__name__}; supported: "
            "NormalizerStandardize, NormalizerMinMaxScaler, "
            "ImagePreProcessingScaler (or pass a DeviceNormalizer)")


# ---------------------------------------------------------------------------
# Device staging
# ---------------------------------------------------------------------------

def _default_put(a):
    # already on device (e.g. a prefetched batch flowing into
    # device_blocks): re-enqueueing a device_put would be a pure-overhead
    # dispatch, so only stage host arrays
    return a if isinstance(a, jax.Array) else jax.device_put(a)


def _stage_array(a, placement: Placement):
    if a is None:
        return None
    return placement(a)


def stage(ds, placement: Optional[Placement] = None):
    """Copy one DataSet/MultiDataSet's arrays onto device (async — returns
    as soon as the transfers are *enqueued*).  `placement` defaults to
    `jax.device_put` (skipped for arrays already on device); ParallelWrapper
    passes a sharded placement so staged batches land split over the mesh's
    data axis (always applied — placement carries the sharding)."""
    put = placement if placement is not None else _default_put
    if isinstance(ds, MultiDataSet) or hasattr(ds, "features_masks"):
        return MultiDataSet(
            features=[put(f) for f in ds.features],
            labels=[put(l) for l in ds.labels],
            features_masks=None if ds.features_masks is None else
            [_stage_array(m, put) for m in ds.features_masks],
            labels_masks=None if ds.labels_masks is None else
            [_stage_array(m, put) for m in ds.labels_masks])
    return DataSet(put(ds.features), put(ds.labels),
                   _stage_array(getattr(ds, "features_mask", None), put),
                   _stage_array(getattr(ds, "labels_mask", None), put))


class DevicePrefetchIterator(DataSetIterator):
    """Prefetch-to-device wrapper: host ETL runs in an
    :class:`AsyncDataSetIterator` producer thread, and this iterator keeps
    up to ``depth`` batches *staged on device* (transfers enqueued via
    `jax.device_put`) ahead of the consumer — the flax
    ``prefetch_to_device`` shape, grown a DataSet/normalizer-aware skin.

    ``depth=2`` double-buffers (next batch's H2D overlaps this step's
    compute); ``depth=3`` adds slack for jittery ETL.  Backpressure is
    structural: at most ``depth`` staged batches + ``queue_size`` host
    batches exist at once, so a slow consumer never balloons memory.
    Early-break consumers shut the producer thread down via the async
    layer's stop event (generator ``finally``), and :meth:`close` does the
    same for owners that never finished iterating.

    A producer-thread exception re-raises HERE as :class:`ProducerError`
    (original chained) instead of silently ending the epoch.  With
    ``retries=N`` (opt-in; default 0 = fail fast) a transient producer
    failure is retried up to N times with exponential backoff: the
    underlying iterator is reset and replayed past the batches already
    delivered, so the consumer sees an uninterrupted batch sequence.
    Retries assume a deterministic, restartable underlying iterator.

    Each batch leaves two intervals in the monitor's ring
    (`monitor.recorded`), both on the consumer's thread and both with
    ``n`` = the batch's ordinal since this ``__iter__`` began:
    ``input_wait`` (blocked on the producer thread's queue) and
    ``input_stage`` (``stage()``: the host-to-device puts, or the sharded
    placement under ``ParallelWrapper.fit_prefetched``).
    """

    def __init__(self, underlying: DataSetIterator, depth: int = 2,
                 queue_size: Optional[int] = None,
                 placement: Optional[Placement] = None, retries: int = 0,
                 retry_backoff_s: float = 0.05):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.underlying = underlying
        self.depth = int(depth)
        self.placement = placement
        self.retries = int(retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self._async = AsyncDataSetIterator(
            underlying, queue_size=queue_size if queue_size is not None
            else self.depth)

    def _recover(self, state: dict, exc: BaseException) -> None:
        """One producer-retry round: restart the underlying iterator and
        replay past the `delivered` batches the consumer already has.
        Failures during the replay consume retry budget too; budget
        exhaustion raises `ProducerError` chained to the original."""
        from deeplearning4j_tpu.monitor.instrument import pipeline_instruments
        attempt = state["attempts"] + 1
        if attempt > self.retries:
            raise ProducerError(
                f"input producer failed at batch {state['delivered']}"
                + (f" (after {state['attempts']} retries)"
                   if state["attempts"] else "")
                + f": {exc!r}") from exc
        state["attempts"] = attempt
        pipeline_instruments().producer_retries.inc()
        try:
            state["it"].close()
        except Exception:
            pass
        time.sleep(self.retry_backoff_s * (2 ** (attempt - 1)))
        self.underlying.reset()
        state["it"] = iter(self._async)
        n = 0
        while n < state["delivered"]:
            try:
                next(state["it"])
            except StopIteration:
                raise ProducerError(
                    f"producer ended after {n} batches during retry "
                    f"replay; consumer already received "
                    f"{state['delivered']}") from exc
            except Exception as again:
                self._recover(state, again)   # fully re-replays
                return
            n += 1

    def __iter__(self):
        from deeplearning4j_tpu.monitor.instrument import pipeline_instruments
        from deeplearning4j_tpu.monitor.spans import note
        ins = pipeline_instruments()
        buf: collections.deque = collections.deque()
        state = {"it": iter(self._async), "delivered": 0, "attempts": 0}
        put = self.placement if self.placement is not None else _default_put

        def counting_put(a):
            # a host array crossing here is one H2D transfer; device arrays
            # pass through untransferred (see _default_put)
            if not isinstance(a, jax.Array):
                ins.h2d_bytes.inc(getattr(a, "nbytes", 0) or 0)
            return put(a)

        def next_batch():
            while True:
                try:
                    return next(state["it"])
                except StopIteration:
                    raise
                except Exception as e:
                    self._recover(state, e)

        try:
            while True:
                t0 = time.perf_counter()
                try:
                    ds = next_batch()
                except StopIteration:
                    break
                state["delivered"] += 1
                t1 = time.perf_counter()
                buf.append(stage(ds, counting_put))
                n = state["delivered"] - 1
                note("input_wait", t0, t1, n)
                note("input_stage", t1, time.perf_counter(), n)
                ins.record_stage(t1 - t0, len(buf))
                if len(buf) >= self.depth:
                    yield buf.popleft()
                    ins.prefetch_depth.set(len(buf))
            while buf:
                yield buf.popleft()
                ins.prefetch_depth.set(len(buf))
        finally:
            state["it"].close()    # releases the producer on early break

    def close(self, timeout: float = 2.0) -> None:
        self._async.close(timeout)

    def active_producers(self) -> int:
        return self._async.active_producers()

    def reset(self):
        self.underlying.reset()

    def batch_size(self) -> int:
        return self.underlying.batch_size()

    def __len__(self):
        return len(self.underlying)


# ---------------------------------------------------------------------------
# Device-staged fused blocks
# ---------------------------------------------------------------------------

def _stack_staged(arrays):
    """[k] per-batch device arrays -> one [k, batch, ...] device array.
    `jnp.stack` dispatches a device-side concat: unlike the old host
    `np.stack`, no host copy of the block is ever materialized, and for
    already-staged (prefetched) inputs it runs entirely device-side."""
    return jnp.stack([jnp.asarray(a) for a in arrays])


def device_blocks(iterator, k: int, placement: Optional[Placement] = None):
    """Group an iterator's batches into fused `[k, batch, ...]` blocks
    staged on device.

    Yields ``("block", (xs, ys, fms, lms))`` — each a list of `k` staged
    per-step arrays (or None) — for full same-shape blocks, and
    ``("single", dataset)`` for tails / shape changes (callers run those
    through the per-step path).  The lists feed `fit_steps`' streaming
    form, which stacks them *inside* the compiled dispatch: no per-block
    host `np.stack`, and no eager device-side stack copy either.  Blocks
    mixing masked and unmasked batches are never fused — `blocks_of` keys
    on mask shapes, and this function re-checks defensively so a mixed
    block degrades to singles instead of silently dropping masks (the old
    `None if fms[0] is None` bug).
    """
    from deeplearning4j_tpu.utils.scan_fit import blocks_of
    for block in blocks_of(iterator, k):
        if len(block) == 1:
            yield "single", block[0]
            continue
        fms = [getattr(ds, "features_mask", None) for ds in block]
        lms = [getattr(ds, "labels_mask", None) for ds in block]
        if (any(m is None for m in fms) != all(m is None for m in fms)
                or any(m is None for m in lms) != all(m is None for m in lms)):
            # mixed mask presence inside one block: not fusable
            for ds in block:
                yield "single", ds
            continue
        staged = [stage(ds, placement) for ds in block]
        yield "block", (
            [ds.features for ds in staged],
            [ds.labels for ds in staged],
            None if fms[0] is None else
            [ds.features_mask for ds in staged],
            None if lms[0] is None else
            [ds.labels_mask for ds in staged])
