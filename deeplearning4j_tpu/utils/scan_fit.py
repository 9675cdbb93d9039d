"""Fused multi-step training dispatch (shared by MultiLayerNetwork,
ComputationGraph).

The TPU-native form of the reference's `fit(DataSetIterator)` hot loop
(`MultiLayerNetwork.fit(DataSetIterator)` upstream): per-step host
dispatch adds the host's latency to every step, so steady-state training
can scan a compiled step over a device-resident `[k, batch, ...]` block —
one host dispatch per k steps, with params/updater-state/rng/iteration
flowing step-to-step as scan carries.
"""
import numpy as np

import jax
import jax.numpy as jnp


def blocks_of(iterator, k: int):
    """Group consecutive same-shape DataSets from `iterator` into lists of
    exactly `k` (ready for one fused `fit_steps` dispatch).  Batches that
    don't fill a block — the epoch tail, or a shape change mid-stream —
    are yielded as single-element lists so the caller takes the per-step
    path instead of compiling a new scan executable for a one-off k."""
    def shapes(x):
        if x is None:
            return None
        if isinstance(x, (list, tuple)):            # multi-input/-output
            return tuple(np.shape(e) for e in x)
        if isinstance(x, dict):
            return tuple(sorted((k, np.shape(v)) for k, v in x.items()))
        return np.shape(x)

    def first_attr(ds, *names):
        # NOT `a or b`: truthiness of a multi-element ndarray mask raises
        for n in names:
            v = getattr(ds, n, None)
            if v is not None:
                return v
        return None

    def key(ds):
        return (shapes(ds.features), shapes(ds.labels),
                shapes(first_attr(ds, "features_mask", "features_masks")),
                shapes(first_attr(ds, "labels_mask", "labels_masks")))

    buf, buf_key = [], None
    for ds in iterator:
        dk = key(ds)
        if buf and dk != buf_key:
            for b in buf:
                yield [b]
            buf = []
        buf.append(ds)
        buf_key = dk
        if len(buf) == k:
            yield buf
            buf = []
    for b in buf:
        yield [b]


def check_steps_axes(named_arrays):
    """Validate that every non-None array shares one leading steps axis.

    `named_arrays` is an iterable of (name, array-or-None); returns k.
    Raising here (with the offending name) beats the opaque
    'different leading axis sizes' error lax.scan gives after tracing."""
    k, ref = None, None
    for name, a in named_arrays:
        if a is None:
            continue
        if k is None:
            k, ref = a.shape[0], name
        elif a.shape[0] != k:
            raise ValueError(
                f"steps axis mismatch: '{name}' has {a.shape[0]} steps but "
                f"'{ref}' has {k} — every array needs the same leading "
                f"[k, batch, ...] steps axis")
    if k is None:
        raise ValueError("fit_steps needs at least one array input")
    return k


def make_scan_step(tick, key_base=None, cache=None, donate: bool = True):
    """Wrap a per-class `tick` adapter into the jitted k-step scan.

    `tick(carry, epoch, batch) -> (carry, loss)` adapts one class's step
    body to a scan carry (each class carries a different tuple: MLN/CG
    `(params, state, opt, rng, it)`, SameDiff `(vars, opt, rng, it)`,
    BERT `(params, opt, it)`).  The returned function is
    `step(carry, epoch, batches) -> (carry, losses)`; the whole carry is
    donated (every element is replaced from the return by the callers —
    `advance()` for the counter, attribute reassignment for the rest).
    `epoch` is NOT donated: `device_counters` caches it across calls.

    With `cache` + `key_base` (a `compile.PersistentExecutableCache` and a
    zero-arg disk-key-parts callable) the scan compiles through the
    persistent tier like the single-step builders — a restarted fused-fit
    loop deserializes instead of recompiling.  The batch block is the only
    dynamic argument (argnum 2)."""
    def many(carry, epoch, batches):
        if (isinstance(batches, (list, tuple)) and len(batches)
                and isinstance(batches[0], (list, tuple))):
            # streaming form: k per-step batch tuples (the device-staged
            # prefetch path).  Stack INSIDE the compiled region — one
            # dispatch instead of one eager jnp.stack per leaf, and XLA
            # folds the concatenate into the scan's per-step slicing
            # rather than materializing a second copy of the block.
            batches = jax.tree.map(lambda *ls: jnp.stack(ls), *batches)
        carry, losses = jax.lax.scan(
            lambda c, b: tick(c, epoch, b), carry, batches)
        # the final-step loss is sliced INSIDE the compiled program: an
        # eager `losses[-1]` after the call would upload a fresh gather
        # index every dispatch (a per-block H2D the sync-free loop bans —
        # tests/test_input_pipeline.py runs fit_steps under
        # transfer_guard("disallow"))
        return carry, losses, losses[-1]

    from deeplearning4j_tpu.compile import step_function
    return step_function(many, donate_argnums=(0,) if donate else (),
                         key_base=key_base, cache=cache,
                         dynamic_argnums=(2,))
