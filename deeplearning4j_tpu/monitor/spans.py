"""Host-side spans: a wall-time histogram per span path, and a flight
recorder of the last 8,192 intervals on the clock the benchmark ties to the
device trace.

`span("fit_epoch")` times a host region into the registry's `span_ms`
histogram (one labeled series per span path, nesting encoded as
`"fit_epoch/fit_step"`) and appends the interval to the ring (below).
`note("step_dispatch", t0, t1, n)` appends an interval that a hot-path site
has already timed: no context-manager object, no histogram.

The ring.  A process-wide `collections.deque(maxlen=8192)` of
`(name, t0, t1, thread_ident, n, parent)` tuples (`Recorded`):

- `t0`, `t1` are `time.perf_counter()` seconds.  THIS IS A CONTRACT: it is
  the clock of the benchmark's `TraceClock` (`benchmark/harness.py`), whose
  two marker runs tie `perf_counter` to the profiler's device clock
  (`benchmark/trace/reduce.align`), so the program's spans can be laid over
  the device's idle gaps exactly as the benchmark's own are
  (`benchmark/trace/program_spans.py`).  A site that takes its reads from
  another clock breaks every reader.
- `n` is an identifier the site gives, or None.  On the train path:
  `input_wait` / `input_stage` (`data/pipeline.DevicePrefetchIterator`)
  carry the batch's ordinal since that `__iter__` began, 0, 1, 2, ...;
  `step_dispatch` (`nn/trainer.py:_dispatched` for the layer-wise models,
  `zoo/bert.py`, `zoo/decoder.py`) carries the model's iteration count
  before the step.  Within one `fit_epoch` at
  one step per dispatch, the k-th `step_dispatch` (k from 0) consumes the
  batch whose `input_*` spans carry `n = k`; a fused dispatch of k steps is
  one span, and the next one's `n` is k higher.
- `parent` is `current_span()` when the interval was recorded: the path of
  the enclosing `span` on that thread, None outside any.  A `span`'s own
  record carries its whole path as `name`.

The ring records whenever telemetry is enabled — the default — as `span_ms`
does; `monitor.set_enabled(False)` stops it with everything else.  Appends
are atomic under the GIL, so no lock; the bound makes it a flight recorder
(ResNet-50 writes three intervals per 108 ms step: the last five minutes).
`recorded(since, until)` copies out what overlaps a host-clock interval.

`span` also forwards its name into `jax.profiler.TraceAnnotation`.  That
puts it on the host timeline of an XProf/TensorBoard capture
(`utils.profiling.trace`) only while the profiler's HOST tracer is on — an
operator's interactive use.  A measured run cannot have it on: while image
batches are staged the runtime's layout transposition floods the host
tracer (30 M events, 0.5 s of host time a step, `benchmark/trace/reduce.py`),
so traced benchmark runs record device ops only and read host time from
the ring.  With no profiler session the annotation costs nothing.

Nesting is thread-local: concurrent threads (trainer, prefetch producer,
serving worker) each carry their own span stack, and a child records under
`parent/child` so the registry distinguishes "compile inside the first
epoch" from "compile at serving warmup".

Cost when telemetry is off (`monitor.set_enabled(False)`): one flag check —
no clock read, no TraceAnnotation, no allocation beyond the context-manager
object itself; `note` is the flag check alone.  When on, the ring costs one
tuple and one append per interval.

The step's text.  Beside the ring sits one slot: the train step this process
compiled last.  A front end calls `note_step(fn, args)` right after the call
that compiled a step, where it notices a new step anyway —
`TrainingInstruments.check_compile`'s compile event in
`nn/trainer.py:_dispatched`, the first call of the function that the
building branch of `BertModel._step` / `DecoderModel._step` made — never
once a step: a steady-state dispatch pays one attribute test.  What is kept
is `fn.trace(...)` for the arguments as `jax.ShapeDtypeStruct`s, with the
sharding of every committed array (a mesh step is lowered as it ran): the
`jax.stages.Traced` around the jaxpr that jit made a moment ago (a hit in
its tracing cache, milliseconds), which refers to neither the model nor its
arrays, so no device buffer outlives its model on the slot's account — and
the slot still answers after a benchmark's driver has dropped its model.
`lowered_step()` lowers it WHEN ASKED and returns the `jax.stages.Lowered`,
or None (no step yet; telemetry off when the step was compiled).
`lowered_step().compile().as_text()` is the running program's text: each
instruction with the `op_name` that says which layer asked for it
(`jax.named_scope`, docs/observability.md "Which layer is `fusion.35`");
with jax's persistent compilation cache on, that compile is a hit.  It is
what `benchmark/trace/step_scopes.py` reads.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Any, List, NamedTuple, Optional

from deeplearning4j_tpu.monitor.registry import (MetricsRegistry, enabled,
                                                 registry)

try:                                # jax is a hard dep of the package, but
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:                   # pragma: no cover - keep monitor usable
    _TraceAnnotation = None         # in stripped-down environments

_local = threading.local()

RING_SIZE = 8192


class Recorded(NamedTuple):
    """One interval of the ring; see the module docstring for each field."""
    name: str
    t0: float
    t1: float
    thread_ident: int
    n: Optional[int]
    parent: Optional[str]


_ring: collections.deque = collections.deque(maxlen=RING_SIZE)


def span_stack() -> List[str]:
    """This thread's active span paths, outermost first."""
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def current_span() -> Optional[str]:
    st = getattr(_local, "stack", None)
    return st[-1] if st else None


def note(name: str, t0: float, t1: float, n: Optional[int] = None) -> None:
    """Record `[t0, t1]` (`time.perf_counter()` seconds, both already read
    by the caller) under `name` in the ring, with the caller's identifier
    `n` and the enclosing `span` of this thread as parent."""
    if enabled():
        _ring.append(Recorded(name, t0, t1, threading.get_ident(), n,
                              current_span()))


def recorded(since: Optional[float] = None,
             until: Optional[float] = None) -> List[Recorded]:
    """A copy of the ring, oldest first; with `since`/`until`
    (`time.perf_counter()` seconds) only the intervals that overlap
    `[since, until]`."""
    out = list(_ring)
    if since is not None:
        out = [r for r in out if r.t1 >= since]
    if until is not None:
        out = [r for r in out if r.t0 <= until]
    return out


def clear_recorded() -> None:
    _ring.clear()


_step = None            # the `jax.stages.Traced` of the step compiled last


def _spec(a) -> Any:
    """What tracing needs of one argument: shape, dtype, weak type, and
    the sharding where the caller committed to one (an uncommitted array
    lowers with none, as the call did).  Anything but an array stays."""
    import jax
    if isinstance(a, jax.Array):
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, weak_type=a.weak_type,
            sharding=a.sharding if a.committed else None)
    if hasattr(a, "shape") and hasattr(a, "dtype"):       # numpy
        return jax.ShapeDtypeStruct(a.shape, a.dtype)
    return a


def note_step(fn, args: tuple) -> None:
    """Remember `fn` (a `jax.jit` function or a
    `compile.step_cache.AotStepFunction`: anything with `trace`) as the
    train step compiled last, by the call `fn(*args)` that has just
    returned.  Donated arrays are fine: only their shapes are read."""
    global _step
    if enabled():
        import jax
        _step = fn.trace(*jax.tree_util.tree_map(_spec, args))


def lowered_step():
    """The `jax.stages.Lowered` of the train step this process compiled
    last, lowered now for the shapes, dtypes and shardings it runs at; None
    where there is none (module docstring)."""
    return None if _step is None else _step.lower()


class span:
    """Context manager: `with span("fit_epoch"):` records host wall time of
    the region into `span_ms{span="<path>"}`, appends the interval to the
    ring under its path, and annotates a host-traced profile.  Extra labels
    ride along (`span("dispatch", model="lenet")`).

    Re-entrant per instance is NOT supported (construct per use); nesting
    different instances is the point."""

    __slots__ = ("name", "_labels", "_registry", "_t0", "_path", "_ann")

    def __init__(self, name: str, registry_: Optional[MetricsRegistry] = None,
                 **labels):
        self.name = name
        self._labels = labels
        self._registry = registry_
        self._t0 = None
        self._path = None
        self._ann = None

    def __enter__(self) -> "span":
        if not enabled():
            return self
        st = span_stack()
        self._path = f"{st[-1]}/{self.name}" if st else self.name
        st.append(self._path)
        if _TraceAnnotation is not None:
            self._ann = _TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._t0 is None:
            return False
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        st = span_stack()
        if st and st[-1] == self._path:
            st.pop()
        _ring.append(Recorded(self._path, self._t0, t1, threading.get_ident(),
                              None, st[-1] if st else None))
        reg = self._registry if self._registry is not None else registry()
        labels = {"span": self._path}
        if self._labels:
            labels.update(self._labels)
        reg.histogram("span_ms", help="host wall time of traced spans (ms)",
                      labels=labels).observe((t1 - self._t0) * 1000.0)
        self._t0 = None
        return False
