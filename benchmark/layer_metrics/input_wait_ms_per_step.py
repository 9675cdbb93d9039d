"""Host time a step that the fit loop spent blocked on the producer thread's
queue: the program's `input_wait` spans (`DevicePrefetchIterator.__iter__`)
in the traced window, over its `step_dispatch` spans.  Read on four chips
too, where `fit_prefetched` builds the prefetcher itself."""
from benchmark.trace.program_spans import collect


def read(run):
    p = collect(run)
    return None if p is None else p.ms_per_step("input_wait")
