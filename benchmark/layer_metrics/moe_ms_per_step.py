"""Device time a step under the expert layer: self time of the device ops
whose scope has `moe` in it (router, dispatch, experts, combine, shared
experts; forward, the backward pass and what it computes again), first chip
of the traced window, over its steps (`benchmark/trace/scopes.py`)."""
from benchmark.trace.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "moe")
