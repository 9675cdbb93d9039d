"""Device time a step under the gated short convolutions: self time of the
device ops whose scope has `short_conv` in it (the in-projection, the mix
and the out-projection; forward, the backward pass and what it computes
again), first chip of the traced window, over its steps
(`benchmark/trace/scopes.py`)."""
from benchmark.trace.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "short_conv")
