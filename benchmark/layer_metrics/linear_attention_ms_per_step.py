"""Device time a step under Kimi delta attention: self time of the device
ops whose scope has `linear_attention` in it (the projections, the
convolution, the gates, the delta rule's chunks and its Mosaic recurrence,
the gated norm and the output product; forward, the backward pass and what
it computes again), first chip of the traced window, over its steps
(`benchmark/trace/scopes.py`).  A program without the scope has nothing to
read."""
from benchmark.trace.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "linear_attention")
