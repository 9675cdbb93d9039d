"""Device time a step under the Mamba-2 mixer: self time of the device ops
whose scope has `mamba_mixer` in it (the three input products, the
convolution, `dt`, the SSD's chunks and the state across them, the gated
norm and the output product; forward, the backward pass and what it
computes again), first chip of the traced window, over its steps
(`benchmark/trace/scopes.py`).  A program without the scope has nothing to
read."""
from benchmark.trace.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "mamba_mixer")
