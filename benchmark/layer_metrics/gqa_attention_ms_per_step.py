"""Device time a step under grouped-query attention: self time of the device
ops whose scope has `gqa_attention` in it (the projections, the norm of
queries and keys, rotary, the attention kernels, the output product;
forward, the backward pass and what it computes again), first chip of the
traced window, over its steps (`benchmark/trace/scopes.py`)."""
from benchmark.trace.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "gqa_attention")
