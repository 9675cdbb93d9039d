"""Device time a step under the sparse-attention index: self time of the
device ops whose scope has `sparse_index` in it (the indexer's projections,
its key's LayerNorm, rotary, the index scores of every causal pair, the exact
top-k a query and the selection packed to bits; the forward pass alone: the
backward and the block's recomputation reuse the selection), first chip of
the traced window, over its steps (`benchmark/trace/scopes.py`).  A program
without the scope has nothing to read."""
from benchmark.trace.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "sparse_index")
