"""Device time a step under the indexer's loss: self time of the device ops
whose scope has `index_loss` in it (the index scores once more, the main
heads' probabilities summed over the heads on the selected pairs, the KL
term and the scores' gradients, all taken in the forward pass; the backward
scales them and runs the indexer's projections' gradients), first chip of
the traced window, over its steps (`benchmark/trace/scopes.py`).  A program
without the scope has nothing to read."""
from benchmark.trace.scopes import ms_per_step


def read(run):
    return ms_per_step(run, "index_loss")
