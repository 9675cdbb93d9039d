"""Device time a step under the encoder's feed-forward block: self time of
the device ops whose scope has `ffn` in it (both products, GELU, residual,
LayerNorm; forward and backward, all layers of the scan), first chip of the
traced window, over its steps (`benchmark/trace/step_scopes.py`)."""
from benchmark.trace.step_scopes import ms_per_step


def read(run):
    return ms_per_step(run, "ffn")
