"""Device time a step under the encoder's self-attention: self time of the
device ops whose scope has `self_attention` in it (the q/k/v products, the
attention itself, the output product, its residual and LayerNorm; forward
and backward, all layers of the scan), first chip of the traced window,
over its steps (`benchmark/trace/step_scopes.py`)."""
from benchmark.trace.step_scopes import ms_per_step


def read(run):
    return ms_per_step(run, "self_attention")
