"""Device time a step under the masked-LM head: self time of the device ops
whose scope has `mlm_head` in it (the gather of the labelled positions, the
transform, the tied product, the loss and their gradients), first chip of
the traced window, over its steps (`benchmark/trace/step_scopes.py`)."""
from benchmark.trace.step_scopes import ms_per_step


def read(run):
    return ms_per_step(run, "mlm_head")
