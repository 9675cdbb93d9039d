"""Device time a step under the casts of the float32 master parameters to
the compute dtype and of their gradients back: self time of the device ops
whose scope has `param_cast` in it, forward, backward and recomputation,
first chip of the traced window, over its steps
(`benchmark/trace/step_scopes.py`)."""
from benchmark.trace.step_scopes import ms_per_step


def read(run):
    return ms_per_step(run, "param_cast")
