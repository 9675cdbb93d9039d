"""Device time a step that no layer's name covers: self time of the device
ops, control ops left out, whose `op_name` is empty (copies, slices and
scan bookkeeping that XLA makes) or holds no scope of the program's beneath
`jit(...)`, first chip of the traced window, over its steps
(`benchmark/trace/step_scopes.py`).  What a `perf_opt` issue cannot place
yet."""
from benchmark.trace.step_scopes import unscoped_ms_per_step


def read(run):
    return unscoped_ms_per_step(run)
