"""Host time a step inside the call of the compiled train step: the
program's `step_dispatch` spans in the traced window, over their number.
Once the runtime's 32 steps in flight are reached the call blocks until the
device retires one, so this grows to a whole step time with the device never
idle: read it beside the idle seconds under `step_dispatch` in the log's
`program spans` line, not alone."""
from benchmark.trace.program_spans import STEP, collect


def read(run):
    p = collect(run)
    return None if p is None else p.ms_per_step(STEP)
