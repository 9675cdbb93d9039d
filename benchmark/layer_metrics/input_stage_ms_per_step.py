"""Host time a step inside `stage()`: the host-to-device puts of a batch on
the fit thread, `parallel/wrapper._shard_batch` on four chips.  The
program's `input_stage` spans in the traced window over its `step_dispatch`
spans."""
from benchmark.trace.program_spans import collect


def read(run):
    p = collect(run)
    return None if p is None else p.ms_per_step("input_stage")
