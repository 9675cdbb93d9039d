"""Share of the worst chip's idle seconds (gaps >= 100 us in the traced
window) that fall under the program's `input_wait` or `input_stage` spans:
the device waiting for its input."""
from benchmark.trace.program_spans import INPUT, collect


def read(run):
    p = collect(run)
    return None if p is None else p.idle_pct(*INPUT)
