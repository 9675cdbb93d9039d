"""Share of the worst chip's idle seconds (gaps >= 100 us in the traced
window) under no program span, or under `fit_epoch` alone: what the
program's spans cannot explain yet."""
from benchmark.trace.program_spans import NONE, OUTER, collect


def read(run):
    p = collect(run)
    return None if p is None else p.idle_pct(NONE, OUTER)
