"""Summed device time of the collective operations (all-reduce,
reduce-scatter, all-gather, ...) on the trace's first chip, per step."""


def read(run):
    steps = run.counters.get("steps_traced")
    if run.trace is None or not steps:
        return None
    return 1e3 * run.trace.collective_s / steps
