"""Device idle time inside the traced steady window, per step: what the
host's per-step work (dispatch, input, bookkeeping) leaves the chip waiting.
Worst chip of the trace."""


def read(run):
    steps = run.counters.get("steps_traced")
    if run.trace is None or not steps:
        return None
    idle_s = run.trace.window_s * run.trace.idle_pct_worst / 100.0
    return 1e3 * idle_s / steps
