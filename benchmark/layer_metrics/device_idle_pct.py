"""1 - union of device-op intervals over the traced window, on the chip that
was idle most."""


def read(run):
    return None if run.trace is None else run.trace.idle_pct_worst
