"""Device time a step under the updater: self time of the device ops whose
scope has `updater` in it (gradient normalisation, the updater's moments
and step, weight decay, the subtraction from the master parameters; every
front end puts its whole update under that scope), first chip of the traced
window, over its steps (`benchmark/trace/step_scopes.py`)."""
from benchmark.trace.step_scopes import ms_per_step


def read(run):
    return ms_per_step(run, "updater")
