"""Device time a step under batch normalisation: self time of the device
ops whose scope has `BatchNormalizationLayer` in it (statistics, the
normalisation, the activation the layer applies; forward and backward),
first chip of the traced window, over its steps
(`benchmark/trace/step_scopes.py`)."""
from benchmark.trace.step_scopes import ms_per_step


def read(run):
    return ms_per_step(run, "BatchNormalizationLayer")
