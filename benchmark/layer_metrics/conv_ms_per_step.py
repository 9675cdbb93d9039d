"""Device time a step under the convolution layers: self time of the device
ops whose scope has `ConvolutionLayer` in it (the layer-wise trainer names
each vertex `<class>/<name>`), forward and backward, first chip of the
traced window, over its steps (`benchmark/trace/step_scopes.py`).  A fusion
of a convolution with the normalisation and activation after it counts on
one side."""
from benchmark.trace.step_scopes import ms_per_step


def read(run):
    return ms_per_step(run, "ConvolutionLayer")
