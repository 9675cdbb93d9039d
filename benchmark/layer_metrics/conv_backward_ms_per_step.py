"""The backward half of `conv_ms_per_step`: of the device ops under
`ConvolutionLayer`, those autodiff put under `transpose(` (the gradients
with respect to the input and to the kernel)."""
from benchmark.trace.step_scopes import ms_per_step


def read(run):
    return ms_per_step(run, "ConvolutionLayer", backward_only=True)
