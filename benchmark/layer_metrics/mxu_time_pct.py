"""Share of the device's busy time (self time of its ops, first chip of the
trace) spent in convolutions, dots and the fusions that hold one
(`benchmark.trace.reduce.op_class`)."""


def read(run):
    return None if run.trace is None else run.trace.mxu_pct
