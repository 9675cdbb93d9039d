"""`tpu_custom_call`s in the lowered train step: the Pallas kernels the
dispatcher put there.  A guard: it changes only when dispatch does."""


def read(run):
    return run.counters.get("mosaic_calls")
