"""XLA backend compiles between the start and the end of the measured
window(s), from jax's monitoring events.  Must be 0: the run is not
`correct` otherwise."""


def read(run):
    return run.counters.get("compiles_in_window")
