"""Share of the measured window the fit loop spent inside the iterator's
`next()`: the step's wait for its input.  Host clock, taken by the driver's
wrapper around the iterator the fit loop consumes (`input_wait_s`); nothing
where the loop cannot be wrapped (`fit_prefetched` builds its own)."""


def read(run):
    wait = run.counters.get("input_wait_s")
    if wait is None:
        return None
    return 100.0 * wait / run.counters["window_s"]
