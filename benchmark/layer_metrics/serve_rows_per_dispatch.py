"""Rows per device dispatch over the window: `ModelServer.stats()`,
`rows_dispatched / dispatches`, snapshot difference.  How much the batcher
aggregates."""


def read(run):
    return run.counters.get("rows_per_dispatch")
