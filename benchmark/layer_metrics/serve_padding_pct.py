"""Share of dispatched rows that were bucket padding over the window:
`ModelServer.stats()["padding_fraction"]`, snapshot difference."""


def read(run):
    return run.counters.get("padding_pct")
