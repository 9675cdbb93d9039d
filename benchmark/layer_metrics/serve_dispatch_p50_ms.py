"""Median wall time of one dispatch (merge, pad, H2D, forward, D2H) as the
batcher times it: `ModelServer.stats()["dispatch_ms"]["p50"]`."""


def read(run):
    return run.counters.get("dispatch_p50_ms")
