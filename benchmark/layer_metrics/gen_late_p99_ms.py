"""How late the benchmark's own generator ran: 99th percentile of (send time
- due time).  Above the traffic's `gen_late_limit_ms` the run is void."""


def read(run):
    return run.counters.get("gen_late_p99_ms")
