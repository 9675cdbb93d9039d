"""Throughput of the traced run's untraced stretch over the chips used.  Set
beside the one-chip cell's `train_samples_per_s` it is the scaling
efficiency."""


def read(run):
    rate = run.counters.get("rows_per_s")
    if rate is None:
        return None
    return rate / run.counters["chips"]
