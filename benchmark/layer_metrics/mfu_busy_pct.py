"""The step's share of the compute roofline while the device is busy: FLOPs
the forward and backward passes REQUIRE per step (the model family's
`flops_per_item`, from shapes; no recompute counted) over device busy time
per step x chips x the bf16 peak of `benchmark/peaks.json`."""


def read(run):
    steps = run.counters.get("steps_traced")
    if run.trace is None or not steps:
        return None
    flops = run.counters["flops_per_row"] * run.counters["rows"] * steps
    peak = run.peaks["bf16_flops_per_s"] * run.counters["chips"]
    return 100.0 * flops / (run.trace.busy_s_mean * peak)
