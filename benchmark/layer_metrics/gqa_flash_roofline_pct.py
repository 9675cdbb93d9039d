"""The grouped-query attention kernels' share of their roofline: what causal
attention REQUIRES of the traced window's steps (the family's
`gqa_attention_work`: two products forward and four backward over the lower
triangle for every query head; q, o, dO and dQ moved once a query head, k,
v, dK and dV once a KEY-VALUE head — the same work whatever implements it)
against the device time of the Mosaic kernels under `gqa_attention` and the
peaks of `benchmark/peaks.json`: the larger of the compute and the bandwidth
share.  The family is the cell's (`harness.load_family`); one without
`gqa_attention_work` has nothing to read."""
from benchmark import harness
from benchmark.trace.scopes import kernel_roofline_pct


def read(run):
    steps = run.counters.get("steps_traced")
    if run.trace is None or not steps:
        return None
    family = harness.load_family(run.cell.config)
    if not hasattr(family, "gqa_attention_work"):
        return None
    work = family.gqa_attention_work(run.cell.config, run.cell.traffic,
                                     run.counters["rows"])
    return kernel_roofline_pct(
        run, "gqa_attention", {k: v * steps for k, v in work.items()})
