"""The attention kernels' share of their roofline: what causal attention at
the configuration's key and value widths REQUIRES of the traced window's
steps (the family's `attention_work`: two products forward and four backward
over the lower triangle, each operand moved once) against the device time of
the Mosaic kernels under `mla_attention` and the peaks of
`benchmark/peaks.json`: the larger of the compute and the bandwidth share.
A forward kernel the backward pass runs again is time and no work."""
from benchmark.trace.scopes import kernel_roofline_pct


def read(run):
    steps = run.counters.get("steps_traced")
    if run.trace is None or not steps:
        return None
    from benchmark.models import deepseek_v3 as family
    work = family.attention_work(run.cell.config, run.cell.traffic,
                                 run.counters["rows"])
    return kernel_roofline_pct(
        run, "mla_attention", {k: v * steps for k, v in work.items()})
