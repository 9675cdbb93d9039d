"""The SSD's share of its roofline: what the chunked state-space dual
REQUIRES of the traced window's steps (the family's `ssd_work`: its
products at the configuration's chunk, forward and backward, and x, B, C,
dt, y and their gradients moved once a pass in float32 — the same work
whatever implements it) against ALL device time under the scope `ssd_scan`
— XLA's ops and any Mosaic kernel alike — and the peaks of
`benchmark/peaks.json`: the larger of the compute and the bandwidth share.
The family is the cell's (`harness.load_family`); one without `ssd_work`,
or a program without the scope, has nothing to read."""
from benchmark import harness
from benchmark.trace.scopes import in_scope, scoped_events, self_seconds


def read(run):
    steps = run.counters.get("steps_traced")
    if run.trace is None or not steps:
        return None
    family = harness.load_family(run.cell.config)
    events = scoped_events(run) if hasattr(family, "ssd_work") else None
    if not events:
        return None
    sec = self_seconds(events, lambda s: in_scope(s.scope, "ssd_scan"))
    if not sec:
        return None
    work = family.ssd_work(run.cell.config, run.cell.traffic,
                           run.counters["rows"])
    least = max(work["flops"] / run.peaks["bf16_flops_per_s"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * steps * least / sec
