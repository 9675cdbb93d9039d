"""The delta rule's share of its roofline: what the chunkwise delta rule
REQUIRES of the traced window's steps (the family's `delta_rule_work`: its
products at a fixed reference chunk, forward and backward, and q, k, v, g,
beta, o and their gradients moved once a pass in float32 — the same work
whatever chunk or kernel implements it) against ALL device time under the
scope `delta_rule` — the chunks' insides in XLA and the recurrence's Mosaic
kernels alike — and the peaks of `benchmark/peaks.json`: the larger of the
compute and the bandwidth share.  The family is the cell's
(`harness.load_family`); one without `delta_rule_work`, or a program
without the scope, has nothing to read."""
from benchmark import harness
from benchmark.trace.scopes import in_scope, scoped_events, self_seconds


def read(run):
    steps = run.counters.get("steps_traced")
    if run.trace is None or not steps:
        return None
    family = harness.load_family(run.cell.config)
    events = scoped_events(run) if hasattr(family, "delta_rule_work") \
        else None
    if not events:
        return None
    sec = self_seconds(events, lambda s: in_scope(s.scope, "delta_rule"))
    if not sec:
        return None
    work = family.delta_rule_work(run.cell.config, run.cell.traffic,
                                  run.counters["rows"])
    least = max(work["flops"] / run.peaks["bf16_flops_per_s"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * steps * least / sec
