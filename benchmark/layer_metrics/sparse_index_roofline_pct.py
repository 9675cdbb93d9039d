"""The indexer's share of its roofline: what the selection and its loss
REQUIRE of the traced window's steps (the family's `index_work`: the index
scores of every causal pair, the loss's pass over the main heads and the
scores' gradients on the selected pairs; the indexer's operands and the
selection's bits moved once a use) against ALL device time under the scopes
`sparse_index` and `index_loss` — Mosaic kernels and XLA's ops alike, the
top-k's passes among them — and the peaks of `benchmark/peaks.json`: the
larger of the compute and the bandwidth share.  The family is the cell's
(`harness.load_family`); one without `index_work`, or a program without the
scopes, has nothing to read."""
from benchmark import harness
from benchmark.trace.scopes import in_scope, scoped_events, self_seconds


def read(run):
    steps = run.counters.get("steps_traced")
    if run.trace is None or not steps:
        return None
    family = harness.load_family(run.cell.config)
    events = scoped_events(run) if hasattr(family, "index_work") else None
    if not events:
        return None
    sec = self_seconds(events, lambda s: in_scope(s.scope, "sparse_index")
                       or in_scope(s.scope, "index_loss"))
    if not sec:
        return None
    work = family.index_work(run.cell.config, run.cell.traffic,
                             run.counters["rows"])
    least = max(work["flops"] / run.peaks["bf16_flops_per_s"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * steps * least / sec
