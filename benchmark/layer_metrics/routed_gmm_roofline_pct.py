"""The routed experts' grouped products' share of their roofline: what the
products REQUIRE for the (token, held expert) pairs the program's routing
counter saw (the family's `grouped_work`: the same work whatever implements
them; the window's mean pairs a step, times the traced steps) against the
device time of the Mosaic kernels under `moe` and the peaks of
`benchmark/peaks.json`: the larger of the compute and the bandwidth share.
The family is the cell's (`harness.load_family`), never a module named
here."""
from benchmark import harness
from benchmark.trace.scopes import kernel_roofline_pct


def read(run):
    traced = run.counters.get("steps_traced")
    if run.trace is None or not traced:
        return None
    family = harness.load_family(run.cell.config)
    model = getattr(family, "LAST_BUILT", None)
    if getattr(model, "state_", None) is None \
            or not hasattr(family, "window_held_load"):
        return None
    # pairs of all expert layers since the measured window began, untraced
    # stretch and traced: their mean a step stands for the traced steps
    load = family.window_held_load(model)
    pairs_a_step = float(load.sum()) / (run.counters["steps"] + traced)
    return kernel_roofline_pct(run, "moe", family.grouped_work(
        run.cell.config, pairs_a_step * traced, load.shape[0] * traced))
