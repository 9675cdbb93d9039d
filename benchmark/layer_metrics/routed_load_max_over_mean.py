"""Routing imbalance over the measured window: the fullest held expert's
(token, expert) pairs over the mean of the held experts, worst expert layer.
From the program's routing counter (`DecoderModel.state_["expert_load"]`,
kept on the device by the train step), the window's start and end read in
one transfer after the window (the family's `window_held_load`; the family
is the cell's, `harness.load_family`).  1.0 is even routing; the grouped
product's longest group is this many times its mean."""
from benchmark import harness


def read(run):
    if not run.counters.get("steps"):
        return None
    family = harness.load_family(run.cell.config)
    model = getattr(family, "LAST_BUILT", None)
    if getattr(model, "state_", None) is None \
            or not hasattr(family, "window_held_load"):
        return None
    load = family.window_held_load(model)
    mean = load.mean(axis=1)
    if not mean.all():
        return None
    return float((load.max(axis=1) / mean).max())
