"""Routing imbalance over the measured window: the fullest held expert's
(token, expert) pairs over the mean of the held experts, worst expert layer.
From the program's routing counter (`DecoderModel.state_["expert_load"]`,
kept on the device by the train step), the window's start and end read in
one transfer after the window (`models/deepseek_v3.window_held_load`).
1.0 is even routing; the grouped product's longest group is this many times
its mean."""


def read(run):
    if not run.counters.get("steps"):
        return None
    from benchmark.models import deepseek_v3 as family
    if getattr(family.LAST_BUILT, "state_", None) is None:
        return None
    load = family.window_held_load(family.LAST_BUILT)
    mean = load.mean(axis=1)
    if not mean.all():
        return None
    return float((load.max(axis=1) / mean).max())
