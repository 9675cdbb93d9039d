"""Keys a query keeps under learned sparse attention: the (query, key) pairs
the indexers selected, from the program's device counter
(`DecoderModel.state_["selected_keys"]`, kept on the device by the train
step), the measured window's start and end read in one transfer after the
window (the family's `window_selected_keys`; the family is the cell's,
`harness.load_family`), over the window's steps (untraced stretch and
traced), the sparse layers and a step's queries.  `sum_t min(t + 1, topk) /
T` where nothing else binds: 1,920.06 at 16,384 tokens and 2,048 keys.  A
guard, as `mosaic_calls_in_step` is — a change that gets faster by selecting
fewer keys shows here."""
from benchmark import harness


def read(run):
    steps = run.counters.get("steps")
    if not steps:
        return None
    family = harness.load_family(run.cell.config)
    model = getattr(family, "LAST_BUILT", None)
    if not hasattr(family, "window_selected_keys") \
            or "selected_keys" not in (getattr(model, "state_", None) or {}):
        return None
    steps += run.counters.get("steps_traced") or 0
    queries = (steps * run.counters["rows"] * int(run.cell.traffic["seq_len"])
               * model.config.kinds.count("sparse_attention"))
    return family.window_selected_keys(model) / queries
