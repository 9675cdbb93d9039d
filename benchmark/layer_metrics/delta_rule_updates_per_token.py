"""State updates a token under Kimi delta attention: the (token, held head)
pairs whose update the KDA layers ran with a step above zero, from the
program's device counter (`DecoderModel.state_["delta_rule_updates"]`, kept
on the device by the train step), the measured window's start and end read
in one transfer after the window (the family's `window_delta_rule_updates`;
the family is the cell's, `harness.load_family`), over the window's steps
(untraced stretch and traced) and a step's tokens.  The held heads times the
KDA layers where nothing is skipped: 8 x 3 = 24 in Solar Open 2's cell.  A
guard, as `mosaic_calls_in_step` is — a change that gets faster by updating
less shows here."""
from benchmark import harness


def read(run):
    steps = run.counters.get("steps")
    if not steps:
        return None
    family = harness.load_family(run.cell.config)
    model = getattr(family, "LAST_BUILT", None)
    if not hasattr(family, "window_delta_rule_updates") \
            or "delta_rule_updates" not in (getattr(model, "state_", None)
                                            or {}):
        return None
    steps += run.counters.get("steps_traced") or 0
    tokens = steps * run.counters["rows"] * int(run.cell.traffic["seq_len"])
    return family.window_delta_rule_updates(model) / tokens
