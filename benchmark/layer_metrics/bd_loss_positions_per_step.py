"""Positions that carried loss a step under the block-diffusion objective:
those the step's noise replaced by the mask token, from the program's
device counter (`DecoderModel.state_["masked_positions"]`, kept on the
device by the train step), the measured window's start and end read in one
transfer after the window (the family's `window_masked_positions`; the
family is the cell's, `harness.load_family`), over the window's steps,
untraced stretch and traced.  About half the clean tokens (t is uniform): a
guard, as `mosaic_calls_in_step` is — a change that gets faster by replacing
fewer positions shows here."""
from benchmark import harness


def read(run):
    steps = run.counters.get("steps")
    if not steps:
        return None
    family = harness.load_family(run.cell.config)
    model = getattr(family, "LAST_BUILT", None)
    if not hasattr(family, "window_masked_positions") \
            or "masked_positions" not in (getattr(model, "state_", None)
                                          or {}):
        return None
    steps += run.counters.get("steps_traced") or 0
    return family.window_masked_positions(model) / steps
