"""Device time a step under the block-diffusion objective's own parts: self
time of the device ops whose scope has `bd_noise` (the draws of t, the
replaced ids, the 2L-row input), `diffusion_loss` (the weighted
cross-entropy on the noisy half and the auxiliary term) or `lm_head` (the
head on the noisy half's rows) in it, forward and backward, first chip of
the traced window, over its steps (`benchmark/trace/scopes.py`).  A program
without the first two scopes is not trained by diffusion: nothing to
read."""
from benchmark.trace.scopes import ms_per_step


def read(run):
    parts = [ms_per_step(run, scope)
             for scope in ("bd_noise", "diffusion_loss", "lm_head")]
    if parts[0] is None and parts[1] is None:
        return None
    return sum(p for p in parts if p is not None)
