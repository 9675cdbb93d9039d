"""Device ops by the program's named scopes.

The program marks its layers with `jax.named_scope` (`mla_attention`, `moe`
and beneath it `router`, `dispatch`, `experts`, `combine`, `shared`).  A
scope reaches the compiled program as the `op_name` of each instruction's
metadata — `jit(step)/while/body/moe/experts/dot_general`, and in the
backward pass `jit(step)/transpose(jvp(mla_attention))/mul` — but not the
profiler's trace, whose events carry the instruction's name and text alone
(seen by hand, PR 27).  So the scope of a trace event is looked up by the
instruction's name in the compiled step's text.  That text comes from
compiling the step the model family lowered for the driver
(`family.LAST_LOWERED`) once more, after the window: the same program, so
the same names, and a hit in jax's compilation cache where that is kept.  A
fusion has the `op_name` of one of the instructions fused into it; a fusion
across a scope's edge is counted on one side of it.

Against a family that lowers nothing, or a program without the scopes, the
readers that start here read nothing.
"""
from __future__ import annotations

import dataclasses
import re
import sys
from typing import Callable, Dict, List, Optional

from benchmark.harness import say
from benchmark.trace import reduce

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')


@dataclasses.dataclass(frozen=True)
class ScopedEvent:
    event: reduce.Event
    scope: str              # the instruction's `op_name`; "" where unknown


def scopes_from_hlo_text(text: str) -> Dict[str, str]:
    """Instruction name -> `op_name`, for every instruction that has one."""
    out = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def in_scope(path: str, scope: str) -> bool:
    """Whether `scope` is a component of the scope path: between slashes, or
    inside the `jvp(...)`/`transpose(...)` wrappers autodiff puts around a
    component."""
    return re.search(r"(?<![\w.\-])" + re.escape(scope) + r"(?![\w.\-])",
                     path) is not None


def scoped_events(run) -> Optional[List[ScopedEvent]]:
    """The first chip's device ops inside the traced window, each with its
    scope; None on an untraced run or where the step's text cannot be had.
    Worked out once a run."""
    if not hasattr(run, "_scoped_events"):
        run._scoped_events = _scoped_events(run)
    return run._scoped_events


def _scoped_events(run) -> Optional[List[ScopedEvent]]:
    if run.trace is None or not run.traced or run.trace_dir is None:
        return None
    family = sys.modules.get("benchmark.models." + run.cell.config["family"])
    lowered = getattr(family, "LAST_LOWERED", None)
    if lowered is None:
        return None
    names = scopes_from_hlo_text(lowered.compile().as_text())
    trace = reduce.load_xplane(reduce.find_xplane(run.trace_dir))
    tied = reduce.align(trace, run.clock.marks, run.clock.spans)
    if tied is None or not trace.device_ops:
        return None
    ops = reduce.clip(trace.device_ops[min(trace.device_ops)], *tied[0])
    out = [ScopedEvent(e, names.get(e.name, "")) for e in ops]
    known = sum(1 for s in out if s.scope)
    say(f"scopes: {known} of {len(out)} device ops in the window carry an "
        f"op_name ({len(names)} instructions in the step's text)")
    return out


def self_seconds(events: List[ScopedEvent],
                 keep: Callable[[ScopedEvent], bool]) -> float:
    """Summed self time (`reduce.self_times`: a `while` counts what its body
    leaves of it) of the events `keep` takes, control ops left out."""
    scope_of = {id(s.event): s for s in events}
    total = 0.0
    for e, sec in reduce.self_times([s.event for s in events]):
        if reduce.op_class(e) != "control" and keep(scope_of[id(e)]):
            total += sec
    return total


def ms_per_step(run, scope: str) -> Optional[float]:
    """Self time a step of the ops under `scope`, forward and backward."""
    steps = run.counters.get("steps_traced")
    events = scoped_events(run) if steps else None
    if not events:
        return None
    sec = self_seconds(events, lambda s: in_scope(s.scope, scope))
    return 1e3 * sec / steps if sec else None


def kernel_roofline_pct(run, scope: str, work: dict) -> Optional[float]:
    """Share of the roofline of the Mosaic kernels under `scope`: the least
    time the chip could take for `work` (`flops`, `bytes` over the whole
    traced window) — the larger of operations over the bf16 peak and bytes
    over the memory bandwidth — over the kernels' device time."""
    events = scoped_events(run)
    if not events:
        return None
    sec = self_seconds(events, lambda s: in_scope(s.scope, scope)
                       and reduce.op_class(s.event) == "mosaic")
    if not sec:
        return None
    least = max(work["flops"] / run.peaks["bf16_flops_per_s"],
                work["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / sec
