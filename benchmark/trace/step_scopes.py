"""Device ops by the layer that asked for them, for every model family.

`trace/scopes.py` (which this file reuses and does not replace) looks a
trace event's scope up in the compiled step's text and gets that text from
`family.LAST_LOWERED`, which only the decoder families set.  Here the text
comes from the PROGRAM: `deeplearning4j_tpu.monitor.lowered_step()`, the
`jax.stages.Lowered` of the train step the process compiled last, lowered
for the shapes and shardings it ran at (a mesh step with its mesh).  A
program without that handle (the parent of PR 35) falls back to the
family's `LAST_LOWERED`, and to nothing where there is none.

Stale names.  jax's persistent compilation cache leaves metadata out of its
key, so a step whose text equals an older tree's but for its scopes comes
back from a cache that tree filled with THAT tree's `op_name`s.  Every
train step of a program with the handle has the scope `updater`; if the
compiled text holds none, the cache's entries of the step's module are
removed, jax's in-process caches dropped (`Lowered.compile` would hand back
the running executable) and the step compiled once more — the same program
under the same options, so the same instruction names — which also leaves
the cache with the right names for the next process: its profiles, and the
readers of `trace/scopes.py`.  That happens here, after the window, and the
log says so and how long it took.

What starts here: `ms_per_step(run, scope)` and its `backward_only` half,
`unscoped_ms_per_step`, and three log lines a run — device time by top
scope, the ten longest device ops of the window each with its scope, and
the ten longest that no scope covers, each with its result's shape.
"""
from __future__ import annotations

import glob
import os
import re
import time
from typing import Dict, List, Optional

from benchmark.harness import say
from benchmark.trace import reduce
from benchmark.trace.scopes import (ScopedEvent, in_scope,
                                    scopes_from_hlo_text, self_seconds)

EVERY_STEP_HAS = "updater"
# path components that autodiff and control flow make, not `named_scope`
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_JAX_MADE = {"while", "body", "cond", "closed_call", "checkpoint",
             "rematted_computation", "custom_jvp_call", "custom_vjp_call"}
_MODULE = re.compile(r"^module @([\w.\-]+)")


def _components(path: str) -> List[str]:
    """The path cut at the slashes outside parentheses."""
    out, depth, at = [], 0, 0
    for i, ch in enumerate(path):
        depth += (ch == "(") - (ch == ")")
        if ch == "/" and depth == 0:
            out.append(path[at:i])
            at = i + 1
    return out + [path[at:]]


def top_scope(path: str) -> str:
    """The outermost scope of the program's in an `op_name`: its first
    component beneath the step's `jit(...)` that neither autodiff
    (`jvp(x)`, `transpose(jvp(x))`: `x` counts), control flow nor
    checkpointing made; of `Kind/name` the kind.  "" where there is none
    before the primitive's own name or a jitted library function's."""
    parts = _components(path)
    for part in parts[1:-1] if parts[0].startswith("jit(") else []:
        m = _WRAPPER.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPER.match(part)
        if not part or part in _JAX_MADE or part.startswith("branch_"):
            continue
        if "(" in part:                 # jit(_threefry_split), ...
            return ""
        return part.split("/")[0]
    return ""


def _program_lowered():
    """`monitor.lowered_step()`, or None where the program has no handle or
    nothing to hand out."""
    try:
        from deeplearning4j_tpu import monitor
    except ImportError:
        return None
    hand_out = getattr(monitor, "lowered_step", None)
    return hand_out() if hand_out is not None else None


def _family_lowered(run):
    import sys
    family = sys.modules.get("benchmark.models." + run.cell.config["family"])
    return getattr(family, "LAST_LOWERED", None)


def has_scope(names: Dict[str, str], scope: str) -> bool:
    return any(in_scope(path, scope) for path in names.values())


def forget_cached(lowered) -> int:
    """Remove the persistent compilation cache's entries of `lowered`'s
    module (`<module>-<key>-cache` and its `-atime`); how many went."""
    import jax
    cache_dir = jax.config.jax_compilation_cache_dir
    module = _MODULE.match(lowered.as_text())
    if not cache_dir or module is None:
        return 0
    gone = 0
    for path in glob.glob(os.path.join(
            cache_dir, glob.escape(module.group(1)) + "-*")):
        try:
            os.remove(path)
            gone += path.endswith("-cache")
        except OSError:
            pass
    return gone


def step_names(run) -> Optional[Dict[str, str]]:
    """Instruction name -> `op_name` of the compiled train step; None where
    neither the program nor the family hands out a step.  Worked out once a
    run, after the window."""
    if not hasattr(run, "_step_names"):
        run._step_names = _step_names(run)
    return run._step_names


def _step_names(run) -> Optional[Dict[str, str]]:
    t0 = time.perf_counter()
    lowered, whose = _program_lowered(), "program's"
    if lowered is None:
        lowered, whose = _family_lowered(run), "family's lowered"
        if lowered is None:
            return None
    names = scopes_from_hlo_text(lowered.compile().as_text())
    took = time.perf_counter() - t0
    # a family's step is a program's without the handle, so without the
    # scope every step has since: nothing to tell stale names by
    if whose != "program's" or has_scope(names, EVERY_STEP_HAS):
        say(f"step scopes: the {whose} step, {len(names)} instructions "
            f"with an op_name, {took:.1f} s to lower, compile (a cache hit "
            f"where the cache is on) and print")
        return names
    import jax
    t1 = time.perf_counter()
    gone = forget_cached(lowered)
    jax.clear_caches()
    fresh = scopes_from_hlo_text(_program_lowered().compile().as_text())
    say(f"step scopes: STALE NAMES: the compiled step's text holds no "
        f"`{EVERY_STEP_HAS}` scope ({took:.1f} s to get it): a compilation "
        f"cache filled by a tree without the scopes.  Removed {gone} "
        f"entries of the step's module from the cache, dropped jax's "
        f"in-process caches and compiled once more, "
        f"{time.perf_counter() - t1:.1f} s: "
        + ("the scopes are there now" if has_scope(fresh, EVERY_STEP_HAS)
           else "still none, the readers go on with what there is"))
    return fresh


def scoped_events(run) -> Optional[List[ScopedEvent]]:
    """The first chip's device ops inside the traced window, each with its
    `op_name` ("" where the text has none); None on an untraced run, without
    a device trace, or where no step's text can be had.  Worked out once a
    run; logs the split by top scope and the longest ops."""
    if not hasattr(run, "_step_scoped_events"):
        run._step_scoped_events = _scoped_events(run)
    return run._step_scoped_events


def _scoped_events(run) -> Optional[List[ScopedEvent]]:
    steps = run.counters.get("steps_traced")
    if run.trace is None or not run.traced or run.trace_dir is None \
            or not steps:
        return None
    names = step_names(run)
    if names is None:
        return None
    trace = reduce.load_xplane(reduce.find_xplane(run.trace_dir))
    tied = reduce.align(trace, run.clock.marks, run.clock.spans)
    if tied is None or not trace.device_ops:
        return None
    ops = reduce.clip(trace.device_ops[min(trace.device_ops)], *tied[0])
    out = [ScopedEvent(e, names.get(e.name, "")) for e in ops]
    known = sum(1 for s in out if s.scope)
    say(f"step scopes: {known} of {len(out)} device ops in the window carry "
        f"an op_name ({len(names)} instructions in the step's text)")
    timed = self_times(out)
    say("device time by top scope, first chip, ms a step: "
        + ", ".join(f"{k or '(none)'} {v:.2f}"
                    for k, v in by_top_scope(timed, steps)))
    say("longest device ops, ms a step, with their scopes: " + "; ".join(
        f"{name} {ms:.2f} {path or '(no op_name)'}"
        for name, ms, path in top_ops(timed, steps)))
    shape = {s.event.name: s.event.text.split("(")[0] for s in out}
    say("longest device ops under no scope, ms a step: " + "; ".join(
        f"{name} {ms:.2f} {shape[name]} {path or '(no op_name)'}"
        for name, ms, path in top_ops(
            timed, steps, keep=lambda s: not top_scope(s.scope))))
    return out


def self_times(events: List[ScopedEvent]) -> List[tuple]:
    """(scoped event, self seconds) of every op but the control ones: what
    `scopes.self_seconds` sums, kept apart for the tables."""
    scope_of = {id(s.event): s for s in events}
    return [(scope_of[id(e)], sec)
            for e, sec in reduce.self_times([s.event for s in events])
            if reduce.op_class(e) != "control"]


def by_top_scope(timed: List[tuple], steps: int) -> List[tuple]:
    """(top scope, ms a step) of `self_times`' pairs, longest first; ""
    collects the unscoped."""
    total: Dict[str, float] = {}
    for s, sec in timed:
        k = top_scope(s.scope)
        total[k] = total.get(k, 0.0) + 1e3 * sec / steps
    return sorted(total.items(), key=lambda kv: -kv[1])


def top_ops(timed: List[tuple], steps: int, n: int = 10,
            keep=None) -> List[tuple]:
    """The `n` longest device ops by summed self time, of those `keep`
    takes of `self_times`' pairs: (instruction name, ms a step,
    `op_name`)."""
    total: Dict[str, float] = {}
    path = {}
    for s, sec in timed:
        if keep is None or keep(s):
            total[s.event.name] = total.get(s.event.name, 0.0) + sec
            path[s.event.name] = s.scope
    longest = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(name, 1e3 * sec / steps, path[name]) for name, sec in longest]


def ms_per_step(run, scope: str, backward_only: bool = False
                ) -> Optional[float]:
    """Self time a step of the ops under `scope`: forward, backward and
    recomputation, or with `backward_only` the ops autodiff put under
    `transpose(` alone."""
    events = scoped_events(run)
    if not events:
        return None
    sec = self_seconds(events, lambda s: in_scope(s.scope, scope) and (
        not backward_only or "transpose(" in s.scope))
    return 1e3 * sec / run.counters["steps_traced"] if sec else None


def unscoped_ms_per_step(run) -> Optional[float]:
    """Self time a step of the ops no scope of the program's covers: no
    `op_name`, or none with a scope beneath `jit(...)` (`top_scope`).  None
    against a program without the `updater` scope: there, every op of the
    updater would count."""
    events = scoped_events(run)
    if not events or not any(in_scope(s.scope, EVERY_STEP_HAS)
                             for s in events):
        return None
    sec = self_seconds(events, lambda s: not top_scope(s.scope))
    return 1e3 * sec / run.counters["steps_traced"]
