"""The program's own host spans, laid over the device trace.

The program keeps a ring of the last 8,192 host intervals it timed
(`deeplearning4j_tpu.monitor.recorded`), on `time.perf_counter`: the clock of
`harness.TraceClock`, so the two marker runs that tie the benchmark's spans
to the trace's clock (`reduce.align`) tie the program's as well.  On the
train path the fit thread records `fit_epoch` (the whole fit loop),
`input_wait` and `input_stage` (inside `DevicePrefetchIterator`, so under
`ParallelWrapper.fit_prefetched` too, which the benchmark cannot wrap) and
`step_dispatch` (around the call of the compiled step).

`collect(run)` is shared by the five readers in `layer_metrics/` that start
from it and is worked out once per run.  The three `*_ms_per_step` readers
need only the ring and the two marks; the two `idle_gap_*` readers also need
a device trace.  Against a program without the ring (`monitor` has no
`recorded`) everything here reads nothing.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from benchmark.harness import say
from benchmark.trace import reduce

STEP = "step_dispatch"
INPUT = ("input_wait", "input_stage")
OUTER = "fit_epoch"      # a gap only this covers is not explained
NONE = "none"            # `reduce.attribute_gap`'s word for no span at all


@dataclasses.dataclass
class ProgramSpans:
    window_s: float                  # host clock, first mark to last mark
    steps: int                       # `step_dispatch` spans in the window
    host_s: Dict[str, float]         # fit thread's seconds by span name
    # worst chip's idle seconds in gaps >= `reduce.MIN_GAP_S` inside the
    # marker window, by the innermost program span over each stretch of a
    # gap (`_idle_gaps`); None where there is no device trace
    idle_s: Optional[Dict[str, float]] = None

    def ms_per_step(self, *names: str) -> float:
        return 1e3 * sum(self.host_s.get(n, 0.0) for n in names) / self.steps

    def idle_pct(self, *names: str) -> Optional[float]:
        """Share of the attributed idle seconds under `names`."""
        if self.idle_s is None:
            return None
        total = sum(self.idle_s.values())
        if not total:
            return 0.0
        return 100.0 * sum(self.idle_s.get(n, 0.0) for n in names) / total


def collect(run) -> Optional[ProgramSpans]:
    """What the ring holds of the run's traced window; None on an untraced
    run, without two marks, or where the fit thread recorded no step."""
    if not hasattr(run, "_program_spans"):
        run._program_spans = _collect(run)
    return run._program_spans


def _collect(run) -> Optional[ProgramSpans]:
    if not run.traced or run.clock is None or len(run.clock.marks) < 2:
        return None
    from deeplearning4j_tpu import monitor
    if not hasattr(monitor, "recorded"):
        return None
    m0, m1 = run.clock.marks[0], run.clock.marks[-1]
    records = monitor.recorded(m0, m1)
    threads = [r.thread_ident for r in records if r.name == STEP]
    if not threads:
        return None
    fit_thread = max(set(threads), key=threads.count)
    spans = [(r.name, max(r.t0, m0), min(r.t1, m1)) for r in records
             if r.thread_ident == fit_thread]
    out = ProgramSpans(window_s=m1 - m0, steps=threads.count(fit_thread),
                       host_s=_seconds_by_name(spans))
    gaps = _idle_gaps(run, spans) if run.trace is not None else None
    if gaps is not None:
        out.idle_s = {}
        for _, _, under in gaps:
            for who, sec in under.items():
                out.idle_s[who] = out.idle_s.get(who, 0.0) + sec
    say(_line(out, gaps, run.clock))
    return out


def _seconds_by_name(spans) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for name, t0, t1 in spans:
        out[name] = out.get(name, 0.0) + t1 - t0
    return out


def _idle_gaps(run, spans
               ) -> Optional[List[Tuple[float, float, Dict[str, float]]]]:
    """Each idle gap >= `reduce.MIN_GAP_S` of the worst chip inside the
    marker window: (start, end, seconds by program span), in seconds into
    that window; None when the trace does not hold the two marker runs.

    A gap is cut at every span edge inside it and each piece is attributed
    (`reduce.attribute_gap`: the innermost span over it), so a long gap the
    fit thread crossed many spans in — the pipeline filling at the window's
    start — is shared out among them by time, not put down to the one outer
    span that alone covers half of it."""
    trace = reduce.load_xplane(reduce.find_xplane(run.trace_dir))
    tied = reduce.align(trace, run.clock.marks, spans)
    if tied is None:
        return None
    t0, t1 = tied[0]
    busy = run.trace.busy_s                # `reduce.reduce` over this window
    ops = trace.device_ops[min(busy, key=busy.get)]
    host = reduce.clip(trace.host_spans, t0, t1)
    edges = sorted({t for s in host for t in (s.start, s.end)})
    out = []
    for g0, g1 in reduce.idle_gaps(ops, t0, t1):
        if g1 - g0 < reduce.MIN_GAP_S:
            continue
        cuts = [g0] + [t for t in edges if g0 < t < g1] + [g1]
        out.append((g0 - t0, g1 - t0, _seconds_by_name(
            (reduce.attribute_gap(piece, host), *piece)
            for piece in zip(cuts, cuts[1:]))))
    return out


def _line(p: ProgramSpans, gaps, clock) -> str:
    """The table for the reader of the log (`breakdown` keeps the
    benchmark's own spans): the fit thread's seconds by program span, beside
    them the benchmark's own spans of the same window (they time the same
    layers from outside), idle seconds by program span, and the three
    longest gaps with where they lie."""
    def table(d):
        return ", ".join(f"{n} {s:.4f}" for n, s in sorted(
            d.items(), key=lambda kv: -kv[1])) or "nothing"
    m0, m1 = clock.marks[0], clock.marks[-1]
    line = (f"program spans: {p.steps} {STEP} in the window of "
            f"{p.window_s:.3f} s; fit thread's seconds by span: "
            f"{table(p.host_s)}; the benchmark's own spans there: "
            + table(_seconds_by_name((n, max(a, m0), min(b, m1))
                                     for n, a, b in clock.spans
                                     if b > m0 and a < m1)))
    if gaps is None:
        return line + "; no device trace to lay them over"
    line += (f"; worst chip's idle seconds in gaps >= "
             f"{1e6 * reduce.MIN_GAP_S:.0f} us by program span: "
             f"{table(p.idle_s)}; {len(gaps)} such gaps")
    for g0, g1, under in sorted(gaps, key=lambda g: g[0] - g[1])[:3]:
        line += (f"; {1e3 * (g1 - g0):.2f} ms at {1e3 * g0:+.1f} ms into "
                 f"the window: {table(under)}")
    return line
