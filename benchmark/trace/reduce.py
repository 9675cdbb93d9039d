"""From a profiler trace to numbers.  Kept with the benchmark so that every
PR reduces its trace in the same way.

The arithmetic (`reduce`) works on plain `Event` lists, so the tests check it
against hand-built traces with known answers; `load_xplane` turns the
`.xplane.pb` that `jax.profiler` writes into those lists with nothing but
jax (`jax.profiler.ProfileData`).

What a TPU trace looks like (seen by hand, PR 22): one plane per chip named
`/device:TPU:<n>`.  Its line `XLA Ops` holds one event per executed HLO
instruction, nested where an instruction (a `while`, a call) runs others; an
event's name is the instruction's whole text, `%fusion.178 = f32[16,512,768]
fusion(...), kind=kOutput, calls=...`: the part before ` = ` is kept as the
name, the rest as the text the classification reads.  Its line `XLA Modules`
holds one event per executed program, `jit_step(<fingerprint>)`.

The host plane is not read.  At the tracer level that records
`TraceAnnotation`s the TPU runtime also records one event per inner call of
the layout transposition it does for every host-to-device copy of an image
batch: 30 million events, 1.1 GB and 0.5 s of host time a step for
ResNet-50 (my chip run, PR 22) — the trace then measures the tracer.  So the
host tracer is off, the benchmark keeps its own host spans on the host clock,
and two runs of a tiny marker program (`bench_marker`), one before and one
after the window, tie that clock to the trace's: `align`.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARKER = "bench_marker"          # the jitted function's name; see `align`

COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute",
                       "collective-broadcast")
# instructions that only run other instructions: their own time is what
# their children leave
CONTROL_PREFIXES = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float        # seconds on the trace's clock
    end: float
    text: str = ""      # device ops: the instruction's text after ` = `

    @property
    def duration(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    device_ops: Dict[int, List[Event]]   # chip ordinal -> its `XLA Ops`
    host_spans: List[Event]              # the benchmark's spans, trace clock
    modules: Dict[int, List[Event]] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: Dict[int, float]             # per chip, union of op intervals
    busy_s_mean: float
    idle_pct_worst: float                # 100 * (1 - busy/window), worst chip
    mxu_pct: float                       # share of chip 0's op self-time
    collective_s: float                  # chip 0, summed self-time
    category_s: Dict[str, float]         # chip 0, self-time by class
    top_ops: List[Tuple[str, float]]     # chip 0, self-time by name, top 10
    top_gaps: List[Tuple[str, float]]    # worst chip's idle time in gaps of
                                         # >= MIN_GAP_S, by host span, top 10


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to [t0, t1]; those wholly outside are dropped."""
    out = []
    for e in events:
        s, t = max(e.start, t0), min(e.end, t1)
        if t > s:
            out.append(dataclasses.replace(e, start=s, end=t))
    return out


def union_intervals(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """The disjoint intervals some event covers, in order."""
    merged: List[List[float]] = []
    for s, t in sorted((e.start, e.end) for e in events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_seconds(events: Iterable[Event]) -> float:
    return sum(t - s for s, t in union_intervals(events))


def idle_gaps(events: Iterable[Event], t0: float,
              t1: float) -> List[Tuple[float, float]]:
    """The stretches of [t0, t1] no event covers."""
    gaps, at = [], t0
    for s, t in union_intervals(clip(events, t0, t1)):
        if s > at:
            gaps.append((at, s))
        at = max(at, t)
    if t1 > at:
        gaps.append((at, t1))
    return gaps


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event's duration less what the events nested in it cover.  Device
    lines nest (a `while` holds its body's ops); summing durations would
    count the body twice."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    selfs = [e.duration for e in order]
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= min(e.end, order[stack[-1]].end) - e.start
        stack.append(i)
    return [(e, max(s, 0.0)) for e, s in zip(order, selfs)]


# ---------------------------------------------------------------------------
# classification of device ops
# ---------------------------------------------------------------------------

def op_class(e: Event) -> str:
    """`mxu` (a convolution, a dot, or a fusion that holds one), `collective`,
    `mosaic` (a Pallas kernel), `control`, `copy`, or `other`, from the
    instruction's name and text.  On the TPU a dot is a convolution, and a
    fusion around one is an output fusion (`kind=kOutput`) whatever XLA
    named it."""
    name, text = e.name.lower(), e.text.lower()
    if name.startswith(COLLECTIVE_PREFIXES):
        return "collective"
    if name.startswith(CONTROL_PREFIXES):
        return "control"
    if "custom_call" in name or "custom-call" in name \
            or "tpu_custom_call" in text:
        return "mosaic"
    if name.startswith(("convolution", "dot")) or " convolution(" in text \
            or " dot(" in text or "kind=koutput" in text:
        return "mxu"
    if name.startswith(("copy", "transpose", "bitcast")):
        return "copy"
    return "other"


# ---------------------------------------------------------------------------
# host attribution
# ---------------------------------------------------------------------------

def attribute_gap(gap: Tuple[float, float],
                  host_spans: Iterable[Event]) -> str:
    """What the host was doing in an idle gap: the shortest (innermost) host
    span that covers at least half of it; failing that, the span that
    overlaps most of it; `none` when no span touches it."""
    g0, g1 = gap
    half = 0.5 * (g1 - g0)
    inner, most = None, None
    for s in host_spans:
        overlap = min(s.end, g1) - max(s.start, g0)
        if overlap <= 0:
            continue
        if overlap >= half and (inner is None or s.duration < inner.duration):
            inner = s
        if most is None or overlap > most[0]:
            most = (overlap, s)
    if inner is not None:
        return inner.name
    return most[1].name if most else "none"


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def align(trace: Trace, host_marks: Sequence[float],
          host_spans: Sequence[Tuple[str, float, float]]
          ) -> Optional[Tuple[Tuple[float, float], float]]:
    """Tie the host clock to the trace's; returns (window, skew).

    `host_marks` are the host times at which the first and the last run of
    the marker program were seen to be done (`block_until_ready` returned);
    the trace holds the same two runs as module events on the first chip.
    The first pair gives the offset between the clocks, and `host_spans`
    (name, start, end on the host clock) are moved onto the trace's clock
    into `trace.host_spans`.  The window is what lies between the two marker
    runs; the skew is the seconds by which the second pair disagrees with
    the offset.  None when the trace does not hold two marker runs."""
    if not trace.modules or len(host_marks) < 2:
        return None
    marks = sorted((e for e in trace.modules[min(trace.modules)]
                    if MARKER in e.name), key=lambda e: e.start)
    if len(marks) < 2:
        return None
    offset = marks[0].end - host_marks[0]
    trace.host_spans = [Event(n, a + offset, b + offset)
                        for n, a, b in host_spans]
    return ((marks[0].end, marks[-1].start),
            marks[-1].end - host_marks[-1] - offset)


MIN_GAP_S = 100e-6     # shorter gaps are the device's own turn-around


def reduce(trace: Trace, window: Optional[Tuple[float, float]] = None,
           n_top: int = 10) -> Optional[Reduced]:
    """Reduce a trace over `window` (default: from the first device op's
    start to the last one's end).  None when no chip ran anything in it."""
    if not trace.device_ops:
        return None
    if window is None:
        every = [e for evs in trace.device_ops.values() for e in evs]
        if not every:
            return None
        window = (min(e.start for e in every), max(e.end for e in every))
    t0, t1 = window
    ops = {d: clip(evs, t0, t1) for d, evs in trace.device_ops.items()}
    ops = {d: evs for d, evs in ops.items() if evs}
    if not ops:
        return None
    window_s = t1 - t0
    busy = {d: busy_seconds(evs) for d, evs in ops.items()}
    worst = min(busy, key=busy.get)
    first = min(ops)                       # "device 0"
    by_class: Dict[str, float] = {}
    by_name: Dict[str, float] = {}
    for e, s in self_times(ops[first]):
        c = op_class(e)
        by_class[c] = by_class.get(c, 0.0) + s
        if c != "control":
            by_name[e.name] = by_name.get(e.name, 0.0) + s
    work = sum(v for c, v in by_class.items() if c != "control")
    spans = clip(trace.host_spans, t0, t1)
    by_span: Dict[str, float] = {}
    for g in idle_gaps(ops[worst], t0, t1):
        if g[1] - g[0] >= MIN_GAP_S:
            who = attribute_gap(g, spans)
            by_span[who] = by_span.get(who, 0.0) + g[1] - g[0]
    return Reduced(
        window_s=window_s, busy_s=busy,
        busy_s_mean=sum(busy.values()) / len(busy),
        idle_pct_worst=100.0 * (1.0 - busy[worst] / window_s),
        mxu_pct=100.0 * by_class.get("mxu", 0.0) / work if work else 0.0,
        collective_s=by_class.get("collective", 0.0),
        category_s=by_class,
        top_ops=sorted(by_name.items(), key=lambda kv: -kv[1])[:n_top],
        top_gaps=sorted(by_span.items(), key=lambda kv: -kv[1])[:n_top])


def breakdown(r: Reduced) -> dict:
    """The `breakdown` of a traced run's result line."""
    return {"device_ops": [[n, s] for n, s in r.top_ops],
            "idle_gaps": [[n, s] for n, s in r.top_gaps]}


# ---------------------------------------------------------------------------
# reading the profiler's file
# ---------------------------------------------------------------------------

def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load_xplane(path: str) -> Trace:
    """Device ops and executed modules per chip, seconds on the trace's
    clock.  `host_spans` is left empty: see `align`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        chip = int(plane.name[len(DEVICE_PLANE_PREFIX):].split()[0])
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules[chip] = [
                    Event(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events]
            elif line.name == OPS_LINE:
                evs = device_ops.setdefault(chip, [])
                for e in line.events:
                    name, _, text = e.name.partition(" = ")
                    evs.append(Event(
                        name.lstrip("%"), e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9, text))
    return Trace(device_ops, [], modules)


def describe_xplane(path: str, n: int = 3) -> str:
    """A page about a trace file, for the eye: planes, lines, how many events
    each holds, its most frequent names, and its `n` longest events with
    their stats.  Look at one before trusting `load_xplane`."""
    import collections
    from jax.profiler import ProfileData
    out = [f"{path}: {os.path.getsize(path) / 2**20:.1f} MiB"]
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            common = collections.Counter(
                e.name[:40] for e in evs).most_common(4)
            out.append(f"  LINE {line.name}: {len(evs)} events, most "
                       f"frequent {common}")
            for e in sorted(evs, key=lambda e: -e.duration_ns)[:n]:
                stats = {k: str(v)[:60] for k, v in e.stats}
                out.append(f"    {e.name[:100]} start={e.start_ns:.0f}ns "
                           f"dur={e.duration_ns:.0f}ns {stats}")
    return "\n".join(out)
