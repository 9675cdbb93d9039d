"""Process-wide metrics registry: counters, gauges, ring-buffer histograms.

The reference stack scattered its observability over four incompatible
stores (StatsListener/StatsStorage, OpProfiler, PerformanceTracker and the
serving-side SLO hub); this module is the one place a process answers
"what am I doing right now".  Design constraints, in order:

1. **Near-zero cost when idle.**  Recording is one module-flag load, one
   lock acquire and one int/float op.  `set_enabled(False)` turns every
   record call into the flag load alone, so instrumented hot paths cost
   no more than that check when telemetry is off.
2. **Thread-safe.**  Training, the prefetch producer, the serving batcher
   worker and the UI server all record concurrently; every metric guards
   its state with its own lock (no global lock on the record path).
3. **Labeled series, Prometheus semantics.**  A metric family (name, type,
   help) fans out into children keyed by a frozen label set; get-or-create
   returns the same child for the same (name, labels), which is what lets
   independent subsystems (two ModelServers, N models) share one registry
   without trampling each other — they differ by label, not by store.
4. **Bounded memory.**  Histograms keep a ring buffer of the last `maxlen`
   observations (percentiles over a sliding window, like the serving
   LatencyWindow they generalize) plus lifetime count/sum/max.

Everything here is stdlib-only and imports nothing from the rest of the
package, so any layer (utils, data, nn, serving, ui) may depend on it
without cycles.
"""
from __future__ import annotations

import math
import threading
from collections import deque
from typing import Dict, Iterable, List, Optional, Tuple

# ---------------------------------------------------------------------------
# Global kill-switch
# ---------------------------------------------------------------------------

_ENABLED = True


def set_enabled(on: bool) -> None:
    """Process-wide telemetry switch.  Off: every Counter.inc / Gauge.set /
    Histogram.observe returns after a single flag check (spans also skip
    their TraceAnnotation)."""
    global _ENABLED
    _ENABLED = bool(on)


def enabled() -> bool:
    return _ENABLED


# ---------------------------------------------------------------------------
# Metric primitives
# ---------------------------------------------------------------------------

Labels = Tuple[Tuple[str, str], ...]


def _freeze_labels(labels: Optional[Dict[str, str]]) -> Labels:
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


class Counter:
    """Thread-safe monotonically increasing counter."""

    def __init__(self, name: str = "counter",
                 labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.labels = _freeze_labels(labels)
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> int:
        if not _ENABLED:
            return self._value
        with self._lock:
            self._value += n
            return self._value

    @property
    def value(self) -> int:
        with self._lock:
            return self._value

    def reset(self) -> None:
        with self._lock:
            self._value = 0

    def __repr__(self) -> str:   # pragma: no cover - debug aid
        return f"Counter({self.name}={self.value})"


class Gauge:
    """Thread-safe point-in-time value (queue depth, replica count, ...)."""

    def __init__(self, name: str = "gauge",
                 labels: Optional[Dict[str, str]] = None):
        self.name = name
        self.labels = _freeze_labels(labels)
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        if not _ENABLED:
            return
        with self._lock:
            self._value = float(v)

    def set_max(self, v: float) -> None:
        """Ratchet: keep the running peak (queue-depth high-water marks)."""
        if not _ENABLED:
            return
        with self._lock:
            if v > self._value:
                self._value = float(v)

    def inc(self, n: float = 1.0) -> None:
        if not _ENABLED:
            return
        with self._lock:
            self._value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value

    def reset(self) -> None:
        with self._lock:
            self._value = 0.0

    def __repr__(self) -> str:   # pragma: no cover - debug aid
        return f"Gauge({self.name}={self.value})"


def _percentile(sorted_vals: List[float], p: float) -> float:
    """Nearest-rank percentile over an already-sorted sample list (the
    serving LatencyWindow convention, kept so its view stays bit-equal)."""
    if not sorted_vals:
        return float("nan")
    k = max(0, min(len(sorted_vals) - 1,
                   int(round(p / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[k]


class Histogram:
    """Sliding-window distribution: ring buffer of the last `maxlen`
    observations (flat memory and percentile cost under sustained traffic)
    plus lifetime count / sum / max for throughput accounting."""

    def __init__(self, name: str = "histogram",
                 labels: Optional[Dict[str, str]] = None,
                 maxlen: int = 2048):
        self.name = name
        self.labels = _freeze_labels(labels)
        self.maxlen = int(maxlen)
        self._samples: deque = deque(maxlen=self.maxlen)
        self._lock = threading.Lock()
        self._count = 0
        self._sum = 0.0
        self._max = 0.0

    def observe(self, v: float) -> None:
        if not _ENABLED:
            return
        v = float(v)
        with self._lock:
            self._samples.append(v)
            self._count += 1
            self._sum += v
            if v > self._max:
                self._max = v

    # lifetime aggregates
    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    @property
    def max(self) -> float:
        with self._lock:
            return self._max

    def percentiles(self, ps: Iterable[float] = (50, 95, 99)
                    ) -> Dict[str, float]:
        with self._lock:
            s = sorted(self._samples)
        return {f"p{p:g}": _percentile(s, p) for p in ps}

    def bins(self, n: int = 20) -> Tuple[float, float, List[int]]:
        """(lo, hi, counts) histogram of the current window — chart fodder
        for the UI report; numpy-free so the registry stays stdlib-only."""
        with self._lock:
            s = list(self._samples)
        if not s:
            return 0.0, 0.0, [0] * n
        lo, hi = min(s), max(s)
        if hi == lo:
            hi = lo + 1e-12
        counts = [0] * n
        w = (hi - lo) / n
        for v in s:
            counts[min(int((v - lo) / w), n - 1)] += 1
        return lo, hi, counts

    def snapshot(self) -> Dict[str, float]:
        out = self.percentiles()
        with self._lock:
            out["count"] = self._count
            out["mean"] = self._sum / self._count if self._count else 0.0
            out["max"] = self._max
        return out

    def reset(self) -> None:
        with self._lock:
            self._samples.clear()
            self._count = 0
            self._sum = 0.0
            self._max = 0.0

    def __repr__(self) -> str:   # pragma: no cover - debug aid
        return f"Histogram({self.name}, n={self.count})"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_TYPES = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}


class _Family:
    __slots__ = ("name", "kind", "help", "children")

    def __init__(self, name: str, kind: str, help: str):
        self.name = name
        self.kind = kind
        self.help = help
        self.children: Dict[Labels, object] = {}


def _series_key(name: str, labels: Labels) -> str:
    if not labels:
        return name
    inner = ",".join(f'{k}="{v}"' for k, v in labels)
    return f"{name}{{{inner}}}"


def _escape_label(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class MetricsRegistry:
    """Get-or-create store of metric families.  `counter/gauge/histogram`
    return the live child for (name, labels) — same args, same object —
    so handles can be cached on hot paths and shared across subsystems."""

    def __init__(self):
        self._lock = threading.Lock()
        self._families: Dict[str, _Family] = {}

    # ---- get-or-create ----
    def _child(self, kind: str, name: str, help: str,
               labels: Optional[Dict[str, str]], **kw):
        frozen = _freeze_labels(labels)
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                fam = self._families[name] = _Family(name, kind, help)
            elif fam.kind != kind:
                raise TypeError(
                    f"metric {name!r} already registered as {fam.kind}, "
                    f"requested {kind}")
            child = fam.children.get(frozen)
            if child is None:
                child = _TYPES[kind](name, dict(frozen), **kw)
                fam.children[frozen] = child
            return child

    def counter(self, name: str, help: str = "",
                labels: Optional[Dict[str, str]] = None) -> Counter:
        return self._child("counter", name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Optional[Dict[str, str]] = None) -> Gauge:
        return self._child("gauge", name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Optional[Dict[str, str]] = None,
                  maxlen: int = 2048) -> Histogram:
        return self._child("histogram", name, help, labels, maxlen=maxlen)

    # ---- introspection ----
    def get(self, name: str, labels: Optional[Dict[str, str]] = None):
        """The live child, or None (never creates)."""
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                return None
            return fam.children.get(_freeze_labels(labels))

    def families(self) -> List[str]:
        with self._lock:
            return sorted(self._families)

    def children(self, name: str) -> List[tuple]:
        """All live children of one family as `(labels_dict, child)`
        pairs; empty when the family does not exist (never creates).
        The arrival-rate forecaster walks `fleet_requests_total`
        children through this without knowing the model names up
        front."""
        with self._lock:
            fam = self._families.get(name)
            if fam is None:
                return []
            return [(dict(frozen), child)
                    for frozen, child in fam.children.items()]

    def unregister(self, name: str) -> None:
        with self._lock:
            self._families.pop(name, None)

    def clear(self) -> None:
        with self._lock:
            self._families.clear()

    def _snapshot_families(self) -> List[_Family]:
        with self._lock:
            fams = list(self._families.values())
        fams.sort(key=lambda f: f.name)
        return fams

    def snapshot(self, bins: int = 0) -> Dict[str, Dict]:
        """JSON-able view: {"counters": {series: int}, "gauges": {...},
        "histograms": {series: {count, mean, max, p50, p95, p99[, bins]}}}.
        `bins > 0` adds a {lo, hi, counts} window histogram per series
        (the UI chart block's input)."""
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        for fam in self._snapshot_families():
            for labels, child in sorted(fam.children.items()):
                key = _series_key(fam.name, labels)
                if fam.kind == "histogram":
                    snap = child.snapshot()
                    if bins > 0:
                        lo, hi, counts = child.bins(bins)
                        snap["bins"] = {"lo": lo, "hi": hi, "counts": counts}
                    out["histograms"][key] = snap
                elif fam.kind == "counter":
                    out["counters"][key] = child.value
                else:
                    out["gauges"][key] = child.value
        return out

    # ---- Prometheus exposition ----
    def render_prometheus(self) -> str:
        """Prometheus text exposition format 0.0.4.  Histograms export as
        summaries (quantile series + _sum/_count): the window percentiles
        are already computed and a fixed-bucket export would have to guess
        bucket bounds per metric."""
        lines: List[str] = []
        for fam in self._snapshot_families():
            if fam.help:
                lines.append(f"# HELP {fam.name} {fam.help}")
            kind = "summary" if fam.kind == "histogram" else fam.kind
            lines.append(f"# TYPE {fam.name} {kind}")
            for labels, child in sorted(fam.children.items()):
                pairs = [(k, _escape_label(v)) for k, v in labels]
                if fam.kind == "histogram":
                    snap = child.snapshot()
                    if snap["count"]:
                        for p, q in (("p50", "0.5"), ("p95", "0.95"),
                                     ("p99", "0.99")):
                            v = snap[p]
                            if math.isfinite(v):
                                lines.append(_prom_line(
                                    fam.name, pairs + [("quantile", q)], v))
                    lines.append(_prom_line(f"{fam.name}_sum", pairs,
                                            child.sum))
                    lines.append(_prom_line(f"{fam.name}_count", pairs,
                                            child.count))
                else:
                    lines.append(_prom_line(fam.name, pairs, child.value))
        return "\n".join(lines) + ("\n" if lines else "")


def _prom_line(name: str, pairs: List[Tuple[str, str]], value) -> str:
    label = "{" + ",".join(f'{k}="{v}"' for k, v in pairs) + "}" \
        if pairs else ""
    if isinstance(value, float):
        if value != value:                       # NaN
            sval = "NaN"
        elif value == int(value) and abs(value) < 1e15:
            sval = str(int(value))
        else:
            sval = repr(value)
    else:
        sval = str(value)
    return f"{name}{label} {sval}"


# ---------------------------------------------------------------------------
# Process-wide default
# ---------------------------------------------------------------------------

_default = MetricsRegistry()


def registry() -> MetricsRegistry:
    """The process-wide registry every subsystem records into by default
    (and the one `GET /metrics` on ui.server.UIServer exposes)."""
    return _default
