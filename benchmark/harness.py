"""The benchmark's loaders and bookkeeping.  Nothing here knows a cell, a
model or a metric by name: every file is found by a name the manifest
(`BENCHMARK.json`) or a data file gives.

    cell      `workloads[i]` of the manifest: `config` + `traffic` + `chips`
    config    `configs[j].file`, a JSON file of sizes; its `family` names
              `benchmark/models/<family>.py`
    traffic   `benchmark/traffic/<traffic>.json`; its `driver` names
              `benchmark/drivers/<driver>.py`
    metric    `benchmark/layer_metrics/<name>.py` exposing `read(run)`

See `benchmark/README.md` for how a later PR adds any of these without
editing a file that exists.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


TRACE_SECONDS = 3.0      # the traced stretch of a `--trace 1` run


class BenchmarkError(RuntimeError):
    """A file the manifest names is missing or malformed, or the machine is
    not the one the cell asks for.  `run.py` prints it and exits non-zero
    without a result line."""


# ---------------------------------------------------------------------------
# manifest and data files
# ---------------------------------------------------------------------------

def load_json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkError(f"no such file: {os.path.relpath(path, ROOT)}")
    except json.JSONDecodeError as e:
        raise BenchmarkError(f"{os.path.relpath(path, ROOT)}: {e}")


def load_manifest(path: str = MANIFEST) -> dict:
    return load_json(path)


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchmarkError(
        f"unknown {what} {name!r}; have {[e['name'] for e in entries]}")


@dataclasses.dataclass
class Cell:
    """One `workloads` entry with everything it names already loaded."""
    name: str
    chips: int
    config_name: str
    config: dict              # the configuration file's content
    traffic_name: str
    traffic: dict             # the traffic file's content
    end_to_end: List[dict]    # this cell's end-to-end metric entries
    per_layer: List[dict]     # this cell's per-layer metric entries


def _in_cell(metric: dict, cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def load_config(manifest: dict, name: str) -> dict:
    entry = _named(manifest["configs"], name, "config")
    return load_json(os.path.join(ROOT, entry["file"]))


def load_traffic(name: str) -> dict:
    return load_json(os.path.join(HERE, "traffic", name + ".json"))


def load_cell(manifest: dict, name: str) -> Cell:
    w = _named(manifest["workloads"], name, "workload")
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=load_config(manifest, w["config"]),
        traffic_name=w["traffic"], traffic=load_traffic(w["traffic"]),
        end_to_end=[m for m in manifest["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _in_cell(m, name)])


def _module(kind: str, name: str, needs: tuple):
    try:
        mod = importlib.import_module(f"benchmark.{kind}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmark.{kind}.{name}":
            raise
        raise BenchmarkError(f"no benchmark/{kind}/{name}.py")
    missing = [n for n in needs if not hasattr(mod, n)]
    if missing:
        raise BenchmarkError(f"benchmark/{kind}/{name}.py lacks {missing}")
    return mod


def load_family(config: dict):
    """`benchmark/models/<family>.py` of a configuration."""
    return _module("models", config["family"],
                   ("build", "flops_per_item", "reference_forward"))


def load_driver(traffic: dict):
    """`benchmark/drivers/<driver>.py` of a traffic mix."""
    return _module("drivers", traffic["driver"], ("run",))


def load_layer_metric(name: str):
    """`benchmark/layer_metrics/<name>.py`, one reader per metric."""
    return _module("layer_metrics", name, ("read",))


def load_peaks(device_kind: str) -> dict:
    """Published peaks of the device, by `device_kind` as jax reports it.
    An unknown kind is an error: a roofline against a guessed peak is a
    number about nothing."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchmarkError(
            f"benchmark/peaks.json has no device_kind {device_kind!r}; "
            f"have {sorted(table['devices'])}")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# what a driver hands back
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """Everything one run observed.  A driver fills it; `run.py` prints the
    end-to-end metrics from `end_to_end` and hands the whole record to each
    per-layer reader."""
    cell: Cell
    seed: int
    seconds: float
    traced: bool
    devices: list                         # jax devices used
    watch: Any = None                     # CompileWatch
    t_start: float = 0.0                  # perf_counter at process start
    trace_dir: Optional[str] = None       # where a traced run writes
    clock: Any = None                     # TraceClock, traced runs
    attempted: int = 0
    failed: int = 0
    checks: Dict[str, bool] = dataclasses.field(default_factory=dict)
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    # driver counters and host spans, by name; readers document what they use
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # the reduced device trace (benchmark.trace.reduce.Reduced), traced runs
    trace: Any = None
    peaks: Optional[dict] = None

    def __post_init__(self):
        if self.traced and self.clock is None:
            self.clock = TraceClock()

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        """Record one correctness check and say what it found."""
        self.checks[name] = bool(ok)
        line = (f"check {name}: {'ok' if ok else 'FAILED'}"
                + (f" ({detail})" if detail else ""))
        say(line)
        if not ok:      # whoever keeps only stderr learns which check it was
            print(f"benchmark: cell {self.cell.name}, seed {self.seed}: "
                  f"{line}", file=sys.stderr, flush=True)
        return bool(ok)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(self.checks.values())

    @property
    def untraced_seconds(self) -> float:
        """A traced run measures an untraced stretch first, then traces
        `TRACE_SECONDS`; together they last about `seconds`."""
        if not self.traced:
            return self.seconds
        return max(2.0, self.seconds - TRACE_SECONDS)


_T0 = time.perf_counter()


def say(msg: str) -> None:
    """A line for the reader of the log; never the last line."""
    print(f"[bench {time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


# ---------------------------------------------------------------------------
# the device, compiles, memory
# ---------------------------------------------------------------------------

def take_devices(chips: int, *, allow_platform: Optional[str] = None) -> list:
    """The `chips` devices a cell runs on.  Anything but a TPU with exactly
    that many chips is an error — there is no fallback.  `allow_platform` is
    for the tests, which drive the drivers in-process on the CPU; no flag or
    variable reaches it from the command line."""
    import jax
    devs = jax.devices()
    want = allow_platform or "tpu"
    if devs[0].platform != want:
        raise BenchmarkError(
            f"found platform={devs[0].platform!r} ({devs[0].device_kind} "
            f"x{len(devs)}), need {want!r}")
    if len(devs) < chips or (allow_platform is None and len(devs) != chips):
        raise BenchmarkError(
            f"the cell asks for {chips} chip(s), jax shows {len(devs)} "
            f"({devs[0].device_kind})")
    return list(devs[:chips])


class CompileWatch:
    """Counts XLA backend compiles (jax monitoring's duration event fires on
    every compile request, cache hit or not) and the persistent cache's
    requests and hits.  A window compiled nothing when `compiles` is the
    same after it as before."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
              "/jax/compilation_cache/cache_hits": "hits"}

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache = {"requests": 0, "hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **kw):
        key = self.EVENTS.get(event)
        if key:
            self.cache[key] += 1


def place_cache() -> str:
    """jax's persistent compilation cache: where the program's own
    `place_compilation_cache` puts it (`$JAX_COMPILATION_CACHE_DIR`, else one
    fixed directory in the checkout), with the thresholds lowered in this
    process so that the many sub-second eager compiles of set-up hit too."""
    import jax
    from deeplearning4j_tpu.compile import place_compilation_cache
    d = place_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


def cache_dir_report(d: str) -> str:
    """Entries and the largest one: a machine that caps file sizes cannot
    hold an entry above the cap, and that cell then compiles in every run."""
    try:
        sizes = [os.path.getsize(os.path.join(d, f)) for f in os.listdir(d)]
    except OSError:
        return f"{d}: not readable"
    if not sizes:
        return f"{d}: empty"
    return (f"{d}: {len(sizes)} entries, {sum(sizes) / 2**20:.0f} MiB, "
            f"largest {max(sizes) / 2**20:.1f} MiB")


def bench_marker(x):
    """The marker program: its runs show in the trace by this name."""
    return x + 1


class TraceClock:
    """The host's side of a traced stretch: the benchmark's own spans on the
    host clock (`time.perf_counter`), and the times at which runs of the
    marker program were seen to be done, which tie that clock to the
    trace's (`benchmark.trace.reduce.align`).  Created in set-up, because
    creating it compiles the marker."""

    def __init__(self):
        import jax
        import jax.numpy as jnp
        self._marker = jax.jit(bench_marker)
        self._x = jnp.zeros((), jnp.int32)
        self._marker(self._x).block_until_ready()
        self.marks: List[float] = []
        self.spans: List[tuple] = []

    def mark(self) -> None:
        self._marker(self._x).block_until_ready()
        self.marks.append(time.perf_counter())

    def add(self, name: str, t0: float, t1: float) -> None:
        self.spans.append((name, t0, t1))


@contextlib.contextmanager
def device_trace(run: Run):
    """Trace the device while the block runs and leave the reduction in
    `run.trace` (None when no chip ran anything).  The block calls
    `run.clock.mark()` before and after its window and records its host
    spans in `run.clock`.  Device ops only: neither the Python tracer nor
    the host tracer (`benchmark/trace/reduce.py` says why)."""
    import jax
    from benchmark.trace import reduce as trace_reduce
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    jax.profiler.start_trace(run.trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(run.trace_dir))
    tied = trace_reduce.align(trace, run.clock.marks, run.clock.spans)
    if tied is None:
        say("trace: no two marker runs found; the window is the extent of "
            "the device ops and idle gaps carry no host span")
    else:
        say(f"trace: host and device clocks tied to {1e3 * tied[1]:+.3f} ms "
            f"over the window")
    run.trace = trace_reduce.reduce(trace, tied and tied[0])


def memory_stats_line(devices: list) -> str:
    return "; ".join(f"{d.id}: {d.memory_stats()}" for d in devices)


def memory_peaks(devices: list) -> List[int]:
    """Peak bytes per device as its allocator saw them: the peak of the
    buffers in use plus the peak it reserved for running programs'
    temporaries.  `peak_bytes_in_use` alone leaves the temporaries out: 1.68
    GB for a ResNet-50 step at batch 256 whose program reserves 8.8 GB (my
    chip run, PR 22).  0 where the backend reports nothing."""
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return out


def device_line(run: Run) -> dict:
    d0 = run.devices[0]
    line = {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(run.devices),
            "memory_peak_bytes": int(max(run.counters["memory_peaks"]))}
    if run.trace is not None:
        line["busy_s"] = run.trace.busy_s_mean
        line["window_s"] = run.trace.window_s
    return line
