"""Schedule autotuner: learned search over the execution-config space.

TVM's lesson (PAPERS.md, arXiv 1802.04799) applied to the knobs this
framework already exposes but makes users hand-tune: `fused_steps` (scan
block size), device prefetch depth, ZeRO-1 optimizer sharding on/off,
buffer donation, and the serving bucket ladder.  The autotuner measures
real steps/sec per candidate through a caller-supplied measure function
(`examples/autotune_and_serve.py` supplies one), searches with a coarse
grid over the
highest-impact dimensions followed by greedy per-dimension refinement,
and persists the winner as a JSON artifact next to the executable store
— `load_schedule()` re-applies it at build time in any later process, so
a tuned config survives restarts the same way the compiled executables
do.

    sch = ScheduleAutotuner(measure).search()
    save_schedule(sch, cache_dir, model=net)
    ...                                   # any later process:
    sch = load_schedule(cache_dir, model=net)
    if sch: sch.apply(net)                # or ParallelWrapper / ModelServer
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

from deeplearning4j_tpu.compile.fingerprint import (environment_fingerprint,
                                                    model_fingerprint)

SCHEDULE_FORMAT = "deeplearning4j_tpu.schedule.v1"


@dataclasses.dataclass
class Schedule:
    """One point in the execution-config space.

    Training knobs: `fused_steps` (k steps per compiled scan dispatch),
    `prefetch_depth` (device-staging depth for DevicePrefetchIterator),
    `zero1` (ZeRO-1 sharded weight update), `donation` (donate
    params/state/opt buffers to the step).  Serving knobs: `min_bucket` /
    `buckets` (the compile-cache bucket ladder).  `steps_per_sec` records
    the winning measurement for regression checks on re-apply."""

    fused_steps: int = 1
    prefetch_depth: int = 2
    zero1: bool = False
    donation: bool = True
    min_bucket: Optional[int] = None
    buckets: Optional[List[int]] = None
    steps_per_sec: Optional[float] = None
    source: str = "default"          # default | autotuned | loaded
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # ---- serialization ----
    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "Schedule":
        known = {f.name for f in dataclasses.fields(Schedule)}
        return Schedule(**{k: v for k, v in d.items() if k in known})

    def config_key(self) -> tuple:
        """Identity of the *configuration* (measurement metadata excluded)
        — the autotuner's dedup key."""
        return (self.fused_steps, self.prefetch_depth, self.zero1,
                self.donation, self.min_bucket,
                tuple(self.buckets) if self.buckets else None)

    # ---- application hooks ----
    def apply(self, target) -> Any:
        """Apply this schedule to a build-time target, duck-typed:

        * MultiLayerNetwork / ComputationGraph / SameDiff — installs the
          schedule (iterator `fit` defaults to `fused_steps`, the step
          builders honor `donation`).
        * ParallelWrapper — toggles ZeRO-1 and applies to the wrapped
          model.
        * ModelServer / BucketedCompileCache — reconfigures the bucket
          ladder (prefer passing `schedule=` at construction).
        * ModelFleet — installs this as the fleet default schedule,
          applied to every replica on warm-pool admission (per-model
          schedules from `schedules_dir` still win).

        Returns `target` for chaining."""
        if hasattr(target, "set_default_schedule"):    # ModelFleet
            return target.set_default_schedule(self)
        if hasattr(target, "apply_schedule"):          # models + wrapper
            return target.apply_schedule(self)
        if hasattr(target, "cache") and hasattr(target.cache, "set_buckets"):
            if self.buckets or self.min_bucket:        # ModelServer
                target.cache.set_buckets(buckets=self.buckets,
                                         min_bucket=self.min_bucket)
            return target
        if hasattr(target, "set_buckets"):             # BucketedCompileCache
            if self.buckets or self.min_bucket:
                target.set_buckets(buckets=self.buckets,
                                   min_bucket=self.min_bucket)
            return target
        raise TypeError(
            f"don't know how to apply a Schedule to {type(target).__name__}")

    def wrap_iterator(self, iterator, **kwargs):
        """Stage `iterator` through a DevicePrefetchIterator at this
        schedule's prefetch depth (the input-pipeline application hook)."""
        from deeplearning4j_tpu.data.pipeline import DevicePrefetchIterator
        return DevicePrefetchIterator(iterator,
                                      depth=max(1, self.prefetch_depth),
                                      **kwargs)


# Coarse-grid dimensions first: block size and optimizer sharding dominate
# steps/sec; prefetch/donation/buckets are refined greedily from the grid
# winner.
DEFAULT_SPACE: Dict[str, List[Any]] = {
    "fused_steps": [1, 2, 4, 8, 16],
    "zero1": [False, True],
    "prefetch_depth": [1, 2, 4],
    "donation": [True, False],
}
GRID_DIMS = ("fused_steps", "zero1")


class ScheduleAutotuner:
    """Grid + greedy-refinement search over `Schedule` space.

    `measure(schedule) -> steps/sec` (higher is better) is the only
    contract; `examples/autotune_and_serve.py` builds one from a model
    factory, tests rig one analytically.  Measurements are memoized per
    config, every evaluation lands in `history`, and the returned
    schedule carries its winning steps/sec + search metadata."""

    def __init__(self, measure: Callable[[Schedule], float],
                 space: Optional[Dict[str, List[Any]]] = None,
                 base: Optional[Schedule] = None,
                 refine_rounds: int = 2,
                 on_candidate: Optional[Callable[[Schedule, float], None]]
                 = None):
        self.measure = measure
        self.space = dict(space if space is not None else DEFAULT_SPACE)
        self.base = base if base is not None else Schedule()
        self.refine_rounds = int(refine_rounds)
        self.on_candidate = on_candidate
        self.history: List[Dict[str, Any]] = []
        self._memo: Dict[tuple, float] = {}

    def _eval(self, cand: Schedule) -> float:
        key = cand.config_key()
        if key in self._memo:
            return self._memo[key]
        sps = float(self.measure(cand))
        self._memo[key] = sps
        self.history.append(dict(cand.to_json(), steps_per_sec=sps))
        if self.on_candidate is not None:
            self.on_candidate(cand, sps)
        return sps

    def search(self) -> Schedule:
        t0 = time.perf_counter()
        best = self.base
        best_sps = self._eval(best)

        # stage 1 — coarse grid over the dominant dimensions
        grid_dims = [d for d in GRID_DIMS if d in self.space]
        def grid(cands, dim_i):
            if dim_i == len(grid_dims):
                yield cands
                return
            for v in self.space[grid_dims[dim_i]]:
                yield from grid(dict(cands, **{grid_dims[dim_i]: v}),
                                dim_i + 1)
        for combo in grid({}, 0):
            cand = dataclasses.replace(best, **combo)
            sps = self._eval(cand)
            if sps > best_sps:
                best, best_sps = cand, sps

        # stage 2 — greedy per-dimension refinement from the grid winner
        for _ in range(self.refine_rounds):
            improved = False
            for dim, values in self.space.items():
                for v in values:
                    cand = dataclasses.replace(best, **{dim: v})
                    sps = self._eval(cand)
                    if sps > best_sps:
                        best, best_sps = cand, sps
                        improved = True
            if not improved:
                break

        return dataclasses.replace(
            best, steps_per_sec=best_sps, source="autotuned",
            meta={"evaluated": len(self._memo),
                  "search_wall_s": round(time.perf_counter() - t0, 3),
                  "baseline_steps_per_sec": self.history[0]["steps_per_sec"],
                  "env": environment_fingerprint()})


# ---------------------------------------------------------------------------
# Persistence (JSON artifact next to the executable store)
# ---------------------------------------------------------------------------

def _schedule_name(name: Optional[str], model) -> str:
    if name is not None:
        return name
    if model is not None:
        return model_fingerprint(model)[:16]
    return "default"


def schedule_path(directory: str, name: Optional[str] = None,
                  model=None) -> str:
    return os.path.join(os.path.expanduser(directory),
                        f"schedule-{_schedule_name(name, model)}.json")


def save_schedule(schedule: Schedule, directory: str,
                  name: Optional[str] = None, model=None) -> str:
    """Atomically persist `schedule` as
    `<directory>/schedule-<name|model-fingerprint>.json`; returns the
    path.  Same tmp+rename commit discipline as the executable entries."""
    directory = os.path.expanduser(directory)
    os.makedirs(directory, exist_ok=True)
    path = schedule_path(directory, name, model)
    doc = {"format": SCHEDULE_FORMAT,
           "schedule": schedule.to_json(),
           "env": environment_fingerprint(),
           "written_at": time.time()}
    fd, tmp = tempfile.mkstemp(dir=directory, prefix=".tmp-schedule-")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=2)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


def load_schedule(directory: str, name: Optional[str] = None,
                  model=None) -> Optional[Schedule]:
    """The persisted schedule for (directory, name-or-model), or None when
    absent/unreadable/wrong format.  Loaded schedules are marked
    `source="loaded"`; the recorded `steps_per_sec` rides along so callers
    can regression-check a re-application against the tuning measurement."""
    path = schedule_path(directory, name, model)
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("format") != SCHEDULE_FORMAT:
            return None
        sch = Schedule.from_json(doc["schedule"])
    except (OSError, ValueError, KeyError, TypeError):
        return None
    sch.source = "loaded"
    sch.meta = dict(sch.meta, loaded_from=path)
    return sch


# ---------------------------------------------------------------------------
# Tile-size search for the fused-kernel tier (ops/pallas)
# ---------------------------------------------------------------------------
#
# Same shape as the schedule search one level down: a coarse grid over the
# dominant tile dimensions, greedy per-dimension refinement, memoized
# measurements — but the search space is a kernel's TileConfig and the
# persisted artifact is a per-device-kind tile table
# (`tiles-<device_kind>.json`) keyed by `<kernel>/<shape_class>`, living
# next to the schedule store.  Winners are installed into
# `ops.pallas.dispatch`, which folds them into `kernel_tier_fingerprint`
# so a tile change can never collide with a stale AOT executable.

from deeplearning4j_tpu.ops.pallas.tiles import (  # noqa: E402
    DEFAULT_TILES, TILE_FORMAT, TILE_GRID_DIMS, TILE_SPACES, TileConfig,
    iter_space)


class TileAutotuner:
    """Grid + greedy-refinement search over one kernel's TileConfig space.

    `measure(tile) -> rate` (higher is better — steps/sec, GFLOP/s,
    1/latency; any consistent unit).  Measurements are memoized per
    config; every evaluation lands in `history`; `search()` returns the
    winning TileConfig and records `best_rate` / `evaluated` on self."""

    def __init__(self, measure: Callable[[TileConfig], float],
                 kernel: str,
                 space: Optional[Dict[str, List[int]]] = None,
                 base: Optional[TileConfig] = None,
                 refine_rounds: int = 2,
                 on_candidate: Optional[Callable[[TileConfig, float], None]]
                 = None):
        self.measure = measure
        self.kernel = kernel
        self.space = dict(space if space is not None
                          else TILE_SPACES.get(kernel, {}))
        self.base = base if base is not None else DEFAULT_TILES.get(
            kernel, TileConfig())
        self.refine_rounds = int(refine_rounds)
        self.on_candidate = on_candidate
        self.history: List[Dict[str, Any]] = []
        self._memo: Dict[str, float] = {}
        self.best_rate: Optional[float] = None
        self.evaluated: int = 0

    def _eval(self, cand: TileConfig) -> float:
        key = cand.config_key()
        if key in self._memo:
            return self._memo[key]
        rate = float(self.measure(cand))
        self._memo[key] = rate
        self.history.append(dict(cand.to_json(), rate=rate))
        if self.on_candidate is not None:
            self.on_candidate(cand, rate)
        return rate

    def search(self) -> TileConfig:
        best = self.base
        best_rate = self._eval(best)

        grid_dims = [d for d in TILE_GRID_DIMS.get(self.kernel, ())
                     if d in self.space] or sorted(self.space)[:2]
        for combo in iter_space({d: self.space[d] for d in grid_dims}):
            cand = best.replace(**combo)
            rate = self._eval(cand)
            if rate > best_rate:
                best, best_rate = cand, rate

        for _ in range(self.refine_rounds):
            improved = False
            for dim in sorted(self.space):
                for v in self.space[dim]:
                    cand = best.replace(**{dim: v})
                    rate = self._eval(cand)
                    if rate > best_rate:
                        best, best_rate = cand, rate
                        improved = True
            if not improved:
                break

        self.best_rate = best_rate
        self.evaluated = len(self._memo)
        return best


def _device_kind_slug(device_kind: Optional[str] = None) -> str:
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    return "".join(c if c.isalnum() else "-" for c in str(device_kind).lower())


def tile_table_path(directory: str,
                    device_kind: Optional[str] = None) -> str:
    return os.path.join(os.path.expanduser(directory),
                        f"tiles-{_device_kind_slug(device_kind)}.json")


def _load_tile_doc(directory: str,
                   device_kind: Optional[str] = None) -> Dict[str, Any]:
    try:
        with open(tile_table_path(directory, device_kind)) as f:
            doc = json.load(f)
        if doc.get("format") != TILE_FORMAT:
            return {}
        entries = doc.get("entries")
        return entries if isinstance(entries, dict) else {}
    except (OSError, ValueError):
        return {}


def load_tile_table(directory: str, device_kind: Optional[str] = None
                    ) -> Dict[str, TileConfig]:
    """The persisted tile table as `{<kernel>/<shape_class>: TileConfig}`,
    or `{}` when absent/unreadable/wrong format — ready for
    `ops.pallas.dispatch.install_tile_table`."""
    out: Dict[str, TileConfig] = {}
    for key, entry in _load_tile_doc(directory, device_kind).items():
        try:
            out[key] = TileConfig.from_json(entry["tile"])
        except (KeyError, TypeError, ValueError):
            continue
    return out


def save_tile_entry(directory: str, kernel: str, shape_class: str,
                    tile: TileConfig, rate: Optional[float] = None,
                    meta: Optional[Dict[str, Any]] = None,
                    device_kind: Optional[str] = None) -> str:
    """Read-modify-write one `<kernel>/<shape_class>` entry into the
    per-device tile table, with the same tmp+rename commit discipline as
    the schedule artifact.  Returns the table path."""
    directory = os.path.expanduser(directory)
    os.makedirs(directory, exist_ok=True)
    path = tile_table_path(directory, device_kind)
    entries = _load_tile_doc(directory, device_kind)
    entries[f"{kernel}/{shape_class}"] = {
        "tile": tile.to_json(),
        "rate": rate,
        "meta": dict(meta or {}),
        "written_at": time.time(),
    }
    doc = {"format": TILE_FORMAT,
           "device_kind": _device_kind_slug(device_kind),
           "entries": entries,
           "env": environment_fingerprint()}
    fd, tmp = tempfile.mkstemp(dir=directory, prefix=".tmp-tiles-")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=2)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return path


def autotune_tiles(kernel: str, shape_class: str,
                   measure: Callable[[TileConfig], float],
                   directory: str,
                   space: Optional[Dict[str, List[int]]] = None,
                   base: Optional[TileConfig] = None,
                   refine_rounds: int = 2,
                   install: bool = True,
                   device_kind: Optional[str] = None
                   ) -> "tuple[TileConfig, Dict[str, Any]]":
    """Memoized tile search: serve `<kernel>/<shape_class>` from the
    persisted per-device tile table when present (zero re-search, counted
    as `autotune_tile_cache_hits_total`), otherwise run the grid+greedy
    `TileAutotuner`, persist the winner, and (by default) install it into
    `ops.pallas.dispatch` so subsequent dispatches — and AOT fingerprints
    — pick it up.  Returns `(tile, info)`."""
    from deeplearning4j_tpu.monitor.instrument import ops_instruments
    from deeplearning4j_tpu.ops.pallas import dispatch as _kd

    key = f"{kernel}/{shape_class}"
    entry = _load_tile_doc(directory, device_kind).get(key)
    if entry is not None:
        try:
            tile = TileConfig.from_json(entry["tile"])
        except (KeyError, TypeError, ValueError):
            tile = None
        if tile is not None:
            ops_instruments().record_tile_cache_hit()
            if install:
                _kd.set_tile(kernel, tile, shape_class)
            return tile, {"source": "cache", "evaluated": 0,
                          "rate": entry.get("rate"),
                          "path": tile_table_path(directory, device_kind)}

    t0 = time.perf_counter()
    tuner = TileAutotuner(measure, kernel, space=space, base=base,
                          refine_rounds=refine_rounds)
    tile = tuner.search()
    search_ms = (time.perf_counter() - t0) * 1000.0
    ops_instruments().record_tile_search_ms(search_ms)
    path = save_tile_entry(directory, kernel, shape_class, tile,
                           rate=tuner.best_rate,
                           meta={"evaluated": tuner.evaluated,
                                 "search_ms": round(search_ms, 3)},
                           device_kind=device_kind)
    if install:
        _kd.set_tile(kernel, tile, shape_class)
    return tile, {"source": "searched", "evaluated": tuner.evaluated,
                  "rate": tuner.best_rate,
                  "search_ms": search_ms, "path": path}
