"""Cached metric handles for the framework's hot paths.

The registry's get-or-create is a dict lookup under a lock — fine per
epoch, wasteful per step.  Each instrumented subsystem grabs one of these
handle bundles ONCE (lazily, on first dispatch) and then records through
plain attribute access.  All record methods early-out on the global
telemetry switch, so an instrumented step costs two `perf_counter` reads
and a flag check when telemetry is off.

Metric naming (the contract `GET /metrics` exposes, see
docs/observability.md):

  training_step_ms{model=}           per-step host dispatch time
  training_steps_total{model=}       optimizer steps (fused steps count k)
  training_dispatches_total{model=}  host->device dispatches (fused = 1)
  training_compiles_total{model=}    executable-cache fills (trace+compile)
  training_donated_bytes{model=}     params+state+opt bytes donated per step
  training_epochs_total{model=}      completed epochs
  pipeline_prefetch_depth            batches staged on device right now
  pipeline_producer_wait_ms          consumer wait on the ETL producer
  pipeline_h2d_bytes_total           bytes staged host->device
  pipeline_producer_retries_total    producer restarts (retries= opt-in)
  pipeline_batches_total             batches staged
  parallel_replicas                  mesh data-parallel degree
  parallel_dispatch_ms               SPMD step host dispatch time
  parallel_replica_skew_ms           per-replica completion skew (opt-in)
  training_opt_state_bytes{sharded=} per-replica optimizer-state bytes
                                     (ZeRO-1 sharded=true vs replicated)
  resilience_checkpoint_save_ms      wall time of one checkpoint save
                                     (async saves: the background write)
  resilience_checkpoint_bytes        size of the latest checkpoint payload
  resilience_checkpoints_total       committed checkpoint saves
  resilience_checkpoint_gc_total     checkpoints removed by retention GC
  resilience_restores_total          successful checkpoint restores
  resilience_restore_fallbacks_total restores that skipped a torn/corrupt
                                     newest checkpoint for an older one
  resilience_rollbacks_total         divergence rollbacks to a checkpoint
  resilience_divergence_events_total NaN/inf/spike steps the guard caught
  resilience_preemptions_total       SIGTERM checkpoint-and-exit events
  chaos_faults_injected_total{kind=} faults injected by utils.chaos
  aot_cache_hits_total               executables deserialized from disk
  aot_cache_misses_total             disk lookups that found no usable entry
  aot_cache_compiles_total           fresh XLA compiles through the cache
  aot_cache_stores_total             executables serialized+committed to disk
  aot_cache_errors_total             corrupt/mismatched/unserializable events
  aot_cache_bytes_read_total         entry bytes deserialized from disk
  aot_cache_bytes_written_total      entry bytes committed to disk
  aot_cache_load_ms                  disk-hit deserialize wall time
  aot_cache_store_ms                 serialize+commit wall time
  comms_bytes_on_wire_total{codec=}  gradient bytes over the DCN/host hop
                                     (codec=threshold vs codec=dense is the
                                     compression saving)
  comms_compression_ratio            dense/compressed byte ratio of the most
                                     recent exchange
  comms_exchange_ms                  wall time of one cross-host gradient
                                     exchange (encode + TCP + decode + sum)
  comms_exchanges_total{codec=}      cross-host gradient exchanges run
  fleet_models                       models deployed to the fleet
  fleet_models_resident              models currently holding device
                                     residency (<= warm-pool capacity)
  fleet_admissions_total{warm=}      warm-pool admissions (warm=true →
                                     served from the persistent AOT cache,
                                     zero fresh compiles)
  fleet_evictions_total              LRU warm-pool evictions (drain +
                                     device-buffer drop)
  fleet_requests_total{model=}       requests routed per model (QPS source)
  fleet_sheds_total{model=,priority=} requests shed by SLO pressure,
                                     lowest priority first
  fleet_slo_breaches_total{model=}   sustained-SLO-breach onsets
  fleet_routing_ms                   router decision time (admission check
                                     + replica pick; excludes admission
                                     warmup)
  fleet_rebalances_total             controller slice reallocations
  fleet_replica_unhealthy_total      replicas removed from routing after
                                     consecutive dispatch failures
  fleet_replica_probes_total         requests routed to an unhealthy
                                     replica as a recovery probe
  serving_drain_timeouts_total       replica drains that blew the shared
                                     concurrent-drain deadline
  fleet_hedges_total                 speculative duplicate dispatches
                                     (launched at hedge_fraction of the
                                     deadline budget)
  fleet_hedge_wasted_total           late duplicate completions suppressed
                                     after the client future settled
  fleet_failovers_total              failed attempts re-routed to the next
                                     healthy replica
  fleet_replica_respawns_total{cause=} replicas torn down + rebuilt by the
                                     controller (poisoned|unhealthy|hung)
  fleet_respawn_ms                   detection->routable wall time of one
                                     replica self-heal
  fleet_breaker_state{model=}        worst replica breaker state per model
                                     (0=closed 1=half-open 2=open)
  fleet_degraded_level               degraded-mode ladder level (0=full
                                     1=hedges_off 2=quantized 3=shed_floor)
  fleet_snapshot_age_s               seconds since the last committed
                                     fleet topology snapshot (-1 = none)
  gang_generation                    current gang membership generation
  gang_members                       live ranks in the gradient-mesh gang
  gang_reformations_total{cause=}    membership reformations (cause=crash|
                                     partition|straggler|join)
  gang_detection_ms                  silence observed on a peer when it
                                     was declared lost (failure-detection
                                     latency)
  gang_resume_ms                     reform-to-training-resumed wall time
                                     (rebuild + checkpoint restore +
                                     iterator fast-forward)
  gang_stale_frames_total            stale-generation data frames fenced
                                     and dropped (never summed into
                                     gradients)
  fed_hosts                          live hosts in the serving federation
  fed_generation                     current federation membership
                                     generation
  fed_host_evictions_total{cause=}   hosts evicted from the federation
                                     (cause=crash|partition|straggler)
  fed_replacements_total{warm=}      dead-host model re-placements onto
                                     survivors (warm=true paid zero fresh
                                     compiles through the AOT cache)
  fed_cross_host_failovers_total     requests re-dispatched to another
                                     host with the remaining deadline
                                     budget
  fed_stale_dispatch_total           stale-generation dispatch replies
                                     fenced (never returned to a client)
  fed_detection_ms                   silence observed on a host when it
                                     was declared lost
  fed_replace_ms                     eviction-to-replaced wall time of one
                                     dead-host model re-placement
  fleet_arrival_forecast{model=}     forecast per-model arrival rate for
                                     the next horizon (req/s; EWMA/Holt
                                     over fleet_requests_total deltas)
  quant_calibration_batches_total    batches consumed by PTQ calibration
                                     passes (quant.calibrate)
  quant_models_total{dtype=}         models quantized, by produced dtype
                                     (int8 vs bf16-fallback-dominant)
  quant_bytes_saved                  param bytes saved by the most recent
                                     quantizations (f32 resident bytes
                                     minus quantized resident bytes)
  quant_accuracy_delta               f32-vs-quantized accuracy delta of
                                     the most recent parity check
                                     (fraction of disagreeing top-1
                                     predictions / relative error)
  ops_kernel_dispatch_total{kernel=,impl=}
                                     fused-kernel tier dispatch decisions
                                     (ops.pallas.dispatch), impl=pallas|
                                     reference — counted at trace time
  autotune_tile_search_ms            wall time of one TileConfig search
                                     (compile.autotune.autotune_tiles,
                                     cache-miss path)
  autotune_tile_cache_hits_total     tile lookups served by the persisted
                                     tile table with zero re-search
"""
from __future__ import annotations

import time
from typing import Optional

from deeplearning4j_tpu.monitor.registry import (MetricsRegistry, enabled,
                                                 registry)


class TrainingInstruments:
    """Per-model-instance handle bundle over shared labeled series.

    Two instances of the same model class share series (same labels);
    compile detection state (`_cache_size`) stays per instance because it
    tracks that instance's jitted step."""

    def __init__(self, model_kind: str,
                 registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        lbl = {"model": model_kind}
        self.step_ms = reg.histogram(
            "training_step_ms", help="host dispatch wall time per training "
            "step (ms; async — excludes device completion)", labels=lbl)
        self.steps = reg.counter(
            "training_steps_total", help="optimizer steps run", labels=lbl)
        self.dispatches = reg.counter(
            "training_dispatches_total",
            help="host->device step dispatches (a fused k-step scan is 1)",
            labels=lbl)
        self.compiles = reg.counter(
            "training_compiles_total",
            help="compiled-executable cache fills (trace + XLA compile)",
            labels=lbl)
        self.donated_bytes = reg.gauge(
            "training_donated_bytes",
            help="bytes of params/state/opt-state donated per step "
            "(sampled at compile events)", labels=lbl)
        self.epochs = reg.counter(
            "training_epochs_total", help="completed epochs", labels=lbl)
        self._cache_sizes: dict = {}

    def record_dispatch(self, dt_s: float, steps: int = 1) -> None:
        """One host dispatch of `steps` optimizer steps taking `dt_s`
        host seconds (dispatch time — the device may still be running)."""
        if not enabled():
            return
        self.steps.inc(steps)
        self.dispatches.inc()
        self.step_ms.observe(dt_s * 1000.0 / max(steps, 1))

    def check_compile(self, jit_fn, model=None) -> bool:
        """Detect executable-cache growth on the model's jitted step — each
        fill is one trace+compile event (a new input shape/dtype or a step
        rebuild).  On a compile event, sample the donated-buffer footprint
        (params/state/opt-state leaves) so HBM reuse is visible; walking
        the tree only on compile events keeps the steady state free of it.
        True on a compile event, for the caller's own once-a-compile work
        (`monitor.note_step`)."""
        if not enabled() or jit_fn is None:
            return False
        try:
            n = jit_fn._cache_size()
        except Exception:      # non-jit callable (e.g. scan wrapper fn)
            return False
        key = id(jit_fn)       # a rebuilt step (set_normalizer) is a new fn
        prev = self._cache_sizes.get(key, 0)
        if n == prev:
            return False
        if n > prev:
            self.compiles.inc(n - prev)
            if model is not None:
                self.donated_bytes.set(_donated_nbytes(model))
        self._cache_sizes[key] = n
        return n > prev

    def record_epoch(self) -> None:
        if not enabled():
            return
        self.epochs.inc()


def _donated_nbytes(model) -> int:
    import jax
    total = 0
    for tree in (getattr(model, "params_", None),
                 getattr(model, "state_", None),
                 getattr(model, "opt_state_", None)):
        if tree is None:
            continue
        for leaf in jax.tree_util.tree_leaves(tree):
            total += getattr(leaf, "nbytes", 0) or 0
    return total


class PipelineInstruments:
    """Input-pipeline handles (one unlabeled series set per process — the
    prefetch iterators all feed the same trainer)."""

    def __init__(self, registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        self.prefetch_depth = reg.gauge(
            "pipeline_prefetch_depth",
            help="batches currently staged on device ahead of the consumer")
        self.producer_wait_ms = reg.histogram(
            "pipeline_producer_wait_ms",
            help="time the consumer waited on the ETL producer per batch "
            "(ms); sustained >0 means ETL is the bottleneck")
        self.h2d_bytes = reg.counter(
            "pipeline_h2d_bytes_total",
            help="bytes staged host->device by the input pipeline")
        self.batches = reg.counter(
            "pipeline_batches_total", help="batches staged to device")
        self.producer_retries = reg.counter(
            "pipeline_producer_retries_total",
            help="producer restarts by DevicePrefetchIterator retries=")

    def record_stage(self, wait_s: float, depth: int) -> None:
        if not enabled():
            return
        self.producer_wait_ms.observe(wait_s * 1000.0)
        self.prefetch_depth.set(depth)
        self.batches.inc()


class ParallelInstruments:
    """Data-parallel wrapper handles."""

    def __init__(self, registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        self.replicas = reg.gauge(
            "parallel_replicas", help="mesh data-parallel degree")
        self.dispatch_ms = reg.histogram(
            "parallel_dispatch_ms",
            help="SPMD step host dispatch wall time (ms)")
        self.replica_skew_ms = reg.gauge(
            "parallel_replica_skew_ms",
            help="latest measured per-replica completion skew (ms; "
            "blocking diagnostic, see ParallelWrapper.measure_replica_skew)")
        self._opt_state_bytes = {
            flag: reg.gauge(
                "training_opt_state_bytes",
                help="optimizer-state bytes resident per replica "
                "(sharded=true → ZeRO-1 sharded weight update; compare "
                "against sharded=false for the HBM saving)",
                labels={"sharded": "true" if flag else "false"})
            for flag in (True, False)}

    def record_dispatch(self, dt_s: float) -> None:
        if not enabled():
            return
        self.dispatch_ms.observe(dt_s * 1000.0)

    def record_opt_state_bytes(self, nbytes: int, sharded: bool) -> None:
        """Per-replica optimizer-state footprint sampled at placement."""
        if not enabled():
            return
        self._opt_state_bytes[bool(sharded)].set(int(nbytes))


class ResilienceInstruments:
    """Fault-tolerance handles (train.resilience + utils.chaos)."""

    def __init__(self, registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        self.checkpoint_save_ms = reg.histogram(
            "resilience_checkpoint_save_ms",
            help="wall time of one checkpoint save (ms); for async saves "
            "this is the background write, NOT the step-loop stall")
        self.checkpoint_bytes = reg.gauge(
            "resilience_checkpoint_bytes",
            help="payload bytes of the most recent checkpoint save")
        self.checkpoints = reg.counter(
            "resilience_checkpoints_total",
            help="checkpoint saves committed (manifest written)")
        self.checkpoint_gc = reg.counter(
            "resilience_checkpoint_gc_total",
            help="checkpoints removed by keep-last-K retention GC")
        self.restores = reg.counter(
            "resilience_restores_total",
            help="successful restores from a committed checkpoint")
        self.restore_fallbacks = reg.counter(
            "resilience_restore_fallbacks_total",
            help="restores that skipped a torn or checksum-corrupt newer "
            "checkpoint and fell back to an older intact one")
        self.rollbacks = reg.counter(
            "resilience_rollbacks_total",
            help="divergence-guard rollbacks to the last checkpoint")
        self.divergence_events = reg.counter(
            "resilience_divergence_events_total",
            help="steps the divergence guard flagged (NaN/inf/spike)")
        self.preemptions = reg.counter(
            "resilience_preemptions_total",
            help="preemption signals honored with a checkpoint-and-exit")

    def record_save(self, dt_s: float, nbytes: int) -> None:
        if not enabled():
            return
        self.checkpoint_save_ms.observe(dt_s * 1000.0)
        self.checkpoint_bytes.set(int(nbytes))
        self.checkpoints.inc()


class AotCacheInstruments:
    """Persistent-executable-cache handles (compile.persistent)."""

    def __init__(self, registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        self.hits = reg.counter(
            "aot_cache_hits_total",
            help="compiled executables deserialized from the persistent "
            "on-disk cache (a warm process start shows only these)")
        self.misses = reg.counter(
            "aot_cache_misses_total",
            help="persistent-cache lookups that found no usable entry")
        self.compiles = reg.counter(
            "aot_cache_compiles_total",
            help="fresh XLA compiles performed through the persistent "
            "cache (each one is then serialized when the backend allows)")
        self.stores = reg.counter(
            "aot_cache_stores_total",
            help="serialized executables committed to disk")
        self.errors = reg.counter(
            "aot_cache_errors_total",
            help="defective entries (crc/header mismatch, torn write) and "
            "serialize/deserialize failures — all degrade to a recompile, "
            "never to serving a stale executable")
        self.bytes_read = reg.counter(
            "aot_cache_bytes_read_total",
            help="entry bytes read on disk hits")
        self.bytes_written = reg.counter(
            "aot_cache_bytes_written_total",
            help="entry bytes committed on stores")
        self.load_ms = reg.histogram(
            "aot_cache_load_ms",
            help="disk-hit wall time: read + crc verify + deserialize (ms)")
        self.store_ms = reg.histogram(
            "aot_cache_store_ms",
            help="store wall time: serialize + atomic commit (ms)")
        self.last_error: Optional[str] = None

    def note_error(self, where: str, exc: BaseException) -> None:
        """Keep the most recent defect human-readable for debugging (the
        counters say how often; this says what)."""
        self.last_error = f"{where}: {exc!r}"[:500]


class CommsInstruments:
    """Cross-host compressed-gradient-exchange handles
    (parallel.hierarchical).  Labeled by codec so the threshold path and
    the dense A/B baseline stay separable in one registry."""

    def __init__(self, registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        self._bytes = {
            codec: reg.counter(
                "comms_bytes_on_wire_total",
                help="gradient payload bytes sent+received over the "
                "DCN/host hop (TCP frames incl. length prefixes)",
                labels={"codec": codec})
            for codec in ("threshold", "dense")}
        self._exchanges = {
            codec: reg.counter(
                "comms_exchanges_total",
                help="cross-host gradient exchanges completed",
                labels={"codec": codec})
            for codec in ("threshold", "dense")}
        self.compression_ratio = reg.gauge(
            "comms_compression_ratio",
            help="dense-bytes / wire-bytes of the most recent compressed "
            "exchange (1.0 on the dense path)")
        self.exchange_ms = reg.histogram(
            "comms_exchange_ms",
            help="wall time of one cross-host gradient exchange: D2H + "
            "encode + TCP all-gather + decode + sum (ms)")

    def record_exchange(self, dt_s: float, wire_bytes: int, ratio: float,
                        compressed: bool) -> None:
        if not enabled():
            return
        codec = "threshold" if compressed else "dense"
        self._bytes[codec].inc(int(wire_bytes))
        self._exchanges[codec].inc()
        self.compression_ratio.set(float(ratio))
        self.exchange_ms.observe(dt_s * 1000.0)


class GangInstruments:
    """Elastic gang-membership handles (parallel.transport elastic mesh +
    train.resilience ElasticTrainer).  One unlabeled series set per
    process — a process is exactly one gang member."""

    def __init__(self, registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        self._reg = reg
        self.generation = reg.gauge(
            "gang_generation",
            help="current membership generation of the gradient-mesh gang "
            "(bumped by every reformation; stale-generation traffic is "
            "fenced)")
        self.members = reg.gauge(
            "gang_members", help="live ranks in the gradient-mesh gang")
        self.detection_ms = reg.histogram(
            "gang_detection_ms",
            help="silence observed on a peer at the moment it was declared "
            "lost (ms) — the failure-detection latency the heartbeat "
            "deadline bounds")
        self.resume_ms = reg.histogram(
            "gang_resume_ms",
            help="wall time from catching a reformation to training "
            "resumed: sharing rebuild + checkpoint restore + iterator "
            "fast-forward (ms)")
        self.stale_frames = reg.counter(
            "gang_stale_frames_total",
            help="stale-generation data frames fenced and dropped — "
            "traffic from a previous membership generation that must "
            "never be summed into gradients")
        self._reformations: dict = {}

    def reformations(self, cause: str):
        c = self._reformations.get(cause)
        if c is None:
            c = self._reg.counter(
                "gang_reformations_total",
                help="gang membership reformations, by cause "
                "(crash|partition|straggler|join)",
                labels={"cause": cause})
            self._reformations[cause] = c
        return c

    def record_membership(self, generation: int, members: int) -> None:
        if not enabled():
            return
        self.generation.set(int(generation))
        self.members.set(int(members))

    def record_reform(self, cause: str, detection_ms: Optional[float],
                      generation: int, members: int) -> None:
        if not enabled():
            return
        self.reformations(cause).inc()
        if detection_ms is not None:
            self.detection_ms.observe(float(detection_ms))
        self.record_membership(generation, members)


class FleetInstruments:
    """Multi-model fleet handles (serving.fleet).  Per-model families are
    created lazily and memoized — a 64-model long-tail fleet touches each
    child once, then records through plain attribute access."""

    def __init__(self, registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        self._reg = reg
        self.models = reg.gauge(
            "fleet_models", help="models deployed to the fleet")
        self.resident = reg.gauge(
            "fleet_models_resident",
            help="models currently device-resident (warm-pool occupancy; "
            "bounded by max_resident)")
        self._admissions = {
            flag: reg.counter(
                "fleet_admissions_total",
                help="warm-pool admissions (warm=true deserialized every "
                "executable from the persistent AOT cache — zero compiles)",
                labels={"warm": "true" if flag else "false"})
            for flag in (True, False)}
        self.evictions = reg.counter(
            "fleet_evictions_total",
            help="LRU warm-pool evictions (batcher drained, device "
            "buffers dropped, host registry entry kept)")
        self.rebalances = reg.counter(
            "fleet_rebalances_total",
            help="controller device-slice reallocations between replica "
            "groups")
        self.routing_ms = reg.histogram(
            "fleet_routing_ms",
            help="router decision wall time: admission/shed check + "
            "least-loaded replica pick (ms; excludes admission warmup)")
        self.replica_unhealthy = reg.counter(
            "fleet_replica_unhealthy_total",
            help="replicas removed from routing after consecutive "
            "dispatch failures (the gang-heartbeat analog for serving)")
        self.replica_probes = reg.counter(
            "fleet_replica_probes_total",
            help="requests deliberately routed to an unhealthy replica "
            "as a recovery probe (one success restores routing)")
        self.drain_timeouts = reg.counter(
            "serving_drain_timeouts_total",
            help="replica drains that did not finish inside the shared "
            "concurrent-drain deadline (the drain keeps running on its "
            "daemon thread; leftover futures fail over)")
        self.hedges = reg.counter(
            "fleet_hedges_total",
            help="speculative duplicate dispatches launched after "
            "hedge_fraction of a request's deadline budget elapsed")
        self.hedge_wasted = reg.counter(
            "fleet_hedge_wasted_total",
            help="duplicate completions suppressed after the client "
            "future was already settled (a late original or hedge — "
            "never double-counted)")
        self.failovers = reg.counter(
            "fleet_failovers_total",
            help="failed dispatch attempts re-routed to the next healthy "
            "replica with the remaining deadline budget")
        self.respawn_ms = reg.histogram(
            "fleet_respawn_ms",
            help="detection-to-routable wall time of one replica "
            "self-heal (detect + drain + rebuild through the AOT cache)")
        self.degraded_level = reg.gauge(
            "fleet_degraded_level",
            help="degraded-mode ladder level: 0=full 1=hedges_off "
            "2=quantized 3=shed_floor")
        self.snapshot_age = reg.gauge(
            "fleet_snapshot_age_s",
            help="seconds since the last committed fleet topology "
            "snapshot (-1 before the first, or when snapshots are off)")
        self._requests: dict = {}
        self._sheds: dict = {}
        self._breaches: dict = {}
        self._respawns: dict = {}
        self._breaker_state: dict = {}

    def record_admission(self, warm: bool) -> None:
        if not enabled():
            return
        self._admissions[bool(warm)].inc()

    def requests(self, model: str):
        c = self._requests.get(model)
        if c is None:
            c = self._reg.counter(
                "fleet_requests_total",
                help="requests routed through the fleet per model",
                labels={"model": model})
            self._requests[model] = c
        return c

    def sheds(self, model: str, priority: int):
        key = (model, int(priority))
        c = self._sheds.get(key)
        if c is None:
            c = self._reg.counter(
                "fleet_sheds_total",
                help="requests shed under sustained SLO pressure "
                "(lowest priority classes first)",
                labels={"model": model, "priority": str(int(priority))})
            self._sheds[key] = c
        return c

    def breaches(self, model: str):
        c = self._breaches.get(model)
        if c is None:
            c = self._reg.counter(
                "fleet_slo_breaches_total",
                help="sustained p99-over-target onsets per model",
                labels={"model": model})
            self._breaches[model] = c
        return c

    def respawns(self, cause: str):
        c = self._respawns.get(cause)
        if c is None:
            c = self._reg.counter(
                "fleet_replica_respawns_total",
                help="replicas torn down and rebuilt by the controller, "
                "by cause (poisoned | unhealthy | hung)",
                labels={"cause": cause})
            self._respawns[cause] = c
        return c

    def breaker_state(self, model: str):
        g = self._breaker_state.get(model)
        if g is None:
            g = self._reg.gauge(
                "fleet_breaker_state",
                help="worst replica circuit-breaker state per model: "
                "0=closed 1=half-open 2=open",
                labels={"model": model})
            self._breaker_state[model] = g
        return g


class FederationInstruments:
    """Cross-host federation handles (serving.federation).  Mirrors the
    gang bundle's membership surface — generation, live-member gauge,
    cause-labeled evictions, detection latency, stale-frame fencing —
    plus the serving-side recovery counters (warm re-placements and
    cross-host deadline-carrying failovers)."""

    def __init__(self, registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        self._reg = reg
        self.hosts = reg.gauge(
            "fed_hosts", help="live hosts in the serving federation")
        self.generation = reg.gauge(
            "fed_generation",
            help="current federation membership generation (bumps on "
            "every eviction and admission)")
        self.cross_host_failovers = reg.counter(
            "fed_cross_host_failovers_total",
            help="requests re-dispatched to another host with the "
            "remaining deadline budget after their host failed")
        self.stale_dispatch = reg.counter(
            "fed_stale_dispatch_total",
            help="stale-generation dispatch replies fenced at the router "
            "or a host agent — counted, never returned to a client")
        self.detection_ms = reg.histogram(
            "fed_detection_ms",
            help="silence observed on a host when it was declared lost "
            "(federation failure-detection latency)")
        self.replace_ms = reg.histogram(
            "fed_replace_ms",
            help="eviction-to-replaced wall time of one dead-host model "
            "re-placement on a survivor")
        self._evictions: dict = {}
        self._replacements = {
            flag: reg.counter(
                "fed_replacements_total",
                help="dead-host model re-placements onto survivor hosts "
                "(warm=true paid zero fresh compiles through the shared "
                "persistent AOT cache)",
                labels={"warm": "true" if flag else "false"})
            for flag in (True, False)}

    def evictions(self, cause: str):
        c = self._evictions.get(cause)
        if c is None:
            c = self._reg.counter(
                "fed_host_evictions_total",
                help="hosts evicted from the federation, by cause "
                "(crash | partition | straggler)",
                labels={"cause": cause})
            self._evictions[cause] = c
        return c

    def record_membership(self, generation: int, hosts: int) -> None:
        if not enabled():
            return
        self.generation.set(int(generation))
        self.hosts.set(int(hosts))

    def record_eviction(self, cause: str, detection_ms: float,
                        generation: int, hosts: int) -> None:
        if not enabled():
            return
        self.evictions(cause).inc()
        self.detection_ms.observe(float(detection_ms))
        self.record_membership(generation, hosts)

    def record_replacement(self, warm: bool, replace_ms: float) -> None:
        if not enabled():
            return
        self._replacements[bool(warm)].inc()
        self.replace_ms.observe(float(replace_ms))


class QuantInstruments:
    """Quantized-inference handles (quant.calibrate / quant.ptq).
    Per-dtype model counters are created lazily and memoized, matching
    the fleet bundle's labeled-child pattern."""

    def __init__(self, registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        self._reg = reg
        self.calibration_batches = reg.counter(
            "quant_calibration_batches_total",
            help="batches consumed by PTQ calibration passes (percentile "
            "observers replay the iterator, so each pass counts)")
        self.bytes_saved = reg.gauge(
            "quant_bytes_saved",
            help="param bytes saved by quantization: f32 resident bytes "
            "minus quantized resident bytes, summed over quantized models")
        self.accuracy_delta = reg.gauge(
            "quant_accuracy_delta",
            help="f32-vs-quantized disagreement of the most recent parity "
            "check (top-1 disagreement fraction, or relative error for "
            "regression heads)")
        self._models: dict = {}

    def record_calibration_batch(self) -> None:
        if not enabled():
            return
        self.calibration_batches.inc()

    def models(self, dtype: str):
        c = self._models.get(dtype)
        if c is None:
            c = self._reg.counter(
                "quant_models_total",
                help="models quantized, labeled by the dominant produced "
                "dtype (int8, or bf16 when range-hostile fallback won)",
                labels={"dtype": dtype})
            self._models[dtype] = c
        return c

    def record_model(self, dtype: str, bytes_saved: int) -> None:
        if not enabled():
            return
        self.models(dtype).inc()
        self.bytes_saved.inc(bytes_saved)

    def record_accuracy_delta(self, delta: float) -> None:
        if not enabled():
            return
        self.accuracy_delta.set(float(delta))


class DecodeInstruments:
    """Autoregressive decode-engine handles (serving.decode).  Everything
    is a lazily-created labeled child keyed per model, matching the fleet
    bundle's pattern, so N decode fleet members land on one aggregatable
    family each instead of N private stores."""

    def __init__(self, registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        self._reg = reg
        self._tokens: dict = {}
        self._inter_token: dict = {}
        self._blocks: dict = {}
        self._bytes: dict = {}
        self._active: dict = {}
        self._restarts: dict = {}

    def tokens(self, model: str):
        c = self._tokens.get(model)
        if c is None:
            c = self._reg.counter(
                "decode_tokens_total",
                help="tokens emitted by the decode engine (prefill last "
                "token + every generated token)",
                labels={"model": model})
            self._tokens[model] = c
        return c

    def inter_token(self, model: str):
        h = self._inter_token.get(model)
        if h is None:
            h = self._reg.histogram(
                "decode_inter_token_ms",
                help="wall time between consecutive tokens of one "
                "sequence — the per-token SLO series (p99 drives the "
                "fleet tracker for decode members)",
                labels={"model": model})
            self._inter_token[model] = h
        return h

    def kv_blocks(self, model: str):
        g = self._blocks.get(model)
        if g is None:
            g = self._reg.gauge(
                "decode_kv_blocks_in_use",
                help="KV pages currently allocated out of the shared "
                "pool (free-list allocator occupancy)",
                labels={"model": model})
            self._blocks[model] = g
        return g

    def kv_bytes(self, model: str, dtype: str):
        key = (model, dtype)
        g = self._bytes.get(key)
        if g is None:
            g = self._reg.gauge(
                "decode_kv_bytes",
                help="bytes of KV-cache pages currently in use, labeled "
                "by page dtype (int8 pages count their f32 scales too)",
                labels={"model": model, "dtype": dtype})
            self._bytes[key] = g
        return g

    def sequences_active(self, model: str):
        g = self._active.get(model)
        if g is None:
            g = self._reg.gauge(
                "decode_sequences_active",
                help="sequences currently holding KV pages in the "
                "token-level continuous batcher (admitted, not retired)",
                labels={"model": model})
            self._active[model] = g
        return g

    def restarts(self, model: str):
        c = self._restarts.get(model)
        if c is None:
            c = self._reg.counter(
                "decode_sequence_restarts_total",
                help="sequences explicitly restarted from token 0 on "
                "another replica after a replica failure (decode "
                "failover is restart-and-count, never silent resume)",
                labels={"model": model})
            self._restarts[model] = c
        return c

    def record_token(self, model: str, inter_token_ms: Optional[float],
                     n: int = 1) -> None:
        if not enabled():
            return
        self.tokens(model).inc(n)
        if inter_token_ms is not None:
            self.inter_token(model).observe(float(inter_token_ms))

    def record_kv(self, model: str, blocks_in_use: int, bytes_in_use: int,
                  dtype: str) -> None:
        if not enabled():
            return
        self.kv_blocks(model).set(int(blocks_in_use))
        self.kv_bytes(model, dtype).set(int(bytes_in_use))

    def record_active(self, model: str, n: int) -> None:
        if not enabled():
            return
        self.sequences_active(model).set(int(n))

    def record_restart(self, model: str) -> None:
        if not enabled():
            return
        self.restarts(model).inc()


_pipeline: Optional[PipelineInstruments] = None
_resilience: Optional[ResilienceInstruments] = None
_aot: Optional[AotCacheInstruments] = None
class OpsInstruments:
    """Fused-kernel tier handles (ops.pallas.dispatch + the tile stage of
    compile.autotune).  Per-(kernel, impl) dispatch counters are created
    lazily and memoized, matching the fleet bundle's labeled-child
    pattern."""

    def __init__(self, registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        self._reg = reg
        self.tile_search_ms = reg.histogram(
            "autotune_tile_search_ms",
            help="wall time of one TileConfig grid+greedy search "
            "(cache-miss path of compile.autotune.autotune_tiles)")
        self.tile_cache_hits = reg.counter(
            "autotune_tile_cache_hits_total",
            help="tile lookups served by the persisted tile table with "
            "zero re-search")
        self._dispatch: dict = {}

    def dispatch(self, kernel: str, impl: str):
        key = (kernel, impl)
        c = self._dispatch.get(key)
        if c is None:
            c = self._reg.counter(
                "ops_kernel_dispatch_total",
                help="fused-kernel tier dispatch decisions, labeled by "
                "kernel name and chosen implementation (pallas vs jnp "
                "reference); counted at trace time",
                labels={"kernel": kernel, "impl": impl})
            self._dispatch[key] = c
        return c

    def record_dispatch(self, kernel: str, impl: str) -> None:
        if not enabled():
            return
        self.dispatch(kernel, impl).inc()

    def record_tile_search_ms(self, ms: float) -> None:
        if not enabled():
            return
        self.tile_search_ms.observe(float(ms))

    def record_tile_cache_hit(self) -> None:
        if not enabled():
            return
        self.tile_cache_hits.inc()


class ArbiterInstruments:
    """Pod-arbiter handles (train.arbiter SliceArbiter) — slice
    movement between the elastic training gang and the serving fleet.
    Labeled children (direction/outcome/owner) are created lazily and
    memoized, matching the fleet bundle's pattern."""

    def __init__(self, registry_: Optional[MetricsRegistry] = None):
        reg = registry_ if registry_ is not None else registry()
        self._reg = reg
        self.handoff_ms = reg.histogram(
            "arbiter_handoff_ms",
            help="wall time of one committed slice handoff, journal "
            "phase-1 write to commit (shrink/drain + lease/readmit "
            "inclusive)")
        self.journal_replays = reg.counter(
            "arbiter_journal_replays_total",
            help="in-flight handoffs resumed from the crc-guarded "
            "journal after an arbiter restart (crash recovery, not the "
            "happy path)")
        self.leases = reg.gauge(
            "arbiter_leases",
            help="slices currently leased to the serving fleet (owner="
            "serving rows of the lease table)")
        self._handoffs: dict = {}
        self._slices: dict = {}

    def handoffs(self, direction: str, outcome: str):
        key = (direction, outcome)
        c = self._handoffs.get(key)
        if c is None:
            c = self._reg.counter(
                "arbiter_handoffs_total",
                help="slice handoffs by direction "
                "(to_serving|to_training) and outcome "
                "(committed|replayed|aborted)",
                labels={"direction": direction, "outcome": outcome})
            self._handoffs[key] = c
        return c

    def slices(self, owner: str):
        g = self._slices.get(owner)
        if g is None:
            g = self._reg.gauge(
                "arbiter_slices",
                help="pod slices by current lease-table owner "
                "(training|serving|transit)",
                labels={"owner": owner})
            self._slices[owner] = g
        return g

    def record_handoff(self, direction: str, outcome: str,
                       ms: Optional[float] = None) -> None:
        if not enabled():
            return
        self.handoffs(direction, outcome).inc()
        if ms is not None:
            self.handoff_ms.observe(float(ms))

    def record_owners(self, counts: dict) -> None:
        """Export the lease table: {owner: n_slices}."""
        if not enabled():
            return
        for owner in ("training", "serving", "transit"):
            self.slices(owner).set(int(counts.get(owner, 0)))
        self.leases.set(int(counts.get("serving", 0)))


_quant: Optional[QuantInstruments] = None
_ops: Optional[OpsInstruments] = None
_decode: Optional[DecodeInstruments] = None
_arbiter: Optional[ArbiterInstruments] = None


def arbiter_instruments() -> ArbiterInstruments:
    """Process-wide pod-arbiter handle bundle (lazy singleton)."""
    global _arbiter
    if _arbiter is None:
        _arbiter = ArbiterInstruments()
    return _arbiter


def decode_instruments() -> DecodeInstruments:
    """Process-wide decode-engine handle bundle (lazy singleton)."""
    global _decode
    if _decode is None:
        _decode = DecodeInstruments()
    return _decode


def quant_instruments() -> QuantInstruments:
    """Process-wide quant handle bundle (lazy singleton)."""
    global _quant
    if _quant is None:
        _quant = QuantInstruments()
    return _quant


def ops_instruments() -> OpsInstruments:
    """Process-wide fused-kernel-tier handle bundle (lazy singleton)."""
    global _ops
    if _ops is None:
        _ops = OpsInstruments()
    return _ops


def aot_instruments() -> AotCacheInstruments:
    """Process-wide AOT-cache handle bundle (lazy singleton)."""
    global _aot
    if _aot is None:
        _aot = AotCacheInstruments()
    return _aot


_comms: Optional[CommsInstruments] = None
_gang: Optional[GangInstruments] = None
_federation: Optional[FederationInstruments] = None


def gang_instruments() -> GangInstruments:
    """Process-wide gang handle bundle (lazy singleton)."""
    global _gang
    if _gang is None:
        _gang = GangInstruments()
    return _gang


def federation_instruments() -> FederationInstruments:
    """Process-wide federation handle bundle (lazy singleton)."""
    global _federation
    if _federation is None:
        _federation = FederationInstruments()
    return _federation


def comms_instruments() -> CommsInstruments:
    """Process-wide comms handle bundle (lazy singleton)."""
    global _comms
    if _comms is None:
        _comms = CommsInstruments()
    return _comms


def pipeline_instruments() -> PipelineInstruments:
    """Process-wide pipeline handle bundle (lazy singleton)."""
    global _pipeline
    if _pipeline is None:
        _pipeline = PipelineInstruments()
    return _pipeline


def resilience_instruments() -> ResilienceInstruments:
    """Process-wide resilience handle bundle (lazy singleton)."""
    global _resilience
    if _resilience is None:
        _resilience = ResilienceInstruments()
    return _resilience


perf_counter = time.perf_counter   # re-export: hot paths import one name
