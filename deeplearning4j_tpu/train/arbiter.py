"""Pod-level slice arbiter: preemption-safe DeviceSlice handoffs between
an elastic training gang and a serving fleet.

One pod runs both workloads (the DL4J ParallelWrapper-vs-Spark
train/serve duality): serving peaks daytime, training backfills nights.
The :class:`SliceArbiter` owns the pod's movable slice inventory and
moves slices between the two sides as a TWO-PHASE, JOURNALED state
machine:

* scale-to-serving — checkpoint-coordinated ``GangReformed`` shrink
  (blocking save at the coordinated resume step, survivors bitwise-
  rewind, ZeRO-1 moments reshard to the surviving world), then the freed
  slice is leased to the fleet, pre-warmed through the shared persistent
  AOT cache (``fresh_compiles == 0``);
* scale-to-training — the fleet drains the replica(s) off the slice
  (remove-from-routing first, concurrent drain under a deadline; a hung
  replica expires and the slice is released anyway), the slice returns,
  and the gang re-admits it as a parked joiner at a bumped generation.

Every transition is written to a crc-guarded journal (tmp + fsync +
``os.replace``, the fleet-snapshot discipline) BEFORE it executes, so a
crash at ANY point — gang rank killed mid-shrink, replica hung
mid-drain, the arbiter process killed between journal phases — recovers
by replaying the journal: each executor is idempotent, the slice is
never double-owned, never orphaned, and training always bitwise-resumes
from the pre-shrink checkpoint.

The lease table (`owner` per slice: ``training | serving | transit``) is
consulted by ``FleetController.reconcile`` via
``fleet.attach_arbiter(arbiter)`` — the controller never grows onto a
slice the journal says is in transit back to the gang.

Training-side endpoints (duck-typed — ``held_slices() / shrink(slice) /
readmit(slice)``):

* :class:`LocalElasticGang` — in-process reference implementation over a
  model + :class:`~deeplearning4j_tpu.train.resilience.CheckpointManager`
  (what the tests and the example drive); shrink/readmit exercise the
  real blocking-save + pinned-restore path, so the bitwise gate is
  load-bearing, not assumed.
* :class:`GangControlClient` — file-protocol client for a REAL elastic
  gang in other processes, speaking ``ElasticTrainer``'s control-dir
  ``shrink-request.json`` / ``shrink-ack.json`` handshake.
"""
from __future__ import annotations

import json
import os
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional

from deeplearning4j_tpu.monitor.instrument import arbiter_instruments
from deeplearning4j_tpu.serving.slo import ArbiterPolicy

JOURNAL_FORMAT = 1

OWNER_TRAINING = "training"
OWNER_SERVING = "serving"
OWNER_TRANSIT = "transit"

TO_SERVING = "to_serving"
TO_TRAINING = "to_training"

# phase order per direction; a journal record at phase P means every
# phase before P has fully executed and P is the next thing to (re)do
PHASES = {TO_SERVING: ("shrink", "grant"),
          TO_TRAINING: ("drain", "readmit")}


class JournalCorruptError(RuntimeError):
    """The handoff journal failed its crc32 / structure check."""


class ArbiterBusyError(RuntimeError):
    """A handoff is already journaled in flight; finish or recover it
    before starting another (one slice in transit at a time is the
    invariant that keeps replay unambiguous)."""


class HandoffAbortedError(RuntimeError):
    """The counterparty refused or timed out; the journal was rolled
    back and the slice returned to its previous owner."""


def _canonical(body: Dict[str, Any]) -> bytes:
    return json.dumps(body, sort_keys=True,
                      separators=(",", ":")).encode("utf-8")


class HandoffJournal:
    """Single-file crc-guarded journal: the lease table plus at most one
    in-flight handoff record.  `commit()` is atomic (tmp + fsync +
    ``os.replace``) — a crash mid-write leaves the previous committed
    state intact; `load()` refuses torn or bit-rotted files outright
    rather than half-applying them."""

    def __init__(self, path: str):
        self.path = str(path)
        self.commits = 0

    def load(self) -> Optional[Dict[str, Any]]:
        """The last committed state, or None when no journal exists yet.
        Raises :class:`JournalCorruptError` on damage."""
        if not os.path.exists(self.path):
            return None
        try:
            with open(self.path, "r", encoding="utf-8") as f:
                payload = json.load(f)
        except (OSError, ValueError) as e:
            raise JournalCorruptError(
                f"{self.path}: unreadable journal ({e})") from e
        if not isinstance(payload, dict) \
                or payload.get("format") != JOURNAL_FORMAT:
            raise JournalCorruptError(
                f"{self.path}: journal format mismatch "
                f"(got {payload.get('format')!r}, "
                f"want {JOURNAL_FORMAT})")
        body = payload.get("state")
        crc = zlib.crc32(_canonical(body)) & 0xFFFFFFFF
        if crc != payload.get("crc32"):
            raise JournalCorruptError(
                f"{self.path}: crc mismatch "
                f"(stored {payload.get('crc32')}, computed {crc})")
        return body

    def commit(self, state: Dict[str, Any]) -> str:
        payload = {"format": JOURNAL_FORMAT, "saved_at": time.time(),
                   "state": state,
                   "crc32": zlib.crc32(_canonical(state)) & 0xFFFFFFFF}
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(payload, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)
        self.commits += 1
        return self.path


# ---------------------------------------------------------------------------
# Training-side endpoints
# ---------------------------------------------------------------------------

class LocalElasticGang:
    """In-process training-side endpoint: the reference implementation
    of the gang protocol the arbiter drives.

    World size is the number of slices held.  `shrink` commits a
    BLOCKING checkpoint first, then drops the slice and restores the
    model pinned to that coordinated step — the same save-then-rewind
    ordering the real gang's coordinator performs, through the real
    :class:`CheckpointManager`, so a test comparing post-handoff
    training against an uninterrupted run is checking actual restore
    bitwise-ness, not a stub.  `readmit` is the epoch-boundary grow:
    blocking save, add the slice at a bumped generation, restore from
    the same step (the joiner starts from identical state).

    `reshard` (optional callable, `devices -> None`) is invoked after
    every world change with the devices of the surviving slices — hook
    `parallel.zero.reshard_to_devices` here for ZeRO-1 models.
    """

    def __init__(self, model, manager, slices: List[int],
                 devices_of: Optional[Callable[[int], Any]] = None,
                 reshard: Optional[Callable[[List[Any]], Any]] = None):
        self.model = model
        self.manager = manager
        self._held = [int(s) for s in slices]
        self.devices_of = devices_of
        self.reshard = reshard
        self.generation = 0
        self.events: List[Dict[str, Any]] = []

    # ---- protocol ----
    def held_slices(self) -> List[int]:
        return list(self._held)

    @property
    def world(self) -> int:
        return len(self._held)

    def _world_changed(self, cause: str, step: int) -> Dict[str, Any]:
        self.generation += 1
        if self.reshard is not None and self.devices_of is not None:
            devices = [d for s in self._held
                       for d in (self.devices_of(s) or ())]
            if devices:
                self.reshard(devices)
        # coordinated rewind: restore pinned to the step just saved, so
        # the post-handoff world starts from exactly the committed state
        self.manager.restore(self.model, step=step)
        info = {"cause": cause, "generation": self.generation,
                "world": self.world, "resume_step": step}
        self.events.append(info)
        return info

    def shrink(self, pod_slice: int) -> Dict[str, Any]:
        """Release `pod_slice` at a coordinated checkpoint.  Idempotent:
        shrinking a slice no longer held re-reports the last state."""
        pod_slice = int(pod_slice)
        if pod_slice not in self._held:
            return {"resume_step": self.manager.latest_step(),
                    "generation": self.generation, "world": self.world,
                    "already": True}
        self.manager.save(self.model, block=True)
        step = int(self.manager.latest_step() or 0)
        self._held.remove(pod_slice)
        return self._world_changed("shrink", step)

    def readmit(self, pod_slice: int) -> Dict[str, Any]:
        """Re-admit `pod_slice` as a joiner at a bumped generation.
        Idempotent: readmitting a slice already held is a no-op."""
        pod_slice = int(pod_slice)
        if pod_slice in self._held:
            return {"generation": self.generation, "world": self.world,
                    "already": True}
        self.manager.save(self.model, block=True)
        step = int(self.manager.latest_step() or 0)
        self._held.append(pod_slice)
        self._held.sort()
        return self._world_changed("join", step)


class GangControlClient:
    """Arbiter-side endpoint for a REAL elastic gang running in other
    processes: speaks ``ElasticTrainer``'s control-dir file protocol.

    `shrink` atomically writes ``shrink-request.json`` naming the gang
    rank to evict (default: `rank_of(pod_slice)`, default identity) and
    waits up to `timeout_s` for the coordinator's ``shrink-ack.json``
    carrying the coordinated resume step and new generation.  `readmit`
    only updates the held-set — a parked/relaunched worker re-admits
    ITSELF through the gang's joiner path (epoch boundary); the arbiter
    just stops counting the slice as leased out.
    """

    REQUEST = "shrink-request.json"
    ACK = "shrink-ack.json"

    def __init__(self, control_dir: str, slices: List[int],
                 rank_of: Optional[Callable[[int], int]] = None,
                 timeout_s: float = 30.0, poll_s: float = 0.05):
        self.control_dir = str(control_dir)
        os.makedirs(self.control_dir, exist_ok=True)
        self._held = [int(s) for s in slices]
        self.rank_of = rank_of if rank_of is not None else (lambda s: s)
        self.timeout_s = float(timeout_s)
        self.poll_s = float(poll_s)
        self._seq = 0

    def held_slices(self) -> List[int]:
        return list(self._held)

    def shrink(self, pod_slice: int) -> Dict[str, Any]:
        pod_slice = int(pod_slice)
        if pod_slice not in self._held:
            return {"already": True}
        self._seq += 1
        req_id = f"shrink-{os.getpid()}-{self._seq}-{time.time_ns()}"
        req_path = os.path.join(self.control_dir, self.REQUEST)
        ack_path = os.path.join(self.control_dir, self.ACK)
        tmp = req_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"id": req_id, "rank": int(self.rank_of(pod_slice)),
                       "slice": pod_slice}, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, req_path)
        deadline = time.monotonic() + self.timeout_s
        while time.monotonic() < deadline:
            try:
                with open(ack_path) as f:
                    ack = json.load(f)
            except (OSError, ValueError):
                time.sleep(self.poll_s)
                continue
            if ack.get("request_id") != req_id:
                time.sleep(self.poll_s)     # stale ack from a prior run
                continue
            try:
                os.remove(ack_path)
            except OSError:
                pass
            if ack.get("error"):
                raise HandoffAbortedError(
                    f"gang refused shrink: {ack['error']}")
            self._held.remove(pod_slice)
            return ack
        # withdraw the request: a timed-out shrink must leave no residue,
        # or the coordinator could later execute a shrink nobody wants
        # (and the stale file would shadow the next request)
        try:
            with open(req_path) as f:
                pending = json.load(f)
            if pending.get("id") == req_id:
                os.remove(req_path)
        except (OSError, ValueError):
            pass
        raise HandoffAbortedError(
            f"gang did not ack shrink request {req_id} within "
            f"{self.timeout_s}s")

    def readmit(self, pod_slice: int) -> Dict[str, Any]:
        pod_slice = int(pod_slice)
        if pod_slice not in self._held:
            self._held.append(pod_slice)
            self._held.sort()
        return {"parked_joiner": True}


# ---------------------------------------------------------------------------
# The arbiter
# ---------------------------------------------------------------------------

class SliceArbiter:
    """Owns the pod's movable slice inventory; every ownership change is
    journaled BEFORE it executes (see module docstring).

        gang = LocalElasticGang(model, manager, slices=[0, 1, 2])
        arb = SliceArbiter("pod/journal.json", training=gang,
                           fleet=fleet, policy=ArbiterPolicy())
        fleet.attach_arbiter(arb)
        arb.to_serving()            # shrink gang, lease slice to fleet
        arb.to_training()           # drain fleet, return slice to gang

    A relaunched arbiter constructs over the same journal path and calls
    `recover()` (the constructor does it): an in-flight handoff resumes
    from its journaled phase with idempotent executors and counts one
    `arbiter_journal_replays_total`.

    `devices_of(pod_slice)` maps a pod slice id to its device tuple (or
    None on virtual fleets) so the leased fleet slice pins the same
    hardware.  `chaos` (an object with ``on_journal(direction, phase)``)
    is the :class:`utils.chaos.HandoffChaos` injection point, called
    right after every journal commit — exactly between phases.
    """

    def __init__(self, journal_path: str, training,
                 fleet=None, policy: Optional[ArbiterPolicy] = None,
                 devices_of: Optional[Callable[[int], Any]] = None,
                 recover: bool = True, registry_=None):
        self.journal = HandoffJournal(journal_path)
        self.training = training
        self.fleet = fleet
        self.policy = policy if policy is not None else ArbiterPolicy()
        self.devices_of = devices_of
        self.chaos = None
        self.history: List[Dict[str, Any]] = []
        self._lock = threading.RLock()
        self._last_handoff_at: Optional[float] = None
        if registry_ is not None:
            from deeplearning4j_tpu.monitor.instrument import \
                ArbiterInstruments
            self._ins = ArbiterInstruments(registry_)
        else:
            self._ins = arbiter_instruments()
        self._state = self.journal.load()
        if self._state is None:
            self._state = {"seq": 0, "replays": 0, "handoff": None,
                           "leases": {str(s): OWNER_TRAINING
                                      for s in training.held_slices()},
                           "fleet_index": {}}
            self.journal.commit(self._state)
        self.recovered: Optional[Dict[str, Any]] = None
        if recover:
            self.recovered = self.recover()
        self._export_owners()

    # ---- lease table ----
    def owners(self) -> Dict[int, str]:
        """The lease table: pod slice id -> training|serving|transit."""
        with self._lock:
            return {int(s): o for s, o in self._state["leases"].items()}

    def owner_counts(self) -> Dict[str, int]:
        counts = {OWNER_TRAINING: 0, OWNER_SERVING: 0, OWNER_TRANSIT: 0}
        for o in self.owners().values():
            counts[o] = counts.get(o, 0) + 1
        return counts

    def fleet_index_of(self, pod_slice: int) -> Optional[int]:
        """The fleet-local slice index a pod slice is leased as."""
        with self._lock:
            idx = self._state["fleet_index"].get(str(int(pod_slice)))
            return int(idx) if idx is not None else None

    def blocked_fleet_slices(self) -> frozenset:
        """Fleet-local indexes the fleet must NOT place onto: the leased
        index of a handoff journaled back to training (any phase — from
        the moment the intent is journaled, the slice belongs to the
        gang even while it still sits in the fleet's free list)."""
        with self._lock:
            h = self._state.get("handoff")
            if h is not None and h["direction"] == TO_TRAINING \
                    and h.get("fleet_index") is not None:
                return frozenset({int(h["fleet_index"])})
            return frozenset()

    def describe(self) -> Dict[str, Any]:
        with self._lock:
            return {"leases": self.owners(),
                    "fleet_index": {int(k): v for k, v in
                                    self._state["fleet_index"].items()},
                    "handoff": (dict(self._state["handoff"])
                                if self._state["handoff"] else None),
                    "seq": self._state["seq"],
                    "replays": self._state["replays"],
                    "journal_commits": self.journal.commits}

    def _export_owners(self) -> None:
        self._ins.record_owners(self.owner_counts())

    # ---- journal plumbing ----
    def _commit(self, phase_note: Optional[str] = None) -> None:
        """Journal the current state, THEN run the chaos hook — the
        injection point 'arbiter killed between journal phases' needs
        the record durable before the fault fires."""
        self.journal.commit(self._state)
        h = self._state.get("handoff")
        if self.chaos is not None and h is not None:
            self.chaos.on_journal(h["direction"],
                                  phase_note or h.get("phase"))

    # ---- handoffs ----
    def _pick(self, owner: str, pod_slice: Optional[int]) -> int:
        leases = self._state["leases"]
        owned = sorted(int(s) for s, o in leases.items() if o == owner)
        if pod_slice is not None:
            pod_slice = int(pod_slice)
            if leases.get(str(pod_slice)) != owner:
                raise ValueError(
                    f"slice {pod_slice} is owned by "
                    f"{leases.get(str(pod_slice))!r}, not {owner!r}")
            return pod_slice
        if not owned:
            raise ValueError(f"no slice owned by {owner!r} to move")
        # highest index first: slice 0 is conventionally the
        # coordinator's and moves last (never, under min_training_slices)
        return owned[-1]

    def to_serving(self, pod_slice: Optional[int] = None
                   ) -> Dict[str, Any]:
        """Move one training slice to the fleet (two-phase).  Raises
        :class:`ArbiterBusyError` if a handoff is already in flight, and
        ``ValueError`` when policy floors forbid the move."""
        with self._lock:
            if self._state["handoff"] is not None:
                raise ArbiterBusyError(
                    f"handoff in flight: {self._state['handoff']}")
            counts = self.owner_counts()
            if counts[OWNER_TRAINING] <= self.policy.min_training_slices:
                raise ValueError(
                    f"training holds {counts[OWNER_TRAINING]} slice(s); "
                    f"min_training_slices={self.policy.min_training_slices}"
                    " forbids another shrink")
            if self.policy.max_fleet_leases \
                    and counts[OWNER_SERVING] \
                    >= self.policy.max_fleet_leases:
                raise ValueError(
                    f"{counts[OWNER_SERVING]} slices already leased; "
                    f"max_fleet_leases={self.policy.max_fleet_leases}")
            s = self._pick(OWNER_TRAINING, pod_slice)
            self._state["seq"] += 1
            self._state["handoff"] = {
                "id": f"h{self._state['seq']}", "direction": TO_SERVING,
                "slice": s, "phase": "shrink", "started_at": time.time()}
            self._state["leases"][str(s)] = OWNER_TRANSIT
            self._commit()              # phase-1 record BEFORE any effect
            return self._run_handoff()

    def to_training(self, pod_slice: Optional[int] = None
                    ) -> Dict[str, Any]:
        """Return one leased slice from the fleet to the gang
        (two-phase)."""
        with self._lock:
            if self._state["handoff"] is not None:
                raise ArbiterBusyError(
                    f"handoff in flight: {self._state['handoff']}")
            s = self._pick(OWNER_SERVING, pod_slice)
            self._state["seq"] += 1
            self._state["handoff"] = {
                "id": f"h{self._state['seq']}", "direction": TO_TRAINING,
                "slice": s, "phase": "drain",
                "fleet_index": self._state["fleet_index"].get(str(s)),
                "started_at": time.time()}
            self._state["leases"][str(s)] = OWNER_TRANSIT
            self._commit()
            return self._run_handoff()

    def recover(self) -> Optional[Dict[str, Any]]:
        """Resume a journaled in-flight handoff (idempotent executors
        re-run the recorded phase and everything after it).  Returns the
        completed handoff record, or None when nothing was in flight."""
        with self._lock:
            if self._state.get("handoff") is None:
                return None
            self._state["replays"] += 1
            self._ins.journal_replays.inc()
            return self._run_handoff(replay=True)

    # ---- the state machine ----
    def _run_handoff(self, replay: bool = False) -> Dict[str, Any]:
        """Execute (or resume) the in-flight handoff from its journaled
        phase.  Caller holds the lock and has committed the current
        record.  Every phase executor is idempotent — replay-safe."""
        h = self._state["handoff"]
        t0 = time.perf_counter()
        direction = h["direction"]
        s = int(h["slice"])
        try:
            if direction == TO_SERVING:
                if h["phase"] == "shrink":
                    if s in set(self.training.held_slices()):
                        info = self.training.shrink(s) or {}
                        h["resume_step"] = info.get("resume_step")
                        h["generation"] = info.get("generation")
                    h["phase"] = "grant"
                    self._commit()      # phase-2 record: shrink is done
                if h["phase"] == "grant":
                    if self.fleet is not None:
                        devices = (self.devices_of(s)
                                   if self.devices_of is not None else None)
                        idx = self.fleet.lease_slice(
                            devices=devices, tag=f"pod-{s}")
                        self._state["fleet_index"][str(s)] = int(idx)
                    self._state["leases"][str(s)] = OWNER_SERVING
            else:                       # TO_TRAINING
                if h["phase"] == "drain":
                    if self.fleet is not None \
                            and h.get("fleet_index") is not None:
                        h["released"] = self.fleet.release_slice(
                            int(h["fleet_index"]),
                            timeout=self.policy.drain_timeout_s)
                    h["phase"] = "readmit"
                    self._commit()      # phase-2 record: drain is done
                if h["phase"] == "readmit":
                    info = self.training.readmit(s) or {}
                    h["generation"] = info.get("generation")
                    self._state["fleet_index"].pop(str(s), None)
                    self._state["leases"][str(s)] = OWNER_TRAINING
        except HandoffAbortedError:
            # counterparty refused/timed out with NO side effect
            # committed: roll the lease back to its previous owner
            prev = OWNER_TRAINING if direction == TO_SERVING \
                else OWNER_SERVING
            self._state["leases"][str(s)] = prev
            self._state["handoff"] = None
            self.journal.commit(self._state)
            self._ins.record_handoff(direction, "aborted")
            self._export_owners()
            raise
        record = dict(h)
        record["outcome"] = "replayed" if replay else "committed"
        record["handoff_ms"] = round((time.perf_counter() - t0) * 1000.0,
                                     3)
        self._state["handoff"] = None
        self.journal.commit(self._state)    # commit record: handoff done
        self._last_handoff_at = time.monotonic()
        self._ins.record_handoff(direction, record["outcome"],
                                 record["handoff_ms"])
        self._export_owners()
        self.history.append(record)
        return record

    # ---- policy loop ----
    def pressure(self) -> float:
        """The scale-to-serving pressure signal: the max
        ``fleet_arrival_forecast{model=}`` gauge across models,
        normalized by the fleet's current request capacity estimate
        (healthy replicas x grow_at_queue — the queue depth reconcile
        itself grows at).  Returns 0.0 with no fleet or no forecast."""
        if self.fleet is None:
            return 0.0
        children = self.fleet._reg.children("fleet_arrival_forecast")
        forecast = max((g.value for _, g in children), default=0.0)
        if forecast <= 0.0:
            return 0.0
        replicas = sum(
            len(m.group.replicas) for m in self.fleet.pool.resident()
            if m.group is not None) or 1
        capacity = replicas * max(self.fleet.policy.grow_at_queue, 1)
        return forecast / capacity

    def maybe_rebalance(self, pressure: Optional[float] = None
                        ) -> Optional[Dict[str, Any]]:
        """One policy tick: grant a slice to serving when `pressure`
        (explicit, or :meth:`pressure`) exceeds `grant_at_forecast`,
        reclaim one when it falls below `return_below_forecast` — with
        the policy's cooldown and floors.  Returns the handoff record or
        None when no move is due/possible."""
        with self._lock:
            if self._state["handoff"] is not None:
                return None
            if self._last_handoff_at is not None \
                    and time.monotonic() - self._last_handoff_at \
                    < self.policy.cooldown_s:
                return None
            p = self.pressure() if pressure is None else float(pressure)
            counts = self.owner_counts()
            at_cap = (self.policy.max_fleet_leases
                      and counts[OWNER_SERVING]
                      >= self.policy.max_fleet_leases)
            if p >= self.policy.grant_at_forecast \
                    and counts[OWNER_TRAINING] \
                    > self.policy.min_training_slices \
                    and not at_cap:
                return self.to_serving()
            if p <= self.policy.return_below_forecast \
                    and counts[OWNER_SERVING] > 0:
                return self.to_training()
            return None
