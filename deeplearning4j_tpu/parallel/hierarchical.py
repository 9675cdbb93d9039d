"""Hierarchical compressed cross-host gradient all-reduce.

Reference: ParallelWrapper's Aeron threshold GradientSharing (SURVEY.md
§3.4) at DCN scale.  A TPU pod has two very different links: ICI inside a
slice (fast — XLA all-reduce belongs there, full precision, inside the
compiled step) and DCN between slices/hosts (slow — worth compressing).
The hierarchy:

    1. ICI phase (compiled "grad half"): every host's local mesh computes
       data-parallel gradients and reduces them over ICI exactly as the
       single-host step does.  Output: ONE gradient tree per host.
    2. DCN phase (this module, host-side): each host threshold-encodes its
       ICI-reduced tree (error-feedback residuals carried per host by the
       codecs), ships the sparse int32 streams over `TcpGradientMesh`,
       decodes every peer's stream, and sums.
    3. apply phase (compiled "apply half"): the summed (then averaged —
       `combine="mean"`) gradient feeds the normal updater loop, donated
       buffers and all.

Convergence parity comes from the error feedback: what a threshold cut
this step, the residual re-emits a later step, so the *sum over steps* of
applied gradients tracks the true sum (the reference's delta semantics).

The split-step threading lives in `nn/multilayer.py` / `nn/graph.py`
(`set_gradient_sharing`); this module owns the config, the host-side
exchange runtime, and the metric recording.  `world == 1` is fully
supported WITHOUT sockets — the encode/decode/residual path still runs,
which is what the in-process convergence tests and the single-host
default exercise.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Optional

import jax
import numpy as np

ENV_PID = "DL4J_TPU_PROCESS_ID"
ENV_NPROC = "DL4J_TPU_NUM_PROCESSES"
ENV_GRAD_PORT = "DL4J_TPU_GRADIENT_PORT"
ENV_GRAD_HOST = "DL4J_TPU_GRADIENT_HOST"
ENV_HEARTBEAT = "DL4J_TPU_HEARTBEAT_S"
ENV_DEADLINE = "DL4J_TPU_FAILURE_DEADLINE_S"
ENV_JOIN = "DL4J_TPU_JOIN"

PyTree = Any


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return default if v in (None, "") else int(v)


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return default if v in (None, "") else float(v)


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v in (None, ""):
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


@dataclasses.dataclass(frozen=True)
class HierarchicalGradientSharing:
    """Config for the DCN-phase gradient exchange.

    `rank`/`world`/`port`/`host` default to the `DL4J_TPU_*` env the
    multihost launchers already export (resolved at `resolve()` time, not
    import time), so a worker script just passes the config through.
    `compressed=False` selects the dense f32 wire path — same topology,
    no codec — the baseline the compressed path is compared against.
    `combine="mean"` divides the cross-host sum by `world`, matching the
    global-mean gradient a single SPMD mesh over all devices would produce;
    `combine="sum"` keeps the reference accumulator's raw-sum semantics.
    """

    threshold: float = 1e-3
    adaptive_target_density: float = 1e-2
    compressed: bool = True
    combine: str = "mean"             # "mean" | "sum"
    rank: Optional[int] = None        # default: env, else 0
    world: Optional[int] = None       # default: env, else 1
    port: Optional[int] = None        # default: env, else 49152
    host: Optional[str] = None        # default: env, else 127.0.0.1
    timeout: float = 60.0
    # elastic gang membership (PR 9): heartbeat failure detection +
    # generation-fenced reformation instead of fail-stop
    elastic: bool = False
    heartbeat_interval_s: Optional[float] = None   # env, else 0.25
    failure_deadline_s: Optional[float] = None     # env, else 5.0
    join: Optional[bool] = None       # env DL4J_TPU_JOIN, else False

    def __post_init__(self):
        if self.combine not in ("mean", "sum"):
            raise ValueError(f"combine must be 'mean' or 'sum', "
                             f"got {self.combine!r}")

    def resolve(self) -> "HierarchicalGradientSharing":
        """Fill rank/world/port/host (and the elastic knobs) from the
        launcher env."""
        return dataclasses.replace(
            self,
            rank=self.rank if self.rank is not None
            else _env_int(ENV_PID, 0),
            world=self.world if self.world is not None
            else _env_int(ENV_NPROC, 1),
            port=self.port if self.port is not None
            else _env_int(ENV_GRAD_PORT, 49152),
            host=self.host if self.host is not None
            else os.environ.get(ENV_GRAD_HOST, "127.0.0.1"),
            heartbeat_interval_s=self.heartbeat_interval_s
            if self.heartbeat_interval_s is not None
            else _env_float(ENV_HEARTBEAT, 0.25),
            failure_deadline_s=self.failure_deadline_s
            if self.failure_deadline_s is not None
            else _env_float(ENV_DEADLINE, 5.0),
            join=self.join if self.join is not None
            else _env_bool(ENV_JOIN, False))


class HierarchicalAllReduce:
    """The host-side DCN exchange runtime one model instance owns.

    Lazily builds the per-leaf codecs (from the first gradient tree it
    sees — that fixes leaf count/shapes) and the TCP mesh (skipped when
    `world == 1`).  `exchange(grads)` is the whole DCN phase: device →
    host, encode (or dense-pack), all-gather, decode, sum, combine, and
    metric recording.  NOT thread-safe — one exchange per model at a
    time, which the per-step training loop guarantees.
    """

    def __init__(self, config: HierarchicalGradientSharing):
        self.config = config.resolve()
        self._exchange = None          # CompressedGradientExchange
        self._mesh = None              # TcpGradientMesh | ElasticGradientMesh
        self._ready = False
        self._instr = None
        self._template = None          # gradient tree shape template
        self._resume_step_provider = None
        self._last_wire_bytes = 0
        self._last_ratio = 1.0
        self.exchanges = 0

    @property
    def rank(self) -> int:
        # elastic reformation can remap the rank in place
        return self._mesh.rank if self._mesh is not None \
            else self.config.rank

    @property
    def world(self) -> int:
        return self._mesh.world if self._mesh is not None \
            else self.config.world

    @property
    def mesh(self):
        return self._mesh

    def set_resume_step_provider(self, fn) -> None:
        """Coordinator-side callable returning the checkpoint step every
        member must resume from after a reformation (wired by
        ElasticTrainer to `CheckpointManager.latest_step`)."""
        self._resume_step_provider = fn
        if self._mesh is not None and hasattr(self._mesh,
                                              "resume_step_provider"):
            self._mesh.resume_step_provider = fn

    def _ensure(self, grads: PyTree) -> None:
        if self._ready:
            return
        from deeplearning4j_tpu.monitor.instrument import comms_instruments
        self._instr = comms_instruments()
        self._template = jax.tree_util.tree_map(
            lambda g: np.zeros(np.shape(g), np.float32), grads)
        if self.config.compressed:
            self._build_exchange()
        if self.config.elastic:
            from deeplearning4j_tpu.parallel.transport import (
                ElasticGradientMesh, GangReformed)
            self._mesh = ElasticGradientMesh(
                rank=self.config.rank, world=self.config.world,
                port=self.config.port, host=self.config.host,
                timeout=self.config.timeout,
                heartbeat_interval=self.config.heartbeat_interval_s,
                failure_deadline=self.config.failure_deadline_s,
                join=bool(self.config.join),
                resume_step_provider=self._resume_step_provider)
            if self.config.join and self._mesh.join_info is not None:
                # a replacement worker learns its resume point only at
                # admission — surface it as a reformation so the trainer
                # restores the SAME checkpoint the survivors rewound to
                # (the pre-join restore may be stale by now)
                self._ready = True
                raise GangReformed({
                    "generation": self._mesh.generation,
                    "world": self._mesh.world,
                    "rank": self._mesh.rank,
                    "rank_map": {self._mesh.rank: self._mesh.rank},
                    "lost": [], "cause": "join",
                    "resume_step": self._mesh.join_info.get(
                        "resume_step", 0)})
        elif self.config.world > 1:
            from deeplearning4j_tpu.parallel.transport import TcpGradientMesh
            self._mesh = TcpGradientMesh(
                rank=self.config.rank, world=self.config.world,
                port=self.config.port, host=self.config.host,
                timeout=self.config.timeout)
        self._ready = True

    def _build_exchange(self) -> None:
        from deeplearning4j_tpu.parallel.compression import (
            CompressedGradientExchange)
        self._exchange = CompressedGradientExchange(
            self._template, threshold=self.config.threshold,
            adaptive_target_density=self.config.adaptive_target_density)

    def rebuild(self, flush_residuals: bool = False) -> None:
        """Reset codec state after a gang reformation.

        Default (`flush_residuals=False`) builds FRESH codecs — zero
        residuals, thresholds back at the configured start — which is
        what checkpoint-rewind resume requires: the parked residual and
        the adapted thresholds were accumulated from steps the rewind
        discards, and every survivor resetting identically is what makes
        the resumed run bitwise-match a clean run from that checkpoint.
        `flush_residuals=True` instead carries the old error-feedback
        mass into the new codecs (forward, non-rewind semantics — no
        gradient silently lost when membership changes without a
        rewind)."""
        if self._template is None or not self.config.compressed:
            return
        old = self._exchange
        self._build_exchange()
        if flush_residuals and old is not None:
            self._exchange.flush_into(old.residuals())

    def exchange(self, grads: PyTree) -> PyTree:
        """ICI-reduced gradient tree in, DCN-combined tree out (numpy
        leaves — the apply half re-places them on device)."""
        t0 = time.perf_counter()
        host_grads = jax.tree_util.tree_map(
            lambda g: np.asarray(g, np.float32), grads)
        self._ensure(host_grads)
        mesh = self._mesh
        sent0 = mesh.bytes_sent + mesh.bytes_received if mesh else 0
        if self.config.compressed:
            total = self._exchange_compressed(host_grads)
            ratio = self._last_ratio
        else:
            total = self._exchange_dense(host_grads)
            ratio = 1.0
        if mesh is not None:
            self._last_wire_bytes = (mesh.bytes_sent + mesh.bytes_received
                                     - sent0)
        w = self.world                 # dynamic under elastic membership
        if self.config.combine == "mean" and w > 1:
            inv = np.float32(1.0 / w)
            total = jax.tree_util.tree_map(lambda a: a * inv, total)
        self.exchanges += 1
        self._instr.record_exchange(
            time.perf_counter() - t0, self._last_wire_bytes, ratio,
            self.config.compressed)
        return total

    def _exchange_compressed(self, host_grads: PyTree) -> PyTree:
        from deeplearning4j_tpu.parallel.transport import (pack_streams,
                                                           unpack_streams)
        ex = self._exchange
        streams = ex.encode(host_grads)
        self._last_ratio = ex.compression_ratio(streams)
        if self._mesh is None:
            # single host: the codec round-trip (residual semantics
            # included) still runs — convergence behavior matches a
            # 1-host member of a larger mesh
            self._last_wire_bytes = sum(4 * (len(s) + 1) for s in streams)
            return ex.decode(streams, ex.thresholds())
        payload = pack_streams(streams, ex.thresholds())
        total = None
        for peer_payload in self._mesh.allgather(payload):
            peer_streams, peer_thr = unpack_streams(peer_payload)
            dense = ex.decode(peer_streams, peer_thr)
            total = dense if total is None else jax.tree_util.tree_map(
                lambda a, b: a + b, total, dense)
        return total

    def _exchange_dense(self, host_grads: PyTree) -> PyTree:
        if self._mesh is None:
            leaves = jax.tree_util.tree_leaves(host_grads)
            self._last_wire_bytes = sum(4 * l.size for l in leaves)
            return host_grads
        from deeplearning4j_tpu.parallel.compression import allreduce_dense
        return allreduce_dense(self._mesh, host_grads)

    # ---- elastic joiner admission passthroughs (coordinator only) ----
    def has_pending_joiner(self) -> bool:
        return self._mesh is not None and \
            getattr(self._mesh, "has_pending_joiner", lambda: False)()

    def wait_for_joiner(self, timeout: float) -> bool:
        if self._mesh is None or not hasattr(self._mesh,
                                             "wait_for_joiner"):
            return False
        return self._mesh.wait_for_joiner(timeout)

    def admit_joiners(self, resume_step: int):
        """Admit parked replacement workers (bumps the generation; the
        peers raise GangReformed).  Returns the reform info dict or None.
        The caller (ElasticTrainer) rebuilds codecs and restores the
        checkpoint inline on the coordinator."""
        if self._mesh is None or not hasattr(self._mesh, "admit_joiners"):
            return None
        return self._mesh.admit_joiners(resume_step)

    def request_evict(self, rank: int, resume_step=None,
                      cause: str = "shrink"):
        """Coordinated shrink: evict `rank` at an agreed resume step (the
        pod arbiter's scale-to-serving path).  Returns the reform info
        dict or None when not elastic."""
        if self._mesh is None or not hasattr(self._mesh, "request_evict"):
            return None
        return self._mesh.request_evict(rank, resume_step=resume_step,
                                        cause=cause)

    def stats(self) -> dict:
        """Last-exchange wire bytes and compression ratio, the running byte
        totals, and the gang's generation when elastic."""
        mesh = self._mesh
        out = {
            "rank": self.rank,
            "world": self.world,
            "compressed": self.config.compressed,
            "exchanges": self.exchanges,
            "last_wire_bytes": self._last_wire_bytes,
            "last_compression_ratio": self._last_ratio,
            "bytes_sent_total": mesh.bytes_sent if mesh else 0,
            "bytes_received_total": mesh.bytes_received if mesh else 0,
        }
        if self.config.elastic and mesh is not None:
            out["generation"] = mesh.generation
            out["reformations"] = mesh.reformations
            out["stale_frames"] = mesh.stale_frames
        return out

    def close(self) -> None:
        if self._mesh is not None:
            self._mesh.close()
            self._mesh = None
        self._ready = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
