"""Multi-host training runtime — the Spark/Aeron scale-out replacement.

Reference: `deeplearning4j-scaleout/spark/dl4j-spark*` (TrainingMaster,
SharedTrainingMaster) + the Aeron mesh under `nd4j-parameter-server-parent/`
(SURVEY.md §2.4, §3.4): a JVM cluster forms a UDP mesh, workers push
threshold-compressed gradients, a master coordinates epochs.

TPU-native inversion: the *control plane* is `jax.distributed` (one
coordinator, N processes) and the *data plane* is XLA collectives over
ICI/DCN inside the one jitted SPMD step — there is no parameter server, no
gossip, no per-batch host hop.  What remains host-side is exactly what the
reference kept host-side: process bootstrap, global-mesh formation, and the
optional compressed-gradient DCN path (`parallel.transport` +
`parallel.compression`).

`LocalLauncher` is SURVEY §4's "multi-node without a cluster" story
(Aeron-on-loopback / Spark local[*]): N OS processes on localhost, each
with its own XLA CPU client, forming one global device mesh over the
`jax.distributed` coordination service with gloo collectives.
"""
from __future__ import annotations

import os
import random
import socket
import subprocess
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


# Env keys the launcher sets and `initialize()` reads (the moral equivalent
# of Spark's master URL + executor id).
ENV_COORD = "DL4J_TPU_COORDINATOR"
ENV_NPROC = "DL4J_TPU_NUM_PROCESSES"
ENV_PID = "DL4J_TPU_PROCESS_ID"
ENV_CKPT = "DL4J_TPU_CHECKPOINT_DIR"
# TCP port for the hierarchical compressed gradient exchange
# (parallel.hierarchical resolves its config from these; hierarchical
# multi-host mode needs NO jax.distributed — each host runs its own local
# mesh and the gradient mesh is the only coupling)
ENV_GRAD_PORT = "DL4J_TPU_GRADIENT_PORT"


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join the training cluster (reference: SharedTrainingMaster worker
    bootstrap).  Arguments default to the `DL4J_TPU_*` env the launcher
    sets; on real TPU pods, call with no args — `jax.distributed.initialize`
    auto-detects the slice topology from the TPU metadata."""
    import jax
    coordinator_address = coordinator_address or os.environ.get(ENV_COORD)
    if num_processes is None and ENV_NPROC in os.environ:
        num_processes = int(os.environ[ENV_NPROC])
    if process_id is None and ENV_PID in os.environ:
        process_id = int(os.environ[ENV_PID])
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def process_index() -> int:
    import jax
    return jax.process_index()


def process_count() -> int:
    import jax
    return jax.process_count()


def global_mesh(axes: Optional[Dict[str, int]] = None):
    """Mesh over every device of every process (default: pure DP)."""
    import jax
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    return make_mesh(axes, jax.devices())


def shard_host_local_batch(mesh, batch, axis: str = "data",
                           batch_dim: int = 0):
    """Each process contributes its *local* slice of the global batch; the
    result is one global jax.Array sharded over `axis` (the SPMD analog of
    Spark partitioning an RDD of DataSets across executors).  All processes
    must feed equal-sized local batches.  `batch_dim=1` handles stacked
    `[k, batch, ...]` fit_steps blocks (steps axis leads, sharded on the
    batch axis)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    nproc = jax.process_count()

    def place(leaf):
        leaf = np.asarray(leaf)
        spec = P(*([None] * batch_dim + [axis]
                   + [None] * (leaf.ndim - batch_dim - 1)))
        global_shape = (leaf.shape[:batch_dim]
                        + (leaf.shape[batch_dim] * nproc,)
                        + leaf.shape[batch_dim + 1:])
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, spec), leaf, global_shape)
    return jax.tree_util.tree_map(place, batch)


def allgather_params(tree):
    """Gather a (possibly sharded) param tree to replicated host numpy on
    every process — the checkpoint/eval hook (reference: params sync back
    to the Spark driver)."""
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(tree, tiled=False)


# ---------------------------------------------------------------------------
# localhost launcher (SURVEY §4: "multi-node without a cluster")
# ---------------------------------------------------------------------------

def free_port(max_tries: int = 16) -> int:
    """Pick a currently-free localhost port.

    The OS can hand the probed port to another process between the probe
    socket closing and the caller's bind — so verify the port is still
    bindable with a second bind and re-probe when it is not, instead of
    letting the caller's server raise EADDRINUSE."""
    last_err: Optional[OSError] = None
    for _ in range(max_tries):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        try:
            with socket.socket() as v:
                v.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
                v.bind(("127.0.0.1", port))
            return port
        except OSError as e:
            last_err = e
    raise OSError(
        f"free_port: no bindable port after {max_tries} probes"
    ) from last_err


def child_env(coordinator: str, num_processes: int, process_id: int,
              devices_per_process: int = 1,
              platform: str = "cpu") -> Dict[str, str]:
    """Environment for a spawned worker: a CPU process with K virtual
    devices standing in for one host.  A chip belongs to one process at a
    time and the launching parent may hold it, so workers never inherit
    the parent's platform."""
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # prepend (don't clobber) so parent-supplied deps stay importable
    inherited = [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
                 if p]
    env["PYTHONPATH"] = os.pathsep.join([repo_root] + inherited)
    env["JAX_PLATFORMS"] = platform
    if platform == "cpu":
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                            f"{devices_per_process}")
    env[ENV_COORD] = coordinator
    env[ENV_NPROC] = str(num_processes)
    env[ENV_PID] = str(process_id)
    return env


class ElasticLocalRunner:
    """Failure detection + elastic restart (SURVEY §5.3; reference analog:
    Spark task retry around SharedTraining workers).

    Failure DETECTION is the `jax.distributed` coordination service's
    heartbeat: when any rank dies, every surviving rank is killed with a
    "peer task died" fatal within the service timeout — exactly the
    reference Aeron mesh's session-timeout role.  This runner supervises
    on top: it relaunches the whole gang after a failure, and the worker
    script resumes from its latest checkpoint (checkpoint/resume is exact,
    utils.serialization), giving crash-restart elasticity without any
    parameter-server state."""

    def __init__(self, num_processes: int, devices_per_process: int = 1,
                 platform: str = "cpu", max_restarts: int = 2,
                 backoff_base_s: float = 1.0, backoff_cap_s: float = 30.0,
                 jitter_seed: Optional[int] = None):
        self.num_processes = num_processes
        self.devices_per_process = devices_per_process
        self.platform = platform
        self.max_restarts = max_restarts
        self.restarts = 0
        self.backoff_base_s = backoff_base_s
        self.backoff_cap_s = backoff_cap_s
        # decorrelated-jitter state: a seeded PRNG (NOT wall-clock) so
        # tests are deterministic while real fleets still spread out
        self._rng = random.Random(jitter_seed)
        self._prev_backoff: Optional[float] = None
        # (attempt, kind, message-tail) per failure — kind in
        # crash | hang | peer-loss (see _classify_failure)
        self.failure_history: List[tuple] = []

    @staticmethod
    def _classify_failure(message: str) -> str:
        """Failure taxonomy: `corrupt` = a rank failed restoring a
        checkpoint whose bytes don't match their recorded checksum
        (NON-retryable — a relaunch reads the same rotten bytes);
        `hang` = a rank hit the subprocess timeout (no exit);
        `peer-loss` = a rank died because the coordination service
        reported a peer's death (secondary casualty — the real fault is
        elsewhere); `crash` = a rank exited nonzero on its own."""
        low = message.lower()
        if "checksummismatch" in low.replace(" ", "") \
                or "checksumerror" in low:
            return "corrupt"
        if "<rank timed out>" in message:
            return "hang"
        if "peer task" in low or "coordination service" in low \
                or "heartbeat" in low:
            return "peer-loss"
        return "crash"

    def backoff_s(self, attempt: int) -> float:
        """Decorrelated-jitter backoff before restart `attempt`
        (1-based): sleep ~ U(base, 3 * previous-sleep), capped.  Unlike
        plain exponential, simultaneous relaunches on one host draw
        different sleeps and stop thundering-herding the coordinator
        port; the jitter PRNG is seeded (`jitter_seed`), so no
        wall-clock dependence leaks into tests."""
        if attempt <= 1 or self._prev_backoff is None:
            self._prev_backoff = self.backoff_base_s
            return self._prev_backoff
        v = self._rng.uniform(
            self.backoff_base_s,
            max(self._prev_backoff * 3.0, self.backoff_base_s))
        self._prev_backoff = min(v, self.backoff_cap_s)
        return self._prev_backoff

    def run(self, script: str, args: Sequence[str] = (),
            timeout: float = 300.0,
            checkpoint_dir: Optional[str] = None,
            gradient_mesh: bool = False) -> List[str]:
        """Run the gang, relaunching after retryable failures.  With
        `checkpoint_dir=` every (re)launch exports it to the workers as
        `DL4J_TPU_CHECKPOINT_DIR`, so a resilience-aware worker (e.g.
        tests/mh_worker_elastic.py via `train.resilience`) resumes from
        the last committed sharded checkpoint instead of step 0.  With
        `gradient_mesh=True` every (re)launch exports a FRESH
        `DL4J_TPU_GRADIENT_PORT` for the hierarchical compressed
        exchange (a new port per attempt — the dead gang's socket may
        linger in TIME_WAIT).  A `corrupt` failure (checksum-mismatch
        restore) aborts immediately: relaunching cannot fix rotten
        bytes."""
        import time as _time
        extra_env = {} if checkpoint_dir is None \
            else {ENV_CKPT: checkpoint_dir}
        last_error: Optional[RuntimeError] = None
        for attempt in range(self.max_restarts + 1):
            launcher = LocalLauncher(self.num_processes,
                                     self.devices_per_process,
                                     self.platform)
            try:
                return launcher.run(
                    script, args, timeout, extra_env=extra_env,
                    gradient_port=free_port() if gradient_mesh else None)
            except RuntimeError as e:
                last_error = e
                kind = self._classify_failure(str(e))
                self.failure_history.append((attempt, kind,
                                             str(e)[-500:]))
                if kind == "corrupt":
                    raise RuntimeError(
                        "checkpoint restore failed with a checksum "
                        "mismatch — non-retryable (a relaunch reads the "
                        "same corrupt bytes); restore an older intact "
                        "checkpoint or repair storage") from e
                self.restarts = min(attempt + 1, self.max_restarts)
                if attempt < self.max_restarts:
                    _time.sleep(self.backoff_s(attempt + 1))
        kinds = [k for _, k, _ in self.failure_history]
        raise RuntimeError(
            f"training failed after {self.max_restarts} restarts "
            f"(failure kinds: {kinds})") from last_error

    # ------------------------------------------------------------------
    # per-worker elastic supervision (gang survives member loss)
    # ------------------------------------------------------------------
    def run_elastic(self, script: str, args: Sequence[str] = (),
                    timeout: float = 600.0,
                    checkpoint_dir: Optional[str] = None,
                    policy: str = "shrink",
                    heartbeat_s: float = 0.25,
                    failure_deadline_s: float = 2.0,
                    max_replacements: int = 2,
                    relaunch: bool = True,
                    extra_env: Optional[Dict[str, str]] = None
                    ) -> Dict[str, Tuple[int, str]]:
        """Supervise an ELASTIC gang: per-worker monitoring instead of
        whole-gang relaunch.

        Workers run `HierarchicalGradientSharing(elastic=True)` +
        `ElasticTrainer`; when a non-coordinator worker dies the gang
        itself re-forms and keeps training (shrink-and-continue), and —
        with `relaunch=True` — this supervisor launches a REPLACEMENT
        worker after a jittered backoff with ``DL4J_TPU_JOIN=1`` on the
        SAME gradient port and checkpoint dir: it joins the coordinator's
        listening socket, parks until admitted (immediately under the
        ``"block"`` policy, at the next epoch boundary under
        ``"shrink"``), and enters at a fresh generation.  Coordinator
        (rank 0) death is gang-fatal — the star has no other hub — and
        raises with rank 0's output tail; use :meth:`run` around an
        elastic worker script when whole-gang restart is the desired
        recovery for that.

        Returns ``{label: (returncode, output)}`` per worker, labels
        ``"r<rank>"`` for the initial gang and ``"r<rank>+j<n>"`` for
        replacements.  The run succeeds when rank 0 exits 0 — peer
        deaths are recorded in `failure_history`, not fatal."""
        if policy not in ("shrink", "block"):
            raise ValueError(
                f"policy must be 'shrink' or 'block', got {policy!r}")
        port = free_port()
        base_env = {
            ENV_GRAD_PORT: str(port),
            "DL4J_TPU_HEARTBEAT_S": str(heartbeat_s),
            "DL4J_TPU_FAILURE_DEADLINE_S": str(failure_deadline_s),
            "DL4J_TPU_ELASTIC_POLICY": policy,
        }
        if checkpoint_dir is not None:
            base_env[ENV_CKPT] = checkpoint_dir
        if extra_env:
            base_env.update(extra_env)
        coordinator = f"127.0.0.1:{free_port()}"   # unused by elastic
        logdir = tempfile.mkdtemp(prefix="elastic-gang-")

        def spawn(rank: int, label: str, join: bool):
            env = child_env(coordinator, self.num_processes, rank,
                            self.devices_per_process, self.platform)
            env.update(base_env)
            if join:
                env["DL4J_TPU_JOIN"] = "1"
            path = os.path.join(logdir, f"{label}.log")
            f = open(path, "w")
            p = subprocess.Popen(
                [sys.executable, "-u", script, *map(str, args)],
                stdout=f, stderr=subprocess.STDOUT, text=True, env=env)
            return (p, f, path)

        alive: Dict[str, tuple] = {}
        for rank in range(self.num_processes):
            alive[f"r{rank}"] = spawn(rank, f"r{rank}", join=False)
        results: Dict[str, Tuple[int, str]] = {}
        replacements = 0
        rank0_rc: Optional[int] = None
        deadline = time.monotonic() + timeout
        grace_deadline: Optional[float] = None

        def reap(label: str, p, f, path) -> Tuple[int, str]:
            f.close()
            with open(path, "r") as rf:
                out = rf.read()
            results[label] = (p.returncode, out)
            return results[label]

        try:
            while alive:
                now = time.monotonic()
                if now > deadline or (grace_deadline is not None
                                      and now > grace_deadline):
                    for label, (p, f, path) in alive.items():
                        p.kill()
                        p.wait()
                        rc, out = reap(label, p, f, path)
                        results[label] = (rc, out + "\n<rank timed out>")
                    alive.clear()
                    if now > deadline:
                        raise RuntimeError(
                            f"elastic gang timed out after {timeout:.0f}s"
                            f" (still running: {sorted(results)})")
                    break
                exited = [(label, t) for label, t in alive.items()
                          if t[0].poll() is not None]
                for label, (p, f, path) in exited:
                    del alive[label]
                    rc, out = reap(label, p, f, path)
                    if label == "r0":
                        rank0_rc = rc
                        if rc != 0:
                            raise RuntimeError(
                                f"elastic gang coordinator (rank 0) "
                                f"failed (rc={rc}):\n{out[-4000:]}")
                        # coordinator done: peers must wind down on
                        # their own within the failure deadline
                        grace_deadline = time.monotonic() + max(
                            failure_deadline_s * 3, 5.0)
                    elif rc != 0:
                        kind = self._classify_failure(out)
                        self.failure_history.append(
                            (replacements, kind, out[-500:]))
                        if relaunch and rank0_rc is None \
                                and replacements < max_replacements:
                            replacements += 1
                            time.sleep(self.backoff_s(replacements))
                            jl = f"{label.split('+')[0]}+j{replacements}"
                            alive[jl] = spawn(
                                int(label.split('+')[0][1:]), jl,
                                join=True)
                time.sleep(0.05)
        finally:
            for label, (p, f, path) in alive.items():
                p.kill()
                p.wait()
                reap(label, p, f, path)
        self.restarts = replacements
        return results


class LocalLauncher:
    """Spawn an SPMD worker script across N localhost processes and wait.

    Each process sees `devices_per_process` XLA CPU devices; together they
    form an `N*devices_per_process`-device global mesh.  stdout/stderr are
    captured per rank; a nonzero exit raises with the failing rank's tail.
    """

    def __init__(self, num_processes: int, devices_per_process: int = 1,
                 platform: str = "cpu"):
        self.num_processes = num_processes
        self.devices_per_process = devices_per_process
        self.platform = platform

    def run(self, script: str, args: Sequence[str] = (),
            timeout: float = 300.0,
            extra_env: Optional[Dict[str, str]] = None,
            gradient_port: Optional[int] = None) -> List[str]:
        """`gradient_port=` exports `DL4J_TPU_GRADIENT_PORT` so workers
        using hierarchical gradient sharing form their TCP gradient mesh
        on a known port (pass `free_port()` for a fresh one per launch —
        an elastic relaunch must NOT reuse a port still in TIME_WAIT)."""
        coordinator = f"127.0.0.1:{free_port()}"
        procs = []
        for rank in range(self.num_processes):
            env = child_env(coordinator, self.num_processes, rank,
                            self.devices_per_process, self.platform)
            if gradient_port is not None:
                env[ENV_GRAD_PORT] = str(gradient_port)
            if extra_env:
                env.update(extra_env)
            procs.append(subprocess.Popen(
                [sys.executable, "-u", script, *map(str, args)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, env=env))
        outs: List[str] = []
        failed = None
        for rank, p in enumerate(procs):
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
                out += "\n<rank timed out>"
                failed = failed or (rank, out, -9)
            outs.append(out)
            if p.returncode not in (0, None) and failed is None:
                failed = (rank, out, p.returncode)
        if failed is not None:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            rank, out, rc = failed
            raise RuntimeError(
                f"multihost rank {rank} failed (rc={rc}):\n{out[-4000:]}")
        return outs


# ---------------------------------------------------------------------------
# Multi-host inference (reference: ParallelInference under
# SparkDl4jMultiLayer — replica inference across executors; here one SPMD
# forward over the global mesh, each process feeding/receiving its local
# slice)
# ---------------------------------------------------------------------------

class MultiHostParallelInference:
    """Sharded inference over a multi-process global mesh: every process
    submits a host-local request batch, the forward runs once as SPMD over
    the global `data` axis, and each process receives exactly its own
    rows back (no cross-process result shipping beyond XLA's own
    collectives)."""

    def __init__(self, model, mesh=None, data_axis: str = "data"):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.model = model
        self.mesh = mesh if mesh is not None else global_mesh()
        self.data_axis = data_axis
        repl = NamedSharding(self.mesh, P())

        def replicate(leaf):
            import numpy as _np
            leaf = _np.asarray(leaf)
            return jax.make_array_from_process_local_data(repl, leaf,
                                                          leaf.shape)
        model.params_ = jax.tree_util.tree_map(replicate, model.params_)
        model.state_ = jax.tree_util.tree_map(replicate, model.state_)

    def output(self, x_local):
        """x_local: this process's [b_local, ...] request batch (equal
        sizes across processes).  Returns this process's [b_local, ...]
        predictions as numpy."""
        xg = shard_host_local_batch(self.mesh, np.asarray(x_local),
                                    self.data_axis)
        with self.mesh:
            out = self.model.output(xg)
        if isinstance(out, (list, tuple)):   # ComputationGraph
            out = out[0]
        # one shard per distinct batch slice: meshes with a non-data axis
        # replicate each slice across that axis's devices — keep one copy
        by_start = {}
        for s in out.addressable_shards:
            by_start.setdefault(s.index[0].start or 0, s)
        shards = [by_start[k] for k in sorted(by_start)]
        return np.concatenate([np.asarray(s.data) for s in shards], axis=0)
