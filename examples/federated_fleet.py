"""Cross-host fleet federation — host failure domains, generation-fenced
membership, warm host-loss re-placement (docs/robustness.md).

This script is both supervisor and worker.  Run it plain and it starts a
`FederationRouter` front door plus 3 worker processes, each a full
`ModelFleet` (model "m", deployed warm against one SHARED persistent AOT
cache) wrapped by a `HostAgent` that joins the router over loopback TCP.
A `HostChaos(mode="kill", os_kill=True)` hook hard-kills the host that
rendezvous-affinity routes "m" to, two dispatches into the client flood.
The router detects the EOF in milliseconds, evicts the host under a
bumped membership generation (stale in-flight replies are fenced, never
returned), fails the in-flight request over to a survivor with its
remaining deadline budget, and warm-re-places the dead host's model from
its replicated topology snapshot — zero fresh compiles.  The supervisor
then relaunches the killed host under the same host_id: it is re-admitted
at a bumped generation and offered its own snapshot back, restoring
compile-free.  No accepted request is lost at any point.

    python examples/federated_fleet.py
"""
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np                                         # noqa: E402

N_IN, N_OUT, HOSTS = 8, 3, ("h1", "h2", "h3")
KILL_AFTER = 2                    # victim dies 2 dispatches into the flood


def _net():
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.train.updaters import Sgd
    # every host builds the SAME seeded net, so a survivor re-places a
    # dead host's model straight from the shared AOT cache
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Sgd(1e-1))
            .list([DenseLayer(n_out=16, activation="relu"),
                   OutputLayer(n_out=N_OUT, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(N_IN)).build())
    return MultiLayerNetwork(conf).init()


def worker(host_id: str, port: int, work_dir: str, kill_after: int):
    from deeplearning4j_tpu.serving import (FederationPolicy, HostAgent,
                                            LatencySLO, ModelFleet)
    from deeplearning4j_tpu.utils.chaos import HostChaos

    host_dir = os.path.join(work_dir, host_id)
    os.makedirs(host_dir, exist_ok=True)
    fleet = ModelFleet(max_resident=2, n_slices=2, max_batch=8,
                       batch_timeout_ms=1.0,
                       cache_dir=os.path.join(work_dir, "exec-cache"),
                       snapshot_path=os.path.join(host_dir, "snapshot.json"),
                       snapshot_interval_s=0.2, host_id=host_id)
    fleet.deploy("m", _net(),
                 slo=LatencySLO(target_p99_ms=2000.0, priority=5), warm=True)
    policy = FederationPolicy(heartbeat_interval_s=0.1,
                              failure_deadline_s=0.8,
                              straggler_deadline_s=5.0)
    agent = HostAgent(host_id, fleet, ("127.0.0.1", port), policy=policy,
                      replicas_dir=os.path.join(host_dir, "replicas"))
    agent.start(timeout=30.0)
    if kill_after >= 0:
        # marker file keeps the relaunched replacement from re-firing
        chaos = HostChaos(mode="kill", at_dispatch=kill_after, os_kill=True,
                          marker=os.path.join(work_dir, f"{host_id}.killed"))
        if chaos.armed():
            chaos.arm(agent)
    fleet.save_snapshot()            # replicate topology to the router
    if agent.restored:
        print(f"{host_id}: restored from replicated snapshot "
              f"(fresh_compiles={agent.restored['fresh_compiles']})",
              flush=True)
    with open(os.path.join(work_dir, f"{host_id}.ready"), "w") as f:
        json.dump({"generation": agent.generation}, f)
    print(f"{host_id}: joined at generation {agent.generation}", flush=True)
    stop = os.path.join(work_dir, "stop")
    while not os.path.exists(stop):
        time.sleep(0.05)
    agent.close()
    fleet.shutdown()
    print(f"{host_id}: done at generation {agent.generation}", flush=True)


def _spawn(host_id, port, work_dir, kill_after=-1):
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), host_id, str(port),
         work_dir, str(kill_after)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _wait_file(path, timeout, what):
    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out waiting for {what}")
        time.sleep(0.05)


def supervisor():
    from deeplearning4j_tpu.serving import FederationRouter
    from deeplearning4j_tpu.serving.federation import _rendezvous
    from deeplearning4j_tpu.serving.slo import FederationPolicy

    rng = np.random.RandomState(0)
    with tempfile.TemporaryDirectory() as td:
        policy = FederationPolicy(heartbeat_interval_s=0.1,
                                  failure_deadline_s=0.8,
                                  straggler_deadline_s=5.0)
        router = FederationRouter(
            policy, replicas_dir=os.path.join(td, "router-replicas"))
        port = router.start(port=0)
        victim = _rendezvous(list(HOSTS), "m")   # affinity host for "m"
        print(f"--- launching 3-host federation (router :{port}; "
              f"{victim} carries 'm' and dies {KILL_AFTER} dispatches "
              f"into the flood) ---")
        procs = {h: _spawn(h, port, td, KILL_AFTER if h == victim else -1)
                 for h in HOSTS}
        for h in HOSTS:
            _wait_file(os.path.join(td, f"{h}.ready"), 90.0, f"{h} join")
        while set(router.federation_stats()["replicas"]) < set(HOSTS):
            time.sleep(0.05)         # snapshots replicated to the router
        print(f"federation formed: hosts={router.hosts()} "
              f"generation={router.generation}")

        served = 0
        deadline = time.monotonic() + 60.0
        while not any(e["event"] == "replaced" and e["host"] == victim
                      for e in router.events):
            if time.monotonic() > deadline:
                raise TimeoutError("host never re-placed")
            x = rng.randn(2, N_IN).astype(np.float32)
            y = router.output("m", x, deadline_ms=8000.0)
            assert y.shape == (2, N_OUT)
            served += 1
        evict = next(e for e in router.events if e["event"] == "evict")
        repl = next(e for e in router.events if e["event"] == "replaced")
        print(f"served {served}/{served} requests across the host kill "
              f"(zero lost)")
        print(f"evicted {evict['host']} cause={evict['cause']} "
              f"detected in {evict['detection_ms']:.1f} ms "
              f"-> generation {evict['generation']}")
        print(f"re-placed {repl['models']} on {repl['on']} in "
              f"{repl['replace_ms']:.1f} ms (warm={repl['warm']}, "
              f"fresh_compiles={repl['fresh_compiles']})")
        assert repl["fresh_compiles"] == 0 and repl["warm"]

        gen_before = router.generation
        print(f"--- relaunching {victim} under the same host_id ---")
        relaunched = _spawn(victim, port, td)    # no chaos this time
        while victim not in router.hosts():
            time.sleep(0.05)
        y = router.output("m", rng.randn(2, N_IN).astype(np.float32),
                          deadline_ms=8000.0)
        assert y.shape == (2, N_OUT)
        print(f"{victim} re-admitted: generation {gen_before} -> "
              f"{router.generation}, hosts={router.hosts()}")

        open(os.path.join(td, "stop"), "w").close()
        outputs = {victim: procs.pop(victim).communicate()[0]}
        outputs[f"{victim}'"] = relaunched.communicate()[0]
        outputs.update({h: p.communicate()[0] for h, p in procs.items()})
        for label in sorted(outputs):
            tail = [ln for ln in outputs[label].strip().splitlines()
                    if ":" in ln][-2:]
            for ln in tail:
                print(f"    [{label}] {ln}")
        router.shutdown()
        print("\n=> federation survived a hard host kill with zero lost "
              "requests, a compile-free warm re-placement, and a "
              "generation-fenced re-admission")


if __name__ == "__main__":
    if len(sys.argv) >= 4:
        worker(sys.argv[1], int(sys.argv[2]), sys.argv[3],
               int(sys.argv[4]) if len(sys.argv) > 4 else -1)
    else:
        supervisor()
