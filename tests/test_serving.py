"""Serving runtime contract (ISSUE 1 acceptance): registry versioning,
bucketed AOT compile cache, continuous batching under real thread
concurrency, deadlines/admission control with typed errors, graceful
shutdown, metrics, and the deprecated DynamicBatchingInference shim."""
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.serving import (BucketedCompileCache,
                                        ContinuousBatcher,
                                        DeadlineExceededError, ModelRegistry,
                                        ModelServer, RejectedError,
                                        bucket_for, bucket_sizes)
from deeplearning4j_tpu.train.updaters import Sgd


def _net(seed=0, n_in=8, n_out=3):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(1e-1))
            .list([DenseLayer(n_out=16, activation="relu"),
                   OutputLayer(n_out=n_out, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


# ---------------------------------------------------------------------------
# buckets
# ---------------------------------------------------------------------------

def test_bucket_ladder():
    assert bucket_sizes(32) == [1, 2, 4, 8, 16, 32]
    assert bucket_sizes(20) == [1, 2, 4, 8, 16, 32]   # top covers max_batch
    assert bucket_sizes(32, min_bucket=8) == [8, 16, 32]
    assert bucket_for(1, 32) == 1
    assert bucket_for(3, 32) == 4
    assert bucket_for(17, 32) == 32
    assert bucket_for(5, 32, min_bucket=8) == 8
    with pytest.raises(ValueError):
        bucket_for(0, 32)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_versioning_and_sources():
    reg = ModelRegistry()
    a = reg.register("m", _net(seed=1))
    b = reg.register("m", _net(seed=2))
    assert (a.version, b.version) == (1, 2)
    assert reg.get("m").version == 2            # newest wins
    assert reg.get("m", 1) is a
    assert reg.versions("m") == [1, 2]
    assert a.input_shape == (8,)                # inferred from InputType
    with pytest.raises(ValueError, match="already registered"):
        reg.register("m", _net(), version=2)
    with pytest.raises(KeyError, match="no model"):
        reg.get("missing")
    with pytest.raises(KeyError, match="versions"):
        reg.get("m", 9)
    z = reg.register_zoo("lenet", "LeNet")
    assert z.source == "zoo" and z.input_shape == (28, 28, 1)
    with pytest.raises(KeyError, match="unknown zoo model"):
        reg.register_zoo("x", "NoSuchModel")
    reg.unregister("m", 1)
    assert reg.versions("m") == [2]


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------

def test_compile_cache_pads_transparently_and_counts():
    net = _net(seed=3)
    cache = BucketedCompileCache(max_batch=16)
    x = np.random.RandomState(0).randn(5, 8).astype(np.float32)
    got = cache.run("m:v1", net, x)             # 5 rows -> bucket 8
    np.testing.assert_array_equal(got, np.asarray(net.output(x)))
    assert cache.counters.misses.value == 1
    got2 = cache.run("m:v1", net, x[:7])        # same bucket -> hit
    np.testing.assert_array_equal(got2, np.asarray(net.output(x[:7])))
    assert cache.counters.misses.value == 1
    assert cache.counters.hits.value == 1
    cache.run("m:v1", net, x[:1])               # bucket 1 -> new compile
    assert cache.counters.misses.value == 2
    with pytest.raises(ValueError, match="max_batch"):
        cache.run("m:v1", net, np.zeros((17, 8), np.float32))
    cache.invalidate("m:v1")
    cache.run("m:v1", net, x)
    assert cache.counters.misses.value == 3


def test_compile_cache_warmup_covers_every_bucket():
    net = _net(seed=4)
    cache = BucketedCompileCache(max_batch=8)
    warmed = cache.warmup("m:v1", net, (8,))
    assert warmed == [1, 2, 4, 8] == cache.buckets
    assert cache.counters.misses.value == cache.num_buckets
    # traffic at any size <= max_batch never compiles again
    for n in range(1, 9):
        cache.run("m:v1", net, np.zeros((n, 8), np.float32))
    assert cache.counters.misses.value == cache.num_buckets


def test_compile_cache_sharded_mesh_matches_single_device():
    from deeplearning4j_tpu.parallel import make_mesh
    net = _net(seed=5)
    ref = np.asarray(net.output(
        np.random.RandomState(1).randn(11, 8).astype(np.float32)))
    mesh = make_mesh()
    cache = BucketedCompileCache(max_batch=32, mesh=mesh)
    assert cache.min_bucket == mesh.shape["data"]   # buckets divide the mesh
    x = np.random.RandomState(1).randn(11, 8).astype(np.float32)
    got = cache.run("m:v1", net, x)                  # 11 -> bucket 16, SPMD
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# batcher semantics (driven directly, no model)
# ---------------------------------------------------------------------------

def _echo_dispatch(group, xs):
    return [x * 2.0 for x in xs]


def test_batcher_queue_full_sheds_load():
    gate = threading.Event()

    def slow(group, xs):
        gate.wait(timeout=30)
        return xs

    b = ContinuousBatcher(slow, max_batch=1, batch_timeout_ms=0.0,
                          max_queue=2)
    futs = [b.submit(np.zeros((1, 4)))]          # dispatched, blocks worker
    time.sleep(0.1)
    futs += [b.submit(np.zeros((1, 4))) for _ in range(2)]   # fills queue
    with pytest.raises(RejectedError, match="queue full"):
        b.submit(np.zeros((1, 4)))
    assert b.metrics.rejected.value == 1
    gate.set()
    for f in futs:
        f.result(timeout=30)
    b.shutdown()
    with pytest.raises(RejectedError, match="shut down"):
        b.submit(np.zeros((1, 4)))


def test_batcher_deadline_expires_as_timeout_error():
    gate = threading.Event()

    def slow(group, xs):
        gate.wait(timeout=30)
        return xs

    b = ContinuousBatcher(slow, max_batch=1, batch_timeout_ms=0.0,
                          max_queue=16)
    first = b.submit(np.zeros((1, 4)))           # occupies the worker
    time.sleep(0.05)
    doomed = b.submit(np.zeros((1, 4)), deadline_ms=10.0)
    ok = b.submit(np.zeros((1, 4)))
    time.sleep(0.1)                              # deadline passes in queue
    gate.set()
    with pytest.raises(DeadlineExceededError):
        doomed.result(timeout=30)
    assert isinstance(doomed.exception(), TimeoutError)
    first.result(timeout=30)
    ok.result(timeout=30)
    assert b.metrics.expired.value == 1
    b.shutdown()


def test_batcher_priority_orders_dispatch():
    order = []
    gate = threading.Event()

    def record(group, xs):
        gate.wait(timeout=30)
        order.append(group[0])
        return xs

    b = ContinuousBatcher(record, max_batch=1, batch_timeout_ms=0.0,
                          max_queue=16)
    b.submit(np.zeros((1, 2)), group=("warm",))  # keeps worker busy
    time.sleep(0.05)
    lo = b.submit(np.zeros((1, 2)), group=("lo",), priority=0)
    hi = b.submit(np.zeros((1, 2)), group=("hi",), priority=5)
    gate.set()
    hi.result(timeout=30)
    lo.result(timeout=30)
    b.shutdown()
    assert order[1] == "hi"                      # after warm, hi beats lo


def test_batcher_groups_heterogeneous_shapes():
    seen = []

    def spy(group, xs):
        seen.append({x.shape[1:] for x in xs})
        return [x.sum(axis=tuple(range(1, x.ndim))) for x in xs]

    b = ContinuousBatcher(spy, max_batch=64, batch_timeout_ms=50.0,
                          max_queue=64)
    sub = lambda x: b.submit(x, group=("m", x.shape[1:]))  # noqa: E731
    futs = [sub(np.ones((2, 3))), sub(np.ones((1, 5))),
            sub(np.ones((3, 3))), sub(np.ones((2, 5)))]
    for f in futs:
        f.result(timeout=30)
    b.shutdown()
    for shapes in seen:
        assert len(shapes) == 1                  # never mixed in a dispatch


def test_batcher_dispatch_error_propagates_to_all_waiters():
    def boom(group, xs):
        raise RuntimeError("kaboom")

    b = ContinuousBatcher(boom, max_batch=8, batch_timeout_ms=20.0)
    futs = [b.submit(np.zeros((1, 2))) for _ in range(3)]
    for f in futs:
        with pytest.raises(RuntimeError, match="kaboom"):
            f.result(timeout=30)
    assert b.metrics.failed.value == 3
    b.shutdown()


def test_batcher_shutdown_drains_and_is_idempotent():
    b = ContinuousBatcher(_echo_dispatch, max_batch=4,
                          batch_timeout_ms=200.0, max_queue=64)
    futs = [b.submit(np.full((1, 2), i, np.float32)) for i in range(6)]
    b.shutdown()                                 # drain=True default
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(f.result(timeout=1),
                                      np.full((1, 2), 2.0 * i))
    b.shutdown()                                 # second call: no-op
    b.shutdown(drain=False)


# ---------------------------------------------------------------------------
# ModelServer end to end
# ---------------------------------------------------------------------------

def _padded_rows(x, bucket):
    """`x` zero-padded to `bucket` rows, as the compile cache pads it."""
    pad = np.zeros((bucket - x.shape[0],) + x.shape[1:], x.dtype)
    return np.concatenate([x, pad], axis=0)


def test_model_server_acceptance_64_concurrent_mixed_shapes():
    """ISSUE acceptance: 64 concurrent mixed-size requests all return
    correct results with <= num_buckets compilations (compile-cache
    counters) and mean batch occupancy > 1 request/dispatch; padding is
    bitwise free at the bucket's own compiled shape."""
    net = _net(seed=7)
    srv = ModelServer(max_batch=32, batch_timeout_ms=100.0, max_queue=256)
    srv.deploy("m", model=net)                   # cold cache: compiles are
    rng = np.random.RandomState(0)               # counted under traffic
    reqs = [rng.randn(1 + i % 4, 8).astype(np.float32) for i in range(64)]
    want = [np.asarray(net.output(r)) for r in reqs]

    with ThreadPoolExecutor(max_workers=16) as ex:
        futs = [ex.submit(srv.output, "m", r, timeout=120) for r in reqs]
        got = [f.result(timeout=120) for f in futs]
    stats = srv.stats()

    # Which requests merged into which bucket is up to the threads, and
    # `want` ran each request at its own 1-4 row shape.  Another batch shape
    # is another XLA program, and on the CPU it may pick another reduction:
    # the two differ by one unit in the last place (1.5e-8 and 6.0e-8
    # observed), so across shapes the comparison is to rounding.
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-7)
    # Bitwise where it is true: a request dispatched alone is padded to its
    # bucket, and the same zero-padded rows run directly at that compiled
    # batch shape give the same bits.
    for r in reqs[:4]:
        bucket = srv.cache.bucket_for(r.shape[0])
        alone = srv.output("m", r, timeout=120)
        direct = np.asarray(net.output(_padded_rows(r, bucket)))
        np.testing.assert_array_equal(alone, direct[:r.shape[0]])
    srv.shutdown()

    assert stats["compile_cache"]["misses"] <= srv.cache.num_buckets, stats
    assert stats["batch_occupancy"] > 1.0, stats
    assert stats["completed"] == 64
    assert stats["rejected"] == 0 and stats["expired"] == 0


def test_model_server_mixed_trailing_dims_and_versions():
    """Different input widths (true heterogeneous shapes) and model
    versions serve concurrently — each group hits its own executable."""
    a, b = _net(seed=1, n_in=4), _net(seed=2, n_in=6)
    srv = ModelServer(max_batch=16, batch_timeout_ms=20.0)
    srv.deploy("m", model=a)                     # v1: 4-wide
    srv.deploy("m", model=b)                     # v2: 6-wide (newest)
    rng = np.random.RandomState(0)
    x4 = rng.randn(3, 4).astype(np.float32)
    x6 = rng.randn(2, 6).astype(np.float32)
    with ThreadPoolExecutor(max_workers=4) as ex:
        f1 = ex.submit(srv.output, "m", x4, 1)   # pinned to v1
        f2 = ex.submit(srv.output, "m", x6)      # newest
        got4, got6 = f1.result(timeout=60), f2.result(timeout=60)
    # x6's 2 rows are a bucket: the reply ran at the shape `b.output(x6)`
    # compiles, bit for bit.  x4's 3 rows ran padded to the 4-row bucket:
    # bitwise against the same padded rows run directly at 4 rows, and to
    # rounding against the 3-row program (another batch shape is another
    # XLA program; the observed difference is one unit in the last place)
    np.testing.assert_array_equal(got6, np.asarray(b.output(x6)))
    np.testing.assert_array_equal(
        got4, np.asarray(a.output(_padded_rows(x4, 4)))[:3])
    np.testing.assert_allclose(got4, np.asarray(a.output(x4)),
                               rtol=1e-6, atol=1e-7)
    srv.shutdown()


def test_model_server_typed_errors_fail_fast():
    srv = ModelServer(max_batch=8, batch_timeout_ms=5.0, max_queue=4)
    srv.deploy("m", model=_net())
    with pytest.raises(KeyError):
        srv.submit("nope", np.zeros((1, 8), np.float32))
    with pytest.raises(ValueError, match=">= 1 rows"):
        srv.submit("m", np.zeros((0, 8), np.float32))
    with pytest.raises(ValueError, match="max_batch"):
        srv.submit("m", np.zeros((9, 8), np.float32))
    fut = srv.submit("m", np.zeros((1, 8), np.float32), deadline_ms=0.0)
    with pytest.raises(TimeoutError):
        fut.result(timeout=30)
    srv.shutdown()
    with pytest.raises(RejectedError):
        srv.submit("m", np.zeros((1, 8), np.float32))
    srv.shutdown()                               # idempotent


def test_model_server_warmup_precompiles_all_buckets():
    srv = ModelServer(max_batch=16, batch_timeout_ms=1.0)
    srv.deploy("m", model=_net(), warmup=True)
    assert srv.metrics.cache.misses.value == srv.cache.num_buckets
    srv.output("m", np.zeros((5, 8), np.float32), timeout=60)
    assert srv.metrics.cache.misses.value == srv.cache.num_buckets  # no new
    assert srv.metrics.cache.hits.value >= 1
    srv.shutdown()


def test_model_server_stats_and_ui_endpoint():
    import json
    import urllib.request
    from deeplearning4j_tpu.ui.server import UIServer

    srv = ModelServer(max_batch=8, batch_timeout_ms=1.0)
    srv.deploy("m", model=_net(), warmup=True)
    srv.output("m", np.zeros((2, 8), np.float32), timeout=60)
    s = srv.stats()
    assert s["completed"] == 1 and s["models"] == {"m": [1]}
    assert {"p50", "p95", "p99"} <= set(s["latency_ms"])

    ui = UIServer()                              # fresh instance, not the
    ui.attach_serving(srv)                       # process-global singleton
    port = ui.start(0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/serving", timeout=10) as r:
            scraped = json.loads(r.read())
        assert scraped[0]["completed"] == 1
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/", timeout=10) as r:
            page = r.read().decode()
        assert "Serving" in page and "batch occupancy" in page
    finally:
        ui.stop()
        srv.shutdown()


# ---------------------------------------------------------------------------
# satellites: ParallelInference fixes + deprecated shim
# ---------------------------------------------------------------------------

def test_parallel_inference_heterogeneous_shapes_raise():
    from deeplearning4j_tpu.parallel import ParallelInference
    pi = ParallelInference(_net(n_in=8))
    with pytest.raises(ValueError, match="heterogeneous request shapes"):
        pi.output([np.zeros((2, 8), np.float32),
                   np.zeros((2, 5), np.float32)])
    assert pi.output([]) == []


def test_parallel_inference_zero_row_input():
    from deeplearning4j_tpu.parallel import ParallelInference
    pi = ParallelInference(_net(n_in=8))
    out = pi.output(np.zeros((0, 8), np.float32))
    assert out.shape == (0, 3)


def test_dynamic_batching_shim_deprecated_idempotent_mixed_shapes():
    from deeplearning4j_tpu.parallel import (DynamicBatchingInference,
                                             ParallelInference)
    net = _net(seed=9)
    pi = ParallelInference(net)
    with pytest.warns(DeprecationWarning, match="serving.ModelServer"):
        dyn = DynamicBatchingInference(pi, max_batch=16, timeout_ms=50.0)
    # mixed trailing dims used to crash the concatenate; now they group
    seq = np.random.RandomState(0).randn(2, 8).astype(np.float32)
    seq2 = np.random.RandomState(1).randn(3, 8).astype(np.float32)
    f1, f2 = dyn.submit(seq), dyn.submit(seq2)
    np.testing.assert_allclose(f1.result(timeout=60),
                               np.asarray(net.output(seq)),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(f2.result(timeout=60),
                               np.asarray(net.output(seq2)),
                               rtol=1e-6, atol=1e-7)
    dyn.shutdown()
    dyn.shutdown()                               # idempotent now
    with pytest.raises(RuntimeError):
        dyn.submit(seq)


# ---------------------------------------------------------------------------
# soak (excluded from tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_model_server_soak_sustained_mixed_traffic():
    """Sustained closed-loop traffic: no leaks of queue depth, every
    request accounted, occupancy stays > 1 and compiles stay bounded."""
    srv = ModelServer(max_batch=32, batch_timeout_ms=2.0, max_queue=1024)
    srv.deploy("m", model=_net(seed=11), warmup=True)

    def client(i):
        rs = np.random.RandomState(i)
        n_done = 0
        end = time.monotonic() + 3.0
        while time.monotonic() < end:
            x = rs.rand(1 + n_done % 4, 8).astype(np.float32)
            y = srv.output("m", x, deadline_ms=5000.0, timeout=60)
            assert y.shape == (x.shape[0], 3)
            n_done += 1
        return n_done

    with ThreadPoolExecutor(max_workers=12) as ex:
        done = sum(ex.map(client, range(12)))
    s = srv.stats()
    srv.shutdown()
    assert done > 50
    assert s["completed"] == s["submitted"] == done
    assert s["expired"] == 0 and s["failed"] == 0
    assert s["queue_depth"] == 0
    assert s["batch_occupancy"] > 1.0
    assert s["compile_cache"]["misses"] == srv.cache.num_buckets
