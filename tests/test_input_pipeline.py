"""Async input pipeline: device prefetch, on-device normalization,
sync-free step loop (data/pipeline.py + nn set_normalizer + listeners).

Covers the pipeline's load-bearing invariants:
- prefetch depth bounds how far the producer runs ahead (backpressure)
- early-break consumers and close() shut the producer thread down
- on-device normalization is BITWISE identical to the host normalizer,
  under jit and inside lax.scan, for every supported kind
- the streaming fused epoch (per-step staged lists, stacked inside the
  compiled dispatch) matches the stacked fit_steps form exactly and the
  per-step path numerically
- the steady-state loop performs no per-iteration blocking host read and
  no per-step H2D uploads (score spy + transfer_guard + counter_uploads)
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.data import (DataSet, DeviceNormalizer,
                                     DevicePrefetchIterator,
                                     ImagePreProcessingScaler,
                                     ListDataSetIterator,
                                     NormalizerMinMaxScaler,
                                     NormalizerStandardize, device_blocks)
from deeplearning4j_tpu.data.iterators import (AsyncDataSetIterator,
                                               DataSetIterator)
from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)


def _batches(n, batch=8, n_in=6, n_out=3, seed=0):
    rng = np.random.RandomState(seed)
    return [DataSet((rng.rand(batch, n_in) * 10.0).astype(np.float32),
                    np.eye(n_out, dtype=np.float32)[
                        rng.randint(0, n_out, batch)])
            for _ in range(n)]


def _mlp(n_in=6, n_out=3, seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .list([DenseLayer(n_out=12, activation="relu"),
                   OutputLayer(n_out=n_out, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


class CountingIterator(DataSetIterator):
    """Counts how many batches the producer has pulled."""

    def __init__(self, batches):
        self.batches = batches
        self.produced = 0

    def __iter__(self):
        for ds in self.batches:
            self.produced += 1
            yield ds

    def reset(self):
        pass

    def batch_size(self):
        return int(self.batches[0].features.shape[0])

    def __len__(self):
        return len(self.batches)


# ---------------------------------------------------------------------------
# Prefetch: depth / backpressure / shutdown
# ---------------------------------------------------------------------------

def test_prefetch_yields_all_batches_staged():
    batches = _batches(7)
    pf = DevicePrefetchIterator(ListDataSetIterator(list(batches)), depth=3)
    out = list(pf)
    pf.close()
    assert len(out) == len(batches)
    for got, want in zip(out, batches):
        assert isinstance(got.features, jax.Array)   # staged on device
        np.testing.assert_array_equal(np.asarray(got.features),
                                      want.features)
        np.testing.assert_array_equal(np.asarray(got.labels), want.labels)


def test_prefetch_depth_backpressure():
    # a stalled consumer bounds the producer's run-ahead at
    # depth (staged) + queue_size (host queue) + 1 (in-flight item)
    depth, qsize = 2, 2
    src = CountingIterator(_batches(16))
    pf = DevicePrefetchIterator(src, depth=depth, queue_size=qsize)
    it = iter(pf)
    consumed = 3
    for _ in range(consumed):
        next(it)
    deadline = time.time() + 1.0      # let the producer run as far as it can
    while src.produced < len(src.batches) and time.time() < deadline:
        time.sleep(0.02)
    assert src.produced <= consumed + depth + qsize + 1
    assert src.produced < len(src.batches)     # backpressure actually bit
    it.close()
    pf.close()


def test_prefetch_early_break_stops_producer():
    src = CountingIterator(_batches(32))
    pf = DevicePrefetchIterator(src, depth=2)
    for i, _ in enumerate(pf):
        if i == 1:
            break                      # generator close -> producer stop
    deadline = time.time() + 2.0
    while pf.active_producers() and time.time() < deadline:
        time.sleep(0.02)
    assert pf.active_producers() == 0
    pf.close()                         # idempotent
    assert pf.active_producers() == 0


def test_prefetch_depth_validation():
    with pytest.raises(ValueError):
        DevicePrefetchIterator(ListDataSetIterator(_batches(2)), depth=0)


def test_async_iterator_close_joins_producers():
    src = CountingIterator(_batches(64))
    ait = AsyncDataSetIterator(src, queue_size=2)
    it = iter(ait)
    next(it)
    assert ait.active_producers() == 1
    ait.close(timeout=2.0)
    assert ait.active_producers() == 0
    ait.close(timeout=2.0)             # idempotent
    # no thread leak beyond the joined producers
    assert not [t for t in threading.enumerate()
                if t.name.startswith("AsyncDataSetIterator")]


# ---------------------------------------------------------------------------
# On-device normalization: bitwise parity with the host path
# ---------------------------------------------------------------------------

def _fitted(nz, batches):
    return nz.fit(ListDataSetIterator(list(batches)))


@pytest.mark.parametrize("make_nz", [
    lambda b: _fitted(NormalizerStandardize(), b),
    lambda b: _fitted(NormalizerStandardize(fit_labels=True), b),
    lambda b: _fitted(NormalizerMinMaxScaler(), b),
    lambda b: _fitted(NormalizerMinMaxScaler(-1.0, 2.0), b),
    lambda b: ImagePreProcessingScaler(),
    lambda b: ImagePreProcessingScaler(-1.0, 1.0),
], ids=["standardize", "standardize+labels", "minmax01", "minmax-12",
        "image01", "image-11"])
def test_device_normalizer_bitwise(make_nz):
    batches = _batches(3, batch=16, n_in=5, seed=3)
    nz = make_nz(batches)
    x = batches[0].features
    y = batches[0].labels
    host = DataSet(x.copy(), y.copy())
    nz.transform(host)

    dn = DeviceNormalizer.from_host(nz)
    dev_jit = jax.jit(dn.apply_features)(jnp.asarray(x))
    assert np.asarray(dev_jit).dtype == np.float32
    assert np.array_equal(np.asarray(dev_jit).view(np.uint32),
                          host.features.view(np.uint32)), \
        "on-device normalization is not bitwise identical under jit"

    # inside lax.scan — the position it occupies in the fused step body
    def body(c, xi):
        return c, dn.apply_features(xi)
    _, scanned = jax.jit(
        lambda xs: lax.scan(body, 0, xs))(jnp.stack([jnp.asarray(x)] * 2))
    for row in np.asarray(scanned):
        assert np.array_equal(row.view(np.uint32),
                              host.features.view(np.uint32)), \
            "on-device normalization is not bitwise identical inside scan"

    # labels: normalized iff the host normalizer was label-fitted
    dev_y = np.asarray(jax.jit(dn.apply_labels)(jnp.asarray(y)))
    assert np.array_equal(dev_y.view(np.uint32),
                          host.labels.view(np.uint32))


def test_device_normalizer_rejects_unfitted_and_unknown():
    with pytest.raises(ValueError):
        DeviceNormalizer.from_host(NormalizerStandardize())
    with pytest.raises(ValueError):
        DeviceNormalizer.from_host(NormalizerMinMaxScaler())
    with pytest.raises(TypeError):
        DeviceNormalizer.from_host(object())
    dn = DeviceNormalizer.from_host(ImagePreProcessingScaler())
    assert DeviceNormalizer.from_host(dn) is dn        # passthrough


def test_set_normalizer_matches_host_preprocessing():
    batches = _batches(6, seed=11)
    nz = _fitted(NormalizerStandardize(), batches)

    host_net = _mlp()
    for ds in batches:
        d = DataSet(ds.features.copy(), ds.labels)
        nz.transform(d)
        host_net.fit(d.features, d.labels)

    dev_net = _mlp()
    dev_net.set_normalizer(nz)
    for ds in batches:
        dev_net.fit(ds.features, ds.labels)

    for a, b in zip(jax.tree.leaves(host_net.params_),
                    jax.tree.leaves(dev_net.params_)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # clearing restores the raw path
    dev_net.set_normalizer(None)
    assert dev_net._device_norm is None


# ---------------------------------------------------------------------------
# Streaming fused epoch
# ---------------------------------------------------------------------------

def test_streaming_fused_epoch_matches_stacked_and_per_step():
    batches = _batches(8, seed=5)

    streaming = _mlp()
    streaming.fit(ListDataSetIterator(list(batches)), fused_steps=4)

    stacked = _mlp()
    for lo in (0, 4):
        stacked.fit_steps(
            jnp.stack([jnp.asarray(d.features) for d in batches[lo:lo + 4]]),
            jnp.stack([jnp.asarray(d.labels) for d in batches[lo:lo + 4]]))

    per_step = _mlp()
    per_step.fit(ListDataSetIterator(list(batches)), fused_steps=1)

    for a, b in zip(jax.tree.leaves(streaming.params_),
                    jax.tree.leaves(stacked.params_)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "streaming (in-jit stacked) fused epoch != stacked fit_steps"
    for a, c in zip(jax.tree.leaves(streaming.params_),
                    jax.tree.leaves(per_step.params_)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-6)


def test_streaming_fused_epoch_from_prefetcher():
    batches = _batches(8, seed=5)
    plain = _mlp()
    plain.fit(ListDataSetIterator(list(batches)), fused_steps=4)

    pf = DevicePrefetchIterator(ListDataSetIterator(list(batches)), depth=2)
    try:
        prefetched = _mlp()
        prefetched.fit(pf, fused_steps=4)
    finally:
        pf.close()
    for a, b in zip(jax.tree.leaves(plain.params_),
                    jax.tree.leaves(prefetched.params_)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert pf.active_producers() == 0


def test_device_blocks_mixed_masks_degrade_to_singles():
    batches = _batches(4, seed=9)
    batches[2].features_mask = np.ones_like(batches[2].features)
    out = list(device_blocks(ListDataSetIterator(list(batches)), 4))
    # the masked batch must not fuse with unmasked neighbours, and its
    # mask must survive
    kinds = [k for k, _ in out]
    assert "block" not in kinds or all(
        payload[2] is not None or all(
            getattr(p, "features_mask", None) is None
            for p in ([payload] if kind == "single" else []))
        for kind, payload in out)
    singles = [p for k, p in out if k == "single"]
    assert any(getattr(p, "features_mask", None) is not None
               for p in singles)
    total = sum(1 if k == "single" else len(p[0]) for k, p in out)
    assert total == len(batches)


def test_fit_steps_list_form_validation():
    net = _mlp()
    xs = [np.zeros((4, 6), np.float32)] * 2
    with pytest.raises(ValueError):
        net.fit_steps(xs, np.zeros((2, 4, 3), np.float32))  # ys not a list


# ---------------------------------------------------------------------------
# Sync-free step loop
# ---------------------------------------------------------------------------

def test_steady_state_loop_no_blocking_score_and_no_h2d():
    from deeplearning4j_tpu.train.listeners import (CollectScoresListener,
                                                    ScoreIterationListener)
    from deeplearning4j_tpu.utils import counters

    batches = _batches(4, seed=13)
    net = _mlp()
    collect = CollectScoresListener()
    net.listeners = [collect, ScoreIterationListener(print_every=1)]

    xs = [jnp.asarray(d.features) for d in batches]
    ys = [jnp.asarray(d.labels) for d in batches]
    net.fit_steps(xs, ys)              # warmup: compile + counter upload

    # any blocking score read in the loop trips this spy
    def boom():                        # pragma: no cover - failure path
        raise AssertionError("blocking score() read in steady-state loop")
    net.score = boom

    uploads_before = counters.counter_uploads.value
    # the guard turns any fresh host->device transfer inside the loop into
    # an error (CPU D2H is zero-copy, so the score spy covers that side)
    with jax.transfer_guard("disallow"):
        for _ in range(3):
            net.fit_steps(xs, ys)
    assert counters.counter_uploads.value == uploads_before, \
        "schedule counters were re-uploaded inside the steady-state loop"

    del net.score                      # restore the class method
    # scores were collected lazily as device arrays; the read syncs
    raw = net.score_array()
    assert isinstance(raw, jax.Array)
    assert len(collect.scores) == 4
    assert all(np.isfinite(s) for s in collect.scores)


def test_score_iteration_listener_skips_sync_when_muted(caplog):
    import logging
    from deeplearning4j_tpu.train.listeners import ScoreIterationListener

    net = _mlp()
    calls = []
    net.score = lambda: calls.append(1) or 0.5
    lst = ScoreIterationListener(print_every=1)
    logger = logging.getLogger("deeplearning4j_tpu")
    old = logger.level
    logger.setLevel(logging.WARNING)   # INFO muted -> no score read at all
    try:
        lst.iteration_done(net, 1, 0)
        assert not calls
        logger.setLevel(logging.INFO)
        with caplog.at_level(logging.INFO, logger="deeplearning4j_tpu"):
            lst.iteration_done(net, 2, 0)
        assert calls                   # emitted line pays the one sync
    finally:
        logger.setLevel(old)


# ---------------------------------------------------------------------------
# SPMD composition
# ---------------------------------------------------------------------------

def test_parallel_wrapper_fit_prefetched():
    from deeplearning4j_tpu.parallel import ParallelWrapper

    n_dev = len(jax.devices())
    batch = 2 * n_dev
    batches = _batches(4, batch=batch, seed=17)
    nz = _fitted(NormalizerStandardize(), batches)
    net = _mlp()
    net.set_normalizer(nz)
    pw = ParallelWrapper(net)
    pw.fit_prefetched(ListDataSetIterator(list(batches)), epochs=1,
                      fused_steps=2)
    assert np.isfinite(float(net.score()))


# ---------------------------------------------------------------------------
# Host spans in the monitor's ring (monitor/spans.py)
# ---------------------------------------------------------------------------

def _named(records, name):
    return [r for r in records if r.name == name]


@pytest.mark.parametrize("depth", [1, 2])
def test_prefetch_pass_records_wait_and_stage_per_batch(ring, depth):
    from deeplearning4j_tpu.monitor import recorded
    k = 5
    pf = DevicePrefetchIterator(ListDataSetIterator(_batches(k)), depth=depth)
    t_before = time.perf_counter()
    assert len(list(pf)) == k
    t_after = time.perf_counter()
    pf.close()
    got = recorded()
    waits, stages = _named(got, "input_wait"), _named(got, "input_stage")
    assert [r.n for r in waits] == [r.n for r in stages] == list(range(k))
    assert {r.name for r in got} == {"input_wait", "input_stage"}
    me = threading.get_ident()         # both on the consumer's thread
    assert {r.thread_ident for r in got} == {me}
    for w, s in zip(waits, stages):
        # the stage of a batch starts on the read that ends its wait
        assert t_before <= w.t0 <= w.t1 == s.t0 <= s.t1 <= t_after
        assert w.parent is None and s.parent is None
    for a, b in zip(stages, waits[1:]):
        assert a.t1 <= b.t0            # batches follow one another


def _mln_case():
    return _mlp(), [DataSet(b.features, b.labels) for b in _batches(4)]


def _graph_case():
    from deeplearning4j_tpu.nn import ComputationGraph, GraphBuilder
    conf = (GraphBuilder().seed(3).add_inputs("in")
            .set_input_types(InputType.feed_forward(6))
            .add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=3, loss="mcxent",
                                          activation="softmax"), "d")
            .set_outputs("out").build())
    return ComputationGraph(conf).init(), _batches(4)


def _bert_case():
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.train.updaters import Adam
    from deeplearning4j_tpu.zoo import BertConfig, BertModel
    rng = np.random.RandomState(0)
    batches = [MultiDataSet(
        features=[rng.randint(0, 100, (4, 16)).astype(np.int32),
                  np.ones((4, 16), np.float32)],
        labels=[rng.randint(0, 100, (4, 16)).astype(np.int32)],
        labels_masks=[(rng.rand(4, 16) < 0.2).astype(np.float32)])
        for _ in range(4)]
    return BertModel(BertConfig.tiny(), seed=0, updater=Adam(1e-3)), batches


@pytest.mark.parametrize("case", [_mln_case, _graph_case, _bert_case],
                         ids=["MultiLayerNetwork", "ComputationGraph",
                              "BertModel"])
def test_fit_records_one_step_dispatch_per_batch_under_fit_epoch(ring, case):
    from deeplearning4j_tpu.monitor import recorded
    model, batches = case()
    k = len(batches)
    start = model.iteration
    model.fit(DevicePrefetchIterator(ListDataSetIterator(batches)))
    got = recorded()
    (epoch,) = _named(got, "fit_epoch")
    steps = _named(got, "step_dispatch")
    assert len(steps) == k
    # n is the model's iteration count before the step ...
    assert [r.n for r in steps] == list(range(start, start + k))
    for r in steps:
        assert r.parent == "fit_epoch"
        assert r.thread_ident == epoch.thread_ident
        assert epoch.t0 <= r.t0 <= r.t1 <= epoch.t1
    # ... and the k-th dispatch of the epoch consumes the batch whose input
    # spans carry n = k: staged before it is dispatched, on the fit thread,
    # inside the same fit_epoch
    stages = _named(got, "input_stage")
    assert [r.n for r in stages] == list(range(k))
    for s, d in zip(stages, steps):
        assert s.t1 <= d.t0
        assert s.parent == "fit_epoch" and s.thread_ident == d.thread_ident
    assert len(_named(got, "input_wait")) == k


def test_fused_dispatch_is_one_step_dispatch_span(ring):
    from deeplearning4j_tpu.monitor import recorded
    net, batches = _mln_case()
    net.fit(ListDataSetIterator(batches), fused_steps=2)
    steps = _named(recorded(), "step_dispatch")
    assert [r.n for r in steps] == [0, 2]      # two dispatches of two steps
    assert net.iteration == 4
