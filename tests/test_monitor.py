"""Unified telemetry tests: registry primitives, spans, Prometheus
exposition, the UIServer `/metrics` endpoint, and the end-to-end acceptance
path (fit + prefetch + serving all visible in one scrape)."""
import threading
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.monitor import (Counter, Gauge, Histogram,
                                        MetricsRegistry, clear_recorded,
                                        current_span, enabled, note, recorded,
                                        registry, set_enabled, span,
                                        span_stack)
from deeplearning4j_tpu.monitor import spans as spans_mod
from deeplearning4j_tpu.monitor.instrument import TrainingInstruments


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", help="requests")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("depth")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2.0
    g.set_max(10)
    g.set_max(4)                    # ratchet: never goes down
    assert g.value == 10.0


def test_get_or_create_returns_same_child():
    reg = MetricsRegistry()
    a = reg.counter("c", labels={"m": "x"})
    b = reg.counter("c", labels={"m": "x"})
    other = reg.counter("c", labels={"m": "y"})
    assert a is b
    assert a is not other
    # label order must not matter
    h1 = reg.histogram("h", labels={"a": "1", "b": "2"})
    h2 = reg.histogram("h", labels={"b": "2", "a": "1"})
    assert h1 is h2


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x_total")
    with pytest.raises(TypeError):
        reg.gauge("x_total")


def test_get_never_creates():
    reg = MetricsRegistry()
    assert reg.get("nope") is None
    reg.counter("yes", labels={"k": "v"})
    assert reg.get("yes") is None               # different (empty) labels
    assert reg.get("yes", {"k": "v"}) is not None


def test_registry_concurrent_increments():
    """8 threads x 1000 increments each land exactly — the counter lock
    holds under the kind of contention training + prefetch producer +
    batcher worker + UI scraper generate."""
    reg = MetricsRegistry()
    c = reg.counter("hits_total")
    h = reg.histogram("lat_ms", maxlen=128)
    n_threads, n_iter = 8, 1000

    def work(i):
        for k in range(n_iter):
            c.inc()
            h.observe(float(k % 17))

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    assert h.count == n_threads * n_iter        # lifetime count, not window


def test_histogram_percentiles_match_numpy():
    rng = np.random.RandomState(3)
    vals = rng.lognormal(0.0, 1.0, 500)
    h = Histogram("h", maxlen=1000)
    for v in vals:
        h.observe(v)
    got = h.percentiles((50, 95, 99))
    s = np.sort(vals)
    for p in (50, 95, 99):
        # nearest-rank over the sorted sample — numpy's equivalent mode
        expect = s[int(round(p / 100.0 * (len(s) - 1)))]
        assert got[f"p{p}"] == pytest.approx(expect)


def test_histogram_window_slides_but_lifetime_accumulates():
    h = Histogram("h", maxlen=10)
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100
    assert h.sum == pytest.approx(sum(range(100)))
    assert h.max == 99.0
    # window holds only the last 10 -> p50 reflects recent traffic
    assert h.percentiles((50,))["p50"] >= 90.0
    lo, hi, counts = h.bins(5)
    assert (lo, hi) == (90.0, 99.0)
    assert sum(counts) == 10


def test_kill_switch_makes_recording_free():
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    g = reg.gauge("g")
    h = reg.histogram("h")
    set_enabled(False)
    try:
        assert not enabled()
        c.inc()
        g.set(5)
        h.observe(1.0)
        assert c.value == 0
        assert g.value == 0.0
        assert h.count == 0
    finally:
        set_enabled(True)
    c.inc()
    assert c.value == 1


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

def test_span_records_and_nests():
    reg = MetricsRegistry()
    with span("outer", registry_=reg):
        assert current_span() == "outer"
        with span("inner", registry_=reg):
            assert current_span() == "outer/inner"
            assert span_stack() == ["outer", "outer/inner"]
    assert current_span() is None
    outer = reg.get("span_ms", {"span": "outer"})
    inner = reg.get("span_ms", {"span": "outer/inner"})
    assert outer is not None and outer.count == 1
    assert inner is not None and inner.count == 1
    assert outer.sum >= inner.sum               # child time nests in parent


def test_span_stack_unwinds_on_exception():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        with span("boom", registry_=reg):
            raise ValueError("x")
    assert span_stack() == []
    rec = reg.get("span_ms", {"span": "boom"})
    assert rec is not None and rec.count == 1   # time still recorded


def test_span_disabled_is_a_noop():
    reg = MetricsRegistry()
    set_enabled(False)
    try:
        with span("quiet", registry_=reg):
            assert span_stack() == []
    finally:
        set_enabled(True)
    assert reg.get("span_ms", {"span": "quiet"}) is None


# ---------------------------------------------------------------------------
# The ring of recorded intervals
# ---------------------------------------------------------------------------

def test_note_and_span_land_in_the_ring_with_all_six_fields(ring):
    reg = MetricsRegistry()
    before = time.perf_counter()
    with span("outer", registry_=reg):
        note("leaf", 1.0, 2.5, n=7)
        with span("inner", registry_=reg):
            pass
    after = time.perf_counter()
    note("bare", 3.0, 4.0)
    leaf, inner, outer, bare = recorded()
    me = threading.get_ident()
    assert leaf == ("leaf", 1.0, 2.5, me, 7, "outer")
    assert (leaf.name, leaf.t0, leaf.t1, leaf.thread_ident, leaf.n,
            leaf.parent) == tuple(leaf)
    assert (inner.name, inner.n, inner.parent) == ("outer/inner", None,
                                                   "outer")
    assert (outer.name, outer.n, outer.parent) == ("outer", None, None)
    # a span's interval is read from perf_counter and holds its child's
    assert before <= outer.t0 <= inner.t0 <= inner.t1 <= outer.t1 <= after
    assert outer.thread_ident == inner.thread_ident == me
    assert bare == ("bare", 3.0, 4.0, me, None, None)
    # note() writes no histogram: the ring is its only trace
    assert reg.get("span_ms", {"span": "leaf"}) is None


def test_ring_is_bounded(ring):
    for i in range(spans_mod.RING_SIZE + 10):
        note("x", float(i), float(i) + 0.5, n=i)
    got = recorded()
    assert len(got) == spans_mod.RING_SIZE == 8192
    assert got[0].n == 10 and got[-1].n == spans_mod.RING_SIZE + 9
    clear_recorded()
    assert recorded() == []


def test_nothing_is_recorded_when_disabled(ring):
    reg = MetricsRegistry()
    set_enabled(False)
    try:
        note("quiet", 0.0, 1.0, n=1)
        with span("quiet_span", registry_=reg):
            note("inside", 0.0, 1.0)
    finally:
        set_enabled(True)
    assert recorded() == []
    note("loud", 0.0, 1.0)
    assert [r.name for r in recorded()] == ["loud"]


@pytest.mark.parametrize("since,until,want", [
    (None, None, ["a", "b", "c"]),
    (2.5, None, ["b", "c"]),          # b straddles `since`
    (None, 2.5, ["a", "b"]),          # b straddles `until`
    (1.5, 1.8, ["a"]),                # window inside an interval
    (4.5, 4.8, []),                   # between b and c: overlaps neither
    (0.0, 0.5, []),
    (7.0, None, []),
])
def test_recorded_filters_by_overlap(ring, since, until, want):
    note("a", 1.0, 2.0)
    note("b", 2.2, 4.0)
    note("c", 5.0, 6.0)
    got = recorded(since, until)
    assert [r.name for r in got] == want
    got.clear()                       # a copy: the ring is untouched
    assert len(recorded()) == 3


def test_spans_of_two_threads_carry_two_thread_identifiers(ring):
    reg = MetricsRegistry()

    def work():
        with span("worker", registry_=reg):
            note("w_leaf", 0.0, 1.0)

    with span("main", registry_=reg):
        t = threading.Thread(target=work)
        t.start()
        t.join()
        note("m_leaf", 0.0, 1.0)
    by_name = {r.name: r for r in recorded()}
    assert by_name["w_leaf"].thread_ident == by_name["worker"].thread_ident \
        == t.ident
    assert by_name["m_leaf"].thread_ident == by_name["main"].thread_ident \
        == threading.get_ident() != t.ident
    # nesting is per thread: the worker's spans do not hang under `main`
    assert by_name["w_leaf"].parent == "worker"
    assert by_name["worker"].parent is None
    assert by_name["m_leaf"].parent == "main"


def test_ring_takes_appends_from_many_threads_while_it_is_read(ring):
    """More writers than cores, a short switch interval, a reader copying
    all the while: no append is lost or torn, each thread's stay in order."""
    import sys
    writers, each = 16, 400            # 6,400 < RING_SIZE: nothing drops
    start = threading.Event()

    def work(k):
        start.wait(5.0)
        for i in range(each):
            note(f"w{k}", float(i), float(i) + 1.0, n=i)

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(writers)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        start.set()
        deadline = time.time() + 30.0
        while any(t.is_alive() for t in threads) and time.time() < deadline:
            for r in recorded():       # a copy taken while others append
                assert len(r) == 6 and r.t1 == r.t0 + 1.0
        for t in threads:
            t.join(30.0)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    got = recorded()
    assert len(got) == writers * each
    for k in range(writers):
        mine = [r for r in got if r.name == f"w{k}"]
        assert [r.n for r in mine] == list(range(each))
        assert len({r.thread_ident for r in mine}) == 1


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

def test_prometheus_text_format_golden():
    """Pin the exposition format: HELP/TYPE lines, label rendering,
    counter value, summary quantiles + _sum/_count."""
    reg = MetricsRegistry()
    c = reg.counter("jobs_total", help="jobs run", labels={"kind": "fit"})
    c.inc(3)
    h = reg.histogram("lat_ms", help="latency", labels={"server": "s0"})
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    text = reg.render_prometheus()
    expected = (
        "# HELP jobs_total jobs run\n"
        "# TYPE jobs_total counter\n"
        'jobs_total{kind="fit"} 3\n'
        "# HELP lat_ms latency\n"
        "# TYPE lat_ms summary\n"
        'lat_ms{server="s0",quantile="0.5"} 3\n'
        'lat_ms{server="s0",quantile="0.95"} 4\n'
        'lat_ms{server="s0",quantile="0.99"} 4\n'
        'lat_ms_sum{server="s0"} 10\n'
        'lat_ms_count{server="s0"} 4\n')
    assert text == expected


def test_prometheus_escapes_label_values():
    reg = MetricsRegistry()
    reg.counter("c_total", labels={"p": 'a"b\\c\nd'}).inc()
    text = reg.render_prometheus()
    assert 'p="a\\"b\\\\c\\nd"' in text


def test_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("c_total").inc(2)
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(7.0)
    snap = reg.snapshot(bins=4)
    assert snap["counters"] == {"c_total": 2}
    assert snap["gauges"] == {"g": 1.5}
    h = snap["histograms"]["h"]
    assert h["count"] == 1 and h["max"] == 7.0
    assert sum(h["bins"]["counts"]) == 1


# ---------------------------------------------------------------------------
# Instrument bundles
# ---------------------------------------------------------------------------

def test_training_instruments_compile_detection():
    import jax

    reg = MetricsRegistry()
    ins = TrainingInstruments("M", registry_=reg)
    f = jax.jit(lambda x: x * 2)
    ins.check_compile(f)
    assert ins.compiles.value == 0              # nothing traced yet
    f(np.float32(1.0))
    ins.check_compile(f)
    assert ins.compiles.value == 1
    f(np.float32(2.0))                          # same shape: cache hit
    ins.check_compile(f)
    assert ins.compiles.value == 1
    f(np.ones(3, np.float32))                   # new shape: retrace
    ins.check_compile(f)
    assert ins.compiles.value == 2
    g = jax.jit(lambda x: x + 1)                # rebuilt step = new fn
    g(np.float32(1.0))
    ins.check_compile(g)
    assert ins.compiles.value == 3


def test_training_instruments_record_dispatch_fused():
    reg = MetricsRegistry()
    ins = TrainingInstruments("M", registry_=reg)
    ins.record_dispatch(0.080, steps=8)
    assert ins.steps.value == 8
    assert ins.dispatches.value == 1
    assert ins.step_ms.percentiles((50,))["p50"] == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# End-to-end: training + pipeline + serving -> one /metrics scrape
# ---------------------------------------------------------------------------

def _mlp(n_in=6, n_out=3):
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    conf = (NeuralNetConfiguration.builder().seed(7)
            .list([DenseLayer(n_out=12, activation="relu"),
                   OutputLayer(n_out=n_out, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def test_metrics_endpoint_round_trip_during_fit():
    """The ISSUE acceptance path: train through the prefetch pipeline with
    a ModelServer live, then curl /metrics and find step-time,
    prefetch-depth and serving-queue series in one Prometheus scrape."""
    from deeplearning4j_tpu.data import DataSet
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.data.pipeline import DevicePrefetchIterator
    from deeplearning4j_tpu.serving import ModelServer
    from deeplearning4j_tpu.ui.server import UIServer

    rng = np.random.RandomState(0)
    batches = [DataSet(rng.rand(8, 6).astype(np.float32),
                       np.eye(3, dtype=np.float32)[rng.randint(0, 3, 8)])
               for _ in range(4)]
    net = _mlp()
    pf = DevicePrefetchIterator(ListDataSetIterator(batches), depth=2)
    try:
        net.fit(pf, epochs=1)
    finally:
        pf.close()

    server = ModelServer(max_batch=8, batch_timeout_ms=2.0)
    ui = UIServer()
    try:
        server.deploy("m", net)
        server.output("m", rng.rand(2, 6).astype(np.float32))
        port = ui.start(port=0)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
    finally:
        ui.stop()
        server.shutdown()

    assert "training_step_ms" in text
    assert "pipeline_prefetch_depth" in text
    assert "serving_queue_depth" in text
    assert "# TYPE training_step_ms summary" in text
    assert 'model="MultiLayerNetwork"' in text
    # the fit above really happened: non-zero step count in the scrape
    steps = registry().get("training_steps_total",
                           {"model": "MultiLayerNetwork"})
    assert steps is not None and steps.value >= 4


def test_dashboard_renders_registry_block():
    from deeplearning4j_tpu.ui.server import UIServer
    from deeplearning4j_tpu.ui.stats import render_registry_html

    registry().counter("dash_total", help="x").inc()
    html = render_registry_html(registry().snapshot(bins=8))
    assert "dash_total" in html
    ui = UIServer()
    try:
        port = ui.start(port=0)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/", timeout=10) as r:
            page = r.read().decode()
    finally:
        ui.stop()
    assert "Telemetry registry" in page


def test_serving_metrics_is_registry_view():
    """ServingMetrics has no private store: the same numbers the snapshot
    reports are live labeled series in the shared registry."""
    from deeplearning4j_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics(window=16)
    m.record_submit(queue_depth=3)
    m.record_dispatch(n_requests=2, rows=8, padded_rows=2, dispatch_ms=1.5)
    m.record_latency(4.0)
    lbl = {"server": m.server_label}
    assert registry().get("serving_submitted_total", lbl).value == 1
    assert registry().get("serving_queue_depth", lbl).value == 3
    assert registry().get("serving_latency_ms", lbl).count == 1
    snap = m.snapshot()
    assert snap["submitted"] == 1
    assert snap["dispatches"] == 1
    assert snap["batch_occupancy"] == pytest.approx(2.0)
    assert snap["padding_fraction"] == pytest.approx(0.2)


def test_counter_uploads_is_shared_series():
    """The sync-free invariant counter and the /metrics series are ONE
    object — incrementing one is visible through the other."""
    from deeplearning4j_tpu.utils import counters

    series = registry().get("device_counter_uploads_total")
    assert series is counters.counter_uploads
