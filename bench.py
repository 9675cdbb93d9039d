"""Benchmark harness — prints ONE JSON line for the driver.

Headline metric (BASELINE.json): ResNet-50 training samples/sec/chip on the
real TPU.  `vs_baseline` is measured-vs-north-star: the reference publishes
no numbers (BASELINE.md), so the comparison point is the commonly cited
nd4j-cuda/V100-class ResNet-50 training throughput of ~400 samples/sec/GPU
(MLPerf-era V100 fp32 figures); >1.0 means we beat it.

Extra per-config results (LeNet, LSTM char-LM) go to stderr so the stdout
contract stays one line.  Run: `python bench.py [--quick]`.

`python bench.py --serving [--quick]` instead benchmarks the
`deeplearning4j_tpu.serving` runtime (closed-loop concurrent clients
against a warmed ModelServer): p50/p99 latency, throughput and batch
occupancy go to stderr; stdout still carries exactly one JSON line (the
serving headline).

`python bench.py --pipeline [--quick]` A/Bs the async input pipeline
(device prefetch + on-device normalization + fused dispatch, no per-step
sync) against the old synchronous per-batch loop; detail to stderr, one
stdout JSON line.

`python bench.py --obs [--quick]` A/Bs the telemetry instrumentation
(monitor registry + spans) enabled vs disabled on that same pipeline loop
and asserts the overhead stays under 2%; detail to stderr, one stdout JSON
line.

`python bench.py --zero1 [--quick]` A/Bs the ZeRO-1 sharded weight update
(`ParallelWrapper.optimizer_sharding`, arXiv:2004.13336) against the
replicated update on the SAME mesh and model: wall time, per-replica
optimizer-state bytes (the HBM headline) and end-of-run parity; detail to
stderr + `BENCH_zero1.json`, one stdout JSON line.

`python bench.py --aot [--quick]` A/Bs cold vs warm PROCESS start through
the persistent executable cache (`deeplearning4j_tpu.compile`): two
identical subprocesses share one cache directory — the first pays every
compile (train step + serving bucket ladder), the second must start warm
with ZERO compiles (exit 1 otherwise); detail to stderr +
`BENCH_aot.json`, one stdout JSON line.

`python bench.py --autotune [--quick]` runs the schedule autotuner
(`compile.ScheduleAutotuner`) over {fused_steps, prefetch_depth,
donation} on the pipeline fixture, persists the winning schedule, reloads
and re-measures it (restart-survival check); detail to stderr +
`BENCH_autotune.json`, one stdout JSON line.

`python bench.py --comms [--quick]` A/Bs the hierarchical compressed
cross-host gradient exchange (threshold int streams + error-feedback
residuals over TCP) against the dense f32 exchange on a simulated 2-host
gang (LocalLauncher: real processes, real sockets): cross-host bytes on
wire (gate: >=5x reduction), steps/sec, and end-of-run loss parity
(gate: within 1%); detail to stderr + `BENCH_comms.json`, one stdout
JSON line.

`python bench.py --elastic [--quick]` A/Bs elastic gang survival: a
3-process gang whose rank 2 is killed mid-run (heartbeat detection,
generation-fenced re-formation at world 2, checkpoint-coordinated
resume) against the same training uninterrupted — gates: detection
within the failure deadline, resumed final loss matches an
uninterrupted world-2 run from the same checkpoint, and the whole
interruption inside the overhead budget; detail to stderr +
`BENCH_elastic.json`, one stdout JSON line.

`python bench.py --fleet [--quick]` A/Bs a long-tail model population
through the warm-pooled `serving.ModelFleet` against the naive
always-resident posture: models served per fixed device-memory budget
(gate: >=2x, with a compile-free second sweep via the persistent AOT
cache) and an overload phase where low-priority traffic is shed while
the high-priority p99 stays within its SLO (gate: both); detail to
stderr + `BENCH_fleet.json`, one stdout JSON line.

`python bench.py --fleetchaos [--quick]` gates serving fault tolerance
(`serving/resilience.py`): `ReplicaChaos` kills one replica and hangs
another mid-flood — gates: zero lost accepted requests, hi-priority p99
within SLO through the failure, every controller respawn compile-free
(`fresh_compiles == 0`), detection->respawn bounded, and a fleet restart
from the crc-guarded topology snapshot reconverging to the pre-crash
shape with zero cold compiles; detail to stderr +
`BENCH_fleetchaos.json`, one stdout JSON line.

`python bench.py --pallas [--quick]` benchmarks the Pallas fused-kernel
tier (`ops.pallas`): per-kernel conformance vs the jnp reference (always,
interpret mode on CPU), timed A/B vs the XLA-fused baseline on an
accelerator (gate: >=1.15x on at least one kernel; on CPU the A/B leg is
skipped and flagged `"simulated": true`), tile search -> persist -> replay
through `compile.autotune_tiles` (gate: the replay is a cache hit with
ZERO re-search), and the AOT-key proof (gate: a warm restart through the
persistent executable cache recompiles NOTHING, while installing a
different tile schedule produces a DISTINCT cache entry); detail to
stderr + `BENCH_pallas.json`, one stdout JSON line.

`python bench.py --quant [--quick]` A/Bs post-training-quantized serving
(`deeplearning4j_tpu.quant`: calibrate → int8 per-channel weights → fused
quantized forward) against the f32 model through the bucketed serving
cache, and round-trips the quantized executables through the persistent
AOT cache in a second subprocess — gates: >=2x throughput per byte
resident OR >=1.5x QPS, parity delta <=1%, warm restart with zero
compiles, quantized fingerprint distinct from f32; detail to stderr +
`BENCH_quant.json`, one stdout JSON line.

`python bench.py --decode [--quick]` floods the autoregressive decode
engine (`serving.decode`: bucketed prefill → token-level continuous
batching → paged KV cache) with sequence-length-skewed traffic and A/Bs
paged-int8 against contiguous-f32 KV memory — gates: zero fresh XLA
compiles after warmup across the skewed flood, tokens/sec floor,
inter-token p99 bound, int8 paged KV holds >=1.5x concurrent sequences
per HBM byte vs an f32 contiguous (max-length-reserving) cache at <=1%
attention parity; detail to stderr + `BENCH_decode.json`, one stdout
JSON line.
"""
import json
import sys
import time

import numpy as np

V100_RESNET50_SAMPLES_SEC = 400.0   # north-star comparison point (fp32 V100)

_DEVICE_KEYS = ("platform", "device_kind", "device_count")


def _device_fields():
    """What the numbers in a result line were measured on, as jax reports
    it.  Every mode's stdout JSON line carries these."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _time_steps(fit_fn, n_warmup, n_steps, sync_fn=None):
    """Chained-step timing: steps dispatch back-to-back (device-resident
    data, no per-step host sync — the async-prefetch training loop shape);
    `sync_fn` forces completion once, inside the timed region."""
    for _ in range(n_warmup):
        fit_fn()
    if sync_fn is not None:
        sync_fn()
    t0 = time.perf_counter()
    for _ in range(n_steps):
        fit_fn()
    if sync_fn is not None:
        sync_fn()
    return time.perf_counter() - t0


def _time_train(make_net, x, y, steps, fused_steps):
    """Train-throughput timing.  `fused_steps=k` (k > 1 dividing `steps`)
    times the fit_steps scan dispatch — one host dispatch per k steps;
    otherwise the per-step `fit` loop.  Whichever path is chosen is the
    path timed: a failure in it fails the run."""
    import jax.numpy as jnp

    net = make_net()
    if fused_steps and fused_steps > 1 and steps % fused_steps == 0:
        xs = jnp.broadcast_to(x, (fused_steps,) + x.shape)
        ys = jnp.broadcast_to(y, (fused_steps,) + y.shape)

        def block():
            net.fit_steps(xs, ys)

        return _time_steps(block, n_warmup=1, n_steps=steps // fused_steps,
                           sync_fn=lambda: float(net.score()))

    def step():
        net.fit(x, y)

    return _time_steps(step, n_warmup=3, n_steps=steps,
                       sync_fn=lambda: float(net.score()))


def bench_resnet50(batch=64, steps=20, image=224, classes=1000,
                   compute_dtype="bfloat16", fused_steps=5):
    # fused_steps=5 -> a 3.9 GB [k,64,224,224,3] f32 block; k=10 doubles
    # that against ~16 GB HBM with step activations live
    """bf16 compute / f32 master params — the TPU-native precision choice
    (f32: ~375 samples/sec on v5e; bf16: ~1636)."""
    from deeplearning4j_tpu.train.updaters import Nesterovs
    from deeplearning4j_tpu.zoo import ResNet50

    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, image, image, 3).astype(np.float32))
    y = jnp.asarray(
        np.eye(classes, dtype=np.float32)[rng.randint(0, classes, batch)])

    dt = _time_train(
        lambda: ResNet50(n_classes=classes, input_shape=(image, image, 3),
                         updater=Nesterovs(0.1, 0.9),
                         compute_dtype=compute_dtype).init_model(),
        x, y, steps, fused_steps)
    return batch * steps / dt


def bench_lenet(batch=256, steps=30, fused_steps=10):
    from deeplearning4j_tpu.zoo import LeNet

    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 28, 28, 1).astype(np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch)])

    dt = _time_train(lambda: LeNet().init_model(), x, y, steps, fused_steps)
    return batch * steps / dt


def bench_bert_base(batch=64, steps=10, t=128, compute_dtype="bfloat16"):
    """BERT-base masked-LM fine-tune step, tokens/sec (BASELINE config 3).
    bf16 compute (master params f32) — the TPU-native precision choice."""
    import jax
    from deeplearning4j_tpu.train.updaters import Adam
    from deeplearning4j_tpu.zoo import BertConfig, BertModel

    model = BertModel(BertConfig.base(max_len=t,
                                      compute_dtype=compute_dtype),
                      updater=Adam(1e-4))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 30522, (batch, t)).astype(np.int32)
    mask = np.ones((batch, t), np.float32)
    sel = rng.rand(batch, t) < 0.15
    lmask = sel.astype(np.float32)

    import jax.numpy as jnp

    from deeplearning4j_tpu.data.dataset import MultiDataSet
    mds = MultiDataSet(features=[jnp.asarray(ids), jnp.asarray(mask)],
                       labels=[jnp.asarray(ids)],
                       labels_masks=[jnp.asarray(lmask)])   # sparse labels

    fused = 5
    if steps % fused == 0:
        stk = MultiDataSet(
            features=[jnp.broadcast_to(f, (fused,) + f.shape)
                      for f in mds.features],
            labels=[jnp.broadcast_to(l, (fused,) + l.shape)
                    for l in mds.labels],
            labels_masks=[jnp.broadcast_to(m, (fused,) + m.shape)
                          for m in mds.labels_masks])

        def block():
            model.fit_steps(stk)

        dt = _time_steps(block, n_warmup=1, n_steps=steps // fused,
                         sync_fn=lambda: model.score())
        return batch * t * steps / dt

    def step():
        model.fit_batch(mds)

    dt = _time_steps(step, n_warmup=3, n_steps=steps,
                     sync_fn=lambda: model.score())
    return batch * t * steps / dt


def bench_bert_long_seq(batch=4, steps=5, t=2048, compute_dtype="bfloat16"):
    """Long-context BERT MLM step at seq 2048 — the regime where the
    Pallas flash-attention kernels engage (`_FLASH_MIN_SEQ`); at seq 128
    the dispatcher takes the XLA path, so the short-seq config cannot
    exercise them."""
    from deeplearning4j_tpu.train.updaters import Adam
    from deeplearning4j_tpu.zoo import BertConfig, BertModel

    model = BertModel(BertConfig.base(max_len=t,
                                      compute_dtype=compute_dtype),
                      updater=Adam(1e-4))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 30522, (batch, t)).astype(np.int32)
    mask = np.ones((batch, t), np.float32)
    lmask = (rng.rand(batch, t) < 0.15).astype(np.float32)

    import jax.numpy as jnp

    from deeplearning4j_tpu.data.dataset import MultiDataSet
    mds = MultiDataSet(features=[jnp.asarray(ids), jnp.asarray(mask)],
                       labels=[jnp.asarray(ids)],
                       labels_masks=[jnp.asarray(lmask)])

    def step():
        model.fit_batch(mds)

    dt = _time_steps(step, n_warmup=2, n_steps=steps,
                     sync_fn=lambda: model.score())
    return batch * t * steps / dt


def build_tf_bert_frozen(batch=32, t=128, layers=12, hidden=768,
                         heads=12, vocab=30522):
    """Build the BERT-base-shaped frozen TF GraphDef (BASELINE config 3's
    source model).  Returns (graph_def, frozen_concrete_fn, encoder_out
    name) — shared by the bench and the full-depth import-conformance
    test (`tests/test_modelimport.py`), so the timed path and the
    value-asserted path are THE SAME graph."""
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)

    rs = np.random.RandomState(0)
    H, NH, L, T, B = hidden, heads, layers, t, batch
    p = {"tok_emb": tf.constant(rs.randn(vocab, H).astype(np.float32)
                                * 0.02),
         "pos_emb": tf.constant(rs.randn(T, H).astype(np.float32) * 0.02)}
    for l in range(L):
        for w in ["wq", "wk", "wv", "wo"]:
            p[f"{l}.{w}"] = tf.constant(
                rs.randn(H, H).astype(np.float32) * 0.02)
        p[f"{l}.w1"] = tf.constant(rs.randn(H, 4 * H).astype(np.float32)
                                   * 0.02)
        p[f"{l}.w2"] = tf.constant(rs.randn(4 * H, H).astype(np.float32)
                                   * 0.02)
        p[f"{l}.g1"] = tf.constant(np.ones(H, np.float32))
        p[f"{l}.b1"] = tf.constant(np.zeros(H, np.float32))
        p[f"{l}.g2"] = tf.constant(np.ones(H, np.float32))
        p[f"{l}.b2"] = tf.constant(np.zeros(H, np.float32))

    def ln(x, g, b):
        mean = tf.reduce_mean(x, axis=-1, keepdims=True)
        var = tf.reduce_mean(tf.math.squared_difference(x, mean), axis=-1,
                             keepdims=True)
        return (x - mean) * tf.math.rsqrt(var + 1e-6) * g + b

    def gelu(x):
        return 0.5 * x * (1.0 + tf.math.erf(
            x / np.sqrt(2.0).astype(np.float32)))

    def f(ids):
        x = tf.gather(p["tok_emb"], ids, axis=0) + p["pos_emb"]
        for l in range(L):
            def heads_of(w):
                y = tf.matmul(tf.reshape(x, [B * T, H]), w)
                return tf.transpose(tf.reshape(y, [B, T, NH, H // NH]),
                                    [0, 2, 1, 3])
            q, k, v = (heads_of(p[f"{l}.wq"]), heads_of(p[f"{l}.wk"]),
                       heads_of(p[f"{l}.wv"]))
            s = tf.matmul(q, k, adjoint_b=True) / np.float32(
                np.sqrt(H // NH))
            ctx = tf.matmul(tf.nn.softmax(s, axis=-1), v)
            ctx = tf.reshape(tf.transpose(ctx, [0, 2, 1, 3]), [B, T, H])
            a = tf.matmul(tf.reshape(ctx, [B * T, H]), p[f"{l}.wo"])
            x = ln(x + tf.reshape(a, [B, T, H]), p[f"{l}.g1"],
                   p[f"{l}.b1"])
            h = gelu(tf.matmul(tf.reshape(x, [B * T, H]), p[f"{l}.w1"]))
            h = tf.matmul(h, p[f"{l}.w2"])
            x = ln(x + tf.reshape(h, [B, T, H]), p[f"{l}.g2"],
                   p[f"{l}.b2"])
        return x

    frozen = convert_variables_to_constants_v2(
        tf.function(f).get_concrete_function(
            tf.TensorSpec((B, T), tf.int32)))
    gd = frozen.graph.as_graph_def()
    # the frozen fn's structured output tensor names the true graph output
    enc = frozen.outputs[0].name.split(":")[0]
    return gd, frozen, enc


def bench_bert_tf_import(batch=32, steps=5, t=128, layers=12,
                         hidden=768, heads=12, vocab=30522):
    """BASELINE config 3 AS WRITTEN: BERT-base fine-tune via SameDiff TF
    import — build the frozen GraphDef in TF, import through
    modelimport.tf_import, attach a trainable head, measure the jitted
    SameDiff fine-tune step.  (Values of this exact import path are
    asserted against TF at full 12-layer depth in
    tests/test_modelimport.py::test_tf_import_full_depth_bert.)"""
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.modelimport import import_graph_def
    from deeplearning4j_tpu.train.updaters import Adam

    rs = np.random.RandomState(0)
    H, T, B = hidden, t, batch
    gd, frozen, enc = build_tf_bert_frozen(batch, t, layers, hidden,
                                           heads, vocab)
    sd = import_graph_def(gd)

    # trainable MLM head over the imported (constant) encoder
    import jax
    import jax.numpy as jnp
    w_head = sd.var("head_w", "XAVIER", H, vocab)
    logits = sd.op("matmul", sd.get_variable(enc), w_head, name="logits")
    lab = sd.placeholder("lab", (B, T))
    sd.loss.sparse_softmax_cross_entropy(lab, logits, name="loss")
    sd.set_loss_variables("loss")
    sd.set_training_config(TrainingConfig(
        updater=Adam(1e-4), data_set_feature_mapping=["ids"],
        data_set_label_mapping=["lab"]))
    ids = jnp.asarray(rs.randint(0, vocab, (B, T)).astype(np.int32))
    lab_v = jnp.asarray(rs.randint(0, vocab, (B, T)).astype(np.int32))

    def step():
        sd.fit(ids, lab_v)

    dt = _time_steps(step, n_warmup=2, n_steps=steps,
                     sync_fn=lambda: sd.score())
    return B * T * steps / dt


def bench_lstm_charlm(batch=64, steps=10, t=64, vocab=77, fused_steps=5):
    from deeplearning4j_tpu.zoo import TextGenLSTM

    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    idx = rng.randint(0, vocab, (batch, t))
    x = jnp.asarray(np.eye(vocab, dtype=np.float32)[idx])
    y = jnp.asarray(np.eye(vocab, dtype=np.float32)[np.roll(idx, -1, 1)])

    dt = _time_train(
        lambda: TextGenLSTM(n_classes=vocab,
                            input_shape=(t, vocab)).init_model(),
        x, y, steps, fused_steps)
    return batch * t * steps / dt


def bench_serving(duration_s=3.0, n_clients=16, max_batch=64,
                  batch_timeout_ms=2.0):
    """Closed-loop serving benchmark: `n_clients` threads drive mixed-size
    requests through a warmed `serving.ModelServer` (zoo LeNet) for
    `duration_s`.  Returns the SLO summary: requests/sec, rows/sec,
    latency percentiles, batch occupancy, compile-cache stats."""
    from concurrent.futures import ThreadPoolExecutor
    from deeplearning4j_tpu.serving import ModelServer

    srv = ModelServer(max_batch=max_batch, batch_timeout_ms=batch_timeout_ms,
                      max_queue=4096)
    srv.deploy("lenet", zoo="LeNet", warmup=True)
    sizes = (1, 2, 3, 4, 8)

    def client(i):
        rs = np.random.RandomState(i)
        reqs = rows = 0
        end = time.monotonic() + duration_s
        while time.monotonic() < end:
            n = sizes[reqs % len(sizes)]
            x = rs.rand(n, 28, 28, 1).astype(np.float32)
            srv.output("lenet", x, timeout=60)
            reqs += 1
            rows += n
        return reqs, rows

    t0 = time.perf_counter()
    with ThreadPoolExecutor(n_clients) as ex:
        totals = list(ex.map(client, range(n_clients)))
    dt = time.perf_counter() - t0
    snap = srv.stats()
    srv.shutdown()
    reqs = sum(r for r, _ in totals)
    rows = sum(r for _, r in totals)
    lat = snap["latency_ms"]
    return {
        "requests_per_sec": reqs / dt,
        "rows_per_sec": rows / dt,
        "p50_ms": lat["p50"], "p95_ms": lat["p95"], "p99_ms": lat["p99"],
        "batch_occupancy": snap["batch_occupancy"],
        "padding_fraction": snap["padding_fraction"],
        "compile_cache": snap["compile_cache"],
        "dispatches": snap["dispatches"],
        "clients": n_clients, "duration_s": dt,
    }


def _pipeline_fixture(n_batches, batch, n_in):
    """Shared fixture for `--pipeline` and `--obs`: an ETL-bearing iterator
    factory, an MLP factory, and a fitted normalizer over deterministic raw
    float64 rows."""
    from deeplearning4j_tpu.data import (DataSet, DataSetIterator,
                                         NormalizerStandardize)
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)

    class EtlIterator(DataSetIterator):
        """Materializes each batch from raw f64 rows on demand — the
        per-batch host cost a record-reader/augmentation pipeline pays."""

        def __init__(self, raw_x, raw_y, batch):
            self.raw_x, self.raw_y, self._batch = raw_x, raw_y, batch

        def __iter__(self):
            for i in range(0, len(self.raw_x), self._batch):
                x = (self.raw_x[i:i + self._batch] * 0.5
                     + 1.0).astype(np.float32)
                y = np.eye(10, dtype=np.float32)[self.raw_y[i:i + self._batch]]
                yield DataSet(x, y)

        def reset(self):
            pass

        def batch_size(self):
            return self._batch

        def __len__(self):
            return (len(self.raw_x) + self._batch - 1) // self._batch

    def make_net():
        conf = (NeuralNetConfiguration.builder().seed(0)
                .list([DenseLayer(n_out=512, activation="relu"),
                       DenseLayer(n_out=256, activation="relu"),
                       OutputLayer(n_out=10, loss="mcxent",
                                   activation="softmax")])
                .set_input_type(InputType.feed_forward(n_in)).build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.RandomState(0)
    raw_x = rng.rand(n_batches * batch, n_in) * 100.0       # float64 rows
    raw_y = rng.randint(0, 10, n_batches * batch)

    def make_it():
        return EtlIterator(raw_x, raw_y, batch)

    nz = NormalizerStandardize().fit(make_it())
    return make_it, make_net, nz


def bench_pipeline(n_batches=128, batch=64, fused_steps=16, depth=2,
                   n_in=784):
    """A/B the async input pipeline against the old synchronous loop on the
    SAME ETL-bearing iterator + model (an MLP — dense layers time
    identically inside and outside `lax.scan` on every backend, so the A/B
    isolates the pipeline; conv models hit an XLA:CPU while-loop slow path
    that would swamp it).  Each batch is materialized on demand from raw
    float64 rows (cast + affine + one-hot), the record-reader shape:

    A (sync): host ETL, host normalization, one dispatch per step, and a
      blocking `float(score())` read every iteration — host work and
      device compute strictly serialized, the pre-pipeline loop.
    B (pipeline): the SAME ETL runs in the `DevicePrefetchIterator`
      producer thread overlapped with compute (numpy and XLA both release
      the GIL), staged on device `depth` batches ahead; normalization is
      folded into the jitted step; fused k-step dispatch; one sync at the
      end.

    Default config uses small batches: the pipeline's structural win is
    amortizing per-step host dispatch, which dominates when step compute
    is short (the TPU regime it targets).  At large CPU batches both
    sides are compute-bound on the same single core and the A/B reads
    ~1.0x either way.
    """
    from deeplearning4j_tpu.data import DevicePrefetchIterator

    make_it, make_net, nz = _pipeline_fixture(n_batches, batch, n_in)

    net_a = make_net()

    def run_sync():
        for ds in make_it():
            nz.transform(ds)                      # host-side normalize
            net_a.fit(ds.features, ds.labels)     # one dispatch per step
            float(net_a.score())                  # per-iteration sync

    # best-of-3 epochs per side: a single epoch is short enough on CPU
    # that scheduler noise would dominate a one-shot reading
    t_sync = min(_time_steps(run_sync, n_warmup=1, n_steps=1)
                 for _ in range(3))

    net_b = make_net()
    net_b.set_normalizer(nz)                      # on-device prologue
    pf = DevicePrefetchIterator(make_it(), depth=depth)
    try:
        def run_pipe():
            net_b.fit(pf, fused_steps=fused_steps)

        t_pipe = min(_time_steps(run_pipe, n_warmup=1, n_steps=1,
                                 sync_fn=lambda: float(net_b.score()))
                     for _ in range(3))
    finally:
        pf.close()
    n = batch * n_batches
    return {"sync_wall_s": t_sync, "pipeline_wall_s": t_pipe,
            "speedup": t_sync / t_pipe,
            "sync_samples_per_sec": n / t_sync,
            "pipeline_samples_per_sec": n / t_pipe,
            "n_batches": n_batches, "batch": batch,
            "fused_steps": fused_steps, "prefetch_depth": depth}


def bench_obs(n_batches=96, batch=64, fused_steps=8, depth=2, n_in=784,
              repeats=3):
    """A/B the telemetry overhead on the `--pipeline` training loop: the
    SAME instrumented code runs with the registry enabled vs disabled
    (`monitor.set_enabled`), so the delta is exactly what the PR's
    instrumentation costs on the hottest loop in the repo (per-dispatch
    timing + counters in `_fit_batch`/`fit_steps`, prefetch gauges and
    producer-wait timing in `DevicePrefetchIterator`, the epoch span).

    Each side gets its own net + prefetch iterator, one warmup epoch
    (compile), then `repeats` measured epochs interleaved on/off so clock
    drift and cache effects hit both sides equally; min-of-N per side.
    """
    from deeplearning4j_tpu.data import DevicePrefetchIterator
    from deeplearning4j_tpu.monitor import registry, set_enabled

    make_it, make_net, nz = _pipeline_fixture(n_batches, batch, n_in)

    def make_side():
        net = make_net()
        net.set_normalizer(nz)                    # on-device prologue
        return net, DevicePrefetchIterator(make_it(), depth=depth)

    net_on, pf_on = make_side()
    net_off, pf_off = make_side()

    def epoch(net, pf):
        t0 = time.perf_counter()
        net.fit(pf, fused_steps=fused_steps)
        float(net.score())                        # one sync at the end
        return time.perf_counter() - t0

    t_on, t_off = [], []
    try:
        set_enabled(True)
        epoch(net_on, pf_on)                      # warmup + compile
        set_enabled(False)
        epoch(net_off, pf_off)
        for _ in range(repeats):
            set_enabled(True)
            t_on.append(epoch(net_on, pf_on))
            set_enabled(False)
            t_off.append(epoch(net_off, pf_off))
    finally:
        set_enabled(True)
        pf_on.close()
        pf_off.close()

    best_on, best_off = min(t_on), min(t_off)
    steps = registry().get("training_steps_total",
                           {"model": "MultiLayerNetwork"})
    return {"wall_on_s": best_on, "wall_off_s": best_off,
            "overhead_pct": (best_on - best_off) / best_off * 100.0,
            "steps_recorded": steps.value if steps is not None else 0,
            "n_batches": n_batches, "batch": batch,
            "fused_steps": fused_steps, "prefetch_depth": depth,
            "repeats": repeats}


def bench_resilience(n_batches=256, batch=64, n_in=784, save_every=128,
                     keep_last=3, depth=2, repeats=3):
    """A/B the fault-tolerance tax on the `--pipeline` training loop: the
    SAME per-step loop over `DevicePrefetchIterator`-staged batches runs
    with a `CheckpointManager(async_save=True)` committing every
    `save_every` steps (host snapshot on the step path, npz write +
    retention GC on a background thread, `wait()` INSIDE the timed
    region so in-flight writes are charged to the checkpointing side)
    versus bare.  The async design means the on-path cost is the
    synchronous `device_get` snapshot only (~1ms here); the rest is the
    background writer contending for host cores with XLA — real on this
    CPU A/B, absent on an accelerator.  Even at the bench cadence (a
    full checkpoint every ~128 steps, i.e. every few hundred ms of
    compute — production jobs checkpoint every few MINUTES) the gate
    asserts the whole thing stays under 5% of step time.

    Each side gets its own net + prefetch iterator, one warmup epoch
    (compile), then `repeats` measured epochs interleaved so clock drift
    hits both sides equally; min-of-N per side.
    """
    import os
    import shutil
    import tempfile

    from deeplearning4j_tpu.data import DevicePrefetchIterator
    from deeplearning4j_tpu.monitor.registry import registry
    from deeplearning4j_tpu.train.resilience import CheckpointManager

    make_it, make_net, nz = _pipeline_fixture(n_batches, batch, n_in)
    ckpt_root = tempfile.mkdtemp(prefix="bench_resilience_")

    def make_side(with_ckpt):
        net = make_net()
        net.set_normalizer(nz)                    # on-device prologue
        mgr = CheckpointManager(
            os.path.join(ckpt_root, "ck"), keep_last=keep_last,
            save_every_steps=save_every,
            async_save=True) if with_ckpt else None
        return net, mgr

    def epoch(net, mgr):
        pf = DevicePrefetchIterator(make_it(), depth=depth)
        t0 = time.perf_counter()
        for ds in pf:
            net._fit_dataset(ds)
            if mgr is not None:
                mgr.maybe_save(net)
        if mgr is not None:
            mgr.wait()                            # charge in-flight writes
        float(net.score())                        # one sync at the end
        return time.perf_counter() - t0

    net_ck, mgr = make_side(True)
    net_bare, _ = make_side(False)
    t_ck, t_bare = [], []
    try:
        epoch(net_ck, mgr)                        # warmup + compile
        epoch(net_bare, None)
        for _ in range(repeats):
            t_ck.append(epoch(net_ck, mgr))
            t_bare.append(epoch(net_bare, None))
    finally:
        mgr.wait()
        shutil.rmtree(ckpt_root, ignore_errors=True)

    best_ck, best_bare = min(t_ck), min(t_bare)
    n = n_batches * batch
    saves = registry().counter("resilience_checkpoints_total").value
    saved_bytes = registry().gauge("resilience_checkpoint_bytes").value
    return {"wall_ckpt_s": best_ck, "wall_bare_s": best_bare,
            "overhead_pct": (best_ck - best_bare) / best_bare * 100.0,
            "ckpt_samples_per_sec": n / best_ck,
            "bare_samples_per_sec": n / best_bare,
            "checkpoints_committed": saves,
            "checkpoint_bytes_total": saved_bytes,
            "save_every_steps": save_every, "keep_last": keep_last,
            "n_batches": n_batches, "batch": batch, "repeats": repeats}


def bench_zero1(batch=256, steps=48, fused_steps=8, n_in=256, hidden=1024):
    """A/B the ZeRO-1 sharded weight update against the replicated update
    on the same data mesh, model and batches (`ParallelWrapper` with and
    without `optimizer_sharding`): identical math (asserted at the end),
    different schedule + optimizer-state residency.  The structural win is
    per-replica optimizer-state HBM (~N×, `opt_bytes_ratio`); on real
    chips the reduce-scatter/all-gather decomposition also overlaps with
    backward, on a host-simulated CPU mesh the wall A/B mostly reads
    collective overhead."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.parallel import (ParallelWrapper, make_mesh,
                                             zero)
    from deeplearning4j_tpu.train.updaters import Adam

    devs = jax.devices()
    n = len(devs)

    def make_net():
        conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
                .list([DenseLayer(n_out=hidden, activation="relu"),
                       DenseLayer(n_out=hidden, activation="relu"),
                       OutputLayer(n_out=10, loss="mcxent",
                                   activation="softmax")])
                .set_input_type(InputType.feed_forward(n_in)).build())
        return MultiLayerNetwork(conf).init()

    rng = np.random.RandomState(0)
    x = rng.randn(batch, n_in).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch)]
    xs = jnp.broadcast_to(jnp.asarray(x), (fused_steps,) + x.shape)
    ys = jnp.broadcast_to(jnp.asarray(y), (fused_steps,) + y.shape)
    blocks = max(steps // fused_steps, 1)

    def side(sharded):
        net = make_net()
        pw = ParallelWrapper(net, make_mesh({"data": n}, devs),
                             optimizer_sharding=sharded)
        dt = _time_steps(lambda: pw.fit_steps(xs, ys), n_warmup=1,
                         n_steps=blocks, sync_fn=lambda: float(net.score()))
        return net, dt, zero.opt_state_bytes_per_replica(net.opt_state_)

    net_a, t_repl, bytes_repl = side(False)
    net_b, t_z1, bytes_z1 = side(True)
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        net_a.params_, net_b.params_)
    max_diff = max(jax.tree_util.tree_leaves(diffs))
    n_samples = batch * fused_steps * blocks
    return {"devices": n, "batch": batch, "fused_steps": fused_steps,
            "steps": fused_steps * blocks,
            "replicated_wall_s": t_repl, "zero1_wall_s": t_z1,
            "replicated_samples_per_sec": n_samples / t_repl,
            "zero1_samples_per_sec": n_samples / t_z1,
            "speedup_vs_replicated": t_repl / t_z1,
            "opt_bytes_replicated": bytes_repl,
            "opt_bytes_zero1": bytes_z1,
            "opt_bytes_ratio": bytes_repl / max(bytes_z1, 1),
            "max_param_diff": max_diff}


def main_zero1(quick: bool):
    """`--zero1` mode: A/B detail to stderr + BENCH_zero1.json, ONE stdout
    JSON line.  With JAX_PLATFORMS=cpu it asks for an 8-device virtual
    mesh (a 1-device run would make both the sharding and the A/B
    degenerate)."""
    import os
    if os.environ.get("JAX_PLATFORMS") == "cpu" and \
            "device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    try:
        r = (bench_zero1(batch=64, steps=16, fused_steps=4, hidden=256)
             if quick else bench_zero1())
    except Exception as e:
        print(json.dumps({"metric": "zero1_train_samples_per_sec",
                          "value": None, "unit": "samples/sec",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[zero1] {k} = {v}", file=sys.stderr, flush=True)
    import os
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_zero1.json"), "w") as f:
        json.dump(r, f, indent=2)
    print(json.dumps({
        "metric": "zero1_train_samples_per_sec",
        "value": round(r["zero1_samples_per_sec"], 1),
        "unit": "samples/sec",
        "replicated_samples_per_sec":
            round(r["replicated_samples_per_sec"], 1),
        "speedup_vs_replicated": round(r["speedup_vs_replicated"], 3),
        "opt_bytes_ratio": round(r["opt_bytes_ratio"], 2),
        "max_param_diff": r["max_param_diff"],
        **_device_fields(),
    }))


def bench_comms(steps=150, batch=32, procs=2, devices_per_process=2):
    """A/B the hierarchical gradient exchange: dense f32 vs threshold-
    compressed int streams across a simulated 2-host gang.

    Each "host" is a real OS process with its own XLA CPU client and
    local mesh (LocalLauncher), coupled ONLY by the TCP gradient mesh —
    the compiled grad half reduces over the local devices (ICI role), the
    host-side exchange combines across processes (DCN role).  Both sides
    train the same model on the same global data stream; the compressed
    side must land within 1% of the dense final loss on >=5x fewer
    cross-host bytes."""
    import os
    import tempfile
    from deeplearning4j_tpu.parallel.multihost import (LocalLauncher,
                                                       free_port)
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "mh_worker_comms.py")
    out = {}
    with tempfile.TemporaryDirectory() as td:
        for mode in ("dense", "compressed"):
            launcher = LocalLauncher(procs, devices_per_process)
            t0 = time.time()
            launcher.run(worker, [td, mode, steps, batch], timeout=600.0,
                         gradient_port=free_port())
            dt = time.time() - t0
            curves = [np.load(os.path.join(td, f"curve_{mode}_{r}.npz"))
                      for r in range(procs)]
            stats = []
            for r in range(procs):
                with open(os.path.join(td,
                                       f"stats_{mode}_{r}.json")) as f:
                    stats.append(json.load(f))
            # replica consistency: every rank applies the same combined
            # gradient, so end-of-run params must agree across ranks
            for r in range(1, procs):
                np.testing.assert_allclose(curves[0]["w0"],
                                           curves[r]["w0"],
                                           rtol=1e-5, atol=1e-6)
            wire = sum(s["bytes_sent_total"] + s["bytes_received_total"]
                       for s in stats)
            mean_curve = np.mean([c["losses"] for c in curves], axis=0)
            out[mode] = {
                "wall_s": dt, "steps_per_sec": steps / dt,
                "wire_bytes": wire,
                "final_loss": float(mean_curve[-1]),
                "compression_ratio_last":
                    max(s["last_compression_ratio"] for s in stats),
                "loss_curve": [round(float(v), 5) for v in mean_curve],
            }
    dense, comp = out["dense"], out["compressed"]
    reduction = dense["wire_bytes"] / max(comp["wire_bytes"], 1)
    parity = (abs(comp["final_loss"] - dense["final_loss"])
              / max(abs(dense["final_loss"]), 1e-9))
    return {"procs": procs, "devices_per_process": devices_per_process,
            "steps": steps, "batch_per_host": batch,
            "bytes_reduction_x": reduction, "loss_parity_rel": parity,
            "dense": dense, "compressed": comp}


def main_comms(quick: bool):
    """`--comms` mode: A/B detail to stderr + BENCH_comms.json, ONE
    stdout JSON line.  The gang itself always runs on forced-CPU child
    processes (LocalLauncher) — this mode measures the DCN exchange, not
    the accelerator."""
    import os
    try:
        r = (bench_comms(steps=100) if quick else bench_comms())
    except Exception as e:
        print(json.dumps({"metric": "comms_bytes_reduction_x",
                          "value": None, "unit": "x",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        if k in ("dense", "compressed"):
            for kk, vv in v.items():
                if kk != "loss_curve":
                    print(f"[comms] {k}.{kk} = {vv}", file=sys.stderr,
                          flush=True)
        else:
            print(f"[comms] {k} = {v}", file=sys.stderr, flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_comms.json"), "w") as f:
        json.dump(r, f, indent=2)
    ok = r["bytes_reduction_x"] >= 5.0 and r["loss_parity_rel"] <= 0.01
    print(json.dumps({
        "metric": "comms_bytes_reduction_x",
        "value": round(r["bytes_reduction_x"], 2),
        "unit": "x",
        "loss_parity_rel": round(r["loss_parity_rel"], 5),
        "dense_steps_per_sec": round(r["dense"]["steps_per_sec"], 1),
        "compressed_steps_per_sec":
            round(r["compressed"]["steps_per_sec"], 1),
        "pass": ok,
        "platform": "cpu",      # LocalLauncher workers, whatever the host
    }))
    if not ok:
        sys.exit(1)


def bench_elastic(steps=24, kill_step=8, heartbeat_s=0.1,
                  failure_deadline_s=2.0, overhead_budget_ms=15000.0):
    """A/B elastic gang survival: a 3-process gang whose rank 2 is killed
    mid-run (shrink-and-continue) vs the same training uninterrupted.

    Three runs: (A) 3-proc gang with a mid-run kill — the survivors must
    detect within the failure deadline, re-form at world 2 under a new
    generation, and resume from the coordinated checkpoint; (B) a clean
    world-2 gang started from THAT checkpoint — A's final loss must match
    it (nothing lost or double-counted across the reformation); (C) a
    clean 3-proc run of the same length, the wall-clock baseline the
    reformation overhead is reported against."""
    import os
    import shutil
    import tempfile
    from deeplearning4j_tpu.parallel.multihost import ElasticLocalRunner
    from deeplearning4j_tpu.train.resilience import CheckpointManager
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tests", "mh_worker_elastic_gang.py")

    def run(tag, td, procs, kill_rank, kill_at, ckpt_dir):
        out_dir = os.path.join(td, f"out_{tag}")
        os.makedirs(out_dir)
        t0 = time.time()
        res = ElasticLocalRunner(procs, backoff_base_s=0.2).run_elastic(
            worker, [out_dir, str(steps), "1", str(kill_rank), str(kill_at)],
            timeout=600.0, checkpoint_dir=ckpt_dir, policy="shrink",
            heartbeat_s=heartbeat_s, failure_deadline_s=failure_deadline_s,
            relaunch=False)
        wall = time.time() - t0
        if res["r0"][0] != 0:
            raise RuntimeError(f"{tag}: rank 0 failed:\n"
                               + res["r0"][1][-2000:])
        final = np.load(os.path.join(out_dir, "final_0.npz"))
        with open(os.path.join(out_dir, "elastic_0.json")) as f:
            info = json.load(f)
        return wall, final, info

    with tempfile.TemporaryDirectory() as td:
        ckpt_a = os.path.join(td, "ckpt_a")
        wall_a, final_a, info_a = run("a", td, 3, 2, kill_step, ckpt_a)
        reforms = info_a["reformations"]
        if len(reforms) != 1:
            raise RuntimeError(f"expected 1 reformation, got {reforms}")
        rf = reforms[0]
        # B: uninterrupted world-2 comparator from the resume checkpoint
        ckpt_b = os.path.join(td, "ckpt_b")
        shutil.copytree(ckpt_a, ckpt_b)
        for name in os.listdir(ckpt_b):
            p = os.path.join(ckpt_b, name)
            if os.path.isdir(p) and name.startswith(CheckpointManager.PREFIX) \
                    and int(name[len(CheckpointManager.PREFIX):]) \
                    > int(rf["resume_step"]):
                shutil.rmtree(p)
        _, final_b, _ = run("b", td, 2, -1, 0, ckpt_b)
        # C: clean 3-proc baseline for the wall-clock overhead
        wall_c, _, _ = run("c", td, 3, -1, 0, os.path.join(td, "ckpt_c"))
    loss_a, loss_b = float(final_a["score"]), float(final_b["score"])
    loss_delta_rel = abs(loss_a - loss_b) / max(abs(loss_b), 1e-12)
    return {
        "steps": steps, "kill_step": kill_step,
        "heartbeat_s": heartbeat_s,
        "failure_deadline_s": failure_deadline_s,
        "cause": rf["cause"], "world_after": rf["world"],
        "generation_after": info_a["stats"]["generation"],
        "detection_ms": rf["detection_ms"],
        "resume_ms": rf["resume_ms"],
        "reformation_ms": rf["detection_ms"] + rf["resume_ms"],
        "overhead_budget_ms": overhead_budget_ms,
        "final_loss_chaos": loss_a,
        "final_loss_uninterrupted": loss_b,
        "loss_delta_rel": loss_delta_rel,
        "wall_chaos_s": wall_a, "wall_clean_s": wall_c,
        "wall_overhead_s": wall_a - wall_c,
    }


def main_elastic(quick: bool):
    """`--elastic` mode: chaos A/B detail to stderr + BENCH_elastic.json,
    ONE stdout JSON line.  Gates: failure detected within the configured
    deadline (plus reactor slack), resumed final loss matches the
    uninterrupted-from-checkpoint run, and the whole
    detection-to-resumed interruption stays inside the overhead budget.
    The gang runs on forced-CPU child processes."""
    import os
    try:
        r = (bench_elastic(steps=12, kill_step=4) if quick
             else bench_elastic())
    except Exception as e:
        print(json.dumps({"metric": "elastic_reformation_ms",
                          "value": None, "unit": "ms",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[elastic] {k} = {v}", file=sys.stderr, flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_elastic.json"), "w") as f:
        json.dump(r, f, indent=2)
    detect_ok = r["detection_ms"] is not None and \
        r["detection_ms"] <= r["failure_deadline_s"] * 1000.0 + 2000.0
    loss_ok = r["loss_delta_rel"] <= 1e-9       # bitwise in practice
    overhead_ok = r["reformation_ms"] <= r["overhead_budget_ms"]
    ok = detect_ok and loss_ok and overhead_ok
    print(json.dumps({
        "metric": "elastic_reformation_ms",
        "value": round(r["reformation_ms"], 1),
        "unit": "ms",
        "detection_ms": round(r["detection_ms"], 1),
        "resume_ms": round(r["resume_ms"], 1),
        "loss_delta_rel": r["loss_delta_rel"],
        "detect_ok": detect_ok, "loss_ok": loss_ok,
        "overhead_ok": overhead_ok,
        "pass": ok,
        "platform": "cpu",      # LocalLauncher workers, whatever the host
    }))
    if not ok:
        sys.exit(1)


def main_pipeline(quick: bool):
    """`--pipeline` mode: A/B detail to stderr, ONE stdout JSON line."""
    import os
    try:
        r = (bench_pipeline(n_batches=96, batch=64, fused_steps=8)
             if quick else bench_pipeline())
    except Exception as e:
        print(json.dumps({"metric": "pipeline_train_samples_per_sec",
                          "value": None, "unit": "samples/sec",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[pipeline] {k} = {v}", file=sys.stderr, flush=True)
    print(json.dumps({
        "metric": "pipeline_train_samples_per_sec",
        "value": round(r["pipeline_samples_per_sec"], 1),
        "unit": "samples/sec",
        "sync_wall_s": round(r["sync_wall_s"], 3),
        "pipeline_wall_s": round(r["pipeline_wall_s"], 3),
        "speedup_vs_sync_loop": round(r["speedup"], 2),
        **_device_fields(),
    }))


def main_obs(quick: bool):
    """`--obs` mode: telemetry-overhead A/B detail to stderr, ONE stdout
    JSON line asserting the enabled-path overhead stays under 2%."""
    import os
    try:
        r = (bench_obs(n_batches=48, repeats=2) if quick else bench_obs())
    except Exception as e:
        print(json.dumps({"metric": "telemetry_overhead_pct", "value": None,
                          "unit": "%", "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[obs] {k} = {v}", file=sys.stderr, flush=True)
    ok = r["overhead_pct"] < 2.0 and r["steps_recorded"] > 0
    print(json.dumps({
        "metric": "telemetry_overhead_pct",
        "value": round(r["overhead_pct"], 3),
        "unit": "%",
        "threshold_pct": 2.0,
        "pass": ok,
        "wall_on_s": round(r["wall_on_s"], 3),
        "wall_off_s": round(r["wall_off_s"], 3),
        "steps_recorded": r["steps_recorded"],
        **_device_fields(),
    }))
    if not ok:
        sys.exit(1)


def main_resilience(quick: bool):
    """`--resilience` mode: checkpointing-overhead A/B detail to stderr,
    ONE stdout JSON line asserting the async-save step overhead stays
    under 5%."""
    import os
    try:
        r = (bench_resilience(n_batches=128, repeats=2) if quick
             else bench_resilience())
    except Exception as e:
        print(json.dumps({"metric": "resilience_ckpt_overhead_pct",
                          "value": None, "unit": "%",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[resilience] {k} = {v}", file=sys.stderr, flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_resilience.json"), "w") as f:
        json.dump(r, f, indent=2)
    ok = r["overhead_pct"] < 5.0 and r["checkpoints_committed"] > 0
    print(json.dumps({
        "metric": "resilience_ckpt_overhead_pct",
        "value": round(r["overhead_pct"], 3),
        "unit": "%",
        "threshold_pct": 5.0,
        "pass": ok,
        "wall_ckpt_s": round(r["wall_ckpt_s"], 3),
        "wall_bare_s": round(r["wall_bare_s"], 3),
        "checkpoints_committed": r["checkpoints_committed"],
        "checkpoint_bytes_total": r["checkpoint_bytes_total"],
        **_device_fields(),
    }))
    if not ok:
        sys.exit(1)


def main_serving(quick: bool):
    """`--serving` mode: serving metrics to stderr, ONE stdout JSON line."""
    import os
    try:
        r = bench_serving(duration_s=1.0 if quick else 3.0,
                          n_clients=8 if quick else 16)
    except Exception as e:
        print(json.dumps({"metric": "serving_lenet_requests_per_sec",
                          "value": None, "unit": "requests/sec",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[serving] {k} = {v}", file=sys.stderr, flush=True)
    print(json.dumps({
        "metric": "serving_lenet_requests_per_sec",
        "value": round(r["requests_per_sec"], 1),
        "unit": "requests/sec",
        "p50_ms": round(r["p50_ms"], 2),
        "p99_ms": round(r["p99_ms"], 2),
        "rows_per_sec": round(r["rows_per_sec"], 1),
        "batch_occupancy": round(r["batch_occupancy"], 2),
        **_device_fields(),
    }))


def bench_fleet(n_models=16, max_resident=4, duration_s=4.0,
                flood_requests=400):
    """`--fleet` A/B: a long-tail model population through a warm-pooled
    `serving.ModelFleet` vs the naive always-resident posture.

    Phase A (capacity): `n_models` distinct MLPs served through a
    `max_resident`-slot warm pool backed by a persistent AOT cache.  The
    naive baseline needs all `n_models` param sets device-resident at
    once; the fleet's peak residency is `max_resident` of them.  Gate (i):
    models served per fixed device-memory budget >= 2x naive.  The second
    sweep must be compile-free — every re-admission deserializes from the
    persistent cache.

    Phase B (overload): one high-priority model (generous SLO) plus one
    low-priority model flooded far past capacity.  The flood drives the
    low-priority p99 over its target; the fleet sheds low-priority traffic
    and keeps serving.  Gate (ii): high-priority p99 stays within its SLO
    while low-priority sheds are non-zero."""
    import shutil
    import tempfile
    import threading
    from concurrent.futures import ThreadPoolExecutor
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.serving import (LatencySLO, ModelFleet,
                                            RejectedError)
    from deeplearning4j_tpu.train.updaters import Sgd

    n_in = 32

    def make_net(seed, hidden):
        conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(1e-1))
                .list([DenseLayer(n_out=hidden, activation="relu"),
                       OutputLayer(n_out=10, loss="mcxent",
                                   activation="softmax")])
                .set_input_type(InputType.feed_forward(n_in)).build())
        return MultiLayerNetwork(conf).init()

    cache_dir = tempfile.mkdtemp(prefix="bench-fleet-")
    try:
        # ---- Phase A: long-tail capacity through the warm pool ----
        fleet = ModelFleet(max_resident=max_resident,
                           n_slices=2 * max_resident, max_batch=8,
                           batch_timeout_ms=1.0, cache_dir=cache_dir)
        per_model_bytes = []
        for i in range(n_models):
            # distinct widths -> distinct architecture fingerprints (no
            # cross-model executable sharing flattering the cache)
            net = make_net(i, 48 + 8 * (i % 8))
            import jax
            per_model_bytes.append(sum(
                leaf.nbytes for leaf in
                jax.tree_util.tree_leaves(net.params_)))
            fleet.deploy(f"m{i:02d}", net,
                         slo=LatencySLO(target_p99_ms=1000.0))
        rng = np.random.RandomState(0)
        reqs = 0
        t0 = time.perf_counter()
        compiles_after_first = None
        for sweep in range(2):
            for i in rng.permutation(n_models):
                x = rng.rand(2, n_in).astype(np.float32)
                fleet.output(f"m{i:02d}", x, deadline_ms=60_000.0,
                             timeout=120)
                reqs += 1
            if sweep == 0:
                compiles_after_first = fleet.cache.stats["compiles"]
        sweep_dt = time.perf_counter() - t0
        second_sweep_compiles = (fleet.cache.stats["compiles"]
                                 - compiles_after_first)
        st = fleet.fleet_stats()
        cache_stats = dict(fleet.cache.stats)
        warm_admissions = sum(
            1 for m in st["models"].values()
            if m["last_admission_fresh_compiles"] == 0)
        peak_bytes = fleet.resident_bytes_peak
        naive_bytes = sum(per_model_bytes)
        # models servable per fixed budget: the fleet serves all n_models
        # inside a peak residency the naive posture would exhaust after
        # budget/per_model models
        ratio = naive_bytes / peak_bytes if peak_bytes else 0.0
        fleet.shutdown()

        # ---- Phase B: overload -> shed low priority, hold high p99 ----
        hi_slo_ms = 500.0
        fleet = ModelFleet(max_resident=2, n_slices=2, max_batch=8,
                           batch_timeout_ms=1.0, cache_dir=cache_dir,
                           observe_every=4)
        fleet.deploy("hi", make_net(1001, 64),
                     slo=LatencySLO(target_p99_ms=hi_slo_ms, priority=10),
                     warm=True)
        fleet.deploy("lo", make_net(1002, 64),
                     slo=LatencySLO(target_p99_ms=2.0, priority=0),
                     warm=True)
        stop = threading.Event()
        hi_results = []

        def hi_client():
            rs = np.random.RandomState(7)
            while not stop.is_set():
                x = rs.rand(2, n_in).astype(np.float32)
                try:
                    fleet.output("hi", x, timeout=60)
                    hi_results.append(1)
                except RejectedError:
                    hi_results.append(0)
                time.sleep(0.002)

        hi_thread = threading.Thread(target=hi_client, daemon=True)
        hi_thread.start()

        def lo_flood(i):
            rs = np.random.RandomState(i)
            served = shed = 0
            for _ in range(flood_requests):
                x = rs.rand(4, n_in).astype(np.float32)
                try:
                    f = fleet.submit("lo", x)
                    f.exception(timeout=60)          # resolve, keep going
                    served += 1
                except RejectedError:
                    shed += 1
            return served, shed

        t0 = time.perf_counter()
        with ThreadPoolExecutor(8) as ex:
            flood_totals = list(ex.map(lo_flood, range(8)))
        flood_dt = time.perf_counter() - t0
        end = time.monotonic() + min(duration_s, 2.0)
        while time.monotonic() < end:               # hold hi load post-flood
            time.sleep(0.05)
        stop.set()
        hi_thread.join(timeout=30)
        hi_p99 = fleet.member("hi").latency.percentiles((99,))["p99"]
        lo_sheds = fleet.member("lo").sheds
        lo_served = sum(s for s, _ in flood_totals)
        hi_served = sum(hi_results)
        hi_shed = len(hi_results) - hi_served
        breached = fleet.member("lo").tracker.breaches_total
        fleet.shutdown()
        return {
            "n_models": n_models,
            "max_resident": max_resident,
            "sweep_requests": reqs,
            "sweep_requests_per_sec": reqs / sweep_dt,
            "naive_resident_bytes": naive_bytes,
            "fleet_peak_resident_bytes": peak_bytes,
            "models_per_budget_ratio": ratio,
            "second_sweep_compiles": second_sweep_compiles,
            "warm_admissions": warm_admissions,
            "evictions": sum(m["evictions"] for m in st["models"].values()),
            "aot_cache": cache_stats,
            "hi_slo_ms": hi_slo_ms,
            "hi_p99_ms": hi_p99,
            "hi_served": hi_served,
            "hi_shed": hi_shed,
            "lo_served": lo_served,
            "lo_sheds": lo_sheds,
            "lo_breaches": breached,
            "flood_duration_s": flood_dt,
        }
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


def main_fleet(quick: bool):
    """`--fleet` mode: A/B detail to stderr + BENCH_fleet.json, ONE stdout
    JSON line.  Gates: (i) >= 2x models per fixed device-memory budget vs
    always-resident, with a compile-free second sweep; (ii) high-priority
    p99 within SLO while low-priority traffic is shed under overload."""
    import os
    try:
        r = bench_fleet(n_models=8 if quick else 16,
                        max_resident=2 if quick else 4,
                        duration_s=1.0 if quick else 4.0,
                        flood_requests=120 if quick else 400)
    except Exception as e:
        print(json.dumps({"metric": "fleet_models_per_memory_budget",
                          "value": None, "unit": "x",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[fleet] {k} = {v}", file=sys.stderr, flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_fleet.json"), "w") as f:
        json.dump(r, f, indent=2)
    ok = (r["models_per_budget_ratio"] >= 2.0
          and r["second_sweep_compiles"] == 0
          and r["hi_p99_ms"] <= r["hi_slo_ms"]
          and r["lo_sheds"] > 0)
    print(json.dumps({
        "metric": "fleet_models_per_memory_budget",
        "value": round(r["models_per_budget_ratio"], 2),
        "unit": "x",
        "threshold": 2.0,
        "pass": ok,
        "second_sweep_compiles": r["second_sweep_compiles"],
        "hi_p99_ms": round(r["hi_p99_ms"], 2),
        "hi_slo_ms": r["hi_slo_ms"],
        "lo_sheds": r["lo_sheds"],
        "evictions": r["evictions"],
        "warm_admissions": r["warm_admissions"],
        **_device_fields(),
    }))
    if not ok:
        sys.exit(1)


def bench_fleetchaos(quick=False):
    """`--fleetchaos` gate: serving fault tolerance under injected
    replica failure (serving/resilience.py).

    Phase A (chaos flood): a hi-priority and a lo-priority member, two
    replicas each, flooded from client threads while `ReplicaChaos`
    KILLS one hi replica (every dispatch raises `ReplicaKilledError` —
    poison + failover) and HANGS one lo replica (a dispatch sleeps
    inside the compiled run — hedges cover the stuck requests, the
    controller declares it hung).  The reconcile loop must detect both,
    tear them down (remove-from-routing-first, bounded concurrent
    drain) and respawn them on the SAME slice through the persistent
    AOT cache.  Gates: zero lost accepted requests, hi-priority p99
    within its SLO through the failure, every respawn
    `fresh_compiles == 0`, detection->respawn bounded, and the
    degraded-mode ladder back at `full` once healed.

    Phase B (snapshot restart): the fleet commits a topology snapshot
    and shuts down; a NEW fleet process deploys the same models against
    the same cache dir and calls `restore_snapshot()`.  Gate: the
    pre-crash resident set and slice placements reconverge with zero
    cold compiles."""
    import itertools
    import os
    import shutil
    import tempfile
    import threading
    from concurrent.futures import ThreadPoolExecutor
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.serving import (FleetPolicy, LatencySLO,
                                            ModelFleet, RejectedError)
    from deeplearning4j_tpu.train.updaters import Sgd
    from deeplearning4j_tpu.utils.chaos import ReplicaChaos

    n_in = 16
    hi_slo_ms = 1500.0
    # 3s budget: the hedge fires at 1.5s — INSIDE the 2.5s hang window,
    # so requests stuck behind the hung dispatch resolve via their hedge
    deadline_ms = 3000.0
    flood = 60 if quick else 200            # requests per client thread
    clients = 3

    def make_net(seed, hidden=32):
        conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(1e-1))
                .list([DenseLayer(n_out=hidden, activation="relu"),
                       OutputLayer(n_out=4, loss="mcxent",
                                   activation="softmax")])
                .set_input_type(InputType.feed_forward(n_in)).build())
        return MultiLayerNetwork(conf).init()

    work_dir = tempfile.mkdtemp(prefix="bench-fleetchaos-")
    cache_dir = os.path.join(work_dir, "exec-cache")
    snap_path = os.path.join(work_dir, "fleet-snapshot.json")
    policy = FleetPolicy(respawn_after_s=0.3, hang_after_s=0.6,
                         drain_timeout_s=1.0, max_failovers=3,
                         ladder_down_after=4, ladder_up_after=3)

    def build_fleet(interval):
        return ModelFleet(max_resident=2, n_slices=4, max_batch=8,
                          batch_timeout_ms=1.0, cache_dir=cache_dir,
                          snapshot_path=snap_path, snapshot_interval_s=0.2,
                          reconcile_interval_s=interval, policy=policy,
                          observe_every=4)

    try:
        # ---- Phase A: chaos flood ----
        fleet = build_fleet(0.05)
        fleet.deploy("hi", make_net(1001),
                     slo=LatencySLO(target_p99_ms=hi_slo_ms, priority=10),
                     replicas=2, warm=True)
        fleet.deploy("lo", make_net(1002),
                     slo=LatencySLO(target_p99_ms=500.0, priority=0),
                     replicas=2, warm=True)
        # int8 standby for the ladder's quantized step; also makes every
        # later respawn warm BOTH versions from the shared AOT cache
        fleet.prepare_quantized("lo")
        x0 = np.random.RandomState(0).rand(2, n_in).astype(np.float32)
        for name in ("hi", "lo"):
            fleet.output(name, x0, deadline_ms=60_000.0, timeout=120)

        kill = ReplicaChaos(mode="kill", at_dispatch=0)
        hang = ReplicaChaos(mode="hang", at_dispatch=0, duration_s=2.5)
        armed = threading.Event()
        progress = itertools.count()         # requests submitted so far
        arm_at = flood * clients // 3        # fire MID-flood, data-driven

        def client(spec):
            name, seed = spec
            rs = np.random.RandomState(seed)
            served = failed = shed = 0
            lat = []
            for _ in range(flood):
                if next(progress) == arm_at:
                    # arm inside the flood, not on a wall clock — on a
                    # fast backend a timed arm can miss the flood window
                    kill.arm(fleet.member("hi").group.replicas[0])
                    hang.arm(fleet.member("lo").group.replicas[0])
                    armed.set()
                x = rs.rand(2, n_in).astype(np.float32)
                t0 = time.perf_counter()
                try:
                    f = fleet.submit(name, x, deadline_ms=deadline_ms)
                except RejectedError:
                    shed += 1
                    continue
                # accepted: this future MUST resolve — a kill/hang on
                # its replica has to fail over, not lose it
                if f.exception(timeout=60) is None:
                    served += 1
                    lat.append((time.perf_counter() - t0) * 1000.0)
                else:
                    failed += 1
            return name, served, failed, shed, lat

        specs = [("hi", 100 + i) for i in range(clients)] \
            + [("lo", 200 + i) for i in range(clients)]
        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(specs)) as ex:
            results = list(ex.map(client, specs))
        flood_dt = time.perf_counter() - t0
        assert armed.wait(timeout=10), "chaos never armed"

        # wait for the controller to heal both members
        heal_deadline = time.monotonic() + 15.0
        while time.monotonic() < heal_deadline:
            healthy = all(
                r.healthy and not r.poisoned
                for name in ("hi", "lo")
                for r in fleet.member(name).group.snapshot())
            if healthy and fleet.member("hi").respawns >= 1 \
                    and fleet.member("lo").respawns >= 1:
                break
            time.sleep(0.05)
        # recovery: "lo" is in sustained SLO breach from the hang window
        # (its p99 window still holds the stuck-request latencies), so
        # it self-sheds all but every-8th probe.  Drive probe traffic
        # until fresh under-target samples displace the hang latencies,
        # the breach clears, and the ladder hysteresis walks back to
        # `full` — the explicit recovery half of the degraded ladder.
        lo_recovery_probes = 0
        recover_deadline = time.monotonic() + 30.0
        while time.monotonic() < recover_deadline:
            try:
                fleet.output("lo", x0, deadline_ms=60_000.0, timeout=120)
                lo_recovery_probes += 1
            except RejectedError:
                pass
            if not fleet.member("lo").tracker.breached \
                    and fleet.ladder.level == 0:
                break
        fleet.output("hi", x0, deadline_ms=60_000.0, timeout=120)

        respawn_actions = [a for rec in fleet.controller.history
                           for a in rec["actions"]
                           if a["action"] == "respawn"]
        hi_p99 = fleet.member("hi").latency.percentiles((99,))["p99"]
        served = {n: 0 for n, *_ in results}
        failed = dict(served)
        shed = dict(served)
        for name, s, f_, sh, _ in results:
            served[name] += s
            failed[name] += f_
            shed[name] += sh
        inst = fleet.instruments
        counters = {
            "hedges": inst.hedges.value,
            "hedge_wasted": inst.hedge_wasted.value,
            "failovers": inst.failovers.value,
            "drain_timeouts": inst.drain_timeouts.value,
            "replica_probes": inst.replica_probes.value,
        }
        ladder_transitions = list(fleet.ladder.transitions)
        ladder_level_end = fleet.ladder.level
        topo_before = {
            "resident": fleet.pool.resident_names(),
            "slices": {name: sorted(r.slice.index
                                    for r in fleet.member(name)
                                    .group.snapshot())
                       for name in ("hi", "lo")},
        }
        fleet.save_snapshot()
        fleet.shutdown()                     # commits a final snapshot too
        kill.restore()
        hang.restore()

        # ---- Phase B: restart from snapshot, zero cold compiles ----
        fleet2 = build_fleet(None)
        fleet2.deploy("hi", make_net(1001),
                      slo=LatencySLO(target_p99_ms=hi_slo_ms, priority=10))
        fleet2.deploy("lo", make_net(1002),
                      slo=LatencySLO(target_p99_ms=500.0, priority=0))
        restore = fleet2.restore_snapshot()
        topo_after = {
            "resident": fleet2.pool.resident_names(),
            "slices": {name: sorted(r.slice.index
                                    for r in fleet2.member(name)
                                    .group.snapshot())
                       for name in ("hi", "lo")},
        }
        for name in ("hi", "lo"):            # the restored fleet serves
            # the snapshot restores lo's sustained-breach hysteresis, so
            # its first probes may be shed exactly like pre-crash
            for _ in range(256):
                try:
                    fleet2.output(name, x0, deadline_ms=60_000.0,
                                  timeout=120)
                    break
                except RejectedError:
                    time.sleep(0.02)
            else:
                raise RuntimeError(
                    f"restored probe for '{name}' never admitted")
        fleet2.shutdown()

        return {
            "flood_requests": flood * clients * 2,
            "flood_duration_s": flood_dt,
            "hi_slo_ms": hi_slo_ms,
            "hi_p99_ms": hi_p99,
            "served": served,
            "failed": failed,
            "shed": shed,
            "lost_accepted": sum(failed.values()),
            "respawns": respawn_actions,
            "respawn_fresh_compiles": [a["fresh_compiles"]
                                       for a in respawn_actions],
            "detect_to_respawn_ms": [
                round(a["detect_ms"] + a["respawn_ms"], 3)
                for a in respawn_actions],
            "counters": counters,
            "lo_recovery_probes": lo_recovery_probes,
            "ladder_transitions": ladder_transitions,
            "ladder_level_end": ladder_level_end,
            "topology_before": topo_before,
            "topology_after": topo_after,
            "restore": restore,
        }
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


def main_fleetchaos(quick: bool):
    """`--fleetchaos` mode: chaos detail to stderr + BENCH_fleetchaos.json,
    ONE stdout JSON line.  Gates: zero lost accepted requests through a
    replica kill + hang, hi-priority p99 within SLO, every respawn
    compile-free, detection->respawn bounded, snapshot restart
    reconverges to the pre-crash topology with zero cold compiles."""
    import os
    try:
        r = bench_fleetchaos(quick=quick)
    except Exception as e:
        print(json.dumps({"metric": "fleetchaos_lost_accepted",
                          "value": None, "unit": "requests",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[fleetchaos] {k} = {v}", file=sys.stderr, flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_fleetchaos.json"), "w") as f:
        json.dump(r, f, indent=2)
    causes = {a["cause"] for a in r["respawns"]}
    ok = (r["lost_accepted"] == 0
          and r["hi_p99_ms"] <= r["hi_slo_ms"]
          and len(r["respawns"]) >= 2
          and {"poisoned", "hung"} <= causes
          and all(c == 0 for c in r["respawn_fresh_compiles"])
          and all(ms <= 10_000.0 for ms in r["detect_to_respawn_ms"])
          and r["ladder_level_end"] == 0
          and r["restore"]["fresh_compiles"] == 0
          and r["topology_after"] == r["topology_before"])
    print(json.dumps({
        "metric": "fleetchaos_lost_accepted",
        "value": r["lost_accepted"],
        "unit": "requests",
        "threshold": 0,
        "pass": ok,
        "hi_p99_ms": round(r["hi_p99_ms"], 2),
        "hi_slo_ms": r["hi_slo_ms"],
        "respawns": len(r["respawns"]),
        "respawn_causes": sorted(causes),
        "respawn_fresh_compiles": r["respawn_fresh_compiles"],
        "detect_to_respawn_ms": r["detect_to_respawn_ms"],
        "restore_fresh_compiles": r["restore"]["fresh_compiles"],
        "ladder_level_end": r["ladder_level_end"],
        "hedges": r["counters"]["hedges"],
        "failovers": r["counters"]["failovers"],
        **_device_fields(),
    }))
    if not ok:
        sys.exit(1)


def bench_federation(quick=False):
    """`--federation` gate: cross-host fleet federation under injected
    host failure (serving/federation.py).

    Three in-process hosts, each a full `ModelFleet` (hi + lo members,
    all sharing one persistent AOT cache dir) behind a `HostAgent`,
    fronted by one `FederationRouter`.  Hi/lo client threads flood the
    router; mid-flood `HostChaos` KILLS the hi-affinity host (EOF ->
    cause ``crash``) and PARTITIONS a second host for a window (silence
    -> cause ``partition``; the replies it flushes on heal are
    generation-fenced and counted).  The router must evict both, fail
    over every orphaned in-flight request inside its deadline budget,
    and warm-re-place each dead host's models on a survivor from the
    replicated snapshot (`fresh_compiles == 0`).  The partitioned host
    auto-rejoins on heal; the killed host is relaunched as a NEW agent
    with the same host id and must be re-admitted at a bumped
    generation with its snapshot offered back.  Gates: zero lost
    accepted requests, zero malformed replies delivered, hi-priority
    p99 within SLO through both events, eviction causes >= {crash,
    partition}, every re-placement warm, stale dispatches fenced AND
    counted, detection->replacement bounded, both failed hosts back in
    the membership at the end."""
    import os
    import shutil
    import tempfile
    import threading
    from concurrent.futures import ThreadPoolExecutor
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.serving import (FederationPolicy,
                                            FederationRouter, HostAgent,
                                            LatencySLO, ModelFleet,
                                            RejectedError)
    from deeplearning4j_tpu.serving.federation import _rendezvous
    from deeplearning4j_tpu.train.updaters import Sgd
    from deeplearning4j_tpu.utils.chaos import HostChaos

    n_in = 16
    n_out = 4
    hi_slo_ms = 2500.0
    deadline_ms = 8000.0
    flood = 40 if quick else 120            # requests per client thread
    clients = 2                             # threads per priority class
    host_ids = ["h1", "h2", "h3"]

    def make_net(seed, hidden=32):
        conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(1e-1))
                .list([DenseLayer(n_out=hidden, activation="relu"),
                       OutputLayer(n_out=n_out, loss="mcxent",
                                   activation="softmax")])
                .set_input_type(InputType.feed_forward(n_in)).build())
        return MultiLayerNetwork(conf).init()

    work_dir = tempfile.mkdtemp(prefix="bench-federation-")
    cache_dir = os.path.join(work_dir, "exec-cache")   # SHARED across hosts
    policy = FederationPolicy(heartbeat_interval_s=0.1,
                              failure_deadline_s=0.8,
                              straggler_deadline_s=6.0,
                              max_failovers=3, affinity_slack=4,
                              ghost_linger_s=8.0)

    def build_fleet(host_id):
        d = os.path.join(work_dir, host_id)
        os.makedirs(d, exist_ok=True)
        fleet = ModelFleet(max_resident=2, n_slices=4, max_batch=8,
                           batch_timeout_ms=1.0, cache_dir=cache_dir,
                           snapshot_path=os.path.join(d, "snapshot.json"),
                           snapshot_interval_s=0.2, host_id=host_id,
                           observe_every=4)
        fleet.deploy("hi", make_net(1001),
                     slo=LatencySLO(target_p99_ms=hi_slo_ms, priority=10),
                     warm=True)
        fleet.deploy("lo", make_net(1002),
                     slo=LatencySLO(target_p99_ms=1000.0, priority=0),
                     warm=True)
        return fleet

    router = FederationRouter(
        policy, replicas_dir=os.path.join(work_dir, "router-replicas"))
    os.makedirs(router.replicas_dir, exist_ok=True)
    fleets, agents = {}, {}
    try:
        port = router.start(0)
        for h in host_ids:
            fleets[h] = build_fleet(h)
            agents[h] = HostAgent(
                h, fleets[h], ("127.0.0.1", port), policy=policy,
                replicas_dir=os.path.join(work_dir, h, "replicas")).start()
        x0 = np.random.RandomState(0).rand(2, n_in).astype(np.float32)
        for name in ("hi", "lo"):           # warm the cross-host path
            router.output(name, x0, deadline_ms=60_000.0, timeout=120)
        for h in host_ids:                  # replicate a snapshot of each
            fleets[h].save_snapshot()       # host's topology to the router
        rep_deadline = time.monotonic() + 10.0
        while time.monotonic() < rep_deadline:
            if set(router.federation_stats()["replicas"]) >= set(host_ids):
                break
            time.sleep(0.05)
        else:
            raise RuntimeError("snapshot replication never completed")

        # the hi-affinity host takes the kill (it is guaranteed traffic);
        # the lo-affinity host among the SURVIVORS takes the partition,
        # so its post-kill lo dispatches trip the chaos wrapper
        kill_host = _rendezvous(host_ids, "hi")
        part_host = _rendezvous([h for h in host_ids if h != kill_host],
                                "lo")
        kill = HostChaos(mode="kill", at_dispatch=0)
        part = HostChaos(mode="partition", at_dispatch=0, duration_s=1.5)
        armed = {"kill": threading.Event(), "part": threading.Event()}
        progress = threading.Lock()
        submitted = [0]
        total = flood * clients * 2

        def client(spec):
            name, prio, seed = spec
            rs = np.random.RandomState(seed)
            served = failed = shed = bad = 0
            lat = []
            for _ in range(flood):
                with progress:
                    submitted[0] += 1
                    n = submitted[0]
                if n == total // 4 and not kill.fired:
                    kill.arm(agents[kill_host])
                    armed["kill"].set()
                if n == total // 2 and not part.fired:
                    part.arm(agents[part_host])
                    armed["part"].set()
                x = rs.rand(2, n_in).astype(np.float32)
                t0 = time.perf_counter()
                try:
                    f = router.submit(name, x, priority=prio,
                                      deadline_ms=deadline_ms)
                except RejectedError:
                    shed += 1
                    continue
                # accepted: this future MUST resolve — a killed or
                # partitioned host has to fail over, not lose it
                exc = f.exception(timeout=60)
                if exc is None:
                    y = f.result()
                    if y.shape != (2, n_out):   # a stale reply delivered
                        bad += 1                # to a client would land here
                    else:
                        served += 1
                        lat.append((time.perf_counter() - t0) * 1000.0)
                else:
                    failed += 1
            return name, served, failed, shed, bad, lat

        specs = [("hi", 10, 100 + i) for i in range(clients)] \
            + [("lo", 0, 200 + i) for i in range(clients)]
        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(specs)) as ex:
            results = list(ex.map(client, specs))
        flood_dt = time.perf_counter() - t0
        assert armed["kill"].wait(10) and armed["part"].wait(10), \
            "chaos never armed"

        # ---- sustain + recovery: the flood can outrun the failure
        # detector, so keep traffic flowing (still SLO-gated: sustain
        # hi latencies count toward p99) until BOTH faults have fired,
        # both evictions are replaced, and the partitioned host is back
        sustain = {"served": 0, "failed": 0, "shed": 0}
        sustain_hi_lat = []
        rs = np.random.RandomState(999)
        recover_deadline = time.monotonic() + 45.0
        while time.monotonic() < recover_deadline:
            ev = list(router.events)
            replaced = {e["host"] for e in ev if e["event"] == "replaced"}
            if kill.fired and part.fired \
                    and {kill_host, part_host} <= replaced \
                    and part_host in router.hosts() \
                    and agents[part_host].generation == router.generation:
                break
            for name, prio in (("hi", 10), ("lo", 0)):
                x = rs.rand(2, n_in).astype(np.float32)
                ts = time.perf_counter()
                try:
                    f = router.submit(name, x, priority=prio,
                                      deadline_ms=deadline_ms)
                except RejectedError:
                    sustain["shed"] += 1
                    continue
                if f.exception(timeout=60) is None:
                    sustain["served"] += 1
                    if name == "hi":
                        sustain_hi_lat.append(
                            (time.perf_counter() - ts) * 1000.0)
                else:
                    sustain["failed"] += 1
            time.sleep(0.02)
        else:
            raise RuntimeError(
                "federation never recovered: "
                f"kill.fired={kill.fired} part.fired={part.fired} "
                f"events={list(router.events)[-12:]}")
        events = list(router.events)
        evictions = [e for e in events if e["event"] == "evict"]
        replacements = [e for e in events if e["event"] == "replaced"]
        stale_fenced = int(router.instruments.stale_dispatch.value)

        # ---- relaunch the killed host: same id, NEW agent, bumped gen ----
        gen_before = router.generation
        relaunched = HostAgent(
            kill_host, fleets[kill_host], ("127.0.0.1", port),
            policy=policy,
            replicas_dir=os.path.join(work_dir, kill_host, "replicas"))
        relaunched.start(timeout=15.0)
        old_agent, agents[kill_host] = agents[kill_host], relaunched
        old_agent.close()
        for name in ("hi", "lo"):           # full membership serves again
            router.output(name, x0, deadline_ms=60_000.0, timeout=120)

        served = {n: 0 for n, *_ in results}
        failed, shed, bad = dict(served), dict(served), dict(served)
        hi_lat = list(sustain_hi_lat)
        for name, s, f_, sh, b, lat in results:
            served[name] += s
            failed[name] += f_
            shed[name] += sh
            bad[name] += b
            if name == "hi":
                hi_lat.extend(lat)
        hi_lat.sort()
        hi_p99 = hi_lat[min(len(hi_lat) - 1,
                            int(len(hi_lat) * 0.99))] if hi_lat else -1.0

        return {
            "flood_requests": total,
            "flood_duration_s": flood_dt,
            "hi_slo_ms": hi_slo_ms,
            "hi_p99_ms": hi_p99,
            "served": served,
            "failed": failed,
            "shed": shed,
            "bad_replies": bad,
            "sustain": sustain,
            "lost_accepted": sum(failed.values()) + sustain["failed"],
            "kill_host": kill_host,
            "part_host": part_host,
            "evictions": [{k: e[k] for k in
                           ("host", "cause", "detection_ms", "generation")}
                          for e in evictions],
            "replacements": [{k: e[k] for k in
                              ("host", "on", "models", "fresh_compiles",
                               "warm", "replace_ms")}
                             for e in replacements],
            "stale_fenced": stale_fenced,
            "part_host_rejoins": agents[part_host].rejoins,
            "relaunch_generation_before": gen_before,
            "relaunch_generation_after": router.generation,
            "relaunch_agent_generation": relaunched.generation,
            "relaunch_snapshot_restored": relaunched.restored is not None,
            "final_hosts": router.hosts(),
            "final_healthz": router.healthz(),
        }
    finally:
        for a in agents.values():
            try:
                a.close()
            except Exception:
                pass
        router.shutdown()
        for f in fleets.values():
            try:
                f.shutdown()
            except Exception:
                pass
        shutil.rmtree(work_dir, ignore_errors=True)


def main_federation(quick: bool):
    """`--federation` mode: federation detail to stderr +
    BENCH_federation.json, ONE stdout JSON line.  Gates: zero lost
    accepted requests through a host kill + a host partition, zero
    stale replies delivered to clients (fenced AND counted instead),
    hi-priority p99 within SLO, both evictions warm-re-placed within
    bound, partitioned host auto-rejoined, killed host re-admitted at a
    bumped generation."""
    import os
    try:
        r = bench_federation(quick=quick)
    except Exception as e:
        print(json.dumps({"metric": "federation_lost_accepted",
                          "value": None, "unit": "requests",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[federation] {k} = {v}", file=sys.stderr, flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_federation.json"), "w") as f:
        json.dump(r, f, indent=2)
    causes = {e["cause"] for e in r["evictions"]}
    replaced_hosts = {p["host"] for p in r["replacements"]}
    ok = (r["lost_accepted"] == 0
          and sum(r["bad_replies"].values()) == 0
          and r["hi_p99_ms"] <= r["hi_slo_ms"]
          and {"crash", "partition"} <= causes
          and {r["kill_host"], r["part_host"]} <= replaced_hosts
          and all(p["warm"] and p["fresh_compiles"] == 0
                  for p in r["replacements"])
          and all(e["detection_ms"] <= 5_000.0 for e in r["evictions"])
          and all(p["replace_ms"] <= 10_000.0 for p in r["replacements"])
          and r["stale_fenced"] >= 1
          and r["part_host_rejoins"] >= 1
          and r["relaunch_generation_after"]
          > r["relaunch_generation_before"]
          and r["relaunch_agent_generation"]
          == r["relaunch_generation_after"]
          and sorted(r["final_hosts"]) == ["h1", "h2", "h3"]
          and r["final_healthz"]["ok"])
    print(json.dumps({
        "metric": "federation_lost_accepted",
        "value": r["lost_accepted"],
        "unit": "requests",
        "threshold": 0,
        "pass": ok,
        "hi_p99_ms": round(r["hi_p99_ms"], 2),
        "hi_slo_ms": r["hi_slo_ms"],
        "eviction_causes": sorted(causes),
        "replacements_warm": [p["warm"] for p in r["replacements"]],
        "detection_ms": [e["detection_ms"] for e in r["evictions"]],
        "replace_ms": [p["replace_ms"] for p in r["replacements"]],
        "stale_fenced": r["stale_fenced"],
        "part_host_rejoins": r["part_host_rejoins"],
        "relaunch_generation": r["relaunch_generation_after"],
        "final_hosts": r["final_hosts"],
        **_device_fields(),
    }))
    if not ok:
        sys.exit(1)


def aot_child(cache_dir: str, steps: int, batch: int, n_in: int):
    """`--aot-child` worker: ONE process's cold-or-warm measurement.

    Builds the pipeline-fixture MLP with its train step routed through the
    persistent executable cache at `cache_dir`, times time-to-first-step
    and steady-state throughput, then warms a persistent-tier serving
    bucket ladder for the same model.  Prints one JSON line; the parent
    (`bench_aot`) runs this twice against the same directory — the first
    run pays every compile, the second must deserialize all of them."""
    from deeplearning4j_tpu.compile import PersistentExecutableCache
    from deeplearning4j_tpu.serving import BucketedCompileCache

    _, make_net, _ = _pipeline_fixture(1, batch, n_in)
    cache = PersistentExecutableCache(cache_dir)
    net = make_net().set_executable_cache(cache)
    rng = np.random.RandomState(0)
    x = rng.rand(batch, n_in).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch)]

    t0 = time.perf_counter()
    net.fit(x, y)
    float(net.score())                       # force completion
    t_first = time.perf_counter() - t0       # compile-or-deserialize + step

    t0 = time.perf_counter()
    for _ in range(steps):
        net.fit(x, y)
    float(net.score())
    t_steady = time.perf_counter() - t0

    scache = BucketedCompileCache(max_batch=16, persistent=cache)
    t0 = time.perf_counter()
    scache.warmup("bench:v1", net, (n_in,), np.float32, parallel=True)
    t_warm = time.perf_counter() - t0

    print(json.dumps({
        "time_to_first_step_s": t_first,
        "steady_steps_per_sec": steps / t_steady,
        "serving_warmup_s": t_warm,
        "serving_buckets": len(scache.buckets),
        "compiles": cache.stats["compiles"],
        "disk_hits": cache.stats["disk_hits"],
        "stores": cache.stats["stores"],
        "bytes_read": cache.stats["bytes_read"],
        "bytes_written": cache.stats["bytes_written"],
        **_device_fields(),
    }))


def bench_aot(steps=24, batch=64, n_in=256):
    """Cold vs warm process-start A/B through the persistent executable
    cache: two identical subprocesses share one cache directory — the
    first compiles and persists every executable (train step + every
    serving bucket), the second must start warm (0 compiles, pure
    deserialization)."""
    import os
    import shutil
    import subprocess
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="bench-aot-")
    try:
        def child(tag):
            cmd = [sys.executable, os.path.abspath(__file__), "--aot-child",
                   cache_dir, str(steps), str(batch), str(n_in)]
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=1200, env=dict(os.environ))
            if p.returncode != 0:
                raise RuntimeError(
                    f"{tag} aot child failed:\n{p.stderr[-2000:]}")
            return json.loads(p.stdout.strip().splitlines()[-1])

        cold = child("cold")
        warm = child("warm")
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    return {
        "cold": cold, "warm": warm,
        "cold_start_s": cold["time_to_first_step_s"],
        "warm_start_s": warm["time_to_first_step_s"],
        "first_step_speedup": (cold["time_to_first_step_s"]
                               / max(warm["time_to_first_step_s"], 1e-9)),
        "warmup_speedup": (cold["serving_warmup_s"]
                           / max(warm["serving_warmup_s"], 1e-9)),
        "warm_compiles": warm["compiles"],
        "warm_zero_compiles": warm["compiles"] == 0,
        "steps": steps, "batch": batch, "n_in": n_in,
    }


def main_aot(quick: bool):
    """`--aot` mode: cold/warm subprocess A/B detail to stderr +
    BENCH_aot.json, ONE stdout JSON line.  Fails (exit 1) if the warm
    process performed any compile — that IS the acceptance contract."""
    import os
    try:
        r = (bench_aot(steps=8, batch=32, n_in=64) if quick
             else bench_aot())
    except Exception as e:
        print(json.dumps({"metric": "aot_warm_start_speedup",
                          "value": None, "unit": "x",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[aot] {k} = {v}", file=sys.stderr, flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_aot.json"), "w") as f:
        json.dump(r, f, indent=2)
    print(json.dumps({
        "metric": "aot_warm_start_speedup",
        "value": round(r["first_step_speedup"], 2),
        "unit": "x",
        "cold_start_s": round(r["cold_start_s"], 3),
        "warm_start_s": round(r["warm_start_s"], 3),
        "warmup_speedup": round(r["warmup_speedup"], 2),
        "warm_compiles": r["warm_compiles"],
        "warm_zero_compiles": r["warm_zero_compiles"],
        **{k: r["warm"][k] for k in _DEVICE_KEYS},
    }))
    if not r["warm_zero_compiles"]:
        sys.exit(1)


def quant_child(cache_dir: str, steps: int, batch: int, n_in: int,
                hidden: int):
    """`--quant-child` worker: ONE process's f32-vs-int8 serving A/B.

    Builds a deterministic MLP, calibrates + quantizes it, warms both the
    f32 and the quantized bucket ladders through a BucketedCompileCache
    backed by the persistent executable cache at `cache_dir`, then times
    steady-state serving QPS for each.  Prints one JSON line; the parent
    (`bench_quant`) runs this twice against the same directory — the warm
    run must deserialize every executable (0 compiles), under a quantized
    fingerprint distinct from the f32 one."""
    from deeplearning4j_tpu.compile import (PersistentExecutableCache,
                                            model_fingerprint)
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.quant import (calibrate, parity_check,
                                          quantize_model)
    from deeplearning4j_tpu.serving import BucketedCompileCache
    from deeplearning4j_tpu.train.updaters import Sgd
    import jax

    conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(1e-1))
            .list([DenseLayer(n_out=hidden, activation="relu"),
                   DenseLayer(n_out=hidden, activation="relu"),
                   OutputLayer(n_out=10, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(n_in)).build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    x = rng.randn(batch, n_in).astype(np.float32)
    # train briefly: parity on an untrained net is all near-tied logits,
    # where a single int8 rounding flip misreads as an accuracy loss
    xt = rng.randn(256, n_in).astype(np.float32)
    yt = np.eye(10, dtype=np.float32)[rng.randint(0, 10, 256)]
    for _ in range(8):
        net.fit(xt, yt)
    stats = calibrate(net, [rng.randn(batch, n_in).astype(np.float32)
                            for _ in range(4)], observer="percentile")
    qm = quantize_model(net, calibration=stats)
    x_eval = rng.randn(512, n_in).astype(np.float32)

    cache = PersistentExecutableCache(cache_dir)
    scache = BucketedCompileCache(max_batch=batch, persistent=cache)
    scache.warmup("f32:v1", net, (n_in,), np.float32)
    scache.warmup("int8:v1", qm, (n_in,), np.float32)

    def qps(key, model):
        scache.run(key, model, x)            # touch the exact bucket
        t0 = time.perf_counter()
        for _ in range(steps):
            out = scache.run(key, model, x)
        np.asarray(out)
        return steps * batch / (time.perf_counter() - t0)

    bytes_f32 = sum(l.nbytes
                    for l in jax.tree_util.tree_leaves(net.params_))
    print(json.dumps({
        "qps_f32": qps("f32:v1", net),
        "qps_int8": qps("int8:v1", qm),
        "bytes_f32": bytes_f32,
        "bytes_int8": qm.bytes_resident(),
        "parity_delta": parity_check(net, qm, x_eval)["delta"],
        "fp_f32": model_fingerprint(net),
        "fp_quant": model_fingerprint(qm),
        "compiles": cache.stats["compiles"],
        "disk_hits": cache.stats["disk_hits"],
        "stores": cache.stats["stores"],
        **_device_fields(),
    }))


def bench_quant(steps=200, batch=64, n_in=512, hidden=1024):
    """f32 vs int8 serving A/B plus the quantized warm-restart contract:
    two identical subprocesses share one persistent cache directory — the
    first compiles and persists the f32 AND quantized bucket ladders, the
    second must start warm with zero compiles."""
    import os
    import shutil
    import subprocess
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="bench-quant-")
    try:
        def child(tag):
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--quant-child", cache_dir, str(steps), str(batch),
                   str(n_in), str(hidden)]
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=1200, env=dict(os.environ))
            if p.returncode != 0:
                raise RuntimeError(
                    f"{tag} quant child failed:\n{p.stderr[-2000:]}")
            return json.loads(p.stdout.strip().splitlines()[-1])

        cold = child("cold")
        warm = child("warm")
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)
    qps_ratio = warm["qps_int8"] / max(warm["qps_f32"], 1e-9)
    bytes_ratio = cold["bytes_f32"] / max(cold["bytes_int8"], 1)
    tpb_ratio = qps_ratio * bytes_ratio      # throughput per byte resident
    return {
        "cold": cold, "warm": warm,
        "qps_speedup": qps_ratio,
        "bytes_resident_ratio": bytes_ratio,
        "throughput_per_byte_ratio": tpb_ratio,
        "parity_delta": cold["parity_delta"],
        "fp_distinct": cold["fp_quant"] != cold["fp_f32"],
        "fp_stable": warm["fp_quant"] == cold["fp_quant"],
        "warm_compiles": warm["compiles"],
        "warm_zero_compiles": warm["compiles"] == 0,
        "steps": steps, "batch": batch, "n_in": n_in, "hidden": hidden,
    }


def main_quant(quick: bool):
    """`--quant` mode: A/B detail to stderr + BENCH_quant.json, ONE
    stdout JSON line.  Gates (exit 1 on any failure): >=2x throughput per
    byte resident OR >=1.5x QPS, parity delta <=1%, warm restart with
    zero compiles, quantized fingerprint distinct from f32."""
    import os
    try:
        r = (bench_quant(steps=25, batch=32, n_in=128, hidden=256)
             if quick else bench_quant())
    except Exception as e:
        print(json.dumps({"metric": "quant_throughput_per_byte_ratio",
                          "value": None, "unit": "x",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[quant] {k} = {v}", file=sys.stderr, flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_quant.json"), "w") as f:
        json.dump(r, f, indent=2)
    perf_gate = (r["throughput_per_byte_ratio"] >= 2.0
                 or r["qps_speedup"] >= 1.5)
    gates = {
        "perf": perf_gate,
        "parity": r["parity_delta"] <= 0.01,
        "warm_zero_compiles": r["warm_zero_compiles"],
        "fp_distinct": r["fp_distinct"] and r["fp_stable"],
    }
    print(json.dumps({
        "metric": "quant_throughput_per_byte_ratio",
        "value": round(r["throughput_per_byte_ratio"], 2),
        "unit": "x",
        "qps_speedup": round(r["qps_speedup"], 3),
        "bytes_resident_ratio": round(r["bytes_resident_ratio"], 2),
        "parity_delta": round(r["parity_delta"], 5),
        "warm_compiles": r["warm_compiles"],
        "gates": gates,
        "pass": all(gates.values()),
        **{k: r["warm"][k] for k in _DEVICE_KEYS},
    }))
    if not all(gates.values()):
        sys.exit(1)


def bench_autotune(n_batches=64, batch=64, n_in=256, quick=False):
    """Schedule-autotuner search over the execution-config space on the
    pipeline fixture, then persist → load → re-apply the winner and
    re-measure to confirm the tuned throughput survives a restart."""
    import tempfile

    from deeplearning4j_tpu.compile import (ScheduleAutotuner, load_schedule,
                                            save_schedule)

    make_it, make_net, nz = _pipeline_fixture(n_batches, batch, n_in)

    def measure(sch):
        net = make_net()
        net.set_normalizer(nz)
        net.apply_schedule(sch)
        it = sch.wrap_iterator(make_it())
        try:
            t = _time_steps(lambda: net.fit(it, epochs=1),
                            n_warmup=1, n_steps=1,
                            sync_fn=lambda: float(net.score()))
        finally:
            it.close()
        return n_batches / t

    space = ({"fused_steps": [1, 8], "prefetch_depth": [2],
              "donation": [True]} if quick
             else {"fused_steps": [1, 4, 16], "prefetch_depth": [1, 2, 4],
                   "donation": [True, False]})
    tuner = ScheduleAutotuner(measure, space=space,
                              refine_rounds=0 if quick else 1)
    best = tuner.search()

    sched_dir = tempfile.mkdtemp(prefix="bench-autotune-")
    path = save_schedule(best, sched_dir, name="bench")
    loaded = load_schedule(sched_dir, name="bench")
    remeasured = measure(loaded)
    return {
        "best": best.to_json(),
        "best_steps_per_sec": best.steps_per_sec,
        "baseline_steps_per_sec": best.meta["baseline_steps_per_sec"],
        "speedup_vs_baseline": (best.steps_per_sec
                                / max(best.meta["baseline_steps_per_sec"],
                                      1e-9)),
        "evaluated": best.meta["evaluated"],
        "schedule_path": path,
        "remeasured_steps_per_sec": remeasured,
        "remeasure_ratio": remeasured / max(best.steps_per_sec, 1e-9),
        "n_batches": n_batches, "batch": batch,
    }


def main_autotune(quick: bool):
    """`--autotune` mode: search detail to stderr + BENCH_autotune.json,
    ONE stdout JSON line."""
    import os
    try:
        r = bench_autotune(n_batches=16, batch=32, n_in=64, quick=True) \
            if quick else bench_autotune()
    except Exception as e:
        print(json.dumps({"metric": "autotune_steps_per_sec",
                          "value": None, "unit": "steps/sec",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[autotune] {k} = {v}", file=sys.stderr, flush=True)
    import os
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_autotune.json"), "w") as f:
        json.dump(r, f, indent=2)
    print(json.dumps({
        "metric": "autotune_steps_per_sec",
        "value": round(r["best_steps_per_sec"], 1),
        "unit": "steps/sec",
        "speedup_vs_baseline": round(r["speedup_vs_baseline"], 3),
        "fused_steps": r["best"]["fused_steps"],
        "prefetch_depth": r["best"]["prefetch_depth"],
        "donation": r["best"]["donation"],
        "evaluated": r["evaluated"],
        "remeasure_ratio": round(r["remeasure_ratio"], 3),
        **_device_fields(),
    }))


def _bench_pallas_conformance(quick: bool):
    """Per-kernel conformance vs the jnp reference — runs everywhere (the
    Pallas impls go through interpret mode off-accelerator).  Returns
    {kernel: max_abs_err or bitwise bool}."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas import attention as pa
    from deeplearning4j_tpu.ops.pallas import dispatch as kd
    from deeplearning4j_tpu.ops.pallas import matmul as pm
    from deeplearning4j_tpu.ops.pallas.tiles import TileConfig

    interp = kd.interpret_mode()
    att_tile = TileConfig(block_q=32, block_kv=64)
    mm_tile = TileConfig(block_m=8, block_n=128, block_k=128)
    rng = np.random.RandomState(0)
    out = {}

    # attention: ragged causal+masked (query 0 kept attendable — fully
    # masked rows are mathematically undefined)
    B, H, T, S, D = 1, 2, 100, 72, 64
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    keep = (rng.rand(B, S) > 0.3).astype(np.float32)
    keep[:, 0] = 1.0
    mask = jnp.asarray(keep)
    got = pa.flash_attention(q, k, v, mask=mask, causal=True,
                             tile=att_tile, interpret=interp)
    want = pa.attention_reference(q, k, v, mask=mask, causal=True)
    out["attention_max_err"] = float(jnp.max(jnp.abs(got - want)))

    # int8 matmul: the integer contraction must be BITWISE under tiling
    M, K, N = 37, 70, 45
    xq = jnp.asarray(rng.randint(-128, 128, (M, K)), jnp.int8)
    wq = jnp.asarray(rng.randint(-128, 128, (K, N)), jnp.int8)
    ws = jnp.asarray(rng.rand(N) * 0.1 + 1e-3, jnp.float32)
    got = pm.int8_matmul(xq, wq, ws, tile=mm_tile, interpret=interp)
    want = pm.int8_matmul_reference(xq, wq, ws)
    out["int8_matmul_bitwise"] = bool(jnp.all(got == want))

    # bf16/f32-activation x int8-weight matmul
    x = jnp.asarray(rng.randn(M, K), jnp.float32)
    got = pm.q_matmul(x, wq, ws, tile=mm_tile, interpret=interp)
    want = pm.q_matmul_reference(x, wq, ws)
    out["q_matmul_max_err"] = float(jnp.max(jnp.abs(got - want)))

    # fused dense epilogue
    w = jnp.asarray(rng.randn(K, N) * 0.1, jnp.float32)
    b = jnp.asarray(rng.randn(N) * 0.1, jnp.float32)
    got = pm.fused_dense(x, w, bias=b, activation="gelu",
                         tile=mm_tile, interpret=interp)
    want = pm.fused_dense_reference(x, w, bias=b, activation="gelu")
    out["fused_dense_max_err"] = float(jnp.max(jnp.abs(got - want)))

    out["pass"] = (out["int8_matmul_bitwise"]
                   and out["attention_max_err"] < 2e-5
                   and out["q_matmul_max_err"] < 2e-5
                   and out["fused_dense_max_err"] < 2e-5)
    return out


def _bench_pallas_ab(quick: bool):
    """Accelerator-only timed A/B: each Pallas kernel vs the XLA-fused
    jnp reference, both jitted, chained dispatch + block_until_ready."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.pallas import attention as pa
    from deeplearning4j_tpu.ops.pallas import dispatch as kd
    from deeplearning4j_tpu.ops.pallas import matmul as pm

    iters = 10 if quick else 50
    rng = np.random.RandomState(1)

    def timed(fn, *args):
        jf = jax.jit(fn)
        jf(*args).block_until_ready()          # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            y = jf(*args)
        y.block_until_ready()
        return (time.perf_counter() - t0) / iters

    speedups = {}

    # flash attention vs XLA-fused reference (causal, long seq)
    B, H, T, D = (1, 4, 2048, 64) if quick else (4, 8, 2048, 64)
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
    tile = kd.get_tile("attention")
    t_ref = timed(lambda a, b, c: pa.attention_reference(
        a, b, c, causal=True), q, k, v)
    t_pal = timed(lambda a, b, c: pa.flash_attention(
        a, b, c, causal=True, tile=tile, interpret=False), q, k, v)
    speedups["attention"] = t_ref / max(t_pal, 1e-12)

    # int8-native matmul vs dequantize-then-f32-dot
    M = K = N = 1024 if quick else 4096
    xq = jnp.asarray(rng.randint(-128, 128, (M, K)), jnp.int8)
    wq = jnp.asarray(rng.randint(-128, 128, (K, N)), jnp.int8)
    ws = jnp.asarray(rng.rand(N) * 0.1 + 1e-3, jnp.float32)
    tile = kd.get_tile("int8_matmul")

    def dequant_first(a, b, s):                # the pre-fix lowering
        return (a.astype(jnp.float32) @ (b.astype(jnp.float32)
                                         * s[None, :]))
    t_ref = timed(dequant_first, xq, wq, ws)
    t_pal = timed(lambda a, b, s: pm.int8_matmul(
        a, b, s, tile=tile, interpret=False), xq, wq, ws)
    speedups["int8_matmul"] = t_ref / max(t_pal, 1e-12)

    # fused dense bias+gelu epilogue vs XLA's fusion
    rows = 2048 if quick else 8192
    x = jnp.asarray(rng.randn(rows, K), jnp.bfloat16)
    w = jnp.asarray(rng.randn(K, N) * 0.02, jnp.bfloat16)
    b = jnp.asarray(rng.randn(N) * 0.02, jnp.float32)
    tile = kd.get_tile("fused_dense")
    t_ref = timed(lambda a, c, d: pm.fused_dense_reference(
        a, c, bias=d, activation="gelu"), x, w, b)
    t_pal = timed(lambda a, c, d: pm.fused_dense(
        a, c, bias=d, activation="gelu", tile=tile, interpret=False),
        x, w, b)
    speedups["fused_dense"] = t_ref / max(t_pal, 1e-12)
    return speedups


def bench_pallas(quick=False):
    """The Pallas fused-kernel tier bench: conformance (always), timed A/B
    vs XLA baselines (accelerator only), tile search->persist->replay, and
    the AOT cache-key proof (warm restart compiles nothing; a different
    tile schedule is a distinct entry)."""
    import os
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.compile.autotune import autotune_tiles
    from deeplearning4j_tpu.compile.fingerprint import \
        kernel_tier_fingerprint
    from deeplearning4j_tpu.compile.persistent import \
        PersistentExecutableCache
    from deeplearning4j_tpu.compile.step_cache import step_function
    from deeplearning4j_tpu.ops.pallas import dispatch as kd
    from deeplearning4j_tpu.ops.pallas import matmul as pm
    from deeplearning4j_tpu.ops.pallas.tiles import TileConfig, shape_class

    kd.reset()
    on_accel = kd.on_accelerator()
    r = {"backend": jax.default_backend(), "accelerator": on_accel,
         "simulated": not on_accel, "quick": quick}

    r["conformance"] = _bench_pallas_conformance(quick)

    if on_accel:
        r["speedups"] = _bench_pallas_ab(quick)
        r["best_speedup"] = max(r["speedups"].values())
    else:
        r["speedups"] = None                  # CPU: conformance leg only
        r["best_speedup"] = None

    # --- tile search -> persist -> replay --------------------------------
    M = K = N = 1024 if quick else 4096
    sc = shape_class(m=M, k=K, n=N)
    calls = {"n": 0}
    if on_accel:
        rng = np.random.RandomState(2)
        xq = jnp.asarray(rng.randint(-128, 128, (M, K)), jnp.int8)
        wq = jnp.asarray(rng.randint(-128, 128, (K, N)), jnp.int8)
        ws = jnp.asarray(rng.rand(N) * 0.1 + 1e-3, jnp.float32)

        def measure(cfg):
            calls["n"] += 1
            f = jax.jit(lambda a, b, s: pm.int8_matmul(
                a, b, s, tile=cfg, interpret=False))
            f(xq, wq, ws).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(3 if quick else 10):
                y = f(xq, wq, ws)
            y.block_until_ready()
            return 1.0 / max(time.perf_counter() - t0, 1e-12)
    else:
        def measure(cfg):                     # analytic stand-in (CPU)
            calls["n"] += 1
            return -(abs(cfg.block_m - 256) + abs(cfg.block_n - 256)
                     + abs(cfg.block_k - 1024))

    tdir = tempfile.mkdtemp(prefix="bench-pallas-tiles-")
    try:
        t0 = time.perf_counter()
        tile1, info1 = autotune_tiles("int8_matmul", sc, measure, tdir)
        search_ms = (time.perf_counter() - t0) * 1000.0
        n_search = calls["n"]
        tile2, info2 = autotune_tiles("int8_matmul", sc, measure, tdir)
        r["tile_search"] = {
            "shape_class": sc,
            "winner": tile1.to_json(),
            "evaluated": info1["evaluated"],
            "search_ms": round(search_ms, 1),
            "replay_source": info2["source"],
            "replay_measure_calls": calls["n"] - n_search,
            "replay_matches": tile2 == tile1,
        }
    finally:
        shutil.rmtree(tdir, ignore_errors=True)

    # --- AOT proof: warm restart compiles nothing; a different tile is a
    # distinct entry (kernel_tier_fingerprint splits the key) ------------
    rng = np.random.RandomState(3)
    xq = jnp.asarray(rng.randint(-128, 128, (64, 128)), jnp.int8)
    wq = jnp.asarray(rng.randint(-128, 128, (128, 128)), jnp.int8)
    ws = jnp.asarray(rng.rand(128) * 0.1 + 1e-3, jnp.float32)
    interp = kd.interpret_mode()
    mm_tile = kd.get_tile("int8_matmul")

    def body(a, b, s):
        return pm.int8_matmul(a, b, s, tile=mm_tile, interpret=interp)

    key_base = lambda: {"bench": "pallas",
                        "tier": kernel_tier_fingerprint()}
    cdir = tempfile.mkdtemp(prefix="bench-pallas-aot-")
    try:
        f_cold = step_function(body, key_base=key_base,
                               cache=PersistentExecutableCache(cdir))
        f_cold(xq, wq, ws)
        f_warm = step_function(body, key_base=key_base,
                               cache=PersistentExecutableCache(cdir))
        f_warm(xq, wq, ws)
        kd.set_tile("int8_matmul", TileConfig(block_m=128, block_n=128,
                                              block_k=256))
        f_retuned = step_function(body, key_base=key_base,
                                  cache=PersistentExecutableCache(cdir))
        f_retuned(xq, wq, ws)
        r["aot"] = {
            "cold_compiles": f_cold._cache_size(),
            "warm_compiles": f_warm._cache_size(),
            "retuned_tile_compiles": f_retuned._cache_size(),
        }
    finally:
        kd.reset()
        shutil.rmtree(cdir, ignore_errors=True)
    return r


def main_pallas(quick: bool):
    """`--pallas` mode: detail to stderr + BENCH_pallas.json, ONE stdout
    JSON line.  Gates (exit 1 on any failure): conformance, tile replay
    from the persisted table with zero re-search, warm AOT restart with
    zero compiles + distinct entry for a retuned tile, and — on an
    accelerator only — >=1.15x vs the XLA baseline on >=1 kernel (on CPU
    the perf gate is skipped and the line carries `"simulated": true`)."""
    import os
    try:
        r = bench_pallas(quick=quick)
    except Exception as e:
        print(json.dumps({"metric": "pallas_best_kernel_speedup",
                          "value": None, "unit": "x",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[pallas] {k} = {v}", file=sys.stderr, flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_pallas.json"), "w") as f:
        json.dump(r, f, indent=2)
    gates = {
        "conformance": r["conformance"]["pass"],
        "tile_replay_zero_research": (
            r["tile_search"]["replay_source"] == "cache"
            and r["tile_search"]["replay_measure_calls"] == 0
            and r["tile_search"]["replay_matches"]),
        "aot_warm_zero_compiles": r["aot"]["warm_compiles"] == 0,
        "aot_tile_splits_key": r["aot"]["retuned_tile_compiles"] == 1,
        "perf": (r["best_speedup"] >= 1.15 if r["accelerator"]
                 else True),   # CPU: simulated, conformance-only
    }
    print(json.dumps({
        "metric": "pallas_best_kernel_speedup",
        "value": (round(r["best_speedup"], 3)
                  if r["best_speedup"] is not None else None),
        "unit": "x",
        "simulated": r["simulated"],
        "speedups": ({k: round(v, 3) for k, v in r["speedups"].items()}
                     if r["speedups"] else None),
        "tile_search_evaluated": r["tile_search"]["evaluated"],
        "tile_replay_source": r["tile_search"]["replay_source"],
        "warm_compiles": r["aot"]["warm_compiles"],
        "gates": gates,
        "pass": all(gates.values()),
        **_device_fields(),
    }))
    if not all(gates.values()):
        sys.exit(1)


def bench_decode(n_seqs=48, max_seq_len=256, max_decode_batch=8,
                 num_blocks=192, vocab=96, d_model=64, n_heads=4,
                 seed=0):
    """Sequence-length-skewed decode flood + paged-vs-contiguous KV A/B.

    One `DecodeEngine` with int8 paged KV serves `n_seqs` prompts whose
    lengths are skewed across every prefill bucket (short head, long
    tail).  Measured: tokens/sec and inter-token p99 across the flood,
    fresh XLA compiles after warmup (must be zero — admits/retires and
    ragged lengths never change a traced shape), peak KV pages vs peak
    concurrent sequences.  The memory A/B compares measured bytes per
    concurrent sequence against the contiguous-f32 baseline every
    pre-paged serving stack pays: a `max_seq_len` * heads * head_dim *
    2(K,V) * 4(f32) reservation per sequence regardless of actual
    length.  Parity: int8-KV vs f32-KV paged attention on the engine's
    OWN prefill KV (not synthetic noise), relative L2."""
    from deeplearning4j_tpu.ops.pallas import paged_attention as pa
    from deeplearning4j_tpu.ops.quant_kernels import quantize_tensor
    from deeplearning4j_tpu.serving.decode import (DecodeEngine,
                                                   TinyDecodeModel)

    rng = np.random.default_rng(seed)
    model = TinyDecodeModel(vocab=vocab, d_model=d_model,
                            n_heads=n_heads, seed=seed)
    eng = DecodeEngine(model, num_blocks=num_blocks,
                       max_seq_len=max_seq_len,
                       max_decode_batch=max_decode_batch,
                       kv_dtype="int8", model_label="bench")
    try:
        warm = eng.warmup()
        fresh_before = eng.fresh_compiles()

        # skewed lengths: most prompts short, a long tail touching the
        # top buckets — every bucket in the ladder gets traffic
        max_prompt = max_seq_len - 24
        pool = [3, 5, 7, 9, 14, 20, 33, 60]
        pool = [p for p in pool if p < max_prompt] + [max_prompt]
        weights = np.array([4.0] * (len(pool) - 1) + [1.0])
        lens = rng.choice(pool, size=n_seqs, p=weights / weights.sum())
        t0 = time.monotonic()
        futs = [eng.submit(rng.integers(1, vocab, size=int(n)),
                           max_new_tokens=int(rng.integers(4, 20)))
                for n in lens]
        peak_active = peak_blocks = 0
        pending = list(futs)
        while pending:
            peak_active = max(peak_active, eng.cache.active_sequences)
            peak_blocks = max(peak_blocks, eng.cache.blocks_in_use)
            pending = [f for f in pending if not f.done()]
            time.sleep(0.002)
        outs = [f.result(timeout=60) for f in futs]
        wall_s = time.monotonic() - t0
        tokens = int(sum(len(o) for o in outs))
        fresh_after = eng.fresh_compiles()
        p99 = eng.instruments.inter_token("bench").percentiles(
            (50, 99))

        # ---- memory A/B: measured paged-int8 vs contiguous-f32 ----
        head_dim = model.head_dim
        contig_f32_bytes = max_seq_len * n_heads * head_dim * 2 * 4
        paged_bytes = (peak_blocks * eng.cache.bytes_per_block
                       / max(peak_active, 1))
        density_ratio = contig_f32_bytes / max(paged_bytes, 1.0)

        # ---- parity: int8-KV vs f32-KV attention on real prefill KV ----
        import jax.numpy as jnp
        T = min(64, max_prompt)
        prompt = rng.integers(1, vocab, size=(1, T)).astype(np.int32)
        _, k, v = model.prefill(jnp.asarray(prompt),
                                jnp.asarray([T], np.int32))
        k = np.asarray(k)[0]
        v = np.asarray(v)[0]                      # [T, H, D]
        page = eng.page_size
        n_pages = -(-T // page)
        shape = (n_pages, page, n_heads, head_dim)
        kf = np.zeros(shape, np.float32)
        vf = np.zeros(shape, np.float32)
        kf.reshape(-1, n_heads, head_dim)[:T] = k
        vf.reshape(-1, n_heads, head_dim)[:T] = v
        k8 = np.zeros(shape, np.int8)
        v8 = np.zeros(shape, np.int8)
        ks = np.ones(shape[:3], np.float32)
        vs = np.ones(shape[:3], np.float32)
        for p in range(n_pages):
            for s in range(page):
                qt = quantize_tensor(kf[p, s], axis=0)
                k8[p, s] = np.asarray(qt.q)
                ks[p, s] = np.asarray(qt.scale).reshape(-1)
                qt = quantize_tensor(vf[p, s], axis=0)
                v8[p, s] = np.asarray(qt.q)
                vs[p, s] = np.asarray(qt.scale).reshape(-1)
        q1 = rng.standard_normal((1, n_heads, head_dim)).astype(
            np.float32)
        bt = np.arange(n_pages, dtype=np.int32)[None, :]
        sl = np.array([T], np.int32)
        a_f32 = np.asarray(pa.paged_attention_reference(
            q1, kf, vf, bt, sl))
        a_i8 = np.asarray(pa.paged_attention_reference(
            q1, k8, v8, bt, sl, k_scales=ks, v_scales=vs))
        parity = float(np.linalg.norm(a_i8 - a_f32)
                       / max(np.linalg.norm(a_f32), 1e-12))
        stats = eng.stats()
    finally:
        eng.shutdown(drain=False)
    return {
        "n_seqs": n_seqs, "max_seq_len": max_seq_len,
        "max_decode_batch": max_decode_batch, "num_blocks": num_blocks,
        "prompt_lens": sorted(set(int(n) for n in lens)),
        "tokens": tokens, "wall_s": wall_s,
        "tokens_per_sec": tokens / max(wall_s, 1e-9),
        "inter_token_p50_ms": p99["p50"],
        "inter_token_p99_ms": p99["p99"],
        "warmup_programs": warm,
        "fresh_compiles_after_warmup": fresh_after - fresh_before,
        "peak_concurrent_sequences": peak_active,
        "peak_kv_blocks": peak_blocks,
        "paged_int8_bytes_per_seq": paged_bytes,
        "contiguous_f32_bytes_per_seq": contig_f32_bytes,
        "seqs_per_byte_ratio": density_ratio,
        "int8_attention_parity": parity,
        "engine_stats": stats,
    }


def main_decode(quick: bool):
    """`--decode` mode: flood detail to stderr + BENCH_decode.json, ONE
    stdout JSON line.  Gates (exit 1 on any failure): zero fresh compiles
    after warmup across the skewed flood, tokens/sec floor, inter-token
    p99 bound, paged-int8 >=1.5x concurrent sequences per HBM byte vs
    contiguous f32 at <=1% attention parity."""
    import os
    try:
        r = (bench_decode(n_seqs=12, max_seq_len=64, max_decode_batch=4,
                          num_blocks=64)
             if quick else bench_decode())
    except Exception as e:
        print(json.dumps({"metric": "decode_tokens_per_sec",
                          "value": None, "unit": "tokens/sec",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[decode] {k} = {v}", file=sys.stderr, flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_decode.json"), "w") as f:
        json.dump(r, f, indent=2)
    gates = {
        "zero_recompile": r["fresh_compiles_after_warmup"] == 0,
        "throughput": r["tokens_per_sec"] >= 5.0,
        "inter_token_p99": r["inter_token_p99_ms"] <= 1000.0,
        "int8_density": r["seqs_per_byte_ratio"] >= 1.5,
        "parity": r["int8_attention_parity"] <= 0.01,
    }
    print(json.dumps({
        "metric": "decode_tokens_per_sec",
        "value": round(r["tokens_per_sec"], 1),
        "unit": "tokens/sec",
        "inter_token_p99_ms": round(r["inter_token_p99_ms"], 3),
        "fresh_compiles_after_warmup": r["fresh_compiles_after_warmup"],
        "seqs_per_byte_ratio": round(r["seqs_per_byte_ratio"], 2),
        "int8_attention_parity": round(r["int8_attention_parity"], 5),
        "gates": gates,
        "pass": all(gates.values()),
        **_device_fields(),
    }))
    if not all(gates.values()):
        sys.exit(1)


def arbiter_child(workdir: str, phase: str):
    """`--arbiter-child` subprocess for the --arbiter chaos episode (the
    bench twin of tests/arbiter_worker.py).

    Phase ``run``: build a seeded net + CheckpointManager, a
    LocalElasticGang over slices [0, 1], a ModelFleet sharing `workdir`,
    and a SliceArbiter with a REAL `HandoffChaos(target="arbiter",
    mode="kill", at_phase="shrink")` hooked in — `to_serving()` journals
    the phase-1 intent and the chaos hook `os._exit(9)`s the process
    with the record durable and ZERO side effects executed.

    Phase ``recover``: a fresh process over the SAME journal — the
    arbiter constructor replays the in-flight handoff (the marker keeps
    the chaos one-shot), then writes `recover_result.json` so the parent
    can assert single ownership and a counted replay."""
    import os
    import numpy as np
    from deeplearning4j_tpu.monitor.registry import MetricsRegistry
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.serving import ModelFleet
    from deeplearning4j_tpu.serving.slo import ArbiterPolicy
    from deeplearning4j_tpu.train.arbiter import (LocalElasticGang,
                                                  SliceArbiter)
    from deeplearning4j_tpu.train.resilience import CheckpointManager
    from deeplearning4j_tpu.train.updaters import Sgd
    from deeplearning4j_tpu.utils.chaos import HandoffChaos

    journal = os.path.join(workdir, "journal.json")
    marker = os.path.join(workdir, "chaos_once")
    conf = (NeuralNetConfiguration.builder().seed(11).updater(Sgd(0.1))
            .list([DenseLayer(n_out=8, activation="tanh"),
                   OutputLayer(n_out=2, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    manager = CheckpointManager(os.path.join(workdir, "ckpt"),
                                keep_last=50, save_every_steps=None)
    rng = np.random.RandomState(3)
    x = rng.randn(6, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x[:, 0] > 0).astype(int)]
    net.fit(x, y)               # the shrink checkpoint is non-trivial
    gang = LocalElasticGang(net, manager, slices=[0, 1])
    fleet = ModelFleet(max_resident=1, n_slices=1,
                       cache_dir=os.path.join(workdir, "exec-cache"),
                       registry_=MetricsRegistry())
    arb = SliceArbiter(journal, training=gang, fleet=fleet,
                       policy=ArbiterPolicy(min_training_slices=1),
                       registry_=MetricsRegistry())
    if phase == "run":
        arb.chaos = HandoffChaos(target="arbiter", mode="kill",
                                 at_phase="shrink", marker=marker)
        arb.to_serving()                # chaos kills us after phase-1
        print("UNREACHABLE: chaos did not fire", flush=True)
        sys.exit(3)
    # phase == "recover": the constructor already replayed (recover=True)
    result = {
        "recovered": arb.recovered,
        "describe": arb.describe(),
        "gang_held": gang.held_slices(),
        "ckpt_latest": manager.latest_step(),
        "marker_exists": os.path.exists(marker),
    }
    with open(os.path.join(workdir, "recover_result.json"), "w") as f:
        json.dump(result, f)


def bench_arbiter(quick=False):
    """`--arbiter` gate: preemption-safe train/serve slice handoffs
    (train/arbiter.py + docs/robustness.md "Pod arbiter").

    A compressed diurnal pressure trace with a 10x flash spike drives
    `SliceArbiter.maybe_rebalance` over a 3-slice pod shared by a
    LocalElasticGang (training a real net through the real blocking-
    checkpoint shrink/readmit path) and a ModelFleet serving a
    hi-priority model off the shared persistent AOT cache.  An
    uninterrupted reference net trains on the IDENTICAL batch stream.

    Gates: >= 2 full handoff cycles; zero hi-priority SLO breaches at
    peak; per-step training loss bitwise-identical to the uninterrupted
    run (checked at every shrink/grow boundary and every tick) and final
    params bitwise-equal; `fresh_compiles == 0` on BOTH sides of every
    handoff (fleet AOT cache delta == 0, the gang's jitted train step
    never re-traces); plus one REAL mid-handoff arbiter kill in a child
    process (`--arbiter-child`, HandoffChaos `os._exit(9)` right after
    the phase-1 journal commit) recovered by a relaunched arbiter
    replaying the journal with the slice single-owned."""
    import os
    import shutil
    import subprocess
    import tempfile
    import numpy as np
    from deeplearning4j_tpu.monitor.registry import MetricsRegistry
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.serving import LatencySLO, ModelFleet
    from deeplearning4j_tpu.serving.slo import ArbiterPolicy
    from deeplearning4j_tpu.train.arbiter import (LocalElasticGang,
                                                  SliceArbiter)
    from deeplearning4j_tpu.train.resilience import CheckpointManager
    from deeplearning4j_tpu.train.updaters import Sgd

    n_in = 12
    hi_slo_ms = 1500.0
    base_p, peak_p = 0.3, 3.0           # 10x flash spike
    cycles = 2 if quick else 3
    base_len, spike_len = (3, 4) if quick else (5, 6)
    burst = 4 if quick else 8           # hi requests per peak tick

    def make_net(seed):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Sgd(0.05))
                .list([DenseLayer(n_out=24, activation="relu"),
                       OutputLayer(n_out=3, loss="mcxent",
                                   activation="softmax")])
                .set_input_type(InputType.feed_forward(n_in)).build())
        return MultiLayerNetwork(conf).init()

    # diurnal trace: lull -> flash spike -> lull, repeated
    trace = []
    for _ in range(cycles):
        trace += [base_p] * base_len + [peak_p] * spike_len
    trace += [base_p] * (base_len + 1)  # final lull reclaims the slice

    work_dir = tempfile.mkdtemp(prefix="bench-arbiter-")
    try:
        journal = os.path.join(work_dir, "journal.json")
        # the arbitrated net and the uninterrupted reference: same seed,
        # same batch stream — the handoffs are the ONLY difference
        net, ref = make_net(21), make_net(21)
        rng = np.random.RandomState(5)
        batches = []
        for _ in range(len(trace)):
            x = rng.randn(16, n_in).astype(np.float32)
            y = np.eye(3, dtype=np.float32)[
                (np.abs(x[:, 0]) * 2.9).astype(int) % 3]
            batches.append((x, y))

        manager = CheckpointManager(os.path.join(work_dir, "ckpt"),
                                    keep_last=100, save_every_steps=None)
        gang = LocalElasticGang(net, manager, slices=[0, 1, 2])
        fleet = ModelFleet(max_resident=2, n_slices=1, max_batch=8,
                           batch_timeout_ms=1.0,
                           cache_dir=os.path.join(work_dir, "exec-cache"),
                           registry_=MetricsRegistry())
        fleet.deploy("hi", make_net(1001),
                     slo=LatencySLO(target_p99_ms=hi_slo_ms, priority=10),
                     warm=True)
        policy = ArbiterPolicy(grant_at_forecast=1.5,
                               return_below_forecast=0.5,
                               min_training_slices=1, max_fleet_leases=1,
                               drain_timeout_s=2.0, cooldown_s=0.0)
        arb = SliceArbiter(journal, training=gang, fleet=fleet,
                           policy=policy, registry_=MetricsRegistry())
        fleet.attach_arbiter(arb)

        # pre-warm the request shape so peak traffic (and the leased
        # slice's replicas) runs entirely off the warm AOT cache
        req_x = np.random.RandomState(9).rand(4, n_in).astype(np.float32)
        for _ in range(2):
            fleet.output("hi", req_x, deadline_ms=60_000.0, timeout=120)

        # first step pays the one train-step trace+compile on each net;
        # from here both jit caches must be frozen across every handoff
        net.fit(*batches[0])
        ref.fit(*batches[0])
        step_fn = net._get_train_step()
        train_cache0 = step_fn._cache_size()

        boundaries = []
        loss_mismatch_ticks = []
        hi_lat_ms, hi_breaches, hi_requests = [], 0, 0
        to_serving = to_training = 0
        for t, p in enumerate(trace):
            if t > 0:                   # tick 0 trained above (warmup)
                net.fit(*batches[t])
                ref.fit(*batches[t])
            loss_n, loss_r = net.score(), ref.score()
            if loss_n != loss_r:        # bitwise: exact float equality
                loss_mismatch_ticks.append(t)
            cache_before = fleet.cache.stats["compiles"]
            rec = arb.maybe_rebalance(pressure=p)
            if rec is not None:
                serving_fresh = (fleet.cache.stats["compiles"]
                                 - cache_before)
                cur_step = net._get_train_step()
                train_fresh = (cur_step._cache_size() - train_cache0
                               if cur_step is step_fn else -1)
                if rec["direction"] == "to_serving":
                    to_serving += 1
                else:
                    to_training += 1
                boundaries.append({
                    "tick": t, "direction": rec["direction"],
                    "slice": rec["slice"],
                    "loss": loss_n, "ref_loss": loss_r,
                    "bitwise": loss_n == loss_r,
                    "serving_fresh_compiles": serving_fresh,
                    "training_fresh_compiles": train_fresh,
                    "gang_world": gang.world,
                    "gang_generation": gang.generation,
                })
            if p >= policy.grant_at_forecast:       # peak: hi flood
                for _ in range(burst):
                    hi_requests += 1
                    t0 = time.perf_counter()
                    try:
                        fleet.output("hi", req_x, deadline_ms=60_000.0,
                                     timeout=120)
                        lat = (time.perf_counter() - t0) * 1000.0
                        hi_lat_ms.append(lat)
                        if lat > hi_slo_ms:
                            hi_breaches += 1
                    except Exception:
                        hi_breaches += 1

        hi_member = fleet.member("hi")
        hi_p99 = hi_member.latency.percentiles((99,))["p99"]
        tracker_breaches = hi_member.tracker.breaches_total
        import jax
        params_equal = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree_util.tree_leaves(net.params_),
                            jax.tree_util.tree_leaves(ref.params_)))
        end_step = net._get_train_step()
        train_fresh_total = (end_step._cache_size() - train_cache0
                             if end_step is step_fn else -1)
        final = arb.describe()
        fleet.shutdown()

        # ---- chaos episode: REAL kill between journal phases ----
        chaos_dir = os.path.join(work_dir, "chaos")
        os.makedirs(chaos_dir, exist_ok=True)
        here = os.path.dirname(os.path.abspath(__file__))
        env = dict(os.environ)
        env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
        # this process holds the device and a chip takes one process, so
        # the journal-replay children run on the CPU
        env["JAX_PLATFORMS"] = "cpu"
        print("[arbiter] journal-replay children run with "
              "JAX_PLATFORMS=cpu", file=sys.stderr, flush=True)

        def child(phase):
            return subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--arbiter-child", chaos_dir, phase],
                cwd=here, env=env, capture_output=True, text=True,
                timeout=300)

        run = child("run")
        with open(os.path.join(chaos_dir, "journal.json")) as f:
            killed_state = json.load(f)["state"]
        recover = child("recover")
        rec_result = {}
        rec_path = os.path.join(chaos_dir, "recover_result.json")
        if os.path.exists(rec_path):
            with open(rec_path) as f:
                rec_result = json.load(f)
        recovered = rec_result.get("recovered") or {}
        chaos = {
            "run_rc": run.returncode,                       # want 9
            "journal_phase_after_kill":
                (killed_state.get("handoff") or {}).get("phase"),
            "lease_after_kill":
                killed_state.get("leases", {}).get("1"),
            "recover_rc": recover.returncode,
            "outcome": recovered.get("outcome"),
            "replays": (rec_result.get("describe") or {}).get("replays"),
            "single_owned": (
                (rec_result.get("describe") or {}).get("leases", {})
                .get("1") == "serving"
                and 1 not in (rec_result.get("gang_held") or [1])),
            "marker_exists": rec_result.get("marker_exists"),
            "stderr_tail": (run.stderr or "")[-300:]
            if run.returncode != 9 else "",
        }
        return {
            "ticks": len(trace),
            "base_pressure": base_p,
            "peak_pressure": peak_p,
            "spike_ratio": peak_p / base_p,
            "to_serving_handoffs": to_serving,
            "to_training_handoffs": to_training,
            "handoff_cycles": min(to_serving, to_training),
            "boundaries": boundaries,
            "loss_mismatch_ticks": loss_mismatch_ticks,
            "final_params_bitwise_equal": bool(params_equal),
            "hi_requests_at_peak": hi_requests,
            "hi_breaches_at_peak": hi_breaches,
            "hi_p99_ms": hi_p99,
            "hi_slo_ms": hi_slo_ms,
            "hi_tracker_breaches": tracker_breaches,
            "serving_fresh_compiles_total": sum(
                b["serving_fresh_compiles"] for b in boundaries),
            "training_fresh_compiles_total": train_fresh_total,
            "gang_generation": gang.generation,
            "journal_replays": final["replays"],
            "journal_commits": final["journal_commits"],
            "final_leases": {str(k): v
                             for k, v in final["leases"].items()},
            "chaos": chaos,
        }
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


def main_arbiter(quick: bool):
    """`--arbiter` mode: trace detail to stderr + BENCH_arbiter.json,
    ONE stdout JSON line.  Gates (exit 1 on any failure): >= 2 handoff
    cycles under the diurnal 10x-spike trace, zero hi-priority SLO
    breaches at peak, bitwise training-loss parity with the
    uninterrupted run at every boundary, fresh_compiles == 0 on both
    sides of every handoff, and the injected mid-handoff kill recovered
    by journal replay with the slice single-owned."""
    import os
    try:
        r = bench_arbiter(quick)
    except Exception as e:
        print(json.dumps({"metric": "arbiter_handoff_cycles",
                          "value": None, "unit": "cycles",
                          "error": repr(e)[:300]}))
        sys.exit(1)
    for k, v in r.items():      # detail to stderr: stdout stays one line
        print(f"[arbiter] {k} = {v}", file=sys.stderr, flush=True)
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_arbiter.json"), "w") as f:
        json.dump(r, f, indent=2)
    c = r["chaos"]
    gates = {
        "cycles": r["handoff_cycles"] >= 2,
        "slo_at_peak": (r["hi_requests_at_peak"] > 0
                        and r["hi_breaches_at_peak"] == 0
                        and r["hi_tracker_breaches"] == 0),
        "bitwise": (not r["loss_mismatch_ticks"]
                    and all(b["bitwise"] for b in r["boundaries"])
                    and r["final_params_bitwise_equal"]),
        "zero_recompile": (r["serving_fresh_compiles_total"] == 0
                           and r["training_fresh_compiles_total"] == 0),
        "chaos_replay": (c["run_rc"] == 9
                         and c["journal_phase_after_kill"] == "shrink"
                         and c["lease_after_kill"] == "transit"
                         and c["recover_rc"] == 0
                         and c["outcome"] == "replayed"
                         and c["replays"] == 1
                         and bool(c["single_owned"])),
    }
    print(json.dumps({
        "metric": "arbiter_handoff_cycles",
        "value": r["handoff_cycles"],
        "unit": "cycles",
        "threshold": 2,
        "hi_breaches_at_peak": r["hi_breaches_at_peak"],
        "hi_p99_ms": round(r["hi_p99_ms"], 2),
        "fresh_compiles": (r["serving_fresh_compiles_total"]
                           + max(r["training_fresh_compiles_total"], 0)),
        "journal_replays_after_kill": c["replays"],
        "gates": gates,
        "pass": all(gates.values()),
        **_device_fields(),
    }))
    if not all(gates.values()):
        sys.exit(1)


def main():
    from deeplearning4j_tpu.compile import place_compilation_cache
    place_compilation_cache()
    quick = "--quick" in sys.argv
    if "--arbiter-child" in sys.argv:
        i = sys.argv.index("--arbiter-child")
        arbiter_child(sys.argv[i + 1], sys.argv[i + 2])
        return
    if "--arbiter" in sys.argv:
        main_arbiter(quick)
        return
    if "--aot-child" in sys.argv:
        i = sys.argv.index("--aot-child")
        aot_child(sys.argv[i + 1], int(sys.argv[i + 2]),
                  int(sys.argv[i + 3]), int(sys.argv[i + 4]))
        return
    if "--aot" in sys.argv:
        main_aot(quick)
        return
    if "--quant-child" in sys.argv:
        i = sys.argv.index("--quant-child")
        quant_child(sys.argv[i + 1], int(sys.argv[i + 2]),
                    int(sys.argv[i + 3]), int(sys.argv[i + 4]),
                    int(sys.argv[i + 5]))
        return
    if "--quant" in sys.argv:
        main_quant(quick)
        return
    if "--decode" in sys.argv:
        main_decode(quick)
        return
    if "--pallas" in sys.argv:
        main_pallas(quick)
        return
    if "--autotune" in sys.argv:
        main_autotune(quick)
        return
    if "--serving" in sys.argv:
        main_serving(quick)
        return
    if "--fleetchaos" in sys.argv:
        main_fleetchaos(quick)
        return
    if "--federation" in sys.argv:
        main_federation(quick)
        return
    if "--fleet" in sys.argv:
        main_fleet(quick)
        return
    if "--pipeline" in sys.argv:
        main_pipeline(quick)
        return
    if "--obs" in sys.argv:
        main_obs(quick)
        return
    if "--zero1" in sys.argv:
        main_zero1(quick)
        return
    if "--comms" in sys.argv:
        main_comms(quick)
        return
    if "--elastic" in sys.argv:
        main_elastic(quick)
        return
    if "--resilience" in sys.argv:
        main_resilience(quick)
        return
    import jax
    dev = _device_fields()
    print(f"devices: {jax.devices()}", file=sys.stderr)
    if dev["platform"] != "tpu":
        # the default mode is the on-chip benchmark: no chip, no number
        print(f"[bench] found platform={dev['platform']!r} "
              f"({dev['device_kind']} x{dev['device_count']}), need 'tpu'; "
              "nothing measured", file=sys.stderr, flush=True)
        sys.exit(1)

    if quick:
        sps = bench_resnet50(batch=16, steps=5, image=96, classes=100)
    else:
        sps = bench_resnet50()
    per_chip = sps / dev["device_count"]

    # One JSON line per BASELINE config on stdout so the recorded artifact
    # carries all metrics, not just the headline.  A config that fails
    # fails the run.  The headline is printed LAST — the driver's `parsed`
    # field takes the final stdout JSON line.
    configs = [
        ("lenet_mnist_samples_per_sec", "samples/sec", lambda: bench_lenet()),
        ("lstm_charlm_tokens_per_sec", "tokens/sec",
         lambda: bench_lstm_charlm(steps=3 if quick else 10)),
        ("bert_base_mlm_tokens_per_sec", "tokens/sec",
         lambda: bench_bert_base(steps=3 if quick else 10)),
    ]
    if not quick:
        configs.append(("bert_long_seq2048_mlm_tokens_per_sec",
                        "tokens/sec", lambda: bench_bert_long_seq()))
        configs.append(("bert_tf_import_finetune_tokens_per_sec",
                        "tokens/sec", lambda: bench_bert_tf_import()))
    for metric, unit, fn in configs:
        print(json.dumps({"metric": metric, "value": round(fn(), 1),
                          "unit": unit, **dev}), flush=True)

    print(json.dumps({
        "metric": "resnet50_train_samples_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": round(per_chip / V100_RESNET50_SAMPLES_SEC, 3),
        **dev,
    }))


if __name__ == "__main__":
    main()
