"""Persistent AOT executable cache + schedule autotuner (ISSUE 6).

Acceptance contract under test:

* a second cache instance over the same directory serves executables from
  disk with ZERO compiles, and the cached executable is bitwise-identical
  in behaviour to a fresh compile;
* every defect (corrupt bytes, torn write, header mismatch) and every
  version/topology change degrades to a recompile — stale executables are
  never served;
* the train-step builders (MLN/CG/SameDiff) route through the cache, so a
  simulated restart pays 0 compiles and reproduces the exact same math;
* the autotuner picks the known-best config on a rigged measure function,
  and schedules round-trip through save/load and apply.

The slow lane (`test_warm_restart_subprocess`) proves the warm start
cross-process: a child process trains against a shared cache directory
twice and the second run must report 0 compiles.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.autodiff import SameDiff, TrainingConfig
from deeplearning4j_tpu.compile import (DEFAULT_SPACE, AotStepFunction,
                                        PersistentExecutableCache, Schedule,
                                        ScheduleAutotuner, load_schedule,
                                        model_fingerprint, save_schedule,
                                        step_function)
from deeplearning4j_tpu.compile.fingerprint import (
    _reset_environment_fingerprint, environment_fingerprint)
from deeplearning4j_tpu.compile.persistent import ENTRY_SUFFIX
from deeplearning4j_tpu.nn import (ComputationGraph, DenseLayer, GraphBuilder,
                                   InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.serving import BucketedCompileCache
from deeplearning4j_tpu.train.updaters import Adam, Sgd


def _net(seed=0, n_in=8, n_out=3):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(1e-1))
            .list([DenseLayer(n_out=16, activation="relu"),
                   OutputLayer(n_out=n_out, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _graph(seed=1):
    conf = (GraphBuilder().seed(seed).updater(Sgd(1e-1))
            .add_inputs("in").set_input_types(InputType.feed_forward(6))
            .add_layer("h", DenseLayer(n_out=8, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=2, loss="mcxent",
                                          activation="softmax"), "h")
            .set_outputs("out").build())
    return ComputationGraph(conf).init()


def _sd_mlp():
    sd = SameDiff.create()
    x = sd.placeholder("input", shape=(-1, 4))
    y = sd.placeholder("label", shape=(-1, 3))
    w0 = sd.var("w0", "XAVIER", 4, 16)
    b0 = sd.var("b0", np.zeros(16, np.float32))
    w1 = sd.var("w1", "XAVIER", 16, 3)
    b1 = sd.var("b1", np.zeros(3, np.float32))
    h = sd.nn.tanh(sd.nn.linear(x, w0, b0))
    logits = sd.nn.linear(h, w1, b1, name="logits")
    sd.nn.softmax(logits, name="out")
    sd.loss.softmax_cross_entropy(y, logits, name="loss")
    sd.set_loss_variables("loss")
    sd.set_training_config(TrainingConfig(
        updater=Adam(1e-2), data_set_feature_mapping=["input"],
        data_set_label_mapping=["label"]))
    return sd


def _xy(n=12, n_in=8, n_out=3, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, n_in).astype(np.float32)
    y = np.eye(n_out, dtype=np.float32)[rs.randint(0, n_out, n)]
    return x, y


# ---------------------------------------------------------------------------
# PersistentExecutableCache core
# ---------------------------------------------------------------------------

def test_disk_round_trip_zero_compiles(tmp_path):
    """A second cache instance over the same directory deserializes the
    stored executable — compile_fn must never run — and the result is
    bitwise-identical to the fresh compile's output."""
    def body(a, b):
        return a @ b + 1.0

    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    b = np.arange(20, dtype=np.float32).reshape(4, 5)
    parts = {"kind": "unit", "name": "mm"}

    c1 = PersistentExecutableCache(str(tmp_path))
    fn1, src1 = c1.get_or_compile(
        parts, lambda: jax.jit(body).lower(a, b).compile())
    assert src1 == "compiled"
    assert c1.stats["compiles"] == 1 and c1.stats["stores"] == 1
    y1 = np.asarray(fn1(a, b))

    c2 = PersistentExecutableCache(str(tmp_path))

    def boom():
        raise AssertionError("warm path must not compile")

    fn2, src2 = c2.get_or_compile(parts, boom)
    assert src2 == "disk"
    assert c2.stats == {"disk_hits": 1, "disk_misses": 0, "compiles": 0,
                        "stores": 0, "errors": 0,
                        "bytes_read": c2.stats["bytes_read"],
                        "bytes_written": 0}
    assert np.array_equal(np.asarray(fn2(a, b)), y1)


def test_corrupted_entry_recompiles_and_rewrites(tmp_path):
    """Flipping payload bytes after commit → crc mismatch → treated as a
    miss, recompiled, entry rewritten; truncation likewise."""
    def body(a):
        return a * 2.0

    a = np.ones((4,), np.float32)
    parts = {"kind": "unit", "name": "corrupt"}
    c = PersistentExecutableCache(str(tmp_path))
    c.get_or_compile(parts, lambda: jax.jit(body).lower(a).compile())
    (entry,) = [p for p in os.listdir(str(tmp_path))
                if p.endswith(ENTRY_SUFFIX)]
    path = os.path.join(str(tmp_path), entry)

    blob = bytearray(open(path, "rb").read())
    blob[-10] ^= 0xFF                      # corrupt committed payload
    open(path, "wb").write(bytes(blob))

    c2 = PersistentExecutableCache(str(tmp_path))
    fn, src = c2.get_or_compile(parts,
                                lambda: jax.jit(body).lower(a).compile())
    assert src == "compiled"               # defect degraded to recompile
    assert c2.stats["errors"] >= 1
    assert np.array_equal(np.asarray(fn(a)), np.full((4,), 2.0, np.float32))

    # ...and the rewrite healed the entry for the next process
    c3 = PersistentExecutableCache(str(tmp_path))
    _, src3 = c3.get_or_compile(parts, lambda: (_ for _ in ()).throw(
        AssertionError("healed entry must hit")))
    assert src3 == "disk"

    # torn write (truncation) is also a miss, never an exception
    blob = open(path, "rb").read()
    open(path, "wb").write(blob[: len(blob) // 2])
    c4 = PersistentExecutableCache(str(tmp_path))
    assert c4.load(parts) is None


def test_version_mismatch_is_a_fresh_key(tmp_path):
    """The environment fingerprint is hashed into the key, so a different
    jax/XLA version (simulated via env=) can never reach the old entry."""
    def body(a):
        return a + 1.0

    a = np.zeros((3,), np.float32)
    parts = {"kind": "unit", "name": "ver"}
    c1 = PersistentExecutableCache(str(tmp_path))
    c1.get_or_compile(parts, lambda: jax.jit(body).lower(a).compile())

    fake_env = dict(environment_fingerprint(), jax_version="0.0.0-other")
    c2 = PersistentExecutableCache(str(tmp_path), env=fake_env)
    assert c2.key_for(parts) != c1.key_for(parts)
    assert c2.load(parts) is None          # unreachable, not mis-served
    _, src = c2.get_or_compile(parts,
                               lambda: jax.jit(body).lower(a).compile())
    assert src == "compiled"


def test_renamed_entry_never_serves_wrong_program(tmp_path):
    """A cache file renamed to another request's key fails the header
    key/parts check and is treated as a miss."""
    def body(a):
        return a - 5.0

    a = np.zeros((2,), np.float32)
    c = PersistentExecutableCache(str(tmp_path))
    c.get_or_compile({"name": "one"},
                     lambda: jax.jit(body).lower(a).compile())
    (entry,) = [p for p in os.listdir(str(tmp_path))
                if p.endswith(ENTRY_SUFFIX)]
    other_key = c.key_for({"name": "two"})
    os.rename(os.path.join(str(tmp_path), entry),
              os.path.join(str(tmp_path), other_key + ENTRY_SUFFIX))
    assert c.load({"name": "two"}) is None
    assert c.stats["errors"] >= 1


def test_environment_fingerprint_cached_and_resettable():
    e1 = environment_fingerprint()
    assert environment_fingerprint() is e1       # cached
    _reset_environment_fingerprint()
    e2 = environment_fingerprint()
    assert e2 == e1                              # same machine, same content


# ---------------------------------------------------------------------------
# step_function / AotStepFunction
# ---------------------------------------------------------------------------

def test_step_function_plain_jit_when_no_cache():
    def body(a):
        return a * 3.0
    fn = step_function(body)
    assert not isinstance(fn, AotStepFunction)    # plain jax.jit, no wrapper
    assert float(fn(np.float32(2.0))) == 6.0


def test_aot_step_function_counts_only_real_compiles(tmp_path):
    """_cache_size() (monitor's check_compile contract) counts compile
    events, not disk hits — a warm restart must read as 0 recompiles."""
    def body(a, b):
        return a.sum() + b.sum()

    cache = PersistentExecutableCache(str(tmp_path))
    f1 = step_function(body, key_base=lambda: {"k": "s"}, cache=cache,
                       dynamic_argnums=(1,))
    a = np.ones((4,), np.float32)
    f1(a, a)
    assert f1._cache_size() == 1
    f1(a, a)                                      # in-memory table hit
    assert f1._cache_size() == 1
    f1(a, np.ones((8,), np.float32)[:4] * 2)      # same sig, table hit
    assert f1._cache_size() == 1
    f1(a, np.ones((2,), np.float32))              # new dynamic sig
    assert f1._cache_size() == 2

    f2 = step_function(body, key_base=lambda: {"k": "s"},
                       cache=PersistentExecutableCache(str(tmp_path)),
                       dynamic_argnums=(1,))
    f2(a, a)
    assert f2._cache_size() == 0                  # disk hit, no compile


# ---------------------------------------------------------------------------
# model restart path (the FaultTolerantTrainer warm-resume contract)
# ---------------------------------------------------------------------------

def test_mln_restart_zero_compiles_bitwise(tmp_path):
    x, y = _xy()
    c1 = PersistentExecutableCache(str(tmp_path))
    n1 = _net().set_executable_cache(c1)
    for _ in range(3):
        n1.fit(x, y)
    assert c1.stats["compiles"] == 1

    c2 = PersistentExecutableCache(str(tmp_path))
    n2 = _net().set_executable_cache(c2)
    for _ in range(3):
        n2.fit(x, y)
    assert c2.stats["compiles"] == 0 and c2.stats["disk_hits"] == 1
    assert n2._train_step._cache_size() == 0
    assert float(n1.score()) == float(n2.score())   # bitwise parity
    np.testing.assert_array_equal(
        np.asarray(n1.params_["layer_0"]["W"]),
        np.asarray(n2.params_["layer_0"]["W"]))

    # uncached baseline computes the same numbers
    n3 = _net()
    for _ in range(3):
        n3.fit(x, y)
    assert float(n3.score()) == float(n1.score())


def test_mln_scan_step_through_cache(tmp_path):
    x, y = _xy()
    xs, ys = np.stack([x, x]), np.stack([y, y])
    n1 = _net().set_executable_cache(str(tmp_path))   # directory coercion
    n1.fit_steps(xs, ys)
    assert n1._exec_cache().stats["compiles"] == 1
    n2 = _net().set_executable_cache(str(tmp_path))
    n2.fit_steps(xs, ys)
    assert n2._exec_cache().stats["compiles"] == 0
    assert float(n1.score()) == float(n2.score())


def test_graph_and_samediff_restart_zero_compiles(tmp_path):
    xg, yg = _xy(8, 6, 2, seed=1)
    g1 = _graph().set_executable_cache(PersistentExecutableCache(str(tmp_path)))
    g1.fit(xg, yg)
    g2 = _graph().set_executable_cache(PersistentExecutableCache(str(tmp_path)))
    g2.fit(xg, yg)
    assert g2._exec_cache().stats["compiles"] == 0
    assert float(g1.score()) == float(g2.score())

    xs, ys = _xy(8, 4, 3, seed=2)
    s1 = _sd_mlp().set_executable_cache(
        PersistentExecutableCache(str(tmp_path)))
    s1.fit(xs, ys)
    s2 = _sd_mlp().set_executable_cache(
        PersistentExecutableCache(str(tmp_path)))
    s2.fit(xs, ys)
    assert s2._exec_cache().stats["compiles"] == 0
    assert float(s1.score()) == float(s2.score())


def test_model_fingerprint_ignores_weights_not_architecture():
    n1, n2 = _net(seed=0), _net(seed=7)      # same arch, different weights
    assert model_fingerprint(n1) == model_fingerprint(n2)
    n3 = _net(n_out=4)                       # different architecture
    assert model_fingerprint(n3) != model_fingerprint(n1)


def test_normalizer_stats_change_the_key(tmp_path):
    """DeviceNormalizer stats are baked into the executable as constants,
    so different stats MUST produce different disk keys."""
    from deeplearning4j_tpu.data import DataSet, NormalizerStandardize
    x, y = _xy(32)
    nz1 = NormalizerStandardize().fit([DataSet(x, y)])
    nz2 = NormalizerStandardize().fit([DataSet(x * 3.0 + 1.0, y)])
    n1 = _net().set_normalizer(nz1)
    n2 = _net().set_normalizer(nz2)
    assert model_fingerprint(n1) != model_fingerprint(n2)
    n3 = _net().set_normalizer(nz1)
    assert model_fingerprint(n1) == model_fingerprint(n3)


# ---------------------------------------------------------------------------
# serving cache: persistent tier, pads, set_buckets, parallel warmup
# ---------------------------------------------------------------------------

def test_serving_warm_instance_zero_compiles(tmp_path):
    net = _net()
    x, _ = _xy(5)
    c1 = BucketedCompileCache(max_batch=16, persistent=str(tmp_path))
    y1 = c1.run("m:v1", net, x)
    assert c1.persistent.stats["compiles"] == 1

    c2 = BucketedCompileCache(max_batch=16, persistent=str(tmp_path))
    y2 = c2.run("m:v1", net, x)
    assert c2.persistent.stats["compiles"] == 0
    assert c2.persistent.stats["disk_hits"] == 1
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))

    # a weights-only model roll (same architecture) also comes up warm
    c3 = BucketedCompileCache(max_batch=16, persistent=str(tmp_path))
    y3 = c3.run("m:v2", _net(seed=9), x)
    assert c3.persistent.stats["compiles"] == 0
    assert y3.shape == y1.shape


def test_serving_pad_buffer_reused(tmp_path):
    net = _net()
    cache = BucketedCompileCache(max_batch=16)
    x, _ = _xy(5)
    cache.run("m:v1", net, x)
    cache.run("m:v1", net, x[:3])
    # one pad buffer per (bucket, trailing, dtype), reused across runs
    assert len(cache._pads) == 2
    pads_before = dict(cache._pads)
    cache.run("m:v1", net, x)
    assert cache._pads == pads_before
    for pad in cache._pads.values():
        assert not pad.any()               # still zeros (never written)


def test_set_buckets_and_parallel_warmup():
    net = _net()
    cache = BucketedCompileCache(max_batch=16)
    assert cache.set_buckets(buckets=[3, 12]) == [3, 12]
    assert cache.bucket_for(2) == 3
    assert cache.bucket_for(4) == 12
    with pytest.raises(ValueError):
        cache.bucket_for(13)
    with pytest.raises(ValueError):
        cache.set_buckets(buckets=[4, 4])
    cache.set_buckets(min_bucket=4)
    assert cache.buckets == [4, 8, 16]
    warmed = cache.warmup("m:v1", net, (8,), np.float32, parallel=True)
    assert warmed == [4, 8, 16]
    assert cache.counters.misses.value == 3
    # every warmed bucket is now an in-memory hit
    cache.run("m:v1", net, np.zeros((5, 8), np.float32))
    assert cache.counters.misses.value == 3


# ---------------------------------------------------------------------------
# autotuner + schedule persistence
# ---------------------------------------------------------------------------

def test_autotuner_finds_rigged_optimum():
    """Analytic measure with a known best point: the search must find it
    and memoize (never re-measure a config)."""
    calls = []

    def measure(s):
        calls.append(s.config_key())
        v = 100.0
        v += {1: 0, 2: 10, 4: 25, 8: 20, 16: 5}[s.fused_steps]
        v += {1: 0, 2: 6, 4: 3}[s.prefetch_depth]
        v += 8 if s.zero1 else 0
        v += 4 if s.donation else 0
        return v

    tuner = ScheduleAutotuner(measure, space=DEFAULT_SPACE)
    best = tuner.search()
    assert (best.fused_steps, best.prefetch_depth, best.zero1,
            best.donation) == (4, 2, True, True)
    assert best.steps_per_sec == measure(best)
    assert best.source == "autotuned"
    assert len(calls) - 1 == len(set(calls[:-1]))   # memoized (re-measure
    # above adds the final duplicate)
    assert best.meta["evaluated"] == len(set(calls))
    assert tuner.history[0]["steps_per_sec"] == \
        best.meta["baseline_steps_per_sec"]


def test_schedule_save_load_apply(tmp_path):
    sch = Schedule(fused_steps=8, prefetch_depth=4, zero1=False,
                   donation=False, steps_per_sec=123.4)
    path = save_schedule(sch, str(tmp_path), name="t")
    assert os.path.basename(path) == "schedule-t.json"
    loaded = load_schedule(str(tmp_path), name="t")
    assert loaded.source == "loaded"
    assert loaded.config_key() == sch.config_key()
    assert loaded.steps_per_sec == 123.4
    assert load_schedule(str(tmp_path), name="absent") is None

    # defect → None, never an exception
    with open(path, "w") as f:
        f.write("{not json")
    assert load_schedule(str(tmp_path), name="t") is None

    # model-keyed path: same architecture resolves the same file
    sch2 = Schedule(fused_steps=2)
    save_schedule(sch2, str(tmp_path), model=_net(seed=0))
    got = load_schedule(str(tmp_path), model=_net(seed=5))
    assert got is not None and got.fused_steps == 2


def test_schedule_apply_to_model_and_buckets():
    net = _net()
    sch = Schedule(fused_steps=4, donation=False)
    assert sch.apply(net) is net
    assert net._schedule is sch
    assert net._donate_argnums() == ()       # donation honored
    x, y = _xy()
    net.fit(x, y)                            # no-donation step still trains
    assert np.isfinite(float(net.score()))

    cache = BucketedCompileCache(max_batch=32)
    Schedule(buckets=[8, 32]).apply(cache)
    assert cache.buckets == [8, 32]


def test_wrapper_apply_schedule_toggles_zero1():
    from deeplearning4j_tpu.parallel import ParallelWrapper
    net = _net()
    pw = ParallelWrapper.builder(net).build()
    sch = Schedule(fused_steps=2, zero1=True)
    pw.apply_schedule(sch)
    assert pw._zero1 is True
    assert net._schedule is sch
    x, y = _xy(16)
    pw.fit(x, y)
    assert np.isfinite(float(net.score()))
    pw.apply_schedule(Schedule(zero1=False))
    assert pw._zero1 is False


# ---------------------------------------------------------------------------
# jax's own compilation cache: placed once, from outside or in the checkout
# ---------------------------------------------------------------------------

def test_place_compilation_cache_leaves_env_choice_alone(monkeypatch,
                                                         tmp_path):
    from deeplearning4j_tpu.compile import place_compilation_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    before = jax.config.jax_compilation_cache_dir
    assert place_compilation_cache() == str(tmp_path / "cc")
    assert jax.config.jax_compilation_cache_dir == before   # nothing set
    assert not (tmp_path / "cc").exists()                   # nor created


def test_place_compilation_cache_fixed_in_checkout_path():
    """Unset, two fresh processes agree on one directory inside the
    checkout — never a temporary name, pid or timestamp."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    code = ("import jax\n"
            "from deeplearning4j_tpu.compile import place_compilation_cache\n"
            "d = place_compilation_cache()\n"
            "assert jax.config.jax_compilation_cache_dir == d\n"
            "print(d)\n")

    def run():
        p = subprocess.run([sys.executable, "-c", code], env=env, cwd="/",
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        return p.stdout.strip().splitlines()[-1]

    first, second = run(), run()
    assert first == second == os.path.join(repo, ".jax_compilation_cache")


# ---------------------------------------------------------------------------
# slow lane: true cross-process warm restart
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_warm_restart_subprocess(tmp_path):
    """Two real processes share a cache directory: the second must train
    with 0 compiles and land on the exact same score."""
    here = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(here, "aot_warm_worker.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(here),
               DL4J_TPU_TEST_CACHE=str(tmp_path))

    def run():
        p = subprocess.run([sys.executable, worker], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    cold = run()
    warm = run()
    assert cold["compiles"] >= 1 and cold["stores"] >= 1
    assert warm["compiles"] == 0
    assert warm["disk_hits"] >= cold["stores"]
    assert warm["score"] == cold["score"]
