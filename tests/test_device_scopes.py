"""The device-side names of a train step (docs/observability.md): every
front end puts its layers under `jax.named_scope`, the compiled step's
`op_name`s carry them forward and backward, no op falls under two of a
step's top scopes, the scopes change no byte of the program, and
`monitor.lowered_step()` hands out the step that runs."""
import contextlib
import functools
import gc
import re
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.data.dataset import MultiDataSet
from deeplearning4j_tpu.monitor import spans
from deeplearning4j_tpu.nn import (DenseLayer, InputType, MultiLayerNetwork,
                                   NeuralNetConfiguration, OutputLayer)
from deeplearning4j_tpu.train.updaters import Adam
from deeplearning4j_tpu.zoo import (BertConfig, BertModel, DecoderConfig,
                                    DecoderModel, ResNet50)

_OP_NAME = re.compile(r'op_name="([^"]*)"')
TRAINER_TOPS = ("param_cast", "input_normalize", "loss", "updater")


def _graph():
    from deeplearning4j_tpu.data.normalizers import ImagePreProcessingScaler
    cut = type("ResNetCut", (ResNet50,), {"STAGES": ((1, 8), (1, 16))})
    net = cut(n_classes=10, input_shape=(16, 16, 3), seed=3,
              compute_dtype="bfloat16").init_model()
    return net.set_normalizer(ImagePreProcessingScaler())


def _graph_step(net):
    x = np.random.default_rng(0).integers(0, 255, (4, 16, 16, 3)).astype(
        np.uint8)
    net.fit(x, np.eye(10, dtype=np.float32)[[1, 2, 3, 4]])


def _stack():
    conf = (NeuralNetConfiguration.builder().seed(7).updater(Adam(1e-2))
            .compute_dtype("bfloat16").l2(1e-4)
            .list([DenseLayer(n_out=16, activation="relu"),
                   DenseLayer(name="mid", n_out=12, activation="tanh"),
                   OutputLayer(n_out=3, activation="softmax", loss="mcxent")])
            .set_input_type(InputType.feed_forward(10)).build())
    return MultiLayerNetwork(conf).init()


def _stack_step(net, rows=8):
    rng = np.random.RandomState(0)
    net.fit(rng.randn(rows, 10).astype(np.float32),
            np.eye(3, dtype=np.float32)[rng.randint(0, 3, rows)])


def _bert():
    return BertModel(BertConfig.tiny(max_len=16, compute_dtype="bfloat16"),
                     seed=1)


def _bert_step(model):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 100, (2, 16)).astype(np.int32)
    model.fit_batch(MultiDataSet(
        features=[ids, np.ones((2, 16), np.float32)], labels=[ids],
        labels_masks=[(rng.random((2, 16)) < 0.3).astype(np.float32)]))


def _decoder_step(model):
    ids = np.random.default_rng(0).integers(0, 95, (2, 16)).astype(np.int32)
    labels = np.concatenate([ids[:, 1:], np.zeros((2, 1), np.int32)], 1)
    model.fit_batch(MultiDataSet(features=[ids], labels=[labels]))


# name -> (build, one step, scopes with a forward AND a backward half,
#          scopes without a gradient, all of the step's top scopes)
FRONT_ENDS = {
    "ComputationGraph": (
        _graph, _graph_step,
        ["ConvolutionLayer/stem_conv", "BatchNormalizationLayer/stem_bn",
         "ConvolutionLayer/s1b0_proj_conv", "SubsamplingLayer/stem_pool",
         "loss"],
        # an addition's gradient is its cotangent: no op of its own; the
        # casts' gradients fuse into their consumers
        ["ElementWiseVertex/s0b0_add", "input_normalize", "param_cast",
         "updater/stem_conv", "updater/output"],
        # no `OutputLayer`: the head's work is `compute_loss`, under `loss`
        TRAINER_TOPS + ("ConvolutionLayer", "BatchNormalizationLayer",
                        "ElementWiseVertex", "SubsamplingLayer",
                        "ActivationLayer", "GlobalPoolingLayer")),
    "MultiLayerNetwork": (
        _stack, _stack_step,
        ["DenseLayer/layer_0", "DenseLayer/mid", "loss", "param_cast"],
        ["updater/layer_0", "updater/mid", "updater/layer_2"],
        ("param_cast", "loss", "updater", "DenseLayer")),
    "BertModel": (
        _bert, _bert_step,
        ["embeddings", "self_attention", "ffn", "param_cast"],
        ["mlm_head", "updater"],      # the head's gradients: a custom_vjp
        ("embeddings", "self_attention", "ffn", "param_cast", "mlm_head",
         "updater")),
    "DecoderModel": (
        lambda: DecoderModel(DecoderConfig.tiny(compute_dtype="bfloat16"),
                             seed=1), _decoder_step,
        ["embed", "param_cast", "mla_attention", "dense_mlp", "moe",
         "lm_head", "loss"],
        ["updater", "router_bias"],
        ("embed", "param_cast", "mla_attention", "dense_mlp", "moe",
         "lm_head", "loss", "updater", "router_bias")),
    "DecoderModel-linear": (
        lambda: DecoderModel(DecoderConfig.tiny_linear(
            compute_dtype="bfloat16"), seed=1), _decoder_step,
        ["embed", "param_cast", "gqa_attention", "linear_attention",
         "delta_rule", "moe", "lm_head", "loss"],
        ["updater", "router_bias"],
        ("embed", "param_cast", "gqa_attention", "linear_attention", "moe",
         "lm_head", "loss", "updater", "router_bias")),
    "DecoderModel-mamba": (
        lambda: DecoderModel(DecoderConfig.tiny_mamba(
            compute_dtype="bfloat16"), seed=1), _decoder_step,
        ["embed", "param_cast", "gqa_attention", "mamba_mixer", "ssd_scan",
         "moe", "lm_head", "loss"],
        ["updater", "router_bias"],
        ("embed", "param_cast", "gqa_attention", "mamba_mixer", "moe",
         "lm_head", "loss", "updater", "router_bias")),
    "DecoderModel-diffusion": (
        lambda: DecoderModel(DecoderConfig.tiny_diffusion(
            compute_dtype="bfloat16"), seed=1), _decoder_step,
        ["embed", "param_cast", "gqa_attention", "moe", "lm_head",
         "diffusion_loss"],
        ["updater", "router_bias", "bd_noise"],
        ("embed", "param_cast", "gqa_attention", "moe", "lm_head",
         "diffusion_loss", "bd_noise", "updater", "router_bias")),
}


def _in(path, scope):
    return re.search(r"(?<![\w.\-])" + re.escape(scope) + r"(?![\w.\-])",
                     path) is not None


@functools.lru_cache(maxsize=None)
def _texts(name):
    """(lowered text, op_names of the compiled step) of one front end's
    step, from the handle, and the lowered text of the same step built with
    `jax.named_scope` a no-op."""
    build, step = FRONT_ENDS[name][:2]
    model = build()
    step(model)
    lowered = monitor.lowered_step()
    scoped = lowered.as_text()
    op_names = _OP_NAME.findall(lowered.compile().as_text())
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        bare = build()
        step(bare)
        unscoped = monitor.lowered_step().as_text()
    finally:
        jax.named_scope = real
    return scoped, op_names, unscoped


@pytest.mark.parametrize("name", FRONT_ENDS)
def test_compiled_step_names_each_layer_forward_and_backward(name):
    _, op_names, _ = _texts(name)
    both, forward_only = FRONT_ENDS[name][2:4]
    for scope in both + forward_only:
        assert any(_in(p, scope) for p in op_names), scope
    for scope in both:
        assert any(_in(p, scope) and "transpose(" in p
                   for p in op_names), scope
        assert any(_in(p, scope) and "transpose(" not in p
                   for p in op_names), scope


@pytest.mark.parametrize("name", FRONT_ENDS)
def test_no_op_falls_under_two_top_scopes(name):
    _, op_names, _ = _texts(name)
    tops = FRONT_ENDS[name][4]
    seen = set()
    for path in op_names:
        under = [t for t in tops if _in(path, t)]
        # a layer's own name may repeat a kind (`updater/output` under the
        # updater is not the OutputLayer): the outermost one decides
        first = min(under, key=lambda t: re.search(re.escape(t), path).start(),
                    default=None)
        nested = [t for t in under if t != first
                  and not _in(path.split(first, 1)[1], t)]
        assert not nested, path
        seen.add(first)
    assert set(tops) <= seen | {None}, set(tops) - seen


@pytest.mark.parametrize("name", FRONT_ENDS)
def test_scopes_change_no_byte_of_the_program(name):
    scoped, op_names, unscoped = _texts(name)
    assert scoped == unscoped
    assert "named_scope" not in scoped and op_names


# ---------------------------------------------------------------------------
# the handle
# ---------------------------------------------------------------------------

@pytest.fixture
def no_step(monkeypatch):
    monkeypatch.setattr(spans, "_step", None)


def test_handle_is_none_before_any_step(no_step):
    assert monitor.lowered_step() is None
    _stack()                                  # building a model notes nothing
    assert monitor.lowered_step() is None


def test_handle_follows_rebuilds_and_new_shapes(no_step):
    net = _stack()
    _stack_step(net, rows=8)
    first = monitor.lowered_step().as_text()
    assert "tensor<8x10xf32>" in first
    _stack_step(net, rows=8)                  # steady state: the same step
    assert monitor.lowered_step().as_text() == first
    _stack_step(net, rows=4)                  # a second batch shape
    assert "tensor<4x10xf32>" in monitor.lowered_step().as_text()
    from deeplearning4j_tpu.data.normalizers import ImagePreProcessingScaler
    before = monitor.lowered_step().compile().as_text()
    net.set_normalizer(ImagePreProcessingScaler())  # drops the model's step;
    assert "input_normalize" not in before          # nothing compiled yet
    assert monitor.lowered_step().compile().as_text() == before
    _stack_step(net, rows=4)
    rebuilt = monitor.lowered_step()
    assert "input_normalize" in rebuilt.compile().as_text()
    # another front end's step takes the slot
    _bert_step(_bert())
    assert "self_attention" in monitor.lowered_step().compile().as_text()


class _Stub:
    """Stands in for a jitted step and for what its `trace` returns: hands
    the traced arguments on and counts what `lower` is asked."""
    calls = 0

    def trace(self, *args):
        self.args = args
        return self

    def lower(self):
        self.calls += 1
        return self.args


def test_nothing_is_lowered_until_asked_and_no_array_is_kept(no_step):
    stub = _Stub()
    x = jnp.ones((4, 3))
    monitor.note_step(stub, (x, {"w": np.zeros((2,), np.int32)}, None, 7))
    assert stub.calls == 0
    assert not any(isinstance(l, (jax.Array, np.ndarray))
                   for l in jax.tree_util.tree_leaves(stub.args))
    (a, d, none, seven) = monitor.lowered_step()
    assert stub.calls == 1 and none is None and seven == 7
    assert (a.shape, a.dtype, a.sharding) == ((4, 3), x.dtype, None)
    assert (d["w"].shape, d["w"].dtype) == ((2,), np.int32)
    committed = jax.device_put(x, jax.devices()[0])
    monitor.note_step(stub, (committed,))
    assert monitor.lowered_step()[0].sharding == committed.sharding


def test_the_slot_keeps_no_model_and_no_array_alive(no_step):
    """A benchmark's driver drops its model before the readers ask: the
    slot still answers, and holds neither the model nor its buffers."""
    net = _stack()
    _stack_step(net)
    text = monitor.lowered_step().as_text()
    model = weakref.ref(net)
    leaf = weakref.ref(jax.tree_util.tree_leaves(net.params_)[0])
    del net
    gc.collect()
    assert model() is None and leaf() is None
    assert monitor.lowered_step().as_text() == text


def test_handle_through_the_persistent_executable_tier(no_step, tmp_path):
    """With a `.jexe` store the trainer's step is an `AotStepFunction`: its
    compile event notes the step like `jax.jit`'s."""
    net = _stack().set_executable_cache(str(tmp_path))
    _stack_step(net)
    assert "updater" in monitor.lowered_step().compile().as_text()


def test_telemetry_off_notes_nothing(no_step):
    monitor.set_enabled(False)
    try:
        _stack_step(_stack())
        _bert_step(_bert())
    finally:
        monitor.set_enabled(True)
    assert monitor.lowered_step() is None


@pytest.mark.parametrize("front_end", ["BertModel", "DecoderModel",
                                       "MultiLayerNetwork"])
def test_steady_state_dispatch_notes_no_step(front_end, monkeypatch):
    """One `note_step` for the step's first call, none for the next three."""
    import importlib
    build, step = FRONT_ENDS[front_end][:2]
    module = importlib.import_module({
        "BertModel": "deeplearning4j_tpu.zoo.bert",
        "DecoderModel": "deeplearning4j_tpu.zoo.decoder",
        "MultiLayerNetwork": "deeplearning4j_tpu.nn.trainer"}[front_end])
    noted = []
    monkeypatch.setattr(module, "note_step",
                        lambda fn, args: noted.append(fn))
    model = build()
    for _ in range(4):
        step(model)
    assert len(noted) == 1
