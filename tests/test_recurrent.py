"""Recurrent + attention layer tests (reference: platform-tests RNN tests,
`LSTMGradientCheckTests`, attention layer tests)."""
import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import (
    Bidirectional, GravesLSTM, InputType, LastTimeStep,
    LearnedSelfAttentionLayer, LSTM, MultiLayerNetwork,
    NeuralNetConfiguration, OutputLayer, RecurrentAttentionLayer,
    RnnOutputLayer, SelfAttentionLayer, SimpleRnn)
from deeplearning4j_tpu.nn.core import Layer
from deeplearning4j_tpu.train import Adam, Sgd
from deeplearning4j_tpu.train.gradientcheck import check_gradients

KEY = jax.random.PRNGKey(0)


def run(layer, input_type, x, mask=None):
    params, state, out_type = layer.initialize(KEY, input_type)
    y, _ = layer.apply(params, state, x, mask=mask)
    return y, out_type


def test_simple_rnn_shapes():
    x = jnp.ones((2, 5, 3))
    y, ot = run(SimpleRnn(n_out=4, weight_init="XAVIER"),
                InputType.recurrent(3, 5), x)
    assert y.shape == (2, 5, 4) and ot.shape == (5, 4)


def test_lstm_shapes_and_mask():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 6, 3)))
    mask = jnp.asarray([[1, 1, 1, 1, 0, 0], [1, 1, 1, 1, 1, 1]], jnp.float32)
    layer = LSTM(n_out=4, weight_init="XAVIER")
    params, state, _ = layer.initialize(KEY, InputType.recurrent(3, 6))
    y, _ = layer.apply(params, state, x, mask=mask)
    assert y.shape == (2, 6, 4)
    # masked steps produce zero output
    np.testing.assert_allclose(np.asarray(y)[0, 4:], 0.0)
    # mask makes trailing input values irrelevant
    x2 = x.at[0, 4:].set(99.0)
    y2, _ = layer.apply(params, state, x2, mask=mask)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=1e-6)


def test_lstm_forget_bias():
    layer = LSTM(n_out=4, forget_gate_bias_init=1.0, weight_init="XAVIER")
    params, _, _ = layer.initialize(KEY, InputType.recurrent(3, 6))
    b = np.asarray(params["b"])
    np.testing.assert_allclose(b[4:8], 1.0)   # forget block (IFOG order)
    np.testing.assert_allclose(b[:4], 0.0)


def test_graves_lstm_has_peepholes():
    layer = GravesLSTM(n_out=4, weight_init="XAVIER")
    params, state, _ = layer.initialize(KEY, InputType.recurrent(3, 5))
    assert params["pW"].shape == (3, 4)
    y, _ = layer.apply(params, state, jnp.ones((2, 5, 3)))
    assert y.shape == (2, 5, 4)


def test_bidirectional_concat_and_add():
    x = jnp.ones((2, 5, 3))
    y, ot = run(Bidirectional(fwd=LSTM(n_out=4), weight_init="XAVIER"),
                InputType.recurrent(3, 5), x)
    assert y.shape == (2, 5, 8) and ot.shape == (5, 8)
    y, ot = run(Bidirectional(fwd=LSTM(n_out=4), mode="ADD",
                              weight_init="XAVIER"),
                InputType.recurrent(3, 5), x)
    assert y.shape == (2, 5, 4)


def test_last_time_step_with_mask():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 5, 3)))
    mask = jnp.asarray([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], jnp.float32)
    layer = LastTimeStep(underlying=SimpleRnn(n_out=4), weight_init="XAVIER")
    params, state, ot = layer.initialize(KEY, InputType.recurrent(3, 5))
    assert ot.kind == "feedforward" and ot.shape == (4,)
    y, _ = layer.apply(params, state, x, mask=mask)
    full, _ = layer.underlying.apply(params, state, x, mask=mask)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(full[0, 2]))
    np.testing.assert_allclose(np.asarray(y[1]), np.asarray(full[1, 4]))


def test_self_attention_shapes():
    x = jnp.ones((2, 5, 6))
    y, ot = run(SelfAttentionLayer(n_out=8, n_heads=2, weight_init="XAVIER"),
                InputType.recurrent(6, 5), x)
    assert y.shape == (2, 5, 8) and ot.shape == (5, 8)


def test_learned_self_attention_fixed_queries():
    x = jnp.ones((3, 7, 6))
    y, ot = run(LearnedSelfAttentionLayer(n_out=8, n_heads=2, n_queries=4,
                                          weight_init="XAVIER"),
                InputType.recurrent(6, 7), x)
    assert y.shape == (3, 4, 8) and ot.shape == (4, 8)


def test_recurrent_attention_shapes():
    x = jnp.ones((2, 5, 6))
    y, ot = run(RecurrentAttentionLayer(n_out=4, weight_init="XAVIER"),
                InputType.recurrent(6, 5), x)
    assert y.shape == (2, 5, 4)


def test_attention_mask_excludes_keys():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(1, 4, 6)))
    layer = SelfAttentionLayer(n_out=6, n_heads=2, weight_init="XAVIER")
    params, state, _ = layer.initialize(KEY, InputType.recurrent(6, 4))
    mask = jnp.asarray([[1, 1, 0, 0]], jnp.float32)
    y1, _ = layer.apply(params, state, x, mask=mask)
    x2 = x.at[0, 2:].set(55.0)
    y2, _ = layer.apply(params, state, x2, mask=mask)
    np.testing.assert_allclose(np.asarray(y1[0, :2]), np.asarray(y2[0, :2]),
                               atol=1e-5)


def build_net(layers, input_type, seed=7):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(0.1)).weight_init("XAVIER")
            .dtype("float64")
            .list(layers).set_input_type(input_type).build())
    return MultiLayerNetwork(conf).init()


def test_lstm_gradient_check():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3, 4, 3))
    y = np.eye(2)[rng.integers(0, 2, (3, 4))]
    net = build_net([
        LSTM(n_out=5, activation="tanh"),
        RnnOutputLayer(n_out=2, loss="mcxent", activation="softmax"),
    ], InputType.recurrent(3, 4))
    @jax.jit
    def score(params):
        return net._loss(params, net.state_, jnp.asarray(x, jnp.float64),
                         jnp.asarray(y, jnp.float64), None)[0]
    check_gradients(score, net.params_)


def test_rnn_output_layer_mask_loss():
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 5, 3))
    y = np.eye(2)[rng.integers(0, 2, (2, 5))]
    mask = np.asarray([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], np.float64)
    net = build_net([
        SimpleRnn(n_out=4, activation="tanh"),
        RnnOutputLayer(n_out=2, loss="mcxent", activation="softmax"),
    ], InputType.recurrent(3, 5))
    s = net.score_for(x, y, features_mask=jnp.asarray(mask),
                      labels_mask=jnp.asarray(mask))
    assert np.isfinite(s)


def test_bidirectional_json_roundtrip():
    layer = Bidirectional(fwd=LSTM(n_out=4, activation="tanh"), mode="ADD")
    d = layer.to_json()
    back = Layer.from_json(d)
    assert isinstance(back, Bidirectional)
    assert isinstance(back.fwd, LSTM) and back.fwd.n_out == 4
    assert back.mode == "ADD"


def test_lstm_net_fits():
    rng = np.random.default_rng(5)
    x = rng.normal(size=(8, 6, 3)).astype(np.float32)
    y = np.eye(2)[rng.integers(0, 2, 8)].astype(np.float32)
    conf = (NeuralNetConfiguration.builder()
            .seed(1).updater(Sgd(0.5)).weight_init("XAVIER")
            .list([
                LSTM(n_out=8, activation="tanh"),
                LastTimeStep(underlying=SimpleRnn(n_out=8, activation="tanh")),
                OutputLayer(n_out=2, loss="mcxent", activation="softmax"),
            ]).set_input_type(InputType.recurrent(3, 6)).build())
    net = MultiLayerNetwork(conf).init()
    net.fit(x, y)
    first = net.score()
    for _ in range(30):
        net.fit(x, y)
    assert net.score() < first


def test_last_time_step_non_contiguous_mask():
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 4, 3)))
    mask = jnp.asarray([[1, 0, 1, 0]], jnp.float32)
    layer = LastTimeStep(underlying=SimpleRnn(n_out=4), weight_init="XAVIER")
    params, state, _ = layer.initialize(KEY, InputType.recurrent(3, 4))
    y, _ = layer.apply(params, state, x, mask=mask)
    full, _ = layer.underlying.apply(params, state, x, mask=mask)
    # last VALID step is t=2, not count-1=1
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(full[0, 2]))


def test_wrapped_layers_are_regularized():
    net = build_net([
        Bidirectional(fwd=LSTM(n_out=4, activation="tanh")),
        LastTimeStep(underlying=SimpleRnn(n_out=4, activation="tanh")),
        OutputLayer(n_out=2, loss="mcxent", activation="softmax"),
    ], InputType.recurrent(3, 5))
    for layer in net.conf.layers:
        layer.l2 = 0.7
    base = float(net._reg_penalty(net.params_))
    # zero the wrapped LSTM weights -> the penalty must drop
    p2 = jax.tree_util.tree_map(lambda a: a, net.params_)
    p2 = dict(p2)
    name0 = net.conf.layer_name(0)
    p2[name0] = {
        "fwd": {**net.params_[name0]["fwd"],
                "W": jnp.zeros_like(net.params_[name0]["fwd"]["W"]),
                "RW": jnp.zeros_like(net.params_[name0]["fwd"]["RW"])},
        "bwd": net.params_[name0]["bwd"],
    }
    assert float(net._reg_penalty(p2)) < base


def test_mask_cleared_after_seq_length_change():
    # LearnedSelfAttention changes T=6 -> n_queries=3; the [B,6] mask must
    # not reach the downstream SimpleRnn (reference feedForwardMaskArray).
    rng = np.random.default_rng(6)
    x = rng.normal(size=(2, 6, 5)).astype(np.float32)
    y = np.eye(2)[rng.integers(0, 2, 2)].astype(np.float32)
    mask = jnp.asarray(np.ones((2, 6), np.float32))
    conf = (NeuralNetConfiguration.builder()
            .seed(1).updater(Sgd(0.1)).weight_init("XAVIER")
            .list([
                LearnedSelfAttentionLayer(n_out=4, n_heads=2, n_queries=3),
                LastTimeStep(underlying=SimpleRnn(n_out=4, activation="tanh")),
                OutputLayer(n_out=2, loss="mcxent", activation="softmax"),
            ]).set_input_type(InputType.recurrent(5, 6)).build())
    net = MultiLayerNetwork(conf).init()
    net.fit(x, y, features_mask=mask)   # must not crash
    assert np.isfinite(net.score())


def test_wrapper_dropout_applied():
    x = jnp.ones((4, 5, 3))
    layer = Bidirectional(fwd=SimpleRnn(n_out=4, activation="tanh"),
                          dropout=0.5, weight_init="XAVIER")
    params, state, _ = layer.initialize(KEY, InputType.recurrent(3, 5))
    y1, _ = layer.apply(params, state, x, train=True,
                        rng=jax.random.PRNGKey(1))
    y2, _ = layer.apply(params, state, x, train=False, rng=None)
    assert not np.allclose(np.asarray(y1), np.asarray(y2))


def test_learned_self_attention_rejects_no_projection():
    import pytest
    layer = LearnedSelfAttentionLayer(n_out=4, n_queries=2,
                                      project_input=False)
    with np.testing.assert_raises(ValueError):
        layer.initialize(KEY, InputType.recurrent(4, 5))


def test_gru_layer_trains_and_serializes(tmp_path):
    """GRU (exceeds-reference layer): converges on the sequence-sum sign
    task, config/params round-trip through the zip."""
    from deeplearning4j_tpu.nn import GRU

    rng = np.random.RandomState(0)
    x = rng.randn(128, 10, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[
        (x.sum((1, 2)) > 0).astype(int)]
    conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
            .list([GRU(n_out=16),
                   LastTimeStep(underlying=GRU(n_out=8)),
                   OutputLayer(n_out=2, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.recurrent(4, 10)).build())
    net = MultiLayerNetwork(conf).init()
    first = net.score_for(x, y)
    for _ in range(60):
        net.fit(x, y)
    assert net.score_for(x, y) < first * 0.5
    p = str(tmp_path / "gru.zip")
    net.save(p)
    net2 = MultiLayerNetwork.load(p)
    np.testing.assert_array_equal(np.asarray(net.params()),
                                  np.asarray(net2.params()))
    np.testing.assert_allclose(np.asarray(net.output(x[:8])),
                               np.asarray(net2.output(x[:8])), atol=0)


def test_gru_mask_equals_truncated_sequence():
    """A [B,T] mask zeroing the tail must give the same last valid hidden
    state as physically truncating the sequence (state held at pads)."""
    from deeplearning4j_tpu.nn import GRU

    rng = np.random.RandomState(4)
    layer = GRU(n_out=6)
    params, state, _ = layer.initialize(jax.random.PRNGKey(0),
                                        InputType.recurrent(3, 8))
    x = rng.randn(2, 8, 3).astype(np.float32)
    mask = np.ones((2, 8), np.float32)
    mask[:, 5:] = 0.0
    out_m, _ = layer.apply(params, state, jnp.asarray(x),
                           mask=jnp.asarray(mask))
    out_t, _ = layer.apply(params, state, jnp.asarray(x[:, :5]))
    # last valid step matches the truncated run's last step
    np.testing.assert_allclose(np.asarray(out_m[:, 4]),
                               np.asarray(out_t[:, 4]), atol=1e-6)
    # padded steps are zeroed in the output
    assert float(np.abs(np.asarray(out_m[:, 5:])).max()) == 0.0
