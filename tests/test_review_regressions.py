"""Regression tests for review findings (round 1)."""
import json

import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import (
    DenseLayer, InputType, MultiLayerNetwork, NeuralNetConfiguration,
    OutputLayer, BatchNormalizationLayer)
from deeplearning4j_tpu.ops.losses import mse, xent
from deeplearning4j_tpu.train import Adam, AdamW, MapSchedule
from deeplearning4j_tpu.train.updaters import IUpdater


def test_adamw_applies_weight_decay():
    params = {"W": jnp.ones((3, 3))}
    grads = {"W": jnp.zeros((3, 3))}
    u = AdamW(1e-2, weight_decay=0.1)
    upd, _ = u.apply(u.init_state(params), grads, 0, params=params)
    # zero grads -> update is purely lr*wd*p
    np.testing.assert_allclose(np.asarray(upd["W"]), 1e-2 * 0.1, rtol=1e-6)
    plain, _ = Adam(1e-2).apply(Adam(1e-2).init_state(params), grads, 0,
                                params=params)
    assert not np.allclose(np.asarray(upd["W"]), np.asarray(plain["W"]))


def test_score_for_uses_eval_mode_batchnorm():
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3))
            .list([DenseLayer(n_out=4, activation="identity",
                              weight_init="XAVIER"),
                   BatchNormalizationLayer(),
                   OutputLayer(n_out=2, loss="mcxent", activation="softmax",
                               weight_init="XAVIER")])
            .set_input_type(InputType.feed_forward(3)).build())
    net = MultiLayerNetwork(conf).init()
    x = np.random.default_rng(0).normal(5.0, 1.0, (16, 3)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[np.random.default_rng(1).integers(0, 2, 16)]
    # eval-mode score must agree with loss computed from output() probs
    # (clip at f32-tiny, not 1e-7 — untrained logits legitimately exceed ±16)
    probs = np.asarray(net.output(x))
    manual = -np.mean(np.sum(y * np.log(np.clip(probs, 1e-37, 1)), axis=-1))
    assert abs(net.score_for(x, y) - manual) < 1e-3
    # and it must NOT equal the train-mode (batch-stats) loss
    train_loss = float(net._loss(net.params_, net.state_, jnp.asarray(x),
                                 jnp.asarray(y), None, train=True)[0])
    assert abs(net.score_for(x, y) - train_loss) > 0.1


def test_masked_timeseries_losses():
    labels = jnp.ones((2, 4, 3))
    preds = jnp.zeros((2, 4, 3))
    mask = jnp.array([[1, 1, 0, 0], [1, 1, 1, 1]], jnp.float32)
    # mse: masked timesteps excluded; all errors are 1 -> mean = 1
    assert float(mse(labels, preds, mask)) == 1.0
    # unmasked differs when preds nonzero in masked region
    preds2 = preds.at[0, 3].set(100.0)
    assert float(mse(labels, preds2, mask)) == float(mse(labels, preds, mask))
    # xent with [batch, time] mask runs without shape errors
    assert np.isfinite(float(xent(labels, preds, mask)))


def test_mapschedule_json_roundtrip():
    u = Adam(MapSchedule({0: 0.1, 10: 0.01}))
    u2 = IUpdater.from_json(json.loads(json.dumps(u.to_json())))
    assert float(u2.lr_at(5)) == 0.1
    assert float(u2.lr_at(15)) == 0.01


def test_labels_mask_threaded_from_dataset():
    from deeplearning4j_tpu.data import DataSet, ListDataSetIterator
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-2))
            .list([DenseLayer(n_out=4, activation="tanh", weight_init="XAVIER"),
                   OutputLayer(n_out=2, loss="mse", activation="identity",
                               weight_init="XAVIER")])
            .set_input_type(InputType.recurrent(3, 4)).build())
    net = MultiLayerNetwork(conf).init()
    x = np.random.default_rng(0).normal(size=(2, 4, 3)).astype(np.float32)
    y = np.zeros((2, 4, 2), np.float32)
    lmask = np.array([[1, 1, 0, 0], [1, 1, 1, 1]], np.float32)
    ds = DataSet(x, y, labels_mask=lmask)
    net.fit(ListDataSetIterator([ds]))  # must run with mask threading
    assert np.isfinite(net.score())


# ---- round 2: review findings ----

def _tiny_net(seed=0):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(1e-2))
            .list([DenseLayer(n_out=8, activation="relu"),
                   OutputLayer(n_out=2, loss="mcxent", activation="softmax")])
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def _xy(n=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]
    return x, y


def test_transfer_learning_does_not_alias_donated_buffers():
    """ADVICE r1 (medium): fit() on the derived net must not delete the
    source net's buffers via donation."""
    from deeplearning4j_tpu.nn.transferlearning import (TransferLearning,
                                                        TransferLearningHelper)
    x, y = _xy()
    src = _tiny_net()
    src.fit(x, y)
    derived = TransferLearning.builder(src).set_feature_extractor(0).build()
    derived.fit(x, y)
    out = np.asarray(src.output(x))          # must not raise "deleted"
    assert np.all(np.isfinite(out))

    from deeplearning4j_tpu.data.dataset import DataSet
    helper = TransferLearningHelper(src, frozen_till=0)
    feat = helper.featurize(DataSet(x, y))
    helper.fit_featurized(feat)              # donates unfrozen-net buffers
    out2 = np.asarray(src.output(x))         # source must stay intact
    assert np.all(np.isfinite(out2))


def test_inmemory_saver_best_survives_later_fit():
    """ADVICE r1: restoring best then fitting must not destroy the stored
    snapshot for subsequent restores."""
    from deeplearning4j_tpu.train.earlystopping import InMemoryModelSaver
    x, y = _xy()
    net = _tiny_net()
    net.fit(x, y)
    saver = InMemoryModelSaver()
    saver.save_best_model(net)
    best_params = np.asarray(saver._best[0]["layer_0"]["W"]).copy()
    m = saver.get_best_model()
    m.fit(x, y)                               # donates the restored buffers
    m2 = saver.get_best_model()               # must still restore cleanly
    np.testing.assert_allclose(
        np.asarray(m2.params_["layer_0"]["W"]), best_params)


def test_checkpoint_listener_epoch_cadence(tmp_path):
    """ADVICE r1: every_n_epochs=2 fires after epochs 2,4,... not 1,3."""
    from deeplearning4j_tpu.train.listeners import CheckpointListener

    class FakeModel:
        epoch = 0
        iteration = 0

        def save(self, path):
            with open(path, "w") as f:
                f.write("x")

    lst = CheckpointListener(str(tmp_path), every_n_epochs=2)
    m = FakeModel()
    fired = []
    for ep in range(1, 5):
        m.epoch = ep                          # completed epochs count
        before = len(lst._saved)
        lst.on_epoch_end(m)
        if len(lst._saved) > before:
            fired.append(ep)
    assert fired == [2, 4]


def test_gather_indexed_rejects_out_of_range():
    """ADVICE r1: native path must validate indices, not memcpy OOB."""
    from deeplearning4j_tpu.native_ops import gather_indexed
    base = np.arange(12, dtype=np.float32).reshape(4, 3)
    np.testing.assert_array_equal(gather_indexed(base, [2, 0]),
                                  base[[2, 0]])
    for bad in ([-1], [4], [0, 100]):
        try:
            gather_indexed(base, bad)
            assert False, f"expected IndexError for {bad}"
        except IndexError:
            pass
