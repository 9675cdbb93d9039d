"""BERT model tests: masked-LM + classification training, serde, shapes."""
import functools

import numpy as np
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nlp import BertIterator, BertWordPieceTokenizer
from deeplearning4j_tpu.zoo import BertConfig, BertModel
from deeplearning4j_tpu.train.updaters import Adam


VOCAB = (["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"]
         + [f"w{i}" for i in range(95)])


def _tok():
    return BertWordPieceTokenizer(VOCAB)


def _sentences(n=32, seed=0):
    rng = np.random.RandomState(seed)
    # structured sentences: wK follows wK-1 — learnable co-occurrence
    out = []
    for _ in range(n):
        start = rng.randint(0, 80)
        out.append(" ".join(f"w{start + j}" for j in range(8)))
    return out


def test_bert_mlm_trains():
    model = BertModel(BertConfig.tiny(), seed=0, updater=Adam(1e-3))
    it = BertIterator(_tok(), _sentences(), batch_size=8, max_length=16,
                      task=BertIterator.TASK_UNSUPERVISED, seed=1)
    losses = []
    for _ in range(6):
        if hasattr(it, "reset"):
            it.reset()
        for mds in it:
            losses.append(model.fit_batch(mds))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_bert_classification_trains():
    cfg = BertConfig.tiny(n_classes=2)
    model = BertModel(cfg, seed=0, updater=Adam(1e-3))
    sents = _sentences(32)
    # label = whether sentence contains w10
    labels = [1 if "w10" in s.split() else 0 for s in sents]
    it = BertIterator(_tok(), sents, batch_size=8, max_length=16,
                      task=BertIterator.TASK_SEQ_CLASSIFICATION,
                      labels=labels, n_classes=2)
    first = None
    for _ in range(10):
        for mds in it:
            loss = model.fit_batch(mds)
            if first is None:
                first = loss
    assert loss < first
    ids = np.zeros((2, 16), np.int32)
    mask = np.ones((2, 16), np.float32)
    probs = np.asarray(model.output_cls(ids, mask))
    assert probs.shape == (2, 2)
    np.testing.assert_allclose(probs.sum(1), 1.0, atol=1e-5)


def test_bert_hidden_and_mlm_shapes():
    cfg = BertConfig.tiny()
    model = BertModel(cfg)
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (3, 12))
    mask = np.ones((3, 12), np.float32)
    h = np.asarray(model.output_hidden(ids, mask))
    assert h.shape == (3, 12, cfg.hidden)
    logits = np.asarray(model.output_mlm(ids, mask))
    assert logits.shape == (3, 12, cfg.vocab_size)


def test_bert_bf16_compute():
    cfg = BertConfig.tiny(compute_dtype="bfloat16")
    model = BertModel(cfg, updater=Adam(1e-3))
    it = BertIterator(_tok(), _sentences(16), batch_size=8, max_length=16,
                      seed=2)
    for mds in it:
        loss = model.fit_batch(mds)
    assert np.isfinite(loss)
    # master params stay f32
    assert model.params_["tok_emb"].dtype == jnp.float32


def test_bert_save_load_resume(tmp_path):
    model = BertModel(BertConfig.tiny(), updater=Adam(1e-3))
    it = BertIterator(_tok(), _sentences(16), batch_size=8, max_length=16)
    for mds in it:
        model.fit_batch(mds)
    p = str(tmp_path / "bert.zip")
    model.save(p)
    m2 = BertModel.load(p)
    assert m2.iteration == model.iteration
    ids = np.zeros((1, 8), np.int32)
    mask = np.ones((1, 8), np.float32)
    np.testing.assert_allclose(np.asarray(model.output_hidden(ids, mask)),
                               np.asarray(m2.output_hidden(ids, mask)),
                               rtol=1e-5, atol=1e-6)
    # updater state round-trips: one more identical step matches
    it2 = BertIterator(_tok(), _sentences(16), batch_size=8, max_length=16)
    mds = next(iter(it2))
    l1 = model.fit_batch(mds)
    l2 = m2.fit_batch(mds)
    assert np.isclose(l1, l2, rtol=1e-4)


def test_bert_fit_steps_matches_sequential():
    """fit_steps (k steps fused into one lax.scan dispatch) must match k
    sequential fit_batch calls bit-exactly on the MLM path."""
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    import jax

    rng = np.random.RandomState(0)
    k, b, t, vocab = 4, 8, 16, 100
    ids = rng.randint(0, vocab, (k, b, t)).astype(np.int32)
    mask = np.ones((k, b, t), np.float32)
    lmask = (rng.rand(k, b, t) < 0.15).astype(np.float32)

    a = BertModel(BertConfig.tiny(), seed=0, updater=Adam(1e-3))
    b_ = BertModel(BertConfig.tiny(), seed=0, updater=Adam(1e-3))
    seq_losses = []
    for i in range(k):
        mds = MultiDataSet(features=[ids[i], mask[i]], labels=[ids[i]],
                           labels_masks=[lmask[i]])
        seq_losses.append(float(a.fit_batch(mds)))
    stacked = MultiDataSet(features=[ids, mask], labels=[ids],
                           labels_masks=[lmask])
    losses = b_.fit_steps(stacked)
    np.testing.assert_allclose(np.asarray(losses), seq_losses, rtol=1e-6)
    for la, lb in zip(jax.tree_util.tree_leaves(a.params_),
                      jax.tree_util.tree_leaves(b_.params_)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    assert a.iteration == b_.iteration == k


def test_bert_fit_iterator_fused_matches_sequential():
    """BertModel.fit(iterator, fused_steps=2) == plain fit(iterator)."""
    import jax

    def run(fused):
        model = BertModel(BertConfig.tiny(), seed=0, updater=Adam(1e-3))
        it = BertIterator(_tok(), _sentences(), batch_size=8, max_length=16,
                          task=BertIterator.TASK_UNSUPERVISED, seed=1)
        model.fit(it, epochs=2, fused_steps=2 if fused else 1)
        return model

    a, b = run(False), run(True)
    assert a.iteration == b.iteration
    for la, lb in zip(jax.tree_util.tree_leaves(a.params_),
                      jax.tree_util.tree_leaves(b.params_)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---- the masked-LM train head runs on the labelled positions only ----------
# 8 x 256 positions: `_head_capacity` gives 512 rows a pass (at 8 x 16 the
# capacity covers every position and the head runs dense)

_B, _T = 8, 256
_CAP = 512


def _big_model(**kw):
    return BertModel(BertConfig.tiny(max_len=_T, **kw), seed=0,
                     updater=Adam(1e-3))


def _mask_with(n, seed=0, weights=None):
    """[B, T] label mask with exactly `n` non-zero entries."""
    rng = np.random.RandomState(seed)
    flat = np.zeros(_B * _T, np.float32)
    at = rng.permutation(_B * _T)[:n]
    flat[at] = 1.0 if weights is None else weights(rng, n)
    return flat.reshape(_B, _T)


def _dense_mlm_loss(model, params, ids, input_mask, labels, label_mask):
    """The dense formula: the head at every position in plain jnp, the
    per-token loss multiplied by the mask."""
    import jax
    c = model.config
    h = model._encode(params, ids, input_mask)
    y = jax.nn.gelu(h @ params["mlm_W"] + params["mlm_b"])
    mu = jnp.mean(y, -1, keepdims=True)
    var = jnp.mean((y - mu) ** 2, -1, keepdims=True)
    y = (y - mu) / jnp.sqrt(var + c.eps) * params["mlm_ln_g"] \
        + params["mlm_ln_b"]
    lp = jax.nn.log_softmax(y @ params["tok_emb"].T + params["mlm_bias"], -1)
    if labels.ndim == 2:
        per_tok = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    else:
        per_tok = -jnp.sum(labels * lp, -1)
    return jnp.sum(per_tok * label_mask) / jnp.maximum(jnp.sum(label_mask),
                                                       1.0)


_MLM_CASES = {
    # name: (labelled positions, weights, one-hot labels, compute dtype)
    "none": (0, None, False, "float32"),
    "few": (5, None, False, "float32"),
    "exactly_capacity": (_CAP, None, False, "float32"),
    "capacity_plus_one": (_CAP + 1, None, False, "float32"),
    "every_position": (_B * _T, None, False, "float32"),
    "fractional_weights": (300, lambda rng, n: rng.uniform(0.1, 2.0, n),
                           False, "float32"),
    "one_hot_labels": (_CAP + 40, None, True, "float32"),
    "bfloat16_compute": (300, None, False, "bfloat16"),
}


@functools.lru_cache(maxsize=None)
def _mlm_fn(one_hot, dtype):
    """(model, new loss+grads, dense loss+grads), compiled once per label
    form and compute dtype."""
    import jax
    model = _big_model(compute_dtype=dtype)
    new = jax.jit(jax.value_and_grad(model._mlm_loss, has_aux=True))
    dense = jax.jit(jax.value_and_grad(lambda *a: _dense_mlm_loss(model, *a)))
    return model, new, dense


@pytest.mark.parametrize("case", list(_MLM_CASES))
def test_bert_mlm_loss_and_gradients_equal_the_dense_formula(case):
    import jax
    from deeplearning4j_tpu.zoo.bert import _head_capacity
    assert _head_capacity(_B * _T) == _CAP
    n, weights, one_hot, dtype = _MLM_CASES[case]
    model, new, dense = _mlm_fn(one_hot, dtype)
    rng = np.random.RandomState(1)
    vocab = model.config.vocab_size
    ids = rng.randint(0, vocab, (_B, _T)).astype(np.int32)
    mask = np.ones((_B, _T), np.float32)
    mask[:, -7:] = 0.0
    label_ids = rng.randint(0, vocab, (_B, _T)).astype(np.int32)
    labels = np.eye(vocab, dtype=np.float32)[label_ids] if one_hot \
        else label_ids
    lmask = _mask_with(n, seed=2, weights=weights)

    (loss, report), grads = new(model.params_, ids, mask, labels, lmask)
    want_loss, want = dense(model.params_, ids, mask, labels, lmask)
    assert [int(v) for v in report] == [n, -(-n // _CAP), _CAP]
    # bf16: the encoder's backward rounds d hidden to 8 bits of mantissa,
    # and the two sides add it up in different orders
    rtol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=rtol)
    scale = max(float(jnp.max(jnp.abs(g)))
                for g in jax.tree_util.tree_leaves(want))
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=rtol, atol=rtol * scale,
            err_msg=jax.tree_util.keystr(path))
    if n == 0:
        assert float(loss) == 0.0


def _mlm_block(ns, seed=0):
    """k batches of 8 x 256 with ns[i] labelled positions in step i."""
    rng = np.random.RandomState(seed)
    k = len(ns)
    ids = rng.randint(0, 100, (k, _B, _T)).astype(np.int32)
    mask = np.ones((k, _B, _T), np.float32)
    lmask = np.stack([_mask_with(n, seed=seed + i) for i, n in enumerate(ns)])
    return ids, mask, lmask


def test_bert_fit_steps_matches_sequential_on_both_sides_of_capacity():
    import jax
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    ns = [300, _CAP + 1, 0, _B * _T]
    ids, mask, lmask = _mlm_block(ns)
    a, b = _big_model(), _big_model()
    seq_losses = [float(a.fit_batch(MultiDataSet(
        features=[ids[i], mask[i]], labels=[ids[i]],
        labels_masks=[lmask[i]]))) for i in range(len(ns))]
    losses = b.fit_steps(MultiDataSet(features=[ids, mask], labels=[ids],
                                      labels_masks=[lmask]))
    np.testing.assert_allclose(np.asarray(losses), seq_losses, rtol=1e-6)
    for la, lb in zip(jax.tree_util.tree_leaves(a.params_),
                      jax.tree_util.tree_leaves(b.params_)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-6, atol=1e-7)
    assert a.iteration == b.iteration == len(ns)
    assert a.mlm_head_stats() == b.mlm_head_stats()


def test_bert_mlm_head_stats_counts_passes_without_recompiling():
    import jax
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    model = _big_model()
    assert model.mlm_head_stats() == {
        "steps": 0, "gathered_steps": 0, "fallback_steps": 0,
        "max_labelled": 0, "capacity": 0}
    ns = [300, 20, _CAP, _CAP + 1, 3 * _CAP, 0]
    ids, mask, lmask = _mlm_block(ns, seed=3)
    for i, n in enumerate(ns):
        model.fit_batch(MultiDataSet(features=[ids[i], mask[i]],
                                     labels=[ids[i]],
                                     labels_masks=[lmask[i]]))
        if i == 0:
            model.mlm_head_stats()        # the read compiles nothing later
            first = len(compiles)
    assert len(compiles) == first         # one program for every count
    assert model.mlm_head_stats() == {
        "steps": 6, "gathered_steps": 4, "fallback_steps": 2,
        "max_labelled": 3 * _CAP, "capacity": _CAP}
    # a fused block folds its steps into the same counters
    model.fit_steps(MultiDataSet(features=[ids[:4], mask[:4]],
                                 labels=[ids[:4]], labels_masks=[lmask[:4]]))
    got = model.mlm_head_stats()
    assert (got["steps"], got["fallback_steps"]) == (10, 3)
    # at 8 x 16 the capacity is every position: one dense pass, never more
    tiny = BertModel(BertConfig.tiny(), seed=0, updater=Adam(1e-3))
    t_ids = np.zeros((8, 16), np.int32)
    tiny.fit_batch(MultiDataSet(
        features=[t_ids, np.ones((8, 16), np.float32)], labels=[t_ids],
        labels_masks=[np.ones((8, 16), np.float32)]))
    assert tiny.mlm_head_stats() == {
        "steps": 1, "gathered_steps": 1, "fallback_steps": 0,
        "max_labelled": 128, "capacity": 128}


def test_bert_mlm_step_writes_no_positions_by_vocab_array():
    """The train step at 8 x 256 holds head arrays of [capacity, vocab] and
    none of [B*T, vocab], lowered or compiled: differentiating the step
    keeps nothing of that size either (the loss and its gradients are taken
    together inside the pass loop)."""
    import re
    from deeplearning4j_tpu.utils.counters import device_counters
    model = _big_model()
    vocab = model.config.vocab_size
    ids, mask, lmask = (a[0] for a in _mlm_block([300]))
    it, ep = device_counters(model)
    lowered = model._step("mlm").lower(
        model.params_, model.opt_state_, it, ep, ids, mask, ids, lmask)
    full = [(_B * _T, vocab), (_B, _T, vocab), (vocab, _B * _T)]
    stablehlo = set(re.findall(r"tensor<([0-9x]+)x[a-z]", lowered.as_text()))
    hlo = set(re.findall(r"\[([0-9,]+)\]", lowered.compile().as_text()))
    assert f"{_CAP}x{vocab}" in stablehlo and f"{_CAP},{vocab}" in hlo
    for shape in full:
        assert "x".join(map(str, shape)) not in stablehlo
        assert ",".join(map(str, shape)) not in hlo
