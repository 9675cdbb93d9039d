"""The yardstick against the system at tiny size on the CPU: each family's
`reference_forward` (logits and loss) and `flops_per_item`."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.models import bert, resnet  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def fixture(name, **changes):
    cfg = harness.load_json(os.path.join(FIXTURES, name))
    cfg.update(changes)
    return cfg


# Tolerances.  In float32 the system and the reference differ only in the
# order of sums (fused kernels, one-pass moments, scan against a loop):
# 1e-4 of the logits' scale.  In bfloat16 (8 bits of mantissa, 0.4% a
# rounding) a few layers give about 1%; the bound is 5%, which float8 or a
# wrong formula would not fit.
F32_TOL, BF16_TOL = 1e-4, 5e-2


@pytest.mark.parametrize("dtype,tol", [(None, F32_TOL),
                                       ("bfloat16", BF16_TOL)])
def test_resnet_reference_matches_system(dtype, tol):
    cfg = fixture("resnet_tiny.json", compute_dtype=dtype)
    net = resnet.build(cfg, seed=3)
    batch = resnet.make_pool(cfg, {"pool_batches": 1}, 3, 8)[0]
    net.fit(batch.features, batch.labels)        # running statistics move
    got = resnet.reference_check(net, cfg, batch, 8)
    assert got["rel_err"] <= tol
    assert abs(got["loss"] - got["loss_reference"]) \
        <= tol * abs(got["loss_reference"])


def test_resnet_reference_inference_mode_matches_output():
    """With the running statistics, against `output()` in inference mode
    (what serving runs), float32."""
    import jax
    cfg = fixture("resnet_tiny.json", compute_dtype=None)
    net = resnet.build(cfg, seed=4)
    batch = resnet.make_pool(cfg, {"pool_batches": 1}, 4, 8)[0]
    for _ in range(3):
        net.fit(batch.features, batch.labels)
    (got,) = net.output(batch.features)
    want = jax.jit(lambda p, s, x: resnet.reference_forward(cfg, p, s, x))(
        net.params_, net.state_, batch.features)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=F32_TOL, rtol=0)


@pytest.mark.parametrize("dtype,tol", [("float32", F32_TOL),
                                       ("bfloat16", BF16_TOL)])
def test_bert_reference_matches_system(dtype, tol):
    cfg = fixture("bert_tiny.json", compute_dtype=dtype)
    traffic = {"pool_batches": 1, "seq_len": 32, "mask_rate": 0.15}
    model = bert.build(cfg, seed=5)
    batch = bert.make_pool(cfg, traffic, 5, 4)[0]
    model.fit_batch(batch)
    got = bert.reference_check(model, cfg, batch, 4)
    assert got["rel_err"] <= tol
    assert abs(got["loss"] - got["loss_reference"]) \
        <= tol * abs(got["loss_reference"])


def test_bert_reference_honours_the_attention_mask():
    """Masked keys change nothing for the kept positions' logits."""
    cfg = fixture("bert_tiny.json", compute_dtype="float32")
    model = bert.build(cfg, seed=6)
    rng = np.random.default_rng(6)
    ids = rng.integers(0, cfg["vocab_size"], (2, 16), dtype=np.int32)
    mask = np.ones((2, 16), np.float32)
    mask[:, 12:] = 0.0
    other = ids.copy()
    other[:, 12:] = (other[:, 12:] + 1) % cfg["vocab_size"]
    a = np.asarray(bert.reference_forward(cfg, model.params_, ids, mask))
    b = np.asarray(bert.reference_forward(cfg, model.params_, other, mask))
    np.testing.assert_allclose(a[:, :12], b[:, :12], atol=1e-5)
    got = np.asarray(model.output_mlm(ids, mask))
    np.testing.assert_allclose(got[:, :12], a[:, :12], atol=1e-3)


def test_conv_flops_by_hand():
    # the stem of ResNet-50: 7x7, 3 -> 64, stride 2 on 224x224 -> 112x112
    # 2 * 112 * 112 * 49 * 3 * 64 = 236,027,904
    cfg = harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                         "resnet50.json"))
    stem = resnet.conv_table(cfg)[0]
    assert (stem["oh"], stem["ow"], stem["cin"], stem["cout"]) == (
        112, 112, 3, 64)
    assert resnet.conv_flops(stem) == 236_027_904
    assert len(resnet.conv_table(cfg)) == 53      # 1 + 16 * 3 + 4 projections


def test_resnet50_forward_flops_match_the_published_count():
    """He et al. give 3.8e9 multiply-adds for the 50-layer net (Table 1);
    with this zoo's stride on the first 1x1 and its projections the count
    is 3.86e9, i.e. 7.7e9 FLOPs."""
    cfg = harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                         "resnet50.json"))
    fwd = resnet.forward_flops(cfg)
    assert 7.6e9 < fwd < 7.8e9
    train = resnet.flops_per_item(cfg, {})
    assert train == 3 * fwd - 236_027_904


def test_transformer_layer_flops_by_hand():
    # BERT-base layer, one token of a 512-token sequence:
    # projections 4 * 768^2 = 2,359,296; FFN 2 * 768 * 3072 = 4,718,592;
    # x 2 FLOPs = 14,155,776; attention 2 * 2 * 512 * 768 = 1,572,864
    assert bert.layer_flops_per_token(768, 3072, 512) == 15_728_640


def test_bert_flops_per_sequence_by_hand():
    cfg = harness.load_json(os.path.join(ROOT, "benchmark", "configs",
                                         "bert_base.json"))
    traffic = {"seq_len": 512, "mask_rate": 0.15}
    head = 2 * (768 * 768 + 768 * 30522)
    fwd = 512 * (12 * 15_728_640 + 0.15 * head)
    assert bert.flops_per_item(cfg, traffic, training=False) \
        == pytest.approx(fwd)
    assert bert.flops_per_item(cfg, traffic) == pytest.approx(3 * fwd)
