"""Family `lfm2_moe` at tiny size on the CPU: the plain reference against
the system (logits, loss, gradients) on the 5-layer cut and on a 12-layer
list with a period scan and a remainder, the share it is given, the required
work against hand counts, the `train_loop` driver end to end, and the cell's
per-layer readers on hand-built input."""
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import harness  # noqa: E402
from benchmark.models import lfm2_moe as family  # noqa: E402
from benchmark.trace import reduce as tr  # noqa: E402
from benchmark.trace import scopes  # noqa: E402
from test_harness import drive  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
TRAFFIC = {"driver": "train_loop", "batch_per_chip": 2, "pool_batches": 4,
           "mesh": None, "check_rows": 1, "loss_rows": 1, "seq_len": 32,
           "zipf_exponent": 1.0}
MANIFEST = harness.load_manifest()
LFM2 = harness.load_config(MANIFEST, "lfm2_24b_a2b")
CELL = harness.load_cell(MANIFEST, "lfm2_24b_train_s8192")
CELL_TRAFFIC = CELL.traffic
# the fixture's layers 1..5, `c a c c c` with one dense layer (the cell's
# cut), and its whole 12-layer list `c c a c c c a c c c a c` with two: two
# whole periods under the scan and a remainder `a c` after it
LISTS = {"cut": {}, "periods_and_remainder": {
    "first_layer_held": 0, "num_layers": 12, "num_dense_layers": 2}}


def fixture(**changes):
    cfg = harness.load_json(os.path.join(FIXTURES, "lfm2_moe_tiny.json"))
    cfg.update(changes)
    return cfg


# ---------------------------------------------------------------------------
# the reference against the system
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layers", list(LISTS))
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 2e-2)])
def test_reference_matches_system(dtype, tol, layers):
    """Logits and loss after a few steps (so the selection bias is no longer
    zero), on a share of the experts (2..5 of 8)."""
    cfg = fixture(compute_dtype=dtype, **LISTS[layers])
    model = family.build(cfg, seed=3)
    if layers == "periods_and_remainder":
        assert model.config.layout()[1:] == (
            ("full_attention", "conv", "conv", "conv"), 2,
            ("full_attention", "conv"))
    batch = family.make_pool(cfg, TRAFFIC, 3, 2)[0]
    for _ in range(3):
        model.fit_batch(batch)
    assert np.any(np.asarray(model.state_["router_bias"]))
    got = family.reference_check(model, cfg, batch, 2)
    assert got["rel_err"] <= tol
    assert abs(got["loss"] - got["loss_reference"]) \
        <= tol * abs(got["loss_reference"])
    assert got["tol"] == 0.05 and got["loss_tol"] == 0.02


@pytest.mark.parametrize("layers", list(LISTS))
def test_reference_gradients_match_one_train_steps_gradients(layers):
    """`jax.grad` of the reference's loss against the gradients the system's
    train step takes, float32, seeded weights."""
    cfg = fixture(compute_dtype="float32", **LISTS[layers])
    model = family.build(cfg, seed=5)
    batch = family.make_pool(cfg, TRAFFIC, 5, 2)[0]
    ids, labels = (jnp.asarray(batch.features[0]),
                   jnp.asarray(batch.labels[0]))
    bias = model.state_["router_bias"]
    (loss, _), got = jax.jit(jax.value_and_grad(model._loss, has_aux=True))(
        model.params_, bias, ids, labels)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: family.reference_loss(cfg, p, bias, ids, labels)))(
            model.params_)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) > 40
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-3 * scale, \
            jax.tree_util.keystr(path)


def test_reference_in_a_lower_precision_reads_higher():
    """The reference computed with every product's operands rounded to
    float8 (the nearest precision below the bfloat16 the configuration
    states) reads several times what it reads rounded to bfloat16, and one
    held expert's term dropped is outside what the float32 comparison
    above allows."""
    cfg = fixture()
    model = family.build(cfg, seed=6)
    batch = family.make_pool(cfg, TRAFFIC, 6, 2)[0]
    ids = batch.features[0]
    bias = model.state_["router_bias"]
    want = family.reference_jitted(cfg, model.params_, bias, ids)
    fp8 = family.reference_jitted(cfg, model.params_, bias, ids,
                                  round_to=jnp.float8_e4m3fn)
    bf16 = family.reference_jitted(cfg, model.params_, bias, ids,
                                   round_to=jnp.bfloat16)
    # at these widths of tens float8 reads 0.032 and bfloat16 0.002; at the
    # published widths, on the chip, 0.070 and 0.013 around the cell's limit
    # (the configuration's `tolerance.why`)
    assert family.rel_rms(bf16, want) < LFM2["tolerance"]["output_rel"] / 4
    assert family.rel_rms(fp8, want) > 5 * family.rel_rms(bf16, want)
    # a dropped term: one held expert's down-projection zeroed in the
    # attention layer of the period
    dropped = dict(model.params_)
    first = dict(dropped["moe"][0])
    first["w_down"] = first["w_down"].at[:, 0].set(0.0)
    dropped["moe"] = (first,) + tuple(dropped["moe"][1:])
    assert family.rel_rms(
        family.reference_jitted(cfg, dropped, bias, ids), want) > 3 * 1e-4


def test_the_reference_is_given_the_share():
    """Held experts 2..5: the same matrices read as experts 0..3 give other
    logits."""
    cfg = fixture(compute_dtype="float32")
    model = family.build(cfg, seed=7)
    ids = family.make_pool(cfg, TRAFFIC, 7, 2)[0].features[0]
    bias = model.state_["router_bias"]
    base = np.asarray(family.reference_forward(cfg, model.params_, bias, ids))
    moved = dict(cfg, first_expert_held=0)
    assert np.abs(np.asarray(family.reference_forward(
        moved, model.params_, bias, ids)) - base).max() > 1e-4


def test_the_reference_reads_the_layers_in_the_lists_order():
    """`layers_of` on the 12-layer list: two dense layers, two periods
    unstacked layer by layer, then the remainder; a tree that does not fit
    the list is refused."""
    cfg = fixture(**LISTS["periods_and_remainder"])
    model = family.build(cfg, seed=8)
    bias = np.arange(10 * 8, dtype=np.float32).reshape(10, 8)
    layers = family.layers_of(cfg, model.params_, bias)
    assert [k for k, _, _ in layers] == cfg["layer_types"]
    assert [b is None for _, _, b in layers] == [True] * 2 + [False] * 10
    np.testing.assert_array_equal(
        [b[0] for _, _, b in layers[2:]], bias[:, 0])
    # layer 7 (expert layer 5) is the second period's second layer
    np.testing.assert_array_equal(layers[7][1]["conv_in"],
                                  model.params_["moe"][1]["conv_in"][1])
    np.testing.assert_array_equal(layers[10][1]["Wqkv"],
                                  model.params_["rest"][0]["Wqkv"])
    with pytest.raises(ValueError, match="not the configuration's"):
        family.layers_of(dict(cfg, first_layer_held=1, num_layers=11),
                         model.params_, bias)


# ---------------------------------------------------------------------------
# required work against hand counts
# ---------------------------------------------------------------------------

def test_flops_per_item_against_the_issues_hand_count():
    """LFM2-24B-A2B's share at 8,192 tokens, in MFLOP a token forward: the
    dense layer 178.3 (conv operator 33.6, SwiGLU 144.7); a conv expert
    layer 43.3 (33.6 + routed 9.4 at 0.5 held expert a token + router 0.3);
    the attention expert layer 64.2 (products 21.0, causal scores and values
    33.6, routed 9.4, router 0.3); head 33.6; 406 in all, 1.22 GFLOP
    trained, 9.97 TFLOP a step."""
    dense = family.layer_flops_per_token(LFM2, 8192, "conv", False)
    assert dense == {"conv_products": 2 * (2048 * 6144 + 2048 * 2048),
                     "mlp": 2 * 3 * 2048 * 11776}
    assert round(sum(dense.values()) / 1e6, 1) == 178.3
    conv = family.layer_flops_per_token(LFM2, 8192, "conv", True)
    assert conv["routed"] == 0.5 * 2 * 3 * 2048 * 1536
    assert conv["router"] == 2 * 2048 * 64
    assert round(sum(conv.values()) / 1e6, 1) == 43.3
    att = family.layer_flops_per_token(LFM2, 8192, "full_attention", True)
    assert att["gqa_products"] == 2 * (2048 * (32 + 16) * 64 + 2048 * 2048)
    assert att["attention"] == 2 * 32 * 128 * 8193 / 2
    assert round(sum(att.values()) / 1e6, 1) == 64.2
    fwd = family.flops_per_item(LFM2, CELL_TRAFFIC, training=False)
    assert fwd == 8192 * (sum(dense.values()) + 3 * sum(conv.values())
                          + sum(att.values()) + 2 * 2048 * 8192)
    assert round(fwd / 8192 / 1e6) == 406
    assert family.flops_per_item(LFM2, CELL_TRAFFIC) == 3 * fwd
    assert round(3 * fwd / 1e12, 2) == 9.97       # the issue rounds 9.98
    assert family.held_per_token(LFM2) == 0.5


def test_kernel_work_against_hand_counts():
    """Attention: 32 query heads x 1 layer of an 8,192 lower triangle, 128
    FLOP-pairs a score forward and twice that backward; bytes: q, o, dO, dQ
    once a query head, k, v, dK, dV once a key-value head.  Grouped
    products: 9 of them a layer, 2 x 2048 x 1536 a row."""
    att = family.gqa_attention_work(LFM2, CELL_TRAFFIC, rows=1)
    pairs = 8192 * 8193 / 2
    assert att["flops"] == 32 * 2 * pairs * 128 * 3
    # forward reads q k v, writes o; backward reads q k v o dO, writes dQ
    # dK dV; 64 wide, bf16
    per_query_head = 2 + 4            # q o | q o dO dQ
    per_kv_head = 2 + 4               # k v | k v dK dV
    assert att["bytes"] == 8192 * 64 * 2 * (32 * per_query_head
                                            + 8 * per_kv_head)
    # every head its own key-value head would move 1.6x as much
    assert att["bytes"] * 1.6 == 8192 * 64 * 2 * 32 * 12
    gm = family.grouped_work(LFM2, pairs=4 * 4096, layer_steps=4)
    assert gm["flops"] == 9 * 2 * 4 * 4096 * 2048 * 1536
    assert gm["bytes"] == 9 * 2 * 4 * (4096 * (2048 + 1536)
                                       + 8 * 2048 * 1536)
    # a list with no attention layer requires no attention work
    assert family.gqa_attention_work(
        dict(LFM2, first_layer_held=3, num_layers=3), CELL_TRAFFIC, 1) \
        == {"flops": 0.0, "bytes": 0.0}


def test_the_configuration_file_is_the_catalogs_but_for_the_cut():
    published = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
                 "intermediate_size": 11776, "moe_intermediate_size": 1536,
                 "max_position_embeddings": 128000, "norm_eps": 1e-05,
                 "norm_topk_prob": True, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "num_experts_per_tok": 4,
                 "num_hidden_layers": 40, "routed_scaling_factor": 1,
                 "use_expert_bias": True, "model_type": "lfm2_moe",
                 "rope_parameters": {"rope_theta": 1000000,
                                     "rope_type": "default"},
                 "layer_types": ["conv", "conv", "full_attention",
                                 "conv"] * 10}
    assert {k: LFM2[k] for k in published} == published
    assert LFM2["reduced"] == ["num_layers", "num_dense_layers",
                               "num_experts", "vocab_size"]
    assert (LFM2["num_layers"], LFM2["num_dense_layers"],
            LFM2["num_experts"], LFM2["vocab_size"]) == (5, 1, 8, 8192)
    assert (LFM2["num_dense_layers_published"],
            LFM2["num_experts_published"],
            LFM2["vocab_size_published"]) == (2, 64, 65536)
    assert LFM2["vocab_size_published"] == 8 * LFM2["vocab_size"]
    # published layers 1..5: one of the two dense conv layers, then one
    # whole period of expert layers, 1 attention to 3 conv
    assert family.held_layer_types(LFM2) == LFM2["layer_types_held"] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    c = family.decoder_config(LFM2)
    assert (c.n_experts, c.held, c.first_expert, c.top_k) == (64, 8, 0, 4)
    assert (c.n_heads, c.n_kv_heads, c.head_dim) == (32, 8, 64)
    assert c.layout() == ("conv", ("full_attention", "conv", "conv", "conv"),
                          1, ())
    assert c.tie_embeddings and c.n_shared_experts == 0
    assert (c.router_eps, c.routed_scale, c.eps) == (1e-6, 1.0, 1e-5)
    # 469.3M parameters in its matrices, 7.5 GB of training state at 16
    # bytes each
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    experts = 8 * 3 * 2048 * 1536 + 2048 * 64
    n = (8192 * 2048 + conv + 3 * 2048 * 11776
         + (attention + experts) + 3 * (conv + experts))
    assert round(n / 1e6, 1) == 469.3 and round(16 * n / 1e9, 1) == 7.5


def test_the_published_layer_list_builds():
    """All 40 layers at the fixture's widths: 2 dense, then 9 periods
    `a c c c` under the scan and the remainder `a c`."""
    cfg = fixture(layer_types=LFM2["layer_types"], first_layer_held=0,
                  num_layers=40, num_dense_layers=2)
    model = family.build(cfg, seed=1)
    assert model.config.layout() == (
        "conv", ("full_attention", "conv", "conv", "conv"), 9,
        ("full_attention", "conv"))
    assert model.state_["expert_load"].shape == (38, 8)
    batch = family.make_pool(cfg, TRAFFIC, 1, 2)[0]
    assert np.isfinite(float(model.fit_batch(batch)))
    np.testing.assert_array_equal(model.expert_load().sum(1),
                                  [2 * 32 * 2] * 38)


# ---------------------------------------------------------------------------
# the driver end to end, and the readers
# ---------------------------------------------------------------------------

def _read(name, run):
    return harness.load_layer_metric(name).read(run)


def test_train_loop_end_to_end_on_the_family():
    run = drive("lfm2_moe_tiny.json", TRAFFIC, 1)
    assert run.correct, run.checks
    # a count that holds on a loaded machine: the window is two seconds
    assert run.attempted >= 2 and run.failed == 0
    assert run.end_to_end["train_tokens_per_s"] \
        == pytest.approx(32 * run.end_to_end["train_samples_per_s"])
    assert run.counters["compiles_in_window"] == 0
    # the routing counter, over the window only: every step's tokens chose
    # top-2 in all four expert layers
    load = family.window_expert_load(family.LAST_BUILT)
    np.testing.assert_array_equal(load.sum(1),
                                  [run.counters["steps"] * 2 * 32 * 2] * 4)
    held = family.window_held_load(family.LAST_BUILT)
    np.testing.assert_array_equal(held, load[:, 2:6])
    ratio = _read("routed_load_max_over_mean", run)
    assert ratio == pytest.approx((held.max(1) / held.mean(1)).max())
    assert ratio >= 1.0
    # untraced: the device readers have nothing to read
    for name in ("moe_ms_per_step", "short_conv_ms_per_step",
                 "gqa_attention_ms_per_step", "gqa_flash_roofline_pct",
                 "routed_gmm_roofline_pct"):
        assert _read(name, run) is None


def _ev(name, start, end, scope="", text=""):
    return scopes.ScopedEvent(tr.Event(name, start, end, text), scope)


def _run_with(events, steps=2, pairs=None, monkeypatch=None, cell=CELL):
    """A run record as the readers see it, with hand-built scoped events in
    place of a trace file and, with `pairs`, a model whose routing counter
    says each of 4 expert layers saw `pairs` held pairs a step."""
    run = harness.Run(cell=cell, seed=0, seconds=1.0, traced=True, devices=[],
                      clock=types.SimpleNamespace(marks=[0.0, 1.0], spans=[]),
                      peaks=harness.load_peaks("TPU v5 lite"))
    run.trace = object()
    run.counters.update(steps_traced=steps, rows=1, steps=steps)
    run._scoped_events = events
    if pairs is not None:
        load = np.zeros((4, 64), np.int64)
        load[:, :8] = pairs * 2 * steps / 8        # `steps` untraced + traced
        monkeypatch.setattr(family, "_LOAD_AT_WINDOW_START", None)
        monkeypatch.setattr(family, "LAST_BUILT", types.SimpleNamespace(
            config=family.decoder_config(LFM2),
            state_={"expert_load": load}))
    return run


def test_scope_readers_on_hand_built_events():
    """Self time by scope: `short_conv` and `gqa_attention` are taken from
    the op's scope path, forward (`.../short_conv/mix/...`) and backward
    (`transpose(jvp(gqa_attention))`); a name that only contains one is
    not it."""
    events = [
        _ev("while.1", 0.0, 1.0, "jit(step)/while"),
        _ev("fusion.1", 0.0, 0.3, "jit(step)/while/body/short_conv/mix/mul"),
        _ev("fusion.2", 0.3, 0.4,
            "jit(step)/transpose(jvp(gqa_attention))/mul"),
        _ev("fusion.3", 0.4, 0.5,
            "jit(step)/transpose(jvp())/while/body/checkpoint/short_conv/"
            "in_proj/dot_general"),
        _ev("fusion.4", 0.5, 0.6, "jit(step)/lm_head/dot_general"),
        _ev("fusion.5", 0.6, 0.7, "jit(step)/short_convolution/x"),
        _ev("fusion.6", 0.7, 0.8, "jit(step)/while/body/moe/router/dot"),
    ]
    run = _run_with(events, steps=2)
    assert _read("short_conv_ms_per_step", run) \
        == pytest.approx(1e3 * 0.4 / 2)
    assert _read("gqa_attention_ms_per_step", run) \
        == pytest.approx(1e3 * 0.1 / 2)
    assert _read("moe_ms_per_step", run) == pytest.approx(1e3 * 0.1 / 2)
    # a program without the scopes: nothing to read, no error
    bare = _run_with([_ev("fusion.1", 0.0, 0.3, "jit(step)/dot")], steps=2)
    for name in ("short_conv_ms_per_step", "gqa_attention_ms_per_step"):
        assert _read(name, bare) is None


def test_roofline_readers_on_hand_built_events(monkeypatch):
    """The attention kernels are the Mosaic calls under `gqa_attention`, the
    grouped products those under `moe`; required work over their device
    time, against the v5e's peaks: the larger of the two shares."""
    mosaic = 'custom-call(...), custom_call_target="tpu_custom_call"'
    att = family.gqa_attention_work(LFM2, CELL_TRAFFIC, rows=1)
    t_att = 4 * att["flops"] / 197e12               # a quarter of the peak
    pairs = 4096.0
    gm = family.grouped_work(LFM2, 4 * pairs, layer_steps=4)
    t_gm = 10 * max(gm["flops"] / 197e12, gm["bytes"] / 819e9)
    events = [
        _ev("closed_call.1", 0.0, t_att,
            "jit(step)/transpose(jvp(gqa_attention))/pallas_call", mosaic),
        _ev("fusion.9", 2.0, 2.5, "jit(step)/gqa_attention/dot_general"),
        _ev("closed_call.2", 3.0, 3.0 + t_gm,
            "jit(step)/while/body/moe/experts/pallas_call", mosaic),
    ]
    # one step traced; four expert layers each saw `pairs` pairs a step
    run = _run_with(events, steps=1, pairs=pairs, monkeypatch=monkeypatch)
    assert _read("gqa_flash_roofline_pct", run) == pytest.approx(25.0)
    assert _read("routed_gmm_roofline_pct", run) == pytest.approx(10.0)
    assert _read("routed_load_max_over_mean", run) == pytest.approx(1.0)
    # no kernel of that scope in the trace: nothing to read, no error
    run = _run_with(events[1:2], steps=1, pairs=pairs,
                    monkeypatch=monkeypatch)
    assert _read("gqa_flash_roofline_pct", run) is None
    assert _read("routed_gmm_roofline_pct", run) is None


def test_the_readers_take_the_family_from_the_cell(monkeypatch):
    """On another family's cell the same readers read that family's model
    and work, or nothing where it has none: no module is named in them."""
    kanana = harness.load_cell(MANIFEST, "kanana2_30b_train_s4096")
    other = harness.load_family(kanana.config)
    mosaic = 'custom-call(...), custom_call_target="tpu_custom_call"'
    events = [_ev("closed_call.2", 0.0, 1.0,
                  "jit(step)/while/body/moe/experts/pallas_call", mosaic),
              _ev("closed_call.3", 1.0, 2.0,
                  "jit(step)/gqa_attention/pallas_call", mosaic)]
    load = np.zeros((4, 128), np.int64)
    load[:, :16] = 100
    load[0, 0] = 400
    monkeypatch.setattr(other, "_LOAD_AT_WINDOW_START", None)
    monkeypatch.setattr(other, "LAST_BUILT", types.SimpleNamespace(
        config=other.decoder_config(kanana.config),
        state_={"expert_load": load}))
    run = _run_with(events, steps=1, cell=kanana)
    assert _read("routed_load_max_over_mean", run) \
        == pytest.approx(400 / (1900 / 16))
    assert _read("routed_gmm_roofline_pct", run) > 0
    assert _read("gqa_flash_roofline_pct", run) is None    # no such work
    # and with no model built: nothing
    monkeypatch.setattr(other, "LAST_BUILT", None)
    assert _read("routed_load_max_over_mean", run) is None
    assert _read("routed_gmm_roofline_pct", run) is None
