"""Family `sdar_moe` at tiny size on the CPU: the plain reference against
the system (the noisy half's logits, the weighted loss, gradients) under
noise the family draws itself, the share it is given, the mask as BD3-LM
writes it, the required work against hand counts, the `train_loop` driver
end to end, and the cell's two new readers on hand-built input."""
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
from benchmark.models import sdar_moe as family  # noqa: E402
from benchmark.trace import reduce as tr  # noqa: E402
from benchmark.trace import scopes  # noqa: E402
from test_harness import drive  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
TRAFFIC = {"driver": "train_loop", "batch_per_chip": 2, "pool_batches": 4,
           "mesh": None, "check_rows": 1, "loss_rows": 1, "seq_len": 32,
           "zipf_exponent": 1.0}
MANIFEST = harness.load_manifest()
SDAR = harness.load_config(MANIFEST, "sdar_30b_a3b")
CELL = harness.load_cell(MANIFEST, "sdar_30b_train_s4096")


def fixture(**changes):
    cfg = harness.load_json(os.path.join(FIXTURES, "sdar_moe_tiny.json"))
    cfg.update(changes)
    return cfg


# ---------------------------------------------------------------------------
# the reference against the system
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 2e-2)])
def test_reference_matches_system(dtype, tol):
    """The noisy half's logits and the weighted loss after a few steps, on
    a share of the experts (2..5 of 8), under noise drawn by the family."""
    cfg = fixture(compute_dtype=dtype)
    model = family.build(cfg, seed=3)
    assert model.config.n_dense_layers == 0 and model.config.held == 4
    batch = family.make_pool(cfg, TRAFFIC, 3, 2)[0]
    np.testing.assert_array_equal(batch.features[0], batch.labels[0])
    assert batch.features[0].max() < cfg["mask_token_id"]
    for _ in range(3):
        model.fit_batch(batch)
    got = family.reference_check(model, cfg, batch, 2)
    assert got["rel_err"] <= tol
    assert abs(got["loss"] - got["loss_reference"]) \
        <= tol * abs(got["loss_reference"])
    assert got["tol"] == 0.05 and got["loss_tol"] == 0.02


def test_reference_gradients_match_one_train_steps_gradients():
    """`jax.grad` of the reference's loss (weighted cross-entropy and the
    balance term) against the gradients the system's train step takes for
    the same noise, float32, seeded weights."""
    cfg = fixture(compute_dtype="float32")
    model = family.build(cfg, seed=5)
    ids = jnp.asarray(family.make_pool(cfg, TRAFFIC, 5, 2)[0].features[0])
    noisy, weight = family.reference_noise(cfg, ids, 11)
    assert (noisy == cfg["mask_token_id"]).sum() == (weight > 0).sum() > 8
    (loss, seen), got = jax.jit(jax.value_and_grad(
        model._diffusion_loss, has_aux=True))(
            model.params_, model.state_["router_bias"], ids,
            jnp.asarray(noisy), jnp.asarray(weight))
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: family.reference_loss(cfg, p, ids, noisy, weight)))(
            model.params_)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    assert int(seen["masked_positions"]) == (weight > 0).sum()
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == 13
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-3 * scale, \
            jax.tree_util.keystr(path)
    # the balance term reaches the router: without it the gradient differs
    bare = jax.grad(lambda p: family.reference_loss(
        dict(cfg, router_aux_loss_coef=0.0), p, ids, noisy, weight))(
            model.params_)
    assert float(jnp.max(jnp.abs(
        bare["moe"]["router"] - want["moe"]["router"]))) > 0


def test_reference_in_a_lower_precision_reads_higher():
    """The reference with every product's operands rounded to float8 (the
    nearest precision below the bfloat16 the configuration states) reads
    several times what it reads rounded to bfloat16, and one held expert's
    term dropped is outside what the float32 comparison above allows."""
    cfg = fixture()
    model = family.build(cfg, seed=6)
    ids = family.make_pool(cfg, TRAFFIC, 6, 2)[0].features[0]
    noisy, _ = family.reference_noise(cfg, ids, 2)
    want = family.reference_jitted(cfg, model.params_, ids, noisy)
    fp8 = family.reference_jitted(cfg, model.params_, ids, noisy,
                                  round_to=jnp.float8_e4m3fn)
    bf16 = family.reference_jitted(cfg, model.params_, ids, noisy,
                                   round_to=jnp.bfloat16)
    assert family.rel_rms(bf16, want) < SDAR["tolerance"]["output_rel"] / 4
    assert family.rel_rms(fp8, want) > 5 * family.rel_rms(bf16, want)
    dropped = dict(model.params_)
    dropped["moe"] = dict(dropped["moe"])
    dropped["moe"]["w_down"] = dropped["moe"]["w_down"].at[:, 0].set(0.0)
    assert family.rel_rms(
        family.reference_jitted(cfg, dropped, ids, noisy), want) > 1e-4


def test_the_reference_is_given_the_share_and_the_noise():
    """Held experts 2..5: the same matrices read as experts 0..3 give other
    logits; other noise gives other logits; the clean half's ids matter to
    the noisy half only through earlier blocks."""
    cfg = fixture(compute_dtype="float32")
    model = family.build(cfg, seed=7)
    ids = family.make_pool(cfg, TRAFFIC, 7, 2)[0].features[0]
    noisy, _ = family.reference_noise(cfg, ids, 1)
    base = np.asarray(family.reference_forward(cfg, model.params_, ids,
                                               noisy))
    assert base.shape == (2, 32, 96)        # the noisy half's rows alone
    moved = dict(cfg, first_expert_held=0)
    assert np.abs(np.asarray(family.reference_forward(
        moved, model.params_, ids, noisy)) - base).max() > 1e-4
    other, _ = family.reference_noise(cfg, ids, 2)
    assert (other != noisy).any()
    # a clean token of the LAST block changed: no noisy row sees it
    later = ids.copy()
    later[:, -4:] = (later[:, -4:] + 1) % 95
    np.testing.assert_allclose(np.asarray(family.reference_forward(
        cfg, model.params_, later, noisy)), base, atol=1e-6)
    # of the FIRST block: every later block's noisy rows do
    first = ids.copy()
    first[:, :4] = (first[:, :4] + 1) % 95
    moved = np.asarray(family.reference_forward(cfg, model.params_, first,
                                                noisy))
    np.testing.assert_allclose(moved[:, :4], base[:, :4], atol=1e-6)
    assert np.abs(moved[:, 4:] - base[:, 4:]).max() > 1e-4


def test_the_mask_is_bd3lms_four_quadrants():
    """`[[M_BD, M_OBC], [0, M_BC]]` at L = 8, B = 4, written out."""
    m = family.block_mask(8, 4).astype(int)
    one, zero = np.ones((4, 4), int), np.zeros((4, 4), int)
    np.testing.assert_array_equal(m, np.block([
        [one, zero, zero, zero],        # noisy block 0: itself
        [zero, one, one, zero],         # noisy block 1: itself, clean 0
        [zero, zero, one, zero],        # clean block 0
        [zero, zero, one, one]]))       # clean block 1: clean 0 and 1
    assert m.sum() == family.live_pairs({"block_length": 4}, 8) == 64 + 32
    assert family.block_mask(4096, 4).sum() == 4096 ** 2 + 4096 * 4


def test_reference_noise_follows_the_objectives_rule():
    cfg = fixture()
    ids = np.arange(2 * 4096).reshape(2, 4096) % 95
    noisy, weight = family.reference_noise(cfg, ids, 4)
    replaced = noisy == 95
    np.testing.assert_array_equal(replaced, weight > 0)
    np.testing.assert_array_equal(noisy[~replaced], ids[~replaced])
    # one level a block of 4, t in (0.001, 1]; about half are replaced
    levels = weight.reshape(2, 1024, 4)
    for blk in levels.reshape(-1, 4)[:200]:
        assert len(set(blk[blk > 0].tolist())) <= 1
    assert 1.0 <= weight[replaced].min() and weight.max() <= 1000.0
    assert abs(replaced.mean() - 0.5) < 0.03
    # E[weight] = E[t * 1/t] = 1: the loss is an average over positions
    assert abs(weight.mean() - 1.0) < 0.1
    again, _ = family.reference_noise(cfg, ids, 4)
    np.testing.assert_array_equal(again, noisy)


# ---------------------------------------------------------------------------
# required work against hand counts
# ---------------------------------------------------------------------------

def test_flops_per_item_against_the_issues_hand_count():
    """SDAR-30B-A3B's share at 4,096 clean tokens = 8,192 rows, forward, a
    layer: the q/k/v and output products 37.7 MFLOP a row (0.31e12), the
    mask's 16.79M live pairs at 32 heads x 256 FLOP-pairs (0.275e12), one
    held expert a row 9.4 MFLOP (0.077e12), the router 0.5 (0.004e12);
    five layers 3.33e12, the head on 4,096 rows 0.32e12: 3.65e12 forward,
    1.09e13 trained."""
    parts = family.layer_flops_per_sequence(SDAR, 4096)
    assert parts == {
        "gqa_products": 8192 * 2 * (2048 * 5120 + 4096 * 2048),
        "attention": 2 * 32 * 256 * (4096 * 4096 + 4096 * 4),
        "routed": 8192 * 2 * 3 * 2048 * 768 * 1.0,
        "router": 8192 * 2 * 2048 * 128}
    assert family.held_per_token(SDAR) == 1.0       # 8 x 16 / 128
    assert family.live_pairs(SDAR, 4096) == 16_793_600
    assert [round(parts[k] / 1e12, 3) for k in
            ("gqa_products", "attention", "routed", "router")] \
        == [0.309, 0.275, 0.077, 0.004]
    fwd = family.flops_per_item(SDAR, CELL.traffic, training=False)
    assert fwd == 5 * sum(parts.values()) + 4096 * 2 * 2048 * 18992
    assert round(fwd / 1e12, 2) == 3.65
    assert family.flops_per_item(SDAR, CELL.traffic) == 3 * fwd
    assert round(3 * fwd / 1e13, 2) == 1.09
    assert family.items_per_row(SDAR, CELL.traffic) == {
        "samples": 1, "tokens": 4096}       # the clean tokens, not 2L rows


def test_kernel_work_against_hand_counts():
    """Attention: 32 query heads x 5 layers over the `L^2 + L B` live pairs,
    128 + 128 FLOP-pairs a pair forward and twice that backward; bytes over
    the 8,192 rows: q, o, dO, dQ once a query head, k, v, dK, dV once a
    key-value head.  Grouped products: 9 of them a layer, 2 x 2048 x 768 a
    row."""
    att = family.gqa_attention_work(SDAR, CELL.traffic, rows=1)
    pairs = 4096 * 4096 + 4096 * 4
    assert att["flops"] == 5 * 32 * 2 * pairs * 256 * 3
    assert att["bytes"] == 5 * 8192 * 128 * 2 * (32 * 6 + 4 * 6)
    # the whole [2L, 2L] square would be 4x less 0.1% as much
    assert 3.99 < 5 * 32 * 2 * 8192 ** 2 * 256 * 3 / att["flops"] < 4.0
    gm = family.grouped_work(SDAR, pairs=5 * 8192, layer_steps=5)
    assert gm["flops"] == 9 * 2 * 5 * 8192 * 2048 * 768
    assert gm["bytes"] == 9 * 2 * 5 * (8192 * (2048 + 768)
                                       + 16 * 2048 * 768)


def test_the_configuration_file_is_the_catalogs_but_for_the_cut():
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False}
    assert {k: SDAR[k] for k in published} == published
    assert SDAR["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert (SDAR["num_layers"], SDAR["num_experts"],
            SDAR["vocab_size"]) == (5, 16, 18992)
    assert (SDAR["num_experts_published"],
            SDAR["vocab_size_published"]) == (128, 151936)
    assert SDAR["vocab_size_published"] == 8 * SDAR["vocab_size"]
    assert SDAR["num_experts_published"] == 8 * SDAR["num_experts"]
    assert SDAR["mask_token_id"] == SDAR["vocab_size"] - 1
    for key in ("block_length", "noise", "no_logit_shift", "mask_token_id",
                "router_aux_loss_coef", "router", "qk_norm", "updater",
                "init", "compute_dtype", "data"):
        assert len(SDAR["assumed"][key]) > 40, key
    assert "float8" in SDAR["tolerance"]["why"]
    c = family.decoder_config(SDAR)
    assert (c.n_experts, c.held, c.first_expert, c.top_k) == (128, 16, 0, 8)
    assert (c.n_heads, c.n_kv_heads, c.head_dim) == (32, 4, 128)
    assert (c.n_layers, c.n_dense_layers, c.n_shared_experts) == (5, 0, 0)
    assert c.layout() == ("full_attention", ("full_attention",), 5, ())
    assert (c.router_score, c.aux_loss_coef, c.routed_scale, c.router_eps) \
        == ("softmax", 0.1, 1.0, 0.0)
    assert (c.objective, c.block_length, c.mask_token_id, c.noise_eps) \
        == ("block_diffusion", 4, 18991, 0.001)
    assert not c.tie_embeddings and (c.rope_base, c.eps) == (1e6, 1e-6)
    # the two levers set by measurement (`assumed` says why)
    assert SDAR["router_aux_loss_coef"] == 0.1
    assert SDAR["updater"]["args"][0] == {"schedule": "RampSchedule",
                                          "args": [0.00022, 200]}
    # 551.0M parameters in its matrices, 8.82 GB of training state at 16
    # bytes each; the step's rows and the routed part's bound
    layer = (2048 * 5120 + 4096 * 2048 + 2048 * 128 + 2 * 2048 + 2 * 128
             + 16 * 3 * 2048 * 768)
    n = 5 * layer + 2 * 18992 * 2048 + 2048
    assert round(layer / 1e6, 2) == 94.64
    assert round(n / 1e6, 1) == 551.0 and round(16 * n / 1e9, 2) == 8.82
    from deeplearning4j_tpu.ops.moe import row_bound
    assert row_bound(2 * 4096 * 8, 16, 128) == 16384


def test_a_program_without_the_objective_is_refused_cleanly(monkeypatch):
    """The parent commit's `DecoderConfig` has no `objective`: the family
    says so in a `BenchmarkError` instead of a `TypeError` from deep
    inside."""
    import dataclasses
    import deeplearning4j_tpu.zoo as zoo

    @dataclasses.dataclass
    class Older:
        vocab_size: int = 1

    monkeypatch.setattr(zoo, "DecoderConfig", Older)
    with pytest.raises(harness.BenchmarkError, match="block diffusion"):
        family.build(fixture(), seed=0)


# ---------------------------------------------------------------------------
# the driver end to end, and the readers
# ---------------------------------------------------------------------------

def _read(name, run):
    return harness.load_layer_metric(name).read(run)


def test_train_loop_end_to_end_on_the_family():
    run = drive("sdar_moe_tiny.json", TRAFFIC, 1)
    assert run.correct, run.checks
    # a count that holds on a loaded machine: the window is two seconds
    assert run.attempted >= 2 and run.failed == 0
    # tokens are the clean sequence's, not the 2L rows'
    assert run.end_to_end["train_tokens_per_s"] \
        == pytest.approx(32 * run.end_to_end["train_samples_per_s"])
    assert run.counters["compiles_in_window"] == 0
    model = family.LAST_BUILT
    steps = run.counters["steps"]
    # the routing counter, over the window only: every row of both copies
    # chose top-2 in both layers
    load = family.window_expert_load(model)
    np.testing.assert_array_equal(load.sum(1), [steps * 2 * 64 * 2] * 2)
    np.testing.assert_array_equal(family.window_held_load(model),
                                  load[:, 2:6])
    # the positions that carried loss, over the window only: about half
    masked = family.window_masked_positions(model)
    assert 0 < masked < int(model.state_["masked_positions"])
    got = _read("bd_loss_positions_per_step", run)
    assert got == pytest.approx(masked / steps)
    assert 0.4 * 64 < got < 0.6 * 64
    assert _read("routed_load_max_over_mean", run) >= 1.0
    # untraced: the device readers have nothing to read
    for name in ("diffusion_head_ms_per_step", "moe_ms_per_step",
                 "gqa_attention_ms_per_step", "gqa_flash_roofline_pct",
                 "routed_gmm_roofline_pct"):
        assert _read(name, run) is None


def _ev(name, start, end, scope="", text=""):
    return scopes.ScopedEvent(tr.Event(name, start, end, text), scope)


def _run_with(events, steps=2, cell=CELL):
    run = harness.Run(cell=cell, seed=0, seconds=1.0, traced=True, devices=[],
                      clock=types.SimpleNamespace(marks=[0.0, 1.0], spans=[]),
                      peaks=harness.load_peaks("TPU v5 lite"))
    run.trace = object()
    run.counters.update(steps_traced=steps, rows=1, steps=steps)
    run._scoped_events = events
    return run


def test_diffusion_head_reader_on_hand_built_events():
    """Self time under `bd_noise`, `diffusion_loss` and `lm_head`, forward
    and backward; a program with an `lm_head` but neither diffusion scope
    (kanana's, LFM2's) has nothing to read."""
    events = [
        _ev("fusion.1", 0.0, 0.1, "jit(step)/bd_noise/threefry2x32"),
        _ev("fusion.2", 0.1, 0.3, "jit(step)/jvp(diffusion_loss)/log_softmax"),
        _ev("fusion.3", 0.3, 0.4,
            "jit(step)/transpose(jvp(diffusion_loss))/mul"),
        _ev("fusion.4", 0.4, 0.8, "jit(step)/jvp(lm_head)/dot_general"),
        _ev("fusion.5", 0.8, 0.9, "jit(step)/while/body/gqa_attention/dot"),
        _ev("fusion.6", 0.9, 1.0, "jit(step)/bd_noise_like/x"),
    ]
    run = _run_with(events, steps=2)
    assert _read("diffusion_head_ms_per_step", run) \
        == pytest.approx(1e3 * 0.8 / 2)
    assert _read("gqa_attention_ms_per_step", run) \
        == pytest.approx(1e3 * 0.1 / 2)
    bare = _run_with(events[3:5], steps=2)
    assert _read("diffusion_head_ms_per_step", bare) is None


def test_roofline_readers_take_this_familys_live_pairs(monkeypatch):
    """`gqa_flash_roofline_pct` on this cell is against the block mask's
    live pairs over five layers, `routed_gmm_roofline_pct` against the held
    pairs the counter saw; `bd_loss_positions_per_step` from the window's
    counter over untraced and traced steps."""
    mosaic = 'custom-call(...), custom_call_target="tpu_custom_call"'
    att = family.gqa_attention_work(SDAR, CELL.traffic, rows=1)
    t_att = 4 * att["flops"] / 197e12               # a quarter of the peak
    pairs = 8192.0
    gm = family.grouped_work(SDAR, 5 * pairs, layer_steps=5)
    t_gm = 10 * max(gm["flops"] / 197e12, gm["bytes"] / 819e9)
    events = [
        _ev("closed_call.1", 0.0, t_att,
            "jit(step)/while/body/transpose(jvp(gqa_attention))/pallas_call",
            mosaic),
        _ev("closed_call.2", 3.0, 3.0 + t_gm,
            "jit(step)/while/body/moe/experts/pallas_call", mosaic),
    ]
    load = np.zeros((5, 128), np.int64)
    load[:, :16] = pairs * 2 / 16           # one step untraced, one traced
    monkeypatch.setattr(family, "_AT_WINDOW_START", None)
    monkeypatch.setattr(family, "LAST_BUILT", types.SimpleNamespace(
        config=family.decoder_config(SDAR),
        state_={"expert_load": load,
                "masked_positions": np.int32(2 * 2050)}))
    run = _run_with(events, steps=1)
    assert _read("gqa_flash_roofline_pct", run) == pytest.approx(25.0)
    assert _read("routed_gmm_roofline_pct", run) == pytest.approx(10.0)
    assert _read("routed_load_max_over_mean", run) == pytest.approx(1.0)
    assert _read("bd_loss_positions_per_step", run) == pytest.approx(2050.0)
    # on another family's cell, or with no model built: nothing
    lfm2 = harness.load_cell(MANIFEST, "lfm2_24b_train_s8192")
    assert _read("bd_loss_positions_per_step",
                 _run_with(events, steps=1, cell=lfm2)) is None
    monkeypatch.setattr(family, "LAST_BUILT", None)
    assert _read("bd_loss_positions_per_step", run) is None
