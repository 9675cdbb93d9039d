"""Family `keye_vl2_moe` at tiny size on the CPU: the plain reference against
the system (logits, the three terms of the loss, every gradient, the selected
sets), what the indexer's loss may reach, the share the reference is given,
the required work against hand counts, the `train_loop` driver end to end,
and the cell's four new readers on hand-built input."""
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
from benchmark.models import keye_vl2_moe as family  # noqa: E402
from benchmark.trace import reduce as tr  # noqa: E402
from benchmark.trace import scopes  # noqa: E402
from test_harness import drive  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
TRAFFIC = {"driver": "train_loop", "batch_per_chip": 2, "pool_batches": 4,
           "mesh": None, "check_rows": 1, "loss_rows": 1, "seq_len": 64,
           "zipf_exponent": 1.0}
MANIFEST = harness.load_manifest()
KEYE = harness.load_config(MANIFEST, "keye_vl2_30b_a3b")
CELL = harness.load_cell(MANIFEST, "keye_vl2_30b_train_s16384")
INDEXER = ("Wq_idx", "Wk_idx", "Ww_idx", "k_idx_gain", "k_idx_bias")


def fixture(**changes):
    cfg = harness.load_json(os.path.join(FIXTURES, "keye_vl2_moe_tiny.json"))
    cfg.update(changes)
    return cfg


def _batch(cfg, seed):
    batch = family.make_pool(cfg, TRAFFIC, seed, 2)[0]
    return jnp.asarray(batch.features[0]), jnp.asarray(batch.labels[0])


# ---------------------------------------------------------------------------
# the reference against the system
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 3e-2)])
def test_reference_matches_system(dtype, tol):
    """Logits on all rows and on the rows where the selection binds, the
    loss and the first layer's selected sets after a few steps, on a share
    of the experts (2..5 of 8)."""
    cfg = fixture(compute_dtype=dtype)
    model = family.build(cfg, seed=3)
    assert model.config.n_dense_layers == 0 and model.config.held == 4
    assert model.config.layout() == (
        "sparse_attention", ("sparse_attention",), 2, ())
    batch = family.make_pool(cfg, TRAFFIC, 3, 2)[0]
    for _ in range(3):
        model.fit_batch(batch)
    got = family.reference_check(model, cfg, batch, 2)
    assert got["rel_err"] <= tol
    assert abs(got["loss"] - got["loss_reference"]) \
        <= tol * abs(got["loss_reference"])
    assert got["tol"] == 0.05 and got["loss_tol"] == 0.02
    ids = batch.features[0]
    want = np.asarray(family.reference_selection(cfg, model.params_, ids))
    agree = family.selection_agreement(model.selection(ids), want)
    # sum_n min(n, 16) pairs a sequence, causal, 16 a query from the 16th on
    assert want.sum() == 2 * family.selected_pairs(cfg, 64) == 2 * 904
    assert not np.triu(want[0], 1).any()
    np.testing.assert_array_equal(want.sum(-1)[:, 15:], 16)
    if dtype == "float32":      # the selected sets are equal as sets
        np.testing.assert_array_equal(np.asarray(model.selection(ids)), want)
    else:
        assert agree > 0.9


def test_a_selection_the_tolerance_refuses_makes_the_check_fail():
    """Where the selections agree on less than `select_agree`, `rel_err` is
    infinite: the driver holds it to `output_rel`."""
    cfg = fixture(compute_dtype="float32")
    cfg["tolerance"] = dict(cfg["tolerance"], select_agree=1.01)
    model = family.build(cfg, seed=3)
    batch = family.make_pool(cfg, TRAFFIC, 3, 2)[0]
    assert family.reference_check(model, cfg, batch, 1)["rel_err"] \
        == float("inf")


def test_reference_gradients_match_one_train_steps_gradients():
    """`jax.grad` of the reference's loss (cross-entropy, balance term and
    the indexer's loss) against the gradients the system's train step takes,
    float32, seeded weights: every leaf; the indexer's parameters from the
    indexer's loss alone, nothing else from it."""
    cfg = fixture(compute_dtype="float32")
    model = family.build(cfg, seed=5)
    ids, labels = _batch(cfg, 5)
    (loss, seen), got = jax.jit(jax.value_and_grad(
        model._loss, has_aux=True))(
            model.params_, model.state_["router_bias"], ids, labels)
    grad = lambda terms=None: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda p: family.reference_loss(cfg, p, ids, labels, terms)))(
            model.params_)
    want_loss, want = grad()
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    index_loss, from_index = grad(("index",))
    np.testing.assert_allclose(seen["index_kl"], index_loss, rtol=1e-5)
    assert float(seen["index_kl"]) > 1e-3
    assert float(jnp.sum(seen["selected_keys"])) == 2 * 2 * 904
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == 18
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-3 * scale, \
            jax.tree_util.keystr(path)
    # what each term reaches: the indexer's loss the indexer alone ...
    _, without = grad(("next_token", "balance"))
    for name, g in from_index["moe"].items():
        reach = float(jnp.max(jnp.abs(g)))
        assert (reach > 0) == (name in INDEXER), name
        # ... and the indexer learns from nothing else
        if name in INDEXER:
            assert float(jnp.max(jnp.abs(without["moe"][name]))) == 0.0
            np.testing.assert_allclose(got["moe"][name], g, rtol=1e-3,
                                       atol=1e-9)
    for name in ("tok_emb", "head", "final_norm"):
        assert float(jnp.max(jnp.abs(from_index[name]))) == 0.0


def test_reference_in_a_lower_precision_reads_higher():
    """The reference with every product's operands rounded to float8 (the
    nearest precision below the bfloat16 the configuration states) reads
    several times what it reads rounded to bfloat16, on the logits and on
    the first layer's selection; an approximate top-k is another model."""
    cfg = fixture()
    model = family.build(cfg, seed=6)
    ids, _ = _batch(cfg, 6)
    want = family.reference_jitted(cfg, model.params_, ids)
    fp8 = family.reference_jitted(cfg, model.params_, ids,
                                  round_to=jnp.float8_e4m3fn)
    bf16 = family.reference_jitted(cfg, model.params_, ids,
                                   round_to=jnp.bfloat16)
    assert family.rel_rms(bf16, want) < KEYE["tolerance"]["output_rel"]
    assert family.rel_rms(fp8, want) > 3 * family.rel_rms(bf16, want)
    exact = family.reference_selection(cfg, model.params_, ids)
    agree = {r: family.selection_agreement(family.reference_selection(
        cfg, model.params_, ids, round_to=r), exact)
        for r in (jnp.bfloat16, jnp.float8_e4m3fn)}
    assert 1.0 >= agree[jnp.bfloat16] > agree[jnp.float8_e4m3fn]
    # the CPU's `approx_max_k` is exact; on the chip it is not (PERF.md)
    approx = family.reference_selection(cfg, model.params_, ids,
                                        approx_recall=0.95)
    assert approx.shape == exact.shape


def test_the_reference_is_given_the_share_and_is_causal():
    """Held experts 2..5: the same matrices read as experts 0..3 give other
    logits; a later token changes no earlier row."""
    cfg = fixture(compute_dtype="float32")
    model = family.build(cfg, seed=7)
    ids = np.asarray(_batch(cfg, 7)[0])
    base = np.asarray(family.reference_forward(cfg, model.params_, ids))
    assert base.shape == (2, 64, 96)
    moved = dict(cfg, first_expert_held=0)
    assert np.abs(np.asarray(family.reference_forward(
        moved, model.params_, ids)) - base).max() > 1e-4
    later = ids.copy()
    later[:, -4:] = (later[:, -4:] + 1) % 96
    np.testing.assert_allclose(np.asarray(family.reference_forward(
        cfg, model.params_, later))[:, :-4], base[:, :-4], atol=1e-6)
    # fewer keys a query is another model from the 9th token on
    fewer = dict(cfg, sa_config=dict(cfg["sa_config"], topk=8))
    got = np.asarray(family.reference_forward(fewer, model.params_, ids))
    np.testing.assert_allclose(got[:, :8], base[:, :8], atol=1e-6)
    assert np.abs(got[:, 8:] - base[:, 8:]).max() > 1e-4


# ---------------------------------------------------------------------------
# required work against hand counts
# ---------------------------------------------------------------------------

def test_pairs_against_the_sum():
    """`sum_n min(n, topk)`: 31,458,304 of 134,225,920 causal pairs at
    16,384 tokens (23.4%), 1,920.06 keys a query."""
    for seq, topk in ((64, 16), (8, 16), (16384, 2048), (8192, 2048)):
        cfg = {"sa_config": dict(KEYE["sa_config"], topk=topk)}
        assert family.selected_pairs(cfg, seq) \
            == sum(min(n, topk) for n in range(1, seq + 1))
    assert family.selected_pairs(KEYE, 16384) == 31_458_304
    assert family.causal_pairs(16384) == 134_225_920
    assert round(31_458_304 / 134_225_920, 3) == 0.234
    assert round(family.selected_pairs(KEYE, 16384) / 16384, 2) == 1920.06
    assert round(family.selected_pairs(KEYE, 8192)
                 / family.causal_pairs(8192), 3) == 0.437


def test_flops_per_item_against_a_hand_count():
    """The share at 16,384 tokens, forward, a layer: the q/k/v and output
    products 0.618e12, attention over the selected pairs 0.515e12, the
    indexer's products 0.074e12 and its scores over every causal pair
    0.275e12, one held expert a row 0.155e12, the router 0.009e12."""
    parts = family.layer_flops_per_sequence(KEYE, 16384)
    assert parts == {
        "gqa_products": 16384 * 2 * (2048 * 5120 + 4096 * 2048),
        "attention": 2 * 32 * 256 * 31_458_304,
        "index_products": 16384 * 2 * 2048 * (1024 + 64 + 16),
        "index_scores": 2 * 16 * 64 * 134_225_920,
        "routed": 16384 * 2 * 3 * 2048 * 768 * 1.0,
        "router": 16384 * 2 * 2048 * 128}
    assert [round(parts[k] / 1e12, 3) for k in parts] \
        == [0.618, 0.515, 0.074, 0.275, 0.155, 0.009]
    main = sum(parts[k] for k in ("gqa_products", "attention", "routed",
                                  "router"))
    head = 16384 * 2 * 2048 * 18992
    fwd = family.flops_per_item(KEYE, CELL.traffic, training=False)
    assert fwd == 5 * (main + parts["index_products"]
                       + parts["index_scores"]) + head
    loss = 31_458_304 * (2 * 32 * 128 + 2 * 2 * 16 * 64)
    assert family.index_loss_flops_per_sequence(KEYE, 16384) == loss
    assert family.flops_per_item(KEYE, CELL.traffic) == 3 * (
        5 * main + head) + 5 * (2 * parts["index_products"]
                                + parts["index_scores"] + loss)
    assert family.items_per_row(KEYE, CELL.traffic) == {
        "samples": 1, "tokens": 16384}


def test_kernel_work_against_hand_counts():
    """Attention: 32 query heads x 5 layers over the selected pairs, 128 +
    128 FLOP-pairs a pair forward and twice that backward; bytes by LFM2's
    rule.  The indexer: `2 x 64 x 16` a causal pair, the loss's pass and the
    scores' two gradients a selected pair."""
    att = family.gqa_attention_work(KEYE, CELL.traffic, rows=1)
    assert att["flops"] == 5 * 32 * 2 * 31_458_304 * 256 * 3
    assert att["bytes"] == 5 * 16384 * 128 * 2 * (32 * 6 + 4 * 6)
    # the whole lower triangle would be 4.27x as much
    assert round(134_225_920 / 31_458_304, 2) == 4.27
    idx = family.index_work(KEYE, CELL.traffic, rows=1)
    assert idx["flops"] == 5 * (
        2 * 64 * 16 * 134_225_920
        + 31_458_304 * (2 * 32 * 128 + 2 * 2 * 64 * 16))
    assert idx["bytes"] == 5 * (
        3 * 16384 * (1024 + 64) * 2 + 3 * 16384 * 16 * 4
        + 16384 * 36 * 128 * 2 + 5 * 16384 * 16384 / 8)
    gm = family.grouped_work(KEYE, pairs=5 * 16384, layer_steps=5)
    assert gm["flops"] == 9 * 2 * 5 * 16384 * 2048 * 768
    assert gm["bytes"] == 9 * 2 * 5 * (16384 * (2048 + 768)
                                       + 16 * 2048 * 768)


def test_the_configuration_file_is_the_catalogs_but_for_the_cut():
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: KEYE[k] for k in published} == published
    assert KEYE["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    assert (KEYE["num_layers"], KEYE["num_experts"],
            KEYE["vocab_size"]) == (5, 16, 18992)
    assert KEYE["vocab_size_published"] == 8 * KEYE["vocab_size"]
    assert KEYE["num_experts_published"] == 8 * KEYE["num_experts"]
    for key in ("qk_norm", "mrope", "indexer", "topk", "chunk_sizes",
                "index_loss", "text_only", "router", "router_aux_loss_coef",
                "updater", "init", "compute_dtype", "data"):
        assert len(KEYE["assumed"][key]) > 40, key
    assert "float8" in KEYE["tolerance"]["why"]
    assert "approx" in KEYE["tolerance"]["why"]
    c = family.decoder_config(KEYE)
    assert (c.n_experts, c.held, c.first_expert, c.top_k) == (128, 16, 0, 8)
    assert (c.n_heads, c.n_kv_heads, c.head_dim) == (32, 4, 128)
    assert (c.n_layers, c.n_dense_layers, c.n_shared_experts) == (5, 0, 0)
    assert c.layout() == ("sparse_attention", ("sparse_attention",), 5, ())
    assert (c.index_heads, c.index_head_dim, c.index_topk,
            c.index_loss_coef) == (16, 64, 2048, 1.0)
    assert tuple(c.rope_sections) == (16, 24, 24)
    assert (c.router_score, c.aux_loss_coef, c.objective) \
        == ("softmax", 0.1, "next_token")
    assert not c.tie_embeddings and (c.rope_base, c.eps) == (1e7, 1e-6)
    # 562.3M parameters, 9.00 GB of training state at 16 bytes each
    layer = (2048 * 5120 + 4096 * 2048 + 2048 * 128 + 2 * 2048 + 2 * 128
             + 16 * 3 * 2048 * 768
             + 2048 * 1024 + 2048 * 64 + 2048 * 16 + 2 * 64)
    n = 5 * layer + 2 * 18992 * 2048 + 2048
    assert round(layer / 1e6, 2) == 96.90
    assert n == 562_290_560 and round(16 * n / 1e9, 2) == 9.00


def test_a_program_without_the_layer_kind_is_refused_cleanly(monkeypatch):
    """The parent commit's `zoo/decoder.py` has no `sparse_attention`: the
    family says so in a `BenchmarkError`, not a `TypeError` from inside."""
    from deeplearning4j_tpu.zoo import decoder
    monkeypatch.setattr(decoder, "LAYER_KINDS", decoder.LAYER_KINDS[:3])
    with pytest.raises(harness.BenchmarkError, match="sparse_attention"):
        family.build(fixture(), seed=0)


# ---------------------------------------------------------------------------
# the driver end to end, and the readers
# ---------------------------------------------------------------------------

def _read(name, run):
    return harness.load_layer_metric(name).read(run)


def test_train_loop_end_to_end_on_the_family():
    run = drive("keye_vl2_moe_tiny.json", TRAFFIC, 1)
    assert run.correct, run.checks
    # a count that holds on a loaded machine: the window is two seconds
    assert run.attempted >= 2 and run.failed == 0
    assert run.end_to_end["train_tokens_per_s"] \
        == pytest.approx(64 * run.end_to_end["train_samples_per_s"])
    assert run.counters["compiles_in_window"] == 0
    model = family.LAST_BUILT
    steps = run.counters["steps"]
    load = family.window_expert_load(model)
    np.testing.assert_array_equal(load.sum(1), [steps * 2 * 64 * 2] * 2)
    # the pairs the indexers kept, over the window only: 904 a sequence
    assert family.window_selected_keys(model) == steps * 2 * 2 * 904
    assert _read("sparse_keys_per_query", run) == pytest.approx(904 / 64)
    stats = model.sparse_stats()
    assert stats["keys_per_query"] == pytest.approx(904 / 64)
    assert stats["steps"] == model.iteration and stats["index_kl"] > 0
    assert _read("routed_load_max_over_mean", run) >= 1.0
    # untraced: the device readers have nothing to read
    for name in ("sparse_index_ms_per_step", "index_loss_ms_per_step",
                 "sparse_index_roofline_pct", "gqa_attention_ms_per_step",
                 "gqa_flash_roofline_pct", "routed_gmm_roofline_pct"):
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


def test_the_new_readers_on_hand_built_events(monkeypatch):
    """`sparse_index` and `index_loss` are siblings of `gqa_attention`;
    the indexer's roofline is against ALL device time under the two, the
    attention kernels' against the selected pairs; a program with neither
    scope (the parent's, SDAR's) has nothing to read."""
    mosaic = 'custom-call(...), custom_call_target="tpu_custom_call"'
    idx = family.index_work(KEYE, CELL.traffic, rows=1)
    least = max(idx["flops"] / 197e12, idx["bytes"] / 819e9)
    assert least == idx["flops"] / 197e12       # the MXU bounds it
    att = family.gqa_attention_work(KEYE, CELL.traffic, rows=1)
    t_att = 4 * att["flops"] / 197e12
    events = [
        _ev("closed_call.1", 0.0, 6 * least,
            "jit(step)/while/body/sparse_index/pallas_call", mosaic),
        _ev("fusion.2", 10.0, 10.0 + 3 * least,
            "jit(step)/while/body/sparse_index/while/body/reduce_sum"),
        _ev("closed_call.3", 20.0, 20.0 + least,
            "jit(step)/while/body/jvp(index_loss)/pallas_call", mosaic),
        _ev("closed_call.4", 30.0, 30.0 + t_att,
            "jit(step)/while/body/transpose(jvp(gqa_attention))/pallas_call",
            mosaic),
        _ev("fusion.5", 40.0, 40.5, "jit(step)/while/body/sparse_indexer/x"),
    ]
    run = _run_with(events, steps=1)
    assert _read("sparse_index_ms_per_step", run) \
        == pytest.approx(1e3 * 9 * least)
    assert _read("index_loss_ms_per_step", run) == pytest.approx(1e3 * least)
    assert _read("sparse_index_roofline_pct", run) == pytest.approx(10.0)
    assert _read("gqa_flash_roofline_pct", run) == pytest.approx(25.0)
    assert _read("gqa_attention_ms_per_step", run) \
        == pytest.approx(1e3 * t_att)
    bare = _run_with(events[3:], steps=1)
    for name in ("sparse_index_ms_per_step", "index_loss_ms_per_step",
                 "sparse_index_roofline_pct"):
        assert _read(name, bare) is None
    # the counter: window start to now, over untraced and traced steps
    monkeypatch.setattr(family, "_AT_WINDOW_START", None)
    monkeypatch.setattr(family, "LAST_BUILT", types.SimpleNamespace(
        config=family.decoder_config(KEYE),
        state_={"selected_keys": np.full(5, 2 * 31_458_304.0, np.float32)}))
    assert _read("sparse_keys_per_query", run) \
        == pytest.approx(31_458_304 / 16384)
    sdar = harness.load_cell(MANIFEST, "sdar_30b_train_s4096")
    for name in ("sparse_keys_per_query", "sparse_index_roofline_pct"):
        assert _read(name, _run_with(events, steps=1, cell=sdar)) is None
    monkeypatch.setattr(family, "LAST_BUILT", None)
    assert _read("sparse_keys_per_query", run) is None
