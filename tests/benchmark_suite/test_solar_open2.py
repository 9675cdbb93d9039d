"""Family `solar_open2` at tiny size on the CPU: the plain reference (KDA as
its token-by-token recurrence) against the system (logits, loss, every
gradient), the shares of heads and experts against the uncut layer, what a
lower precision reads, the share the reference is given, the required work
against hand counts, the configuration file against the catalog's, the
parent's refusal, the `train_loop` driver end to end, and the cell's three
new readers on hand-built input."""
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
from benchmark.models import solar_open2 as family  # noqa: E402
from benchmark.trace import reduce as tr  # noqa: E402
from benchmark.trace import scopes  # noqa: E402
from test_harness import drive  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
TRAFFIC = {"driver": "train_loop", "batch_per_chip": 2, "pool_batches": 4,
           "mesh": None, "check_rows": 1, "loss_rows": 1, "seq_len": 70,
           "zipf_exponent": 1.0}
MANIFEST = harness.load_manifest()
SOLAR = harness.load_config(MANIFEST, "solar_open2_250b")
CELL = harness.load_cell(MANIFEST, "solar_open2_train_s4096")


def fixture(**changes):
    cfg = harness.load_json(os.path.join(FIXTURES, "solar_open2_tiny.json"))
    cfg.update(changes)
    return cfg


def _batch(cfg, seed, T=70):
    batch = family.make_pool(cfg, dict(TRAFFIC, seq_len=T), seed, 2)[0]
    return jnp.asarray(batch.features[0]), jnp.asarray(batch.labels[0])


# ---------------------------------------------------------------------------
# the reference against the system
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 3e-2)])
def test_reference_matches_system(dtype, tol):
    """Logits and loss after a few steps, on heads 4..7 of 8 and experts
    2..5 of 8, 70 tokens: two chunks and a tail of the delta rule."""
    cfg = fixture(compute_dtype=dtype)
    model = family.build(cfg, seed=3)
    c = model.config
    assert c.layout() == ("full_attention", ("full_attention",) + (
        "linear_attention",) * 3, 1, ())
    assert (c.heads_held, c.first_head, c.held, c.first_expert) \
        == ((4, 1), 4, 4, 2)
    batch = family.make_pool(cfg, TRAFFIC, 3, 2)[0]
    for _ in range(3):
        model.fit_batch(batch)
    got = family.reference_check(model, cfg, batch, 2)
    assert got["rel_err"] <= tol
    assert abs(got["loss"] - got["loss_reference"]) \
        <= tol * abs(got["loss_reference"])
    assert got["tol"] == 0.02 and got["loss_tol"] == 0.02


def test_reference_gradients_match_one_train_steps_gradients():
    """`jax.grad` of the reference's loss against the gradients the system's
    train step takes, float32, seeded weights, a nonzero selection bias:
    every leaf, the decay's and the gates' among them."""
    cfg = fixture(compute_dtype="float32")
    model = family.build(cfg, seed=5)
    ids, labels = _batch(cfg, 5)
    bias = jnp.asarray(np.random.default_rng(5).normal(size=(4, 8)) * 0.05,
                       jnp.float32)
    (loss, seen), got = jax.jit(jax.value_and_grad(
        model._loss, has_aux=True))(model.params_, bias, ids, labels)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: family.reference_loss(cfg, p, bias, ids, labels)))(
            model.params_)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    np.testing.assert_array_equal(seen["delta_rule_updates"],
                                  [0, 2 * 70 * 4, 2 * 70 * 4, 2 * 70 * 4])
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == 3 + 12 + 3 * 20
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-3 * scale, \
            jax.tree_util.keystr(path)


# ---------------------------------------------------------------------------
# the shares against the uncut layer
# ---------------------------------------------------------------------------

UNCUT = dict(num_heads_held=8, first_head_held=0, n_routed_experts=8,
             first_expert_held=0)


def _heads(lp, kind, first, held, nkv, d=8):
    """The columns (rows of W_o) of heads `first ..` + `held` of an uncut
    layer's parameters; what every chip holds, whole."""
    out = dict(lp)
    q = np.arange(first * d, (first + held) * d)
    if kind == "linear_attention":
        w = lp["A_log"].shape[0] * d
        thirds = np.concatenate([q + i * w for i in range(3)])
        out.update(Wqkv=lp["Wqkv"][:, thirds],
                   conv_qkv=lp["conv_qkv"][:, thirds],
                   Wf_b=lp["Wf_b"][:, q], dt_bias=lp["dt_bias"][q],
                   A_log=lp["A_log"][first:first + held],
                   Wbeta=lp["Wbeta"][:, first:first + held],
                   Wg_b=lp["Wg_b"][:, q], Wo=lp["Wo"][q])
    else:
        nh = lp["Wg"].shape[1] // d
        group = nh // nkv
        kv = np.arange(first // group * d, (first + held) // group * d)
        cols = np.concatenate([q, nh * d + kv, (nh + nkv) * d + kv])
        out.update(Wqkv=lp["Wqkv"][:, cols], Wg=lp["Wg"][:, q],
                   Wo=lp["Wo"][q])
    return out


def _layer(params, j):
    """Layer `j`'s parameters, float32 (the norms' gains are float64 under
    the tests' x64)."""
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a[0], jnp.float32),
                                  params["moe"][j])


@pytest.mark.parametrize("j,kind", [(0, "full_attention"),
                                    (1, "linear_attention")])
@pytest.mark.parametrize("nkv,shares", [(2, ((0, 4), (4, 4))),
                                        (4, ((0, 2), (2, 2), (4, 4)))])
def test_the_head_shares_add_up_to_the_uncut_mixer(j, kind, nkv, shares):
    """For each attention kind, the program's layer on each chip's share of
    the heads (its columns and its rows of W_o) gives a part of the output;
    the parts of all the shares add up to the uncut reference's mixer output
    on the same input."""
    uncut = fixture(**UNCUT, num_key_value_heads=nkv)
    params = family.build(uncut, seed=7).params_
    lp = _layer(params, j)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 40, 32)),
                    jnp.float32)
    total = 0.0
    for first, held in shares:
        model = family.build(dict(uncut, num_heads_held=held,
                                  first_head_held=first), seed=0)
        y = model._operator(kind)(x, _heads(lp, kind, first, held, nkv))
        total = total + ((y[0] if kind == "linear_attention" else y) - x)
    ref = (family.reference_kda if kind == "linear_attention"
           else family.reference_gqa)
    with jax.default_matmul_precision("highest"):
        want = ref(uncut, family._rms(uncut, x, lp["norm1"]), lp)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_the_expert_shares_add_up_to_the_uncut_moe():
    """The routed parts of chips holding experts 0..2, 3..4 and 5..7 (the
    program's `expert_layer` with each one's `first_held`), and the shared
    expert counted once, add up to the uncut reference's expert layer."""
    from deeplearning4j_tpu.ops.moe import expert_layer, swiglu
    uncut = fixture(**UNCUT)
    lp = _layer(family.build(uncut, seed=8).params_, 1)
    r = np.random.default_rng(8)
    u = jnp.asarray(r.normal(size=(1, 64, 32)), jnp.float32)
    bias = jnp.asarray(r.normal(size=8) * 0.05, jnp.float32)
    shared = swiglu(u[0], lp["shared_gate"], lp["shared_up"],
                    lp["shared_down"])
    total = shared
    for first, end in ((0, 3), (3, 5), (5, 8)):
        share = {**lp, **{n: lp[n][first:end]
                          for n in ("w_gate", "w_up", "w_down")}}
        y, *_ = expert_layer(u[0], share, bias, top_k=2, scale=1.0,
                             first_held=first, eps=1e-20)
        total = total + (y - shared)
    with jax.default_matmul_precision("highest"):
        want = family.reference_moe(uncut, u, lp, bias)[0]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the reference's own properties
# ---------------------------------------------------------------------------

def test_reference_in_a_lower_precision_reads_higher():
    """The reference with every product's operands rounded to float8 (the
    nearest precision below the bfloat16 the configuration states) reads
    several times what it reads rounded to bfloat16."""
    cfg = fixture()
    model = family.build(cfg, seed=6)
    ids, _ = _batch(cfg, 6)
    bias = model.state_["router_bias"]
    want = family.reference_jitted(cfg, model.params_, bias, ids)
    fp8, bf16 = (family.reference_jitted(cfg, model.params_, bias, ids,
                                         round_to=r)
                 for r in (jnp.float8_e4m3fn, jnp.bfloat16))
    assert family.rel_rms(bf16, want) < SOLAR["tolerance"]["output_rel"]
    assert family.rel_rms(fp8, want) > 3 * family.rel_rms(bf16, want)


def test_the_reference_is_given_the_share_and_is_causal():
    """Held experts 2..5 and heads 4..7: the same matrices read as experts
    0..3 or as heads 0..3 give other logits; a later token changes no
    earlier row; without negative eigenvalues beta stops at 1."""
    cfg = fixture(compute_dtype="float32")
    model = family.build(cfg, seed=7)
    ids = np.asarray(_batch(cfg, 7)[0])
    bias = model.state_["router_bias"]
    base = np.asarray(family.reference_forward(cfg, model.params_, bias, ids))
    assert base.shape == (2, 70, 96)
    for moved in (dict(cfg, first_expert_held=0),
                  dict(cfg, kda_allow_neg_eigval=False)):
        assert np.abs(np.asarray(family.reference_forward(
            moved, model.params_, bias, ids)) - base).max() > 1e-4
    later = ids.copy()
    later[:, -4:] = (later[:, -4:] + 1) % 96
    np.testing.assert_allclose(np.asarray(family.reference_forward(
        cfg, model.params_, bias, later))[:, :-4], base[:, :-4], atol=1e-5)


# ---------------------------------------------------------------------------
# required work against hand counts, the configuration against the catalog
# ---------------------------------------------------------------------------

def test_flops_per_item_against_a_hand_count():
    """A token, forward: the KDA layer's products 36.24M (q, k, v 3 x 4096 x
    1024, the two low-rank gates, beta, o), its delta rule 1.11M (8 heads at
    8,904,704 a chunk of 64), GQA's products 27.3M and its attention over
    the causal half, each layer's shared expert 31.5M, 0.2 held experts a
    token 6.3M, the router 2.6M; the head 201.3M: 6.27 TFLOP a step."""
    assert family.delta_rule_chunk_flops(SOLAR) == 2 * (
        2016 * 128 + 2080 * 128 + 2016 * 256 + 3 * 64 * 128 * 128
        + 2080 * 128) == 8_904_704
    kda = family.layer_flops_per_token(SOLAR, 4096, "linear_attention")
    assert kda["kda_products"] == 2 * (4096 * 3072 + 2 * (4096 * 128
                                                           + 128 * 1024)
                                       + 4096 * 8 + 1024 * 4096)
    assert kda["delta_rule"] == 8 * 8_904_704 / 64
    gqa = family.layer_flops_per_token(SOLAR, 4096, "full_attention")
    assert gqa["gqa_products"] == 2 * (4096 * 1280 + 2 * 1024 * 4096)
    assert gqa["attention"] == 2 * 8 * 256 * 4097 / 2
    for part in (kda, gqa):
        assert part["shared"] == 2 * 3 * 4096 * 1280
        assert part["routed"] == 2 * 3 * 4096 * 1280 * 8 * 8 / 320
        assert part["router"] == 2 * 4096 * 320
    fwd = 4096 * (sum(gqa.values()) + 3 * sum(kda.values())
                  + 2 * 4096 * 24576)
    assert family.flops_per_item(SOLAR, CELL.traffic, training=False) == fwd
    assert family.flops_per_item(SOLAR, CELL.traffic) == 3 * fwd
    assert round(3 * fwd / 1e12, 2) == 6.27
    assert family.items_per_row(SOLAR, CELL.traffic) == {
        "samples": 1, "tokens": 4096}


def test_kernel_work_against_hand_counts():
    """The delta rule: 64 chunks x 8 heads x 3 layers x 3 passes of
    8,904,704 FLOPs, 41.0 GFLOP; 14 x 128 + 3 float32 elements a (token,
    head) a layer moved, 705 MB: bound by bandwidth, 0.86 ms at 819 GB/s.
    GQA by LFM2's rule over 8 query heads and 1 key-value head, one layer;
    the held experts' grouped products."""
    dr = family.delta_rule_work(SOLAR, CELL.traffic, rows=1)
    assert dr["flops"] == 3 * 64 * 8 * 3 * 8_904_704
    assert dr["bytes"] == 4 * 3 * 4096 * 8 * (14 * 128 + 3)
    assert round(dr["bytes"] / 819e9 * 1e3, 2) == 0.86
    assert dr["bytes"] / 819e9 > dr["flops"] / 197e12
    att = family.gqa_attention_work(SOLAR, CELL.traffic, rows=1)
    assert att["flops"] == 8 * 2 * (4096 * 4097 / 2) * 256 * 3
    assert att["bytes"] == 4096 * 128 * 2 * (8 * 6 + 1 * 6)
    gm = family.grouped_work(SOLAR, pairs=4 * 4096 * 0.2, layer_steps=4)
    assert gm["flops"] == 9 * 2 * 4 * 4096 * 0.2 * 4096 * 1280
    assert gm["bytes"] == 9 * 2 * (4 * 4096 * 0.2 * (4096 + 1280)
                                   + 8 * 4096 * 1280 * 4)


PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8}


def test_the_configuration_file_is_the_catalogs_but_for_the_cut():
    changed = {k for k in PUBLISHED if SOLAR[k] != PUBLISHED[k]}
    assert changed == {"vocab_size", "n_routed_experts"}
    assert changed <= set(SOLAR["reduced"])
    assert SOLAR["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    assert (SOLAR["num_layers"], SOLAR["n_routed_experts"],
            SOLAR["vocab_size"]) == (4, 8, 24576)
    assert SOLAR["vocab_size_published"] == 8 * SOLAR["vocab_size"]
    assert SOLAR["n_routed_experts_published"] == 40 * 8
    assert (SOLAR["num_heads_held"], SOLAR["first_head_held"]) == (8, 0)
    for key in ("router", "gqa_gate", "gqa", "kda", "kda_numerics", "init",
                "updater", "compute_dtype", "chunk", "data"):
        assert len(SOLAR["assumed"][key]) > 40, key
    assert "float8" in SOLAR["tolerance"]["why"]
    assert "40-chip" in SOLAR["deployment"]
    c = family.decoder_config(SOLAR)
    assert (c.n_experts, c.held, c.first_expert, c.top_k,
            c.n_shared_experts) == (320, 8, 0, 8, 1)
    assert (c.n_heads, c.n_kv_heads, c.head_dim, c.heads_held) \
        == (64, 8, 128, (8, 1))
    assert c.layout() == ("full_attention", ("full_attention",) + (
        "linear_attention",) * 3, 1, ())
    assert (c.rope, c.qk_norm, c.attn_output_gate, c.conv_kernel) \
        == (False, False, True, 4)
    assert (c.router_score, c.routed_scale, c.eps) == ("sigmoid", 1.0, 1e-5)


def test_the_parameter_count_is_the_deployments_share():
    """840,871,320 parameters built (840.9M), 13.45 GB of training state at
    16 bytes each: the shapes alone, nothing allocated."""
    from deeplearning4j_tpu.zoo import DecoderModel
    model = object.__new__(DecoderModel)
    model.config = family.decoder_config(SOLAR)
    shapes = jax.eval_shape(model._init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    kda = (4096 * 3072 + 4 * 3072 + 4096 * 128 + 128 * 1024 + 8 + 1024
           + 4096 * 8 + 4096 * 128 + 128 * 1024 + 128 + 1024 * 4096
           + 2 * 4096)
    gqa = 4096 * 1280 + 1024 * 4096 + 4096 * 1024 + 2 * 4096
    moe = 3 * 4096 * 1280 + 4096 * 320 + 8 * 3 * 4096 * 1280
    assert n == gqa + 3 * kda + 4 * moe + 2 * 24576 * 4096 + 4096 \
        == 840_871_320
    assert round(n / 1e6, 1) == 840.9 and round(16 * n / 1e9, 2) == 13.45


def test_a_program_without_the_layer_kind_is_refused_cleanly(monkeypatch):
    """The parent commit's `zoo/decoder.py` has no `linear_attention`: the
    family says so in a `BenchmarkError`, not a `TypeError` from inside."""
    from deeplearning4j_tpu.zoo import decoder
    monkeypatch.setattr(decoder, "LAYER_KINDS", decoder.LAYER_KINDS[:4])
    with pytest.raises(harness.BenchmarkError, match="linear_attention"):
        family.build(fixture(), seed=0)


# ---------------------------------------------------------------------------
# the driver end to end, and the readers
# ---------------------------------------------------------------------------

def _read(name, run):
    return harness.load_layer_metric(name).read(run)


def test_train_loop_end_to_end_on_the_family():
    run = drive("solar_open2_tiny.json", TRAFFIC, 1)
    assert run.correct, run.checks
    assert run.attempted >= 2 and run.failed == 0
    assert run.end_to_end["train_tokens_per_s"] \
        == pytest.approx(70 * run.end_to_end["train_samples_per_s"])
    model = family.LAST_BUILT
    steps = run.counters["steps"]
    # (token, held head) pairs of the 3 KDA layers, over the window only
    assert family.window_delta_rule_updates(model) == steps * 2 * 70 * 4 * 3
    assert _read("delta_rule_updates_per_token", run) == 12.0
    load = family.window_expert_load(model)
    np.testing.assert_array_equal(load.sum(1), [steps * 2 * 70 * 2] * 4)
    assert _read("routed_load_max_over_mean", run) >= 1.0
    for name in ("linear_attention_ms_per_step", "delta_rule_roofline_pct",
                 "gqa_attention_ms_per_step", "routed_gmm_roofline_pct"):
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
    """`delta_rule` lies inside `linear_attention`; the rule's roofline is
    against ALL device time under `delta_rule`, XLA's and Mosaic's, bound by
    bandwidth; a program without the scopes (the parent's, Keye's) has
    nothing to read; the counter reads window start to now."""
    mosaic = 'custom-call(...), custom_call_target="tpu_custom_call"'
    work = family.delta_rule_work(SOLAR, CELL.traffic, rows=1)
    least = work["bytes"] / 819e9
    events = [
        _ev("closed_call.1", 0.0, 2 * least,
            "jit(step)/while/body/linear_attention/delta_rule/pallas_call",
            mosaic),
        _ev("fusion.2", 10.0, 10.0 + 2 * least,
            "jit(step)/transpose(jvp(while/body/linear_attention/delta_rule"
            "))/reduce_sum"),
        _ev("fusion.3", 20.0, 20.0 + least,
            "jit(step)/while/body/linear_attention/dot_general"),
        _ev("fusion.4", 30.0, 31.0, "jit(step)/while/body/moe/x"),
    ]
    run = _run_with(events, steps=1)
    assert _read("linear_attention_ms_per_step", run) \
        == pytest.approx(1e3 * 5 * least)
    assert _read("delta_rule_roofline_pct", run) == pytest.approx(25.0)
    bare = _run_with(events[2:], steps=1)
    assert _read("delta_rule_roofline_pct", bare) is None
    keye = harness.load_cell(MANIFEST, "keye_vl2_30b_train_s16384")
    assert _read("delta_rule_roofline_pct",
                 _run_with(events, steps=1, cell=keye)) is None
    monkeypatch.setattr(family, "_AT_WINDOW_START", None)
    monkeypatch.setattr(family, "LAST_BUILT", types.SimpleNamespace(
        config=family.decoder_config(SOLAR), state_={
            "delta_rule_updates": np.array([0, 2, 2, 2], np.float32)
            * 4096 * 8}))
    assert _read("delta_rule_updates_per_token", run) == 24.0
    monkeypatch.setattr(family, "LAST_BUILT", None)
    assert _read("delta_rule_updates_per_token", run) is None
