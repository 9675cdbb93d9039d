"""Family `nemotron_h` at tiny size on the CPU: the plain reference (Mamba-2
as its token-by-token recurrence) against the system (logits, loss, every
gradient), the pattern string against the layers it builds, the shares of
the squared-ReLU experts against the uncut layer, what a lower precision
reads, the share the reference is given, the required work against hand
counts, the configuration file against the catalog's, the parent's refusal,
the `train_loop` driver end to end, and the cell's two new readers on
hand-built input."""
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
from benchmark.models import nemotron_h as family  # noqa: E402
from benchmark.trace import reduce as tr  # noqa: E402
from benchmark.trace import scopes  # noqa: E402
from test_harness import drive  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
TRAFFIC = {"driver": "train_loop", "batch_per_chip": 2, "pool_batches": 4,
           "mesh": None, "check_rows": 1, "loss_rows": 1, "seq_len": 20,
           "zipf_exponent": 1.0}
MANIFEST = harness.load_manifest()
NEMOTRON = harness.load_config(MANIFEST, "nemotron_twotower_30b_a3b")
CELL = harness.load_cell(MANIFEST, "nemotron_twotower_train_s8192")


def fixture(**changes):
    cfg = harness.load_json(os.path.join(FIXTURES, "nemotron_h_tiny.json"))
    cfg.update(changes)
    return cfg


def _batch(cfg, seed, T=20):
    batch = family.make_pool(cfg, dict(TRAFFIC, seq_len=T), seed, 2)[0]
    return jnp.asarray(batch.features[0]), jnp.asarray(batch.labels[0])


# ---------------------------------------------------------------------------
# the reference against the system
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 3e-2)])
def test_reference_matches_system(dtype, tol):
    """Logits and loss after a few steps, on experts 2..5 of 8, 20 tokens:
    two chunks of 8 and a tail of the SSD."""
    cfg = fixture(compute_dtype=dtype)
    model = family.build(cfg, seed=3)
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
    every leaf, the SSD's A_log, dt_bias and D and the conv's bias among
    them."""
    cfg = fixture(compute_dtype="float32")
    model = family.build(cfg, seed=5)
    ids, labels = _batch(cfg, 5)
    bias = jnp.asarray(np.random.default_rng(5).normal(size=(2, 8)) * 0.05,
                       jnp.float32)
    (loss, _), got = jax.jit(jax.value_and_grad(
        model._loss, has_aux=True))(model.params_, bias, ids, labels)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: family.reference_loss(cfg, p, bias, ids, labels)))(
            model.params_)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    # embedding, head, final norm; two M layers of 9, two E of 6, one * of 3
    assert len(flat_got) == len(flat_want) == 3 + 2 * 9 + 2 * 6 + 3
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale, \
            jax.tree_util.keystr(path)


# ---------------------------------------------------------------------------
# the pattern against the layers
# ---------------------------------------------------------------------------

def test_the_pattern_builds_one_sub_block_a_layer():
    """`MEM*E`: M a Mamba-2 mixer alone, E experts alone, * attention alone
    (one RMSNorm each, no second one); the router bias and the routing
    counters have a row an E layer; the period is the whole pattern."""
    model = family.build(fixture(), seed=0)
    c = model.config
    assert c.layer_pattern == "MEM*E"
    assert c.kinds == ("mamba2", "moe", "mamba2", "full_attention", "moe")
    assert c.layout() == ("mamba2", c.kinds, 1, ())
    assert c.n_expert_layers == 2
    layers = model.params_["moe"]
    assert [sorted(lp) for lp in layers] == [
        ["A_log", "D", "conv_bias", "conv_xbc", "dt_bias", "in_proj", "norm1",
         "out_proj", "ssm_norm"],
        ["norm1", "router", "shared_down", "shared_up", "w_down", "w_up"],
        ["A_log", "D", "conv_bias", "conv_xbc", "dt_bias", "in_proj", "norm1",
         "out_proj", "ssm_norm"],
        ["Wo", "Wqkv", "norm1"],
        ["norm1", "router", "shared_down", "shared_up", "w_down", "w_up"]]
    assert layers[0]["in_proj"].shape == (1, 32, 2 * 32 + 2 * 2 * 16 + 4)
    assert layers[1]["w_up"].shape == (1, 4, 32, 16)
    assert layers[1]["shared_up"].shape == (1, 32, 32)
    np.testing.assert_allclose(layers[0]["A_log"][0], np.log([1, 2, 3, 4]))
    for name in ("router_bias", "expert_load"):
        assert model.state_[name].shape == (2, 8)
    assert model.state_["rows_over_bound"].shape == (2,)
    model.fit_batch(family.make_pool(fixture(), TRAFFIC, 0, 2)[0])
    np.testing.assert_array_equal(model.expert_load().sum(1), [2 * 20 * 2] * 2)


def test_a_one_trip_period_keeps_its_recomputation_apart():
    """The period scan runs once: its blocks are checkpointed with
    `prevent_cse`, so the backward's recomputation is lowered apart from
    the forward (without it XLA, which inlines a loop of one trip, merges
    the two and keeps every activation alive); the interleaved presets keep
    the step they had."""
    from deeplearning4j_tpu.zoo import DecoderConfig, DecoderModel

    def checkpoints(model):
        ids = jnp.zeros((1, 16), jnp.int32)
        text = str(jax.make_jaxpr(lambda p: model._loss(
            p, model.state_["router_bias"], ids, ids)[0])(model.params_))
        return (text.count("prevent_cse=True"),
                text.count("prevent_cse=False"))

    assert checkpoints(DecoderModel(DecoderConfig.tiny_mamba())) == (5, 0)
    assert checkpoints(DecoderModel(DecoderConfig.tiny_linear())) == (0, 4)


@pytest.mark.parametrize("pattern,layers,changes", [
    ("MEM*F", 5, {}), ("MEM", 5, {}), ("MM*M", 4, {}),
    ("MEM*E", 5, {"n_dense_layers": 1}),
    ("MEM*E", 5, {"layer_types": ("conv",) * 5})])
def test_a_bad_pattern_is_refused(pattern, layers, changes):
    """A character outside `M`, `E`, `*`, a pattern of another length than
    the layers, one with no expert layer, or one beside dense layers or a
    `layer_types` list, is refused; `*E*E` builds, two rows of bias."""
    from deeplearning4j_tpu.zoo import DecoderConfig, DecoderModel
    with pytest.raises(ValueError):
        DecoderModel(DecoderConfig.tiny_mamba(
            layer_pattern=pattern, n_layers=layers, **changes))
    model = DecoderModel(DecoderConfig.tiny_mamba(layer_pattern="*E*E",
                                                  n_layers=4))
    assert model.state_["router_bias"].shape == (2, 8)


# ---------------------------------------------------------------------------
# the shares against the uncut layer
# ---------------------------------------------------------------------------

def test_the_expert_shares_add_up_to_the_uncut_moe():
    """The routed parts of chips holding experts 0..2, 3..4 and 5..7 (the
    program's `expert_layer` with each one's `first_held`), and the shared
    squared-ReLU expert counted once, add up to the uncut reference's expert
    layer."""
    from deeplearning4j_tpu.ops.moe import expert_layer, relu2_mlp
    uncut = fixture(n_routed_experts=8, first_expert_held=0)
    lp = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a[0], jnp.float32),
        family.build(uncut, seed=8).params_["moe"][1])
    assert "w_gate" not in lp and "shared_gate" not in lp
    r = np.random.default_rng(8)
    u = jnp.asarray(r.normal(size=(1, 64, 32)), jnp.float32)
    bias = jnp.asarray(r.normal(size=8) * 0.05, jnp.float32)
    shared = relu2_mlp(u[0], lp["shared_up"], lp["shared_down"])
    total = shared
    for first, end in ((0, 3), (3, 5), (5, 8)):
        share = {**lp, **{n: lp[n][first:end] for n in ("w_up", "w_down")}}
        y, *_ = jax.jit(lambda u, s: expert_layer(
            u, s, bias, top_k=2, scale=2.5, first_held=first,
            eps=1e-20))(u[0], share)
        total = total + (y - shared)
    with jax.default_matmul_precision("highest"):
        want = family.reference_moe(uncut, u, lp, bias)[0]
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_relu2_experts_over_several_passes_match_one():
    """The squared-ReLU experts on a row bound the held pairs overflow (a
    second pass of the routed loop) give one pass's result and gradients."""
    from deeplearning4j_tpu.ops import moe
    r = np.random.default_rng(9)
    t, h, i, held = 64, 16, 8, 4
    x = jnp.asarray(r.normal(size=(t, h)), jnp.float32)
    chosen = jnp.asarray(r.integers(0, held, (t, 2)), jnp.int32)
    weights = jnp.asarray(r.uniform(size=(t, 2)), jnp.float32)
    w_up = jnp.asarray(r.normal(size=(held, h, i)) * 0.3, jnp.float32)
    w_down = jnp.asarray(r.normal(size=(held, i, h)) * 0.3, jnp.float32)

    def run(rows):
        def f(x, w_up, w_down):
            y, over = moe.routed_experts(x, chosen, weights, None, w_up,
                                         w_down, 0, rows)
            return jnp.sum(y * y), (y, over)
        return jax.jit(jax.value_and_grad(f, (0, 1, 2), has_aux=True))(
            x, w_up, w_down)

    (_, (one, over1)), g1 = run(t * 2)
    (_, (many, over2)), g2 = run(32)
    assert int(over1) == 0 and int(over2) == 1
    np.testing.assert_allclose(many, one, rtol=1e-5, atol=1e-6)
    for a, b in zip(g2, g1):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


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
    assert family.rel_rms(bf16, want) < NEMOTRON["tolerance"]["output_rel"]
    assert family.rel_rms(fp8, want) > 3 * family.rel_rms(bf16, want)


def test_the_reference_is_given_the_share_and_is_causal():
    """Held experts 2..5: the same matrices read as experts 0..3 give other
    logits; a later token changes no earlier row; the pattern it reads is
    the held one."""
    cfg = fixture(compute_dtype="float32")
    model = family.build(cfg, seed=7)
    ids = np.asarray(_batch(cfg, 7)[0])
    bias = model.state_["router_bias"]
    base = np.asarray(family.reference_forward(cfg, model.params_, bias, ids))
    assert base.shape == (2, 20, 96)
    assert np.abs(np.asarray(family.reference_forward(
        dict(cfg, first_expert_held=0), model.params_, bias, ids))
        - base).max() > 1e-4
    later = ids.copy()
    later[:, -4:] = (later[:, -4:] + 1) % 96
    np.testing.assert_allclose(np.asarray(family.reference_forward(
        cfg, model.params_, bias, later))[:, :-4], base[:, :-4], atol=1e-5)
    with pytest.raises(ValueError, match="MEM"):
        family.held_pattern(dict(cfg, layer_pattern_held="MEME*"))


# ---------------------------------------------------------------------------
# required work against hand counts, the configuration against the catalog
# ---------------------------------------------------------------------------

def test_flops_per_item_against_a_hand_count():
    """A token, forward: an M layer's products 77.4M (W_in 2688 x 10304,
    W_out 4096 x 2688) and its SSD 2.75M (8 groups' C B^T and 64 heads'
    three products over a chunk of 128, lower pairs); an E layer's shared
    expert 39.9M, 0.375 held experts a token 7.5M, the router 0.69M; the *
    layer's products 46.8M and its attention over the causal half 67.1M;
    the head 88.1M: 17.57 TFLOP a step of 8,192 tokens."""
    lower = 128 * 129 // 2
    assert family.ssd_chunk_flops(NEMOTRON) == 2 * (
        8 * lower * 128 + 64 * (lower * 64 + 2 * 128 * 128 * 64))
    m = family.layer_flops_per_token(NEMOTRON, 8192, "mamba2")
    assert m["mamba_products"] == 2 * (2688 * 10304 + 4096 * 2688)
    assert m["ssd"] == family.ssd_chunk_flops(NEMOTRON) / 128
    e = family.layer_flops_per_token(NEMOTRON, 8192, "moe")
    assert e == {"shared": 4 * 2688 * 3712,
                 "routed": 4 * 2688 * 1856 * 6 * 8 / 128,
                 "router": 2 * 2688 * 128}
    a = family.layer_flops_per_token(NEMOTRON, 8192, "attention")
    assert a["gqa_products"] == 2 * (2688 * 4608 + 4096 * 2688)
    assert a["attention"] == 2 * 32 * 256 * 8193 / 2
    fwd = 8192 * (4 * sum(m.values()) + 4 * sum(e.values()) + sum(a.values())
                  + 2 * 2688 * 16384)
    assert family.flops_per_item(NEMOTRON, CELL.traffic, training=False) \
        == fwd
    assert family.flops_per_item(NEMOTRON, CELL.traffic) == 3 * fwd
    assert round(3 * fwd / 1e12, 2) == 17.57
    assert family.items_per_row(NEMOTRON, CELL.traffic) == {
        "samples": 1, "tokens": 8192}


def test_kernel_work_against_hand_counts():
    """The SSD: 64 chunks x 4 layers x 3 passes of a chunk's products,
    271 GFLOP; x, B, C, dt and y forward, and those, dy and four gradients
    backward, 26,816 float32 elements a token a layer, 3.51 GB: bound by
    bandwidth, 4.29 ms at 819 GB/s.  GQA by LFM2's rule over 32 query heads
    and 2 key-value heads, one layer; the held experts' two and four
    grouped products."""
    w = family.ssd_work(NEMOTRON, CELL.traffic, rows=1)
    assert w["flops"] == 3 * 64 * 4 * family.ssd_chunk_flops(NEMOTRON)
    assert round(w["flops"] / 1e9) == 271
    assert w["bytes"] == 4 * 8192 * 4 * (2 * 6208 + 2 * 4096 + 6208)
    assert round(w["bytes"] / 819e9 * 1e3, 2) == 4.29
    assert w["bytes"] / 819e9 > w["flops"] / 197e12
    att = family.gqa_attention_work(NEMOTRON, CELL.traffic, rows=1)
    assert att["flops"] == 32 * 2 * (8192 * 8193 / 2) * 256 * 3
    assert att["bytes"] == 8192 * 128 * 2 * (32 * 6 + 2 * 6)
    gm = family.grouped_work(NEMOTRON, pairs=4 * 8192 * 0.375, layer_steps=4)
    assert gm["flops"] == 6 * 2 * 4 * 8192 * 0.375 * 2688 * 1856
    assert gm["bytes"] == 6 * 2 * (4 * 8192 * 0.375 * (2688 + 1856)
                                   + 8 * 2688 * 1856 * 4)


PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "n_group": 1, "n_groups": 8, "n_routed_experts": 128,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 52, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_limit": [0, None], "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}


def test_the_configuration_file_is_the_catalogs_but_for_the_cut():
    changed = {k for k in PUBLISHED if NEMOTRON[k] != PUBLISHED[k]}
    assert changed == {"vocab_size", "n_routed_experts"}
    assert changed <= set(NEMOTRON["reduced"])
    assert NEMOTRON["reduced"] == ["num_layers", "n_routed_experts",
                                   "vocab_size"]
    assert (NEMOTRON["num_layers"], NEMOTRON["n_routed_experts"],
            NEMOTRON["vocab_size"]) == (9, 8, 16384)
    assert NEMOTRON["vocab_size_published"] == 8 * NEMOTRON["vocab_size"]
    assert NEMOTRON["n_routed_experts_published"] == 16 * 8
    assert family.held_pattern(NEMOTRON) == "MEMEM*EME"
    for key in ("tower", "attention", "mamba2", "router",
                "bias_update_speed", "experts", "init", "numerics", "chunk",
                "updater", "data"):
        assert len(NEMOTRON["assumed"][key]) > 40, key
    assert "denoiser" in NEMOTRON["assumed"]["tower"]
    assert "float8" in NEMOTRON["tolerance"]["why"]
    assert "16-chip" in NEMOTRON["deployment"]
    assert "666,962,944" in NEMOTRON["deployment"]
    c = family.decoder_config(NEMOTRON)
    assert (c.n_experts, c.held, c.first_expert, c.top_k, c.routed_scale,
            c.n_shared_experts, c.shared_intermediate, c.expert_act) \
        == (128, 8, 0, 6, 2.5, 1, 3712, "relu2")
    assert (c.ssm_heads, c.ssm_head_dim, c.ssm_groups, c.ssm_state,
            c.ssm_chunk, c.conv_kernel) == (64, 64, 8, 128, 128, 4)
    assert (c.n_heads, c.n_kv_heads, c.head_dim, c.hidden) \
        == (32, 2, 128, 2688)
    assert (c.rope, c.qk_norm, c.attn_output_gate) == (False, False, False)
    assert (c.router_score, c.eps, c.n_dense_layers) == ("sigmoid", 1e-5, 0)
    assert c.out_proj_init_std == pytest.approx(
        (3 * 4096) ** -0.5 / 52 ** 0.5)


def test_the_parameter_count_is_the_deployments_share():
    """666,962,944 parameters built, 10.67 GB of training state at 16 bytes
    each: the shapes alone, nothing allocated."""
    from deeplearning4j_tpu.zoo import DecoderModel
    model = object.__new__(DecoderModel)
    model.config = family.decoder_config(NEMOTRON)
    shapes = jax.eval_shape(model._init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    mamba = (2688 * 10304 + 4 * 6144 + 6144 + 4096 * 2688 + 4096 + 3 * 64
             + 2688)
    attention = 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688 + 2688
    experts = 2688 * 128 + 2 * 2688 * 3712 + 8 * 2 * 2688 * 1856 + 2688
    assert (mamba, attention, experts) == (38_744_896, 23_399_040,
                                           100_125_312)
    assert n == 4 * mamba + attention + 4 * experts + 2 * 16384 * 2688 \
        + 2688 == 666_962_944
    assert round(16 * n / 1e9, 2) == 10.67


def test_a_program_without_the_layer_kind_is_refused_cleanly(monkeypatch):
    """The parent commit's `zoo/decoder.py` has no `mamba2`: the family says
    so in a `BenchmarkError`, not a `TypeError` from inside."""
    from deeplearning4j_tpu.zoo import decoder
    monkeypatch.setattr(decoder, "LAYER_KINDS", decoder.LAYER_KINDS[:5])
    with pytest.raises(harness.BenchmarkError, match="mamba2"):
        family.build(fixture(), seed=0)


# ---------------------------------------------------------------------------
# the driver end to end, and the readers
# ---------------------------------------------------------------------------

def _read(name, run):
    return harness.load_layer_metric(name).read(run)


def test_train_loop_end_to_end_on_the_family():
    run = drive("nemotron_h_tiny.json", TRAFFIC, 1)
    assert run.correct, run.checks
    assert run.attempted >= 2 and run.failed == 0
    assert run.end_to_end["train_tokens_per_s"] \
        == pytest.approx(20 * run.end_to_end["train_samples_per_s"])
    model = family.LAST_BUILT
    steps = run.counters["steps"]
    load = family.window_expert_load(model)
    np.testing.assert_array_equal(load.sum(1), [steps * 2 * 20 * 2] * 2)
    assert family.window_held_load(model).shape == (2, 4)
    assert _read("routed_load_max_over_mean", run) >= 1.0
    for name in ("mamba_mixer_ms_per_step", "ssd_scan_roofline_pct",
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


def test_the_new_readers_on_hand_built_events():
    """`ssd_scan` lies inside `mamba_mixer`; the SSD's roofline is against
    ALL device time under `ssd_scan`, bound by bandwidth; a program without
    the scope (the parent's), or a family without `ssd_work` (Solar's), has
    nothing to read."""
    work = family.ssd_work(NEMOTRON, CELL.traffic, rows=1)
    least = work["bytes"] / 819e9
    events = [
        _ev("fusion.1", 0.0, 2 * least,
            "jit(step)/while/body/closed_call/mamba_mixer/ssd_scan/exp"),
        _ev("fusion.2", 10.0, 10.0 + 2 * least,
            "jit(step)/transpose(jvp(while/body/checkpoint/mamba_mixer/"
            "ssd_scan))/dot_general"),
        _ev("fusion.3", 20.0, 20.0 + least,
            "jit(step)/while/body/closed_call/mamba_mixer/dot_general"),
        _ev("fusion.4", 30.0, 31.0, "jit(step)/while/body/moe/x"),
    ]
    run = _run_with(events, steps=1)
    assert _read("mamba_mixer_ms_per_step", run) \
        == pytest.approx(1e3 * 5 * least)
    assert _read("ssd_scan_roofline_pct", run) == pytest.approx(25.0)
    assert _read("ssd_scan_roofline_pct", _run_with(events[2:], 1)) is None
    assert _read("mamba_mixer_ms_per_step", _run_with(events[3:], 1)) is None
    solar = harness.load_cell(MANIFEST, "solar_open2_train_s4096")
    assert _read("ssd_scan_roofline_pct",
                 _run_with(events, steps=1, cell=solar)) is None
