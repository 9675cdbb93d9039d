"""Family `deepseek_v3` at tiny size on the CPU: the plain reference against
the system (logits, loss, gradients), the share it is given, the required
work against hand counts, the `train_loop` driver end to end, and the
family's per-layer readers on hand-built input."""
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
from benchmark.models import deepseek_v3 as family  # noqa: E402
from benchmark.trace import reduce as tr  # noqa: E402
from benchmark.trace import scopes  # noqa: E402
from test_harness import drive  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
TRAFFIC = {"driver": "train_loop", "batch_per_chip": 2, "pool_batches": 4,
           "mesh": None, "check_rows": 1, "loss_rows": 1, "seq_len": 32,
           "zipf_exponent": 1.0}
KANANA = harness.load_config(harness.load_manifest(), "kanana2_30b_a3b")
CELL_TRAFFIC = harness.load_traffic("clm_b2_s4096")


def fixture(**changes):
    cfg = harness.load_json(os.path.join(FIXTURES, "deepseek_v3_tiny.json"))
    cfg.update(changes)
    return cfg


# ---------------------------------------------------------------------------
# the reference against the system
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 1e-2)])
def test_reference_matches_system(dtype, tol):
    """Logits and loss after a few steps (so the selection bias is no longer
    zero), on a share of the experts (2..5 of 8)."""
    cfg = fixture(compute_dtype=dtype)
    model = family.build(cfg, seed=3)
    batch = family.make_pool(cfg, TRAFFIC, 3, 2)[0]
    for _ in range(3):
        model.fit_batch(batch)
    assert np.any(np.asarray(model.state_["router_bias"]))
    got = family.reference_check(model, cfg, batch, 2)
    assert got["rel_err"] <= tol
    assert abs(got["loss"] - got["loss_reference"]) \
        <= tol * abs(got["loss_reference"])
    assert got["tol"] == 0.05 and got["loss_tol"] == 0.02


def test_reference_gradients_match_one_train_steps_gradients():
    """`jax.grad` of the reference's loss against the gradients the system's
    train step takes, float32, seeded weights."""
    cfg = fixture(compute_dtype="float32")
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
    assert len(flat_got) == len(flat_want) > 20
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-3 * scale, \
            jax.tree_util.keystr(path)


def test_reference_in_a_lower_precision_is_refused():
    """The reference computed with every product's operands rounded to
    float8 (the nearest precision below the bfloat16 the configuration
    states) is outside the tolerance that bfloat16 is inside.  One held
    expert's term dropped is 5x outside what the float32 comparison
    above allows (at these widths a routed term is too small beside a
    residual stream of unit scale for the bfloat16 limit to see it)."""
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
    tol = KANANA["tolerance"]["output_rel"]
    assert family.rel_rms(bf16, want) < tol / 2
    assert family.rel_rms(fp8, want) > 1.5 * tol
    # a dropped term: the held experts' matrices zeroed for one expert
    dropped = jax.tree_util.tree_map(lambda a: a, model.params_)
    dropped["moe"] = {**dropped["moe"],
                      "w_down": dropped["moe"]["w_down"].at[:, 0].set(0.0)}
    assert family.rel_rms(
        family.reference_jitted(cfg, dropped, bias, ids), want) > 3 * 1e-4


def test_the_reference_is_given_the_share():
    """Held experts 2..5: an expert outside the share changes nothing, one
    inside does."""
    cfg = fixture(compute_dtype="float32")
    model = family.build(cfg, seed=7)
    ids = family.make_pool(cfg, TRAFFIC, 7, 2)[0].features[0]
    bias = model.state_["router_bias"]
    base = np.asarray(family.reference_forward(cfg, model.params_, bias, ids))
    moved = dict(cfg, first_expert_held=0)
    assert np.abs(np.asarray(family.reference_forward(
        moved, model.params_, bias, ids)) - base).max() > 1e-4


# ---------------------------------------------------------------------------
# required work against hand counts
# ---------------------------------------------------------------------------

def test_flops_per_item_against_the_issues_hand_count():
    """kanana-2-30b-a3b's share at 4,096 tokens, in MFLOP a token forward:
    MLA products 52.7, causal scores and values 41.9, shared 18.9, routed
    7.1 (0.75 expert a token), router 0.5; dense layer 170; head 66; 720 in
    all, 2.16 GFLOP trained."""
    moe = family.layer_flops_per_token(KANANA, 4096, True)
    assert moe["mla_products"] == 2 * (2048 * 6144 + 2048 * 576
                                       + 512 * 8192 + 4096 * 2048)
    assert moe["attention"] == 2 * 32 * 320 * 4097 / 2
    assert moe["shared"] == 2 * 3 * 2048 * 1536
    assert moe["routed"] == 0.75 * 2 * 3 * 2048 * 768
    assert moe["router"] == 2 * 2048 * 128
    assert round(sum(moe.values()) / 1e6) == 121
    dense = family.layer_flops_per_token(KANANA, 4096, False)
    assert dense["mlp"] == 2 * 3 * 2048 * 6144
    assert round(sum(dense.values()) / 1e6) == 170
    fwd = family.flops_per_item(KANANA, CELL_TRAFFIC, training=False)
    assert fwd == 4096 * (sum(dense.values()) + 4 * sum(moe.values())
                          + 2 * 2048 * 16032)
    assert round(fwd / 4096 / 1e6) == 720
    assert family.flops_per_item(KANANA, CELL_TRAFFIC) == 3 * fwd


def test_kernel_work_against_hand_counts():
    """Attention: 2 x 32 heads x 5 layers of a 4,096 lower triangle, 320
    FLOP-pairs a score forward and twice that backward; bytes: each operand
    once.  Grouped products: 9 of them a layer, 2 x 2048 x 768 a row."""
    att = family.attention_work(KANANA, CELL_TRAFFIC, rows=2)
    pairs = 4096 * 4097 / 2
    assert att["flops"] == 2 * 32 * 5 * 2 * pairs * 320 * 3
    # q, k 192 and v, o 128 wide; forward reads q k v, writes o; backward
    # reads q k v o dO, writes dQ dK dV; bf16
    assert att["bytes"] == 2 * 32 * 5 * 4096 * 2 * (
        (192 * 2 + 128 * 2) + (192 * 2 + 128 * 3) + (192 * 2 + 128))
    gm = family.grouped_work(KANANA, pairs=4 * 6144, layer_steps=4)
    assert gm["flops"] == 9 * 2 * 4 * 6144 * 2048 * 768
    assert gm["bytes"] == 9 * 2 * 4 * (6144 * (2048 + 768) + 16 * 2048 * 768)
    assert family.held_per_token(KANANA) == 0.75


def test_the_configuration_file_is_the_catalogs_but_for_the_cut():
    published = {"hidden_size": 2048, "intermediate_size": 6144,
                 "moe_intermediate_size": 768, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "num_attention_heads": 32,
                 "num_experts_per_tok": 6, "n_shared_experts": 2,
                 "num_hidden_layers": 48, "routed_scaling_factor": 2.448,
                 "rope_theta": 1000000, "first_k_dense_replace": 1}
    assert {k: KANANA[k] for k in published} == published
    assert KANANA["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    assert (KANANA["num_layers"], KANANA["n_routed_experts"],
            KANANA["vocab_size"]) == (5, 16, 16032)
    assert (KANANA["n_routed_experts_published"],
            KANANA["vocab_size_published"]) == (128, 128256)
    assert KANANA["vocab_size_published"] == 8 * KANANA["vocab_size"]
    c = family.decoder_config(KANANA)
    assert (c.n_experts, c.held, c.first_expert, c.top_k) == (128, 16, 0, 6)
    # 576M parameters in its matrices, 9.2 GB of training state at 16 bytes
    # each
    attn = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    n = (2 * 16032 * 2048 + attn + 3 * 2048 * 6144
         + 4 * (attn + 2048 * 128 + 16 * 3 * 2048 * 768 + 3 * 2048 * 1536))
    assert round(n / 1e6) == 576 and round(16 * n / 1e9, 1) == 9.2


def test_zipf_ids_are_ranked_and_seeded():
    rng = np.random.default_rng(1)
    ids = family.zipf_ids(rng, 1000, 1.0, (20000,))
    counts = np.bincount(ids, minlength=1000)
    assert ids.min() == 0 and ids.max() <= 999
    assert counts[0] > 1.6 * counts[1] > 1.6 * counts[3]   # p ~ 1 / rank
    again = family.zipf_ids(np.random.default_rng(1), 1000, 1.0, (20000,))
    np.testing.assert_array_equal(ids, again)
    pool = family.make_pool(fixture(), TRAFFIC, 2**31 + 5, 2)
    assert len(pool) == 4 and pool[0].features[0].shape == (2, 32)
    np.testing.assert_array_equal(pool[0].labels[0][:, :-1],
                                  pool[0].features[0][:, 1:])


# ---------------------------------------------------------------------------
# the driver end to end, and the readers
# ---------------------------------------------------------------------------

def test_train_loop_end_to_end_on_the_family():
    run = drive("deepseek_v3_tiny.json", TRAFFIC, 1)
    assert run.correct, run.checks
    assert run.attempted > 10 and run.failed == 0
    assert run.end_to_end["train_tokens_per_s"] \
        == pytest.approx(32 * run.end_to_end["train_samples_per_s"])
    assert run.counters["compiles_in_window"] == 0
    # the routing counter, over the window only: every step's tokens chose
    # top-2 in both expert layers
    load = family.window_expert_load(family.LAST_BUILT)
    np.testing.assert_array_equal(load.sum(1),
                                  [run.counters["steps"] * 2 * 32 * 2] * 2)
    ratio = harness.load_layer_metric(
        "moe_expert_load_max_over_mean").read(run)
    held = family.window_held_load(family.LAST_BUILT)
    np.testing.assert_array_equal(held, load[:, 2:6])
    assert ratio == pytest.approx((held.max(1) / held.mean(1)).max())
    assert ratio >= 1.0
    # untraced: the device readers have nothing to read
    for name in ("moe_ms_per_step", "mla_attention_ms_per_step",
                 "flash_attn_roofline_pct", "moe_gmm_roofline_pct"):
        assert harness.load_layer_metric(name).read(run) is None


def _ev(name, start, end, scope="", text=""):
    return scopes.ScopedEvent(tr.Event(name, start, end, text), scope)


def _run_with(events, steps=2, pairs=None, monkeypatch=None):
    """A run record as the readers see it, with hand-built scoped events in
    place of a trace file and, with `pairs`, a model whose routing counter
    says each of 4 expert layers saw `pairs` held pairs a step."""
    cell = harness.Cell(
        name="fixture", chips=1, config_name="kanana2_30b_a3b", config=KANANA,
        traffic_name="clm_b2_s4096", traffic=CELL_TRAFFIC, end_to_end=[],
        per_layer=[])
    run = harness.Run(cell=cell, seed=0, seconds=1.0, traced=True, devices=[],
                      clock=types.SimpleNamespace(marks=[0.0, 1.0], spans=[]),
                      peaks=harness.load_peaks("TPU v5 lite"))
    run.trace = object()
    run.counters.update(steps_traced=steps, rows=2, steps=steps)
    run._scoped_events = events
    if pairs is not None:
        load = np.zeros((4, 128), np.int64)
        load[:, :16] = pairs * 2 * steps / 16      # `steps` untraced + traced
        monkeypatch.setattr(family, "_LOAD_AT_WINDOW_START", None)
        monkeypatch.setattr(family, "LAST_BUILT", types.SimpleNamespace(
            config=family.decoder_config(KANANA),
            state_={"expert_load": load}))
    return run


def test_scope_readers_on_hand_built_events():
    """Self time by scope: a `while` holding two ops counts what they leave
    of it; `moe` and `mla_attention` are taken from the op's scope path,
    forward (`.../moe/experts/...`) and backward (`transpose(jvp(moe))`)."""
    events = [
        _ev("while.1", 0.0, 1.0, "jit(step)/while"),
        _ev("fusion.1", 0.0, 0.3, "jit(step)/while/body/moe/experts/dot"),
        _ev("fusion.2", 0.3, 0.4,
            "jit(step)/transpose(jvp(mla_attention))/mul"),
        _ev("fusion.3", 0.4, 0.5,
            "jit(step)/transpose(jvp())/while/body/closed_call/moe/router/x"),
        _ev("fusion.4", 0.5, 0.6, "jit(step)/lm_head/dot_general"),
        _ev("fusion.5", 0.6, 0.7, "jit(step)/remoe/moelike/dot"),
    ]
    run = _run_with(events, steps=2)
    read = lambda n: harness.load_layer_metric(n).read(run)
    assert read("moe_ms_per_step") == pytest.approx(1e3 * 0.4 / 2)
    assert read("mla_attention_ms_per_step") == pytest.approx(1e3 * 0.1 / 2)


def test_roofline_readers_on_hand_built_events(monkeypatch):
    """The attention kernels are the Mosaic calls under `mla_attention`, the
    grouped products those under `moe`; required work over their device
    time, against the v5e's peaks: the larger of the two shares."""
    mosaic = 'custom-call(...), custom_call_target="tpu_custom_call"'
    att = family.attention_work(KANANA, CELL_TRAFFIC, rows=2)
    t_att = 4 * att["flops"] / 197e12               # a quarter of the peak
    pairs = 6144.0
    gm = family.grouped_work(KANANA, 4 * pairs, layer_steps=4)
    t_gm = 10 * max(gm["flops"] / 197e12, gm["bytes"] / 819e9)
    events = [
        _ev("closed_call.1", 0.0, t_att,
            "jit(step)/transpose(jvp(mla_attention))/pallas_call", mosaic),
        _ev("fusion.9", 2.0, 2.5, "jit(step)/mla_attention/dot_general"),
        _ev("closed_call.2", 3.0, 3.0 + t_gm,
            "jit(step)/while/body/moe/experts/pallas_call", mosaic),
    ]
    # one step traced; four expert layers each saw `pairs` pairs a step
    run = _run_with(events, steps=1, pairs=pairs, monkeypatch=monkeypatch)
    read = lambda n: harness.load_layer_metric(n).read(run)
    assert read("flash_attn_roofline_pct") == pytest.approx(25.0)
    assert read("moe_gmm_roofline_pct") == pytest.approx(10.0)
    # no kernel of that scope in the trace: nothing to read, no error
    run = _run_with(events[1:2], steps=1, pairs=pairs,
                    monkeypatch=monkeypatch)
    assert read("flash_attn_roofline_pct") is None
    assert read("moe_gmm_roofline_pct") is None


def test_scopes_of_instructions_from_compiled_text():
    text = '''
  %fusion.7 = bf16[8,16]{1,0} fusion(%p0), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/moe/router/mul" source_file="x.py"}
  ROOT %closed_call.3 = (bf16[8]) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/mla_attention/pallas_call"}
  %copy.2 = f32[4]{0} copy(%b)
'''
    got = scopes.scopes_from_hlo_text(text)
    assert got == {"fusion.7": "jit(step)/moe/router/mul",
                   "closed_call.3": "jit(step)/mla_attention/pallas_call"}
    assert scopes.in_scope("jit(step)/moe/router/mul", "moe")
    assert scopes.in_scope("transpose(jvp(moe))/x", "moe")
    assert not scopes.in_scope("jit(step)/remoe/x", "moe")
