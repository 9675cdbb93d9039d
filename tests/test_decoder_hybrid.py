"""The parts a hybrid decoder adds, against hand-written cases: the gated short
convolution, grouped-query heads through each branch of `fused_attention`,
half-split rotary, per-head RMSNorm of queries and keys, a router with
epsilon 1e-6, scale 1 and no shared expert and its shares; and
`zoo.DecoderModel` over a layer list: its layout in periods, its surface, and
what the lowered attention layer hands the kernels."""
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import MultiDataSet
from deeplearning4j_tpu.ops import attention_kernels as ak
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.ops import pallas as tier
from deeplearning4j_tpu.ops.norm_kernels import rms_norm
from deeplearning4j_tpu.ops.rotary import (rotary_half_split,
                                           rotary_interleaved)
from deeplearning4j_tpu.ops.short_conv import (causal_depthwise_conv,
                                               gated_short_conv)
from deeplearning4j_tpu.zoo import DecoderConfig, DecoderModel
from tests.test_attention_kernels import _equations
from tests.test_decoder import bounded_model_against_the_full_pass


@pytest.fixture(autouse=True)
def _reset_tier():
    yield
    tier.dispatch.reset()


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------

def _conv_inputs(t=7, h=5, taps=3, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(2, t, h)), rng.normal(size=(h, 3 * h)),
            rng.normal(size=(taps, h)), rng.normal(size=(h, h)))


def _conv_by_loops(u, w_in, kernel, w_out):
    """The layer's equations, a position and a channel at a time."""
    rows, t, h = u.shape
    taps = kernel.shape[0]
    out = np.zeros((rows, t, h))
    for r in range(rows):
        bcx = u[r] @ w_in
        b, c, x = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]
        z = b * x
        mixed = np.zeros((t, h))
        for pos in range(t):
            for ch in range(h):
                acc = 0.0
                for j in range(taps):
                    src = pos - (taps - 1) + j
                    if src >= 0:            # before position 0: zero
                        acc += kernel[j, ch] * z[src, ch]
                mixed[pos, ch] = c[pos, ch] * acc
        out[r] = mixed @ w_out
    return out


def test_gated_short_conv_against_loops():
    u, w_in, kernel, w_out = _conv_inputs()
    got = gated_short_conv(*map(jnp.asarray, (u, w_in, kernel, w_out)))
    want = _conv_by_loops(u, w_in, kernel, w_out)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the causal edge: position 0 sees the last tap alone, position 1 the
    # last two
    z = np.arange(1.0, 5.0)[:, None] * np.ones((4, 2))
    k = np.array([[100.0, 100.0], [10.0, 10.0], [1.0, 1.0]])
    np.testing.assert_allclose(
        causal_depthwise_conv(jnp.asarray(z), jnp.asarray(k))[:, 0],
        [1.0, 12.0, 123.0, 234.0])


def test_the_convolutions_bias_is_optional_and_adds_nothing_when_absent():
    """Without a bias the function is the taps' sum as it was written before
    the bias existed, bit for bit (float32 and bfloat16); with one, that sum
    plus the bias a channel."""
    r = np.random.default_rng(3)
    for dt in (jnp.float32, jnp.bfloat16):
        z = jnp.asarray(r.normal(size=(2, 9, 5)), dt)
        k = jnp.asarray(r.normal(size=(4, 5)), dt)
        padded = jnp.pad(z, [(0, 0), (3, 0), (0, 0)])
        before = sum(k[j] * padded[:, j:j + 9] for j in range(4))
        got = causal_depthwise_conv(z, k)
        assert got.dtype == before.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(before, np.float32))
        b = jnp.asarray(r.normal(size=5), dt)
        np.testing.assert_array_equal(
            np.asarray(causal_depthwise_conv(z, k, b), np.float32),
            np.asarray(before + b, np.float32))


def test_gated_short_conv_gradients_against_hand_written_ones():
    """dL/dk, dL/dB, dL/dC, dL/dX of the mix by hand (shifted multiply-adds
    again), the two products' by the chain rule, against autodiff."""
    u, w_in, kernel, w_out = _conv_inputs(seed=1)
    g = np.random.default_rng(2).normal(size=u.shape)
    got = jax.grad(lambda *a: jnp.sum(gated_short_conv(*a) * g),
                   argnums=(0, 1, 2, 3))(
        *map(jnp.asarray, (u, w_in, kernel, w_out)))
    rows, t, h = u.shape
    taps = kernel.shape[0]
    du, dw_in = np.zeros_like(u), np.zeros_like(w_in)
    dk, dw_out = np.zeros_like(kernel), np.zeros_like(w_out)
    for r in range(rows):
        bcx = u[r] @ w_in
        b, c, x = bcx[:, :h], bcx[:, h:2 * h], bcx[:, 2 * h:]
        z = b * x
        zp = np.concatenate([np.zeros((taps - 1, h)), z])
        conv = sum(kernel[j] * zp[j:j + t] for j in range(taps))
        dmixed = g[r] @ w_out.T
        dw_out += (c * conv).T @ g[r]
        dc, dconv = dmixed * conv, dmixed * c
        dz = np.zeros((t, h))
        for j in range(taps):
            dk[j] += (dconv * zp[j:j + t]).sum(0)
            shift = taps - 1 - j        # tap j read position pos - shift
            dz[:t - shift] += kernel[j] * dconv[shift:]
        dbcx = np.concatenate([dz * x, dc, dz * b], axis=1)
        dw_in += u[r].T @ dbcx
        du[r] = dbcx @ w_in.T
    for a, b in zip(got, (du, dw_in, dk, dw_out)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_gated_short_conv_is_causal():
    """A change at position t leaves the outputs before t as they were and
    reaches t .. t + taps - 1 only."""
    u, w_in, kernel, w_out = map(jnp.asarray, _conv_inputs(t=9))
    base = gated_short_conv(u, w_in, kernel, w_out)
    moved = gated_short_conv(u.at[:, 4].add(1.0), w_in, kernel, w_out)
    changed = np.abs(np.asarray(moved - base)).max(axis=(0, 2)) > 1e-9
    np.testing.assert_array_equal(
        changed, [False] * 4 + [True] * 3 + [False] * 2)


def test_gated_short_conv_mixes_in_float32_and_returns_the_input_dtype():
    u, w_in, kernel, w_out = (jnp.asarray(a, jnp.bfloat16)
                              for a in _conv_inputs(seed=3))
    out = gated_short_conv(u, w_in, kernel, w_out)
    assert out.dtype == jnp.bfloat16
    want = _conv_by_loops(*(np.asarray(a, np.float64)
                            for a in (u, w_in, kernel, w_out)))
    assert np.abs(np.asarray(out, np.float64) - want).max() \
        <= 0.05 * np.abs(want).max()


# ---------------------------------------------------------------------------
# grouped-query heads
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("branch", ["xla", "blockwise", "flash"])
def test_fused_attention_grouped_query_heads(branch, group, monkeypatch):
    """`group` query heads a key-value head, causal, forward and dQ, dK, dV
    through each of `fused_attention`'s branches, against `mha_reference` on
    keys and values repeated over the query heads (its gradient summed over
    the group by hand).  Group 1 is every head its own: kanana's case."""
    keys = jax.random.split(jax.random.PRNGKey(group), 4)
    heads, kv_heads = 2 * group, 2
    q = jax.random.normal(keys[0], (2, heads, 64, 16), jnp.float32)
    k = jax.random.normal(keys[1], (2, kv_heads, 64, 16), jnp.float32)
    v = jax.random.normal(keys[2], (2, kv_heads, 64, 16), jnp.float32)
    g = jax.random.normal(keys[3], (2, heads, 64, 16), jnp.float32)
    taken = []
    if branch == "blockwise":
        monkeypatch.setattr(ak, "_XLA_SCORE_BYTES_MAX", 0)
        real = ak.blockwise_attention
        monkeypatch.setattr(ak, "blockwise_attention", lambda *a: (
            taken.append(branch), real(*a[:6], 16))[1])
    elif branch == "flash":
        tier.dispatch.set_dispatch_mode("pallas")
        tier.dispatch.set_tile("attention", tier.TileConfig(block_q=16,
                                                            block_kv=32))
        real = tier.attention.flash_attention
        monkeypatch.setattr(tier.attention, "flash_attention",
                            lambda *a, **kw: (taken.append(branch),
                                              real(*a, **kw))[1])
    else:
        real = ak.mha_reference
        monkeypatch.setattr(ak, "mha_reference", lambda *a: (
            taken.append(branch), real(*a))[1])

    out = ak.fused_attention(q, k, v, causal=True)
    assert taken == [branch] and out.shape == q.shape
    dq, dk, dv = jax.grad(
        lambda q, k, v: jnp.sum(ak.fused_attention(q, k, v, causal=True) * g),
        (0, 1, 2))(q, k, v)
    assert dk.shape == k.shape and dv.shape == v.shape
    monkeypatch.undo()
    tier.dispatch.reset()

    def repeated(a):                # head h from key-value head h // group
        return jnp.stack([a[:, h // group] for h in range(heads)], axis=1)

    np.testing.assert_allclose(
        out, ak.mha_reference(q, repeated(k), repeated(v), None, True),
        atol=1e-5)
    wq, wk, wv = jax.grad(
        lambda q, k, v: jnp.sum(ak.mha_reference(q, k, v, None, True) * g),
        (0, 1, 2))(q, repeated(k), repeated(v))
    np.testing.assert_allclose(dq, wq, atol=1e-5)
    for got, full in ((dk, wk), (dv, wv)):
        np.testing.assert_allclose(
            got, full.reshape(2, kv_heads, group, 64, 16).sum(2), atol=1e-5)


def test_flash_kernels_read_key_value_heads_where_they_lie():
    """The kernels' operands: keys and values enter both Mosaic calls folded
    to [B * Hk, S, D], not repeated over the query's heads, after the tile
    schedule's three arrays.  The forward's grid rows are query heads; the
    backward's are key-value heads, each with its group's four query heads
    one after the other."""
    q = jnp.zeros((1, 8, 64, 16), jnp.float32)
    kv = jnp.zeros((1, 2, 64, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: jax.grad(
        lambda q, k, v: ak._flash_attention_diff(
            q, k, v, None, True, None, 16, 32, True).sum(), (0, 1, 2))(
                q, k, v))(q, kv, kv)
    forward, backward = [
        [v.aval.shape for v in e.invars] for e in jaxpr.jaxpr.eqns
        if e.primitive.name == "pallas_call"]
    # the schedule first (query block, key block, flags of the 6 live tiles
    # of 8; the backward walks them for each of a group's four heads)
    assert forward[:3] == [(6,)] * 3 and backward[:3] == [(4 * 6,)] * 3
    assert forward[3:] == [(8, 64, 16), (2, 64, 16), (2, 64, 16)]   # q, k, v
    # q, k, v, dO, and the row statistics as [1, bq] blocks
    assert backward[3:7] == [(2, 4 * 64, 16), (2, 64, 16), (2, 64, 16),
                             (2, 4 * 64, 16)]
    assert backward[7:] == [(2, 4 * 4, 1, 16)] * 2


def test_attention_predicates_state_the_head_rule():
    q = jnp.zeros((1, 32, 4096, 64), jnp.bfloat16)
    kv = jnp.zeros((1, 8, 4096, 64), jnp.bfloat16)
    assert tier.attention.attention_supports(q, kv, kv)
    assert tier.attention.attention_profitable(q, kv, kv)
    assert tier.attention.attention_supports(q, q, q)
    # 32 query heads over 5 key-value heads, or values over other heads than
    # the keys, or another batch, are no attention
    assert not tier.attention.attention_supports(q, kv[:, :5], kv[:, :5])
    assert not tier.attention.attention_supports(q, kv, q)
    assert not tier.attention.attention_supports(
        q, jnp.zeros((2, 8, 4096, 64), jnp.bfloat16),
        jnp.zeros((2, 8, 4096, 64), jnp.bfloat16))


# ---------------------------------------------------------------------------
# half-split rotary, per-head RMSNorm
# ---------------------------------------------------------------------------

def test_rotary_half_split_hand_case():
    """d = 4: pairs (x0, x2) at angle pos and (x1, x3) at pos / base^(1/2)."""
    x = jnp.asarray([1.0, 2.0, 3.0, 4.0]).reshape(1, 1, 4)     # [T, heads, d]
    out = np.asarray(rotary_half_split(x, jnp.asarray([2]), base=100.0))
    a0, a1 = 2.0, 2.0 / 10.0
    want = [1 * np.cos(a0) - 3 * np.sin(a0), 2 * np.cos(a1) - 4 * np.sin(a1),
            1 * np.sin(a0) + 3 * np.cos(a0), 2 * np.sin(a1) + 4 * np.cos(a1)]
    np.testing.assert_allclose(out.reshape(4), want, rtol=1e-6)
    # position 0 turns nothing
    np.testing.assert_allclose(
        rotary_half_split(x, jnp.asarray([0]), base=100.0), x)


def test_rotary_half_split_is_the_interleaved_form_on_a_permuted_axis():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 6, 3, 8)))              # [B, T, h, d]
    pos = jnp.arange(6)
    perm = np.stack([np.arange(4), np.arange(4) + 4], 1).reshape(8)
    inter = rotary_interleaved(x[..., perm], pos, 1e4)  # pairs (x_i, x_i+4)
    np.testing.assert_allclose(rotary_half_split(x, pos, 1e4)[..., perm],
                               inter, rtol=1e-6)
    # and the two forms are not interchangeable
    assert np.abs(np.asarray(rotary_half_split(x, pos, 1e4)
                             - rotary_interleaved(x, pos, 1e4))).max() > 0.1


def test_rms_norm_per_head_shares_one_gain_over_the_heads():
    """q [B, T, heads, d] normed over d with one gain [d]: every head by its
    own root mean square."""
    rng = np.random.default_rng(1)
    q = rng.normal(size=(2, 3, 4, 8)) * np.arange(1, 5)[:, None]
    gain = rng.normal(size=(8,))
    got = rms_norm(jnp.asarray(q), jnp.asarray(gain), 1e-5)
    for h in range(4):
        one = q[:, :, h]
        want = one / np.sqrt((one ** 2).mean(-1, keepdims=True) + 1e-5) * gain
        np.testing.assert_allclose(got[:, :, h], want, rtol=1e-6)


# ---------------------------------------------------------------------------
# the router and a layer with no shared expert
# ---------------------------------------------------------------------------

def test_router_epsilon_is_a_parameter_and_scale_one_leaves_the_weights():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(5, 6)))
    w = jnp.asarray(rng.normal(size=(6, 8)))
    bias = jnp.zeros((8,))
    chosen, weights = moe.router(x, w, bias, top_k=2, scale=1.0, eps=1e-6)
    s = 1.0 / (1.0 + np.exp(-np.asarray(x) @ np.asarray(w)))
    top = np.sort(s, -1)[:, ::-1][:, :2]
    np.testing.assert_allclose(weights, top / (top.sum(-1, keepdims=True)
                                               + 1e-6), rtol=1e-6)
    np.testing.assert_array_equal(
        np.sort(chosen, -1), np.sort(np.argsort(s, -1)[:, -2:], -1))
    # the default is DeepSeek-V3's 1e-20: the weights then sum to one to the
    # last bit where 1e-6 leaves them short
    _, w20 = moe.router(x, w, bias, top_k=2, scale=1.0)
    assert np.all(np.asarray(weights.sum(-1)) < 1.0)
    np.testing.assert_allclose(w20.sum(-1), 1.0, rtol=1e-12)


def test_expert_layer_without_shared_experts_is_the_routed_part():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(12, 6)))
    p = {"router": jnp.asarray(rng.normal(size=(6, 8))),
         "w_gate": jnp.asarray(rng.normal(size=(8, 6, 4))),
         "w_up": jnp.asarray(rng.normal(size=(8, 6, 4))),
         "w_down": jnp.asarray(rng.normal(size=(8, 4, 6)))}
    bias = jnp.zeros((8,))
    y, counts, _ = moe.expert_layer(x, p, bias, top_k=2, scale=1.0,
                                    first_held=0, eps=1e-6)
    chosen, weights = moe.router(x, p["router"], bias, 2, 1.0, 1e-6)
    want = np.zeros((12, 6))
    for t in range(12):
        for e, w in zip(np.asarray(chosen[t]), np.asarray(weights[t])):
            want[t] += w * np.asarray(moe.swiglu(
                x[t], p["w_gate"][e], p["w_up"][e], p["w_down"][e]))
    np.testing.assert_allclose(y, want, rtol=1e-6, atol=1e-9)
    assert int(counts.sum()) == 24
    # with shared matrices the same call adds their SwiGLU
    shared = {"shared_gate": jnp.asarray(rng.normal(size=(6, 4))),
              "shared_up": jnp.asarray(rng.normal(size=(6, 4))),
              "shared_down": jnp.asarray(rng.normal(size=(4, 6)))}
    y2, _, _ = moe.expert_layer(x, {**p, **shared}, bias, top_k=2,
                                scale=1.0, first_held=0, eps=1e-6)
    np.testing.assert_allclose(
        y2 - y, moe.swiglu(x, shared["shared_gate"], shared["shared_up"],
                           shared["shared_down"]), rtol=1e-6, atol=1e-9)


def test_the_shares_add_up_without_a_shared_expert():
    """8 experts cut into 4 shares of 2, no shared expert, epsilon 1e-6 and
    scale 1: the shares' routed parts sum to the uncut layer, token by token
    and expert by expert."""
    rng = np.random.default_rng(4)
    t, h, i, e, k = 24, 16, 8, 8, 2
    x = jnp.asarray(rng.normal(size=(t, h)))
    bias = jnp.asarray(rng.normal(size=(e,)) * 0.1)
    p = {"router": jnp.asarray(rng.normal(size=(h, e)) * 0.3),
         "w_gate": jnp.asarray(rng.normal(size=(e, h, i)) * 0.3),
         "w_up": jnp.asarray(rng.normal(size=(e, h, i)) * 0.3),
         "w_down": jnp.asarray(rng.normal(size=(e, i, h)) * 0.3)}
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(p["router"]))))
    chosen = np.argsort(-(s + np.asarray(bias)), -1, kind="stable")[:, :k]
    want = np.zeros((t, h))
    for tok in range(t):
        w = s[tok, chosen[tok]]
        w = w / (w.sum() + 1e-6)
        for j, ex in enumerate(chosen[tok]):
            want[tok] += w[j] * np.asarray(moe.swiglu(
                x[tok], p["w_gate"][ex], p["w_up"][ex], p["w_down"][ex]))
    total = 0.0
    for first in range(0, e, 2):
        share = {**p, **{n: p[n][first:first + 2]
                         for n in ("w_gate", "w_up", "w_down")}}
        y, counts, _ = moe.expert_layer(x, share, bias, top_k=k, scale=1.0,
                                        first_held=first, eps=1e-6)
        total = total + y
        assert int(counts.sum()) == t * k         # every share counts all E
    np.testing.assert_allclose(total, want, atol=1e-6)


# ---------------------------------------------------------------------------
# the model over a layer list
# ---------------------------------------------------------------------------

A, C = "full_attention", "conv"


def _batch(seed=0, rows=2, t=16, vocab=96):
    ids = np.random.default_rng(seed).integers(0, vocab, (rows, t)).astype(
        np.int32)
    labels = np.concatenate([ids[:, 1:], np.zeros((rows, 1), np.int32)], 1)
    return MultiDataSet(features=[ids], labels=[labels])


@pytest.mark.parametrize("kinds,dense,want", [
    # the cell's cut: one whole period under the scan
    ((C, A, C, C, C), 1, (C, (A, C, C, C), 1, ())),
    # period scan and a remainder
    ((C, C, A, C, C, C, A, C, C, C, A, C), 2,
     (C, (A, C, C, C), 2, (A, C))),
    # the published 40 layers: 2 dense, 9 periods, `a c` left over
    ((C, C, A, C) * 10, 2, (C, (A, C, C, C), 9, (A, C))),
    # all alike: a period of one layer, as kanana
    (None, 1, ("latent_attention", ("latent_attention",), 4, ())),
    # a list that never repeats is one period
    ((C, A, A, C), 1, (C, (A, A, C), 1, ())),
])
def test_layout_cuts_the_expert_layers_into_whole_periods(kinds, dense, want):
    n = 5 if kinds is None else len(kinds)
    c = DecoderConfig.tiny_hybrid(layer_types=kinds, n_layers=n,
                                  n_dense_layers=dense)
    assert c.layout() == want
    dense_kind, period, periods, rest = c.layout()
    assert (dense_kind,) * dense + period * periods + rest == c.kinds


def test_hybrid_model_trains_through_fit_and_its_trees_follow_the_list():
    kinds = (C, C, A, C, C, C, A, C, C, C, A, C)
    m = DecoderModel(DecoderConfig.tiny_hybrid(
        layer_types=kinds, n_layers=12, n_dense_layers=2, first_expert=2,
        n_experts_held=4), seed=1)
    p = m.params_
    assert "head" not in p                                  # tied
    assert p["dense"]["conv_in"].shape == (2, 32, 96)
    assert p["dense"]["conv_kernel"].shape == (2, 3, 32)
    assert [sorted(set(lp) & {"Wqkv", "conv_in"}) for lp in p["moe"]] == [
        ["Wqkv"], ["conv_in"], ["conv_in"], ["conv_in"]]
    assert p["moe"][0]["Wqkv"].shape == (2, 32, (4 + 2 * 2) * 8)
    assert p["moe"][0]["q_norm"].shape == (2, 8)
    assert p["moe"][1]["w_gate"].shape == (2, 4, 32, 16)
    assert not any("shared_gate" in lp for lp in p["moe"])
    assert [sorted(set(lp) & {"Wqkv", "conv_in"}) for lp in p["rest"]] == [
        ["Wqkv"], ["conv_in"]]
    assert p["rest"][0]["Wqkv"].shape == (32, 64)
    assert m.state_["router_bias"].shape == (10, 8)
    first = float(m.fit_batch(_batch()))
    m.fit([_batch()] * 7)
    assert m.iteration == 8 and m.epoch == 1 and m.score() < first
    load = m.expert_load()
    assert load.shape == (10, 8)
    np.testing.assert_array_equal(load.sum(1), [8 * 2 * 16 * 2] * 10)
    bias = np.asarray(m.state_["router_bias"])
    assert np.all(np.abs(bias) <= 8 * 1e-3 + 1e-9) and np.all(
        np.any(bias != 0, axis=1))
    assert m.output(_batch().features[0]).shape == (2, 16, 96)


def test_a_hybrid_on_a_row_bound_is_the_hybrid_on_the_full_pass(monkeypatch):
    """`tests/test_decoder.py`'s case for the period `a c c c`: four
    distinct expert blocks under one scan."""
    bounded_model_against_the_full_pass(
        DecoderConfig.tiny_hybrid(hidden=16, n_experts=16, n_experts_held=2,
                                  first_expert=4), 4, monkeypatch)


def test_a_tied_head_is_the_embeddings_transpose():
    m = DecoderModel(DecoderConfig.tiny_hybrid(), seed=2)
    hidden = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 32))
    gain = jnp.linspace(0.5, 1.5, 32)
    params = {**m.params_, "final_norm": gain}
    want = rms_norm(hidden, gain, 1e-5) @ m.params_["tok_emb"].T
    np.testing.assert_allclose(m._logits(params, hidden), want, rtol=1e-4,
                               atol=1e-5)


def test_hybrid_fit_steps_and_save_load_round_trip():
    a = DecoderModel(DecoderConfig.tiny_hybrid(), seed=4)
    b = DecoderModel(DecoderConfig.tiny_hybrid(), seed=4)
    b1, b2 = _batch(1), _batch(2)
    la = [float(a.fit_batch(b1)), float(a.fit_batch(b2))]
    lb = b.fit_steps(MultiDataSet(
        features=[np.stack([b1.features[0], b2.features[0]])],
        labels=[np.stack([b1.labels[0], b2.labels[0]])]))
    np.testing.assert_allclose(np.asarray(lb), la, rtol=1e-5)
    np.testing.assert_array_equal(a.expert_load(), b.expert_load())
    f = io.BytesIO()
    a.save(f)
    f.seek(0)
    c = DecoderModel.load(f)
    assert c.iteration == 2 and c.num_params() == a.num_params()
    assert c.config.kinds == a.config.kinds
    ids = _batch().features[0]
    np.testing.assert_array_equal(np.asarray(a.output(ids)),
                                  np.asarray(c.output(ids)))
    assert float(a.fit_batch(_batch(3))) == float(c.fit_batch(_batch(3)))


@pytest.mark.parametrize("changes,message", [
    (dict(layer_types=(C, A, C)), "names 3 layers"),
    (dict(layer_types=(C, A, C, C, "mamba")), "mamba"),
    (dict(layer_types=(C, A, C, C, C), n_dense_layers=2), "share one kind"),
    (dict(n_dense_layers=5), "at least one expert layer"),
    (dict(n_heads=3), "no multiple"),
])
def test_a_layer_list_the_model_cannot_build_is_refused(changes, message):
    with pytest.raises(ValueError, match=message):
        DecoderModel(DecoderConfig.tiny_hybrid(**changes))


def test_the_attention_layer_hands_the_kernels_its_own_key_value_heads():
    """One train step's gradient with the kernels forced: a forward and ONE
    backward kernel for the list's attention layer (a scan's body counts
    once; the forward is not run again in the backward pass), keys and values
    entering both as [B * 2, T, 8]; the convolution's in-projection is one
    product of width 3H."""
    tier.dispatch.set_dispatch_mode("pallas")
    tier.dispatch.set_tile("attention", tier.TileConfig(block_q=16,
                                                        block_kv=32))
    m = DecoderModel(DecoderConfig.tiny_hybrid(vocab_size=80), seed=6)
    batch = _batch(5, t=64, vocab=80)       # 80: no other product 96 wide
    ids, labels = (jnp.asarray(batch.features[0]),
                   jnp.asarray(batch.labels[0]))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: m._loss(p, m.state_["router_bias"], ids, labels)[0]))(
            m.params_)
    calls = [(e.params["jaxpr"].debug_info.func_name,
              [v.aval.shape for v in e.invars])
             for e in _equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    attention = [c for c in calls if c[0].startswith("_flash")]
    assert sorted(n for n, _ in attention) == ["_flash_bwd_kernel",
                                               "_flash_kernel"]
    for name, operands in attention:
        assert operands.count((2 * 2, 64, 8)) == 2          # k and v
        # q: a grid row a query head forward, a key-value head with its
        # group's two query heads backward
        assert ((2 * 4, 64, 8) if name == "_flash_kernel"
                else (2 * 2, 2 * 64, 8)) in operands
    in_proj = [e for e in _equations(jaxpr.jaxpr)
               if e.primitive.name == "dot_general"
               and e.outvars[0].aval.shape == (2, 64, 96)]
    assert in_proj and all(
        e.invars[1].aval.shape == (32, 96) for e in in_proj)
    assert float(m.fit_batch(batch)) > 0
