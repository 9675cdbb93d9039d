"""Kimi delta attention through `zoo.DecoderModel` at tiny size on the CPU,
float32: the chunkwise delta rule against its token-by-token recurrence,
forward and gradient, in both tiers (the recurrence across chunks as its
`jax.numpy` definition and as the Mosaic kernels in interpret mode), where
the tail is no whole chunk, the decay is strong, beta is near 2 and the
state starts nonzero; the kernels (across the chunks and inside them)
against their definitions; the layer kind
through `fit`, its counter, `save` and `load`; gated NoPE attention; a share
of the heads, and the shares that cannot be held."""
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import MultiDataSet
from deeplearning4j_tpu.ops import linear_attention as la
from deeplearning4j_tpu.ops import pallas as tier
from deeplearning4j_tpu.ops.pallas import delta_rule as kernels
from deeplearning4j_tpu.zoo import DecoderConfig, DecoderModel

LIMIT = 1e-5        # float32: of the largest magnitude of the reference's


@pytest.fixture(autouse=True)
def _reset_tier():
    yield
    tier.dispatch.reset()


def _inputs(seed, B=1, H=2, T=70, dk=16, dv=8, A=None, dt_shift=0.0,
            beta_max=2.0):
    """q, k L2-normed; g = -A softplus(N(0, 1) + dt_shift) with A ~ U(1, 16)
    a head (or `A`); beta = beta_max sigmoid(N(0, 1))."""
    r = np.random.default_rng(seed)
    q = la.l2_normalize(jnp.asarray(r.normal(size=(B, H, T, dk)), jnp.float32))
    k = la.l2_normalize(jnp.asarray(r.normal(size=(B, H, T, dk)), jnp.float32))
    v = jnp.asarray(r.normal(size=(B, H, T, dv)), jnp.float32)
    a = r.uniform(1, 16, size=H) if A is None else np.full(H, A)
    g = -a[None, :, None, None] * np.logaddexp(
        0.0, r.normal(size=(B, H, T, dk)) + dt_shift)
    beta = beta_max / (1 + np.exp(-r.normal(size=(B, H, T))))
    s0 = r.normal(size=(B, H, dk, dv))
    return (q, k, v, jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32),
            jnp.asarray(s0, jnp.float32))


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


CASES = {
    "tail": dict(T=70),                       # 2 chunks of 32 and 6 tokens
    "strong_decay": dict(A=16.0, dt_shift=4.0),   # g down to -110 a token
    "beta_near_2": dict(beta_max=2.0 * (1 - 1e-6)),
    "one_chunk": dict(T=32),
    "few_tokens": dict(T=5),
}


@pytest.mark.parametrize("mode", ["reference", "pallas"])
@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("start", ["zero", "nonzero"])
def test_chunks_against_the_token_recurrence(mode, case, start):
    """Outputs, the last state and every gradient (of a loss on both) of
    `chunk_delta_rule` against `delta_rule_recurrent`, within `LIMIT` of the
    reference's largest magnitude."""
    tier.dispatch.set_dispatch_mode(mode)
    q, k, v, g, beta, s0 = _inputs(0, **CASES[case])
    s0 = None if start == "zero" else s0
    chunked = lambda *a: la.chunk_delta_rule(*a, chunk=32)  # noqa: E731
    o, s = jax.jit(chunked)(q, k, v, g, beta, s0)
    o_ref, s_ref = jax.jit(la.delta_rule_recurrent)(q, k, v, g, beta, s0)
    assert _rel(o, o_ref) < LIMIT and _rel(s, s_ref) < LIMIT

    def loss(fn):
        def f(*a):
            o, s = fn(*a)
            return jnp.sum(jnp.sin(o)) + jnp.sum(s ** 2)
        return f

    args = (q, k, v, g, beta) + (() if s0 is None else (s0,))
    which = tuple(range(len(args)))
    got = jax.jit(jax.grad(loss(chunked), which))(*args)
    want = jax.jit(jax.grad(loss(la.delta_rule_recurrent), which))(*args)
    for name, a, b in zip("q k v g beta s0".split(), got, want):
        assert _rel(a, b) < LIMIT, name


def test_a_strong_decay_forgets_and_never_overflows():
    """At A = 16 and softplus near 6 a channel decays by e^-96 a token: the
    output is the token's own write, and nothing in the chunk form is inf
    or nan (no exp(-cumsum g) is formed)."""
    q, k, v, g, beta, _ = _inputs(1, A=16.0, dt_shift=6.0)
    o, s = jax.jit(lambda *a: la.chunk_delta_rule(*a, chunk=32))(
        q, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(s)))
    own = (beta[..., None] * jnp.einsum("bhtk,bhtk->bht", q, k)[..., None]
           * v) * q.shape[-1] ** -0.5
    np.testing.assert_allclose(o, own, atol=1e-6)
    grads = jax.jit(jax.grad(lambda g: jnp.sum(la.chunk_delta_rule(
        q, k, v, g, beta, chunk=32)[0])))(g)
    assert bool(jnp.all(jnp.isfinite(grads)))


def test_chunk_length_changes_no_number():
    q, k, v, g, beta, s0 = _inputs(2, T=130, dk=16, dv=16)
    outs = [jax.jit(lambda *a, c=c: la.chunk_delta_rule(*a, chunk=c))(
        q, k, v, g, beta, s0) for c in (16, 64, 128)]
    for o, s in outs[1:]:
        assert _rel(o, outs[0][0]) < LIMIT and _rel(s, outs[0][1]) < LIMIT


def _chunk_operands(seed, BH=3, N=4, C=16, dk=16, dv=8):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.normal(size=s) * 0.5, jnp.float32)  # noqa
    T = N * C
    aqk = jnp.tril(f(BH, N, C, C)).reshape(BH, T, C)
    gc = jnp.asarray(r.uniform(0.2, 1.0, size=(BH, N, 1, dk)), jnp.float32)
    return (f(BH, T, dk), f(BH, T, dv), f(BH, T, dk), f(BH, T, dk), gc, aqk,
            f(BH, dv, dk))


def test_the_kernels_against_their_definitions():
    """Forward (outputs, every chunk's entering state, the last state) and
    the reverse walk (every gradient and the first state's) of the Mosaic
    kernels in interpret mode, three heads a grid step."""
    ops = _chunk_operands(3)
    got = kernels.across_chunks(*ops, interpret=True)
    want = kernels.across_chunks_reference(*ops)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    r = np.random.default_rng(4)
    do = jnp.asarray(r.normal(size=ops[1].shape), jnp.float32)
    d_last = jnp.asarray(r.normal(size=ops[-1].shape), jnp.float32)
    got = kernels.across_chunks_bwd(do, *ops[:-1], want[1], d_last,
                                    interpret=True)
    ref = kernels.across_chunks_bwd_reference(do, *ops[:-1], want[1], d_last)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    # the definition's reverse walk is the forward's VJP
    _, vjp = jax.vjp(lambda *a: kernels.across_chunks_reference(*a)[::2],
                     *ops)
    for a, b in zip(ref, vjp((do, d_last))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def _by_chunks(a, chunk):
    """[B, H, T, ...] -> [B H, N, C, ...] as `chunk_delta_rule` splits it
    (the tail padded with zeros)."""
    B, H, T = a.shape[:3]
    C = min(chunk, -(-T // 8) * 8)
    N = -(-T // C)
    a = jnp.pad(a, [(0, 0), (0, 0), (0, N * C - T)]
                + [(0, 0)] * (a.ndim - 3))
    return a.reshape(B * H, N, C, *a.shape[3:])


def _within(a, b):
    """Largest difference within `LIMIT` of the reference's largest
    magnitude (a decay strong enough leaves `gc` all zeros)."""
    return float(jnp.max(jnp.abs(a - b))) <= LIMIT * float(
        jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("chunk", [32, 64])
@pytest.mark.parametrize("case", list(CASES))
def test_the_inside_kernels_against_their_definition(case, chunk):
    """The chunks' insides as Mosaic kernels in interpret mode: the forward
    (w, u, qg, kd, gc, aqk) against `_within_chunks`, the backward (dq, dk,
    dv, dg, dbeta) against autodiff's VJP of it."""
    q, k, v, g, beta, _ = _inputs(5, **CASES[case])
    ops = [_by_chunks(a, chunk) for a in (q, k, v, g, beta)]
    scale = q.shape[-1] ** -0.5
    want = jax.jit(lambda *a: la._within_chunks(*a, scale))(*ops)
    got = jax.jit(lambda *a: kernels.within_chunks(
        *a, scale, interpret=True))(*ops)
    for name, a, b in zip("w u qg kd gc aqk".split(), got, want):
        assert a.shape == b.shape and _within(a, b), name
    r = np.random.default_rng(6)
    cts = tuple(jnp.asarray(r.normal(size=a.shape), jnp.float32)
                for a in want)
    vjp = jax.jit(lambda ops, cts: jax.vjp(
        lambda *a: la._within_chunks(*a, scale), *ops)[1](cts))
    got = jax.jit(lambda *a: kernels.within_chunks_bwd(
        *a, scale, interpret=True))(*ops, *cts)
    for name, a, b in zip("q k v g beta".split(), got, vjp(ops, cts)):
        assert a.shape == b.shape and _within(a, b), name


def test_the_tier_takes_the_kernels_where_it_says():
    w = jnp.zeros((8, 128, 128), jnp.float32)
    tier.dispatch.set_dispatch_mode("reference")
    inside, across = la._tier(w)
    assert across[0] is kernels.across_chunks_reference
    assert inside == (la._within_chunks, la._within_chunks_bwd)
    tier.dispatch.set_dispatch_mode("pallas")
    inside, across = la._tier(w)
    assert across[0].func is kernels.across_chunks
    assert [f.func for f in inside] == [kernels.within_chunks,
                                       kernels.within_chunks_bwd]


# ---------------------------------------------------------------------------
# the layer kind through the model
# ---------------------------------------------------------------------------

def _batch(T=40, seed=0, rows=2, vocab=96):
    ids = np.random.default_rng(seed).integers(0, vocab, (rows, T)).astype(
        np.int32)
    labels = np.concatenate([ids[:, 1:], np.zeros((rows, 1), np.int32)], 1)
    return MultiDataSet(features=[ids], labels=[labels])


@pytest.mark.parametrize("mode", ["reference", "pallas"])
def test_the_model_trains_through_fit_and_counts_its_updates(mode):
    tier.dispatch.set_dispatch_mode(mode)
    c = DecoderConfig.tiny_linear()
    assert c.layout() == ("full_attention", ("full_attention",) + (
        "linear_attention",) * 3, 1, ())
    model = DecoderModel(c, seed=1)
    batch = _batch(T=40)
    first = float(model.fit_batch(batch))
    for _ in range(5):
        last = float(model.fit_batch(batch))
    assert np.isfinite(last) and last < first
    stats = model.linear_stats()
    # every (token, head) of the three linear layers, every step
    assert stats == {"steps": 6, "delta_rule_updates": 6 * 2 * 40 * 4 * 3,
                     "per_token": 12.0}
    np.testing.assert_array_equal(model.state_["delta_rule_updates"],
                                  [0, 2 * 40 * 4 * 6, 2 * 40 * 4 * 6,
                                   2 * 40 * 4 * 6])


def test_save_and_load_keep_the_layer_and_its_counter():
    model = DecoderModel(DecoderConfig.tiny_linear(), seed=2)
    batch = _batch(T=24)
    model.fit_batch(batch)
    buf = io.BytesIO()
    model.save(buf)
    buf.seek(0)
    back = DecoderModel.load(buf)
    ids = batch.features[0]
    np.testing.assert_array_equal(back.output(ids), model.output(ids))
    # the counter travels; the batch shape (`per_token`) comes with a step
    for name in ("steps", "delta_rule_updates"):
        assert back.linear_stats()[name] == model.linear_stats()[name]


def test_the_parameters_of_a_linear_layer():
    """q, k, v in one product, the taps, the low-rank decay and gate of
    rank `head_dim`, A = U(1, 16), dt = softplus(dt_bias) in [1e-3, 1e-1]."""
    c = DecoderConfig.tiny_linear(n_layers=8, layer_types=(
        "full_attention", "linear_attention", "linear_attention",
        "linear_attention") * 2)
    p = DecoderModel(c, seed=3).params_["moe"][1]
    shapes = {k: v.shape for k, v in p.items() if k not in (
        "router", "w_gate", "w_up", "w_down", "shared_gate", "shared_up",
        "shared_down", "norm2")}
    assert shapes == {
        "norm1": (2, 32), "Wqkv": (2, 32, 96), "conv_qkv": (2, 4, 96),
        "Wf_a": (2, 32, 8), "Wf_b": (2, 8, 32), "A_log": (2, 4),
        "dt_bias": (2, 32), "Wbeta": (2, 32, 4), "Wg_a": (2, 32, 8),
        "Wg_b": (2, 8, 32), "o_norm": (2, 8), "Wo": (2, 32, 32)}
    a = np.exp(np.asarray(p["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.logaddexp(0.0, np.asarray(p["dt_bias"], np.float64))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 0.1 * 1.001


def test_gated_nope_attention_is_the_gate_times_plain_attention():
    """`full_attention` with no rotary, no q/k norm and the output gate:
    the heads' softmax over causal keys as written by hand, times
    sigmoid(h W_g), then W_o."""
    c = DecoderConfig.tiny_linear()
    model = DecoderModel(c, seed=4)
    lp = jax.tree_util.tree_map(lambda a: a[0], model.params_["moe"][0])
    assert "q_norm" not in lp and lp["Wg"].shape == (32, 32)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 12, 32)),
                    jnp.float32)
    got = model._gqa_attention(x, lp) - x
    h = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + c.eps)
    qkv = (h @ lp["Wqkv"]).reshape(1, 12, 8, 8)
    q, k, v = qkv[:, :, :4], qkv[:, :, 4:6], qkv[:, :, 6:]
    k, v = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(8)
    s = jnp.where(jnp.tril(jnp.ones((12, 12), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    want = (o.reshape(1, 12, 32) * jax.nn.sigmoid(h @ lp["Wg"])) @ lp["Wo"]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_a_share_of_the_heads_holds_their_columns():
    """8 heads over 2 key-value heads, 4 held from the 4th: the shapes of
    both kinds shrink to the share; the counter counts the held heads."""
    c = DecoderConfig.tiny_linear(n_heads=8, first_head=4, n_heads_held=4)
    assert c.heads_held == (4, 1)
    model = DecoderModel(c, seed=6)
    gqa, lin = model.params_["moe"][0], model.params_["moe"][1]
    assert gqa["Wqkv"].shape == (1, 32, (4 + 2) * 8)
    assert gqa["Wo"].shape == (1, 32, 32) and gqa["Wg"].shape == (1, 32, 32)
    assert lin["Wqkv"].shape == (1, 32, 96) and lin["A_log"].shape == (1, 4)
    model.fit_batch(_batch(T=16))
    assert model.linear_stats()["per_token"] == 12.0


@pytest.mark.parametrize("changes,message", [
    (dict(n_heads=8, first_head=2, n_heads_held=4), "whole key-value heads"),
    (dict(n_heads=8, first_head=0, n_heads_held=3), "whole key-value heads"),
    (dict(n_heads=8, first_head=6, n_heads_held=4), "whole key-value heads"),
    (dict(n_dense_layers=1, n_layers=5, layer_types=(
        "linear_attention", "full_attention") + ("linear_attention",) * 3),
     "after the dense layers"),
    (dict(objective="block_diffusion", mask_token_id=95), "recurrence"),
])
def test_a_model_that_cannot_be_built_is_refused(changes, message):
    with pytest.raises(ValueError, match=message):
        DecoderModel(DecoderConfig.tiny_linear(**changes))


def test_a_head_share_of_latent_attention_is_refused():
    with pytest.raises(ValueError, match="whole key-value heads"):
        DecoderModel(DecoderConfig.tiny(n_heads_held=1))
