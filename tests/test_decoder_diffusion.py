"""Block-diffusion training through `zoo.DecoderModel` at tiny size on the CPU,
float32: the block mask in every branch of `fused_attention` against the
mask written out as a boolean array; what the 2L-row training forward means
(the forward a block-wise decode would run); the softmax router's shares;
the step's noise; a model with no dense layer through `fit`, `save` and
`load`; and the two older presets' losses, bit for bit what they were."""
import dataclasses
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import MultiDataSet
from deeplearning4j_tpu.ops import attention_kernels as ak
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.ops import pallas as tier
from deeplearning4j_tpu.zoo import DecoderConfig, DecoderModel


@pytest.fixture(autouse=True)
def _reset_tier():
    yield
    tier.dispatch.reset()


# ---------------------------------------------------------------------------
# (a) the mask, in every branch
# ---------------------------------------------------------------------------

def _mask_by_rules(T, L, B):
    """Rows and columns `[noisy (T - L) ; clean (L)]`, pair by pair."""
    o = T - L
    keep = np.zeros((T, T), bool)
    for r in range(T):
        for s in range(T):
            r_noisy, s_noisy = r < o, s < o
            r_blk = (r if r_noisy else r - o) // B
            s_blk = (s if s_noisy else s - o) // B
            if r_noisy and s_noisy:
                keep[r, s] = s_blk == r_blk
            elif r_noisy:
                keep[r, s] = s_blk < r_blk
            elif not s_noisy:
                keep[r, s] = s_blk <= r_blk
    return keep


def _attention_under(keep, q, k, v):
    """Plain softmax attention under a boolean [T, S] mask, query head `h`
    over key-value head `h // group`."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _force(branch, monkeypatch, taken):
    """Send `fused_attention` down one branch and note that it went."""
    def noting(real):
        return lambda *a, **kw: (taken.append(branch), real(*a, **kw))[1]
    if branch == "blockwise":
        monkeypatch.setattr(ak, "_XLA_SCORE_BYTES_MAX", 0)
        real = ak.blockwise_attention
        monkeypatch.setattr(
            ak, "blockwise_attention", lambda *a, **kw: (
                taken.append(branch), real(*a[:6], 16, *a[7:], **kw))[1])
    elif branch == "flash":
        tier.dispatch.set_dispatch_mode("pallas")
        tier.dispatch.set_tile("attention", tier.TileConfig(block_q=16,
                                                            block_kv=32))
        monkeypatch.setattr(tier.attention, "flash_attention",
                            noting(tier.attention.flash_attention))
    else:
        monkeypatch.setattr(ak, "mha_reference", noting(ak.mha_reference))


@pytest.mark.parametrize("block", [1, 4, 32])
@pytest.mark.parametrize("branch", ["xla", "blockwise", "flash"])
def test_block_diffusion_mask_in_every_branch(branch, block, monkeypatch):
    """Forward and dQ, dK, dV over the noisy and the clean copy of 32
    tokens, 4 query heads over 2 key-value heads, blocks of 1, 4 and the
    whole sequence, against the mask as an explicit boolean array; the
    Pallas kernels in interpret mode, their tiles 16 x 32."""
    L, T = 32, 64
    keys = jax.random.split(jax.random.PRNGKey(block), 4)
    q = jax.random.normal(keys[0], (2, 4, T, 16), jnp.float32)
    k = jax.random.normal(keys[1], (2, 2, T, 16), jnp.float32)
    v = jax.random.normal(keys[2], (2, 2, T, 8), jnp.float32)
    g = jax.random.normal(keys[3], (2, 4, T, 8), jnp.float32)
    keep = _mask_by_rules(T, L, block)
    assert keep.sum() == L * L + L * block          # the live pairs
    assert not keep[L:, :L].any()                   # no clean row on noise
    taken = []
    _force(branch, monkeypatch, taken)

    def fused(q, k, v):
        return ak.fused_attention(q, k, v, block_diffusion=(L, block))

    out = fused(q, k, v)
    assert taken == [branch]
    got = jax.grad(lambda *a: jnp.sum(fused(*a) * g), (0, 1, 2))(q, k, v)
    monkeypatch.undo()
    tier.dispatch.reset()
    np.testing.assert_allclose(out, _attention_under(keep, q, k, v),
                               atol=1e-5)
    want = jax.grad(lambda *a: jnp.sum(_attention_under(keep, *a) * g),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("branch", ["xla", "blockwise", "flash"])
def test_the_clean_rows_alone_are_attention_causal_over_blocks(branch,
                                                                monkeypatch):
    """`block_diffusion=(L, B)` over L rows: no noisy copy, so a row sees
    its own block and the earlier ones — the inference forward."""
    L, B = 32, 4
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (1, 4, L, 16), jnp.float32)
    k = jax.random.normal(keys[1], (1, 2, L, 16), jnp.float32)
    v = jax.random.normal(keys[2], (1, 2, L, 16), jnp.float32)
    keep = _mask_by_rules(L, L, B)
    blk = np.arange(L) // B
    np.testing.assert_array_equal(keep, blk[None, :] <= blk[:, None])
    taken = []
    _force(branch, monkeypatch, taken)
    out = ak.fused_attention(q, k, v, block_diffusion=(L, B))
    assert taken == [branch]
    np.testing.assert_allclose(out, _attention_under(keep, q, k, v),
                               atol=1e-5)


def test_backward_in_spans_under_the_block_mask(monkeypatch):
    """The backward kernel's query spans (dQ budget forced to 16 rows of a
    group of 2 heads: four spans, two of noisy and two of clean rows)."""
    L, B, T = 32, 4, 64
    monkeypatch.setattr(ak, "_BWD_DQ_VMEM", 16 * 2 * 16 * 12)
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], (1, 4, T, 16), jnp.float32)
    k = jax.random.normal(keys[1], (1, 2, T, 16), jnp.float32)
    v = jax.random.normal(keys[2], (1, 2, T, 16), jnp.float32)
    g = jax.random.normal(keys[3], (1, 4, T, 16), jnp.float32)
    assert ak._bwd_plan(T, T, 16, 16, 4, 16, 32, 2) == (16, 32, 16)
    out, lse = ak.flash_attention_tpu(q, k, v, block_q=16, block_k=32,
                                      interpret=True, return_lse=True,
                                      block_diffusion=(L, B))
    got = ak.flash_attention_bwd_tpu(q, k, v, out, lse, g, block_q=16,
                                     block_k=32, interpret=True,
                                     block_diffusion=(L, B))
    keep = _mask_by_rules(T, L, B)
    want = jax.grad(lambda *a: jnp.sum(_attention_under(keep, *a) * g),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("blocks", [(16, 32), (32, 16), (8, 8)],
                         ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("T,L,B", [(64, 32, 4), (32, 32, 4), (64, 32, 32),
                                   (64, 32, 1), (192, 96, 3)])
def test_the_schedule_of_live_tiles_is_the_mask_written_out(T, L, B, blocks):
    """`tile_schedule` under the block mask against the mask pair by pair:
    it walks the tiles that keep a pair and no other, and calls full those
    that keep every pair."""
    bq, bk = blocks
    tiles = _mask_by_rules(T, L, B).reshape(T // bq, bq, T // bk, bk)
    live, full = tiles.any((1, 3)), tiles.all((1, 3))
    sched = ak.tile_schedule(T, T, bq, bk, block_diffusion=(L, B))
    assert sched.counts == (live.size, live.sum(), full.sum())
    assert sorted(zip(sched.q, sched.k)) == list(zip(*np.nonzero(live)))
    assert [not f & ak.PARTIAL for f in sched.flags] == list(
        full[sched.q, sched.k])


def test_a_span_writes_and_the_sum_keeps_the_key_blocks_it_saw(monkeypatch):
    """The backward in four spans of 16 queries under the block mask: the
    spans of clean rows see no noisy key, so their dK/dV parts hold rows that
    were never written (NaN in interpret mode); the sum over the spans keeps
    the written blocks alone and is `mha_reference`'s gradient."""
    L, B, T = 32, 4, 64
    monkeypatch.setattr(ak, "_BWD_DQ_VMEM", 16 * 2 * 16 * 12)
    seen = [ak.tile_schedule(T, T, 16, 32, False, (L, B), False, t0, 16, 2,
                             True).keys_seen for t0 in range(0, T, 16)]
    assert seen == [(0, 1), (0, 1), (1,), (1,)]
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q, g = (jax.random.normal(key, (1, 4, T, 16), jnp.float32)
            for key in keys[:2])
    k, v = (jax.random.normal(key, (1, 2, T, 16), jnp.float32)
            for key in keys[2:])
    parts = []
    real = ak._sum_over_spans
    monkeypatch.setattr(ak, "_sum_over_spans", lambda p, *a: (
        parts.append(p), real(p, *a))[1])
    out, lse = ak.flash_attention_tpu(q, k, v, block_q=16, block_k=32,
                                      interpret=True, return_lse=True,
                                      block_diffusion=(L, B))
    got = ak.flash_attention_bwd_tpu(q, k, v, out, lse, g, block_q=16,
                                     block_k=32, interpret=True,
                                     block_diffusion=(L, B))
    for dk_or_dv in parts:
        assert [bool(np.isnan(np.asarray(part[:, :32])).all())
                for part in dk_or_dv] == [False, False, True, True]
        assert not any(np.isnan(np.asarray(part[:, 32:])).any()
                       for part in dk_or_dv)
    want = jax.grad(lambda *a: jnp.sum(ak.mha_reference(
        *a, block_diffusion=(L, B)) * g), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_the_decoders_policy_holds_one_forward_kernel_under_the_block_mask(
        monkeypatch):
    """`jax.grad` through `fused_attention(block_diffusion=)` under the
    decoder blocks' checkpoint policy: the forward kernel once and the
    backward's spans (two here), where a bare checkpoint runs the forward
    again; the same gradients."""
    L, B, T = 32, 4, 64
    monkeypatch.setattr(ak, "_BWD_DQ_VMEM", 32 * 2 * 16 * 12)
    tier.dispatch.set_dispatch_mode("pallas")
    tier.dispatch.set_tile("attention", tier.TileConfig(block_q=16,
                                                        block_kv=32))
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(keys[0], (1, 4, T, 16), jnp.float32)
    k = jax.random.normal(keys[1], (1, 2, T, 16), jnp.float32)
    v = jax.random.normal(keys[2], (1, 2, T, 16), jnp.float32)
    f = lambda q, k, v: jnp.sum(
        ak.fused_attention(q, k, v, block_diffusion=(L, B)) ** 2)
    keep = jax.checkpoint(f, policy=jax.checkpoint_policies
                          .save_only_these_names(ak.FLASH_OUT, ak.FLASH_LSE))

    def kernels(f):
        text = str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, v))
        return text.count("pallas_call")

    assert (kernels(keep), kernels(jax.checkpoint(f)), kernels(f)) == (3, 4, 3)
    want = jax.grad(lambda *a: jnp.sum(ak.mha_reference(
        *a, block_diffusion=(L, B)) ** 2), (0, 1, 2))(q, k, v)
    for a, b, c in zip(jax.grad(keep, (0, 1, 2))(q, k, v),
                       jax.grad(f, (0, 1, 2))(q, k, v), want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(a, c, atol=1e-4)


@pytest.mark.parametrize("kwargs,message", [
    (dict(block_diffusion=(32, 4), causal=True), "mask of its own"),
    (dict(block_diffusion=(24, 4)), "24 or 48 rows"),
    (dict(block_diffusion=(32, 5)), "blocks of 5"),
])
def test_a_block_mask_that_does_not_fit_is_refused(kwargs, message):
    q = jnp.zeros((1, 2, 64, 8))
    with pytest.raises(ValueError, match=message):
        ak.fused_attention(q, q, q, **kwargs)
    # tiles that would lie across the first clean row are refused too
    with pytest.raises(ValueError, match="first clean row"):
        ak.flash_attention_tpu(q, q, q, block_q=64, block_k=64,
                               interpret=True, block_diffusion=(32, 4))


def test_the_kernel_dispatcher_states_the_block_masks_rule():
    q = jnp.zeros((1, 4, 64, 8), jnp.float32)
    kv = jnp.zeros((1, 2, 64, 8), jnp.float32)
    supports = tier.attention.attention_supports
    assert supports(q, kv, kv, block_diffusion=(32, 4))
    assert supports(q, kv, kv, block_diffusion=(64, 4))     # clean rows only
    assert not supports(q, kv, kv, block_diffusion=(48, 4))
    assert not supports(q, kv, kv, block_diffusion=(32, 4), causal=True)
    assert not supports(q, kv, kv, block_diffusion=(32, 4),
                        mask=jnp.ones((1, 64)))
    assert not supports(q, kv, kv, block_diffusion=(32, 3))


# ---------------------------------------------------------------------------
# (b), (c) what the training forward means
# ---------------------------------------------------------------------------

L, B = 32, 4


def _ids(seed=0, rows=2, t=L):
    return np.random.default_rng(seed).integers(0, 95, (rows, t)).astype(
        np.int32)


def _batch(seed=0, rows=2, t=L):
    ids = _ids(seed, rows, t)
    return MultiDataSet(features=[ids], labels=[ids])


def _trained(seed=1, steps=2, **changes):
    model = DecoderModel(DecoderConfig.tiny_diffusion(**changes), seed=seed)
    for i in range(steps):
        model.fit_batch(_batch(i))
    return model


@pytest.mark.parametrize("block", [0, 3, 7])
def test_the_training_forward_means_what_inference_means(block):
    """The noisy half's logits of block b from the 2L-row forward are the
    logits of a plain L-row forward over `x0[< b] ++ xt[b]` under the
    block-causal mask: the positions are `r mod L`, block b's noisy rows
    see the clean blocks before it and themselves, and nothing else."""
    model = _trained()
    ids = _ids(9)
    noisy, _ = model.noise(ids, jax.random.PRNGKey(5))
    noisy = np.asarray(noisy)
    assert (noisy == 95).any() and (noisy != 95).any()
    got = np.asarray(model.output(ids, noisy_ids=noisy))
    lo, hi = block * B, (block + 1) * B
    prefix = ids.copy()
    prefix[:, lo:hi] = noisy[:, lo:hi]       # what follows is never seen
    want = np.asarray(model.output(prefix))
    np.testing.assert_allclose(got[:, lo:hi], want[:, lo:hi], atol=2e-5)
    # ... which the same tokens one position later do not give: the
    # comparison would see a wrong position id
    shifted = np.asarray(model.output(np.roll(prefix, 1, axis=1)))
    assert np.abs(shifted[:, lo + 1:hi] - want[:, lo:hi - 1]).max() > 1e-4


def test_noise_outside_a_block_leaves_its_logits_alone():
    """A token of `xt` outside block b changed: block b's logits unchanged
    (a noisy row sees no other block's noisy keys); inside it, changed."""
    model = _trained()
    ids = _ids(4)
    noisy = np.asarray(model.noise(ids, jax.random.PRNGKey(6))[0]).copy()
    base = np.asarray(model.output(ids, noisy_ids=noisy))
    b = 5
    lo, hi = b * B, (b + 1) * B
    for where in (lo - 1, hi, 0, L - 1):
        other = noisy.copy()
        other[:, where] = (other[:, where] + 1) % 95
        got = np.asarray(model.output(ids, noisy_ids=other))
        np.testing.assert_allclose(got[:, lo:hi], base[:, lo:hi], atol=1e-6)
        assert np.abs(got - base).max() > 1e-4      # its own block's moved
    inside = noisy.copy()
    inside[:, lo] = (inside[:, lo] + 1) % 95
    got = np.asarray(model.output(ids, noisy_ids=inside))
    assert np.abs(got[:, lo + 1:hi] - base[:, lo + 1:hi]).max() > 1e-4
    # a CLEAN token of a later block changed: block b unchanged as well
    later = ids.copy()
    later[:, hi:] = (later[:, hi:] + 1) % 95
    got = np.asarray(model.output(later, noisy_ids=noisy))
    np.testing.assert_allclose(got[:, :hi], base[:, :hi], atol=1e-6)


# ---------------------------------------------------------------------------
# (d) the softmax router and its shares
# ---------------------------------------------------------------------------

def test_softmax_router_by_hand_and_its_balance_loss():
    rng = np.random.default_rng(2)
    t, h, e, k = 12, 8, 8, 2
    x = jnp.asarray(rng.normal(size=(t, h)))
    w = jnp.asarray(rng.normal(size=(h, e)))
    chosen, weights, mean_prob = moe.router(x, w, None, k, 1.0, 0.0,
                                            "softmax")
    z = np.asarray(x) @ np.asarray(w)
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    top = np.argsort(-p, -1, kind="stable")[:, :k]
    np.testing.assert_array_equal(chosen, top)
    picked = np.take_along_axis(p, top, -1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True),
                               atol=1e-7)
    np.testing.assert_allclose(mean_prob, p.mean(0), atol=1e-7)
    counts = moe.expert_counts(chosen, e)
    f = np.bincount(top.reshape(-1), minlength=e) / t
    np.testing.assert_allclose(moe.balance_loss(counts, mean_prob, t),
                               e * (f * p.mean(0)).sum(), rtol=1e-6)
    # even routing reads top_k; the counts carry no gradient
    np.testing.assert_allclose(moe.balance_loss(
        jnp.full((e,), t * k // e), jnp.full((e,), 1 / e), t), k)
    with pytest.raises(ValueError, match="score"):
        moe.router(x, w, None, k, 1.0, 0.0, "tanh")


def _shares_of_the_expert_layer():
    """The softmax-routed expert layer alone: `(the eight shares' parts,
    the uncut layer by hand)`; every share counts all 128 and reads the same
    balance loss."""
    rng = np.random.default_rng(8)
    t, h, i, e, k = 24, 16, 8, 128, 8
    x = jnp.asarray(rng.normal(size=(t, h)))
    p = {"router": jnp.asarray(rng.normal(size=(h, e)) * 0.5),
         "w_gate": jnp.asarray(rng.normal(size=(e, h, i)) * 0.3),
         "w_up": jnp.asarray(rng.normal(size=(e, h, i)) * 0.3),
         "w_down": jnp.asarray(rng.normal(size=(e, i, h)) * 0.3)}
    z = np.asarray(x) @ np.asarray(p["router"])
    prob = np.exp(z - z.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    chosen = np.argsort(-prob, -1, kind="stable")[:, :k]
    want = np.zeros((t, h))
    for tok in range(t):
        w = prob[tok, chosen[tok]]
        w = w / w.sum()
        for j, ex in enumerate(chosen[tok]):
            want[tok] += w[j] * np.asarray(moe.swiglu(
                x[tok], p["w_gate"][ex], p["w_up"][ex], p["w_down"][ex]))
    parts, balances = [], []
    for first in range(0, e, 16):
        share = {**p, **{n: p[n][first:first + 16]
                         for n in ("w_gate", "w_up", "w_down")}}
        y, counts, _, balance = moe.expert_layer(
            x, share, None, top_k=k, scale=1.0, first_held=first, eps=0.0,
            score="softmax")
        parts.append(y)
        balances.append(float(balance))
        assert int(counts.sum()) == t * k
    assert len(set(balances)) == 1 and balances[0] >= k - 1e-6
    return parts, want, 1e-6


def _shares_of_a_sparse_attention_layer():
    """A whole `sparse_attention` layer — grouped-query attention over the
    indexer's selection, then the routed experts — of which every chip
    computes attention, indexer and router alike: `(that part once and the
    eight shares' expert terms, the benchmark family's uncut reference
    layer)`.  Every share selects the same keys and reads the same indexer
    loss."""
    from benchmark.models import keye_vl2_moe as family
    from deeplearning4j_tpu.ops.norm_kernels import rms_norm
    c = DecoderConfig.tiny_sparse(n_layers=1, layer_types=(
        "sparse_attention",), n_experts=128, top_k=8)
    whole = DecoderModel(c, seed=9)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 64, c.hidden)), jnp.float32)
    lp = jax.tree_util.tree_map(lambda a: a[0], whole.params_["moe"])
    cfg = {"hidden_size": c.hidden, "num_attention_heads": c.n_heads,
           "num_key_value_heads": c.n_kv_heads, "head_dim": c.head_dim,
           "rope_theta": c.rope_base, "rms_norm_eps": c.eps,
           "rope_scaling": {"mrope_section": list(c.rope_sections)},
           "sa_config": {"indexer_num_heads": c.index_heads,
                         "indexer_head_dim": c.index_head_dim,
                         "indexer_num_kv_heads": 1, "topk": c.index_topk},
           "num_experts_per_tok": c.top_k, "first_expert_held": 0}
    want, _, want_kl = jax.jit(
        lambda x, lp: family.reference_block(cfg, x, lp))(x, lp)
    alike, counted = jax.jit(whole._sparse_attention)(x, lp)
    np.testing.assert_allclose(counted["index_kl"], want_kl, rtol=1e-5)
    assert float(counted["selected_keys"]) == 2 * 904   # sum_n min(n, 16)
    u = rms_norm(alike, lp["norm2"], c.eps).reshape(-1, c.hidden)
    parts = [alike]
    for first in range(0, 128, 16):
        held = {**lp, **{n: lp[n][first:first + 16]
                         for n in ("w_gate", "w_up", "w_down")}}
        y, counts, _, _ = moe.expert_layer(
            u, held, None, top_k=c.top_k, scale=c.routed_scale,
            first_held=first, eps=c.router_eps, score="softmax")
        assert int(counts.sum()) == 2 * 64 * 8
        parts.append(y.reshape(alike.shape))
    return parts, np.asarray(want), 1e-5


@pytest.mark.parametrize("layer", ["softmax_routed", "sparse_attention"])
def test_the_eight_shares_of_a_softmax_routed_layer_add_up(layer):
    """128 experts cut into 8 shares of 16 (`first_expert` 0, 16, .., 112),
    softmax over all 128, top-8 renormalised, no bias, no shared expert: the
    shares' parts sum to the uncut layer, token by token and expert by
    expert — the expert layer alone against a hand count, and a whole
    `sparse_attention` layer, what every chip computes alike counted once,
    against the benchmark family's uncut reference."""
    parts, want, atol = {
        "softmax_routed": _shares_of_the_expert_layer,
        "sparse_attention": _shares_of_a_sparse_attention_layer}[layer]()
    np.testing.assert_allclose(sum(parts), want, atol=atol)


# ---------------------------------------------------------------------------
# (e) the step's noise
# ---------------------------------------------------------------------------

def test_same_seed_and_iteration_same_noise_next_iteration_other_noise():
    a = DecoderModel(DecoderConfig.tiny_diffusion(), seed=3)
    b = DecoderModel(DecoderConfig.tiny_diffusion(), seed=3)
    other = DecoderModel(DecoderConfig.tiny_diffusion(), seed=4)
    ids = _ids(1, rows=4)
    n0, w0 = (np.asarray(z) for z in a.noise(ids, a.noise_key(0)))
    np.testing.assert_array_equal(n0, b.noise(ids, b.noise_key(0))[0])
    n1, _ = a.noise(ids, a.noise_key(1))
    assert (n0 != np.asarray(n1)).any()
    assert (n0 != np.asarray(other.noise(ids, other.noise_key(0))[0])).any()
    # a replaced position holds the mask id and weighs 1/t of its block:
    # one level a block, within (0.001, 1]
    replaced = n0 == 95
    np.testing.assert_array_equal(replaced, w0 > 0)
    np.testing.assert_array_equal(n0[~replaced], ids[~replaced])
    for row, w_row in zip(replaced.reshape(4, L // B, B),
                          w0.reshape(4, L // B, B)):
        for blk, w_blk in zip(row, w_row):
            assert len(set(w_blk[blk].tolist())) <= 1
            assert all(1.0 <= w < 1000.0 for w in w_blk[blk])
    # the step at iteration 0 trains on exactly that noise: same loss
    key = a.noise_key(0)
    want = float(a.diffusion_loss(ids, key))
    assert want == float(b.diffusion_loss(ids, key))
    batch = MultiDataSet(features=[ids], labels=[ids])
    got = float(a.fit_batch(batch))
    balance = got - want                    # aux_loss_coef x ~top_k
    assert 0.9 * 1e-3 * 2 < balance < 2.5 * 1e-3 * 2
    assert float(b.fit_batch(batch)) == got
    stats = a.noise_stats()
    assert stats["steps"] == 1 and stats["masked_positions"] \
        == int(replaced.sum())
    assert stats["share"] == replaced.mean()


# ---------------------------------------------------------------------------
# (f), (g) a model with no dense layer, through fit, save and load
# ---------------------------------------------------------------------------

def test_no_dense_layer_builds_and_trains_under_the_fixed_key():
    model = DecoderModel(DecoderConfig.tiny_diffusion(), seed=2)
    assert "dense" not in model.params_
    assert model.config.layout() == ("full_attention", ("full_attention",),
                                     2, ())
    assert model.params_["moe"]["Wqkv"].shape == (2, 32, (4 + 4) * 8)
    assert set(model.state_) == {"router_bias", "expert_load",
                                 "rows_over_bound", "masked_positions"}
    key = jax.random.PRNGKey(11)
    ids = _batch(0).features[0]
    before = float(model.diffusion_loss(ids, key))
    losses = [float(model.fit_batch(_batch(0))) for _ in range(3)]
    after = float(model.diffusion_loss(ids, key))
    assert all(np.isfinite(losses)) and np.isfinite(after)
    assert after < before
    # the softmax router has no selection bias to move; every row of both
    # copies chose top-2 in both layers
    assert not np.asarray(model.state_["router_bias"]).any()
    np.testing.assert_array_equal(model.expert_load().sum(1),
                                  [3 * 2 * 2 * L * 2] * 2)
    rows = model.routed_rows()
    assert rows["pairs"] == 2 * 2 * L * 2 and rows["steps"] == 3
    # fit(iterator) consumes the same batches; fit_steps scans them
    model.fit([_batch(1), _batch(2)])
    assert model.iteration == 5
    ids = np.stack([_batch(i).features[0] for i in (3, 4)])
    losses = model.fit_steps(MultiDataSet(features=[ids], labels=[ids]))
    assert losses.shape == (2,) and model.iteration == 7


@pytest.mark.parametrize("changes,message", [
    (dict(layer_types=("full_attention", "conv")), "convolution"),
    (dict(mask_token_id=None), "mask_token_id"),
    (dict(mask_token_id=96), "mask_token_id"),
    (dict(objective="masked_lm"), "objective"),
    (dict(router_score="tanh"), "router_score"),
])
def test_a_diffusion_model_that_cannot_be_built_is_refused(changes, message):
    with pytest.raises(ValueError, match=message):
        DecoderModel(DecoderConfig.tiny_diffusion(**changes))
    with pytest.raises(ValueError, match="whole blocks"):
        DecoderModel(DecoderConfig.tiny_diffusion()).fit_batch(_batch(t=30))
    with pytest.raises(ValueError, match="noisy_ids go with"):
        DecoderModel(DecoderConfig.tiny()).output(_ids(), noisy_ids=_ids())


def test_save_load_round_trip_keeps_the_new_fields_and_the_noise():
    a = _trained(seed=6, steps=2)
    buf = io.BytesIO()
    a.save(buf)
    buf.seek(0)
    b = DecoderModel.load(buf)
    assert b.config.kinds == a.config.kinds
    assert dataclasses.replace(b.config, layer_types=a.config.layer_types) \
        == a.config
    assert b.seed == 6 and b.iteration == 2
    assert (b.config.objective, b.config.router_score, b.config.block_length,
            b.config.mask_token_id, b.config.noise_eps,
            b.config.aux_loss_coef) == ("block_diffusion", "softmax", 4, 95,
                                        1e-3, 1e-3)
    assert int(b.state_["masked_positions"]) \
        == int(a.state_["masked_positions"]) > 0
    ids = _ids(3)
    np.testing.assert_array_equal(np.asarray(a.output(ids)),
                                  np.asarray(b.output(ids)))
    # the third step draws the same noise in both: the same loss
    assert float(a.fit_batch(_batch(3))) == float(b.fit_batch(_batch(3)))


# ---------------------------------------------------------------------------
# (h) the older presets' programs are what they were
# ---------------------------------------------------------------------------

def _next_token_batch(seed, rows=2, t=16, vocab=96):
    ids = np.random.default_rng(seed).integers(0, vocab, (rows, t)).astype(
        np.int32)
    labels = np.concatenate([ids[:, 1:], np.zeros((rows, 1), np.int32)], 1)
    return MultiDataSet(features=[ids], labels=[labels])


@pytest.mark.parametrize("preset,parent", [
    ("tiny", ["0x1.24d4b00000000p+2", "0x1.26521e0000000p+2",
              "0x1.21d2bc0000000p+2"]),
    ("tiny_hybrid", ["0x1.027d000000000p+5", "0x1.c577e40000000p+4",
                     "0x1.182cce0000000p+5"]),
])
def test_the_older_presets_lose_bit_for_bit_what_the_parent_lost(preset,
                                                                 parent):
    """Three steps of kanana's and LFM2's tiny presets against the losses
    the parent commit (PR 32) gave on this CPU backend: the defaults leave
    their programs alone."""
    model = DecoderModel(getattr(DecoderConfig, preset)(), seed=1)
    assert (model.config.objective, model.config.router_score) \
        == ("next_token", "sigmoid")
    assert "masked_positions" not in model.state_
    got = [float(model.fit_batch(_next_token_batch(i))).hex()
           for i in range(3)]
    assert got == parent
