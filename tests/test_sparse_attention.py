"""Learned sparse attention through `zoo.DecoderModel` at tiny size on the
CPU, float32: rotary by sections; the selection as packed bits; the exact
top-k a query; the selection in every branch of `fused_attention` (the flash
kernels in interpret mode, forward and backward, in spans and over grouped
heads) against `mha_reference`; the index kernels against their
definitions; the indexer's loss; the layer kind through `fit`, `save` and
`load`, with one top-k a layer a step."""
import dataclasses
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import MultiDataSet
from deeplearning4j_tpu.ops import attention_kernels as ak
from deeplearning4j_tpu.ops import pallas as tier
from deeplearning4j_tpu.ops import rotary, sparse_index
from deeplearning4j_tpu.ops.pallas import sparse_index as kernels
from deeplearning4j_tpu.zoo import DecoderConfig, DecoderModel


@pytest.fixture(autouse=True)
def _reset_tier():
    yield
    tier.dispatch.reset()


def _normal(seed, *shape):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


# ---------------------------------------------------------------------------
# (a) rotary by sections
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,sections", [(128, (16, 24, 24)), (8, (1, 1, 2)),
                                        (64, (32,))])
def test_rotary_by_sections_on_equal_streams_is_half_split(d, sections):
    x = _normal(0, 2, 16, 3, d)
    pos = jnp.arange(16) + 5
    got = rotary.rotary_sections(x, jnp.stack([pos] * len(sections)),
                                 sections, 1e7)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(rotary.rotary_half_split(x, pos, 1e7)))


def test_rotary_by_sections_on_unequal_streams_by_hand():
    """d = 8, sections [1, 1, 2]: frequency 0 turns by the first stream's
    position, 1 by the second's, 2 and 3 by the third's; pair i is
    `(x[i], x[i + 4])` at `base^(-2i/8)`."""
    x = np.asarray(_normal(1, 5, 1, 8))
    streams = np.array([[0, 1, 2, 3, 4], [0, 10, 20, 30, 40],
                        [7, 7, 7, 7, 7]])
    got = np.asarray(rotary.rotary_sections(
        jnp.asarray(x), jnp.asarray(streams), (1, 1, 2), 100.0))
    of_frequency = [0, 1, 2, 2]
    for t in range(5):
        for i in range(4):
            ang = streams[of_frequency[i], t] * 100.0 ** (-2 * i / 8)
            a, b = x[t, 0, i], x[t, 0, i + 4]
            np.testing.assert_allclose(
                got[t, 0, [i, i + 4]],
                [a * np.cos(ang) - b * np.sin(ang),
                 a * np.sin(ang) + b * np.cos(ang)], rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="frequencies"):
        rotary.rotary_sections(jnp.asarray(x), jnp.asarray(streams),
                               (1, 1, 1), 100.0)


# ---------------------------------------------------------------------------
# (b) the selection: bits, and the exact top-k
# ---------------------------------------------------------------------------

def _random_selection(seed, B, T, share=0.3):
    """Causal pairs kept at random, the diagonal always."""
    keep = np.random.default_rng(seed).random((B, T, T)) < share
    keep |= np.eye(T, dtype=bool)
    return jnp.asarray(keep & np.tril(np.ones((T, T), bool)))


def test_a_selection_is_one_bit_a_pair_packed_both_ways():
    keep = _random_selection(2, 2, 64)
    sel = ak.pack_selection(keep)
    assert sel.by_query.shape == (2, 2, 64) and sel.by_key.shape == (2, 2, 64)
    assert sel.by_query.dtype == sel.by_key.dtype == jnp.int32
    np.testing.assert_array_equal(ak.unpack_selection(sel), keep)
    np.testing.assert_array_equal(
        ak._unpack_bits(sel.by_key, 1).transpose(0, 2, 1), keep)
    # bit r of word i is pair (32 i + r, s)
    word = np.asarray(sel.by_query)[0, 1, 40].astype(np.uint32)
    np.testing.assert_array_equal(
        [(word >> r) & 1 for r in range(32)], np.asarray(keep)[0, 32:, 40])


def _top_k_by_sorting(scores, q_offset, topk):
    """The `min(t + 1, topk)` largest of a row's causal scores, equal ones
    to the lower key: a stable sort."""
    scores = np.asarray(scores)
    want = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for i in range(scores.shape[1]):
            row = scores[b, i, :q_offset + i + 1]
            want[b, i, np.argsort(-row, kind="stable")[:topk]] = True
    return want


@pytest.mark.parametrize("offset", [0, 32])
def test_select_keys_is_an_exact_top_k(offset):
    scores = _normal(3, 2, 32, 64)
    got = jax.jit(lambda s: sparse_index.select_keys(
        s, jnp.int32(offset), 16))(scores)
    np.testing.assert_array_equal(got, _top_k_by_sorting(scores, offset, 16))
    np.testing.assert_array_equal(
        np.asarray(got).sum(-1)[0], np.minimum(offset + np.arange(32) + 1, 16))


def test_ties_go_to_the_lower_index():
    """Scores in steps of 1/2, zeros of both signs among them: many rows'
    16th largest is shared, and the lower keys win."""
    scores = jnp.round(_normal(4, 2, 32, 64) * 2) / 2
    assert (np.asarray(scores) == 0).sum() > 100
    assert np.signbit(np.asarray(scores)[np.asarray(scores) == 0]).any()
    got = np.asarray(jax.jit(lambda s: sparse_index.select_keys(
        s, jnp.int32(32), 16))(scores))
    want = _top_k_by_sorting(scores, 32, 16)
    np.testing.assert_array_equal(got, want)
    # the threshold's ties were split in some row: not every tie was kept
    kth = np.sort(np.where(np.tril(np.ones((64, 64), bool))[32:],
                           np.asarray(scores), -np.inf), -1)[..., -16]
    at_kth = (np.asarray(scores) == kth[..., None]) \
        & np.tril(np.ones((64, 64), bool))[32:]
    assert (at_kth & ~got).any()
    # all equal: the first 16 keys
    flat = np.asarray(jax.jit(lambda s: sparse_index.select_keys(
        s, jnp.int32(32), 16))(jnp.zeros((1, 32, 64))))
    assert flat[:, :, :16].all() and not flat[:, :, 16:].any()


def test_sparse_index_selects_chunk_by_chunk(monkeypatch):
    """Two chunks of 32 queries: the same selection as the whole matrix
    sorted row by row, the count beside it."""
    monkeypatch.setattr(sparse_index, "_CHUNK", 32)
    q, k, w = _normal(5, 2, 4, 64, 8), _normal(6, 2, 64, 8), _normal(7, 2, 64,
                                                                     4)
    sel, selected, _ = jax.jit(
        lambda *a: sparse_index.sparse_index(*a, 16))(q, k, w)
    want = _top_k_by_sorting(kernels.index_scores_reference(q, k, w), 0, 16)
    np.testing.assert_array_equal(ak.unpack_selection(sel), want)
    assert float(selected) == want.sum() == 2 * 904
    with pytest.raises(ValueError, match="32-bit words"):
        sparse_index.sparse_index(q[:, :, :48], k[:, :48], w[:, :48], 16)


# ---------------------------------------------------------------------------
# (c) the selection in every branch of `fused_attention`
# ---------------------------------------------------------------------------

def _value_and_grads(fn, q, k, v):
    def loss(q, k, v):
        out, lse = fn(q, k, v)
        return jnp.sum(out * jnp.cos(out)), (out, lse)
    return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)


@pytest.mark.parametrize("branch,tile", [
    ("xla", None), ("flash", (32, 64)), ("flash", (64, 32)),
    ("flash", (128, 128))])
@pytest.mark.parametrize("heads", [(4, 4), (4, 2), (8, 1)])
def test_a_random_selection_in_every_branch(branch, tile, heads):
    """Forward, logsumexp and the three gradients against `mha_reference`
    under the same pairs as a boolean mask."""
    H, Hk = heads
    B, T, D = 2, 128, 16
    q, k, v = (_normal(s, B, h, T, D)
               for s, h in ((10, H), (11, Hk), (12, Hk)))
    keep = _random_selection(13, B, T)
    sel = ak.pack_selection(keep)
    if branch == "flash":
        tier.dispatch.set_dispatch_mode("pallas")
        tier.dispatch.set_tile("attention", tier.TileConfig(
            block_q=tile[0], block_kv=tile[1]))
    (_, (got, got_lse)), got_grads = _value_and_grads(
        lambda q, k, v: ak.fused_attention(
            q, k, v, causal=True, selection=sel, return_lse=True), q, k, v)
    (_, (want, want_lse)), want_grads = _value_and_grads(
        lambda q, k, v: ak.mha_reference(q, k, v, keep, return_lse=True),
        q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-6)
    np.testing.assert_allclose(got_lse, want_lse, atol=2e-6)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=2e-5)
    # the same pairs through the blockwise scan
    scan = ak.blockwise_attention(q, k, v, keep, False, None, 32)
    np.testing.assert_allclose(scan, want, atol=2e-6)


def test_the_backward_in_spans_under_a_selection(monkeypatch):
    """dQ's budget cut to two spans of 64 queries: each span reads its own
    columns of `by_key`, and the spans' dK/dV add up."""
    B, H, Hk, T, D = 1, 4, 2, 128, 16
    q, k, v = (_normal(s, B, h, T, D)
               for s, h in ((20, H), (21, Hk), (22, Hk)))
    g = _normal(23, B, H, T, D)
    keep = _random_selection(24, B, T)
    sel = ak.pack_selection(keep)
    out, lse = ak.flash_attention_tpu(q, k, v, True, None, 32, 32,
                                      interpret=True, return_lse=True,
                                      selection=sel)
    whole = ak.flash_attention_bwd_tpu(q, k, v, out, lse, g, True, None, 32,
                                       32, interpret=True, selection=sel)
    monkeypatch.setattr(ak, "_BWD_DQ_VMEM", 64 * 2 * D * (4 + 2 * 4))
    assert ak._bwd_plan(T, T, D, D, 4, 32, 32, 2)[2] == 64
    spans = ak.flash_attention_bwd_tpu(q, k, v, out, lse, g, True, None, 32,
                                       32, interpret=True, selection=sel)
    want = jax.vjp(lambda q, k, v: ak.mha_reference(q, k, v, keep),
                   q, k, v)[1](g)
    for a, b, c in zip(whole, spans, want):
        np.testing.assert_allclose(a, c, atol=2e-5)
        np.testing.assert_allclose(b, c, atol=2e-5)


@pytest.mark.parametrize("kwargs,message", [
    ({"causal": False}, "causal pairs"),
    ({"causal": True, "mask": np.ones((1, 64), np.float32)}, "causal pairs"),
    ({"causal": True, "block_diffusion": (64, 4)}, "causal pairs"),
])
def test_a_selection_goes_with_causal_and_nothing_else(kwargs, message):
    q = _normal(30, 1, 2, 64, 8)
    sel = ak.pack_selection(_random_selection(31, 1, 64))
    with pytest.raises(ValueError, match=message):
        ak.fused_attention(q, q, q, selection=sel, **kwargs)
    with pytest.raises(ValueError, match="with a selection"):
        ak.fused_attention(q, q, q, causal=True, return_lse=True)


def test_the_kernel_dispatcher_states_the_selections_rule():
    """Whole 32-bit words along both sides, causal, no other mask."""
    q = _normal(32, 1, 2, 64, 8)
    sel = ak.pack_selection(_random_selection(33, 1, 64))
    supports = tier.attention.attention_supports
    assert supports(q, q, q, causal=True, selection=sel)
    assert not supports(q, q, q, causal=False, selection=sel)
    assert not supports(q, q, q, causal=True, selection=sel,
                        mask=jnp.ones((1, 64)))
    assert not supports(q[:, :, :48], q[:, :, :48], q[:, :, :48],
                        causal=True, selection=sel)
    assert "sparse_index" in tier.dispatch.kernels()
    with pytest.raises(ValueError, match="32-bit words"):
        ak.flash_attention_tpu(q, q, q, causal=True, block_q=16, block_k=64,
                               interpret=True, selection=sel)


# ---------------------------------------------------------------------------
# (d) the index kernels against their definitions
# ---------------------------------------------------------------------------

@pytest.fixture
def small_tiles(monkeypatch):
    """16 x 32 tiles: a chunk of 32 queries over 64 keys is 2 x 2 of them,
    one above the diagonal at offset 0."""
    monkeypatch.setattr(kernels, "_BLOCK_Q", 16)
    monkeypatch.setattr(kernels, "_BLOCK_K", 32)


def _causal(offset, C, S):
    return jnp.asarray(np.arange(S)[None, :] <= offset + np.arange(C)[:, None])


@pytest.mark.parametrize("offset", [0, 32])
def test_index_score_kernels_against_their_definitions(offset, small_tiles):
    """Scores under the diagonal (a tile wholly above it is skipped and
    left zero; the caller masks the rest) and the three gradients of a
    cotangent that is zero above it."""
    q, k, w = _normal(40, 2, 3, 32, 8), _normal(41, 2, 64, 8), _normal(
        42, 2, 32, 3)
    under = _causal(offset, 32, 64)
    got = kernels.index_scores(q, k, w, jnp.int32(offset), interpret=True)
    want = kernels.index_scores_reference(q, k, w)
    np.testing.assert_allclose(jnp.where(under, got, 0.0),
                               jnp.where(under, want, 0.0), atol=1e-5)
    if offset == 0:             # the tile above the diagonal was skipped
        assert not np.asarray(got)[:, :16, 32:].any()
    g = jnp.where(under, _normal(43, 2, 32, 64), 0.0)
    got = kernels.index_scores_bwd(g, q, k, w, jnp.int32(offset),
                                   interpret=True)
    want = kernels.index_scores_bwd_reference(g, q, k, w)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("offset", [0, 32])
def test_head_summed_probabilities_kernel(offset, small_tiles):
    """The heads' probabilities as `kl_and_cotangent` sums them in the
    tile, read back from its cotangent: with every causal pair selected
    and every score 0, `p` is one over a row's keys and `pbar = p - rows *
    d_scores`; the tile above the diagonal is skipped and left zero."""
    q, k = _normal(44, 2, 4, 32, 8), _normal(45, 2, 2, 64, 8)
    lse = _normal(46, 2, 4, 32) + 3.0
    under = _causal(offset, 32, 64)
    keys = jnp.sum(under, -1, dtype=jnp.float32)
    d, _ = kernels.kl_and_cotangent(
        q, k, lse, 8 ** -0.5, jnp.zeros((2, 32, 64), jnp.float32),
        ak._pack_bits(jnp.stack([under] * 2), 1),
        jnp.broadcast_to(jnp.log(keys), (2, 32)), 64, jnp.int32(offset),
        interpret=True)
    got = 4 * (jnp.exp(-jnp.log(keys))[:, None] - 64 * d)
    want = kernels.head_summed_probs_reference(q, k, lse, 8 ** -0.5)
    np.testing.assert_allclose(jnp.where(under, got, 0.0),
                               jnp.where(under, want, 0.0), rtol=1e-5,
                               atol=1e-6)
    if offset == 0:
        assert not np.asarray(d)[:, :16, 32:].any()


def _chunk_selection(scores, offset):
    """A chunk's keep-mask [B, 32, 64] and its words: the top 16 a query,
    so at offset 0 the first 15 rows keep fewer; at offset 32 the first
    eight rows drop keys 0..31, so that a live key block holds none of
    their keys, and keep their diagonal."""
    keep = np.array(sparse_index.select_keys(scores, jnp.int32(offset), 16))
    if offset:
        keep[:, :8, :32] = False
        keep[:, np.arange(8), offset + np.arange(8)] = True
    return jnp.asarray(keep), ak._pack_bits(jnp.asarray(keep), 1)


@pytest.mark.parametrize("offset", [0, 32])
def test_the_scores_logsumexp_over_the_selection(offset, small_tiles):
    """`index_scores_lse` in 32 x 32 tiles (a query block of whole words):
    each row's logsumexp over its selected scores, accumulated across the
    key blocks, against `logsumexp(where(keep, scores, -inf))`; the scores
    under the diagonal as `index_scores` gives them."""
    q, k, w = _normal(70, 2, 3, 32, 8), _normal(71, 2, 64, 8), _normal(
        72, 2, 32, 3)
    want = kernels.index_scores_reference(q, k, w)
    keep, words = _chunk_selection(want, offset)
    assert np.asarray(keep).sum(-1).min() < 16
    if offset:                  # rows whose first key block holds no pick
        assert not np.asarray(keep)[:, :8, :32].any()
    scores, lse = kernels.index_scores_lse(q, k, w, words, jnp.int32(offset),
                                           interpret=True)
    assert lse.shape == (2, 32)
    np.testing.assert_allclose(
        lse, jax.nn.logsumexp(jnp.where(keep, want, -jnp.inf), -1),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        lse, kernels.index_scores_lse_reference(q, k, w, words)[1],
        rtol=1e-6, atol=1e-6)
    under = _causal(offset, 32, 64)
    np.testing.assert_allclose(jnp.where(under, scores, 0.0),
                               jnp.where(under, want, 0.0), atol=1e-5)


@pytest.mark.parametrize("offset", [0, 32])
def test_the_loss_cotangent_kernel(offset, small_tiles):
    """`kl_and_cotangent` from `index_scores_lse`'s results against the
    definition (the indexer loss's arithmetic on a chunk): the scores'
    cotangent and each row's KL; the cotangent exactly zero off the
    selection and in the tile above the diagonal."""
    q_idx, k_idx, w = _normal(73, 2, 3, 32, 8), _normal(74, 2, 64, 8), \
        _normal(75, 2, 32, 3)
    q, k = _normal(76, 2, 4, 32, 8), _normal(77, 2, 2, 64, 8)
    lse = _normal(78, 2, 4, 32) + 3.0
    keep, words = _chunk_selection(
        kernels.index_scores_reference(q_idx, k_idx, w), offset)
    scores, lse_idx = kernels.index_scores_lse(
        q_idx, k_idx, w, words, jnp.int32(offset), interpret=True)
    got, kl = kernels.kl_and_cotangent(q, k, lse, 8 ** -0.5, scores, words,
                                       lse_idx, 2 * 64, jnp.int32(offset),
                                       interpret=True)
    ref_scores, ref_lse = kernels.index_scores_lse_reference(q_idx, k_idx, w,
                                                             words)
    want, want_kl = kernels.kl_and_cotangent_reference(
        q, k, lse, 8 ** -0.5, ref_scores, words, ref_lse, 2 * 64)
    assert got.shape == (2, 32, 64) and kl.shape == (2, 32)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-9)
    np.testing.assert_allclose(kl, want_kl, rtol=1e-5, atol=1e-7)
    assert float(jnp.abs(want).max()) > 1e-4 and float(want_kl.min()) != 0
    got = np.asarray(got)
    assert not got[~np.asarray(keep)].any()
    if offset == 0:             # the tile above the diagonal
        assert not got[:, :, 32:].any()


@pytest.mark.parametrize("T,tile", [(128, (32, 64)), (128, (64, 32)),
                                    (64, (64, 64))])
def test_pack_by_key_kernel_turns_the_bits(T, tile, monkeypatch):
    """A causal selection's `by_key` from its `by_query` in 4 x 2, 2 x 4
    and 1 x 1 tiles: bit-equal to `pack_selection`'s; a tile wholly above
    the diagonal is zero words, whatever block its clamped index read."""
    monkeypatch.setattr(kernels, "_BLOCK_Q", tile[0])
    monkeypatch.setattr(kernels, "_BLOCK_K", tile[1])
    want = ak.pack_selection(_random_selection(47, 2, T))
    got = kernels.pack_by_key(want.by_query, interpret=True)
    assert got.shape == (2, T // 32, T) and got.dtype == jnp.int32
    np.testing.assert_array_equal(got, want.by_key)
    np.testing.assert_array_equal(
        kernels.pack_by_key_reference(want.by_query), want.by_key)
    assert np.asarray(got).any()
    # keys 64.. and queries ..63: above the diagonal
    assert not np.asarray(got)[:, 2:, :64].any()


def test_the_tier_answers_for_the_words_apart():
    """The tier's one answer is for the four kernels that work a chunk of
    scores (the loss's two that read the chunk's words take query blocks
    of whole sublane tiles of words or the whole chunk, so they always
    can); `pack_by_key` also needs blocks whose 32nds are whole sublane
    tiles (or the whole side), the packed words' rows, and where a sequence
    has none it alone falls back to its definition."""
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    for T, ok in [(16384, True), (64, True), (2048, True), (768, True),
                  (1152, False),            # blocks of 128: 4 rows of words
                  (1056, False)]:           # 33 words: blocks of one
        assert kernels.index_supports(spec(1, 16, sparse_index._chunk(T), 64),
                                      spec(1, T, 64)) is (T != 1056), T
        assert kernels.pack_by_key_supports(T, T) is ok, T
    tier.dispatch.set_dispatch_mode("pallas")
    taken = sparse_index._dense(spec(1, 3, 2048, 8), spec(1, 2048, 8), 1024)
    assert [f.func for f in taken] == [
        kernels.index_scores, kernels.pack_by_key, kernels.index_scores_lse,
        kernels.kl_and_cotangent, kernels.index_scores_bwd]
    assert kernels._word_blocks(1024, 16384) == (512, 1024)
    # 1,152 = 9 x 128 tokens: the four take chunks of 128 queries over
    # blocks of 128 keys (the words' 4 rows the chunk's whole side), the
    # sequence's words have 4 rows a block
    assert kernels._word_blocks(128, 1152) == (128, 128)
    taken = sparse_index._dense(spec(1, 3, 1152, 8), spec(1, 1152, 8), 128)
    assert [getattr(f, "func", f) for f in taken] == [
        kernels.index_scores, kernels.pack_by_key_reference,
        kernels.index_scores_lse, kernels.kl_and_cotangent,
        kernels.index_scores_bwd]


def _tied_indexer(seed, T, n, d, alike: int):
    """An indexer's `(q_idx, k_idx, w)` for one sequence; every key stands
    `alike` times in a row, so that equal keys score equal."""
    q, k, w = _normal(seed, 1, n, T, d), _normal(seed + 1, 1, T, d), _normal(
        seed + 2, 1, T, n)
    return q, jnp.repeat(k[:, ::alike], alike, axis=1), w


@pytest.mark.parametrize("alike,split", [(1, 0), (2, 2), (4, 2)])
def test_the_selection_in_both_tiers_bit_for_bit(alike, split):
    """2,048 queries in two chunks of 1,024 at the kernels' own 512 x 1024
    tiles (interpret mode): the kernels' bits are the reference tier's, and
    the reference tier's are the stable sort's.  On random scores no chunk
    has a tie to split (16 heads: a score is exactly 0 only where every
    head's ReLU is shut); where keys come in equal pairs or fours some row
    keeps the lower ones of a group, and both chunks search for their cuts
    (`tie_split_chunks`)."""
    q, k, w = _tied_indexer(60, 2048, 16, 8, alike)
    results = {}
    for mode in ("reference", "pallas"):
        tier.dispatch.set_dispatch_mode(mode)
        results[mode] = jax.jit(
            lambda *a: sparse_index.sparse_index(*a, 256))(q, k, w)
    sel, selected, took = results["pallas"]
    want = results["reference"][0]
    np.testing.assert_array_equal(sel.by_query, want.by_query)
    np.testing.assert_array_equal(sel.by_key, want.by_key)
    assert results["reference"][1:] == (selected, took)
    keep = np.asarray(ak.unpack_selection(want))
    np.testing.assert_array_equal(keep, _top_k_by_sorting(
        kernels.index_scores_reference(q, k, w), 0, 256))
    np.testing.assert_array_equal(want.by_key,
                                  ak.pack_selection(jnp.asarray(keep)).by_key)
    assert float(selected) == keep.sum() == sum(
        min(t + 1, 256) for t in range(2048))
    assert int(took) == split
    if alike > 1:     # some row kept the lower keys of a group and not all
        groups = keep[0].reshape(2048, 2048 // alike, alike)
        assert (groups[..., 0] & ~groups[..., -1])[
            np.arange(2048)[:, None]
            >= alike * np.arange(2048 // alike)[None] + alike - 1].any()


@pytest.mark.parametrize("alike", [1, 2, 8])
def test_the_cut_among_a_thresholds_ties(alike):
    """`_ties` says whether some row has more keys at its threshold than
    it keeps; `_cut` finds the last one a row keeps, the `need`-th of them
    from the left, whether or not the row has a tie to split."""
    rng = np.random.default_rng(61)
    scores = jnp.asarray(np.repeat(rng.normal(size=(2, 32, 64 // alike)),
                                   alike, -1), jnp.float32)
    u, kth, want = jax.jit(lambda s: sparse_index._threshold(
        s, jnp.int32(32), 16))(scores)
    need, split = jax.jit(sparse_index._ties)(u, kth, want)
    assert bool(split) == (alike > 1)
    cut = jax.jit(sparse_index._cut)(u, kth, need)
    u, kth, want, need, cut = (np.asarray(a)
                               for a in (u, kth, want, need, cut))
    for b in range(2):
        for i in range(32):
            ties = np.flatnonzero(u[b, i] == kth[b, i])
            assert need[b, i] == want[i] - (u[b, i] > kth[b, i]).sum()
            assert 1 <= need[b, i] <= len(ties)
            assert cut[b, i] == ties[need[b, i] - 1]
    np.testing.assert_array_equal(
        np.asarray(sparse_index._keep_to(u, kth, cut)),
        _top_k_by_sorting(scores, 32, 16))


@pytest.mark.parametrize("mode", ["reference", "pallas"])
def test_the_indexers_loss_and_its_gradients(mode, monkeypatch, small_tiles):
    """`index_loss` against the KL written out and `jax.grad` of it: the
    loss, and the gradients of the indexer's queries, key and weights; the
    main heads get none."""
    _loss_and_gradients(mode, monkeypatch, 64, 32)


@pytest.mark.parametrize("mode", ["reference", "pallas"])
def test_the_indexers_loss_through_many_tiles(mode, monkeypatch):
    """The same over 256 tokens in four chunks of 64 queries, each 2 x 2
    tiles of 32 x 128: whole lanes, so the tier does take the kernels (64
    keys in blocks of 32 it refuses, and the case above runs the
    definitions in both modes).  The first chunk skips the tile above the
    diagonal; the queries' gradient sums over a row's key blocks in the
    kernel, the key's over the query blocks and the chunks outside it."""
    monkeypatch.setattr(kernels, "_BLOCK_Q", 32)
    monkeypatch.setattr(kernels, "_BLOCK_K", 128)
    _loss_and_gradients(mode, monkeypatch, 256, 64)


def _loss_and_gradients(mode, monkeypatch, T, chunk):
    monkeypatch.setattr(sparse_index, "_CHUNK", chunk)
    tier.dispatch.set_dispatch_mode(mode)
    B, n, d, H, Hk, D, topk = 2, 3, 8, 4, 2, 8, 16
    q_idx, k_idx, w = _normal(50, B, n, T, d), _normal(51, B, T, d), _normal(
        52, B, T, n)
    q, k, v = _normal(53, B, H, T, D), _normal(54, B, Hk, T, D), _normal(
        55, B, Hk, T, D)
    if T > 64:
        assert hasattr(sparse_index._dense(q_idx, k_idx, chunk)[0],
                       "func") is (mode == "pallas")
    sel, *_ = sparse_index.sparse_index(q_idx, k_idx, w, topk)
    keep = ak.unpack_selection(sel)
    _, lse = ak.fused_attention(q, k, v, causal=True, selection=sel,
                                return_lse=True)

    def written_out(q_idx, k_idx, w):
        log_pi = jax.nn.log_softmax(jnp.where(
            keep, kernels.index_scores_reference(q_idx, k_idx, w),
            -jnp.inf), -1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, H // Hk, 1)) \
            * D ** -0.5
        pbar = jnp.mean(jax.nn.softmax(
            jnp.where(keep[:, None], s, -jnp.inf), -1), 1)
        np.testing.assert_allclose(pbar.sum(-1), 1.0, rtol=1e-5)
        return jnp.sum(jnp.where(keep, pbar * (
            jnp.log(jnp.where(keep, pbar, 1.0))
            - jnp.where(keep, log_pi, 0.0)), 0.0)) / (B * T)

    got, got_grads = jax.value_and_grad(
        lambda *a: 2.0 * sparse_index.index_loss(
            *a, sel.by_query, q, k, lse, D ** -0.5), (0, 1, 2))(q_idx, k_idx,
                                                               w)
    want, want_grads = jax.value_and_grad(
        lambda *a: 2.0 * written_out(*a), (0, 1, 2))(q_idx, k_idx, w)
    assert float(want) > 0.01
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_allclose(a, b, atol=1e-6)
    main = jax.grad(lambda q, k, lse: sparse_index.index_loss(
        q_idx, k_idx, w, sel.by_query, q, k, lse, D ** -0.5), (0, 1, 2))(
            q, k, lse)
    assert all(not np.asarray(g).any() for g in main)


# ---------------------------------------------------------------------------
# (e) the layer kind through the model
# ---------------------------------------------------------------------------

def _batch(seed, rows=2, t=64, vocab=96):
    ids = np.random.default_rng(seed).integers(0, vocab, (rows, t)).astype(
        np.int32)
    labels = np.concatenate([ids[:, 1:], np.zeros((rows, 1), np.int32)], 1)
    return MultiDataSet(features=[ids], labels=[labels])


def test_topk_of_the_whole_sequence_is_causal_grouped_query_attention():
    """`index_topk >= T`: every causal key is selected, and the layer's
    output is `_gqa_attention`'s bit for bit."""
    model = DecoderModel(DecoderConfig.tiny_sparse(index_topk=64), seed=1)
    lp = jax.tree_util.tree_map(lambda a: a[0], model.params_["moe"])
    x = _normal(60, 2, 64, 32)
    got, counted = model._sparse_attention(x, lp)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(model._gqa_attention(x, lp)))
    assert float(counted["selected_keys"]) == 2 * 64 * 65 // 2
    np.testing.assert_array_equal(
        np.asarray(model.selection(np.zeros((1, 64), np.int32)))[0],
        np.tril(np.ones((64, 64), bool)))
    # with 16 keys a query it is not, from the 17th query on
    bound = DecoderModel(DecoderConfig.tiny_sparse(), seed=1)
    other, _ = bound._sparse_attention(x, lp)
    np.testing.assert_array_equal(np.asarray(other)[:, :16],
                                  np.asarray(got)[:, :16])
    assert np.abs(np.asarray(other)[:, 16:] - np.asarray(got)[:, 16:]).max() \
        > 1e-3


def _primitives(jaxpr, name):
    """How often a primitive stands in a jaxpr, inner jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _primitives(sub, name)
    return n


def test_one_top_k_a_layer_a_step():
    """The step's jaxpr holds the selection's one `cond` (the tie split of
    `select_keys`) ONCE for the scanned layers: the backward pass and the
    block's recomputation take the selection and the indexer's gradients
    from the forward's residuals."""
    from deeplearning4j_tpu.utils.counters import device_counters
    model = DecoderModel(DecoderConfig.tiny_sparse(), seed=2)
    ids = jnp.zeros((2, 64), jnp.int32)
    it, ep = device_counters(model)
    step = jax.make_jaxpr(model._step_body())(
        model.params_, model.opt_state_, model.state_, it, ep, ids, ids)
    assert _primitives(step.jaxpr, "cond") == 1
    forward = jax.make_jaxpr(lambda p: model._loss(
        p, model.state_["router_bias"], ids, ids)[0])(model.params_)
    assert _primitives(forward.jaxpr, "cond") == 1


@pytest.mark.parametrize("mode", ["reference", "pallas"])
def test_the_model_trains_through_fit_in_both_tiers(mode):
    """`fit` on the CPU's reference lowerings and with every kernel forced
    (interpret mode): the same losses within float32's rounding, the
    counters of `sparse_stats`."""
    tier.dispatch.set_dispatch_mode(mode)
    model = DecoderModel(DecoderConfig.tiny_sparse(), seed=3)
    assert {"selected_keys", "index_kl", "tie_split_chunks"} <= set(
        model.state_)
    model.fit([_batch(i) for i in range(3)])
    losses = float(model.score())
    stats = model.sparse_stats()
    assert stats["steps"] == 3 and stats["selected_keys"] == 3 * 2 * 2 * 904
    assert stats["keys_per_query"] == pytest.approx(904 / 64)
    assert 0 < stats["index_kl"] < 1
    # one chunk a layer a step; two index heads shut their ReLUs together
    # on a quarter of the pairs, and scores of exactly 0 tie
    assert model.state_["tie_split_chunks"].dtype == jnp.int32
    assert 0 < stats["tie_split_chunks"] <= 3 * 2
    tier.dispatch.set_dispatch_mode("reference")
    plain = DecoderModel(DecoderConfig.tiny_sparse(), seed=3)
    plain.fit([_batch(i) for i in range(3)])
    assert losses == pytest.approx(float(plain.score()), rel=1e-5)
    assert plain.sparse_stats()["tie_split_chunks"] \
        == stats["tie_split_chunks"]
    assert model.routed_rows()["steps"] == 3


def test_the_indexer_learns_from_its_own_loss_alone():
    """With `index_loss_coef` 0 the indexer's parameters get no gradient
    (three steps leave them where weight decay puts them) and everything
    else trains as with it: the indexer's loss reaches nothing else."""
    with_loss = DecoderModel(DecoderConfig.tiny_sparse(), seed=4)
    without = DecoderModel(DecoderConfig.tiny_sparse(index_loss_coef=0.0),
                           seed=4)
    start = jax.tree_util.tree_map(np.asarray, without.params_["moe"])
    batch = _batch(9)
    _, grads = jax.value_and_grad(without._loss, has_aux=True)(
        without.params_, without.state_["router_bias"],
        jnp.asarray(batch.features[0]), jnp.asarray(batch.labels[0]))
    _, grads_with = jax.value_and_grad(with_loss._loss, has_aux=True)(
        with_loss.params_, with_loss.state_["router_bias"],
        jnp.asarray(batch.features[0]), jnp.asarray(batch.labels[0]))
    for name in start:
        g0 = np.asarray(grads["moe"][name])
        g1 = np.asarray(grads_with["moe"][name])
        if name in ("Wq_idx", "Wk_idx", "Ww_idx", "k_idx_gain",
                    "k_idx_bias"):
            assert not g0.any() and g1.any(), name
        else:
            np.testing.assert_array_equal(g0, g1, err_msg=name)


@pytest.mark.parametrize("changes,message", [
    ({"objective": "block_diffusion", "mask_token_id": 95}, "next_token"),
    ({"n_layers": 3, "n_dense_layers": 1, "intermediate": 64,
      "layer_types": ("sparse_attention",) * 3}, "after the dense"),
    ({"n_heads": 3}, "no multiple"),
])
def test_a_sparse_model_that_cannot_be_built_is_refused(changes, message):
    with pytest.raises(ValueError, match=message):
        DecoderModel(DecoderConfig.tiny_sparse(**changes))


def test_a_sparse_layer_beside_another_kind_in_one_period():
    """`sparse_attention, conv` x 2: the scan's layers count alike, the
    convolution's zeros beside the indexer's counts."""
    c = DecoderConfig.tiny_sparse(
        n_layers=4, layer_types=("sparse_attention", "conv") * 2)
    model = DecoderModel(c, seed=5)
    assert c.layout() == ("sparse_attention", ("sparse_attention", "conv"),
                          2, ())
    model.fit([_batch(0)])
    np.testing.assert_array_equal(np.asarray(model.state_["selected_keys"]),
                                  [2 * 904, 0, 2 * 904, 0])
    assert model.sparse_stats()["keys_per_query"] == pytest.approx(904 / 64)


def test_save_load_round_trip_keeps_the_indexer_and_its_counters():
    a = DecoderModel(DecoderConfig.tiny_sparse(), seed=6)
    a.fit([_batch(0), _batch(1)])
    buf = io.BytesIO()
    a.save(buf)
    buf.seek(0)
    b = DecoderModel.load(buf)
    assert dataclasses.replace(
        b.config, layer_types=a.config.layer_types,
        rope_sections=a.config.rope_sections) == a.config
    assert tuple(b.config.rope_sections) == (1, 1, 2)
    assert (b.config.index_heads, b.config.index_head_dim,
            b.config.index_topk, b.config.index_loss_coef) == (2, 8, 16, 1.0)
    for name in ("Wq_idx", "Wk_idx", "Ww_idx", "k_idx_gain", "k_idx_bias"):
        np.testing.assert_array_equal(np.asarray(a.params_["moe"][name]),
                                      np.asarray(b.params_["moe"][name]))
    # the counters are the state's; a query count needs a batch's shape
    assert {**a.sparse_stats(), "keys_per_query": 0.0} == b.sparse_stats()
    assert a.sparse_stats()["selected_keys"] == 2 * 2 * 2 * 904
    assert 0 < a.sparse_stats()["tie_split_chunks"] <= 2 * 2
    np.testing.assert_array_equal(a.state_["tie_split_chunks"],
                                  b.state_["tie_split_chunks"])
    ids = _batch(2).features[0]
    np.testing.assert_array_equal(np.asarray(a.output(ids)),
                                  np.asarray(b.output(ids)))
    np.testing.assert_array_equal(np.asarray(a.selection(ids)),
                                  np.asarray(b.selection(ids)))
    assert float(a.fit_batch(_batch(2))) == float(b.fit_batch(_batch(2)))


def test_a_file_from_before_the_counter_loads_with_it_at_zero():
    """A file saved before `tie_split_chunks` was kept lacks the state's
    last leaf: it loads, the counter starts at zero and every other leaf is
    the file's."""
    import zipfile
    a = DecoderModel(DecoderConfig.tiny_sparse(), seed=6)
    a.fit([_batch(0)])
    assert sorted(a.state_)[-1] == "tie_split_chunks"
    new, old = io.BytesIO(), io.BytesIO()
    a.save(new)
    with zipfile.ZipFile(new) as z, zipfile.ZipFile(old, "w") as out:
        for name in z.namelist():
            data = z.read(name)
            if name == "state_.npz":
                with np.load(io.BytesIO(data)) as d:
                    arrays = [d[f"arr_{i}"] for i in range(len(d.files) - 1)]
                data = io.BytesIO()
                np.savez(data, *arrays)
                data = data.getvalue()
            out.writestr(name, data)
    old.seek(0)
    b = DecoderModel.load(old)
    assert a.sparse_stats()["tie_split_chunks"] > 0
    assert b.sparse_stats() == {**a.sparse_stats(), "keys_per_query": 0.0,
                                "tie_split_chunks": 0}
    for name in set(a.state_) - {"tie_split_chunks"}:
        np.testing.assert_array_equal(np.asarray(a.state_[name]),
                                      np.asarray(b.state_[name]))
    assert float(a.fit_batch(_batch(1))) == float(b.fit_batch(_batch(1)))
