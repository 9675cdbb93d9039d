"""The decoder's parts against hand-written cases: RMSNorm, rotary, the
router, the expert layer's shares and its dropless dispatch, the grouped
product, attention with values narrower than keys, the routing counter,
`zoo.DecoderModel`'s surface, and latent attention against the form it had
before its products wrote head-first (PR 36)."""
import hashlib
import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest


from deeplearning4j_tpu.data.dataset import MultiDataSet
from deeplearning4j_tpu.ops import attention_kernels as ak
from deeplearning4j_tpu.ops import moe
from deeplearning4j_tpu.ops import pallas as tier
from deeplearning4j_tpu.ops.norm_kernels import rms_norm
from deeplearning4j_tpu.ops.rotary import rotary_interleaved, rotary_pairs
from deeplearning4j_tpu.utils.counters import device_counters
from deeplearning4j_tpu.zoo import DecoderConfig, DecoderModel
from tests.test_attention_kernels import _equations


@pytest.fixture(autouse=True)
def _reset_tier():
    yield
    tier.dispatch.reset()


# ---------------------------------------------------------------------------
# RMSNorm, rotary
# ---------------------------------------------------------------------------

def test_rms_norm_hand_case():
    x = jnp.array([[3.0, 4.0], [0.0, 0.0]])
    got = rms_norm(x, jnp.array([2.0, 0.5]), eps=0.0 + 1e-12)
    rms = np.sqrt((9 + 16) / 2)
    np.testing.assert_allclose(got[0], [2 * 3 / rms, 0.5 * 4 / rms], rtol=1e-6)
    np.testing.assert_allclose(got[1], [0.0, 0.0])      # eps keeps it finite


def test_rms_norm_computes_in_float32_and_returns_the_input_dtype():
    x = (jnp.arange(8, dtype=jnp.float32).reshape(2, 4) + 300).astype(
        jnp.bfloat16)
    got = rms_norm(x, jnp.ones((4,), jnp.bfloat16), 1e-6)
    assert got.dtype == jnp.bfloat16
    xf = np.asarray(x, np.float32)
    want = xf / np.sqrt((xf ** 2).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=1e-2)


def test_rotary_interleaved_hand_case():
    """d = 4, base 100: pair 0 turns by pos, pair 1 by pos / 10; position 0
    is left alone; pairs are (x0, x1) and (x2, x3), not (x0, x2)."""
    x = jnp.array([[[1.0, 0.0, 0.0, 2.0]], [[1.0, 0.0, 0.0, 2.0]]])  # [T,1,4]
    got = np.asarray(rotary_interleaved(x, jnp.arange(2), base=100.0))
    np.testing.assert_allclose(got[0, 0], [1, 0, 0, 2], atol=1e-7)
    np.testing.assert_allclose(
        got[1, 0], [np.cos(1.0), np.sin(1.0),
                    -2 * np.sin(0.1), 2 * np.cos(0.1)], rtol=1e-6)


def test_rotary_scores_depend_on_the_distance_only():
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jax.random.normal(k[0], (1, 3, 8))
    kk = jax.random.normal(k[1], (1, 3, 8))

    def score(pq, pk):
        return jnp.sum(rotary_interleaved(q, jnp.array([pq]), 1e4)
                       * rotary_interleaved(kk, jnp.array([pk]), 1e4))

    np.testing.assert_allclose(score(7, 3), score(104, 100), rtol=1e-4)


def _pair_stack_rotary(x, positions, base):
    """The interleaved rotation as `_qkv` called it until PR 36, written
    out: pairs pulled apart through a `[..., d/2, 2]` view, turned, stacked.
    `x` [..., T, heads, d]."""
    d = x.shape[-1]
    inv_freq = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (positions.astype(jnp.float32)[..., None] * inv_freq)[..., None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _by_pairs(x, positions, base):
    """`rotary_pairs` on the even and the odd lanes of `x` [..., T, heads,
    d], laid back in the interleaved order."""
    a, b = rotary_pairs(x[..., 0::2], x[..., 1::2], positions[..., None],
                        base)
    return jnp.stack([a, b], -1).reshape(x.shape)


def test_rotary_pairs_hand_case():
    """`rotary_interleaved`'s hand case, the two members of each pair
    handed over apart: d = 4, base 100, pair 0 = (x0, x1) turns by pos,
    pair 1 = (x2, x3) by pos / 10."""
    a = jnp.array([[1.0, 0.0], [1.0, 0.0]])         # [T, d/2]: x0, x2
    b = jnp.array([[0.0, 2.0], [0.0, 2.0]])         #            x1, x3
    ra, rb = (np.asarray(r) for r in rotary_pairs(a, b, jnp.arange(2), 100.0))
    np.testing.assert_allclose(ra[0], [1, 0], atol=1e-7)
    np.testing.assert_allclose(rb[0], [0, 2], atol=1e-7)
    np.testing.assert_allclose(ra[1], [np.cos(1.0), -2 * np.sin(0.1)],
                               rtol=1e-6)
    np.testing.assert_allclose(rb[1], [np.sin(1.0), 2 * np.cos(0.1)],
                               rtol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("positions", ["arange", "repeated", "batch"])
def test_rotary_pairs_is_the_interleaved_rotation(positions, dtype):
    """Against `rotary_interleaved` and against the pair-stack formula
    written out above: the same multiplies and adds in float32, so the same
    bits, at positions `0..T-1`, at positions that repeat (the diffusion
    objective's `arange(2L) % L`) and at a batch of positions."""
    T, nh, d = 12, 3, 8
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T, nh, d)).astype(dtype)
    pos = {"arange": jnp.arange(T), "repeated": jnp.arange(T) % 6,
           "batch": jnp.stack([jnp.arange(T), 100 + 3 * jnp.arange(T)])
           }[positions]
    got = _by_pairs(x, pos, 1e6)
    assert got.dtype == x.dtype
    for want in (rotary_interleaved(x, pos, 1e6),
                 _pair_stack_rotary(x, pos, 1e6)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(x, np.float32)).max() > 0.1


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

def test_router_bias_picks_but_does_not_weigh():
    """One token, four experts, top-2.  Scores sigmoid([2, 1, 0, -1]); a
    bias of +1 on expert 3 lifts it over expert 1 for the CHOICE, but its
    weight is its own score: normalised over the chosen, then scaled."""
    x = jnp.array([[1.0, 0.0]])
    w = jnp.array([[2.0, 1.0, 0.0, -1.0], [0.0, 0.0, 0.0, 0.0]])
    s = 1 / (1 + np.exp(-np.array([2.0, 1.0, 0.0, -1.0])))
    chosen, weights = moe.router(x, w, jnp.zeros(4), top_k=2, scale=2.5)
    assert sorted(np.asarray(chosen[0])) == [0, 1]
    chosen, weights = moe.router(x, w, jnp.array([0.0, 0.0, 0.0, 1.0]),
                                 top_k=2, scale=2.5)
    order = np.argsort(np.asarray(chosen[0]))
    assert list(np.asarray(chosen[0])[order]) == [0, 3]
    np.testing.assert_allclose(
        np.asarray(weights[0])[order],
        2.5 * np.array([s[0], s[3]]) / (s[0] + s[3]), rtol=1e-6)
    np.testing.assert_allclose(np.sum(weights), 2.5, rtol=1e-6)


def test_router_bias_gets_no_gradient():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 8))
    g = jax.grad(lambda b: jnp.sum(moe.router(x, w, b, 2, 1.0)[1] ** 2))(
        jnp.zeros(8))
    assert not np.any(np.asarray(g))


def test_bias_update_sign():
    """An expert under the mean load is made likelier, one over it less
    likely, one at the mean is left; by `speed` whatever the distance."""
    bias = jnp.array([[0.5, 0.5, 0.5, 0.5]])
    counts = jnp.array([[10, 2, 6, 6]])              # mean 6
    got = moe.update_router_bias(bias, counts, speed=0.01)
    np.testing.assert_allclose(got, [[0.49, 0.51, 0.5, 0.5]], rtol=1e-6)


def test_expert_counts_against_a_host_count():
    rng = np.random.default_rng(0)
    chosen = rng.integers(0, 16, (50, 3)).astype(np.int32)
    got = np.asarray(moe.expert_counts(jnp.asarray(chosen), 16))
    np.testing.assert_array_equal(got, np.bincount(chosen.ravel(),
                                                   minlength=16))


# ---------------------------------------------------------------------------
# the expert layer: shares, dropless dispatch
# ---------------------------------------------------------------------------

T, H, I, E, K = 24, 16, 8, 8, 2


def _layer_params(seed=0, experts=E):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)

    def n(i, *shape):
        return jax.random.normal(k[i], shape) * 0.3

    return {"router": n(0, H, experts), "w_gate": n(1, experts, H, I),
            "w_up": n(2, experts, H, I), "w_down": n(3, experts, I, H),
            "shared_gate": n(4, H, 2 * I), "shared_up": n(5, H, 2 * I),
            "shared_down": n(6, 2 * I, H)}


def _choose(x, p, bias, top_k):
    """[T, k] experts with the largest score + bias, on the host."""
    s = 1 / (1 + np.exp(-(np.asarray(x) @ np.asarray(p["router"]))))
    return np.argsort(-(s + np.asarray(bias)), axis=-1, kind="stable")[:, :top_k]


def _uncut_layer(x, p, bias, top_k, scale, held=None, chosen=None):
    """The layer as the equations say, token by token and expert by expert;
    `held` (first, count) leaves the other experts' terms out.  `chosen`:
    the choice made beforehand (it has no gradient), for use under
    `jax.grad`."""
    silu = lambda v: v / (1 + jnp.exp(-v))
    ffn = lambda v, g, u, d: (silu(v @ g) * (v @ u)) @ d
    s = 1 / (1 + jnp.exp(-(x @ p["router"])))
    all_chosen = _choose(x, p, bias, top_k) if chosen is None else chosen
    out = []
    for t in range(x.shape[0]):
        chosen = all_chosen[t]
        w = s[t, chosen]
        w = w / (jnp.sum(w) + 1e-20) * scale
        y = ffn(x[t], p["shared_gate"], p["shared_up"], p["shared_down"])
        for j, e in enumerate(chosen):
            e = int(e)
            if held is None or held[0] <= e < held[0] + held[1]:
                i = e - (held[0] if held else 0)
                y = y + w[j] * ffn(x[t], p["w_gate"][i], p["w_up"][i],
                                   p["w_down"][i])
        out.append(y)
    return jnp.stack(out)


def _share(p, first, count):
    return {**p, **{n: p[n][first:first + count]
                    for n in ("w_gate", "w_up", "w_down")}}


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts cut into 4 shares of 2: the routed parts of all shares plus
    the shared experts counted once are the uncut reference layer."""
    p = _layer_params()
    x = jax.random.normal(jax.random.PRNGKey(9), (T, H))
    bias = jax.random.normal(jax.random.PRNGKey(8), (E,)) * 0.1
    want = _uncut_layer(x, p, bias, K, 2.448)
    shared = moe.swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    total, counts = shared, None
    for first in range(0, E, 2):
        y, counts, _ = moe.expert_layer(x, _share(p, first, 2), bias, top_k=K,
                                        scale=2.448, first_held=first)
        total = total + (y - shared)              # this share's routed part
        # one share against the reference given the same share
        np.testing.assert_allclose(
            y, _uncut_layer(x, _share(p, first, 2), bias, K, 2.448,
                            held=(first, 2)), atol=1e-5)
    np.testing.assert_allclose(total, want, atol=1e-5)
    assert int(jnp.sum(counts)) == T * K          # every share counts all E


@pytest.mark.parametrize("case", ["all_choose_one_held", "none_held"])
def test_dropless_under_imbalance(case):
    """A batch whose tokens all choose one held expert (its group holds
    every token, the other held expert none), and one in which no token
    chooses a held expert: the reference's result and gradients."""
    p = _share(_layer_params(3), 2, 2)            # experts 2, 3 held
    x = jax.random.normal(jax.random.PRNGKey(4), (T, H))
    bias = jnp.zeros(E)
    if case == "all_choose_one_held":
        bias = bias.at[3].set(10.0).at[2].set(-10.0)
    else:
        bias = bias.at[2].set(-10.0).at[3].set(-10.0)

    def system(x, p):
        return moe.expert_layer(x, p, bias, top_k=K, scale=2.448,
                                first_held=2)

    y, counts, _ = system(x, p)
    held_counts = np.asarray(counts)[2:4]
    assert list(held_counts) == ([0, T] if case == "all_choose_one_held"
                                 else [0, 0])
    np.testing.assert_allclose(
        y, _uncut_layer(x, p, bias, K, 2.448, held=(2, 2)), atol=1e-5)
    g = jax.random.normal(jax.random.PRNGKey(5), (T, H))
    got = jax.grad(lambda x, p: jnp.sum(system(x, p)[0] * g), (0, 1))(x, p)
    chosen = _choose(x, p, bias, K)
    want = jax.grad(lambda x, p: jnp.sum(_uncut_layer(
        x, p, bias, K, 2.448, held=(2, 2), chosen=chosen) * g), (0, 1))(x, p)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5)


# the routed part on a row bound: 24 tokens top-3 of 8 (72 pairs), experts
# 2, 3, 4 held, buffers of 16 rows
K_BOUND, ROWS = 3, 16
# case -> (tokens with all three pairs held, with two, with one, overflows)
BOUND_CASES = {"below": (2, 1, 2, 0), "exactly_at": (3, 2, 3, 0),
               "one_above": (3, 3, 2, 1), "every_pair_held": (24, 0, 0, 1),
               "none_held": (0, 0, 0, 0),
               "whole_tokens_beside_tokens_with_none": (5, 0, 0, 0)}


def _chosen_with(whole, two, one):
    """[T, 3] distinct experts a token: `whole` tokens choose the held 2, 3,
    4, `two` tokens two of them, `one` tokens one, the rest none; the kinds
    lie scattered over the batch."""
    rows = ([[2, 3, 4]] * whole + [[7, 4, 2]] * two + [[0, 3, 6]] * one
            + [[5, 0, 1]] * (T - whole - two - one))
    return jnp.asarray(np.asarray(rows, np.int32)[
        np.random.default_rng(0).permutation(T)])


def _bounded(x, p, chosen, g):
    """Result, overflow flag and gradients of the routed part at `ROWS`
    rows, the pairs' weights from the router's scores as the layer makes
    them."""
    def routed(x, p):
        s = jnp.take_along_axis(jax.nn.sigmoid(x @ p["router"]), chosen, -1)
        w = s / (jnp.sum(s, -1, keepdims=True) + 1e-20) * 2.448
        y, over = moe.routed_experts(x, chosen, w, p["w_gate"], p["w_up"],
                                     p["w_down"], 2, ROWS)
        return jnp.sum(y * g), (y, over)

    (_, (y, over)), grads = jax.value_and_grad(routed, (0, 1),
                                               has_aux=True)(x, p)
    return y, over, grads


_bounded_jit = jax.jit(_bounded)


@pytest.mark.parametrize("case", list(BOUND_CASES))
def test_routed_rows_on_a_bound_against_the_uncut_layer(case):
    """Held pairs below the bound, at it, over it (two passes of 16 rows
    then; five where every pair is held, the last over rows past the 72
    pairs): values and the gradients in x, the router (through the weights)
    and the three expert matrices are the reference's, in float32; the
    cases share one compilation."""
    whole, two, one, overflows = BOUND_CASES[case]
    assert (3 * whole + 2 * two + one > ROWS) == bool(overflows)
    f32 = lambda tree: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), tree)
    p = f32({n: v for n, v in _share(_layer_params(3), 2, 3).items()
             if not n.startswith("shared")})
    x = f32(jax.random.normal(jax.random.PRNGKey(4), (T, H)))
    g = f32(jax.random.normal(jax.random.PRNGKey(5), (T, H)))
    chosen = _chosen_with(whole, two, one)
    y, over, got = _bounded_jit(x, p, chosen, g)
    assert _bounded_jit._cache_size() == 1
    assert int(over) == overflows and y.dtype == jnp.float32
    zero = {n: jnp.zeros_like(v) for n, v in _layer_params().items()
            if n.startswith("shared")}

    def reference(x, p):
        return _uncut_layer(x, {**p, **zero}, None, K_BOUND, 2.448,
                            held=(2, 3), chosen=np.asarray(chosen))

    np.testing.assert_allclose(y, reference(x, p), atol=1e-5)
    want = jax.grad(lambda x, p: jnp.sum(reference(x, p) * g), (0, 1))(x, p)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(path))


def test_a_layer_that_holds_all_its_experts_has_one_path():
    """The row bound is T*k where all experts are held (and in any small
    batch): one pass and no loop in the program.  An eighth held of 2,048
    pairs: passes of 512 rows under one `while`."""
    x = jnp.zeros((1024, H), jnp.float32)

    def program(held):
        p = _share(_layer_params(experts=16), 0, held)
        return str(jax.make_jaxpr(lambda x, p: moe.expert_layer(
            x, p, jnp.zeros(16), top_k=2, scale=1.0, first_held=0))(x, p))

    assert moe.row_bound(2048, 16, 16) == 2048
    assert "while[" not in program(16) and "cond[" not in program(16)
    assert moe.row_bound(2048, 2, 16) == 512
    assert program(2).count("while[") == 1 and "cond[" not in program(2)


@pytest.mark.parametrize("sizes", [[100, 0, 60], [0, 0, 0], [256, 0, 0]])
def test_grouped_matmul_kernel_against_the_reference(sizes):
    """The Pallas lowering (interpret mode) against `ragged_dot`: values,
    both gradients, and zeros in the rows of no group.  (Without x64, as on
    the chip: the library kernel's index arithmetic is int32.)"""
    with jax.enable_x64(False):
        _check_grouped_matmul(sizes)


def _check_grouped_matmul(sizes):
    gm = tier.grouped_matmul
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(k[0], (256, 128), jnp.float32)
    rhs = jax.random.normal(k[1], (3, 128, 256), jnp.float32)
    g = jax.random.normal(k[2], (256, 256), jnp.float32)
    gs = jnp.array(sizes, jnp.int32)
    assert gm.grouped_supports(lhs, rhs, gs)
    tile = tier.TileConfig(block_m=64, block_n=128, block_k=128)

    def kernel(l, r):
        return gm.grouped_matmul(l, r, gs, tile=tile, interpret=True)

    def reference(l, r):
        return gm.grouped_matmul_reference(l, r, gs)

    out = kernel(lhs, rhs)
    np.testing.assert_allclose(out, reference(lhs, rhs), atol=1e-3)
    assert not np.any(np.asarray(out[sum(sizes):]))
    got = jax.grad(lambda l, r: jnp.sum(kernel(l, r) * g), (0, 1))(lhs, rhs)
    want = jax.grad(lambda l, r: jnp.sum(reference(l, r) * g), (0, 1))(
        lhs, rhs)
    for a, b in zip(got, want):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(a, b, atol=2e-3)


def test_expert_layer_takes_the_kernel_when_the_tier_is_forced():
    """Widths of 128 so the grouped kernel's tiles divide them: the forced
    tier (interpret mode) gives what the reference lowering gives."""
    with jax.enable_x64(False):
        _check_forced_tier()


def _check_forced_tier():
    k = jax.random.split(jax.random.PRNGKey(1), 8)
    n = lambda i, *s: jax.random.normal(k[i], s) * 0.05
    p = {"router": n(0, 128, 4), "w_gate": n(1, 2, 128, 128),
         "w_up": n(2, 2, 128, 128), "w_down": n(3, 2, 128, 128),
         "shared_gate": n(4, 128, 128), "shared_up": n(5, 128, 128),
         "shared_down": n(6, 128, 128)}
    x = n(7, 64, 128)
    run = lambda: moe.expert_layer(x, p, jnp.zeros(4), top_k=2, scale=1.0,
                                   first_held=1)[0]
    want = run()
    tier.dispatch.set_dispatch_mode("pallas")
    np.testing.assert_allclose(run(), want, atol=1e-4)


# ---------------------------------------------------------------------------
# attention with values narrower than keys
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("branch", ["xla", "blockwise", "flash"])
def test_fused_attention_values_narrower_than_keys(branch, monkeypatch):
    """Keys of 24, values of 16, causal, forward and backward, through each
    of `fused_attention`'s branches against the plain reference."""
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(k[0], (1, 2, 64, 24), jnp.float32)
    kk = jax.random.normal(k[1], (1, 2, 64, 24), jnp.float32)
    v = jax.random.normal(k[2], (1, 2, 64, 16), jnp.float32)
    g = jax.random.normal(k[3], (1, 2, 64, 16), jnp.float32)
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
        monkeypatch.setattr(tier.attention, "flash_attention", lambda *a, **kw: (
            taken.append(branch), real(*a, **kw))[1])
    else:
        real = ak.mha_reference
        monkeypatch.setattr(ak, "mha_reference", lambda *a: (
            taken.append(branch), real(*a))[1])

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) * g)

    out = ak.fused_attention(q, kk, v, causal=True)
    assert taken == [branch] and out.shape == (1, 2, 64, 16)
    got = jax.grad(loss(lambda q, k, v: ak.fused_attention(
        q, k, v, causal=True)), (0, 1, 2))(q, kk, v)
    monkeypatch.undo()
    tier.dispatch.reset()
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / np.sqrt(24)
    s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -jnp.inf)
    np.testing.assert_allclose(
        out, jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v),
        atol=1e-5)
    want = jax.grad(loss(lambda q, k, v: ak.mha_reference(
        q, k, v, None, True)), (0, 1, 2))(q, kk, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_attention_predicates_speak_of_the_value_width():
    q = jnp.zeros((1, 2, 4096, 192), jnp.bfloat16)
    v = jnp.zeros((1, 2, 4096, 128), jnp.bfloat16)
    assert tier.attention.attention_supports(q, q, v)
    assert tier.attention.attention_profitable(q, q, v)
    # keys of another width than the queries, or values over other
    # positions than the keys, are no attention
    assert not tier.attention.attention_supports(q, v, v)
    assert not tier.attention.attention_supports(q, q, v[:, :, :128])
    # the rule is on the key width: 96 is no multiple of 64, whatever v is
    q96 = jnp.zeros((1, 2, 4096, 96), jnp.bfloat16)
    assert not tier.attention.attention_profitable(q96, q96, v)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _batch(seed=0, rows=2, t=16, vocab=96):
    ids = np.random.default_rng(seed).integers(0, vocab, (rows, t)).astype(
        np.int32)
    labels = np.concatenate([ids[:, 1:], np.zeros((rows, 1), np.int32)], 1)
    return MultiDataSet(features=[ids], labels=[labels])


def test_model_trains_through_fit_and_counts_its_routing():
    m = DecoderModel(DecoderConfig.tiny(first_expert=2, n_experts_held=4),
                     seed=1)
    assert m.params_["moe"]["w_gate"].shape == (2, 4, 32, 16)
    assert m.params_["moe"]["router"].shape == (2, 32, 8)
    first = float(m.fit_batch(_batch()))
    m.fit([_batch()] * 7)
    assert m.iteration == 8 and m.epoch == 1
    assert m.score() < first
    # every token chose top_k of the router's 8 in both expert layers, in
    # every step: the device counter against the host's arithmetic
    load = m.expert_load()
    assert load.shape == (2, 8) and load.dtype == np.int32
    np.testing.assert_array_equal(load.sum(1), [8 * 2 * 16 * 2] * 2)
    # the bias moved against the load, by the speed a step
    bias = np.asarray(m.state_["router_bias"])
    assert np.all(np.abs(bias) <= 8 * 1e-3 + 1e-9) and np.any(bias != 0)
    assert m.output(_batch().features[0]).shape == (2, 16, 96)


def test_routing_counter_against_a_count_made_on_the_host():
    """One step in float32: the counter the step kept on the device is the
    host's bincount of the experts the router chose in each expert layer."""
    m = DecoderModel(DecoderConfig.tiny(), seed=2)
    batch = _batch(3)
    ids = jnp.asarray(batch.features[0])
    chosen = []
    real = moe.router

    def spy(*a, **kw):
        c, w = real(*a, **kw)
        # the router's own choices, sent to the host from inside the scan
        jax.debug.callback(lambda c: chosen.append(np.asarray(c)), c)
        return c, w

    moe.router, keep = spy, moe.router
    try:
        jax.block_until_ready(
            m._trunk(m.params_, m.state_["router_bias"], ids))
        jax.effects_barrier()
    finally:
        moe.router = keep
    assert len(chosen) == 2
    want = np.stack([np.bincount(np.asarray(c).ravel(), minlength=8)
                     for c in chosen])
    m.fit_batch(batch)
    np.testing.assert_array_equal(m.expert_load(), want)


def test_fit_steps_is_k_fit_batches():
    a = DecoderModel(DecoderConfig.tiny(), seed=4)
    b = DecoderModel(DecoderConfig.tiny(), seed=4)
    b1, b2 = _batch(1), _batch(2)
    la = [float(a.fit_batch(b1)), float(a.fit_batch(b2))]
    lb = b.fit_steps(MultiDataSet(
        features=[np.stack([b1.features[0], b2.features[0]])],
        labels=[np.stack([b1.labels[0], b2.labels[0]])]))
    np.testing.assert_allclose(np.asarray(lb), la, rtol=1e-5)
    assert b.iteration == 2
    np.testing.assert_array_equal(a.expert_load(), b.expert_load())


def test_save_load_round_trip():
    m = DecoderModel(DecoderConfig.tiny(), seed=5)
    m.fit_batch(_batch())
    f = io.BytesIO()
    m.save(f)
    f.seek(0)
    m2 = DecoderModel.load(f)
    assert m2.iteration == 1 and m2.num_params() == m.num_params()
    ids = _batch().features[0]
    np.testing.assert_array_equal(np.asarray(m.output(ids)),
                                  np.asarray(m2.output(ids)))
    np.testing.assert_array_equal(m.expert_load(), m2.expert_load())
    assert float(m.fit_batch(_batch(1))) == float(m2.fit_batch(_batch(1)))


def routed_part_op_names(jaxpr, stack="", inside=False):
    """The scope path of every `while` equation of a step's jaxpr (the
    layers' `scan` is none), and of every equation inside their bodies
    (inner jaxprs entered, each equation's name stack put after its
    enclosing equations', as the lowering composes an instruction's
    `op_name`)."""
    loops, inner = [], []
    for eqn in jaxpr.eqns:
        path = f"{stack}/{eqn.source_info.name_stack}/{eqn.primitive.name}"
        is_loop = eqn.primitive.name == "while"
        if is_loop:
            loops.append(path)
        if inside:
            inner.append(path)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    l, i = routed_part_op_names(sub, path, inside or is_loop)
                    loops += l
                    inner += i
    return loops, inner


def bounded_model_against_the_full_pass(config, blocks, monkeypatch):
    """A model whose row bound (512) is below its T*k (2 x 512 tokens top-2:
    2,048 pairs, 2 of 16 experts held) against the same model with the bound
    forced to T*k: loss and every gradient leaf, four steps of `fit` of
    which the last two overflow the bound in the first expert layer alone
    (four passes there), the counter, and the step's `while`s, two a
    distinct expert block (forward and backward), with `moe` in the scope
    path of every op in their bodies."""
    c = config
    assert (c.hidden, c.n_experts, c.held, c.top_k) == (16, 16, 2, 2)
    batches = [_batch(s, rows=2, t=512) for s in (1, 2, 3)]
    ids, labels = (jnp.asarray(batches[0].features[0]),
                   jnp.asarray(batches[0].labels[0]))
    held = slice(c.first_expert, c.first_expert + c.held)

    def run(m):
        bias = m.state_["router_bias"]
        (loss, _), grads = jax.jit(jax.value_and_grad(
            m._loss, has_aux=True))(m.params_, bias, ids, labels)
        losses = [float(m.fit_batch(b)) for b in batches[:2]]
        # every token of the first expert layer now picks both held experts
        m.state_["router_bias"] = m.state_["router_bias"].at[0, held].set(10.)
        losses += [float(m.fit_batch(batches[2])),
                   float(m.fit_batch(batches[0]))]
        return loss, grads, losses, m.expert_load(), m.routed_rows()

    bounded = DecoderModel(c, seed=3)
    step_args = (bounded.params_, bounded.opt_state_, bounded.state_,
                 *device_counters(bounded), ids, labels)
    lowered = bounded._step().lower(*step_args)
    step_jaxpr = jax.make_jaxpr(bounded._step_body())(*step_args)
    loss, grads, losses, load, rows = run(bounded)
    with monkeypatch.context() as mp:
        mp.setattr(moe, "row_bound", lambda pairs, held, n: pairs)
        want = run(DecoderModel(c, seed=3))
    np.testing.assert_allclose(loss, want[0], rtol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want[1])):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * scale, \
            jax.tree_util.keystr(path)
    np.testing.assert_allclose(losses, want[2], rtol=1e-5)
    np.testing.assert_array_equal(load, want[3])
    n_moe = c.n_layers - c.n_dense_layers
    assert rows["steps"] == 4
    assert (rows["bound"], rows["pairs"]) == (512, 2048)
    np.testing.assert_array_equal(rows["steps_over_bound"],
                                  [2] + [0] * (n_moe - 1))
    assert not np.any(want[4]["steps_over_bound"])
    in_moe = re.compile(r"(?<![\w.\-])moe(?![\w.\-])")
    loops, names = routed_part_op_names(step_jaxpr.jaxpr)
    assert len(loops) == 2 * blocks and len(names) > 100 * blocks
    assert [n for n in loops + names if not in_moe.search(n)] == []
    for part in ("dispatch", "experts", "combine"):
        assert any(part in n and "transpose(" in n for n in names), part
        assert any(part in n and "transpose(" not in n for n in names), part
    # and in the compiled program's text: the recomputed forward rule's
    # loop is gone, no `conditional` anywhere
    hlo = lowered.compile().as_text()
    made = [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in re.findall(r" while\(.*", hlo)]
    assert len([n for n in made if in_moe.search(n)]) == 2 * blocks
    assert " conditional(" not in hlo


def test_a_model_on_a_row_bound_is_the_model_on_the_full_pass(monkeypatch):
    bounded_model_against_the_full_pass(
        DecoderConfig.tiny(hidden=16, n_experts=16, n_experts_held=2,
                           first_expert=4), 1, monkeypatch)


def test_a_share_outside_the_routers_width_is_refused():
    with pytest.raises(ValueError, match="not among"):
        DecoderModel(DecoderConfig.tiny(first_expert=6, n_experts_held=4))


# ---------------------------------------------------------------------------
# what the blocks save for the backward pass
# ---------------------------------------------------------------------------

T_FLASH = 64          # a sequence the forced tier's tiles (16 x 32) divide


def _flash_model(seed=6):
    """`DecoderConfig.tiny` with the attention kernels forced (interpret
    mode on the CPU), as the chip runs them from 2k tokens on."""
    tier.dispatch.set_dispatch_mode("pallas")
    tier.dispatch.set_tile("attention", tier.TileConfig(block_q=16,
                                                        block_kv=32))
    return DecoderModel(DecoderConfig.tiny(), seed=seed)


def _patch_recomputation(mp, scheme):
    """`policy`: the decoder as it is.  `bare`: each block under a bare
    `jax.checkpoint` (what it was before PR 28).  `none`: no checkpoint."""
    if scheme == "bare":
        mp.setattr(jax.checkpoint_policies, "save_only_these_names",
                   lambda *names: None)
    elif scheme == "none":
        mp.setattr(jax, "checkpoint", lambda f, **kw: f)
    else:
        assert scheme == "policy"


def _blocks(model, scheme="policy"):
    """The dense block and the expert block as `_trunk` wraps them, each with
    arguments to call it on."""
    made = []
    with pytest.MonkeyPatch.context() as mp:
        _patch_recomputation(mp, scheme)
        wrap = jax.checkpoint
        mp.setattr(jax, "checkpoint",
                   lambda f, **kw: made.append(wrap(f, **kw)) or made[-1])
        jax.eval_shape(model._trunk, model.params_,
                       model.state_["router_bias"],
                       jnp.zeros((2, T_FLASH), jnp.int32))
    dense, moe_block = made
    c = model.config
    x = jax.random.normal(jax.random.PRNGKey(9), (2, T_FLASH, c.hidden),
                          jnp.float32)
    lp = jax.tree_util.tree_map(lambda a: a[0], model.params_["dense"])
    layers = (model.params_["moe"], model.state_["router_bias"])
    return {"dense": (dense, (x, lp)),
            "scanned_experts": (
                lambda x, layers: jax.lax.scan(moe_block, x, layers)[0],
                (x, layers)),
            "expert": (lambda x, layer: moe_block(x, layer)[0],
                       (x, jax.tree_util.tree_map(lambda a: a[0], layers)))}


def _kernels(jaxpr):
    """The kernel functions of a jaxpr's `pallas_call`s, by name."""
    return [eqn.params["jaxpr"].debug_info.func_name
            for eqn in _equations(jaxpr)
            if eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("block", ["dense", "scanned_experts"])
@pytest.mark.parametrize("scheme,kernels", [("policy", 2), ("bare", 3)])
def test_a_blocks_gradient_runs_the_flash_forward_once(block, scheme,
                                                       kernels):
    """Forward kernel, backward kernel: 2 a block (a scan's body counts
    once).  Under a bare checkpoint the backward pass holds the forward
    kernel a second time."""
    f, args = _blocks(_flash_model(), scheme)[block]
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(f(*a))))(*args)
    names = _kernels(jaxpr.jaxpr)
    assert sorted(names) == sorted(
        ["_flash_kernel"] * (kernels - 1) + ["_flash_bwd_kernel"]), names


@pytest.mark.parametrize("block", ["dense", "expert", "scanned_experts"])
def test_a_block_saves_its_input_and_the_kernels_two_results(block, capsys):
    """Beside the block's arguments (and the constants of the rotation)
    the backward pass is handed the kernel's output [B, H, T, Dv] and the
    logsumexp as [B*H, T] — not the kernel's own [B*H, T, 1], which pads to
    128 lanes on the chip — and nothing else."""
    m = _flash_model()
    f, args = _blocks(m)[block]
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(f, *args)
    lines = capsys.readouterr().out.strip().splitlines()
    saved = [l for l in lines
             if " from the argument " not in l and " from a constant" not in l]
    c = m.config
    out = f"f32[2,{c.n_heads},{T_FLASH},{c.v_head_dim}]"
    lse = f"f32[{2 * c.n_heads},{T_FLASH}]"
    if block == "scanned_experts":      # stacked by the scan, with its input
        L = c.n_layers - c.n_dense_layers
        want = [f"f32[{L},{s[4:]}" for s in
                (out, lse, f"f32[2,{T_FLASH},{c.hidden}]")]
        assert sorted(l.split()[0] for l in saved) == sorted(want), lines
    else:
        assert [l.split()[0] for l in saved] == [out, lse], lines
        assert f"named '{ak.FLASH_LSE}'" in saved[1], lines
        assert any(" from the argument x" in l for l in lines), lines


def test_fit_batch_is_bit_equal_whatever_the_blocks_save():
    """One train step with the kernels: the parameters after it are the
    same to the last bit whether a block keeps the kernel's two results or
    runs the kernel again, and within float32 round-off of a step that
    recomputes nothing."""
    after = {}
    for scheme in ("policy", "bare", "none"):
        m = _flash_model(seed=7)
        with pytest.MonkeyPatch.context() as mp:
            _patch_recomputation(mp, scheme)
            loss = float(m.fit_batch(_batch(5, t=T_FLASH)))
        assert np.isfinite(loss)
        after[scheme] = (loss, jax.tree_util.tree_leaves(m.params_))
    assert after["policy"][0] == after["bare"][0]
    for a, b, c in zip(*(after[s][1] for s in ("policy", "bare", "none"))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-6)


def test_without_the_kernels_a_block_is_recomputed_whole(capsys):
    """Where `fused_attention` takes the XLA branch (every CPU run, short
    sequences) nothing carries the names: the block saves its arguments
    alone, as under a bare checkpoint."""
    m = DecoderModel(DecoderConfig.tiny(), seed=6)
    f, args = _blocks(m)["dense"]
    assert _kernels(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(f(*a))))(*args).jaxpr) == []
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(f, *args)
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines and all(" from the argument " in l
                         or " from a constant" in l for l in lines), lines


# ---------------------------------------------------------------------------
# latent attention against the form it had until PR 36
# ---------------------------------------------------------------------------

def _qkv_before(model, x, lp):
    """`DecoderModel._qkv` as it stood before PR 36, in plain jnp: one
    product for q and one for k-nope and v together, the rotary lanes
    sliced, turned through the pair-stack, concatenated back, then the
    head-first transposes.  The reference of the tests below."""
    c = model.config
    B, T, _ = x.shape
    nh, dn, dr, dv = c.n_heads, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim
    pos = jnp.arange(T)
    q = (x @ lp["Wq"]).reshape(B, T, nh, dn + dr)
    kva = x @ lp["Wkva"]
    latent = rms_norm(kva[..., :c.kv_lora_rank], lp["kv_norm"], c.eps)
    kv = (latent @ lp["Wkvb"]).reshape(B, T, nh, dn + dv)
    q_rope = _pair_stack_rotary(q[..., dn:], pos, c.rope_base)
    k_rope = _pair_stack_rotary(kva[..., None, c.kv_lora_rank:], pos,
                                c.rope_base)
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (B, T, nh, dr))], -1)
    heads_first = (0, 2, 1, 3)
    return (q.transpose(heads_first), k.transpose(heads_first),
            kv[..., dn:].transpose(heads_first))


def _attention_before(model, x, lp, L=None):
    """`DecoderModel._attention` as it stood before PR 36."""
    c = model.config
    B, T, _ = x.shape
    dt = lp["Wo"].dtype
    q, k, v = _qkv_before(model, rms_norm(x, lp["norm1"], c.eps).astype(dt),
                          lp)
    o = ak.mha_reference(q, k, v, causal=True)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
    return x + (o @ lp["Wo"]).astype(x.dtype)


def _latent_layer(seed=11):
    """A model, its dense layer's parameters with gains that are not all
    one, and a batch of residual-stream rows."""
    m = DecoderModel(DecoderConfig.tiny(), seed=seed)
    lp = jax.tree_util.tree_map(lambda a: a[0], m.params_["dense"])
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    lp = {**lp,
          "kv_norm": 1 + 0.2 * jax.random.normal(k1, lp["kv_norm"].shape),
          "norm1": 1 + 0.2 * jax.random.normal(k2, lp["norm1"].shape)}
    return m, lp, jax.random.normal(k3, (2, 16, m.config.hidden))


def _rotary_lanes_apart(c):
    """Where each lane of a head of `_qkv`'s q and k stands in the
    checkpoint's order: the nope lanes, every pair's first member, every
    pair's second."""
    dn, dr = c.qk_nope_dim, c.qk_rope_dim
    return np.concatenate([np.arange(dn), dn + np.arange(0, dr, 2),
                           dn + np.arange(1, dr, 2)])


def _close(got, want, tol=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max()


@pytest.mark.parametrize("what", ["q", "k", "v", "scores", "out"])
def test_latent_attention_is_what_it_was(what):
    """float32, within 1e-5 of the largest value: v and the layer's output
    as they were; q and k as they were with the rotary lanes of every head
    de-interleaved alike, so every `q . k` as it was."""
    m, lp, x = _latent_layer()
    q0, k0, v0 = _qkv_before(m, x, lp)
    q, k, v, mask = m._qkv(x, lp)
    assert mask == {"causal": True}
    lanes = _rotary_lanes_apart(m.config)
    if what == "q":
        _close(q, q0[..., lanes])
    elif what == "k":
        _close(k, k0[..., lanes])
    elif what == "v":
        _close(v, v0)
    elif what == "scores":
        _close(jnp.einsum("bhqd,bhkd->bhqk", q, k),
               jnp.einsum("bhqd,bhkd->bhqk", q0, k0))
    else:
        _close(m._attention(x, lp), _attention_before(m, x, lp))


@pytest.mark.parametrize("wrt", ["Wq", "Wkva", "Wkvb", "Wo", "kv_norm",
                                 "norm1", "x"])
def test_latent_attention_gradients_are_what_they_were(wrt):
    m, lp, x = _latent_layer()
    ct = jax.random.normal(jax.random.PRNGKey(12), x.shape)

    def grads(layer):
        return jax.jit(jax.grad(lambda x, lp: jnp.sum(layer(x, lp) * ct),
                                argnums=(0, 1)))(x, lp)

    (gx, glp), (gx0, glp0) = grads(m._attention), grads(
        lambda x, lp: _attention_before(m, x, lp))
    _close(*((gx, gx0) if wrt == "x" else (glp[wrt], glp0[wrt])))


# sha256 over the leaves of `DecoderModel(DecoderConfig.tiny(), seed=3)
# .params_` in tree order, read on the tree before PR 36 under the tests' x64
# (the gains are float64 there; with x64 off it reads fe126cdd2423b2d9...)
TINY_PARAMS_SHA256 = (
    "9a061ba339f1eef3b9171ec7d4aba10c3ea7accb0ee4ce6ff3c689010403db20")


def test_a_checkpoint_from_before_gives_the_logits_from_before():
    """Same model: the tree, the shapes and the init draws are what they
    were (the hash was read on the parent's tree), `save`/`load` carry
    them, and the loaded model's logits are those of the same parameters
    under the layer as it stood before."""
    m = DecoderModel(DecoderConfig.tiny(), seed=3)
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(m.params_):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == TINY_PARAMS_SHA256
    assert {k: v.shape for k, v in m.params_["dense"].items()
            if k.startswith(("W", "kv"))} == {
        "Wq": (1, 32, 24), "Wkva": (1, 32, 20), "kv_norm": (1, 16),
        "Wkvb": (1, 16, 32), "Wo": (1, 16, 32)}
    f = io.BytesIO()
    m.save(f)
    f.seek(0)
    loaded = DecoderModel.load(f)
    before = DecoderModel(DecoderConfig.tiny(), seed=3)
    before._attention = lambda x, lp, L=None: _attention_before(before, x, lp)
    ids = _batch(8).features[0]
    _close(loaded.output(ids), before.output(ids))
