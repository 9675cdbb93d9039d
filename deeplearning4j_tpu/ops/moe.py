"""A mixture-of-experts layer that is told which experts it holds.

The layer of DeepSeek-V3 (arXiv:2412.19437, §2.1.2) and its descendants:
a sigmoid router over all `E` routed experts with a selection bias that is
no parameter, top-k, normalised and scaled weights, SwiGLU experts, and
shared experts that see every token.

    s = sigmoid(f32(x) W_g)                      [T, E]
    chosen = the k largest of s + b              (b picks, it does not weigh)
    w = s[chosen] / (sum s[chosen] + eps) * scale
    y = sum_k w_k E_chosen_k(x) + S(x)

`eps` is 1e-20 in DeepSeek-V3's code and 1e-6 in other families'; a layer
may have no shared expert (`S` is then left out).

Under expert parallelism a chip holds the experts `first .. first + held`
of a layer.  The router here keeps all `E` outputs and the published top-k;
`routed_experts` computes the terms of the sum whose expert is held and
leaves the others out — they are the other chips' — so on one chip the
layer runs with no exchange and with no stand-in for the absent chips.

Dropless.  Every (token, chosen expert) pair, T x k of them, gets a row:
the rows are sorted by expert, held experts first, and the grouped product
(`ops/pallas/grouped_matmul`) runs each held expert over its rows, however
many there are.  The row count comes from the batch's shape, a group's size
is data: an imbalance changes the work, never the result, and compiles
nothing.

Named scopes (`moe`, beneath it `router`, `dispatch`, `experts`, `combine`,
`shared`) mark the layer's device ops in a profile, forward and backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------

def router(x, w_router, bias, top_k: int, scale: float, eps: float = 1e-20):
    """(chosen [T, k] int32, weights [T, k] f32) of the tokens `x` [T, H].
    Scores in float32 at full matmul precision: a sixth and a seventh score
    often lie within a bf16 rounding of each other.  `bias` [E] only picks;
    it gets no gradient and is not in the weights.  `eps` is what the
    normalisation adds to the sum of the chosen scores."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps) * scale
    return chosen.astype(jnp.int32), w


def expert_counts(chosen, n_experts: int):
    """How many tokens chose each of the `n_experts`: [E] int32."""
    hit = chosen[..., None] == jnp.arange(n_experts, dtype=jnp.int32)
    return jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)


def update_router_bias(bias, counts, speed: float):
    """The auxiliary-loss-free balancing of DeepSeek-V3 §2.1.2, after a
    step: an expert that got fewer tokens than the mean is made likelier to
    be picked, one that got more less likely, by `speed`."""
    c = counts.astype(jnp.float32)
    return bias + speed * jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)


# ---------------------------------------------------------------------------
# dispatch, experts, combine
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of_tokens(x, order, row_of_pair, k: int):
    """`out[r] = x[order[r] // k]`: the token of the pair that sorted row
    `r` holds.  The gradient is a gather too — a token's gradient is the
    sum over its k pairs' rows — where autodiff would scatter-add."""
    return x[order // k]


def _rows_fwd(x, order, row_of_pair, k):
    return x[order // k], (row_of_pair, x.shape[0])


def _rows_bwd(k, res, g):
    row_of_pair, t = res
    return (jnp.sum(g[row_of_pair].reshape(t, k, g.shape[-1]), axis=1,
                    dtype=jnp.float32).astype(g.dtype), None, None)


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def _rows_of_pairs(ys, order, row_of_pair):
    """`out[p] = ys[row_of_pair[p]]`: back from sorted rows to pairs in
    token order; the gradient goes through the inverse permutation."""
    return ys[row_of_pair]


def _pairs_fwd(ys, order, row_of_pair):
    return ys[row_of_pair], order


def _pairs_bwd(order, g):
    return g[order], None, None


_rows_of_pairs.defvjp(_pairs_fwd, _pairs_bwd)


def _grouped(lhs, rhs, group_sizes):
    from deeplearning4j_tpu.ops import pallas as _tier
    if _tier.dispatch.resolve("grouped_matmul", lhs, rhs,
                              group_sizes) == "pallas":
        return _tier.grouped_matmul.grouped_matmul(
            lhs, rhs, group_sizes,
            tile=_tier.dispatch.get_tile("grouped_matmul"),
            interpret=_tier.dispatch.interpret_mode())
    return _tier.grouped_matmul.grouped_matmul_reference(lhs, rhs,
                                                         group_sizes)


def routed_experts(x, chosen, weights, w_gate, w_up, w_down,
                   first_held: int):
    """The held experts' part of `sum_k w_k E_chosen_k(x)`: x [T, H],
    `chosen`/`weights` [T, k] from `router` over all experts, the held
    experts' matrices `w_gate`/`w_up` [held, H, I] and `w_down`
    [held, I, H], which are experts `first_held .. first_held + held`."""
    t, k = chosen.shape
    held = w_gate.shape[0]
    with jax.named_scope("dispatch"):
        local = chosen - first_held
        is_held = (local >= 0) & (local < held)
        # sort the T*k pairs by expert; pairs of absent experts go last and
        # belong to no group
        key = jnp.where(is_held, local, held).reshape(t * k)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        row_of_pair = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32), unique_indices=True)
        group_sizes = expert_counts(key[:, None], held)
        xs = _rows_of_tokens(x, order, row_of_pair, k)
    with jax.named_scope("experts"):
        gate = _grouped(xs, w_gate, group_sizes)
        up = _grouped(xs, w_up, group_sizes)
        ys = _grouped(jax.nn.silu(gate) * up, w_down, group_sizes)
    with jax.named_scope("combine"):
        per_pair = _rows_of_pairs(ys, order, row_of_pair).reshape(t, k, -1)
        w = jnp.where(is_held, weights, 0.0).astype(x.dtype)
        return jnp.einsum("tkh,tk->th", per_pair, w,
                          preferred_element_type=jnp.float32).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    """`(silu(x W_gate) * x W_up) W_down` (Shazeer 2020, arXiv:2002.05202)."""
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def expert_layer(x, p, bias, *, top_k: int, scale: float, first_held: int,
                 eps: float = 1e-20):
    """This chip's share of the layer for tokens `x` [T, H], and the
    step's count of tokens that chose each of the E experts ([E] int32, held
    or not: the router's load, which the bias update balances).

    `p`: `router` [H, E]; `w_gate`, `w_up` [held, H, I] and `w_down`
    [held, I, H]; `shared_gate`, `shared_up` [H, S] and `shared_down`
    [S, H], the shared experts side by side as one SwiGLU of their summed
    width — or none of the three, for a layer without shared experts.
    `bias` [E] is the router's selection bias, `eps` the router's.

    The routed part's T*k-row buffers are the layer's largest arrays and
    its arithmetic the smallest: a caller short of memory recomputes the
    layer in the backward pass (`jax.checkpoint`) before anything else."""
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            chosen, weights = router(x, p["router"], bias, top_k, scale, eps)
            counts = expert_counts(chosen, p["router"].shape[1])
        routed = routed_experts(x, chosen, weights, p["w_gate"], p["w_up"],
                                p["w_down"], first_held)
        if "shared_gate" not in p:
            return routed, counts
        with jax.named_scope("shared"):
            shared = swiglu(x, p["shared_gate"], p["shared_up"],
                            p["shared_down"])
        return routed + shared, counts
