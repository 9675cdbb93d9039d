"""A mixture-of-experts layer that is told which experts it holds.

The layer of DeepSeek-V3 (arXiv:2412.19437, §2.1.2) and its descendants:
a sigmoid router over all `E` routed experts with a selection bias that is
no parameter, top-k, normalised and scaled weights, SwiGLU experts, and
shared experts that see every token.  Nemotron-H's experts, routed and
shared, are not gated: `relu(x W_up)^2 W_down` (`relu2_mlp`), two grouped
products a pass where SwiGLU's are three; a layer whose parameters have no
`w_gate` (no `shared_gate`) has them.

    s = sigmoid(f32(x) W_g)                      [T, E]
    chosen = the k largest of s + b              (b picks, it does not weigh)
    w = s[chosen] / (sum s[chosen] + eps) * scale
    y = sum_k w_k E_chosen_k(x) + S(x)

`eps` is 1e-20 in DeepSeek-V3's code and 1e-6 in other families'; a layer
may have no shared expert (`S` is then left out).

The older router of Switch/Mixtral/Qwen3-MoE is the other `score`: `p =
softmax(f32(x) W_g)` over all `E`, the k largest probabilities chosen (no
bias), the same normalisation; its load is balanced by an auxiliary loss
(`balance_loss`: Switch Transformer, arXiv:2101.03961, eq. 4), for which it
also returns each expert's mean probability.

Under expert parallelism a chip holds the experts `first .. first + held`
of a layer.  The router here keeps all `E` outputs and the published top-k;
`routed_experts` computes the terms of the sum whose expert is held and
leaves the others out — they are the other chips' — so on one chip the
layer runs with no exchange and with no stand-in for the absent chips.

Dropless, on a row bound.  The (token, chosen expert) pairs, T x k of them,
are sorted by expert, held experts first, and the grouped product
(`ops/pallas/grouped_matmul`) runs each held expert over its rows, however
many there are: a group's size is data, an imbalance changes the work, never
the result, and compiles nothing.  The pairs of absent experts are the other
chips' rows, so the sorted-row buffers are `row_bound` rows long, not T x k:
twice the even share `T*k * held / E`, worked out from shapes alone — what
the chip's receive buffer would be under expert parallelism.  Dispatch
gathers that many rows, the experts run on them, and combine sums each
token's weighted rows into the token (`_sum_into_tokens`).  A step whose
routing sends more pairs than the bound to the held experts takes a second
pass of the same compiled body over the next `row_bound` sorted rows, and so
on while pairs are left (`_routed_in_passes`, a `while_loop` whose trip
count is read on the device): no buffer is ever longer than the bound, no
pair is dropped at any load and nothing compiles again; `expert_layer` says
which steps took more than one pass.  A layer that holds half its experts
or more, or a small batch, has a bound of T x k: one pass, and no loop in
the program.

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

def router(x, w_router, bias, top_k: int, scale: float, eps: float = 1e-20,
           score: str = "sigmoid"):
    """(chosen [T, k] int32, weights [T, k] f32) of the tokens `x` [T, H].
    Scores in float32 at full matmul precision: a sixth and a seventh score
    often lie within a bf16 rounding of each other.  `bias` [E] only picks;
    it gets no gradient and is not in the weights.  `eps` is what the
    normalisation adds to the sum of the chosen scores.

    `score="softmax"`: the scores are probabilities over all E, `bias` is
    not used, and a third result is each expert's mean probability over
    the tokens, [E] f32 — what `balance_loss` needs beside the counts."""
    logits = jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(s, top_k)
    elif score == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias), top_k)
    else:
        raise ValueError(f"score {score!r}; want 'sigmoid' or 'softmax'")
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps) * scale
    if score == "softmax":
        return chosen.astype(jnp.int32), w, jnp.mean(s, axis=0)
    return chosen.astype(jnp.int32), w


def expert_counts(chosen, n_experts: int):
    """How many tokens chose each of the `n_experts`: [E] int32."""
    hit = chosen[..., None] == jnp.arange(n_experts, dtype=jnp.int32)
    return jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)


def balance_loss(counts, mean_prob, tokens: int):
    """`E * sum_e f_e P_e`: `f_e` the share of the step's `tokens` tokens
    that chose expert e (`counts` [E] — no gradient), `P_e` its mean
    probability.  `top_k` under even routing, larger the more the hot
    experts are also the likely ones."""
    f = counts.astype(jnp.float32) / tokens
    return counts.shape[-1] * jnp.sum(f * mean_prob)


def update_router_bias(bias, counts, speed: float):
    """The auxiliary-loss-free balancing of DeepSeek-V3 §2.1.2, after a
    step: an expert that got fewer tokens than the mean is made likelier to
    be picked, one that got more less likely, by `speed`."""
    c = counts.astype(jnp.float32)
    return bias + speed * jnp.sign(jnp.mean(c, axis=-1, keepdims=True) - c)


# ---------------------------------------------------------------------------
# dispatch, experts, combine
# ---------------------------------------------------------------------------

# The sorted-row buffers hold this many times the even share of the pairs,
# `T*k * held / E`: twice, because the routing counter sees ~1.0-1.2x of it
# in a benchmark window's first ~75 steps (kanana 6,100-7,500 pairs a layer
# against 6,144; LFM2 4,100 +- 10% against 4,096).  Where the routers drift
# towards the held experts (kanana's layers 1-3 from step ~80 on, ~10,000
# pairs a step) a step in four takes a second pass: PERF.md, PR 32.
_ROWS_OVER_EVEN_SHARE = 2


def row_bound(pairs: int, held: int, n_experts: int) -> int:
    """Rows of the routed part's buffers for `pairs` = T*k (token, chosen
    expert) pairs of which this chip holds the experts `held` of
    `n_experts`: `_ROWS_OVER_EVEN_SHARE` times the even share, up to a whole
    row tile of the grouped product, and never more than `pairs`."""
    from deeplearning4j_tpu.ops.pallas.tiles import DEFAULT_TILES
    tile = DEFAULT_TILES["grouped_matmul"].block_m
    share = _ROWS_OVER_EVEN_SHARE * pairs * held
    return min(pairs, -(-share // (n_experts * tile)) * tile)


def _rows(route, lo, rows: int, k: int):
    """The index arrays of the sorted rows `lo .. lo + rows` (`lo` traced).
    The held pairs come first in the sort, `n_held` of them.  `pair`/`token`
    of a sorted row; `live`: the row holds a held pair; `group_sizes`: each
    held expert's rows among these; `row_of_slot`: the row of the held pair
    that is number `slot` in TOKEN order among these rows' pairs (a row that
    is not live keeps its place, so the map is a permutation);
    `token_of_slot`, -1 past the live rows; `first` [T]: the slot of each
    token's first pair among them, `has` [T]: whether it has one."""
    at = jnp.arange(rows, dtype=jnp.int32)
    pair = jax.lax.dynamic_slice(route["order"], (lo,), (rows,))
    live = lo + at < route["n_held"]
    here = route["is_held"] & (route["row_of_pair"] >= lo) & (
        route["row_of_pair"] < lo + rows)
    count = here.astype(jnp.int32)
    before = jnp.cumsum(count) - count     # in token order, among these rows
    slot = jnp.where(live, before[pair], at)
    row_of_slot = jnp.zeros((rows,), jnp.int32).at[slot].set(
        at, unique_indices=True)
    token = pair // k
    ends = jnp.cumsum(route["group_sizes"])
    starts = ends - route["group_sizes"]
    return {"pair": pair, "token": token, "live": live,
            "group_sizes": (jnp.clip(ends, lo, lo + rows)
                            - jnp.clip(starts, lo, lo + rows)),
            "row_of_slot": row_of_slot,
            "token_of_slot": jnp.where(live, token[row_of_slot], -1),
            "first": jnp.minimum(before[::k], rows - 1),
            "has": jnp.any(here.reshape(-1, k), axis=1)}


def _gather_rows(x, ix):
    """`out[r] = x[token of sorted row r]`, zero in the rows of no held
    pair."""
    return jnp.where(ix["live"][:, None], x[ix["token"]], 0)


def _sum_into_tokens(z, w_row, ix, k: int):
    """`out[t]` = the sum of `w_row[r] * z[r]` over the sorted rows `r` that
    hold a pair of token `t` (`w_row` None: of `z[r]`), zero for a token
    with none; products and sums in float32, the result in `z`'s dtype.

    The rows are brought into token order, where a token's rows are
    neighbours and at most `k`; each row takes up the k - 1 rows after it
    that are the same token's, so a token's first row holds its sum:
    gathers and one elementwise pass, a fixed order of additions, where a
    scatter-add would serialise on the chip (PERF.md PR 32)."""
    rows = z.shape[0]
    f32 = jnp.promote_types(z.dtype, jnp.float32)
    token = jnp.pad(ix["token_of_slot"], (0, k - 1), constant_values=-1)
    live = token[:rows] >= 0
    by_token = jnp.pad(z[ix["row_of_slot"]], ((0, k - 1), (0, 0)))
    if w_row is not None:
        w_slot = jnp.pad(w_row[ix["row_of_slot"]], (0, k - 1)).astype(f32)
    total = 0
    for j in range(k):
        term = by_token[j:j + rows].astype(f32)
        if w_row is not None:
            term = term * w_slot[j:j + rows, None]
        same = live & (token[j:j + rows] == token[:rows])
        total = total + jnp.where(same[:, None], term, 0)
    return jnp.where(ix["has"][:, None], total.astype(z.dtype)[ix["first"]],
                     0)


def _no_gradient(ix):
    return jax.tree_util.tree_map(lambda _: None, ix)


# dispatch and combine with their gradients by hand: each one's is the
# other's gather or sum, where autodiff would scatter-add

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_of_tokens(x, ix, k: int):
    """Dispatch: the token of each sorted row."""
    return _gather_rows(x, ix)


_rows_of_tokens.defvjp(
    lambda x, ix, k: (_gather_rows(x, ix), ix),
    lambda k, ix, g: (_sum_into_tokens(g, None, ix, k), _no_gradient(ix)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _tokens_of_rows(ys, w_row, ix, k: int):
    """Combine: each token's sum of its rows `ys` [rows, .] weighted by
    `w_row` [rows]."""
    return _sum_into_tokens(ys, w_row, ix, k)


def _combine_bwd(k, res, g):
    ys, w_row, ix = res
    f32 = jnp.promote_types(ys.dtype, jnp.float32)
    g_rows = _gather_rows(g, ix).astype(f32)
    return ((g_rows * w_row.astype(f32)[:, None]).astype(ys.dtype),
            jnp.sum(g_rows * ys.astype(f32), axis=1).astype(w_row.dtype),
            _no_gradient(ix))


_tokens_of_rows.defvjp(
    lambda ys, w_row, ix, k: (_sum_into_tokens(ys, w_row, ix, k),
                              (ys, w_row, ix)),
    _combine_bwd)


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


def _lowering():
    """What `_grouped` reads of the kernel dispatcher while it is traced."""
    from deeplearning4j_tpu.ops.pallas import dispatch
    return (dispatch.dispatch_mode(), dispatch.get_tile("grouped_matmul"),
            dispatch.interpret_mode())


@functools.partial(jax.jit, static_argnames=("rows", "lowering"))
def _routed(x, w, w_gate, w_up, w_down, route, lo, rows: int, lowering):
    """The terms of the held pairs in the sorted rows `lo .. lo + rows`,
    summed into their tokens: the whole algorithm, on whichever rows.  `w`
    [T, k]: the pairs' weights in `x`'s dtype, zero where the expert is not
    held.  `w_gate` None: the experts are `relu2_mlp`'s.

    Under `jit`, so that a step traces and lowers it once for its forward
    rule, the blocks' recomputation and every expert block of its shapes
    (LFM2's four: 4.0 s to lower the step without, 2.8 s with; CPU, PR 32);
    `lowering` = `_lowering()` is in the key because the trace depends on
    it."""
    k = w.shape[1]
    with jax.named_scope("dispatch"):
        ix = _rows(route, lo, rows, k)
        xs = _rows_of_tokens(x, ix, k)
    with jax.named_scope("experts"):
        sizes = ix["group_sizes"]
        if w_gate is None:
            hidden = jnp.square(jax.nn.relu(_grouped(xs, w_up, sizes)))
        else:
            gate = _grouped(xs, w_gate, sizes)
            up = _grouped(xs, w_up, sizes)
            hidden = jax.nn.silu(gate) * up
        ys = _grouped(hidden, w_down, sizes)
    with jax.named_scope("combine"):
        # (the rows past the last pair name no pair: they read a zero)
        w_row = w.reshape(-1).at[ix["pair"]].get(
            unique_indices=True, mode="fill", fill_value=0)
        return _tokens_of_rows(ys, w_row, ix, k)


def _add(total, part):
    """`total + part` leaf by leaf, summed in float32, in `total`'s dtype."""
    def add(t, p):
        f32 = jnp.promote_types(t.dtype, jnp.float32)
        return (t.astype(f32) + p.astype(f32)).astype(t.dtype)
    return jax.tree_util.tree_map(add, total, part)


def _in_passes(one_pass, route, rows: int, total):
    """`total` plus `one_pass(lo)` for `lo` = 0, `rows`, ... while held
    pairs are left in the sorted rows from `lo` on: a trip count read on
    the device, one trip in a step whose held pairs fit `rows`."""
    def one_more(carry):
        i, total = carry
        return i + 1, _add(total, one_pass(i * rows))

    return jax.lax.while_loop(
        lambda carry: carry[0] * rows < route["n_held"], one_more,
        (jnp.int32(0), total))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _routed_in_passes(rows: int, operands, route):
    """`_routed` on the first `rows` sorted rows, and again on the next
    `rows` for as long as held pairs are left: one pass in a step whose held
    pairs fit the bound, `ceil(held pairs / rows)` in one whose do not.  No
    buffer is longer than `rows`, no pair is dropped, and the one compiled
    body serves every load.

    The gradient is a loop too, each trip the gradient of `_routed` on its
    rows: nothing but the operands and the index arrays is kept from the
    forward pass.  The passes' results add up in the operands' dtypes (a
    step of one pass adds its result to zero)."""
    lowering = _lowering()
    return _in_passes(
        lambda lo: _routed(*operands, route, lo, rows, lowering), route,
        rows, jnp.zeros_like(operands[0]))


def _passes_fwd(rows, operands, route):
    return _routed_in_passes(rows, operands, route), (operands, route)


def _passes_bwd(rows, res, g):
    operands, route = res
    lowering = _lowering()

    def gradient(lo):
        return jax.vjp(lambda *a: _routed(*a, route, lo, rows, lowering),
                       *operands)[1](g)

    return (_in_passes(gradient, route, rows,
                       tuple(None if a is None else jnp.zeros_like(a)
                             for a in operands)),
            _no_gradient(route))


_routed_in_passes.defvjp(_passes_fwd, _passes_bwd)


def routed_experts(x, chosen, weights, w_gate, w_up, w_down,
                   first_held: int, rows: int):
    """The held experts' part of `sum_k w_k E_chosen_k(x)`: x [T, H],
    `chosen`/`weights` [T, k] from `router` over all experts, the held
    experts' matrices `w_gate`/`w_up` [held, H, I] and `w_down`
    [held, I, H], which are experts `first_held .. first_held + held`
    (`w_gate` None: `relu2_mlp`'s experts).
    `rows` (static): the sorted-row buffers' length; a step that sends more
    pairs to the held experts takes further passes of as many rows.  Beside
    the result, whether this step did (int32, 0 or 1)."""
    t, k = chosen.shape
    n = t * k
    held = w_up.shape[0]
    rows = min(rows, n)
    with jax.named_scope("dispatch"):
        local = chosen - first_held
        is_held = (local >= 0) & (local < held)
        # sort the T*k pairs by expert; pairs of absent experts go last and
        # belong to no group
        key = jnp.where(is_held, local, held).reshape(n)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = expert_counts(key[:, None], held)
        # int32 arrays about T*k long: what `_routed` needs on any rows.
        # `order` runs on to a whole number of passes with pairs that do not
        # exist
        at = jnp.arange(-(-n // rows) * rows, dtype=jnp.int32)
        route = {
            "order": jnp.concatenate([order, at[n:]]),
            "row_of_pair": jnp.zeros((n,), jnp.int32).at[order].set(
                at[:n], unique_indices=True),
            "is_held": is_held.reshape(n),
            "group_sizes": group_sizes,
            "n_held": jnp.sum(group_sizes)}
        w = jnp.where(is_held, weights, 0.0).astype(x.dtype)
    operands = (x, w, w_gate, w_up, w_down)
    if rows == n:
        return (_routed(*operands, route, 0, n, _lowering()),
                jnp.zeros((), jnp.int32))
    return (_routed_in_passes(rows, operands, route),
            (route["n_held"] > rows).astype(jnp.int32))


def swiglu(x, w_gate, w_up, w_down):
    """`(silu(x W_gate) * x W_up) W_down` (Shazeer 2020, arXiv:2002.05202)."""
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def relu2_mlp(x, w_up, w_down):
    """`relu(x W_up)^2 W_down`: the squared ReLU (Primer, arXiv:2109.08668)
    of Nemotron-H's experts, not gated."""
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def expert_layer(x, p, bias, *, top_k: int, scale: float, first_held: int,
                 eps: float = 1e-20, score: str = "sigmoid"):
    """This chip's share of the layer for tokens `x` [T, H]; the step's
    count of tokens that chose each of the E experts ([E] int32, held or
    not: the router's load, which the bias update balances); and whether
    the step's held pairs were more than the routed part's row bound (int32,
    0 or 1: it then took more than one pass of that many rows).  Under
    `score="softmax"` a fourth result: the layer's `balance_loss`, over all
    E outputs of the router (a chip has them whole).

    `p`: `router` [H, E]; `w_gate`, `w_up` [held, H, I] and `w_down`
    [held, I, H]; `shared_gate`, `shared_up` [H, S] and `shared_down`
    [S, H], the shared experts side by side as one SwiGLU of their summed
    width — or none of the three, for a layer without shared experts.
    Without `w_gate` and `shared_gate` the experts are `relu2_mlp`'s.
    `bias` [E] is the router's selection bias, `eps` the router's.

    The routed part's buffers are the layer's largest arrays and its
    arithmetic the smallest: a caller short of memory recomputes the layer
    in the backward pass (`jax.checkpoint`) before anything else."""
    n_experts = p["router"].shape[1]
    rows = row_bound(x.shape[0] * top_k, p["w_up"].shape[0], n_experts)
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            chosen, weights, *mean_prob = router(
                x, p["router"], bias, top_k, scale, eps, score)
            counts = expert_counts(chosen, n_experts)
            balance = [balance_loss(counts, m, x.shape[0])
                       for m in mean_prob]
        routed, over = routed_experts(x, chosen, weights, p.get("w_gate"),
                                      p["w_up"], p["w_down"], first_held,
                                      rows)
        if "shared_up" not in p:
            return (routed, counts, over, *balance)
        with jax.named_scope("shared"):
            if "shared_gate" in p:
                shared = swiglu(x, p["shared_gate"], p["shared_up"],
                                p["shared_down"])
            else:
                shared = relu2_mlp(x, p["shared_up"], p["shared_down"])
        return (routed + shared, counts, over, *balance)
