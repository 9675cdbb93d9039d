"""Family `keye_vl2_moe`: the language model of configs whose `model_type` is
`KeyeVL2` (Kwai-Keye's Keye-VL-2.0-30B-A3B) — a Qwen3-MoE block (grouped-query
attention with an RMSNorm of every query and key head, a softmax router over
routed experts alone, no dense layer, an untied head) in which every
attention layer carries DeepSeek-V3.2's lightning indexer (`sa_config`;
DeepSeek-V3.2-Exp report, arXiv:2512.02556): a second, small attention whose
scores pick the `topk` keys each query's main heads may see — on the train
path, through the program's `zoo.DecoderModel`.

The layer, for the residual stream `x` [T, H] and `h = RMSNorm(x)`:

- main heads as Qwen3's: `q[t,a] = R_t(RMSNorm(W_q^a h_t))`, `k[s,b] =
  R_s(RMSNorm(W_k^b h_s))`, `v[s,b] = W_v^b h_s`, head `a` on key-value head
  `a // group`; `R` half-split rotary whose frequencies take their position
  from three streams by `mrope_section` (text: all three the token's index);
- indexer on `hd = stop_gradient(h)`: `qI[t,j] = RI_t(W_qI^j hd_t)`, `kI[s] =
  RI_s(LayerNorm(W_kI hd_s))` (ONE key head), `w[t] = W_w hd_t`; `I[t,s] =
  n^-1/2 d^-1/2 sum_j w[t,j] relu(qI[t,j] . kI[s])` for `s <= t`;
- `S_t` = the `min(t + 1, topk)` keys `s <= t` of largest `I[t,s]`, ties to
  the lower `s`, shared by all heads; attention is the softmax over `S_t`;
- then `y + Experts(RMSNorm(y))` as `sdar_moe`'s;
- loss: next-token cross-entropy + `router_aux_loss_coef` x the balance term
  + `index_loss_coef` x mean over layers of `(1/T) sum_t KL(pbar[t,.] ||
  softmax_{s in S_t} I[t,s])`, `pbar` the main heads' probabilities averaged
  over the heads, a constant: the indexer learns from that term alone and
  nothing differentiates through the choice of `S_t` (DeepSeek-V3.2's sparse
  training stage).  The vision tower is left out: the traffic is text.

Program side: `build` and the adapters the drivers call.  Yardstick side:
`flops_per_item` and the kernels' operation and byte counts (from shapes),
and `reference_forward` / `reference_loss` / `reference_selection` (plain
`jax.numpy`, float32, highest matmul precision; the index scores a [block,
T] matrix, `jax.lax.top_k` on it, a boolean mask scattered from its indices,
a masked softmax; a block of queries at a time so that 16,384 rows fit; no
kernels, no packed bits), which read the system's own parameter pytree and
follow the equations above and the config's keys, not the program's code.

A configuration may be one chip's share of an expert- and vocabulary-parallel
deployment (`num_experts` held of `num_experts_published`, the first
`vocab_size` ids, `num_layers` of the published `num_hidden_layers`): the
reference is given the same share and, like the program, leaves the absent
experts' terms out.

What every decoder family of this benchmark does alike is
`models/deepseek_v3.py`'s, what this block shares with SDAR's (its sizes, the
routed experts' work, the head) `models/sdar_moe.py`'s, and both are
imported, not copied; the state a run keeps
is this module's own, because `trace/scopes.py` and the readers find it by
the family's name.
"""
from __future__ import annotations

import numpy as np

from benchmark.models.deepseek_v3 import (  # noqa: F401  (the drivers' API)
    _schedule, _slice, items_per_row, last_loss, make_pool, parameters,
    rel_rms, step_hook)
# the Qwen3-MoE block's sizes, its routed experts' work and its head are
# `sdar_moe`'s, the same block
from benchmark.models.sdar_moe import (  # noqa: F401
    _dims, _itemsize, _layers, grouped_work, held_per_token, reference_head)

# the newest model `build` made: the per-layer readers find the program
# through the cell's family (`harness.load_family(run.cell.config)`)
LAST_BUILT = None
# the newest train step `lower_step` lowered: `trace/scopes.py` compiles it
# again — a cache hit — for the scope of each instruction in the trace
LAST_LOWERED = None
# the step's device counters at the start of the measured window
_AT_WINDOW_START = None


# ---------------------------------------------------------------------------
# shapes: required work
# ---------------------------------------------------------------------------

def _index_dims(config: dict):
    """(index heads, their width, keys a query keeps)."""
    sa = config["sa_config"]
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("the indexer's heads share ONE key head")
    return (int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]),
            int(sa["topk"]))


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def selected_pairs(config: dict, seq: int) -> int:
    """`sum_n min(n, topk)` over a sequence's `seq` queries: the (query,
    key) pairs the selection keeps, a head."""
    topk = min(_index_dims(config)[2], seq)
    return topk * (topk + 1) // 2 + (seq - topk) * topk


def layer_flops_per_sequence(config: dict, seq: int) -> dict:
    """Forward FLOPs one sequence of `seq` tokens requires of one layer, by
    part: attention over the SELECTED pairs, the index scores over the
    causal pairs, routed experts at the expected share of the chosen experts
    that is held."""
    h, nh, nkv, hd = _dims(config)
    n, d, _ = _index_dims(config)
    return {
        "gqa_products": seq * 2.0 * (h * (nh + 2 * nkv) * hd + nh * hd * h),
        "attention": 2.0 * nh * (hd + hd) * selected_pairs(config, seq),
        "index_products": seq * 2.0 * h * (n * d + d + n),
        "index_scores": 2.0 * n * d * causal_pairs(seq),
        "routed": seq * 2.0 * 3 * h * int(config["moe_intermediate_size"])
        * held_per_token(config),
        "router": seq * 2.0 * h * int(config["num_experts_published"])}


def index_loss_flops_per_sequence(config: dict, seq: int) -> float:
    """What the indexer's loss requires of one layer beyond the scores:
    the main heads' scores once more on the selected pairs (their
    probabilities summed over the heads) and the two gradients of the index
    scores there (queries, key); the weights' gradient rides on the scores."""
    _, nh, _, hd = _dims(config)
    n, d, _ = _index_dims(config)
    return selected_pairs(config, seq) * (2.0 * nh * hd + 2 * 2.0 * n * d)


def flops_per_item(config: dict, traffic: dict, training: bool = True) -> float:
    """FLOPs one sequence requires: every held layer, attention over the
    selected pairs, the index scores over the causal pairs, the head over
    the vocabulary held; no recomputation.  Training is 3x the forward of
    everything the next-token loss differentiates, and for the indexer —
    which the selection does not differentiate — its forward, its loss's
    pass and that loss's gradients.  Lookups, norms, rotary, softmax, both
    top-k, sorting and the updater are not counted: the roofline it is set
    against is the MXU's."""
    seq = int(traffic["seq_len"])
    layers = int(config["num_layers"])
    part = layer_flops_per_sequence(config, seq)
    products, scores = part.pop("index_products"), part.pop("index_scores")
    fwd = layers * sum(part.values()) + seq * 2.0 * int(
        config["hidden_size"]) * int(config["vocab_size"])
    if not training:
        return fwd + layers * (products + scores)
    # the indexer's input is detached: its projections have weight
    # gradients and no input gradient
    return 3.0 * fwd + layers * (
        2 * products + scores + index_loss_flops_per_sequence(config, seq))


def gqa_attention_work(config: dict, traffic: dict, rows: int) -> dict:
    """What grouped-query attention over the selection requires of one train
    step of `rows` sequences over the held layers: `flops` (two products
    forward — scores, values — and four backward — dV, dP, dQ, dK — each over
    the SELECTED pairs, 31,458,304 a head at 16,384 tokens and 2,048 keys,
    for every QUERY head) and `bytes` in the compute dtype: q, o, dO and dQ
    once a query head — q and o forward; q, o, dO in and dQ out backward —
    and k, v, dK and dV once a KEY-VALUE head.  The same work whatever
    implements it: kernels that execute every causal tile under a mask are
    charged for the pairs the selection dropped."""
    _, nh, nkv, hd = _dims(config)
    seq = int(traffic["seq_len"])
    layers = int(config["num_layers"])
    flops = 2.0 * selected_pairs(config, seq) * (
        (hd + hd) + 2 * (hd + hd)) * nh
    elements = seq * hd * (nh * (2 + 4) + nkv * (2 + 4))
    return {"flops": flops * rows * layers,
            "bytes": float(elements * _itemsize(config) * rows * layers)}


def index_work(config: dict, traffic: dict, rows: int) -> dict:
    """What the indexer requires of one train step of `rows` sequences over
    the held layers, the selection and its loss: `flops` — the index scores
    forward, `2 d n` a CAUSAL pair; the loss's pass (the main heads' scores
    on the selected pairs) and the scores' two gradients there — and `bytes`:
    the indexer's queries, key and weights read by the scores and by the
    loss and their gradients written (three passes each), the main heads'
    queries and keys read by the loss, and the selection at one bit a pair,
    written in its two packings and read by the forward kernel, the backward
    kernel and the loss.  Top-k, the softmaxes and the recomputed scores are
    time and no work."""
    _, nh, nkv, hd = _dims(config)
    n, d, _ = _index_dims(config)
    seq = int(traffic["seq_len"])
    layers = int(config["num_layers"])
    flops = (2.0 * n * d * causal_pairs(seq)
             + index_loss_flops_per_sequence(config, seq))
    item = _itemsize(config)
    bytes_ = (3 * seq * (n * d + d) * item + 3 * seq * n * 4
              + seq * (nh + nkv) * hd * item + 5 * seq * seq / 8)
    return {"flops": flops * rows * layers,
            "bytes": float(bytes_ * rows * layers)}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def decoder_config(config: dict):
    from benchmark.harness import BenchmarkError
    from deeplearning4j_tpu.zoo import DecoderConfig
    from deeplearning4j_tpu.zoo import decoder
    if "sparse_attention" not in decoder.LAYER_KINDS:
        raise BenchmarkError(
            "this program's zoo.DecoderModel has no `sparse_attention` "
            "layer: it cannot run this configuration")
    layers = int(config["num_layers"])
    n, d, topk = _index_dims(config)
    return DecoderConfig(
        vocab_size=int(config["vocab_size"]),
        hidden=int(config["hidden_size"]),
        n_layers=layers,
        n_dense_layers=len(config["mlp_only_layers"]),
        layer_types=("sparse_attention",) * layers,
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        expert_intermediate=int(config["moe_intermediate_size"]),
        n_experts=int(config["num_experts_published"]),
        n_shared_experts=0,
        top_k=int(config["num_experts_per_tok"]),
        routed_scale=1.0,
        router_eps=0.0,
        router_score="softmax",
        aux_loss_coef=float(config["router_aux_loss_coef"]),
        first_expert=int(config["first_expert_held"]),
        n_experts_held=int(config["num_experts"]),
        rope_base=float(config["rope_theta"]),
        rope_sections=tuple(config["rope_scaling"]["mrope_section"]),
        eps=float(config["rms_norm_eps"]),
        init_std=float(config["init_std"]),
        embedding_init_std=float(config["embedding_init_std"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        compute_dtype=config["compute_dtype"],
        index_heads=n, index_head_dim=d, index_topk=topk,
        index_loss_coef=float(config["index_loss_coef"]))


def build(config: dict, seed: int, serving: bool = False):
    """`zoo.DecoderModel` with the file's sizes and share, parameters
    initialised on the device from `seed`."""
    global LAST_BUILT, LAST_LOWERED, _AT_WINDOW_START
    from deeplearning4j_tpu.train import updaters
    from deeplearning4j_tpu.zoo import DecoderModel
    u = config["updater"]
    LAST_BUILT = DecoderModel(
        decoder_config(config), seed=int(seed),
        updater=getattr(updaters, u["kind"])(
            *[_schedule(a) for a in u["args"]], **u.get("kwargs", {})))
    LAST_LOWERED = _AT_WINDOW_START = None
    return LAST_BUILT


def next_token_ce(logits, labels) -> float:
    """Mean next-token cross-entropy of float32 `logits` [rows, T, vocab]
    over every position but the last, on the device they lie on."""
    import jax
    import jax.numpy as jnp
    logits = jnp.asarray(logits, jnp.float32)[:, :-1]
    picked = jnp.take_along_axis(
        logits, jnp.asarray(labels, jnp.int32)[:, :-1, None], -1)[..., 0]
    return float(jnp.mean(jax.nn.logsumexp(logits, -1) - picked))


def eval_loss(model, batch, rows: int) -> float:
    """Next-token loss of the system's `output` on the batch's first `rows`
    sequences.  The driver calls it right before the measured window and
    right after: the first call also notes where the step's counters stood
    (copies on the device — the step donates its state; nothing is
    transferred)."""
    global _AT_WINDOW_START
    import jax.numpy as jnp
    if _AT_WINDOW_START is None:
        _AT_WINDOW_START = {name: jnp.copy(model.state_[name])
                            for name in ("expert_load", "selected_keys")}
    ids, labels = _slice(batch, rows)
    return next_token_ce(model.output(ids), labels)


def _since_window_start(model, name: str) -> np.ndarray:
    """A device counter of the step's state, now less the window's start:
    both ends read in one transfer."""
    import jax
    now = model.state_[name]
    if _AT_WINDOW_START is None:
        return np.asarray(now)
    start, now = jax.device_get((_AT_WINDOW_START[name], now))
    return now - start


def window_expert_load(model) -> np.ndarray:
    """[expert layers, router width] rows that chose each expert between
    the start of the measured window and now."""
    return _since_window_start(model, "expert_load")


def window_held_load(model) -> np.ndarray:
    """`window_expert_load` of the experts held here: [expert layers, held]
    (row, held expert) pairs, the rows the grouped products ran on."""
    c = model.config
    return window_expert_load(model)[
        :, c.first_expert:c.first_expert + c.held]


def window_selected_keys(model) -> float:
    """(query, key) pairs the indexers kept, all layers, between the start
    of the measured window and now."""
    return float(_since_window_start(model, "selected_keys").sum())


def selection_agreement(system, reference) -> float:
    """The share of the reference's selected pairs (bool [rows, T, T]) that
    the system selected too."""
    import jax.numpy as jnp
    reference = jnp.asarray(reference)
    return float(jnp.sum(reference & jnp.asarray(system), dtype=jnp.float32)
                 / jnp.sum(reference, dtype=jnp.float32))


def reference_check(model, config: dict, batch, rows: int) -> dict:
    """The system's logits on `rows` sequences against `reference_forward`
    on the same parameters, on all rows and on the rows from `topk` on,
    where the selection binds, and the first layer's selection (both sides
    see the embedding there) against `reference_selection`.  `rel_err` is
    the larger of the two root mean squares of the difference over the
    reference's root mean square (the config's `tolerance.why` says why) —
    or infinite where the selections agree on less than
    `tolerance.select_agree` of the reference's pairs: the driver holds
    `rel_err` to `tolerance.output_rel`."""
    from benchmark.harness import say
    ids, labels = _slice(batch, rows)
    topk = _index_dims(config)[2]
    got = np.asarray(model.output(ids), np.float32)
    want = np.asarray(reference_jitted(config, model.params_, ids),
                      np.float32)
    rel = {"all rows": rel_rms(got, want)}
    if ids.shape[1] > topk:
        rel[f"rows {topk}.."] = rel_rms(got[:, topk:], want[:, topk:])
    agree = selection_agreement(
        model.selection(ids), reference_selection(config, model.params_, ids))
    least = float(config["tolerance"]["select_agree"])
    say("reference: logits " + ", ".join(
        f"{k} {v:.3e}" for k, v in rel.items())
        + f"; the first layer's selections agree on {agree:.5f} of the "
        f"reference's pairs (least {least})")
    return {"rel_err": max(rel.values()) if agree >= least else float("inf"),
            "tol": float(config["tolerance"]["output_rel"]),
            "loss": next_token_ce(got, labels),
            "loss_reference": next_token_ce(want, labels),
            "loss_tol": float(config["tolerance"]["loss_rel"])}


def reference_jitted(config: dict, params, ids, round_to=None):
    """`reference_forward` with the block and the head under `jax.jit`: the
    layers, all alike, share one compilation."""
    import functools
    import jax
    block = jax.jit(functools.partial(reference_block, config),
                    static_argnames=("round_to",))
    head = jax.jit(functools.partial(reference_head, config),
                   static_argnames=("round_to",))
    return reference_forward(
        config, params, ids, round_to,
        block=lambda _, x, lp, r: block(x, lp, round_to=r),
        head=lambda _, x, g, w, r: head(x, g, w, round_to=r))


def lower_step(model, batch):
    """The train step as `fit_batch` runs it, lowered for the same
    arguments, for counting the Mosaic calls the kernel dispatcher put in
    it."""
    global LAST_LOWERED
    import jax.numpy as jnp
    from deeplearning4j_tpu.utils.counters import device_counters
    it, ep = device_counters(model)
    LAST_LOWERED = model._step().lower(
        model.params_, model.opt_state_, model.state_, it, ep,
        jnp.asarray(batch.features[0], jnp.int32),
        jnp.asarray(batch.labels[0], jnp.int32))
    return LAST_LOWERED


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

QUERY_BLOCK = 256       # queries whose scores are held at a time


def _rounded(round_to, *vs):
    """`vs` (float32) rounded to the dtype `round_to` and back; themselves
    without one."""
    import jax.numpy as jnp
    if round_to is None:
        return vs
    return tuple(v.astype(round_to).astype(jnp.float32) for v in vs)


def _rms(v, g, eps: float):
    import jax.numpy as jnp
    return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * g


def _rope(v, pos, base: float, sections=None):
    """v [rows, T, heads, d]: pair (v[i], v[i + d/2]) turned by `p *
    base^(-2i/d)`, i in 0 .. d/2 - 1, `p` the token's position — or, with
    `sections` (frequencies a stream, `pos` [streams, T]), its position in
    the stream whose section holds `i`, the sections end to end."""
    import jax.numpy as jnp
    half = v.shape[-1] // 2
    inv = base ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / v.shape[-1])
    if sections is not None:
        stream = np.repeat(np.arange(len(sections)), sections)  # [d/2]
        pos = pos[stream].T                                     # [T, d/2]
    else:
        pos = pos[:, None]
    ang = pos.astype(jnp.float32) * inv[None]                   # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    lo, hi = v[..., :half], v[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], -1)


def reference_attention(config: dict, u, lp, round_to=None,
                        approx_recall=None):
    """The layer's attention on the normed `u` [rows, T, H] (float32):
    `(heads' outputs [rows, T, nh hd] before W_o, the indexer's loss, the
    selection bool [rows, T, T])`.  Queries in blocks of `QUERY_BLOCK`: a
    block's index scores are a [block, T] matrix, `jax.lax.top_k` picks its
    `topk` largest a row (the causal ones alone are finite; equal scores go
    to the lower key), and the picks are scattered into a boolean mask.
    `approx_recall`: `jax.lax.approx_max_k` at that recall instead — ANOTHER
    model, which the selection's tolerance has to refuse."""
    import jax
    import jax.numpy as jnp

    h, nh, nkv, hd = _dims(config)
    n, d, topk = _index_dims(config)
    base = float(config["rope_theta"])
    sections = tuple(config["rope_scaling"]["mrope_section"])
    eps = float(config["rms_norm_eps"])
    b, t, _ = u.shape
    keep_k, blk = min(topk, t), min(QUERY_BLOCK, t)
    if t % blk:
        raise ValueError(f"{t} tokens are no whole blocks of {blk}")

    def mm(a, w):
        a, w = _rounded(round_to, a, w)
        return a @ w

    pos = jnp.arange(t)
    w_qkv = lp["Wqkv"]              # [H, (nh + 2 nkv) hd]: W_q | W_k | W_v
    q = mm(u, w_qkv[:, :nh * hd]).reshape(b, t, nh, hd)
    k = mm(u, w_qkv[:, nh * hd:(nh + nkv) * hd]).reshape(b, t, nkv, hd)
    v = mm(u, w_qkv[:, (nh + nkv) * hd:]).reshape(b, t, nkv, hd)
    streams = jnp.stack([pos] * len(sections))     # text: the streams agree
    q = _rope(_rms(q, lp["q_norm"], eps), streams, base, sections)
    k = _rope(_rms(k, lp["k_norm"], eps), streams, base, sections)
    q, k, v = _rounded(round_to, q, k, v)
    of_head = jnp.arange(nh) // (nh // nkv)
    k, v = k[:, :, of_head], v[:, :, of_head]

    ud = jax.lax.stop_gradient(u)
    q_i = _rope(mm(ud, lp["Wq_idx"]).reshape(b, t, n, d), pos, base)
    k_i = mm(ud, lp["Wk_idx"])
    mean = jnp.mean(k_i, -1, keepdims=True)
    var = jnp.mean((k_i - mean) ** 2, -1, keepdims=True)
    k_i = (k_i - mean) / jnp.sqrt(var + eps) * lp["k_idx_gain"] \
        + lp["k_idx_bias"]
    k_i = _rope(k_i[:, :, None], pos, base)[:, :, 0]
    w_i = mm(ud, lp["Ww_idx"])
    q_i, k_i = _rounded(round_to, q_i, k_i)

    def block(q0):
        rows = q0 + jnp.arange(blk)
        causal = jnp.arange(t)[None, :] <= rows[:, None]        # [Q, T]
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, q0, blk, 1)
        dots = jnp.einsum("bqnd,bkd->bqnk", take(q_i), k_i)
        scores = (n ** -0.5 * d ** -0.5) * jnp.sum(
            take(w_i)[..., None] * jnp.maximum(dots, 0.0), axis=2)
        scores = jnp.where(causal, scores, -jnp.inf)            # [b, Q, T]
        if approx_recall is None:
            _, picks = jax.lax.top_k(scores, keep_k)
        else:
            _, picks = jax.lax.approx_max_k(scores, keep_k,
                                            recall_target=approx_recall)
        keep = jnp.zeros(scores.shape, bool).at[
            jnp.arange(b)[:, None, None],
            jnp.arange(blk)[None, :, None], picks].set(True) & causal
        s = jnp.einsum("bqhd,bkhd->bhqk", take(q), k) / np.sqrt(hd)
        alpha = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), -1)
        out = jnp.einsum("bhqk,bkhd->bqhd", alpha, v)
        pbar = jax.lax.stop_gradient(jnp.mean(alpha, axis=1))   # [b, Q, T]
        log_pi = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(
            pbar > 0, pbar * (jnp.log(jnp.where(pbar > 0, pbar, 1.0))
                              - jnp.where(keep, log_pi, 0.0)), 0.0))
        return out, kl, keep

    outs, kls, keeps = jax.lax.map(block, jnp.arange(0, t, blk))
    join = lambda a: jnp.moveaxis(a, 0, 1).reshape(b, t, *a.shape[3:])
    return (join(outs).reshape(b, t, nh * hd), jnp.sum(kls) / (b * t),
            join(keeps))


def reference_block(config: dict, x, lp, round_to=None):
    """One block on `x` [rows, T, H] (float32): `h = x + Wo Attn(q, k, v |
    S)`, `y = h + MoE(RMSNorm(h))`; beside `y` the layer's balance term `E
    sum_e f_e P_e` and the indexer's loss.  See `reference_forward`."""
    import jax
    import jax.numpy as jnp

    eps = float(config["rms_norm_eps"])
    top_k = int(config["num_experts_per_tok"])
    first = int(config["first_expert_held"])

    def mm(a, b):
        a, b = _rounded(round_to, a, b)
        return a @ b

    def silu(v):
        return v / (1.0 + jnp.exp(-v))

    def ffn(u, wg, wu, wd):
        return mm(silu(mm(u, wg)) * mm(u, wu), wd)

    def moe(u):
        p = jax.nn.softmax(u @ lp["router"], -1)        # [rows, T, E]
        top, chosen = jax.lax.top_k(p, top_k)
        w = top / jnp.sum(top, -1, keepdims=True)       # norm_topk_prob
        y = 0.0
        for e in range(lp["w_gate"].shape[0]):          # held experts
            w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
            y = y + w_e[..., None] * ffn(u, lp["w_gate"][e], lp["w_up"][e],
                                         lp["w_down"][e])
        n_experts = p.shape[-1]
        hit = chosen[..., None] == jnp.arange(n_experts)
        f = jnp.sum(hit, axis=(0, 1, 2)) / (p.shape[0] * p.shape[1])
        return y, n_experts * jnp.sum(f * jnp.mean(p, axis=(0, 1)))

    with jax.default_matmul_precision("highest"):
        heads, index_kl, _ = reference_attention(
            config, _rms(x, lp["norm1"], eps), lp, round_to)
        x = x + mm(heads, lp["Wo"])
        y, balance = moe(_rms(x, lp["norm2"], eps))
        return x + y, balance, index_kl


def _float32(params):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                  params)


def _hidden(config, params, ids, round_to, block):
    """The residual stream after the last block, float32, and each layer's
    balance term and indexer loss."""
    import jax.numpy as jnp
    p = _float32(params)
    x = p["tok_emb"][jnp.asarray(ids, jnp.int32)]
    balance, index_kl = [], []
    for lp in _layers(p):
        x, b, kl = block(config, x, lp, round_to)
        balance.append(b)
        index_kl.append(kl)
    return p, x, balance, index_kl


def reference_forward(config: dict, params, ids, round_to=None,
                      block=reference_block, head=reference_head):
    """Logits [rows, T, vocab held] in float32 at highest matmul precision:
    embedding lookup, no position embedding, the held layers (module
    docstring; of the chosen experts only those held are summed), RMSNorm
    and the untied head.

    `round_to` (a dtype) rounds both operands of every matrix product to it
    first — q, k, v and the indexer's queries and key before their scores —
    (the router's stays float32, as the configuration states): the reference
    "computed in a lower precision", which the tolerances have to refuse.
    `block`/`head`: the same two functions wrapped, e.g. in `jax.jit`."""
    p, x, _, _ = _hidden(config, params, ids, round_to, block)
    return head(config, x, p["final_norm"], p["head"], round_to)


def reference_selection(config: dict, params, ids, round_to=None,
                        approx_recall=None):
    """bool [rows, T, T]: the keys each query keeps in the FIRST layer, whose
    input is the embedding on both sides.  `round_to`, `approx_recall`:
    `reference_attention`'s."""
    import functools
    import jax
    import jax.numpy as jnp
    p = _float32(params)
    lp = _layers(p)[0]
    eps = float(config["rms_norm_eps"])

    @functools.partial(jax.jit, static_argnames=("round_to", "recall"))
    def first(x, lp, round_to, recall):
        with jax.default_matmul_precision("highest"):
            return reference_attention(config, _rms(x, lp["norm1"], eps), lp,
                                       round_to, recall)[2]

    return first(p["tok_emb"][jnp.asarray(ids, jnp.int32)], lp,
                 round_to=round_to, recall=approx_recall)


def reference_loss(config: dict, params, ids, labels, terms=None):
    """The step's loss: mean next-token cross-entropy over every position
    but the last, plus `router_aux_loss_coef` times the layers' mean balance
    term, plus `index_loss_coef` times their mean indexer loss; `jax.grad`
    of it is the reference's gradient.  `terms`: which of `("next_token",
    "balance", "index")` to sum (all by default)."""
    import jax
    import jax.numpy as jnp
    p, x, balance, index_kl = _hidden(config, params, ids, None,
                                      reference_block)
    logits = reference_head(config, x, p["final_norm"], p["head"])
    labels = jnp.asarray(labels, jnp.int32)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               labels[..., None], -1)[..., 0]
    parts = {
        "next_token": jnp.mean(nll[:, :-1]),
        "balance": float(config["router_aux_loss_coef"])
        * jnp.mean(jnp.stack(balance)),
        "index": float(config["index_loss_coef"])
        * jnp.mean(jnp.stack(index_kl))}
    return sum(parts[name] for name in (terms or parts))
