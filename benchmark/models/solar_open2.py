"""Family `solar_open2`: decoder-only language models whose public config says
`model_type: solar_open2` (upstage's Solar Open 2) — Kimi delta attention
(KDA: a gated delta rule with a decay a channel, negative eigenvalues; Kimi
Linear, arXiv:2510.26692) in 3 of every 4 layers beside grouped-query softmax
attention with no positions and a sigmoid output gate (`gqa_layers`), and in
every layer a sigmoid router over routed experts with a selection bias beside
one shared expert — on the train path, through the program's
`zoo.DecoderModel`.

The layers, for the residual stream `x` [T, H] and `h = RMSNorm(x)`:

- KDA, a head of dk = dv = 128: `q, k, v = SiLU(conv4(h W_{q,k,v}))` (a
  causal depthwise convolution of 4 taps, no bias), q and k L2-normed; `g_t
  = -exp(A_log) softplus(h_t W_fa W_fb + dt_bias)` [dk]; `beta_t = 2
  sigmoid(h_t w_beta)`; `S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
  + beta_t k_t v_t^T`, `o_t = dk^-1/2 S_t^T q_t`; `y = [RMSNorm_head(o) *
  sigmoid(h W_ga W_gb)] W_o`;
- GQA: `SDPA(h W_q, h W_k, h W_v)` causal, no rotary, no norm of q or k, times
  `sigmoid(h W_g)` elementwise, then `W_o`;
- then `y + MoE(RMSNorm(y))`: the 8 largest of sigmoid score + bias over all
  routed experts, the chosen scores normalised (times 1), plus the shared
  SwiGLU expert.

Program side: `build` and the adapters the drivers call.  Yardstick side:
`flops_per_item`, the kernels' operation and byte counts (from shapes), and
`reference_forward` / `reference_loss` (plain `jax.numpy`, float32, highest
matmul precision; KDA as its TOKEN-BY-TOKEN recurrence, `lax.scan` over t
with the state [heads, 128, 128]; GQA a masked softmax a block of queries at
a time; the experts a Python loop; no chunks, no kernels), which read the
system's own parameter pytree and follow the equations above and the
config's keys, not the program's code.

A configuration is one chip's share of a deployment (`deployment`): the
routed experts `first_expert_held ..` + `n_routed_experts` of
`n_routed_experts_published`, the heads `first_head_held ..` +
`num_heads_held` of the KDA heads and of the query heads (their key-value
heads with them), the first `vocab_size` ids, `num_layers` of the published
layers from `first_layer_held` on.  The reference is given the same share
and, like the program, computes the held experts' and the held heads' parts
and leaves the other chips' terms out.

What every decoder family of this benchmark does alike (Zipf ids, the pool,
the loss of a set of logits, the relative rms, the untied head) is
`models/deepseek_v3.py`'s and is imported; the state a run keeps is this
module's own, because `trace/scopes.py` and the readers find it by the
family's name.
"""
from __future__ import annotations

import numpy as np

from benchmark.models.deepseek_v3 import (  # noqa: F401  (the drivers' API)
    _next_token_ce, _schedule, _slice, items_per_row, last_loss, make_pool,
    parameters, reference_head, rel_rms, step_hook)

# the newest model `build` made: the per-layer readers find the program
# through the cell's family (`harness.load_family(run.cell.config)`)
LAST_BUILT = None
# the newest train step `lower_step` lowered: `trace/scopes.py` compiles it
# again — a cache hit — for the scope of each instruction in the trace
LAST_LOWERED = None
# the step's device counters at the start of the measured window
_AT_WINDOW_START = None

REFERENCE_CHUNK = 64    # `delta_rule_work` counts the chunkwise algorithm at


# ---------------------------------------------------------------------------
# shapes: required work
# ---------------------------------------------------------------------------

def held_layer_types(config: dict) -> list:
    """The kinds of the layers held here, in order: `num_layers` of the
    published layers from `first_layer_held` on, `full_attention` where
    `gqa_layers` names the layer, `linear_attention` elsewhere."""
    first = int(config["first_layer_held"])
    gqa = set(config["gqa_layers"])
    return ["full_attention" if i in gqa else "linear_attention"
            for i in range(first, first + int(config["num_layers"]))]


def _dims(config: dict):
    """(hidden, held query/KDA heads, held key-value heads, head width)."""
    nh, nkv = (int(config["num_attention_heads"]),
               int(config["num_key_value_heads"]))
    held = int(config["num_heads_held"])
    hd = int(config["head_dim"])
    lin = config["linear_attn_config"]
    if (int(lin["head_dim"]), int(lin["num_heads"])) != (hd, nh):
        raise ValueError("the KDA heads are the query heads' count and width")
    return int(config["hidden_size"]), held, held * nkv // nh, hd


def _itemsize(config: dict) -> int:
    return 4 if config["compute_dtype"] == "float32" else 2


def held_per_token(config: dict) -> float:
    """Routed experts a token needs of those held here, in expectation
    under even routing: top-k x held / router width."""
    return (int(config["num_experts_per_tok"]) * int(config["n_routed_experts"])
            / int(config["n_routed_experts_published"]))


def delta_rule_chunk_flops(config: dict) -> float:
    """Forward products of the chunkwise delta rule for ONE chunk of
    `REFERENCE_CHUNK` tokens of one head: A_kk over the strictly lower
    pairs and A_qk over the lower pairs (dk each), the forward substitution
    on [V | K] (dk + dv a strictly lower pair), the state's three products
    with the chunk (w S, q S, K^T V_new: dk dv a token each) and A_qk V_new
    (dv a lower pair); two FLOPs a multiply-add."""
    hd, C = int(config["head_dim"]), REFERENCE_CHUNK
    strict, lower = C * (C - 1) // 2, C * (C + 1) // 2
    return 2.0 * (strict * hd + lower * hd + strict * (hd + hd)
                  + 3 * C * hd * hd + lower * hd)


def layer_flops_per_token(config: dict, seq: int, kind: str) -> dict:
    """Forward FLOPs one token of a `seq`-token sequence requires of one
    held layer of `kind`, by part.  Causal attention is the lower triangle;
    the delta rule at the reference chunk; routed experts at the expected
    share of the chosen experts that is held."""
    h, n, nkv, hd = _dims(config)
    ie = int(config["moe_intermediate_size"])
    if kind == "full_attention":
        parts = {"gqa_products": 2.0 * (h * (n + 2 * nkv) * hd
                                        + 2 * n * hd * h),
                 "attention": 2.0 * n * (hd + hd) * (seq + 1) / 2.0}
    else:
        w = n * hd
        parts = {"kda_products": 2.0 * (h * 3 * w + 2 * (h * hd + hd * w)
                                        + h * n + w * h),
                 "delta_rule": n * delta_rule_chunk_flops(config)
                 / REFERENCE_CHUNK}
    parts["shared"] = 2.0 * 3 * h * ie * int(config["n_shared_experts"])
    parts["routed"] = 2.0 * 3 * h * ie * held_per_token(config)
    parts["router"] = 2.0 * h * int(config["n_routed_experts_published"])
    return parts


def flops_per_item(config: dict, traffic: dict, training: bool = True) -> float:
    """FLOPs one sequence requires: every held layer's products, causal
    attention as the lower triangle, the delta rule's chunkwise products,
    routed experts at the expected share held, the untied head over the
    vocabulary held; no recomputation.  Training is 3x the forward.
    Lookups, norms, the convolution's taps, gates, softmax, top-k, sorting
    and the updater are not counted: the roofline it is set against is the
    MXU's."""
    seq = int(traffic["seq_len"])
    per_token = 2.0 * int(config["hidden_size"]) * int(config["vocab_size"])
    for kind in held_layer_types(config):
        per_token += sum(layer_flops_per_token(config, seq, kind).values())
    return (3.0 if training else 1.0) * seq * per_token


def delta_rule_work(config: dict, traffic: dict, rows: int) -> dict:
    """What the delta rule requires of one train step of `rows` sequences
    over the held KDA layers and heads: `flops`, the chunkwise algorithm's
    products at `REFERENCE_CHUNK` tokens a chunk (`delta_rule_chunk_flops`)
    forward and twice that backward, for every held head and chunk; and
    `bytes` in float32 (the rule's dtype): q, k, v, g and beta read and o
    written once forward; q, k, v, g, beta and dO read and dq, dk, dv, dg and
    dbeta written once backward.  The same work whatever implements it, at
    whatever chunk or layout."""
    _, n, _, hd = _dims(config)
    seq = int(traffic["seq_len"])
    layers = held_layer_types(config).count("linear_attention")
    chunks = -(-seq // REFERENCE_CHUNK)
    flops = 3 * chunks * n * delta_rule_chunk_flops(config)
    tokens = seq * n
    elements = (5 * hd + 1) * tokens + (5 * hd + 1) * tokens \
        + (4 * hd + 1) * tokens
    return {"flops": flops * rows * layers,
            "bytes": 4.0 * elements * rows * layers}


def gqa_attention_work(config: dict, traffic: dict, rows: int) -> dict:
    """What causal grouped-query attention requires of one train step of
    `rows` sequences over the held `full_attention` layers and heads: two
    products forward and four backward over the lower triangle for every
    held QUERY head; q, o, dO and dQ moved once a query head, k, v, dK and dV
    once a held KEY-VALUE head, in the compute dtype (`models/lfm2_moe.py`'s
    rule)."""
    _, nh, nkv, hd = _dims(config)
    seq = int(traffic["seq_len"])
    layers = held_layer_types(config).count("full_attention")
    pairs = seq * (seq + 1) / 2.0
    flops = 2.0 * pairs * ((hd + hd) + 2 * (hd + hd)) * nh
    elements = seq * hd * (nh * (2 + 4) + nkv * (2 + 4))
    return {"flops": flops * rows * layers,
            "bytes": float(elements * _itemsize(config) * rows * layers)}


def grouped_work(config: dict, pairs: float, layer_steps: float = 1) -> dict:
    """What the routed experts' grouped products require for `pairs`
    (token, held expert) rows in all, spread over `layer_steps` runs of an
    expert layer (layers x steps), forward and backward: three products
    forward and six backward; the bytes of each product's row operand and
    result once, and of each held expert's matrix once a product and run."""
    h, ie = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    weights = int(config["n_routed_experts"]) * h * ie * layer_steps
    return {"flops": 9 * 2.0 * pairs * h * ie,
            "bytes": 9.0 * _itemsize(config) * (pairs * (h + ie) + weights)}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def decoder_config(config: dict):
    from benchmark.harness import BenchmarkError
    from deeplearning4j_tpu.zoo import DecoderConfig
    from deeplearning4j_tpu.zoo import decoder
    if "linear_attention" not in decoder.LAYER_KINDS:
        raise BenchmarkError(
            "this program's zoo.DecoderModel has no `linear_attention` "
            "layer: it cannot run this configuration")
    if config["use_rope"] or int(config["first_k_dense_replace"]) \
            or not config["kda_allow_neg_eigval"]:
        raise ValueError("the program's layers have no rotary, no dense MLP, "
                         "and KDA's beta in (0, 2)")
    _dims(config)
    return DecoderConfig(
        vocab_size=int(config["vocab_size"]),
        hidden=int(config["hidden_size"]),
        n_layers=int(config["num_layers"]),
        n_dense_layers=0,
        layer_types=tuple(held_layer_types(config)),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        expert_intermediate=int(config["moe_intermediate_size"]),
        n_experts=int(config["n_routed_experts_published"]),
        n_shared_experts=int(config["n_shared_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        routed_scale=float(config["routed_scaling_factor"]),
        router_eps=float(config["router_eps"]),
        first_expert=int(config["first_expert_held"]),
        n_experts_held=int(config["n_routed_experts"]),
        eps=float(config["rms_norm_eps"]),
        bias_update_speed=float(config["bias_update_speed"]),
        init_std=float(config["init_std"]),
        embedding_init_std=float(config["embedding_init_std"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        compute_dtype=config["compute_dtype"],
        rope=False, qk_norm=False,
        attn_output_gate=bool(config["use_gqa_gate"]),
        first_head=int(config["first_head_held"]),
        n_heads_held=int(config["num_heads_held"]),
        conv_kernel=int(
            config["linear_attn_config"]["short_conv_kernel_size"]))


def build(config: dict, seed: int, serving: bool = False):
    """`zoo.DecoderModel` with the file's sizes, layer list and share,
    parameters initialised on the device from `seed`."""
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
                            for name in ("expert_load", "delta_rule_updates")}
    ids, labels = _slice(batch, rows)
    return _next_token_ce(model.output(ids), labels)


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


def window_delta_rule_updates(model) -> float:
    """(token, held head) pairs whose state update the KDA layers ran, all
    layers, between the start of the measured window and now."""
    return float(_since_window_start(model, "delta_rule_updates").sum())


def reference_check(model, config: dict, batch, rows: int) -> dict:
    """The system's logits on `rows` sequences against `reference_forward`
    on the same parameters and router bias, all rows.  `rel_err` is the root
    mean square of the difference over all logits over the root mean square
    of the reference's logits (the config's `tolerance.why` says why)."""
    from benchmark.harness import say
    ids, labels = _slice(batch, rows)
    got = np.asarray(model.output(ids), np.float32)
    want = np.asarray(reference_jitted(
        config, model.params_, model.state_["router_bias"], ids), np.float32)
    rel = rel_rms(got, want)
    say(f"reference: logits on all {ids.shape[1]} rows {rel:.4e}")
    return {"rel_err": rel,
            "tol": float(config["tolerance"]["output_rel"]),
            "loss": _next_token_ce(got, labels),
            "loss_reference": _next_token_ce(want, labels),
            "loss_tol": float(config["tolerance"]["loss_rel"])}


def reference_jitted(config: dict, params, router_bias, ids, round_to=None):
    """`reference_forward` with each block and the head under `jax.jit`:
    layers of one kind share a compilation."""
    import functools
    import jax
    block = jax.jit(functools.partial(reference_block, config),
                    static_argnames=("round_to",))
    head = jax.jit(functools.partial(reference_head, config),
                   static_argnames=("round_to",))
    return reference_forward(
        config, params, router_bias, ids, round_to,
        block=lambda _, x, lp, b, r: block(x, lp, b, round_to=r),
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

QUERY_BLOCK = 512       # GQA's scores are computed this many queries at a time


def _rounded(round_to, *vs):
    """`vs` (float32) rounded to the dtype `round_to` and back; themselves
    without one."""
    import jax.numpy as jnp
    if round_to is None:
        return vs
    return tuple(v.astype(round_to).astype(jnp.float32) for v in vs)


def reference_kda(config: dict, u, lp, round_to=None):
    """Kimi delta attention on the normed `u` [rows, T, H] (float32), the
    held heads: `y` [rows, T, H], their part of `W_o`'s product.  The delta
    rule token by token: `lax.scan` over t, the state [rows, heads, dk, dv]."""
    import jax
    import jax.numpy as jnp

    _, n, _, d = _dims(config)
    eps = float(config["rms_norm_eps"])
    l2_eps = float(config["l2_norm_eps"])
    taps = int(config["linear_attn_config"]["short_conv_kernel_size"])
    rows, t, _ = u.shape
    w = n * d

    def mm(a, b):
        a, b = _rounded(round_to, a, b)
        return a @ b

    def conv_silu(z, kernel):
        """Causal depthwise: out_t = sum_j kernel[j] z_{t-(taps-1)+j}."""
        out = 0.0
        for j in range(taps):
            by = taps - 1 - j
            shifted = z if by == 0 else jnp.concatenate(
                [jnp.zeros_like(z[:, :by]), z[:, :-by]], 1)
            out = out + kernel[j] * shifted
        return out / (1.0 + jnp.exp(-out))

    def heads(a):
        return a.reshape(rows, t, n, d)

    def l2(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + l2_eps)

    q, k, v = (heads(conv_silu(mm(u, lp["Wqkv"][:, i * w:(i + 1) * w]),
                               lp["conv_qkv"][:, i * w:(i + 1) * w]))
               for i in range(3))
    q, k = l2(q), l2(k)
    q, k, v = _rounded(round_to, q, k, v)
    pre = heads(mm(mm(u, lp["Wf_a"]), lp["Wf_b"]) + lp["dt_bias"])
    softplus = jnp.maximum(pre, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(pre)))
    g = -jnp.exp(lp["A_log"])[:, None] * softplus
    beta = 1.0 / (1.0 + jnp.exp(-mm(u, lp["Wbeta"])))       # [rows, T, n]
    if config["kda_allow_neg_eigval"]:
        beta = 2.0 * beta

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs                     # [rows, n, .]
        s = s * jnp.exp(g_t)[..., None]
        s = s - b_t[..., None, None] * k_t[..., :, None] * jnp.einsum(
            "rnk,rnkv->rnv", k_t, s)[..., None, :]
        s = s + b_t[..., None, None] * k_t[..., :, None] * v_t[..., None, :]
        return s, d ** -0.5 * jnp.einsum("rnk,rnkv->rnv", q_t, s)

    s0 = jnp.zeros((rows, n, d, d), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0)
                                        for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)                             # [rows, T, n, d]
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * lp["o_norm"]
    gate = heads(mm(mm(u, lp["Wg_a"]), lp["Wg_b"]))
    o = o / (1.0 + jnp.exp(-gate))
    return mm(o.reshape(rows, t, w), lp["Wo"])


def reference_gqa(config: dict, u, lp, round_to=None):
    """Gated NoPE grouped-query attention on the normed `u` [rows, T, H]
    (float32), the held heads: `y` [rows, T, H], their part of `W_o`'s
    product.  Held query head a on held key-value head a // group."""
    import jax
    import jax.numpy as jnp

    _, nh, nkv, hd = _dims(config)
    rows, t, _ = u.shape

    def mm(a, b):
        a, b = _rounded(round_to, a, b)
        return a @ b

    w = lp["Wqkv"]                  # [H, (nh + 2 nkv) hd]: W_q | W_k | W_v
    q = mm(u, w[:, :nh * hd]).reshape(rows, t, nh, hd)
    k = mm(u, w[:, nh * hd:(nh + nkv) * hd]).reshape(rows, t, nkv, hd)
    v = mm(u, w[:, (nh + nkv) * hd:]).reshape(rows, t, nkv, hd)
    q, k, v = _rounded(round_to, q, k, v)
    of_head = jnp.arange(nh) // (nh // nkv)
    k, v = k[:, :, of_head], v[:, :, of_head]
    outs = []
    for q0 in range(0, t, QUERY_BLOCK):
        qb = q[:, q0:q0 + QUERY_BLOCK]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * hd ** -0.5
        qi = q0 + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(qi >= jnp.arange(t)[None, :], s, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(outs, 1).reshape(rows, t, nh * hd)
    o = o / (1.0 + jnp.exp(-mm(u, lp["Wg"])))
    return mm(o, lp["Wo"])


def reference_moe(config: dict, u, lp, bias, round_to=None):
    """The expert layer on the normed `u` [rows, T, H]: sigmoid scores, the
    `num_experts_per_tok` largest of score + bias chosen, the chosen scores
    normalised (`router_eps` added) and scaled; of the chosen experts those
    held summed; plus the shared expert."""
    import jax
    import jax.numpy as jnp
    top_k = int(config["num_experts_per_tok"])
    first = int(config["first_expert_held"])
    scale = float(config["routed_scaling_factor"])

    def mm(a, b):
        a, b = _rounded(round_to, a, b)
        return a @ b

    def ffn(wg, wu, wd):
        a = mm(u, wg)
        return mm(a / (1.0 + jnp.exp(-a)) * mm(u, wu), wd)

    s = jax.nn.sigmoid(u @ lp["router"])                        # float32
    _, chosen = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + float(config["router_eps"])) \
        * scale
    y = ffn(lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    for e in range(lp["w_gate"].shape[0]):                     # held experts
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
        y = y + w_e[..., None] * ffn(lp["w_gate"][e], lp["w_up"][e],
                                     lp["w_down"][e])
    return y


def _rms(config, v, g):
    import jax.numpy as jnp
    return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True)
                        + float(config["rms_norm_eps"])) * g


def reference_block(config: dict, x, lp, bias, round_to=None):
    """One block on `x` [rows, T, H] (float32): `h = x + Op(RMSNorm(x))`,
    `y = h + MoE(RMSNorm(h))`; `Op` KDA where `lp` holds `A_log`, gated GQA
    where it does not."""
    import jax
    with jax.default_matmul_precision("highest"):
        op = reference_kda if "A_log" in lp else reference_gqa
        x = x + op(config, _rms(config, x, lp["norm1"]), lp, round_to)
        return x + reference_moe(config, _rms(config, x, lp["norm2"]), lp,
                                 bias, round_to)


def layers_of(config: dict, params, router_bias):
    """`(kind, layer's parameters, selection bias)` of each held layer in
    order, out of the system's pytree: `moe` stacked over whole periods of
    the layers' kinds (a tuple of one dict a layer of the period), `rest`
    the layers after the last whole period."""
    import jax

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    period = params["moe"] if isinstance(params["moe"], (tuple, list)) \
        else (params["moe"],)
    periods = jax.tree_util.tree_leaves(period[0])[0].shape[0]
    layers = [at(lp, n) for n in range(periods) for lp in period]
    layers += list(params.get("rest", ()))
    kinds = held_layer_types(config)
    if [("linear_attention" if "A_log" in lp else "full_attention")
            for lp in layers] != kinds:
        raise ValueError(f"the parameters hold {len(layers)} layers that "
                         f"are not the configuration's {kinds}")
    return [(kind, lp, router_bias[i])
            for i, (kind, lp) in enumerate(zip(kinds, layers))]


def reference_forward(config: dict, params, router_bias, ids, round_to=None,
                      block=reference_block, head=reference_head):
    """Logits [rows, T, vocab held] in float32 at highest matmul precision:
    embedding lookup, no position embedding, the held layers (module
    docstring), RMSNorm and the untied head.

    `round_to` (a dtype) rounds both operands of every matrix product to it
    first, and q, k, v before the attention and the delta rule (the router's
    product stays float32, as the configuration states): the reference
    "computed in a lower precision", which the tolerance has to refuse.
    `block`/`head`: the same two functions wrapped, e.g. in `jax.jit`."""
    import jax
    import jax.numpy as jnp
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = p["tok_emb"][jnp.asarray(ids, jnp.int32)]
    for _, lp, bias in layers_of(config, p, jnp.asarray(router_bias,
                                                        jnp.float32)):
        x = block(config, x, lp, bias, round_to)
    return head(config, x, p["final_norm"], p["head"], round_to)


def reference_loss(config: dict, params, router_bias, ids, labels):
    """Mean next-token cross-entropy over every position but the last of
    each sequence, `log_softmax` in float32; `jax.grad` of it is the
    reference's gradient."""
    import jax
    import jax.numpy as jnp
    logits = reference_forward(config, params, router_bias, ids)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    nll = -jnp.take_along_axis(
        logp, jnp.asarray(labels, jnp.int32)[:, :-1, None], -1)
    return jnp.mean(nll)
