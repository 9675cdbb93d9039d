"""Family `lfm2_moe`: decoder-only language models whose public config says
`model_type: lfm2_moe` (LiquidAI's LFM2 mixture-of-experts hybrids, e.g.
LFM2-24B-A2B) — gated short-convolution layers beside grouped-query
attention layers by a per-layer `layer_types` list, a sigmoid router with an
expert bias over routed experts alone, a tied output embedding — on the train
path, through the program's `zoo.DecoderModel`.

Program side: `build` and the adapters the drivers call.  Yardstick side:
`flops_per_item` and the kernels' operation and byte counts (from shapes),
and `reference_forward` / `reference_loss` (plain `jax.numpy`, float32,
highest matmul precision, Python loops over layers and experts, the
convolution as explicit shifted terms, no scan, no kernels), which read the
system's own parameter pytree and follow the layer equations and the
config's keys, not the program's code.

A configuration may be one chip's share of an expert- and vocabulary-parallel
deployment (`num_experts` held of `num_experts_published`, the first
`vocab_size` ids) and a run of the published layers (`num_layers` of them
from `first_layer_held` on, `num_dense_layers` of which are dense): the
reference is given the same share and, like the program, leaves the absent
experts' terms out.

What every decoder family of this benchmark does alike — Zipf ids, the pool,
the loss of a set of logits, the relative rms — is `models/deepseek_v3.py`'s
and is imported, not copied; the state a run keeps (the newest model, its
lowered step, the routing counter at the window's start) is this module's
own, because `trace/scopes.py` and the readers find it by the family's name.
"""
from __future__ import annotations

import numpy as np

from benchmark.models.deepseek_v3 import (  # noqa: F401  (the drivers' API)
    _next_token_ce, _schedule, _slice, items_per_row, last_loss, make_pool,
    parameters, rel_rms, step_hook, zipf_ids)

# the newest model `build` made: the per-layer readers find the program
# through the cell's family (`harness.load_family(run.cell.config)`)
LAST_BUILT = None
# the newest train step `lower_step` lowered: `trace/scopes.py` compiles it
# again — a cache hit — for the scope of each instruction in the trace
LAST_LOWERED = None
# the routing counter at the start of the measured window, on the device
_LOAD_AT_WINDOW_START = None


# ---------------------------------------------------------------------------
# shapes: required work
# ---------------------------------------------------------------------------

def held_layer_types(config: dict) -> list:
    """The kinds of the layers held here, in order: `num_layers` of the
    published `layer_types` from `first_layer_held` on."""
    first = int(config["first_layer_held"])
    kinds = list(config["layer_types"][first:first + int(config["num_layers"])])
    if len(kinds) != int(config["num_layers"]):
        raise ValueError(f"layer_types has {len(kinds)} layers from {first} "
                         f"on, num_layers asks for {config['num_layers']}")
    return kinds


def _dims(config: dict):
    """(hidden, query heads, key-value heads, head width)."""
    return (int(config["hidden_size"]), int(config["num_attention_heads"]),
            int(config["num_key_value_heads"]), int(config["head_dim"]))


def _itemsize(config: dict) -> int:
    return 4 if config["compute_dtype"] == "float32" else 2


def held_per_token(config: dict) -> float:
    """Routed experts a token needs of those held here, in expectation
    under even routing: top-k x held / router width."""
    return (int(config["num_experts_per_tok"]) * int(config["num_experts"])
            / int(config["num_experts_published"]))


def layer_flops_per_token(config: dict, seq: int, kind: str,
                          experts: bool) -> dict:
    """Forward FLOPs one token of a `seq`-token sequence requires of one
    layer of `kind`, by part.  Causal attention is the lower triangle:
    position t scores t + 1 keys, (seq + 1) / 2 on average.  Routed experts
    at the expected share of the chosen experts that is held.  The
    convolution's own multiply-adds (2 x 3 a channel) are left out with the
    other elementwise work."""
    h, nh, nkv, hd = _dims(config)
    if kind == "conv":
        parts = {"conv_products": 2.0 * (h * 3 * h + h * h)}
    else:
        parts = {"gqa_products": 2.0 * (h * (nh + 2 * nkv) * hd + nh * hd * h),
                 "attention": 2.0 * nh * (hd + hd) * (seq + 1) / 2.0}
    if experts:
        parts["routed"] = (2.0 * 3 * h * int(config["moe_intermediate_size"])
                           * held_per_token(config))
        parts["router"] = 2.0 * h * int(config["num_experts_published"])
    else:
        parts["mlp"] = 2.0 * 3 * h * int(config["intermediate_size"])
    return parts


def flops_per_item(config: dict, traffic: dict, training: bool = True) -> float:
    """FLOPs one sequence requires: every held layer's products, causal
    attention as the lower triangle, routed experts at the expected share
    held, the tied head over the vocabulary held; no recomputation.
    Training is 3x the forward.  Lookups, norms, rotary, softmax, the
    convolution's taps, top-k, sorting and the updater are not counted: the
    roofline it is set against is the MXU's."""
    seq = int(traffic["seq_len"])
    dense = int(config["num_dense_layers"])
    per_token = 2.0 * int(config["hidden_size"]) * int(config["vocab_size"])
    for i, kind in enumerate(held_layer_types(config)):
        per_token += sum(layer_flops_per_token(
            config, seq, kind, experts=i >= dense).values())
    return (3.0 if training else 1.0) * seq * per_token


def gqa_attention_work(config: dict, traffic: dict, rows: int) -> dict:
    """What causal grouped-query attention requires of one train step of
    `rows` sequences over the held `full_attention` layers: `flops` (two
    products forward — scores, values — and four backward — dV, dP, dQ, dK —
    each over the lower triangle, for every QUERY head; scores computed
    again by a flash backward are not required work) and `bytes` in the
    compute dtype: q, o, dO and dQ once a query head — q and o forward; q,
    o, dO in and dQ out backward — and k, v, dK and dV once a KEY-VALUE
    head — k, v forward; k, v in and dK, dV out backward.  The same work
    whatever implements it: a kernel that reads a key-value head once for
    each of its query heads, or writes dK per query head, moves more and
    is charged for it."""
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
    forward (gate, up, down) and six backward (each product's two
    gradients); the bytes of each product's row operand and result once,
    and of each held expert's matrix once a product and run."""
    h, ie = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    weights = int(config["num_experts"]) * h * ie * layer_steps
    return {"flops": 9 * 2.0 * pairs * h * ie,
            "bytes": 9.0 * _itemsize(config) * (pairs * (h + ie) + weights)}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def decoder_config(config: dict):
    from deeplearning4j_tpu.zoo import DecoderConfig
    return DecoderConfig(
        vocab_size=int(config["vocab_size"]),
        hidden=int(config["hidden_size"]),
        n_layers=int(config["num_layers"]),
        n_dense_layers=int(config["num_dense_layers"]),
        layer_types=tuple(held_layer_types(config)),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        conv_kernel=int(config["conv_L_cache"]),
        intermediate=int(config["intermediate_size"]),
        expert_intermediate=int(config["moe_intermediate_size"]),
        n_experts=int(config["num_experts_published"]),
        n_shared_experts=0,
        top_k=int(config["num_experts_per_tok"]),
        routed_scale=float(config["routed_scaling_factor"]),
        router_eps=float(config["router_norm_eps"]),
        first_expert=int(config["first_expert_held"]),
        n_experts_held=int(config["num_experts"]),
        rope_base=float(config["rope_parameters"]["rope_theta"]),
        eps=float(config["norm_eps"]),
        bias_update_speed=float(config["bias_update_speed"]),
        init_std=float(config["init_std"]),
        embedding_init_std=float(config["embedding_init_std"]),
        tie_embeddings=True,
        compute_dtype=config["compute_dtype"])


def build(config: dict, seed: int, serving: bool = False):
    """`zoo.DecoderModel` with the file's sizes, layer list and share,
    parameters initialised on the device from `seed`."""
    global LAST_BUILT, LAST_LOWERED, _LOAD_AT_WINDOW_START
    from deeplearning4j_tpu.train import updaters
    from deeplearning4j_tpu.zoo import DecoderModel
    u = config["updater"]
    LAST_BUILT = DecoderModel(
        decoder_config(config), seed=int(seed),
        updater=getattr(updaters, u["kind"])(
            *[_schedule(a) for a in u["args"]], **u.get("kwargs", {})))
    LAST_LOWERED = _LOAD_AT_WINDOW_START = None
    return LAST_BUILT


def eval_loss(model, batch, rows: int) -> float:
    """Next-token loss of the system's `output` on the batch's first `rows`
    sequences.  The driver calls it right before the measured window and
    right after: the first call also notes where the routing counter stood
    (a copy on the device — the step donates its state; nothing is
    transferred)."""
    global _LOAD_AT_WINDOW_START
    if _LOAD_AT_WINDOW_START is None:
        import jax.numpy as jnp
        _LOAD_AT_WINDOW_START = jnp.copy(model.state_["expert_load"])
    ids, labels = _slice(batch, rows)
    return _next_token_ce(model.output(ids), labels)


def window_expert_load(model) -> np.ndarray:
    """[expert layers, router width] tokens that chose each expert between
    the start of the measured window and now: the program's device counter,
    both ends read in one transfer."""
    import jax
    start = _LOAD_AT_WINDOW_START
    now = model.state_["expert_load"]
    if start is None:
        return np.asarray(now)
    start, now = jax.device_get((start, now))
    return now - start


def window_held_load(model) -> np.ndarray:
    """`window_expert_load` of the experts held here: [expert layers, held]
    (token, held expert) pairs, the rows the grouped products ran on."""
    c = model.config
    return window_expert_load(model)[
        :, c.first_expert:c.first_expert + c.held]


def reference_check(model, config: dict, batch, rows: int) -> dict:
    """The system's logits on `rows` sequences against `reference_forward`
    on the same parameters and router bias.  `rel_err` is the root mean
    square of the difference over all logits, over the root mean square of
    the reference's logits (the config's `tolerance.why` says why)."""
    ids, labels = _slice(batch, rows)
    got = np.asarray(model.output(ids), np.float32)
    want = np.asarray(reference_jitted(
        config, model.params_, model.state_["router_bias"], ids), np.float32)
    return {"rel_err": rel_rms(got, want),
            "tol": float(config["tolerance"]["output_rel"]),
            "loss": _next_token_ce(got, labels),
            "loss_reference": _next_token_ce(want, labels),
            "loss_tol": float(config["tolerance"]["loss_rel"])}


def reference_jitted(config: dict, params, router_bias, ids, round_to=None):
    """`reference_forward` with each block and the head under `jax.jit`:
    layers of one kind share a compilation (a Python loop over the held
    experts takes the chip's compiler seconds a layer)."""
    import functools
    import jax
    block = jax.jit(functools.partial(reference_block, config),
                    static_argnames=("round_to",))
    head = jax.jit(functools.partial(reference_head, config),
                   static_argnames=("round_to",))
    return reference_forward(
        config, params, router_bias, ids, round_to,
        block=lambda _, x, lp, b, r: block(x, lp, b, round_to=r),
        head=lambda _, x, g, e, r: head(x, g, e, round_to=r))


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

QUERY_BLOCK = 512       # attention is computed this many queries at a time


def reference_block(config: dict, x, lp, bias=None, round_to=None):
    """One block on `x` [B, T, H] (float32): `h = x + Op(RMSNorm(x))`, `y =
    h + F(RMSNorm(h))`.  `Op` is the gated short convolution where `lp`
    holds its matrices (`conv_in`) and grouped-query attention where it
    holds `Wqkv`; `F` is the expert layer where a selection `bias` [E] is
    given (`lp` then holds a router and experts), a SwiGLU where it is None.
    See `reference_forward`."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = float(config["norm_eps"])
    h, nh, nkv, hd = _dims(config)
    group = nh // nkv
    top_k = int(config["num_experts_per_tok"])
    first = int(config["first_expert_held"])
    scale = float(config["routed_scaling_factor"])
    router_eps = float(config["router_norm_eps"])
    base = float(config["rope_parameters"]["rope_theta"])

    def mm(a, b):
        if round_to is not None:
            a, b = (v.astype(round_to).astype(f32) for v in (a, b))
        return a @ b

    def rms(v, g):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * g

    def silu(v):
        return v / (1.0 + jnp.exp(-v))

    def rope(v, t):
        """v [B, T, heads, hd]: pair (v[i], v[i + hd/2]) turned by
        pos * base^(-2i/hd), i in 0 .. hd/2 - 1."""
        half = hd // 2
        inv = base ** (-2.0 * jnp.arange(half, dtype=f32) / hd)
        ang = jnp.arange(t, dtype=f32)[:, None] * inv[None]     # [T, hd/2]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        lo, hi = v[..., :half], v[..., half:]
        return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], -1)

    def shifted(z, by):
        """z [B, T, C] moved `by` positions later, zeros moved in."""
        if by == 0:
            return z
        return jnp.concatenate([jnp.zeros_like(z[:, :by]), z[:, :-by]], 1)

    def conv(u):
        bcx = mm(u, lp["conv_in"])
        b, c, xg = bcx[..., :h], bcx[..., h:2 * h], bcx[..., 2 * h:]
        z = b * xg
        k = lp["conv_kernel"]                                   # [L, H]
        taps = k.shape[0]
        mixed = 0.0
        for j in range(taps):       # tap j reads position t - (L - 1) + j
            mixed = mixed + k[j] * shifted(z, taps - 1 - j)
        return mm(c * mixed, lp["conv_out"])

    def attention(u):
        b, t, _ = u.shape
        w = lp["Wqkv"]              # [H, (nh + 2 nkv) hd]: W_q | W_k | W_v
        q = mm(u, w[:, :nh * hd]).reshape(b, t, nh, hd)
        k = mm(u, w[:, nh * hd:(nh + nkv) * hd]).reshape(b, t, nkv, hd)
        v = mm(u, w[:, (nh + nkv) * hd:]).reshape(b, t, nkv, hd)
        q = rope(rms(q, lp["q_norm"]), t)
        k = rope(rms(k, lp["k_norm"]), t)
        if round_to is not None:
            q, k, v = (a.astype(round_to).astype(f32) for a in (q, k, v))
        # query head i attends key-value head i // group
        of_head = jnp.arange(nh) // group
        k, v = k[:, :, of_head], v[:, :, of_head]
        outs = []
        for q0 in range(0, t, QUERY_BLOCK):
            qb = q[:, q0:q0 + QUERY_BLOCK]
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / np.sqrt(hd)
            qi = q0 + jnp.arange(qb.shape[1])[:, None]
            s = jnp.where(qi >= jnp.arange(t)[None, :], s, -jnp.inf)
            outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                                   jax.nn.softmax(s, -1), v))
        return mm(jnp.concatenate(outs, 1).reshape(b, t, nh * hd), lp["Wo"])

    def ffn(u, wg, wu, wd):
        return mm(silu(mm(u, wg)) * mm(u, wu), wd)

    def moe(u):
        s = jax.nn.sigmoid(u @ lp["router"])                   # [B, T, E]
        _, chosen = jax.lax.top_k(s + bias, top_k)
        w = jnp.take_along_axis(s, chosen, -1)
        w = w / (jnp.sum(w, -1, keepdims=True) + router_eps) * scale
        y = 0.0
        for e in range(lp["w_gate"].shape[0]):                 # held experts
            w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
            y = y + w_e[..., None] * ffn(u, lp["w_gate"][e], lp["w_up"][e],
                                         lp["w_down"][e])
        return y

    with jax.default_matmul_precision("highest"):
        op = conv if "conv_in" in lp else attention
        x = x + op(rms(x, lp["norm1"]))
        if bias is None:
            return x + ffn(rms(x, lp["norm2"]), lp["mlp_gate"], lp["mlp_up"],
                           lp["mlp_down"])
        return x + moe(rms(x, lp["norm2"]))


def reference_head(config: dict, x, final_norm, embedding, round_to=None):
    """RMSNorm, then the tied head: logits [B, T, vocab held] = h E^T."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                         + float(config["norm_eps"])) * final_norm
        if round_to is not None:
            x, embedding = (v.astype(round_to).astype(jnp.float32)
                            for v in (x, embedding))
        return x @ embedding.T


def layers_of(config: dict, params, router_bias):
    """`(kind, layer's parameters, selection bias or None)` for each held
    layer in order, out of the system's pytree: `dense` stacked over the
    leading dense layers; `moe` stacked over whole periods of the expert
    layers' kinds — one dict where the period is one layer, else one dict
    for each layer of the period — and `rest` the layers after the last
    whole period."""
    import jax

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    out = [(at(params["dense"], i), None)
           for i in range(int(config["num_dense_layers"]))]
    period = params["moe"] if isinstance(params["moe"], (tuple, list)) \
        else (params["moe"],)
    periods = jax.tree_util.tree_leaves(period[0])[0].shape[0]
    experts = [at(lp, n) for n in range(periods) for lp in period]
    experts += list(params.get("rest", ()))
    out += [(lp, router_bias[i]) for i, lp in enumerate(experts)]
    kinds = held_layer_types(config)
    if [("conv" if "conv_in" in lp else "full_attention") for lp, _ in out] \
            != kinds:
        raise ValueError(f"the parameters hold {len(out)} layers that are "
                         f"not the configuration's {kinds}")
    return [(kind, lp, bias) for kind, (lp, bias) in zip(kinds, out)]


def reference_forward(config: dict, params, router_bias, ids, round_to=None,
                      block=reference_block, head=reference_head):
    """Logits [B, T, vocab held] in float32 at highest matmul precision.

    Embedding lookup, no position embedding.  Per block `l`: `h = x +
    Op_l(RMSNorm(x))`, `y = h + F_l(RMSNorm(h))`; `Op_l` by the held layer's
    type — `conv`: `[B, C, X] = split3(u W_in)`, `c_t = sum_j k_j (B *
    X)_{t-(L-1)+j}` per channel, zero before position 0, out `(C * c)
    W_out`; `full_attention`: 32 query heads over 8 key-value heads of 64,
    RMSNorm of every query and key head (one gain each), half-split rotary,
    causal softmax — `F_l` a SwiGLU for the first `num_dense_layers` layers
    and the expert layer after: sigmoid scores, the `num_experts_per_tok`
    largest of score + bias chosen, weights the scores at the chosen (no
    bias), normalised with `router_norm_eps`, scaled; of the chosen experts
    only those held (`first_expert_held` .. + `num_experts`) are summed; no
    shared expert.  Then RMSNorm and the head, the embedding's transpose.

    `round_to` (a dtype) rounds both operands of every matrix product to it
    first (the router's stays float32, as the configuration states): the
    reference "computed in a lower precision", which the tolerance has to
    refuse.  `block`/`head`: the same two functions wrapped, e.g. in
    `jax.jit` so that layers of one kind compile once."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, f32), params)
    x = p["tok_emb"][jnp.asarray(ids, jnp.int32)]
    for _, lp, bias in layers_of(config, p, jnp.asarray(router_bias, f32)):
        x = block(config, x, lp, bias, round_to)
    return head(config, x, p["final_norm"], p["tok_emb"], round_to)


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
