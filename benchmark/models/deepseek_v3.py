"""Family `deepseek_v3`: decoder-only language models whose public config
says `model_type: deepseek_v3` — multi-head latent attention and a sigmoid
router over shared + routed experts (DeepSeek-V3, arXiv:2412.19437; latent
attention from DeepSeek-V2, arXiv:2405.04434) — on the train path, through
the program's `zoo.DecoderModel`.

Program side: `build` and the adapters the drivers call.  Yardstick side:
`flops_per_item` and the kernels' operation and byte counts (from shapes),
and `reference_forward` / `reference_loss` (plain `jax.numpy`, float32,
highest matmul precision, Python loops over layers and experts, no scan, no
kernels), which read the system's own parameter pytree and follow the
paper's equations and the config's keys, not the program's code.

A configuration may be one chip's share of an expert- and vocabulary-parallel
deployment (`n_routed_experts` held of `n_routed_experts_published`, the
first `vocab_size` ids): the reference is given the same share and, like
the program, leaves the absent experts' terms out.
"""
from __future__ import annotations

import numpy as np

# the newest model `build` made: the per-layer readers of this family's
# counters (`layer_metrics/moe_*`) find the program through it
LAST_BUILT = None
# the newest train step `lower_step` lowered (the driver asks for it in a
# traced run): `trace/scopes.py` compiles it again — a cache hit — for the
# scope of each instruction in the trace
LAST_LOWERED = None
# the routing counter at the start of the measured window (`eval_loss` is
# the driver's last call before it): a device array, nothing is read there
_LOAD_AT_WINDOW_START = None


# ---------------------------------------------------------------------------
# shapes: required work
# ---------------------------------------------------------------------------

def _dims(config: dict):
    h, nh = int(config["hidden_size"]), int(config["num_attention_heads"])
    dn, dr = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    return h, nh, dn, dr, int(config["v_head_dim"]), int(config["kv_lora_rank"])


def _itemsize(config: dict) -> int:
    return 4 if config["compute_dtype"] == "float32" else 2


def held_per_token(config: dict) -> float:
    """Routed experts a token needs of those held here, in expectation
    under even routing: top-k x held / router width."""
    return (int(config["num_experts_per_tok"]) * int(config["n_routed_experts"])
            / int(config["n_routed_experts_published"]))


def layer_flops_per_token(config: dict, seq: int, moe: bool) -> dict:
    """Forward FLOPs one token of a `seq`-token sequence requires of one
    layer, by part.  Causal attention is the lower triangle: position t
    scores t + 1 keys, (seq + 1) / 2 on average.  Routed experts at the
    expected share of the chosen experts that is held."""
    h, nh, dn, dr, dv, r = _dims(config)
    parts = {
        "mla_products": 2.0 * (h * nh * (dn + dr) + h * (r + dr)
                               + r * nh * (dn + dv) + nh * dv * h),
        "attention": 2.0 * nh * (dn + dr + dv) * (seq + 1) / 2.0}
    if moe:
        ie = int(config["moe_intermediate_size"])
        parts["shared"] = 2.0 * 3 * h * ie * int(config["n_shared_experts"])
        parts["routed"] = 2.0 * 3 * h * ie * held_per_token(config)
        parts["router"] = 2.0 * h * int(config["n_routed_experts_published"])
    else:
        parts["mlp"] = 2.0 * 3 * h * int(config["intermediate_size"])
    return parts


def flops_per_item(config: dict, traffic: dict, training: bool = True) -> float:
    """FLOPs one sequence requires: every layer's products, causal attention
    as the lower triangle, routed experts at the expected share held, the
    head over the vocabulary held; no recomputation.  Training is 3x the
    forward.  Lookups, norms, rotary, softmax, top-k, sorting and the updater
    are not counted: the roofline it is set against is the MXU's."""
    seq = int(traffic["seq_len"])
    dense = int(config["first_k_dense_replace"])
    per_token = (
        dense * sum(layer_flops_per_token(config, seq, False).values())
        + (int(config["num_layers"]) - dense)
        * sum(layer_flops_per_token(config, seq, True).values())
        + 2.0 * int(config["hidden_size"]) * int(config["vocab_size"]))
    return (3.0 if training else 1.0) * seq * per_token


def attention_work(config: dict, traffic: dict, rows: int) -> dict:
    """What causal attention requires of one train step of `rows` sequences
    over all layers: `flops` (two products forward — scores, values — and
    four backward — dV, dP, dQ, dK — each over the lower triangle; the
    scores a flash backward computes again are not required work) and
    `bytes` (every operand read once and every result written once, in the
    compute dtype: q, k, v, o forward; q, k, v, o, dO in and dQ, dK, dV out
    backward)."""
    _, nh, dn, dr, dv, _ = _dims(config)
    seq, layers = int(traffic["seq_len"]), int(config["num_layers"])
    pairs = seq * (seq + 1) / 2.0
    dk = dn + dr
    flops = 2.0 * pairs * ((dk + dv) + 2 * (dk + dv))
    elements = seq * ((2 * dk + 2 * dv) + (2 * dk + 3 * dv) + (2 * dk + dv))
    return {"flops": flops * rows * nh * layers,
            "bytes": float(elements * _itemsize(config) * rows * nh * layers)}


def grouped_work(config: dict, pairs: float, layer_steps: float = 1) -> dict:
    """What the routed experts' grouped products require for `pairs`
    (token, held expert) rows in all, spread over `layer_steps` runs of an
    expert layer (layers x steps), forward and backward: three products
    forward (gate, up, down) and six backward (each product's two
    gradients); the bytes of each product's row operand and result once,
    and of each held expert's matrix once a product and run."""
    h, ie = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    weights = int(config["n_routed_experts"]) * h * ie * layer_steps
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
        n_dense_layers=int(config["first_k_dense_replace"]),
        n_heads=int(config["num_attention_heads"]),
        qk_nope_dim=int(config["qk_nope_head_dim"]),
        qk_rope_dim=int(config["qk_rope_head_dim"]),
        v_head_dim=int(config["v_head_dim"]),
        kv_lora_rank=int(config["kv_lora_rank"]),
        intermediate=int(config["intermediate_size"]),
        expert_intermediate=int(config["moe_intermediate_size"]),
        n_experts=int(config["n_routed_experts_published"]),
        n_shared_experts=int(config["n_shared_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        routed_scale=float(config["routed_scaling_factor"]),
        first_expert=int(config["first_expert_held"]),
        n_experts_held=int(config["n_routed_experts"]),
        rope_base=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]),
        bias_update_speed=float(config["bias_update_speed"]),
        init_std=float(config["init_std"]),
        embedding_init_std=float(config["embedding_init_std"]),
        compute_dtype=config["compute_dtype"])


def build(config: dict, seed: int, serving: bool = False):
    """`zoo.DecoderModel` with the file's sizes and share, parameters
    initialised on the device from `seed`."""
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


def _schedule(arg):
    """A learning rate as the file gives it: a number, or `{"schedule":
    <class of train/schedules.py>, "args": [...]}`."""
    if not isinstance(arg, dict):
        return arg
    from deeplearning4j_tpu.train import schedules
    return getattr(schedules, arg["schedule"])(*arg["args"])


def zipf_ids(rng, vocab: int, exponent: float, shape) -> np.ndarray:
    """Ids 0..vocab-1 with p(id) proportional to (id + 1)^-exponent: the
    id is the rank, as in a vocabulary sorted by frequency."""
    cdf = np.cumsum((np.arange(1, vocab + 1, dtype=np.float64)) ** -exponent)
    draws = np.searchsorted(cdf, rng.random(shape) * cdf[-1], side="right")
    return np.minimum(draws, vocab - 1).astype(np.int32)


def make_pool(config: dict, traffic: dict, seed: int, rows: int):
    """`pool_batches` host batches of `rows` sequences of `seq_len` ids
    drawn from a Zipf distribution over the vocabulary held; the labels are
    the ids shifted by one (the last column, which the loss ignores, 0)."""
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    rng = np.random.default_rng(seed)
    t = int(traffic["seq_len"])
    pool = []
    for _ in range(int(traffic["pool_batches"])):
        ids = zipf_ids(rng, int(config["vocab_size"]),
                       float(traffic["zipf_exponent"]), (rows, t))
        labels = np.concatenate(
            [ids[:, 1:], np.zeros((rows, 1), np.int32)], axis=1)
        pool.append(MultiDataSet(features=[ids], labels=[labels]))
    return pool


def items_per_row(config: dict, traffic: dict) -> dict:
    return {"samples": 1, "tokens": int(traffic["seq_len"])}


def step_hook(model, hook) -> bool:
    return False           # `DecoderModel.fit` has no listener: the driver's
                           # iterator calls the hook between steps


def last_loss(model):
    """The newest minibatch loss as a device scalar; no host sync."""
    return getattr(model, "_score", None)


def parameters(model):
    return model.params_


def _next_token_ce(logits, labels) -> float:
    z = np.asarray(logits, np.float64)[:, :-1]
    z = z - z.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    return float(-np.take_along_axis(
        logp, np.asarray(labels)[:, :-1, None], -1).mean())


def _slice(batch, rows: int):
    return (np.asarray(batch.features[0])[:rows],
            np.asarray(batch.labels[0])[:rows])


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
    the expert layers share one compilation (a Python loop over 16 experts
    takes the chip's compiler 20 s a layer)."""
    import functools
    import jax
    block = jax.jit(functools.partial(reference_block, config),
                    static_argnames=("round_to",))
    head = jax.jit(functools.partial(reference_head, config),
                   static_argnames=("round_to",))
    return reference_forward(
        config, params, router_bias, ids, round_to,
        block=lambda _, x, lp, b, r: block(x, lp, b, round_to=r),
        head=lambda _, x, g, h, r: head(x, g, h, round_to=r))


def rel_rms(got, want) -> float:
    d = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    return float(np.sqrt(np.mean(d * d))
                 / (np.sqrt(np.mean(np.asarray(want, np.float64) ** 2))
                    + 1e-30))


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
    """One block on `x` [B, T, H] (float32): `h = x + MLA(RMSNorm(x))`,
    `y = h + F(RMSNorm(h))`; `F` is the expert layer where a selection
    `bias` [E] is given (`lp` then holds a router and experts), a SwiGLU MLP
    where it is None.  See `reference_forward`."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = float(config["rms_norm_eps"])
    h, nh, dn, dr, dv, rank = _dims(config)
    top_k = int(config["num_experts_per_tok"])
    first = int(config["first_expert_held"])
    scale = float(config["routed_scaling_factor"])
    base = float(config["rope_theta"])

    def mm(a, b):
        if round_to is not None:
            a, b = (v.astype(round_to).astype(f32) for v in (a, b))
        return a @ b

    def rms(v, g):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * g

    def silu(v):
        return v / (1.0 + jnp.exp(-v))

    def rope(v, t):
        """v [B, T, heads, dr]: pair (v[2i], v[2i+1]) turned by
        pos * base^(-2i/dr)."""
        inv = base ** (-jnp.arange(0, dr, 2, dtype=f32) / dr)
        ang = jnp.arange(t, dtype=f32)[:, None] * inv[None]      # [T, dr/2]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        even, odd = v[..., 0::2], v[..., 1::2]
        out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
        return out.reshape(v.shape)

    def mla(x):
        b, t, _ = x.shape
        q = mm(x, lp["Wq"]).reshape(b, t, nh, dn + dr)
        kva = mm(x, lp["Wkva"])
        kv = mm(rms(kva[..., :rank], lp["kv_norm"]), lp["Wkvb"]).reshape(
            b, t, nh, dn + dv)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], t)], -1)
        k_rope = rope(kva[..., None, rank:], t)                 # one head
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (b, t, nh, dr))], -1)
        v = kv[..., dn:]
        if round_to is not None:
            q, k, v = (a.astype(round_to).astype(f32) for a in (q, k, v))
        outs = []
        for q0 in range(0, t, QUERY_BLOCK):
            qb = q[:, q0:q0 + QUERY_BLOCK]
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / np.sqrt(dn + dr)
            qi = q0 + jnp.arange(qb.shape[1])[:, None]
            s = jnp.where(qi >= jnp.arange(t)[None, :], s, -jnp.inf)
            outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                                   jax.nn.softmax(s, -1), v))
        return mm(jnp.concatenate(outs, 1).reshape(b, t, nh * dv), lp["Wo"])

    def ffn(x, wg, wu, wd):
        return mm(silu(mm(x, wg)) * mm(x, wu), wd)

    def moe(x):
        s = jax.nn.sigmoid(x @ lp["router"])                   # [B, T, E]
        _, chosen = jax.lax.top_k(s + bias, top_k)
        w = jnp.take_along_axis(s, chosen, -1)
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * scale
        y = ffn(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
        for e in range(lp["w_gate"].shape[0]):                 # held experts
            w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
            y = y + w_e[..., None] * ffn(x, lp["w_gate"][e], lp["w_up"][e],
                                         lp["w_down"][e])
        return y

    with jax.default_matmul_precision("highest"):
        x = x + mla(rms(x, lp["norm1"]))
        if bias is None:
            return x + ffn(rms(x, lp["norm2"]), lp["mlp_gate"], lp["mlp_up"],
                           lp["mlp_down"])
        return x + moe(rms(x, lp["norm2"]))


def reference_head(config: dict, x, final_norm, head, round_to=None):
    """RMSNorm, then the untied head: logits [B, T, vocab held]."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                         + float(config["rms_norm_eps"])) * final_norm
        if round_to is not None:
            x, head = (v.astype(round_to).astype(jnp.float32)
                       for v in (x, head))
        return x @ head


def reference_forward(config: dict, params, router_bias, ids, round_to=None,
                      block=reference_block, head=reference_head):
    """Logits [B, T, vocab held] in float32 at highest matmul precision.

    Embedding lookup, no position embedding.  Per block `l`: `h = x +
    MLA(RMSNorm(x))`, `y = h + F_l(RMSNorm(h))`, `F_l` a SwiGLU MLP for the
    first `first_k_dense_replace` layers and the expert layer after; then
    RMSNorm and the untied head.  MLA with `q_lora_rank` null; rotary on the
    rope dims in the interleaved form (`rope_interleave`); softmax scale
    over the whole key width.  The expert layer: sigmoid scores, the
    `num_experts_per_tok` largest of score + bias chosen, weights the scores
    at the chosen (no bias), normalised, scaled; of the chosen experts only
    those held (`first_expert_held` .. + `n_routed_experts`) are summed,
    plus the shared experts.

    `round_to` (a dtype) rounds both operands of every matrix product to it
    first (the router's stays float32, as the configuration states): the
    reference "computed in a lower precision", which the tolerance has to
    refuse.  `block`/`head`: the same two functions wrapped, e.g. in
    `jax.jit` so that the identical expert layers compile once."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, f32), params)
    bias = jnp.asarray(router_bias, f32)

    def layer(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    x = p["tok_emb"][jnp.asarray(ids, jnp.int32)]
    for i in range(p["dense"]["Wq"].shape[0]):
        x = block(config, x, layer(p["dense"], i), None, round_to)
    for i in range(p["moe"]["Wq"].shape[0]):
        x = block(config, x, layer(p["moe"], i), bias[i], round_to)
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
