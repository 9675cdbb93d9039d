"""Family `sdar_moe`: decoder-only language models whose public config says
`model_type: sdar_moe` (JetLM's SDAR mixture-of-experts models, e.g.
SDAR-30B-A3B-Chat) — a Qwen3-MoE block (grouped-query attention with an
RMSNorm of every query and key head, a softmax router over routed experts
alone, no dense layer, an untied head) trained by BLOCK DIFFUSION (BD3-LM,
arXiv:2503.09573, section 3; SDAR, arXiv:2510.06303) — on the train path,
through the program's `zoo.DecoderModel`.

The objective, for a clean sequence `x0` of L tokens in blocks of B: each
block draws `t ~ U(eps, 1)` and each of its tokens becomes the mask id with
probability `t`, giving `xt`.  The model runs on the 2L rows `[xt ; x0]`,
row r at position `r mod L`, and row r may attend row s iff both are noisy
and of one block, or r is noisy, s clean and of an EARLIER block, or both
are clean and s of r's block or an earlier one; a clean row never sees a
noisy one.  The loss is `(1/L) sum_i [xt_i = mask] / t_block(i) * -log
softmax(logits_i)[x0_i]` on the noisy half's logits — a replaced position
predicts its own token, no shift — plus `router_aux_loss_coef * E * sum_e f_e
P_e`, averaged over the layers.

Program side: `build` and the adapters the drivers call.  Yardstick side:
`flops_per_item` and the kernels' operation and byte counts (from shapes),
and `reference_forward` / `reference_loss` (plain `jax.numpy`, float32,
highest matmul precision, Python loops over layers and held experts, the
mask an explicit boolean [2L, 2L] array built from the rules above and
applied a block of queries at a time, no scan, no kernels), which read the
system's own parameter pytree and follow the equations and the config's
keys, not the program's code.  The noise of a comparison is drawn HERE, with
numpy, and handed to both sides.

A configuration may be one chip's share of an expert- and vocabulary-parallel
deployment (`num_experts` held of `num_experts_published`, the first
`vocab_size` ids, `num_layers` of the published `num_hidden_layers`): the
reference is given the same share and, like the program, leaves the absent
experts' terms out.

What every decoder family of this benchmark does alike is
`models/deepseek_v3.py`'s and is imported, not copied; the state a run keeps
(the newest model, its lowered step, the counters at the window's start) is
this module's own, because `trace/scopes.py` and the readers find it by the
family's name.
"""
from __future__ import annotations

import numpy as np

from benchmark.models.deepseek_v3 import (  # noqa: F401  (the drivers' API)
    _schedule, _slice, items_per_row, last_loss, parameters, rel_rms,
    step_hook, zipf_ids)

# the newest model `build` made: the per-layer readers find the program
# through the cell's family (`harness.load_family(run.cell.config)`)
LAST_BUILT = None
# the newest train step `lower_step` lowered: `trace/scopes.py` compiles it
# again — a cache hit — for the scope of each instruction in the trace
LAST_LOWERED = None
# the step's device counters at the start of the measured window
_AT_WINDOW_START = None

# the noise `eval_loss` and `reference_check` use: fixed, so that the loss
# before the window and after it are of the same masked positions
EVAL_NOISE_SEED = 20251006


# ---------------------------------------------------------------------------
# shapes: required work
# ---------------------------------------------------------------------------

def _dims(config: dict):
    """(hidden, query heads, key-value heads, head width)."""
    return (int(config["hidden_size"]), int(config["num_attention_heads"]),
            int(config["num_key_value_heads"]), int(config["head_dim"]))


def _itemsize(config: dict) -> int:
    return 4 if config["compute_dtype"] == "float32" else 2


def held_per_token(config: dict) -> float:
    """Routed experts a row needs of those held here, in expectation under
    even routing: top-k x held / router width."""
    return (int(config["num_experts_per_tok"]) * int(config["num_experts"])
            / int(config["num_experts_published"]))


def live_pairs(config: dict, seq: int) -> int:
    """(query, key) pairs the block mask keeps over the 2 x `seq` rows:
    clean on clean `seq (seq + B) / 2`, noisy on clean `seq (seq - B) / 2`,
    noisy on noisy `seq B`: `seq^2 + seq B`."""
    return seq * seq + seq * int(config["block_length"])


def layer_flops_per_sequence(config: dict, seq: int) -> dict:
    """Forward FLOPs one clean sequence of `seq` tokens requires of one
    layer, by part: the products on all 2 x `seq` rows, attention over the
    live pairs, routed experts at the expected share of the chosen experts
    that is held."""
    h, nh, nkv, hd = _dims(config)
    rows = 2 * seq
    return {
        "gqa_products": rows * 2.0 * (h * (nh + 2 * nkv) * hd + nh * hd * h),
        "attention": 2.0 * nh * (hd + hd) * live_pairs(config, seq),
        "routed": rows * 2.0 * 3 * h * int(config["moe_intermediate_size"])
        * held_per_token(config),
        "router": rows * 2.0 * h * int(config["num_experts_published"])}


def flops_per_item(config: dict, traffic: dict, training: bool = True) -> float:
    """FLOPs one clean sequence requires: every held layer on the 2L rows,
    attention over the `L^2 + L B` live pairs, the head on the L noisy rows
    over the vocabulary held; no recomputation.  Training is 3x the
    forward.  Lookups, the noise, norms, rotary, softmax, top-k, sorting
    and the updater are not counted: the roofline it is set against is the
    MXU's."""
    seq = int(traffic["seq_len"])
    fwd = (int(config["num_layers"])
           * sum(layer_flops_per_sequence(config, seq).values())
           + seq * 2.0 * int(config["hidden_size"])
           * int(config["vocab_size"]))
    return (3.0 if training else 1.0) * fwd


def gqa_attention_work(config: dict, traffic: dict, rows: int) -> dict:
    """What grouped-query attention under the block mask requires of one
    train step of `rows` clean sequences over the held layers: `flops` (two
    products forward — scores, values — and four backward — dV, dP, dQ, dK —
    each over the `L^2 + L B` live pairs, for every QUERY head; scores
    computed again by a flash backward are not required work) and `bytes` in
    the compute dtype over the 2L rows: q, o, dO and dQ once a query head —
    q and o forward; q, o, dO in and dQ out backward — and k, v, dK and dV
    once a KEY-VALUE head.  The same work whatever implements it: a kernel
    that computes a tile the mask empties, or moves a key-value head once
    for each of its query heads, is charged for it."""
    _, nh, nkv, hd = _dims(config)
    seq = int(traffic["seq_len"])
    layers = int(config["num_layers"])
    flops = 2.0 * live_pairs(config, seq) * ((hd + hd) + 2 * (hd + hd)) * nh
    elements = 2 * seq * hd * (nh * (2 + 4) + nkv * (2 + 4))
    return {"flops": flops * rows * layers,
            "bytes": float(elements * _itemsize(config) * rows * layers)}


def grouped_work(config: dict, pairs: float, layer_steps: float = 1) -> dict:
    """What the routed experts' grouped products require for `pairs` (row,
    held expert) rows in all, spread over `layer_steps` runs of an expert
    layer (layers x steps), forward and backward: three products forward
    (gate, up, down) and six backward (each product's two gradients); the
    bytes of each product's row operand and result once, and of each held
    expert's matrix once a product and run."""
    h, ie = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    weights = int(config["num_experts"]) * h * ie * layer_steps
    return {"flops": 9 * 2.0 * pairs * h * ie,
            "bytes": 9.0 * _itemsize(config) * (pairs * (h + ie) + weights)}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def decoder_config(config: dict):
    import dataclasses
    from benchmark.harness import BenchmarkError
    from deeplearning4j_tpu.zoo import DecoderConfig
    if "objective" not in {f.name for f in dataclasses.fields(DecoderConfig)}:
        raise BenchmarkError(
            "this program's zoo.DecoderConfig has no `objective`: it cannot "
            "train by block diffusion, so it cannot run this configuration")
    layers = int(config["num_layers"])
    return DecoderConfig(
        vocab_size=int(config["vocab_size"]),
        hidden=int(config["hidden_size"]),
        n_layers=layers,
        n_dense_layers=len(config["mlp_only_layers"]),
        layer_types=("full_attention",) * layers,
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
        eps=float(config["rms_norm_eps"]),
        init_std=float(config["init_std"]),
        embedding_init_std=float(config["embedding_init_std"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        compute_dtype=config["compute_dtype"],
        objective="block_diffusion",
        block_length=int(config["block_length"]),
        mask_token_id=int(config["mask_token_id"]),
        noise_eps=float(config["noise_eps"]))


def build(config: dict, seed: int, serving: bool = False):
    """`zoo.DecoderModel` with the file's sizes and share, parameters
    initialised on the device from `seed` — which is also the seed of the
    noise its steps draw."""
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


def make_pool(config: dict, traffic: dict, seed: int, rows: int):
    """`pool_batches` host batches of `rows` clean sequences of `seq_len`
    ids drawn from a Zipf distribution over the ids held below the mask id
    (data never holds the mask token); the labels repeat the ids: a
    position's target is its own clean token, and the objective reads the
    ids alone."""
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    rng = np.random.default_rng(seed)
    pool = []
    for _ in range(int(traffic["pool_batches"])):
        ids = zipf_ids(rng, int(config["mask_token_id"]),
                       float(traffic["zipf_exponent"]),
                       (rows, int(traffic["seq_len"])))
        pool.append(MultiDataSet(features=[ids], labels=[ids]))
    return pool


def reference_noise(config: dict, ids, seed: int):
    """`(noisy ids, weight)` for clean `ids` [rows, L], by the objective's
    rule and numpy's generator: a `t ~ U(noise_eps, 1)` a block, each token
    replaced by the mask id with probability `t`; `weight` is `1/t` at a
    replaced position, 0 elsewhere."""
    ids = np.asarray(ids)
    rows, seq = ids.shape
    blk = int(config["block_length"])
    rng = np.random.default_rng(seed)
    t = np.repeat(rng.uniform(float(config["noise_eps"]), 1.0,
                              (rows, seq // blk)), blk, axis=1)
    replaced = rng.random((rows, seq)) < t
    return (np.where(replaced, int(config["mask_token_id"]), ids).astype(
        np.int32), np.where(replaced, 1.0 / t, 0.0).astype(np.float32))


def _weighted_ce(logits, ids, weight) -> float:
    """`mean over rows of (1/L) sum_i weight_i * -log softmax(logits_i)
    [ids_i]` in float64."""
    z = np.asarray(logits, np.float64)
    z = z - z.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    nll = -np.take_along_axis(logp, np.asarray(ids)[..., None], -1)[..., 0]
    return float((np.asarray(weight, np.float64) * nll).sum(1).mean()
                 / nll.shape[1])


def eval_loss(model, batch, rows: int) -> float:
    """The masked-token loss of the batch's first `rows` sequences under a
    FIXED noise key: the same positions replaced before the window and
    after it.  The driver calls it right before the measured window and
    right after: the first call also notes where the step's counters stood
    (copies on the device — the step donates its state; nothing is
    transferred)."""
    global _AT_WINDOW_START
    import jax
    import jax.numpy as jnp
    if _AT_WINDOW_START is None:
        _AT_WINDOW_START = {name: jnp.copy(model.state_[name])
                            for name in ("expert_load", "masked_positions")}
    return float(model.diffusion_loss(_slice(batch, rows)[0],
                                      jax.random.PRNGKey(EVAL_NOISE_SEED)))


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


def window_masked_positions(model) -> int:
    """Positions that were replaced by the mask token and carried loss
    between the start of the measured window and now."""
    return int(_since_window_start(model, "masked_positions"))


def reference_check(model, config: dict, batch, rows: int) -> dict:
    """The system's noisy-half logits on `rows` sequences, under noise drawn
    here, against `reference_forward` on the same parameters and the same
    noisy ids.  `rel_err` is the root mean square of the difference over
    all logits, over the root mean square of the reference's logits (the
    config's `tolerance.why` says why)."""
    ids = _slice(batch, rows)[0]
    noisy, weight = reference_noise(config, ids, EVAL_NOISE_SEED)
    got = np.asarray(model.output(ids, noisy_ids=noisy), np.float32)
    want = np.asarray(reference_jitted(config, model.params_, ids, noisy),
                      np.float32)
    return {"rel_err": rel_rms(got, want),
            "tol": float(config["tolerance"]["output_rel"]),
            "loss": _weighted_ce(got, ids, weight),
            "loss_reference": _weighted_ce(want, ids, weight),
            "loss_tol": float(config["tolerance"]["loss_rel"])}


def reference_jitted(config: dict, params, ids, noisy_ids, round_to=None):
    """`reference_forward` with the block and the head under `jax.jit`: the
    layers, all alike, share one compilation (a Python loop over the held
    experts takes the chip's compiler seconds a layer)."""
    import functools
    import jax
    block = jax.jit(functools.partial(reference_block, config),
                    static_argnames=("round_to",))
    head = jax.jit(functools.partial(reference_head, config),
                   static_argnames=("round_to",))
    return reference_forward(
        config, params, ids, noisy_ids, round_to,
        block=lambda _, x, lp, m, r: block(x, lp, m, round_to=r),
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

QUERY_BLOCK = 512       # attention is computed this many queries at a time


def block_mask(seq: int, blk: int) -> np.ndarray:
    """The boolean [2 seq, 2 seq] mask of BD3-LM, `[[M_BD, M_OBC], [0,
    M_BC]]` over rows and columns `[noisy ; clean]`: `M_BD` block-diagonal
    (noisy on noisy, same block), `M_OBC` offset block-causal (noisy on
    clean, earlier blocks), `M_BC` block-causal (clean on clean, its block
    and earlier), and no clean row on a noisy column."""
    b = np.arange(seq) // blk
    m_bd = b[:, None] == b[None, :]
    m_obc = b[None, :] < b[:, None]
    m_bc = b[None, :] <= b[:, None]
    return np.block([[m_bd, m_obc], [np.zeros((seq, seq), bool), m_bc]])


def reference_block(config: dict, x, lp, mask, round_to=None):
    """One block on `x` [rows, 2L, H] (float32) under the boolean `mask`
    [2L, 2L]: `h = x + Wo Attn(q, k, v)`, `y = h + MoE(RMSNorm(h))`; beside
    `y` the layer's balance term `E sum_e f_e P_e`.  See
    `reference_forward`."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = float(config["rms_norm_eps"])
    h, nh, nkv, hd = _dims(config)
    group = nh // nkv
    top_k = int(config["num_experts_per_tok"])
    first = int(config["first_expert_held"])
    base = float(config["rope_theta"])

    def mm(a, b):
        if round_to is not None:
            a, b = (v.astype(round_to).astype(f32) for v in (a, b))
        return a @ b

    def rms(v, g):
        return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + eps) * g

    def silu(v):
        return v / (1.0 + jnp.exp(-v))

    def rope(v, pos):
        """v [rows, T, heads, hd]: pair (v[i], v[i + hd/2]) turned by
        pos * base^(-2i/hd), i in 0 .. hd/2 - 1."""
        half = hd // 2
        inv = base ** (-2.0 * jnp.arange(half, dtype=f32) / hd)
        ang = pos.astype(f32)[:, None] * inv[None]              # [T, hd/2]
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        lo, hi = v[..., :half], v[..., half:]
        return jnp.concatenate([lo * cos - hi * sin, lo * sin + hi * cos], -1)

    def attention(u):
        b, t, _ = u.shape
        pos = jnp.arange(t) % (t // 2)      # both copies at 0 .. L-1
        w = lp["Wqkv"]              # [H, (nh + 2 nkv) hd]: W_q | W_k | W_v
        q = mm(u, w[:, :nh * hd]).reshape(b, t, nh, hd)
        k = mm(u, w[:, nh * hd:(nh + nkv) * hd]).reshape(b, t, nkv, hd)
        v = mm(u, w[:, (nh + nkv) * hd:]).reshape(b, t, nkv, hd)
        q = rope(rms(q, lp["q_norm"]), pos)
        k = rope(rms(k, lp["k_norm"]), pos)
        if round_to is not None:
            q, k, v = (a.astype(round_to).astype(f32) for a in (q, k, v))
        # query head i attends key-value head i // group
        of_head = jnp.arange(nh) // group
        k, v = k[:, :, of_head], v[:, :, of_head]
        outs = []
        for q0 in range(0, t, QUERY_BLOCK):
            qb = q[:, q0:q0 + QUERY_BLOCK]
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / np.sqrt(hd)
            s = jnp.where(mask[q0:q0 + QUERY_BLOCK], s, -jnp.inf)
            outs.append(jnp.einsum("bhqk,bkhd->bqhd",
                                   jax.nn.softmax(s, -1), v))
        return mm(jnp.concatenate(outs, 1).reshape(b, t, nh * hd), lp["Wo"])

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
        x = x + attention(rms(x, lp["norm1"]))
        y, balance = moe(rms(x, lp["norm2"]))
        return x + y, balance


def reference_head(config: dict, x, final_norm, head, round_to=None):
    """RMSNorm, then the untied head: logits [rows, T, vocab held]."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                         + float(config["rms_norm_eps"])) * final_norm
        if round_to is not None:
            x, head = (v.astype(round_to).astype(jnp.float32)
                       for v in (x, head))
        return x @ head


def _layers(params):
    """Each held layer's parameters, in order, out of the system's pytree:
    `moe` stacked over the layers (all alike: a period of one)."""
    import jax
    n = params["moe"]["Wqkv"].shape[0]
    return [jax.tree_util.tree_map(lambda a: a[i], params["moe"])
            for i in range(n)]


def _hidden(config, params, ids, noisy_ids, round_to, block):
    """The residual stream after the last block on the 2L rows `[noisy ;
    clean]`, float32, and each layer's balance term."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, f32), params)
    ids = jnp.asarray(ids, jnp.int32)
    seq = ids.shape[1]
    mask = jnp.asarray(block_mask(seq, int(config["block_length"])))
    x = p["tok_emb"][jnp.concatenate(
        [jnp.asarray(noisy_ids, jnp.int32), ids], axis=1)]
    balance = []
    for lp in _layers(p):
        x, b = block(config, x, lp, mask, round_to)
        balance.append(b)
    return p, x, balance


def reference_forward(config: dict, params, ids, noisy_ids, round_to=None,
                      block=reference_block, head=reference_head):
    """Logits [rows, L, vocab held] of the NOISY copy's rows, in float32 at
    highest matmul precision.

    Embedding lookup of the 2L ids `[noisy ; clean]`, no position
    embedding.  Per layer `l` (all alike, no dense layer): `h = x + Wo
    Attn(q, k, v)` on `u = RMSNorm(x)` with 32 query heads over 4 key-value
    heads of 128, RMSNorm of every query and key head (one gain each),
    half-split rotary at `r mod L`, softmax over the columns the explicit
    boolean mask (`block_mask`) keeps; `y = h + sum_{e in top8(p)} (p_e /
    sum_chosen p) SwiGLU_e(RMSNorm(h))`, `p = softmax(Wr RMSNorm(h))` over
    all `num_experts_published`, no bias, no scale; of the chosen experts
    only those held (`first_expert_held` .. + `num_experts`) are summed.
    Then RMSNorm and the untied head on the first L rows: the clean half
    needs none.

    `round_to` (a dtype) rounds both operands of every matrix product to it
    first (the router's stays float32, as the configuration states): the
    reference "computed in a lower precision", which the tolerance has to
    refuse.  `block`/`head`: the same two functions wrapped, e.g. in
    `jax.jit` so that the layers compile once."""
    p, x, _ = _hidden(config, params, ids, noisy_ids, round_to, block)
    seq = np.asarray(ids).shape[1]
    return head(config, x[:, :seq], p["final_norm"], p["head"], round_to)


def reference_loss(config: dict, params, ids, noisy_ids, weight):
    """The step's loss for given noise: the weighted cross-entropy of the
    noisy half's logits against the clean ids, `mean over rows of (1/L)
    sum_i weight_i nll_i`, plus `router_aux_loss_coef` times the layers'
    mean balance term; `jax.grad` of it is the reference's gradient."""
    import jax
    import jax.numpy as jnp
    p, x, balance = _hidden(config, params, ids, noisy_ids, None,
                            reference_block)
    ids = jnp.asarray(ids, jnp.int32)
    logits = reference_head(config, x[:, :ids.shape[1]], p["final_norm"],
                            p["head"])
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               ids[..., None], -1)[..., 0]
    loss = jnp.mean(jnp.sum(jnp.asarray(weight) * nll, 1) / ids.shape[1])
    return loss + float(config["router_aux_loss_coef"]) * jnp.mean(
        jnp.stack(balance))
