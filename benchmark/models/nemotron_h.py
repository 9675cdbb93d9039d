"""Family `nemotron_h`: decoder-only language models whose public config says
`model_type: nemotron_h` (NVIDIA's Nemotron-H, arXiv:2504.03624) — Mamba-2
layers (the state-space dual, arXiv:2405.21060) beside expert layers of
squared-ReLU experts and a few grouped-query attention layers with no
positions, each layer ONE residual sub-block, in the order
`hybrid_override_pattern` gives — on the train path, through the program's
`zoo.DecoderModel`.

The layers, for the residual stream `x` [T, H]: `x <- x + Op(RMSNorm(x))`,
with `Op` by the pattern's character:

- `M`, Mamba-2, `I = mamba_num_heads x mamba_head_dim` channels: `[z | xBC |
  dt] = h W_in` (widths I, I + 2 n_groups ssm_state_size, heads); `xBC =
  SiLU(conv4(xBC) + b)` (causal, depthwise); `x | B | C` as heads of
  `mamba_head_dim` and `n_groups` groups of `ssm_state_size` (head a reads
  group a // (heads / groups)); `dt = softplus(dt + dt_bias)`, `A =
  -exp(A_log)` a head; `S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T`, `y_t =
  S_t^T C_t + D x_t`; `y = RMSNorm_grouped(y * SiLU(z))` over `n_groups`
  groups; then `W_out`.  No projection has a bias.
- `E`: sigmoid scores in float32, the `num_experts_per_tok` largest of score
  + selection bias chosen, the chosen scores normalised (1e-20 added) and
  times `routed_scaling_factor`; experts `W_down relu(W_up h)^2`, and one
  shared expert of the same form that sees every token.
- `*`: causal softmax attention, `num_attention_heads` query heads over
  `num_key_value_heads` of `head_dim`, no rotary, no norm of queries or keys,
  no gate.

Program side: `build` and the adapters the drivers call.  Yardstick side:
`flops_per_item`, the kernels' operation and byte counts (from shapes), and
`reference_forward` / `reference_loss` (plain `jax.numpy`, float32, highest
matmul precision; Mamba-2 as its TOKEN-BY-TOKEN recurrence, `lax.scan` over
t with the state [rows, heads, N, P]; attention a masked softmax a block of
queries at a time; the experts a Python loop; no chunks, no kernels), which
read the system's own parameter pytree and follow the equations above and
the config's keys, not the program's code.

A configuration is one chip's share of a deployment (`deployment`): the
routed experts `first_expert_held ..` + `n_routed_experts` of
`n_routed_experts_published`, the first `vocab_size` ids, the layers
`layer_pattern_held` of the published pattern from `first_layer_held` on.
The reference is given the same share and, like the program, computes the
held experts' part and leaves the other chips' terms out.

What every decoder family of this benchmark does alike (Zipf ids, the pool,
the loss of a set of logits, the relative rms) is `models/deepseek_v3.py`'s
and is imported; the state a run keeps is this module's own, because
`trace/scopes.py` and the readers find it by the family's name.
"""
from __future__ import annotations

import numpy as np

from benchmark.models.deepseek_v3 import (  # noqa: F401  (the drivers' API)
    _next_token_ce, _schedule, _slice, items_per_row, last_loss, make_pool,
    parameters, rel_rms, step_hook)

# the newest model `build` made: the per-layer readers find the program
# through the cell's family (`harness.load_family(run.cell.config)`)
LAST_BUILT = None
# the newest train step `lower_step` lowered: `trace/scopes.py` compiles it
# again — a cache hit — for the scope of each instruction in the trace
LAST_LOWERED = None
# the routing counter at the start of the measured window
_LOAD_AT_WINDOW_START = None

KINDS = {"M": "mamba2", "E": "moe", "*": "attention"}


# ---------------------------------------------------------------------------
# shapes: required work
# ---------------------------------------------------------------------------

def held_pattern(config: dict) -> str:
    """The pattern of the layers held here: `num_layers` of the published
    `hybrid_override_pattern` from `first_layer_held` on, which the file
    states again as `layer_pattern_held`."""
    first, n = int(config["first_layer_held"]), int(config["num_layers"])
    held = config["hybrid_override_pattern"][first:first + n]
    if held != config["layer_pattern_held"] or len(held) != n:
        raise ValueError(f"layers {first}..{first + n} of the pattern are "
                         f"{held!r}, not {config['layer_pattern_held']!r}")
    return held


def _ssm(config: dict):
    """(heads, head width, groups, state width, conv taps, chunk)."""
    return (int(config["mamba_num_heads"]), int(config["mamba_head_dim"]),
            int(config["n_groups"]), int(config["ssm_state_size"]),
            int(config["conv_kernel"]), int(config["chunk_size"]))


def _itemsize(config: dict) -> int:
    return 4 if config["compute_dtype"] == "float32" else 2


def held_per_token(config: dict) -> float:
    """Routed experts a token needs of those held here, in expectation
    under even routing: top-k x held / router width."""
    return (int(config["num_experts_per_tok"]) * int(config["n_routed_experts"])
            / int(config["n_routed_experts_published"]))


def ssd_chunk_flops(config: dict) -> float:
    """Forward products of the chunked SSD for ONE chunk of `chunk_size`
    tokens: `C B^T` a group over the lower pairs (N each), and a head `(C
    B^T o L) X` over the lower pairs (P each), the state the chunk writes
    and the state's output (N P a token each); two FLOPs a multiply-add."""
    n, p, g, s, _, c = _ssm(config)
    lower = c * (c + 1) // 2
    return 2.0 * (g * lower * s + n * (lower * p + 2 * c * s * p))


def layer_flops_per_token(config: dict, seq: int, kind: str) -> dict:
    """Forward FLOPs one token of a `seq`-token sequence requires of one
    held layer of `kind`, by part.  Causal attention is the lower triangle;
    the SSD at the config's chunk; routed experts at the expected share of
    the chosen experts that is held."""
    h = int(config["hidden_size"])
    if kind == "mamba2":
        n, p, g, s, _, c = _ssm(config)
        i = n * p
        return {"mamba_products": 2.0 * (h * (2 * i + 2 * g * s + n) + i * h),
                "ssd": ssd_chunk_flops(config) / c}
    if kind == "attention":
        nh, nkv = (int(config["num_attention_heads"]),
                   int(config["num_key_value_heads"]))
        hd = int(config["head_dim"])
        return {"gqa_products": 2.0 * (h * (nh + 2 * nkv) * hd + nh * hd * h),
                "attention": 2.0 * nh * (hd + hd) * (seq + 1) / 2.0}
    ie = int(config["moe_intermediate_size"])
    return {"shared": 2.0 * 2 * h * int(config[
                "moe_shared_expert_intermediate_size"])
            * int(config["n_shared_experts"]),
            "routed": 2.0 * 2 * h * ie * held_per_token(config),
            "router": 2.0 * h * int(config["n_routed_experts_published"])}


def flops_per_item(config: dict, traffic: dict, training: bool = True) -> float:
    """FLOPs one sequence requires: every held layer's products, causal
    attention as the lower triangle, the SSD's chunked products, routed
    experts at the expected share held, the untied head over the
    vocabulary held; no recomputation.  Training is 3x the forward.
    Lookups, norms, the convolution's taps, gates, the decays, top-k,
    sorting and the updater are not counted: the roofline it is set against
    is the MXU's."""
    seq = int(traffic["seq_len"])
    per_token = 2.0 * int(config["hidden_size"]) * int(config["vocab_size"])
    for ch in held_pattern(config):
        parts = layer_flops_per_token(config, seq, KINDS[ch])
        per_token += sum(parts.values())
    return (3.0 if training else 1.0) * seq * per_token


def ssd_work(config: dict, traffic: dict, rows: int) -> dict:
    """What the SSD requires of one train step of `rows` sequences over the
    held Mamba-2 layers: `flops`, the chunked algorithm's products at the
    config's chunk (`ssd_chunk_flops`) forward and twice that backward, for
    every chunk; and `bytes` in float32 (the scan's dtype): x, B, C and dt
    read and y written once forward; x, B, C, dt and dy read and dx, dB, dC
    and ddt written once backward.  The same work whatever implements it."""
    n, p, g, s, _, c = _ssm(config)
    seq = int(traffic["seq_len"])
    layers = held_pattern(config).count("M")
    chunks = -(-seq // c)
    inputs = n * p + 2 * g * s + n            # x, B, C, dt a token
    elements = seq * (inputs + n * p) + seq * (inputs + n * p + inputs)
    return {"flops": 3 * chunks * ssd_chunk_flops(config) * rows * layers,
            "bytes": 4.0 * elements * rows * layers}


def gqa_attention_work(config: dict, traffic: dict, rows: int) -> dict:
    """What causal grouped-query attention requires of one train step of
    `rows` sequences over the held `*` layers: two products forward and
    four backward over the lower triangle for every query head; q, o, dO
    and dQ moved once a query head, k, v, dK and dV once a KEY-VALUE head,
    in the compute dtype (`models/lfm2_moe.py`'s rule)."""
    nh, nkv = (int(config["num_attention_heads"]),
               int(config["num_key_value_heads"]))
    hd = int(config["head_dim"])
    seq = int(traffic["seq_len"])
    layers = held_pattern(config).count("*")
    pairs = seq * (seq + 1) / 2.0
    flops = 2.0 * pairs * ((hd + hd) + 2 * (hd + hd)) * nh
    elements = seq * hd * (nh * (2 + 4) + nkv * (2 + 4))
    return {"flops": flops * rows * layers,
            "bytes": float(elements * _itemsize(config) * rows * layers)}


def grouped_work(config: dict, pairs: float, layer_steps: float = 1) -> dict:
    """What the routed experts' grouped products require for `pairs`
    (token, held expert) rows in all, spread over `layer_steps` runs of an
    expert layer (layers x steps), forward and backward: the squared-ReLU
    expert's two products forward and four backward; the bytes of each
    product's row operand and result once, and of each held expert's matrix
    once a product and run."""
    h, ie = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    weights = int(config["n_routed_experts"]) * h * ie * layer_steps
    return {"flops": 6 * 2.0 * pairs * h * ie,
            "bytes": 6.0 * _itemsize(config) * (pairs * (h + ie) + weights)}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def out_proj_init_std(config: dict) -> float:
    """`rescale_prenorm_residual`: W_out with the variance of PyTorch's
    `kaiming_uniform_(a=sqrt(5))` at a fan-in of I, `1 / (3 I)`, over
    `num_hidden_layers`."""
    n, p = _ssm(config)[:2]
    return (3.0 * n * p) ** -0.5 / float(config["num_hidden_layers"]) ** 0.5


def decoder_config(config: dict):
    from benchmark.harness import BenchmarkError
    from deeplearning4j_tpu.zoo import DecoderConfig
    from deeplearning4j_tpu.zoo import decoder
    if "mamba2" not in decoder.LAYER_KINDS:
        raise BenchmarkError(
            "this program's zoo.DecoderModel has no `mamba2` layer: it "
            "cannot run this configuration")
    if (config["mamba_hidden_act"], config["mlp_hidden_act"]) \
            != ("silu", "relu2") or not config["use_conv_bias"] \
            or config["mamba_proj_bias"] or config["attention_bias"] \
            or config["mlp_bias"] or int(config["n_group"]) != 1 \
            or int(config["topk_group"]) != 1 \
            or config["time_step_limit"] != [0, None]:
        raise ValueError("the program's Mamba-2 mixer is SiLU with a bias "
                         "on the convolution alone, its experts relu2, one "
                         "router group, and no limit on dt")
    n, p, g, s, taps, chunk = _ssm(config)
    return DecoderConfig(
        vocab_size=int(config["vocab_size"]),
        hidden=int(config["hidden_size"]),
        n_layers=int(config["num_layers"]),
        n_dense_layers=0,
        layer_pattern=held_pattern(config),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        ssm_heads=n, ssm_head_dim=p, ssm_groups=g, ssm_state=s,
        ssm_chunk=chunk, conv_kernel=taps,
        out_proj_init_std=out_proj_init_std(config),
        expert_intermediate=int(config["moe_intermediate_size"]),
        shared_intermediate=int(
            config["moe_shared_expert_intermediate_size"]),
        expert_act="relu2",
        n_experts=int(config["n_routed_experts_published"]),
        n_shared_experts=int(config["n_shared_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        routed_scale=float(config["routed_scaling_factor"]),
        router_eps=float(config["router_eps"]),
        first_expert=int(config["first_expert_held"]),
        n_experts_held=int(config["n_routed_experts"]),
        eps=float(config["norm_eps"]),
        bias_update_speed=float(config["bias_update_speed"]),
        init_std=float(config["init_std"]),
        embedding_init_std=float(config["embedding_init_std"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        compute_dtype=config["compute_dtype"],
        rope=False, qk_norm=False, attn_output_gate=False)


def build(config: dict, seed: int, serving: bool = False):
    """`zoo.DecoderModel` with the file's sizes, pattern and share,
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
    (a copy on the device — the step donates its state)."""
    global _LOAD_AT_WINDOW_START
    if _LOAD_AT_WINDOW_START is None:
        import jax.numpy as jnp
        _LOAD_AT_WINDOW_START = jnp.copy(model.state_["expert_load"])
    ids, labels = _slice(batch, rows)
    return _next_token_ce(model.output(ids), labels)


def window_expert_load(model) -> np.ndarray:
    """[expert layers, router width] rows that chose each expert between
    the start of the measured window and now: both ends read in one
    transfer."""
    import jax
    now = model.state_["expert_load"]
    if _LOAD_AT_WINDOW_START is None:
        return np.asarray(now)
    start, now = jax.device_get((_LOAD_AT_WINDOW_START, now))
    return now - start


def window_held_load(model) -> np.ndarray:
    """`window_expert_load` of the experts held here: [expert layers, held]
    (row, held expert) pairs, the rows the grouped products ran on."""
    c = model.config
    return window_expert_load(model)[
        :, c.first_expert:c.first_expert + c.held]


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

QUERY_BLOCK = 512       # attention's scores are computed this many queries
                        # at a time


def _rounded(round_to, *vs):
    """`vs` (float32) rounded to the dtype `round_to` and back; themselves
    without one."""
    import jax.numpy as jnp
    if round_to is None:
        return vs
    return tuple(v.astype(round_to).astype(jnp.float32) for v in vs)


def _mm(round_to):
    def mm(a, b):
        a, b = _rounded(round_to, a, b)
        return a @ b
    return mm


def _rms(config, v, g):
    import jax.numpy as jnp
    return v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True)
                        + float(config["norm_eps"])) * g


def reference_mamba(config: dict, u, lp, round_to=None):
    """The Mamba-2 mixer on the normed `u` [rows, T, H] (float32): `y`
    [rows, T, H].  The state-space recurrence token by token: `lax.scan`
    over t, the state [rows, heads, N, P]."""
    import jax
    import jax.numpy as jnp

    n, p, g, s, taps, _ = _ssm(config)
    rows, t, _ = u.shape
    i = n * p
    mm = _mm(round_to)
    zxbcdt = mm(u, lp["in_proj"])
    z, xbc, dt = zxbcdt[..., :i], zxbcdt[..., i:-n], zxbcdt[..., -n:]
    conv = lp["conv_bias"]
    for j in range(taps):          # out_t = b + sum_j w[j] xbc_{t-(taps-1)+j}
        by = taps - 1 - j
        shifted = xbc if by == 0 else jnp.concatenate(
            [jnp.zeros_like(xbc[:, :by]), xbc[:, :-by]], 1)
        conv = conv + lp["conv_xbc"][j] * shifted
    xbc = conv / (1.0 + jnp.exp(-conv))
    x = xbc[..., :i].reshape(rows, t, n, p)
    B = xbc[..., i:i + g * s].reshape(rows, t, g, s)
    C = xbc[..., i + g * s:].reshape(rows, t, g, s)
    x, B, C = _rounded(round_to, x, B, C)
    pre = dt + lp["dt_bias"]
    dt = jnp.maximum(pre, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(pre)))
    A = -jnp.exp(lp["A_log"])
    of_head = jnp.arange(n) // (n // g)

    def step(state, xs):
        x_t, dt_t, b_t, c_t = xs              # [rows, n, p], [rows, n], ..
        b_t, c_t = b_t[:, of_head], c_t[:, of_head]        # [rows, n, s]
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + dt_t[..., None, None] * b_t[..., :, None]
                 * x_t[..., None, :])
        return state, jnp.einsum("rns,rnsp->rnp", c_t, state)

    _, y = jax.lax.scan(step, jnp.zeros((rows, n, s, p), jnp.float32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    y = jnp.moveaxis(y, 0, 1) + lp["D"][:, None] * x      # [rows, T, n, p]
    y = y.reshape(rows, t, i) * (z / (1.0 + jnp.exp(-z)))
    y = y.reshape(rows, t, g, i // g)
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True)
                     + float(config["norm_eps"]))
    return mm(y.reshape(rows, t, i) * lp["ssm_norm"], lp["out_proj"])


def reference_attention(config: dict, u, lp, round_to=None):
    """NoPE grouped-query attention on the normed `u` [rows, T, H]
    (float32): query head a on key-value head a // group, causal, scale
    head_dim^-1/2."""
    import jax
    import jax.numpy as jnp

    nh, nkv = (int(config["num_attention_heads"]),
               int(config["num_key_value_heads"]))
    hd = int(config["head_dim"])
    rows, t, _ = u.shape
    mm = _mm(round_to)
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
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * hd ** -0.5
        qi = q0 + jnp.arange(qb.shape[1])[:, None]
        sc = jnp.where(qi >= jnp.arange(t)[None, :], sc, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v))
    return mm(jnp.concatenate(outs, 1).reshape(rows, t, nh * hd), lp["Wo"])


def reference_moe(config: dict, u, lp, bias, round_to=None):
    """The expert layer on the normed `u` [rows, T, H]: sigmoid scores, the
    `num_experts_per_tok` largest of score + bias chosen, the chosen scores
    normalised (`router_eps` added) and scaled; of the chosen experts those
    held summed; plus the shared expert; every expert `relu(u W_up)^2
    W_down`."""
    import jax
    import jax.numpy as jnp
    top_k = int(config["num_experts_per_tok"])
    first = int(config["first_expert_held"])
    mm = _mm(round_to)

    def ffn(wu, wd):
        return mm(jnp.square(jnp.maximum(mm(u, wu), 0.0)), wd)

    sc = jax.nn.sigmoid(u @ lp["router"])                      # float32
    _, chosen = jax.lax.top_k(sc + bias, top_k)
    w = jnp.take_along_axis(sc, chosen, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + float(config["router_eps"])) \
        * float(config["routed_scaling_factor"])
    y = ffn(lp["shared_up"], lp["shared_down"])
    for e in range(lp["w_up"].shape[0]):                       # held experts
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), -1)
        y = y + w_e[..., None] * ffn(lp["w_up"][e], lp["w_down"][e])
    return y


def reference_block(config: dict, x, lp, bias, round_to=None):
    """One layer on `x` [rows, T, H] (float32): `x + Op(RMSNorm(x))`, `Op`
    Mamba-2 where `lp` holds `in_proj`, the expert layer where it holds
    `router`, attention otherwise."""
    import jax
    with jax.default_matmul_precision("highest"):
        u = _rms(config, x, lp["norm1"])
        if "in_proj" in lp:
            return x + reference_mamba(config, u, lp, round_to)
        if "router" in lp:
            return x + reference_moe(config, u, lp, bias, round_to)
        return x + reference_attention(config, u, lp, round_to)


def reference_head(config: dict, x, final_norm, head, round_to=None):
    """RMSNorm, then the untied head: logits [rows, T, vocab held]."""
    import jax
    with jax.default_matmul_precision("highest"):
        return _mm(round_to)(_rms(config, x, final_norm), head)


def layers_of(config: dict, params, router_bias):
    """`(kind, layer's parameters, selection bias or None)` of each held
    layer in order, out of the system's pytree: `moe` stacked over whole
    periods of the pattern (a tuple of one dict a layer of the period),
    `rest` the layers after the last whole period; the router bias has a row
    an `E` layer."""
    import jax

    def at(tree, i):
        return jax.tree_util.tree_map(lambda a: a[i], tree)

    period = params["moe"] if isinstance(params["moe"], (tuple, list)) \
        else (params["moe"],)
    periods = jax.tree_util.tree_leaves(period[0])[0].shape[0]
    layers = [at(lp, n) for n in range(periods) for lp in period]
    layers += list(params.get("rest", ()))
    kinds = [KINDS[ch] for ch in held_pattern(config)]
    found = ["mamba2" if "in_proj" in lp else "moe" if "router" in lp
             else "attention" for lp in layers]
    if found != kinds:
        raise ValueError(f"the parameters hold the layers {found}, not the "
                         f"configuration's {kinds}")
    out, row = [], 0
    for kind, lp in zip(kinds, layers):
        out.append((kind, lp, router_bias[row] if kind == "moe" else None))
        row += kind == "moe"
    return out


def reference_forward(config: dict, params, router_bias, ids, round_to=None,
                      block=reference_block, head=reference_head):
    """Logits [rows, T, vocab held] in float32 at highest matmul precision:
    embedding lookup, no position embedding, the held layers (module
    docstring), RMSNorm and the untied head.

    `round_to` (a dtype) rounds both operands of every matrix product to it
    first, the SSD's x, B and C, and q, k, v before the attention (the
    router's product stays float32, as the configuration states): the
    reference "computed in a lower precision", which the tolerance has to
    refuse.  `block`/`head`: the same two functions wrapped, e.g. in
    `jax.jit`."""
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
