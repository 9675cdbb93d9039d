"""Family `bert`: BERT encoders (Devlin et al. 2018, arXiv:1810.04805) with
the masked-LM head, through the program's `zoo.BertModel`.

Program side: `build` and the adapters the drivers call.  Yardstick side:
`flops_per_item` (from shapes) and `reference_forward` (plain `jax.numpy`,
float32, highest matmul precision, no kernels, no scan), which reads the
system's own parameter pytree and follows the paper and google-research/bert
`modeling.py`, not the program's code.
"""
from __future__ import annotations

import numpy as np


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def layer_flops_per_token(hidden: int, intermediate: int, seq: int) -> float:
    """Forward FLOPs of one encoder layer for one token of a `seq`-token
    sequence: the four H x H projections, the two FFN products, and
    attention's two s x d products per head (scores and weighted values)."""
    dense = 2.0 * (4 * hidden * hidden + 2 * hidden * intermediate)
    attention = 2.0 * 2 * seq * hidden
    return dense + attention


def flops_per_item(config: dict, traffic: dict, training: bool = True) -> float:
    """FLOPs one sequence requires: per token, every encoder layer; per
    masked position (`mask_rate` of the tokens), the MLM head's H x H
    transform and the H x vocab output product — the loss needs logits
    nowhere else, so a program that computes them everywhere does work this
    count leaves out.  Training is 3x the forward (two backward products per
    forward product).  Embedding lookups, norms, softmax and the updater are
    not counted: the roofline it is set against is the MXU's."""
    h, i = int(config["hidden_size"]), int(config["intermediate_size"])
    seq = int(traffic["seq_len"])
    per_token = int(config["num_hidden_layers"]) * layer_flops_per_token(
        h, i, seq)
    head = 2.0 * (h * h + h * int(config["vocab_size"]))
    fwd = seq * (per_token + float(traffic["mask_rate"]) * head)
    return 3.0 * fwd if training else fwd


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def bert_config(config: dict, max_len: int):
    from deeplearning4j_tpu.zoo import BertConfig
    return BertConfig(
        vocab_size=int(config["vocab_size"]),
        hidden=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        intermediate=int(config["intermediate_size"]),
        max_len=int(max_len), type_vocab=int(config["type_vocab_size"]),
        eps=float(config["layer_norm_eps"]),
        compute_dtype=config["compute_dtype"])


def build(config: dict, seed: int, serving: bool = False):
    """`zoo.BertModel` with the file's sizes, parameters initialised on the
    device from `seed`."""
    from deeplearning4j_tpu.train import updaters
    from deeplearning4j_tpu.zoo import BertModel
    u = config["updater"]
    return BertModel(
        bert_config(config, config["max_position_embeddings"]),
        seed=int(seed), updater=getattr(updaters, u["kind"])(*u["args"]))


def make_pool(config: dict, traffic: dict, seed: int, rows: int):
    """`pool_batches` host batches of `rows` sequences of `seq_len` random
    token ids, a full attention mask, the ids themselves as labels and a
    label mask on `mask_rate` of the positions."""
    from deeplearning4j_tpu.data.dataset import MultiDataSet
    rng = np.random.default_rng(seed)
    t = int(traffic["seq_len"])
    pool = []
    for _ in range(int(traffic["pool_batches"])):
        ids = rng.integers(0, int(config["vocab_size"]), (rows, t),
                           dtype=np.int32)
        lmask = (rng.random((rows, t)) < float(traffic["mask_rate"])
                 ).astype(np.float32)
        pool.append(MultiDataSet(
            features=[ids, np.ones((rows, t), np.float32)], labels=[ids],
            labels_masks=[lmask]))
    return pool


def items_per_row(config: dict, traffic: dict) -> dict:
    return {"samples": 1, "tokens": int(traffic["seq_len"])}


def step_hook(model, hook) -> bool:
    return False           # `BertModel.fit` has no listener: the driver's
                           # iterator calls the hook between steps


def last_loss(model):
    """The newest minibatch loss as a device scalar; no host sync.  The
    program keeps it in `_score` and offers only the blocking `score()`."""
    return getattr(model, "_score", None)


def parameters(model):
    return model.params_


def _masked_ce(logits, labels, lmask) -> float:
    z = np.asarray(logits, np.float64)
    z = z - z.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    per = -np.take_along_axis(logp, np.asarray(labels)[..., None], -1)[..., 0]
    m = np.asarray(lmask, np.float64)
    return float((per * m).sum() / max(m.sum(), 1.0))


def _slice(batch, rows: int):
    ids, mask = (np.asarray(f)[:rows] for f in batch.features)
    return (ids, mask, np.asarray(batch.labels[0])[:rows],
            np.asarray(batch.labels_masks[0])[:rows])


def eval_loss(model, batch, rows: int) -> float:
    """Masked-LM loss of the system's `output_mlm` on the batch's first
    `rows` sequences."""
    ids, mask, labels, lmask = _slice(batch, rows)
    return _masked_ce(model.output_mlm(ids, mask), labels, lmask)


def reference_check(model, config: dict, batch, rows: int) -> dict:
    """The system's MLM logits on `rows` sequences against
    `reference_forward` on the same parameters."""
    import jax
    ids, mask, labels, lmask = _slice(batch, rows)
    got = np.asarray(model.output_mlm(ids, mask), np.float32)
    want = np.asarray(jax.jit(lambda p, i, m: reference_forward(
        config, p, i, m))(model.params_, ids, mask), np.float32)
    err = float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))
    return {"rel_err": err, "tol": float(config["tolerance"]["output_rel"]),
            "loss": _masked_ce(got, labels, lmask),
            "loss_reference": _masked_ce(want, labels, lmask),
            "loss_tol": float(config["tolerance"]["loss_rel"])}


def lower_step(model, batch):
    """The masked-LM train step as `fit_batch` runs it, lowered for the same
    arguments, for counting the Mosaic calls the kernel dispatcher put in it
    (as `chip_smoke.phase_bert` does)."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.utils.counters import device_counters
    ids, mask = (jnp.asarray(f) for f in batch.features)
    it, ep = device_counters(model)
    return model._step("mlm").lower(
        model.params_, model.opt_state_, it, ep,
        ids.astype(jnp.int32), mask, jnp.asarray(batch.labels[0]),
        jnp.asarray(batch.labels_masks[0]))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def reference_forward(config: dict, params, ids, input_mask):
    """MLM logits [B, T, vocab] of a post-LN BERT encoder: float32, highest
    matmul precision, one Python loop over the layers.

    As the paper and `modeling.py`: token + position + segment-0 embeddings,
    LayerNorm; per layer, multi-head self-attention with keys masked by
    `input_mask`, residual + LayerNorm, GELU (the tanh form `modeling.py`
    uses) FFN, residual + LayerNorm; head: dense + GELU + LayerNorm, then
    the tied token embedding and a bias.  Departures, all the program's: no
    dropout, no next-sentence head."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    eps = float(config["layer_norm_eps"])
    nh = int(config["num_attention_heads"])

    def ln(v, g, b):
        mu = jnp.mean(v, -1, keepdims=True)
        var = jnp.mean((v - mu) ** 2, -1, keepdims=True)
        return (v - mu) / jnp.sqrt(var + eps) * g + b

    def gelu(v):
        return 0.5 * v * (1.0 + jnp.tanh(
            np.sqrt(2.0 / np.pi) * (v + 0.044715 * v ** 3)))

    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, f32), params)
        ids = jnp.asarray(ids, jnp.int32)
        b, t = ids.shape
        h = p["tok_emb"].shape[1]
        dh = h // nh
        x = p["tok_emb"][ids] + p["pos_emb"][:t][None] + p["type_emb"][0]
        x = ln(x, p["emb_ln_g"], p["emb_ln_b"])
        keep = jnp.asarray(input_mask, f32)[:, None, None, :]     # [B,1,1,T]
        lp = p["layers"]
        for i in range(lp["Wq"].shape[0]):
            def heads(w, bias):
                return (x @ w[i] + bias[i]).reshape(b, t, nh, dh) \
                    .transpose(0, 2, 1, 3)
            q, k, v = (heads(lp["Wq"], lp["bq"]), heads(lp["Wk"], lp["bk"]),
                       heads(lp["Wv"], lp["bv"]))
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(dh)
            s = jnp.where(keep > 0, s, -1e30)
            a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
            a = a.transpose(0, 2, 1, 3).reshape(b, t, h)
            x = ln(x + a @ lp["Wo"][i] + lp["bo"][i],
                   lp["ln1_g"][i], lp["ln1_b"][i])
            f = gelu(x @ lp["Wi"][i] + lp["bi"][i]) @ lp["Wf"][i] + lp["bf"][i]
            x = ln(x + f, lp["ln2_g"][i], lp["ln2_b"][i])
        y = gelu(x @ p["mlm_W"] + p["mlm_b"])
        y = ln(y, p["mlm_ln_g"], p["mlm_ln_b"])
        return y @ p["tok_emb"].T + p["mlm_bias"]
