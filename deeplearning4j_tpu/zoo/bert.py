"""BERT encoder (the reference's BERT workload: SameDiff TF-imported
BERT-base fine-tune — BASELINE.json config 3 — plus `BertIterator` masked-LM
pretraining, `deeplearning4j-nlp/.../iterator/BertIterator.java`).

TPU-native design choices:
- One jitted train step for the whole model (vs the reference's op-by-op
  SameDiff session execution).
- Transformer blocks have identical shapes -> parameters are STACKED
  [L, ...] and the encoder is a `lax.scan` over layers: compile time stays
  flat in depth and XLA pipelines the blocks.
- Attention runs the fused flash/blockwise path
  (ops/attention_kernels.py); `compute_dtype="bfloat16"` keeps master
  params f32 and casts activations/matmuls to bf16 for the MXU.
- Post-LN residual wiring (original BERT), GELU FFN.
- The masked-LM train step runs its head (transform, GELU, LayerNorm, tied
  product, `log_softmax`) on the labelled positions only: they are compacted
  inside the compiled step to a capacity taken from the batch shape, so no
  `[B*T, vocab]` array is written.  A batch with more labelled positions
  than the capacity takes further passes of the same size — exact for every
  mask, no recompile, no host read (`_head_capacity`, `_mlm_head_loss`).
  `output_mlm` (inference) computes logits at every position.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.monitor.spans import note, note_step, span
from deeplearning4j_tpu.ops.attention_kernels import fused_attention
from deeplearning4j_tpu.train.updaters import Adam, IUpdater


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden: int = 768
    n_layers: int = 12
    n_heads: int = 12
    intermediate: int = 3072
    max_len: int = 512
    type_vocab: int = 2
    eps: float = 1e-12
    compute_dtype: str = "float32"     # "bfloat16" for TPU throughput
    n_classes: int = 2                 # classification head width

    @staticmethod
    def base(**kw) -> "BertConfig":
        return BertConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "BertConfig":
        """Test-sized config."""
        d = dict(vocab_size=100, hidden=64, n_layers=2, n_heads=4,
                 intermediate=128, max_len=64)
        d.update(kw)
        return BertConfig(**d)


_HEAD_KEYS = ("mlm_W", "mlm_b", "mlm_ln_g", "mlm_ln_b", "tok_emb", "mlm_bias")


def _head_capacity(positions: int) -> int:
    """Rows one pass of the masked-LM train head runs on: a quarter of the
    batch's positions (BERT labels 15%), rounded up to the fused-LayerNorm
    kernel's 256-row block; all of them where that is no fewer."""
    return min(positions, -(-positions // (4 * 256)) * 256)


def _fold_head(acc, report):
    """`acc` = int32 [steps, steps that took more than one head pass, most
    labelled positions in a step, rows of a pass at the newest step];
    `report` = `[n, passes, capacity]` of one step or `[k, 3]` of k steps."""
    r = report.reshape(-1, 3)
    return jnp.stack([acc[0] + r.shape[0], acc[1] + jnp.sum(r[:, 1] > 1),
                      jnp.maximum(acc[2], jnp.max(r[:, 0])),
                      r[-1, 2]]).astype(jnp.int32)


_fold_head_jit = jax.jit(_fold_head)


def _ln(x, g, b, eps):
    # measured dispatch: Pallas fused LayerNorm on TPU for tiling shapes
    from deeplearning4j_tpu.ops.norm_kernels import fused_layer_norm
    return fused_layer_norm(x, g, b, eps)


class BertModel:
    """BERT with masked-LM and sequence-classification heads.

    fit(iterator) consumes BertIterator batches (task picked from batch
    shape); output_hidden/output_mlm/output_cls for inference."""

    def __init__(self, config: BertConfig, seed: int = 0,
                 updater: Optional[IUpdater] = None):
        self.config = config
        self.updater = updater or Adam(1e-4)
        self.iteration = 0
        self.epoch = 0
        self._rng = jax.random.PRNGKey(seed)
        self.params_ = self._init(jax.random.PRNGKey(seed))
        self.opt_state_ = self.updater.init_state(self.params_)
        self._steps: Dict[str, Any] = {}
        self._unnoted_step = False    # a step built and not yet run
        self._mlm_head = jnp.zeros((4,), jnp.int32)    # see `_fold_head`

    # ---- init ----
    def _init(self, key) -> Dict[str, Any]:
        c = self.config
        k = jax.random.split(key, 16)
        H, I, L = c.hidden, c.intermediate, c.n_layers
        s = 0.02

        def nrm(kk, *shape):
            return (jax.random.normal(kk, shape) * s).astype(jnp.float32)

        return {
            "tok_emb": nrm(k[0], c.vocab_size, H),
            "pos_emb": nrm(k[1], c.max_len, H),
            "type_emb": nrm(k[2], c.type_vocab, H),
            "emb_ln_g": jnp.ones((H,)), "emb_ln_b": jnp.zeros((H,)),
            "layers": {
                "Wq": nrm(k[3], L, H, H), "bq": jnp.zeros((L, H)),
                "Wk": nrm(k[4], L, H, H), "bk": jnp.zeros((L, H)),
                "Wv": nrm(k[5], L, H, H), "bv": jnp.zeros((L, H)),
                "Wo": nrm(k[6], L, H, H), "bo": jnp.zeros((L, H)),
                "ln1_g": jnp.ones((L, H)), "ln1_b": jnp.zeros((L, H)),
                "Wi": nrm(k[7], L, H, I), "bi": jnp.zeros((L, I)),
                "Wf": nrm(k[8], L, I, H), "bf": jnp.zeros((L, H)),
                "ln2_g": jnp.ones((L, H)), "ln2_b": jnp.zeros((L, H)),
            },
            "pool_W": nrm(k[9], H, H), "pool_b": jnp.zeros((H,)),
            "mlm_W": nrm(k[10], H, H), "mlm_b": jnp.zeros((H,)),
            "mlm_ln_g": jnp.ones((H,)), "mlm_ln_b": jnp.zeros((H,)),
            "mlm_bias": jnp.zeros((c.vocab_size,)),
            "cls_W": nrm(k[11], H, c.n_classes),
            "cls_b": jnp.zeros((c.n_classes,)),
        }

    # ---- forward ----
    def _encode(self, params, ids, input_mask, segment_ids=None):
        c = self.config
        dt = jnp.dtype(c.compute_dtype)
        T = ids.shape[1]
        # the scopes name the device ops by the layer that asked for them
        # (docs/observability.md)
        with jax.named_scope("embeddings"):
            x = (params["tok_emb"][ids]
                 + params["pos_emb"][:T][None]
                 + (params["type_emb"][segment_ids]
                    if segment_ids is not None else params["type_emb"][0]))
            x = _ln(x, params["emb_ln_g"], params["emb_ln_b"], c.eps)
            x = x.astype(dt)
            mask = input_mask.astype(dt)

        def block(x, lp):
            with jax.named_scope("param_cast"):
                lp = jax.tree_util.tree_map(lambda a: a.astype(dt), lp)
            B, T, H = x.shape
            nh = c.n_heads
            dh = H // nh

            def split(y):
                return y.reshape(B, T, nh, dh).transpose(0, 2, 1, 3)

            with jax.named_scope("self_attention"):
                q = split(x @ lp["Wq"] + lp["bq"])
                k = split(x @ lp["Wk"] + lp["bk"])
                v = split(x @ lp["Wv"] + lp["bv"])
                a = fused_attention(q, k, v, mask=mask)
                a = a.transpose(0, 2, 1, 3).reshape(B, T, H)
                a = a @ lp["Wo"] + lp["bo"]
                x = _ln(x + a, lp["ln1_g"], lp["ln1_b"], c.eps)
            with jax.named_scope("ffn"):
                h = jax.nn.gelu(x @ lp["Wi"] + lp["bi"])
                h = h @ lp["Wf"] + lp["bf"]
                x = _ln(x + h, lp["ln2_g"], lp["ln2_b"], c.eps)
            return x.astype(dt), None

        x, _ = jax.lax.scan(block, x, params["layers"])
        return x.astype(jnp.float32)

    def _mlm_logits(self, params, hidden):
        c = self.config
        h = jax.nn.gelu(hidden @ params["mlm_W"] + params["mlm_b"])
        h = _ln(h, params["mlm_ln_g"], params["mlm_ln_b"], c.eps)
        # tied output embedding (BERT standard)
        return h @ params["tok_emb"].T + params["mlm_bias"]

    def _cls_logits(self, params, hidden):
        pooled = jnp.tanh(hidden[:, 0] @ params["pool_W"]
                          + params["pool_b"])
        return pooled @ params["cls_W"] + params["cls_b"]

    # ---- losses ----
    def _head_nll(self, head, rows, labels, weights):
        """Weighted sum of the masked-LM negative log-likelihood over
        `rows` [R, H].  labels: sparse [R] int token ids (preferred — a
        one-hot [B, T, V] labels array is 250MB/step of H2D at BERT-base
        scale) or dense one-hot [R, V]."""
        lp = jax.nn.log_softmax(self._mlm_logits(head, rows), -1)
        if labels.ndim == 1:
            per_row = -jnp.take_along_axis(
                lp, labels[:, None].astype(jnp.int32), axis=-1)[:, 0]
        else:
            per_row = -jnp.sum(labels * lp, -1)
        return jnp.sum(per_row * weights)

    def _head_passes(self, head, rows, labels, weights):
        """Masked-LM loss `sum(nll * weights) / max(sum(weights), 1)` of the
        head on `rows` [R, H], and its gradients, computed over the rows
        whose weight is not zero, `_head_capacity(R)` of them a pass:
        (loss, d head, d rows, [number of such rows, passes, rows a pass]).
        One pass at BERT's 15%; a fuller mask takes more passes of the same
        program, and an empty one none."""
        positions = rows.shape[0]
        cap = _head_capacity(positions)
        grad = jax.value_and_grad(self._head_nll, argnums=(0, 1))
        labelled = weights != 0
        n = jnp.sum(labelled, dtype=jnp.int32)
        denom = jnp.maximum(jnp.sum(weights), 1.0)
        if cap == positions:
            nll, (d_head, d_rows) = grad(head, rows, labels, weights / denom)
            report = jnp.stack([n, 1, cap]).astype(jnp.int32)
            return nll, d_head, d_rows, report
        passes = (n + (cap - 1)) // cap
        # labelled positions first, in order; the tail repeats position 0
        # and is given weight 0 below
        order = jnp.nonzero(labelled, size=-(-positions // cap) * cap,
                            fill_value=0)[0].astype(jnp.int32)
        slots = jnp.arange(cap, dtype=jnp.int32)

        def one_pass(i, acc):
            nll, d_head, d_rows = acc
            at = jax.lax.dynamic_slice(order, (i * cap,), (cap,))
            w = jnp.where(i * cap + slots < n, weights[at], 0) / denom
            nll_i, (g_head, g_rows) = grad(head, rows[at], labels[at], w)
            return (nll + nll_i,
                    jax.tree_util.tree_map(jnp.add, d_head, g_head),
                    d_rows.at[at].add(g_rows))

        zero = (jnp.zeros((), jnp.result_type(
                    rows, weights, *jax.tree_util.tree_leaves(head))),
                jax.tree_util.tree_map(jnp.zeros_like, head),
                jnp.zeros_like(rows))
        nll, d_head, d_rows = jax.lax.fori_loop(0, passes, one_pass, zero)
        return nll, d_head, d_rows, jnp.stack([n, passes, cap])

    def _mlm_head_loss(self, head, rows, labels, weights):
        """(loss, report) of `_head_passes`, differentiable in `head`
        and `rows`.  Loss and gradients are taken together inside the pass
        loop and the gradients kept for the backward pass, so differentiating
        the step stores `d head` and `d rows` and nothing of width `vocab`;
        `labels` and `weights` get no gradient."""
        @jax.custom_vjp
        def f(head, rows, labels, weights):
            loss, _, _, report = self._head_passes(head, rows, labels, weights)
            return loss, report

        def fwd(head, rows, labels, weights):
            loss, d_head, d_rows, report = self._head_passes(
                head, rows, labels, weights)
            return (loss, report), (d_head, d_rows)

        def bwd(res, ct):
            g = ct[0]
            d_head, d_rows = jax.tree_util.tree_map(
                lambda a: (g * a).astype(a.dtype), res)
            return d_head, d_rows, None, None

        f.defvjp(fwd, bwd)
        return f(head, rows, labels, jax.lax.stop_gradient(weights))

    def _mlm_loss(self, params, ids, input_mask, labels, label_mask):
        """Masked-LM loss `sum(nll * label_mask) / max(sum(label_mask), 1)`
        and the head's `[n, passes, capacity]` report.  The head runs only
        where `label_mask` is not zero (`_head_passes`); the value and the
        parameters' gradients are those of the head run at every position.
        labels: [B, T] int token ids or one-hot [B, T, V]."""
        h = self._encode(params, ids, input_mask)
        labels, label_mask = jnp.asarray(labels), jnp.asarray(label_mask)
        positions = h.shape[0] * h.shape[1]
        with jax.named_scope("mlm_head"):
            return self._mlm_head_loss(
                {k: params[k] for k in _HEAD_KEYS}, h.reshape(positions, -1),
                labels.reshape(positions, *labels.shape[2:]),
                label_mask.reshape(positions))

    def _cls_loss(self, params, ids, input_mask, labels):
        h = self._encode(params, ids, input_mask)
        logits = self._cls_logits(params, h)
        return -jnp.mean(jnp.sum(labels * jax.nn.log_softmax(logits, -1),
                                 -1)), None

    # ---- compiled steps ----
    def _step_body(self, kind: str):
        loss_fn = self._mlm_loss if kind == "mlm" else self._cls_loss

        def step(params, opt_state, iteration, epoch, *batch):
            (loss, report), grads = jax.value_and_grad(
                lambda p: loss_fn(p, *batch), has_aux=True)(params)
            with jax.named_scope("updater"):
                upd, new_opt = self.updater.apply(opt_state, grads, iteration,
                                                  epoch, params=params)
                new_params = jax.tree_util.tree_map(lambda p, u: p - u,
                                                    params, upd)
            return new_params, new_opt, loss, iteration + 1, report

        return step

    def _step(self, kind: str):
        if kind not in self._steps:
            self._steps[kind] = jax.jit(self._step_body(kind),
                                        donate_argnums=(0, 1))
            self._unnoted_step = True     # `fit_batch` tells `monitor`
        return self._steps[kind]

    def _scan_step(self, kind: str):
        """k steps per dispatch (see utils/scan_fit.py for the rationale);
        BERT's step carry is (params, opt, iteration) — no state/rng — and,
        for the masked LM, the head's counters (`mlm_head_stats`)."""
        key = "scan_" + kind
        if key not in self._steps:
            from deeplearning4j_tpu.utils.scan_fit import make_scan_step
            body = self._step_body(kind)

            def tick(carry, epoch, batch):
                p, o, it, *head = carry
                p, o, loss, it, report = body(p, o, it, epoch, *batch)
                if head:                                 # masked LM
                    head = [_fold_head(head[0], report)]
                return (p, o, it, *head), loss

            self._steps[key] = make_scan_step(tick)
        return self._steps[key]


    # ---- public API ----
    def fit(self, iterator, epochs: int = 1,
            fused_steps: int = 1) -> "BertModel":
        """`fused_steps=k` stacks k consecutive same-shape batches into one
        `fit_steps` dispatch (tails/shape changes fall back per-step)."""
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            with span("fit_epoch", model=type(self).__name__):
                if fused_steps > 1:
                    self._fit_epoch_fused(iterator, fused_steps)
                else:
                    for mds in iterator:
                        self.fit_batch(mds)
            self.epoch += 1
        return self

    def _fit_epoch_fused(self, iterator, k: int):
        import numpy as np

        from deeplearning4j_tpu.data.dataset import MultiDataSet
        from deeplearning4j_tpu.utils.scan_fit import blocks_of
        for block in blocks_of(iterator, k):
            if len(block) == 1:
                self.fit_batch(block[0])
                continue
            n_f = len(block[0].features)
            n_l = len(block[0].labels)
            stacked = MultiDataSet(
                features=[np.stack([np.asarray(b.features[j])
                                    for b in block]) for j in range(n_f)],
                labels=[np.stack([np.asarray(b.labels[j]) for b in block])
                        for j in range(n_l)],
                labels_masks=None if block[0].labels_masks is None else
                [np.stack([np.asarray(b.labels_masks[j]) for b in block])
                 for j in range(len(block[0].labels_masks))])
            self.fit_steps(stacked)

    def fit_batch(self, mds):
        from deeplearning4j_tpu.utils.counters import advance, device_counters
        ids, input_mask = [jnp.asarray(f) for f in mds.features]
        (labels,) = [jnp.asarray(l) for l in mds.labels]
        it, ep = device_counters(self)
        t0 = time.perf_counter()
        args = (self.params_, self.opt_state_, it, ep, ids.astype(jnp.int32),
                input_mask, labels)
        if mds.labels_masks is not None:                 # masked LM
            step = self._step("mlm")
            args += (jnp.asarray(mds.labels_masks[0]),)
        else:                                            # classification
            step = self._step("cls")
        self.params_, self.opt_state_, loss, new_it, report = step(*args)
        note("step_dispatch", t0, time.perf_counter(), self.iteration)
        if self._unnoted_step:      # the call compiled it: its text is
            self._unnoted_step = False      # `monitor.lowered_step()`
            note_step(step, args)
        if report is not None:                           # masked LM
            self._mlm_head = _fold_head_jit(self._mlm_head, report)
        self._score = loss
        advance(self, new_it)
        # return the device-side loss WITHOUT forcing a D2H sync: a per-step
        # float() round-trip stalls the dispatch pipeline (measured on v5e,
        # 2026-07: 2x step time); score() materializes lazily
        return loss

    def fit_steps(self, mds):
        """Run k train steps in one device dispatch: every array in `mds`
        carries a leading `[k, batch]` steps axis.  Same math as k
        sequential `fit_batch` calls; returns the length-k loss array."""
        from deeplearning4j_tpu.utils.counters import advance, device_counters
        from deeplearning4j_tpu.utils.scan_fit import check_steps_axes
        ids, input_mask = [jnp.asarray(f) for f in mds.features]
        (labels,) = [jnp.asarray(l) for l in mds.labels]
        lm0 = None if mds.labels_masks is None \
            else jnp.asarray(mds.labels_masks[0])
        k = check_steps_axes([("ids", ids), ("input_mask", input_mask),
                              ("labels", labels), ("labels_mask", lm0)])
        it, ep = device_counters(self)
        t0 = time.perf_counter()
        if mds.labels_masks is not None:                 # masked LM
            lmask = lm0
            step = self._scan_step("mlm")
            ((self.params_, self.opt_state_, new_it, self._mlm_head),
             losses, last_loss) = step(
                (self.params_, self.opt_state_, it, self._mlm_head), ep,
                (ids.astype(jnp.int32), input_mask, labels, lmask))
        else:                                            # classification
            step = self._scan_step("cls")
            (self.params_, self.opt_state_, new_it), losses, last_loss = step(
                (self.params_, self.opt_state_, it), ep,
                (ids.astype(jnp.int32), input_mask, labels))
        note("step_dispatch", t0, time.perf_counter(), self.iteration)
        self._score = last_loss
        advance(self, new_it, steps=int(k))
        return losses

    def score(self) -> float:
        s = getattr(self, "_score", None)
        return float(s) if s is not None else float("nan")

    def mlm_head_stats(self) -> Dict[str, Optional[int]]:
        """What the masked-LM train steps' head did so far (one device
        read): `steps`; `fallback_steps`, those with more labelled positions
        than `capacity` (the head then takes further passes); `max_labelled`,
        the most labelled positions a step saw; `capacity`, the rows of one
        head pass at the newest batch shape (0 before the first step)."""
        steps, fallback, most, cap = (int(v) for v in
                                      np.asarray(self._mlm_head))
        return {"steps": steps, "gathered_steps": steps - fallback,
                "fallback_steps": fallback, "max_labelled": most,
                "capacity": cap}

    def output_hidden(self, ids, input_mask):
        return self._encode(self.params_, jnp.asarray(ids, jnp.int32),
                            jnp.asarray(input_mask))

    def output_mlm(self, ids, input_mask):
        h = self.output_hidden(ids, input_mask)
        return self._mlm_logits(self.params_, h)

    def output_cls(self, ids, input_mask):
        h = self.output_hidden(ids, input_mask)
        return jax.nn.softmax(self._cls_logits(self.params_, h), -1)

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(self.params_))

    # ---- persistence ----
    def save(self, path):
        """`path` is a file name or a seekable binary file object."""
        import io, json, zipfile
        leaves, treedef = jax.tree_util.tree_flatten(self.params_)
        opt_leaves = jax.tree_util.tree_leaves(self.opt_state_)
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
            z.writestr("config.json", json.dumps(
                {**dataclasses.asdict(self.config),
                 "iteration": self.iteration, "epoch": self.epoch}))
            buf = io.BytesIO()
            np.savez(buf, *[np.asarray(l) for l in leaves])
            z.writestr("params.npz", buf.getvalue())
            buf = io.BytesIO()
            np.savez(buf, *[np.asarray(l) for l in opt_leaves])
            z.writestr("opt.npz", buf.getvalue())

    @staticmethod
    def load(path) -> "BertModel":
        import io, json, zipfile
        with zipfile.ZipFile(path) as z:
            meta = json.loads(z.read("config.json").decode())
            iteration = meta.pop("iteration")
            epoch = meta.pop("epoch")
            model = BertModel(BertConfig(**meta))
            leaves, treedef = jax.tree_util.tree_flatten(model.params_)
            with np.load(io.BytesIO(z.read("params.npz"))) as d:
                model.params_ = jax.tree_util.tree_unflatten(
                    treedef, [jnp.asarray(d[f"arr_{i}"])
                              for i in range(len(leaves))])
            oleaves, otreedef = jax.tree_util.tree_flatten(model.opt_state_)
            with np.load(io.BytesIO(z.read("opt.npz"))) as d:
                model.opt_state_ = jax.tree_util.tree_unflatten(
                    otreedef, [jnp.asarray(d[f"arr_{i}"])
                               for i in range(len(oleaves))])
            model.iteration, model.epoch = iteration, epoch
        return model
