"""A decoder-only causal language model with latent attention and sparse
experts, on the train path.

The block of DeepSeek-V2/V3 (arXiv:2405.04434, arXiv:2412.19437) as public
`deepseek_v3` configs describe it:

- pre-norm residual blocks, RMSNorm (float32, `ops/norm_kernels.rms_norm`),
  no position embedding, untied output head, next-token cross-entropy;
- multi-head latent attention in its expanded form: queries of
  `qk_nope + qk_rope` dims, keys rebuilt from a `kv_lora_rank`-wide latent
  plus one rotary head shared by all, values of `v_head` dims — so keys
  are wider than values, through `ops/attention_kernels.fused_attention`
  (the flash kernels from 2k tokens on the chip);
- `n_dense_layers` SwiGLU layers first, then `ops/moe.expert_layer`s: a
  sigmoid router over `n_experts` with a selection bias that the step
  updates (no auxiliary loss), top-k, shared experts.

`first_expert`/`n_experts_held` say which routed experts this process
holds of each layer (all of them by default).  Held alone, the layer
computes its experts' part of the result and the partial sum goes on — the
share one chip runs under expert parallelism, less the exchange.  The
vocabulary may be a slice likewise: `vocab_size` rows of embedding and head,
ids, logits and loss over the slice.

TPU-native choices, as `zoo/bert.py`: one jitted, donated train step;
float32 master parameters cast to `compute_dtype` a layer at a time; the
identical expert layers STACKED `[L, ...]` under one `lax.scan`, so compile
time is flat in depth.  What is saved for the backward pass is fixed here,
by measurement (PERF.md, PR 27 and PR 28): each block's input and the
attention kernel's output and logsumexp.  The rest of the block is computed
again in the backward pass, the flash forward kernel is not: its two
results are what its backward kernel needs and 68 MB a layer at 2 x 4,096
tokens, where q, k and v are 268 MB and come back from the projections.  At
8,192 tokens a step beside 9.2 GB of training state saving those too does
not fit a 16 GB chip.  Where `fused_attention` takes no Pallas kernel (the
CPU, short sequences) there is nothing of that name and the block is
recomputed whole.

Not here yet: prefill/decode through a cache, absorbed latent attention,
the experts' exchange over several chips.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.monitor.spans import note, span
from deeplearning4j_tpu.ops.attention_kernels import (FLASH_LSE, FLASH_OUT,
                                                      fused_attention)
from deeplearning4j_tpu.ops.moe import (expert_layer, swiglu,
                                        update_router_bias)
from deeplearning4j_tpu.ops.norm_kernels import rms_norm
from deeplearning4j_tpu.ops.rotary import rotary_interleaved
from deeplearning4j_tpu.train.updaters import AdamW, IUpdater


@dataclasses.dataclass
class DecoderConfig:
    vocab_size: int = 128256           # rows of embedding and head held here
    hidden: int = 2048
    n_layers: int = 48
    n_dense_layers: int = 1            # leading layers with a dense SwiGLU
    n_heads: int = 32
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate: int = 6144           # the dense layers' SwiGLU width
    expert_intermediate: int = 768
    n_experts: int = 128               # the router's width
    n_shared_experts: int = 2
    top_k: int = 6
    routed_scale: float = 2.448
    first_expert: int = 0              # the routed experts held here:
    n_experts_held: Optional[int] = None   # first .. first + held (None: all)
    rope_base: float = 1e6
    eps: float = 1e-6
    bias_update_speed: float = 1e-3
    init_std: float = 0.02             # every matrix but the embedding
    embedding_init_std: float = 1.0    # see `_init`
    compute_dtype: str = "float32"     # "bfloat16" for TPU throughput

    @property
    def held(self) -> int:
        return self.n_experts if self.n_experts_held is None \
            else self.n_experts_held

    @staticmethod
    def tiny(**kw) -> "DecoderConfig":
        """Test-sized config: one dense and two expert layers, 8 experts
        top-2, keys wider than values."""
        d = dict(vocab_size=96, hidden=32, n_layers=3, n_dense_layers=1,
                 n_heads=2, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
                 kv_lora_rank=16, intermediate=64, expert_intermediate=16,
                 n_experts=8, n_shared_experts=2, top_k=2)
        d.update(kw)
        return DecoderConfig(**d)


class DecoderModel:
    """Causal LM over `DecoderConfig`.  `fit(iterator)` consumes
    MultiDataSets with features `[ids]` and labels `[next ids]`, both
    [B, T] int; `output(ids)` returns the logits."""

    def __init__(self, config: DecoderConfig, seed: int = 0,
                 updater: Optional[IUpdater] = None):
        c = self.config = config
        if not 0 <= c.first_expert <= c.n_experts - c.held:
            raise ValueError(
                f"experts {c.first_expert}..{c.first_expert + c.held} are "
                f"not among the router's {c.n_experts}")
        if not 0 < c.n_dense_layers < c.n_layers:
            raise ValueError("need at least one dense and one expert layer")
        self.updater = updater or AdamW(2.2e-4, weight_decay=0.1)
        self.iteration = 0
        self.epoch = 0
        # one program each, not one a leaf: a cold start compiles two
        self.params_ = jax.jit(self._init)(jax.random.PRNGKey(seed))
        self.opt_state_ = jax.jit(self.updater.init_state)(self.params_)
        n_moe = c.n_layers - c.n_dense_layers
        # what the step carries beside the parameters: the routers'
        # selection bias (a buffer, no gradient) and, per expert layer, how
        # many tokens chose each expert since the model was built
        self.state_ = {
            "router_bias": jnp.zeros((n_moe, c.n_experts), jnp.float32),
            "expert_load": jnp.zeros((n_moe, c.n_experts), jnp.int32)}
        self._steps: Dict[str, Any] = {}

    # ---- init ----
    def _init(self, key) -> Dict[str, Any]:
        """Matrices normal(0, `init_std`), RMSNorm gains one.  The embedding
        rows are normal(0, `embedding_init_std`), 1 by default: under
        pre-norm the residual stream then carries the token, and a router
        sees it.  At 0.02 the blocks' outputs — mostly a component all
        positions share — drown it, every token of a batch picks the same
        experts in every layer, and an expert's load is all or nothing
        (measured, PERF.md PR 27): the first steps of a run, before the
        selection bias has done its work, not the routing by token that
        trained experts show (OpenMoE, arXiv:2402.01739, section 4)."""
        c = self.config
        H, nh = c.hidden, c.n_heads
        keys = iter(jax.random.split(key, 32))

        def nrm(*shape, std=c.init_std):
            return (jax.random.normal(next(keys), shape) * std
                    ).astype(jnp.float32)

        def block(L):
            return {
                "norm1": jnp.ones((L, H)), "norm2": jnp.ones((L, H)),
                "Wq": nrm(L, H, nh * (c.qk_nope_dim + c.qk_rope_dim)),
                "Wkva": nrm(L, H, c.kv_lora_rank + c.qk_rope_dim),
                "kv_norm": jnp.ones((L, c.kv_lora_rank)),
                "Wkvb": nrm(L, c.kv_lora_rank,
                            nh * (c.qk_nope_dim + c.v_head_dim)),
                "Wo": nrm(L, nh * c.v_head_dim, H)}

        Ld, Lm = c.n_dense_layers, c.n_layers - c.n_dense_layers
        I, Ie, S = (c.intermediate, c.expert_intermediate,
                    c.n_shared_experts * c.expert_intermediate)
        return {
            "tok_emb": nrm(c.vocab_size, H, std=c.embedding_init_std),
            "dense": {**block(Ld), "mlp_gate": nrm(Ld, H, I),
                      "mlp_up": nrm(Ld, H, I), "mlp_down": nrm(Ld, I, H)},
            "moe": {**block(Lm), "router": nrm(Lm, H, c.n_experts),
                    "w_gate": nrm(Lm, c.held, H, Ie),
                    "w_up": nrm(Lm, c.held, H, Ie),
                    "w_down": nrm(Lm, c.held, Ie, H),
                    "shared_gate": nrm(Lm, H, S), "shared_up": nrm(Lm, H, S),
                    "shared_down": nrm(Lm, S, H)},
            "final_norm": jnp.ones((H,)),
            "head": nrm(H, c.vocab_size),
        }

    # ---- forward ----
    def _qkv(self, x, lp):
        """Queries and keys [B, heads, T, nope + rope] and values
        [B, heads, T, v] of expanded latent attention for `x` [B, T, H]."""
        c = self.config
        B, T, _ = x.shape
        nh, dn, dr, dv = c.n_heads, c.qk_nope_dim, c.qk_rope_dim, c.v_head_dim
        pos = jnp.arange(T)
        q = (x @ lp["Wq"]).reshape(B, T, nh, dn + dr)
        kva = x @ lp["Wkva"]
        latent = rms_norm(kva[..., :c.kv_lora_rank], lp["kv_norm"], c.eps)
        kv = (latent @ lp["Wkvb"]).reshape(B, T, nh, dn + dv)
        q_rope = rotary_interleaved(q[..., dn:], pos, c.rope_base)
        k_rope = rotary_interleaved(          # one rotary head for all
            kva[..., None, c.kv_lora_rank:], pos, c.rope_base)
        q = jnp.concatenate([q[..., :dn], q_rope], -1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_rope, (B, T, nh, dr))], -1)
        heads_first = (0, 2, 1, 3)
        return (q.transpose(heads_first), k.transpose(heads_first),
                kv[..., dn:].transpose(heads_first))

    def _attention(self, x, lp):
        """`x + MLA(RMSNorm(x))` for `x` [B, T, H], causal."""
        c = self.config
        B, T, _ = x.shape
        with jax.named_scope("mla_attention"):
            dt = lp["Wo"].dtype
            q, k, v = self._qkv(
                rms_norm(x, lp["norm1"], c.eps).astype(dt), lp)
            o = fused_attention(q, k, v, causal=True)
            o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
            return x + (o @ lp["Wo"]).astype(x.dtype)

    def _trunk(self, params, router_bias, ids):
        """Hidden states [B, T, H] after the last block (float32: the blocks
        compute in `compute_dtype`, the residual stream they add to does
        not), and the expert layers' token counts [L_moe, E]."""
        c = self.config
        dt = jnp.dtype(c.compute_dtype)

        def cast(lp):
            return jax.tree_util.tree_map(lambda a: a.astype(dt), lp)

        def dense_ffn(x, lp):
            with jax.named_scope("dense_mlp"):
                y = swiglu(rms_norm(x, lp["norm2"], c.eps).astype(dt),
                           lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
                return x + y.astype(x.dtype)

        def moe_ffn(x, lp, bias):
            B, T, H = x.shape
            y, counts = expert_layer(
                rms_norm(x, lp["norm2"], c.eps).astype(dt).reshape(B * T, H),
                lp, bias, top_k=c.top_k, scale=c.routed_scale,
                first_held=c.first_expert)
            return x + y.reshape(B, T, H).astype(x.dtype), counts

        # each block keeps its input and the flash kernel's two results for
        # the backward pass; the rest is computed again there (module
        # docstring)
        keep = jax.checkpoint_policies.save_only_these_names(FLASH_OUT,
                                                             FLASH_LSE)

        @functools.partial(jax.checkpoint, policy=keep)
        def dense_block(x, lp):
            return dense_ffn(self._attention(x, lp), lp)

        @functools.partial(jax.checkpoint, policy=keep,
                           prevent_cse=False)                  # under scan
        def moe_block(x, layer):
            lp, bias = layer
            lp = {**cast(lp), "router": lp["router"]}   # it stays float32
            return moe_ffn(self._attention(x, lp), lp, bias)

        x = params["tok_emb"][ids]          # the residual stream is float32
        for i in range(c.n_dense_layers):
            x = dense_block(x, cast(jax.tree_util.tree_map(
                lambda a: a[i], params["dense"])))
        return jax.lax.scan(moe_block, x, (params["moe"], router_bias))

    def _logits(self, params, hidden):
        """float32 logits [..., vocab] over the vocabulary held."""
        dt = jnp.dtype(self.config.compute_dtype)
        h = rms_norm(hidden, params["final_norm"], self.config.eps)
        with jax.named_scope("lm_head"):
            return jnp.dot(h.astype(dt), params["head"].astype(dt),
                           preferred_element_type=jnp.float32)

    def _loss(self, params, router_bias, ids, labels):
        """Mean next-token cross-entropy over every position but the last
        of each sequence (`labels[:, t]` is the id at `t + 1`; the last
        column is ignored), logits and `log_softmax` in float32."""
        hidden, counts = self._trunk(params, router_bias, ids)
        logp = jax.nn.log_softmax(self._logits(params, hidden), axis=-1)
        nll = -jnp.take_along_axis(
            logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return jnp.mean(nll[:, :-1]), counts

    # ---- compiled steps ----
    def _step_body(self):
        speed = self.config.bias_update_speed

        def step(params, opt_state, state, iteration, epoch, ids, labels):
            (loss, counts), grads = jax.value_and_grad(
                self._loss, has_aux=True)(params, state["router_bias"],
                                          ids, labels)
            upd, new_opt = self.updater.apply(opt_state, grads, iteration,
                                              epoch, params=params)
            new_params = jax.tree_util.tree_map(lambda p, u: p - u,
                                                params, upd)
            new_state = {
                "router_bias": update_router_bias(state["router_bias"],
                                                  counts, speed),
                "expert_load": state["expert_load"] + counts}
            return new_params, new_opt, new_state, loss, iteration + 1

        return step

    def _step(self):
        if "step" not in self._steps:
            self._steps["step"] = jax.jit(self._step_body(),
                                          donate_argnums=(0, 1, 2))
        return self._steps["step"]

    def _scan_step(self):
        if "scan" not in self._steps:
            from deeplearning4j_tpu.utils.scan_fit import make_scan_step
            body = self._step_body()

            def tick(carry, epoch, batch):
                p, o, s, it = carry
                p, o, s, loss, it = body(p, o, s, it, epoch, *batch)
                return (p, o, s, it), loss

            self._steps["scan"] = make_scan_step(tick)
        return self._steps["scan"]

    # ---- public API ----
    def fit(self, iterator, epochs: int = 1) -> "DecoderModel":
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            with span("fit_epoch", model=type(self).__name__):
                for mds in iterator:
                    self.fit_batch(mds)
            self.epoch += 1
        return self

    @staticmethod
    def _batch(mds):
        (ids,) = [jnp.asarray(f) for f in mds.features]
        (labels,) = [jnp.asarray(l) for l in mds.labels]
        return ids.astype(jnp.int32), labels.astype(jnp.int32)

    def fit_batch(self, mds):
        from deeplearning4j_tpu.utils.counters import advance, device_counters
        ids, labels = self._batch(mds)
        it, ep = device_counters(self)
        t0 = time.perf_counter()
        (self.params_, self.opt_state_, self.state_, loss,
         new_it) = self._step()(self.params_, self.opt_state_, self.state_,
                                it, ep, ids, labels)
        note("step_dispatch", t0, time.perf_counter(), self.iteration)
        self._score = loss
        advance(self, new_it)
        return loss          # a device scalar: `score()` reads it lazily

    def fit_steps(self, mds):
        """k train steps in one dispatch: `ids` and `labels` carry a leading
        `[k, batch]` steps axis.  Same math as k `fit_batch` calls; returns
        the length-k loss array."""
        from deeplearning4j_tpu.utils.counters import advance, device_counters
        from deeplearning4j_tpu.utils.scan_fit import check_steps_axes
        ids, labels = self._batch(mds)
        k = check_steps_axes([("ids", ids), ("labels", labels)])
        it, ep = device_counters(self)
        t0 = time.perf_counter()
        ((self.params_, self.opt_state_, self.state_, new_it), losses,
         last_loss) = self._scan_step()(
            (self.params_, self.opt_state_, self.state_, it), ep,
            (ids, labels))
        note("step_dispatch", t0, time.perf_counter(), self.iteration)
        self._score = last_loss
        advance(self, new_it, steps=int(k))
        return losses

    def score(self) -> float:
        s = getattr(self, "_score", None)
        return float(s) if s is not None else float("nan")

    def output(self, ids):
        """float32 logits [B, T, vocab held] of the inference forward."""
        if "output" not in self._steps:
            self._steps["output"] = jax.jit(
                lambda p, b, i: self._logits(p, self._trunk(p, b, i)[0]))
        return self._steps["output"](self.params_, self.state_["router_bias"],
                                     jnp.asarray(ids, jnp.int32))

    def expert_load(self) -> np.ndarray:
        """[expert layers, n_experts] tokens that chose each expert over all
        train steps so far: one device read of the step's own counter."""
        return np.asarray(self.state_["expert_load"])

    def num_params(self) -> int:
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(self.params_))

    # ---- persistence ----
    _TREES = ("params_", "opt_state_", "state_")

    def save(self, path):
        """`path` is a file name or a seekable binary file object."""
        import io, json, zipfile
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
            z.writestr("config.json", json.dumps(
                {**dataclasses.asdict(self.config),
                 "iteration": self.iteration, "epoch": self.epoch}))
            for name in self._TREES:
                buf = io.BytesIO()
                np.savez(buf, *[np.asarray(l) for l in
                                jax.tree_util.tree_leaves(
                                    getattr(self, name))])
                z.writestr(name + ".npz", buf.getvalue())

    @staticmethod
    def load(path, updater: Optional[IUpdater] = None) -> "DecoderModel":
        import io, json, zipfile
        with zipfile.ZipFile(path) as z:
            meta = json.loads(z.read("config.json").decode())
            iteration, epoch = meta.pop("iteration"), meta.pop("epoch")
            model = DecoderModel(DecoderConfig(**meta), updater=updater)
            for name in model._TREES:
                leaves, treedef = jax.tree_util.tree_flatten(
                    getattr(model, name))
                with np.load(io.BytesIO(z.read(name + ".npz"))) as d:
                    setattr(model, name, jax.tree_util.tree_unflatten(
                        treedef, [jnp.asarray(d[f"arr_{i}"])
                                  for i in range(len(leaves))]))
            model.iteration, model.epoch = iteration, epoch
        return model
