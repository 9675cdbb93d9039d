"""MultiLayerNetwork: sequential-stack model with a compiled train step.

Reference: `deeplearning4j-nn/.../nn/multilayer/MultiLayerNetwork.java` (~4k
LoC) plus the config DSL `NeuralNetConfiguration.Builder` ->
`MultiLayerConfiguration` (`nn/conf/**`) and the optimize loop
`Solver`/`StochasticGradientDescent`/`BaseOptimizer`
(`optimize/solvers/**`).

Architectural inversion (SURVEY.md §7): the reference runs layer-by-layer
`activate()`/`backpropGradient()` with hand-choreographed workspaces and an
in-place flattened `gradientView`; here `fit()` traces ONE pure function
(forward + loss + `jax.grad` + updater) and `jax.jit` compiles it, donating
params/updater-state buffers so XLA reuses HBM in place.  Parameter-averaging
/ gradient-sharing DP becomes a sharding annotation on the same step
(see parallel/).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.core import InputType, Layer, PyTree
from deeplearning4j_tpu.nn.trainer import LayerwiseTrainer
from deeplearning4j_tpu.train.updaters import IUpdater, Sgd

Params = Dict[str, PyTree]


def _masked_leaves(params, mask):
    """Yield param leaves where the layer's regularizable_mask is True
    (mask may mark whole subtrees)."""
    if isinstance(mask, dict):
        for k, m in mask.items():
            yield from _masked_leaves(params[k], m)
    elif mask:
        yield from jax.tree_util.tree_leaves(params)


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MultiLayerConfiguration:
    """Sequential config (reference `MultiLayerConfiguration`): ordered layer
    configs + global defaults. JSON round-trip is a public contract
    (checkpoints embed it, `MultiLayerConfiguration.toJson/fromJson`)."""

    layers: List[Layer]
    input_type: InputType
    seed: int = 0
    updater: IUpdater = dataclasses.field(default_factory=lambda: Sgd(1e-2))
    weight_init: str = "XAVIER"
    activation: Any = "identity"
    l1: float = 0.0
    l2: float = 0.0
    weight_decay: float = 0.0
    dtype: str = "float32"
    # bf16 compute path: master params/updater state stay `dtype` (f32);
    # activations + layer params are cast to compute_dtype inside the
    # forward, losses/BN-statistics compute in f32 (the TPU mixed-precision
    # recipe — MXU runs bf16, accumulation stays f32)
    compute_dtype: Optional[str] = None
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0
    # gradient checkpointing (jax.checkpoint per layer): trades ~1 extra
    # forward of FLOPs for O(sqrt)-ish activation memory — the HBM lever
    # for deep models; a capability-exceeding TPU addition (the reference
    # has no rematerialization story)
    remat: bool = False

    def layer_name(self, i: int) -> str:
        return self.layers[i].name or f"layer_{i}"

    def to_json(self) -> str:
        return json.dumps({
            "format": "deeplearning4j_tpu.MultiLayerConfiguration.v1",
            "layers": [l.to_json() for l in self.layers],
            "input_type": self.input_type.to_json(),
            "seed": self.seed,
            "updater": self.updater.to_json(),
            "weight_init": self.weight_init,
            "activation": self.activation if isinstance(self.activation, str)
                          else getattr(self.activation, "__name__", "identity"),
            "l1": self.l1, "l2": self.l2, "weight_decay": self.weight_decay,
            "dtype": self.dtype,
            "compute_dtype": self.compute_dtype,
            "gradient_normalization": self.gradient_normalization,
            "gradient_normalization_threshold": self.gradient_normalization_threshold,
            "remat": self.remat,
        }, indent=2)

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        d = json.loads(s)
        return MultiLayerConfiguration(
            layers=[Layer.from_json(l) for l in d["layers"]],
            input_type=InputType.from_json(d["input_type"]),
            seed=d["seed"],
            updater=IUpdater.from_json(d["updater"]),
            weight_init=d["weight_init"],
            activation=d["activation"],
            l1=d["l1"], l2=d["l2"], weight_decay=d.get("weight_decay", 0.0),
            dtype=d.get("dtype", "float32"),
            compute_dtype=d.get("compute_dtype"),
            gradient_normalization=d.get("gradient_normalization"),
            gradient_normalization_threshold=d.get("gradient_normalization_threshold", 1.0),
            remat=d.get("remat", False),
        )


class NeuralNetConfiguration:
    """Fluent builder mirroring `NeuralNetConfiguration.Builder` ->
    `.list()` -> `.build()`."""

    class Builder:
        def __init__(self):
            self._seed = 0
            self._updater: IUpdater = Sgd(1e-2)
            self._weight_init = "XAVIER"
            self._activation: Any = "identity"
            self._l1 = 0.0
            self._l2 = 0.0
            self._weight_decay = 0.0
            self._dtype = "float32"
            self._compute_dtype = None
            self._grad_norm = None
            self._grad_norm_threshold = 1.0
            self._input_type: Optional[InputType] = None
            self._remat = False

        def seed(self, s: int):
            self._seed = int(s); return self

        def updater(self, u: IUpdater):
            self._updater = u; return self

        def weight_init(self, w: str):
            self._weight_init = w; return self

        def activation(self, a):
            self._activation = a; return self

        def l1(self, v: float):
            self._l1 = float(v); return self

        def l2(self, v: float):
            self._l2 = float(v); return self

        def weight_decay(self, v: float):
            self._weight_decay = float(v); return self

        def dtype(self, dt: str):
            self._dtype = dt; return self

        def compute_dtype(self, dt: str):
            self._compute_dtype = dt; return self

        def gradient_normalization(self, mode: str, threshold: float = 1.0):
            self._grad_norm = mode; self._grad_norm_threshold = threshold; return self

        def gradient_checkpointing(self, on: bool = True):
            """Rematerialize each layer's activations in the backward pass
            (jax.checkpoint) — HBM for FLOPs on deep models."""
            self._remat = bool(on); return self

        def set_input_type(self, it: InputType):
            self._input_type = it; return self

        def list(self, layers: Sequence[Layer]) -> "NeuralNetConfiguration.ListBuilder":
            return NeuralNetConfiguration.ListBuilder(self, list(layers))

    class ListBuilder:
        def __init__(self, parent: "NeuralNetConfiguration.Builder", layers: List[Layer]):
            self.parent = parent
            self.layers = layers

        def set_input_type(self, it: InputType):
            self.parent._input_type = it; return self

        def build(self) -> MultiLayerConfiguration:
            p = self.parent
            if p._input_type is None:
                raise ValueError("set_input_type(...) is required (shape inference)")
            return MultiLayerConfiguration(
                layers=self.layers, input_type=p._input_type, seed=p._seed,
                updater=p._updater, weight_init=p._weight_init,
                activation=p._activation, l1=p._l1, l2=p._l2,
                weight_decay=p._weight_decay, dtype=p._dtype,
                compute_dtype=p._compute_dtype,
                gradient_normalization=p._grad_norm,
                gradient_normalization_threshold=p._grad_norm_threshold,
                remat=p._remat,
            )

    @staticmethod
    def builder() -> "NeuralNetConfiguration.Builder":
        return NeuralNetConfiguration.Builder()


# ---------------------------------------------------------------------------
# Network
# ---------------------------------------------------------------------------

class MultiLayerNetwork(LayerwiseTrainer):
    """Sequential network (reference `MultiLayerNetwork`).

    Public surface parity: `init`, `fit(x, y | iterator)`, `output`,
    `feed_forward`, `score`, `evaluate`, `params`/`set_params` (flat-buffer
    view semantics at the API/checkpoint boundary only), `gradient_for`
    (gradient-check hook), `save`/`load` via utils.serialization.  The
    compiled train step, its cache and its dispatch are `LayerwiseTrainer`'s
    (nn/trainer.py).
    """

    _AOT_PREFIX = "mln"
    _BATCH_ARITY = 4          # x, y, fmask, lmask

    def __init__(self, conf: MultiLayerConfiguration):
        super().__init__()
        self.conf = conf
        self.params_: Optional[Params] = None
        self.state_: Optional[Params] = None      # BN running stats etc.
        self.opt_state_: Optional[PyTree] = None
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[Any] = []
        self._rng = jax.random.PRNGKey(conf.seed)
        self._output_fn = None
        self._layer_types: List[InputType] = []
        self._device_norm = None   # on-device normalizer prologue (pipeline)

    # ---- init ----
    def init(self) -> "MultiLayerNetwork":
        dtype = jnp.dtype(self.conf.dtype)
        it = self.conf.input_type
        params: Params = {}
        state: Params = {}
        key = jax.random.PRNGKey(self.conf.seed)
        self._layer_types = [it]
        for i, layer in enumerate(self.conf.layers):
            key, sub = jax.random.split(key)
            if layer.weight_init is None:
                layer.weight_init = self.conf.weight_init
            if layer.activation is None and not hasattr(layer, "loss"):
                layer.activation = self.conf.activation
            p, s, it = layer.initialize(sub, it, dtype)
            params[self.conf.layer_name(i)] = p
            state[self.conf.layer_name(i)] = s
            self._layer_types.append(it)
        self.params_ = params
        self.state_ = state
        self.opt_state_ = self._init_opt_state(params)
        return self

    def _updater_for(self, i: int) -> IUpdater:
        layer = self.conf.layers[i]
        return layer.updater if layer.updater is not None else self.conf.updater

    def _init_opt_state(self, params: Params) -> PyTree:
        return {
            self.conf.layer_name(i): self._updater_for(i).init_state(
                params[self.conf.layer_name(i)])
            for i in range(len(self.conf.layers))
        }

    # ---- forward ----
    def _cast_compute(self, params: Params, x):
        """Mixed precision: cast activations + params to compute_dtype;
        gradients flow back through the casts to f32 master params."""
        cd = self.conf.compute_dtype
        if cd is None:
            return params, x
        dt = jnp.dtype(cd)
        cast = lambda a: a.astype(dt) if jnp.issubdtype(a.dtype,
                                                        jnp.floating) else a
        with jax.named_scope("param_cast"):
            return (jax.tree_util.tree_map(cast, params),
                    x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
                    else x)

    def _forward(self, params: Params, state: Params, x, *, train: bool,
                 rng: Optional[jax.Array], mask=None,
                 upto: Optional[int] = None) -> Tuple[jnp.ndarray, Params]:
        params, x = self._cast_compute(params, x)
        new_state = dict(state)
        n = len(self.conf.layers) if upto is None else upto
        for i in range(n):
            layer = self.conf.layers[i]
            name = self.conf.layer_name(i)
            lrng = None
            if rng is not None and layer.STOCHASTIC:
                rng, lrng = jax.random.split(rng)
            # the device ops' `op_name` says which layer asked for them
            # (docs/observability.md): `DenseLayer/layer_0`
            with jax.named_scope(f"{type(layer).__name__}/{name}"):
                if self.conf.remat and train:
                    # train only: inference is never differentiated, and
                    # jax.checkpoint's CSE barrier would just slow it down
                    def _apply(p_, s_, x_, r_, m_, _layer=layer,
                               _train=train):
                        return _layer.apply(p_, s_, x_, train=_train, rng=r_,
                                            mask=m_)
                    x, s = jax.checkpoint(_apply)(params[name], state[name],
                                                  x, lrng, mask)
                else:
                    x, s = layer.apply(params[name], state[name], x,
                                       train=train, rng=lrng, mask=mask)
            new_state[name] = s
            if mask is not None and self._layer_types:
                # Mask propagation (the reference's feedForwardMaskArray):
                # once a layer leaves sequence space or changes the sequence
                # length, the [B,T] mask no longer applies downstream.
                t_in, t_out = self._layer_types[i], self._layer_types[i + 1]
                # None (dynamic T) vs a fixed length counts as a change:
                # e.g. LearnedSelfAttention emits n_queries steps regardless
                # of input length, so the [B,T] mask is stale either way.
                if (t_out.kind != "recurrent"
                        or (t_in.kind == "recurrent"
                            and t_in.shape[0] != t_out.shape[0])):
                    mask = None
        return x, new_state

    def _loss(self, params: Params, state: Params, x, y, rng,
              features_mask=None, labels_mask=None, train: bool = True
              ) -> Tuple[jnp.ndarray, Params]:
        """Score = data loss (+ l1/l2 penalties, matching the reference's
        `calcRegularizationScore` contribution to `score()`).

        features_mask feeds the forward pass (sequence padding masks for
        pooling/rnn layers); labels_mask feeds the loss reduction — the same
        split the reference makes in `MultiLayerNetwork.setLayerMaskArrays`.
        """
        out_idx = len(self.conf.layers) - 1
        head = self.conf.layers[out_idx]
        if not hasattr(head, "compute_loss"):
            raise ValueError("Last layer must be an OutputLayer/LossLayer")
        h, new_state = self._forward(params, state, x, train=train, rng=rng,
                                     mask=features_mask, upto=out_idx)
        name = self.conf.layer_name(out_idx)
        hrng = None if rng is None else jax.random.fold_in(rng, out_idx)
        hp, h = self._cast_compute(params[name], h)  # head matmul bf16 too
        with jax.named_scope("loss"):
            loss = head.compute_loss(hp, state[name], h, y, train=train,
                                     rng=hrng, mask=labels_mask)
            loss = loss + self._reg_penalty(params)
        return loss, new_state

    def _reg_penalty(self, params: Params):
        penalty = 0.0
        for i, layer in enumerate(self.conf.layers):
            name = self.conf.layer_name(i)
            l1 = layer.l1 if layer.l1 is not None else self.conf.l1
            l2 = layer.l2 if layer.l2 is not None else self.conf.l2
            if l1 == 0.0 and l2 == 0.0:
                continue
            rmask = layer.regularizable_mask(params[name])
            for w in _masked_leaves(params[name], rmask):
                if l1:
                    penalty = penalty + l1 * jnp.sum(jnp.abs(w))
                if l2:
                    # reference L2Regularization: 0.5 * coeff * ||w||^2
                    penalty = penalty + 0.5 * l2 * jnp.sum(w * w)
        return penalty

    # ---- what the trainer asks of the model (nn/trainer.py) ----
    def _update_entries(self):
        return [(self.conf.layer_name(i), layer, self._updater_for(i))
                for i, layer in enumerate(self.conf.layers)]

    def _batch_loss(self, params, state, batch, rng):
        x, y, fmask, lmask = batch
        return self._loss(params, state, x, y, rng, fmask, lmask)

    def _normalize_batch(self, batch):
        if self._device_norm is None:
            return batch
        x, y, fmask, lmask = batch
        return (self._device_norm.apply_features(x),
                self._device_norm.apply_labels(y), fmask, lmask)

    @staticmethod
    def _batch_rows(batch, axis: int) -> int:
        return int(batch[0].shape[axis])

    def fit_steps(self, xs, ys, features_masks=None, labels_masks=None):
        """Run `k` training steps in one device dispatch.

        Two input forms: stacked `[k, batch, ...]` arrays with a leading
        steps axis, or lists of `k` per-step `[batch, ...]` arrays (the
        streaming prefetch path) — the latter are stacked *inside* the
        compiled dispatch, so pre-staged device batches fuse into the scan
        without an eager host- or device-side stack copy.  Equivalent to
        `k` sequential `fit(x, y)` calls (same math, same updater/iteration
        semantics) but compiled as a single `lax.scan`, eliminating
        per-step host→device dispatch latency.  Listeners fire once per
        block with the final loss; per-step losses are returned as a
        length-k array.  (With gradient sharing installed the block runs as
        k two-phase steps: `LayerwiseTrainer._fit_block`.)"""
        from deeplearning4j_tpu.utils.scan_fit import check_steps_axes
        if isinstance(xs, (list, tuple)):
            k = len(xs)
            if not (isinstance(ys, (list, tuple)) and len(ys) == k):
                raise ValueError("list-form fit_steps needs xs and ys as "
                                 f"equal-length lists, got {k} xs / "
                                 f"{'non-list' if not isinstance(ys, (list, tuple)) else len(ys)} ys")
            fms = features_masks if features_masks is not None else [None] * k
            lms = labels_masks if labels_masks is not None else [None] * k
            batches = tuple(
                (jnp.asarray(xs[i]), jnp.asarray(ys[i]),
                 None if fms[i] is None else jnp.asarray(fms[i]),
                 None if lms[i] is None else jnp.asarray(lms[i]))
                for i in range(k))
            return self._fit_block(batches, k, listed=True)
        xs = jnp.asarray(xs)
        ys = jnp.asarray(ys)
        fm = None if features_masks is None else jnp.asarray(features_masks)
        lm = None if labels_masks is None else jnp.asarray(labels_masks)
        k = check_steps_axes([("xs", xs), ("ys", ys), ("features_masks", fm),
                              ("labels_masks", lm)])
        return self._fit_block((xs, ys, fm, lm), int(k))

    # ---- public API ----
    def fit(self, data, labels=None, *, epochs: int = 1, features_mask=None,
            labels_mask=None, fused_steps: Optional[int] = None):
        """fit(x, y) for one batch, or fit(iterator, epochs=N)
        (reference `fit(INDArray, INDArray)` / `fit(DataSetIterator, int)`).

        `fused_steps=k` stacks k consecutive batches and trains them in a
        single compiled dispatch (`fit_steps`), hiding per-step host
        dispatch latency; odd-sized tail batches (and any batch whose
        shape differs from its block) fall back to the per-step path, so
        results are identical to `fused_steps=1` up to listener cadence.
        Unset, it defaults to the installed schedule's (`apply_schedule`),
        else 1."""
        if labels is None:
            return self._fit_epochs(data, epochs, fused_steps)
        if fused_steps not in (None, 1):
            raise ValueError(
                "fused_steps applies to the iterator form only; for a "
                "pre-stacked [k, batch, ...] block call fit_steps(xs, ys)")
        self._fit_batch(jnp.asarray(data), jnp.asarray(labels),
                        features_mask, labels_mask)
        return self

    def _fit_dataset(self, ds):
        fm = getattr(ds, "features_mask", None)
        lm = getattr(ds, "labels_mask", None)
        self._fit_batch(jnp.asarray(ds.features), jnp.asarray(ds.labels),
                        None if fm is None else jnp.asarray(fm),
                        None if lm is None else jnp.asarray(lm))

    def _fit_epoch_fused(self, iterator, k: int):
        # streaming fused epoch: device_blocks yields per-step staged
        # arrays and fit_steps stacks them INSIDE the compiled dispatch —
        # no per-block host np.stack copy and no eager device stack;
        # prefetched (already-device) batches fuse without any H2D.
        # Mixed-mask blocks degrade to the per-step path instead of
        # silently dropping later batches' masks.
        from deeplearning4j_tpu.data.pipeline import device_blocks
        for kind, payload in device_blocks(iterator, k):
            if kind == "single":
                self._fit_dataset(payload)
            else:
                self.fit_steps(*payload)

    def set_normalizer(self, normalizer) -> "MultiLayerNetwork":
        """Fold a fitted normalizer (NormalizerStandardize / MinMaxScaler /
        ImagePreProcessingScaler, or a DeviceNormalizer) into the compiled
        train step and output fn as an on-device prologue, replacing
        host-side `set_pre_processor` ETL.  Pass None to clear.  Triggers
        a re-trace on the next step (stats are executable constants)."""
        from deeplearning4j_tpu.data.pipeline import DeviceNormalizer
        self._device_norm = (None if normalizer is None
                             else DeviceNormalizer.from_host(normalizer))
        self._invalidate_steps()
        self._output_fn = None
        return self

    def score_for(self, x, y, features_mask=None, labels_mask=None) -> float:
        """Score on given data without updating (reference `score(DataSet)`):
        eval mode — no dropout, BN uses running statistics."""
        x, y = jnp.asarray(x), jnp.asarray(y)
        if self._device_norm is not None:
            x = self._device_norm.apply_features(x)
            y = self._device_norm.apply_labels(y)
        loss, _ = self._loss(self.params_, self.state_, x,
                             y, None, features_mask, labels_mask,
                             train=False)
        return float(loss)

    def output(self, x, train: bool = False) -> jnp.ndarray:
        """Inference forward pass (reference `output(INDArray)`), jitted.
        An attached on-device normalizer (`set_normalizer`) applies here
        too, so inference sees the same prologue as training."""
        if self._output_fn is None:
            def fwd(p, s, x_):
                if self._device_norm is not None:
                    x_ = self._device_norm.apply_features(x_)
                return self._forward(p, s, x_, train=False, rng=None)[0]
            self._output_fn = jax.jit(fwd)
        return self._output_fn(self.params_, self.state_, jnp.asarray(x))

    def feed_forward(self, x, train: bool = False) -> List[jnp.ndarray]:
        """All layer activations (reference `feedForward()`)."""
        acts = [jnp.asarray(x)]
        h = acts[0]
        state = self.state_
        for i in range(len(self.conf.layers)):
            name = self.conf.layer_name(i)
            h, _ = self.conf.layers[i].apply(
                self.params_[name], state[name], h, train=train, rng=None)
            acts.append(h)
        return acts

    def evaluate(self, iterator, evaluation=None):
        """Classification eval over an iterator (reference
        `evaluate(DataSetIterator)`)."""
        from deeplearning4j_tpu.train.evaluation import Evaluation
        ev = evaluation or Evaluation()
        if hasattr(iterator, "reset"):
            iterator.reset()
        for ds in iterator:
            out = self.output(ds.features)
            ev.eval(np.asarray(ds.labels), np.asarray(out))
        return ev

    # ---- flat-param view (checkpoint/API contract) ----
    def num_params(self) -> int:
        return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(self.params_))

    def params(self) -> np.ndarray:
        """Single flat parameter vector — the reference's flattened-view
        `params()` contract, preserved at the boundary only (internally
        params live as a sharded pytree)."""
        leaves = jax.tree_util.tree_leaves(self.params_)
        return np.concatenate([np.asarray(l).ravel() for l in leaves]) if leaves \
            else np.zeros((0,), np.float32)

    def set_params(self, flat: np.ndarray):
        leaves, treedef = jax.tree_util.tree_flatten(self.params_)
        out, off = [], 0
        for l in leaves:
            n = int(np.prod(l.shape))
            out.append(jnp.asarray(flat[off:off + n], l.dtype).reshape(l.shape))
            off += n
        if off != flat.size:
            raise ValueError(f"Param count mismatch: {flat.size} vs {off}")
        self.params_ = jax.tree_util.tree_unflatten(treedef, out)

    # ---- gradient-check hook ----
    def gradient_for(self, x, y, features_mask=None, labels_mask=None) -> Params:
        """Analytic gradients of the score wrt params (no update) — the
        `computeGradientAndScore` half used by GradientCheckUtil.  Eval mode,
        consistent with `score_for` finite differences (BN running stats,
        no dropout)."""
        x, y = jnp.asarray(x), jnp.asarray(y)
        if self._device_norm is not None:   # same prologue as score_for
            x = self._device_norm.apply_features(x)
            y = self._device_norm.apply_labels(y)

        def loss_fn(p):
            return self._loss(p, self.state_, x, y,
                              None, features_mask, labels_mask,
                              train=False)[0]
        return jax.grad(loss_fn)(self.params_)

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listeners(self, *listeners):
        self.listeners.extend(listeners)
        return self

    # ---- persistence (delegates to ModelSerializer) ----
    def save(self, path, save_updater: bool = True):
        from deeplearning4j_tpu.utils.serialization import write_model
        write_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path, load_updater: bool = True) -> "MultiLayerNetwork":
        from deeplearning4j_tpu.utils.serialization import read_model
        return read_model(path, load_updater=load_updater)
