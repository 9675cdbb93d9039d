"""The layer-wise trainer: how a model made of named layers turns a loss
into a compiled, cached, dispatched train step.

`MultiLayerNetwork` and `ComputationGraph` inherit `LayerwiseTrainer`.  It
owns the step builders (the fused step, its grad/apply split for
hierarchical gradient sharing, the k-step scan), their executable cache and
schedule, the dispatch with its bookkeeping, and the epoch loop.  A model
supplies what really differs: its AOT key prefix, how many positional batch
arguments its step takes, its update entries, its loss on a batch tuple, its
device-normalizer prologue, the rows of a batch, and the argument handling
of `fit` / `fit_steps` / `_fit_dataset` / `_fit_epoch_fused`.

The traced functions keep one positional layout whatever the model:
`step(params, state, opt_state, *batch, rng, iteration, epoch)`,
`grad_step(params, state, *batch, rng)`,
`apply_step(params, opt_state, grads, iteration, epoch)`.
"""
from __future__ import annotations

import time
from typing import Any, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.monitor.instrument import TrainingInstruments
from deeplearning4j_tpu.monitor.spans import note, note_step, span
from deeplearning4j_tpu.train.updaters import (
    IUpdater, apply_gradient_normalization)
from deeplearning4j_tpu.utils.counters import advance, device_counters


def _add_scaled_where(upd, params, mask, scale):
    """upd += scale * params wherever mask is True (decoupled weight decay)."""
    if isinstance(mask, dict):
        return {k: _add_scaled_where(upd[k], params[k], mask[k], scale)
                for k in upd}
    if mask:
        return jax.tree_util.tree_map(lambda u, p: u + scale * p, upd, params)
    return upd


def apply_layer_updates(entries: Iterable[Tuple[str, Any, IUpdater]], conf,
                        master, opt_state, grads, iteration, epoch, zt=None,
                        *, grads_in_update_layout: bool = False,
                        skip_empty: bool = False):
    """The per-layer update loop: `(new_params, new_opt_state)` from the
    master parameters, the updater state and the gradients.

    `entries` are the model's `(name, layer_or_None, updater)` in update
    order; a None layer is a graph vertex that is not a layer (it takes the
    configuration's defaults and no weight decay).  `zt` is the ZeRO-1
    transform (`parallel.zero.Zero1Transform`) or None.  With
    `grads_in_update_layout` (the apply half of the split step) the
    gradients came off the wire padded and sharded already, so they are
    re-pinned with `constrain_update` before normalization and not
    scattered again; otherwise (the fused step) they are normalized and
    then reduce-scattered.  `skip_empty` passes an entry with an empty
    parameter tree through untouched, as a frozen one: `ComputationGraph`
    asks for it, `MultiLayerNetwork` runs such an entry through its updater
    (which maps over nothing: tests/test_trainer.py holds the two equal)."""
    new_params, new_opt = {}, {}
    for name, layer, upd_cfg in entries:
        if (skip_empty and not master[name]) or (
                layer is not None and layer.frozen):
            # FrozenLayer semantics (reference `nn/layers/FrozenLayer`):
            # no update applied, updater state untouched.
            new_params[name], new_opt[name] = master[name], opt_state[name]
            continue
        with jax.named_scope(f"updater/{name}"):
            g = grads[name]
            if zt is not None and grads_in_update_layout:
                g = zt.constrain_update(name, g)
            own = (layer is not None
                   and layer.gradient_normalization is not None)
            gn = (layer.gradient_normalization if own
                  else conf.gradient_normalization)
            if gn:
                thr = (layer.gradient_normalization_threshold if own
                       else conf.gradient_normalization_threshold)
                g = apply_gradient_normalization(g, gn, thr)
            if zt is None:
                p_upd = master[name]
            else:
                if not grads_in_update_layout:
                    # reduce-scatter the (already normalized) grads
                    g = zt.scatter(name, g)
                # the updater runs on this device's shard of params/moments
                p_upd = zt.update_view(name, master[name])
            upd, new_o = upd_cfg.apply(opt_state[name], g, iteration,
                                       epoch, params=p_upd)
            # decoupled weight decay (reference WeightDecay regularization,
            # applyLR=true): update += lr * coeff * w for regularizable params
            wd = (layer.weight_decay if layer is not None
                  and layer.weight_decay is not None else conf.weight_decay)
            if wd and layer is not None:
                lr = upd_cfg.lr_at(iteration, epoch)
                upd = _add_scaled_where(
                    upd, p_upd, layer.regularizable_mask(p_upd), lr * wd)
            new_p = jax.tree_util.tree_map(lambda p_, u_: p_ - u_, p_upd,
                                           upd)
            if zt is not None:
                new_p = zt.restore(name, new_p)
                new_o = zt.constrain_opt(name, new_o)
            new_params[name], new_opt[name] = new_p, new_o
    return new_params, new_opt


class CompiledStepOwner:
    """What every front end with a cached, compiled train step shares —
    the two layer-wise models below and `autodiff.SameDiff`: the executable
    cache in play, the autotuned schedule, the donation it decides and the
    persistent tier's disk key.  A subclass sets `_AOT_PREFIX` (and
    `_DONATED`, the argnums of its step's carried trees) and defines
    `_invalidate_steps()`."""

    _AOT_PREFIX: str            # "mln" / "cg" / "samediff": the cache's kinds
    _DONATED: tuple = (0, 1, 2)

    def __init__(self):
        self._step_transform = None   # ZeRO-1 weight update (parallel/zero)
        self._exec_cache_override = None  # compile.PersistentExecutableCache
        self._schedule = None             # compile.Schedule (autotuner)

    def _invalidate_steps(self) -> None:
        """Drop every compiled step: the next dispatch rebuilds (and
        re-traces) it with the current transform, cache, schedule,
        normalizer and sharing."""
        raise NotImplementedError

    def _exec_cache(self):
        """The persistent executable cache in play: the per-model override
        (`set_executable_cache`), else the process default — None keeps
        the plain jax.jit path."""
        if self._exec_cache_override is not None:
            return self._exec_cache_override
        from deeplearning4j_tpu.compile import default_cache
        return default_cache()

    def set_executable_cache(self, cache):
        """Route this model's train-step compilation through a
        `compile.PersistentExecutableCache` (or a directory path), so a
        restarted process deserializes the step instead of recompiling it.
        None reverts to the process default ($DL4J_TPU_EXEC_CACHE /
        `compile.set_default_cache`).  Triggers a step rebuild."""
        if isinstance(cache, str):
            from deeplearning4j_tpu.compile import PersistentExecutableCache
            cache = PersistentExecutableCache(cache)
        self._exec_cache_override = cache
        self._invalidate_steps()
        return self

    def apply_schedule(self, schedule):
        """Install an autotuned `compile.Schedule`: the iterator form of
        `fit()` defaults its `fused_steps` to the schedule's and the step
        builders honor `schedule.donation`.  (`zero1` is a wrapper-level
        knob — `parallel.ParallelWrapper.apply_schedule` handles it and
        delegates the rest here.)  Triggers a step rebuild."""
        self._schedule = schedule
        self._invalidate_steps()
        return self

    def _donate_argnums(self) -> tuple:
        if self._schedule is not None and not self._schedule.donation:
            return ()
        return self._DONATED

    def _aot_key_parts(self, kind: str = "train_step") -> dict:
        """Disk-key parts for the persistent tier: model architecture (not
        weights — restarts and same-arch rolls share the executable) plus
        the step-shaping config the body closes over."""
        from deeplearning4j_tpu.compile import (model_fingerprint,
                                                transform_fingerprint)
        return {"kind": f"{self._AOT_PREFIX}_{kind}",
                "model": model_fingerprint(self),
                "transform": transform_fingerprint(self._step_transform)}


class LayerwiseTrainer(CompiledStepOwner):
    """Base of `MultiLayerNetwork` and `ComputationGraph` (see the module
    docstring).  A subclass sets `_AOT_PREFIX`, `_BATCH_ARITY`,
    `_SKIP_EMPTY_ENTRIES` and defines `_update_entries()`,
    `_batch_loss(params, state, batch, rng)`, `_normalize_batch(batch)`,
    `_batch_rows(batch, axis)`, `_fit_dataset(ds)` and
    `_fit_epoch_fused(iterator, k)`."""

    _BATCH_ARITY: int           # positional batch arguments of the step
    _SKIP_EMPTY_ENTRIES = False  # see apply_layer_updates(skip_empty=)

    def __init__(self):
        super().__init__()
        self._train_step = None
        self._scan_step = None
        self._grad_step = None    # hierarchical-sharing split: grad half
        self._apply_step = None   # hierarchical-sharing split: apply half
        self._grad_sharing = None  # parallel.hierarchical.HierarchicalAllReduce
        self._instr: Optional[TrainingInstruments] = None
        self._score = None
        self._last_batch_size = None

    def _instruments(self) -> TrainingInstruments:
        """Lazy telemetry handles (monitor registry series labeled by
        model kind) — created on first dispatch, shared series thereafter."""
        if self._instr is None:
            self._instr = TrainingInstruments(type(self).__name__)
        return self._instr

    def _invalidate_steps(self) -> None:
        self._train_step = None
        self._scan_step = None
        self._grad_step = None
        self._apply_step = None

    # ---- step bodies ----
    def _loss_and_grads(self, params, state, batch, rng):
        """The device-normalizer prologue (stats are executable constants,
        the apply fuses into the forward — raw batches stream to the device
        with zero host ETL, data.pipeline), the rng split, the all-gather of
        ZeRO-1 master params, forward and backward."""
        zt = self._step_transform
        with jax.named_scope("input_normalize"):
            batch = self._normalize_batch(batch)
        # split inside the compiled step: keeps the per-step host work at
        # zero device round-trips (the carry key + iteration counter live
        # on device and flow step→step without fresh H2D transfers)
        rng, srng = jax.random.split(rng)
        if zt is not None:
            # all-gather the data-axis-sharded master params once;
            # forward/backward run on the gathered (or TP) layout
            params = zt.gather_all(params)

        def loss_fn(p):
            return self._batch_loss(p, state, batch, srng)

        (loss, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return loss, new_state, grads, rng

    def _build_step_body(self):
        conf = self.conf
        zt = self._step_transform   # ZeRO-1 sharded weight update, or None

        def step(params, state, opt_state, *args):
            *batch, rng, iteration, epoch = args
            loss, new_state, grads, rng = self._loss_and_grads(
                params, state, batch, rng)
            new_params, new_opt = apply_layer_updates(
                self._update_entries(), conf, params, opt_state, grads,
                iteration, epoch, zt, skip_empty=self._SKIP_EMPTY_ENTRIES)
            return new_params, new_state, new_opt, loss, rng, iteration + 1

        return step

    def _build_grad_body(self):
        """Grad half of the split step: forward/backward on the local
        mesh (ICI all-reduce via SPMD, reduce-scatter under ZeRO-1), NO
        update.  Params are NOT donated — the apply half needs them."""
        zt = self._step_transform

        def grad_step(params, state, *args):
            *batch, rng = args
            loss, new_state, grads, rng = self._loss_and_grads(
                params, state, batch, rng)
            if zt is not None:
                # ship the reduce-scattered (padded, update-layout) shard —
                # compress the shard, not the gathered tree (ISSUE: ZeRO-1
                # composition); the apply half re-pins the wire grads with
                # constrain_update instead of re-padding.  Empty param
                # subtrees scatter to empty.
                grads = {name: zt.scatter(name, grads[name])
                         for name, _, _ in self._update_entries()}
            return grads, new_state, loss, rng

        return grad_step

    def _build_apply_body(self):
        """Apply half: updater loop on the DCN-combined gradient.
        Gradient normalization runs HERE, on the cross-host-combined
        gradient — the same quantity the single-mesh step normalizes
        (zero pads under ZeRO-1 don't perturb L2 norms)."""
        conf = self.conf
        zt = self._step_transform

        def apply_step(params, opt_state, grads, iteration, epoch):
            new_params, new_opt = apply_layer_updates(
                self._update_entries(), conf, params, opt_state, grads,
                iteration, epoch, zt, grads_in_update_layout=True,
                skip_empty=self._SKIP_EMPTY_ENTRIES)
            return new_params, new_opt, iteration + 1

        return apply_step

    # ---- compiled steps ----
    def _get_train_step(self):
        if self._train_step is None:
            from deeplearning4j_tpu.compile import step_function
            self._train_step = step_function(
                self._build_step_body(),
                donate_argnums=self._donate_argnums(),
                key_base=self._aot_key_parts,
                cache=self._exec_cache(),
                dynamic_argnums=tuple(range(3, 3 + self._BATCH_ARITY)))
        return self._train_step

    def _get_grad_step(self):
        if self._grad_step is None:
            from deeplearning4j_tpu.compile import step_function
            self._grad_step = step_function(
                self._build_grad_body(),
                # state only is donated: params feed the apply half next
                donate_argnums=(1,),
                key_base=lambda: self._aot_key_parts("grad_step"),
                cache=self._exec_cache(),
                dynamic_argnums=tuple(range(2, 2 + self._BATCH_ARITY)))
        return self._grad_step

    def _get_apply_step(self):
        if self._apply_step is None:
            from deeplearning4j_tpu.compile import step_function
            self._apply_step = step_function(
                self._build_apply_body(),
                donate_argnums=(0, 1),
                key_base=lambda: self._aot_key_parts("apply_step"),
                cache=self._exec_cache(),
                dynamic_argnums=())
        return self._apply_step

    def _get_scan_step(self):
        if self._scan_step is None:
            from deeplearning4j_tpu.utils.scan_fit import make_scan_step
            body = self._build_step_body()

            def tick(carry, epoch, batch):
                p, s, o, r, it = carry
                p, s, o, loss, r, it = body(p, s, o, *batch, r, it, epoch)
                return (p, s, o, r, it), loss

            self._scan_step = make_scan_step(
                tick,
                key_base=lambda: self._aot_key_parts("scan_step"),
                cache=self._exec_cache(),
                donate=(self._schedule is None or self._schedule.donation))
        return self._scan_step

    # ---- hierarchical gradient sharing (parallel.hierarchical) ----
    def set_gradient_sharing(self, sharing):
        """Enable/disable hierarchical compressed cross-host gradient
        sharing.  Accepts a `HierarchicalGradientSharing` config (the
        runtime is built here), a prebuilt `HierarchicalAllReduce`, or
        None to clear.  Active sharing splits the compiled step in two —
        a grad half (forward/backward + ICI reduce, emits the local
        gradient tree) and an apply half (updater loop on the DCN-combined
        gradient) — with the host-side compressed exchange between them."""
        from deeplearning4j_tpu.parallel.hierarchical import (
            HierarchicalAllReduce, HierarchicalGradientSharing)
        if sharing is None:
            if self._grad_sharing is not None:
                self._grad_sharing.close()
            self._grad_sharing = None
        elif isinstance(sharing, HierarchicalGradientSharing):
            self._grad_sharing = HierarchicalAllReduce(sharing)
        elif isinstance(sharing, HierarchicalAllReduce):
            self._grad_sharing = sharing
        else:
            raise TypeError(
                "set_gradient_sharing expects HierarchicalGradientSharing, "
                f"HierarchicalAllReduce or None, got {type(sharing).__name__}")
        self._grad_step = None
        self._apply_step = None
        return self

    @property
    def gradient_sharing(self):
        """The installed `HierarchicalAllReduce`, or None."""
        return self._grad_sharing

    # ---- dispatch ----
    def _dispatched(self, t0, steps_fns, loss, new_it, rows_of, axis=0,
                    steps=1, args=None):
        """Bookkeeping after a dispatch that started at `t0`: the
        `step_dispatch` span and series, compile detection on the step
        functions used, the loss (a device array, never read here), the
        batch rows (`rows_of` along `axis`), the counters, the listeners.
        `args`: what the one step function was called with, so that a
        compile event can tell `monitor.lowered_step` which step runs."""
        t1 = time.perf_counter()
        note("step_dispatch", t0, t1, self.iteration)
        ins = self._instruments()
        ins.record_dispatch(t1 - t0, steps=steps)
        for fn in steps_fns:
            if ins.check_compile(fn, self) and args is not None:
                note_step(fn, args)
        self._score = loss
        self._last_batch_size = self._batch_rows(rows_of, axis)
        advance(self, new_it, steps=steps)
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, self.epoch)

    def _fit_batch(self, *batch):
        """One training step on one batch tuple (trailing masks may be
        left out)."""
        batch += (None,) * (self._BATCH_ARITY - len(batch))
        if self._grad_sharing is not None:
            return self._fit_batch_shared(*batch)
        step = self._get_train_step()
        it_dev, ep_dev = device_counters(self)
        args = (self.params_, self.state_, self.opt_state_, *batch,
                self._rng, it_dev, ep_dev)
        t0 = time.perf_counter()
        (self.params_, self.state_, self.opt_state_, loss, self._rng,
         new_it) = step(*args)
        self._dispatched(t0, (step,), loss, new_it, batch, args=args)

    def _fit_batch_shared(self, *batch):
        """One training step through the hierarchical path: compiled grad
        half → host-side DCN exchange → compiled apply half."""
        t0 = time.perf_counter()
        gstep = self._get_grad_step()
        grads, self.state_, loss, self._rng = gstep(
            self.params_, self.state_, *batch, self._rng)
        combined = self._grad_sharing.exchange(grads)
        astep = self._get_apply_step()
        it_dev, ep_dev = device_counters(self)
        self.params_, self.opt_state_, new_it = astep(
            self.params_, self.opt_state_, combined, it_dev, ep_dev)
        self._dispatched(t0, (gstep, astep), loss, new_it, batch)

    def _fit_block(self, batches, k: int, listed: bool = False):
        """The dispatch of `fit_steps`: `batches` is one batch tuple whose
        arrays carry a leading `[k, ...]` steps axis or, `listed`, a tuple
        of k per-step batch tuples (stacked inside the compiled scan).
        Returns the length-k per-step losses."""
        if self._grad_sharing is not None:
            # a host-side exchange cannot run mid-lax.scan: degrade to a
            # per-step two-phase loop — exact same math, the fused-dispatch
            # latency win is traded for the DCN bytes win (documented in
            # docs/performance.md §6)
            losses = []
            for i in range(k):
                self._fit_batch_shared(*(
                    batches[i] if listed else
                    jax.tree_util.tree_map(lambda a: a[i], batches)))
                losses.append(self._score)
            return jnp.stack(losses)
        step = self._get_scan_step()
        it_dev, ep_dev = device_counters(self)
        t0 = time.perf_counter()
        ((self.params_, self.state_, self.opt_state_, self._rng, new_it),
         losses, last_loss) = step((self.params_, self.state_,
                                    self.opt_state_, self._rng, it_dev),
                                   ep_dev, batches)
        self._dispatched(t0, (step,), last_loss, new_it,
                         batches[0] if listed else batches,
                         0 if listed else 1, steps=k)
        return losses

    def _fit_epochs(self, data, epochs: int, fused_steps: Optional[int]):
        """The iterator form of `fit`: `fused_steps` unset defaults to the
        installed schedule's (`apply_schedule`), else 1."""
        if fused_steps is None:
            fused_steps = (self._schedule.fused_steps
                           if self._schedule is not None else 1)
        for _ in range(epochs):
            if hasattr(data, "reset"):
                data.reset()
            with span("fit_epoch", model=type(self).__name__):
                if fused_steps > 1:
                    self._fit_epoch_fused(data, fused_steps)
                else:
                    for ds in data:
                        self._fit_dataset(ds)
            self.epoch += 1
            self._instruments().record_epoch()
            for lst in self.listeners:
                if hasattr(lst, "on_epoch_end"):
                    lst.on_epoch_end(self)
        return self

    def score(self) -> float:
        """Loss of the most recent minibatch (reference `score()`).  This
        is the BLOCKING read: coercing to float waits for the step to
        complete.  Steady-state loops should prefer `score_array()`."""
        return float(self._score) if self._score is not None else float("nan")

    def score_array(self):
        """Loss of the most recent minibatch as a device array (or None
        before the first step).  Never syncs: the array may still be in
        flight — the async-dispatch window stays open until the caller
        coerces it (float/np.asarray), so listeners can record scores
        without stalling the step pipeline."""
        return self._score
