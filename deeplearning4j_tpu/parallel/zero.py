"""ZeRO-1 cross-replica sharded weight update (optimizer-state sharding).

Xu et al., *Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training* (arXiv:2004.13336): instead of every replica
all-reducing full gradients and redundantly running the full optimizer
step on a full replicated copy of the moments, shard the weight update —
reduce-scatter the gradients over the data axis, apply the updater on each
device's 1/N shard of params/moments, and all-gather the updated params
for the next forward.  Same math, ~N× less optimizer-state HBM per
replica, and the all-reduce decomposed into reduce-scatter + all-gather
that XLA can overlap with the backward pass.

GSPMD expression (no hand-written collectives): the step body computes the
usual data-parallel gradients and we pin *layouts* with
`jax.lax.with_sharding_constraint` —

    grads   (all-reduced, replicated)  --constrain P(axis)--> reduce-scatter
    updater runs elementwise on the local shard of params/moments
    new params (sharded)               --constrain P()------> all-gather

`with_sharding_constraint` is value-preserving, so parity with the
replicated path holds by construction; only the schedule changes.

Per-leaf policy (`build_plans`):
  * a TP rule hit (any non-None dim in its `ShardingRules` spec) takes
    precedence — that leaf keeps its tensor-parallel layout everywhere
    and its moments follow it (already distributed; ZeRO adds nothing);
  * leading dim >= N: shard dim 0 over the data axis.  Non-divisible
    leading dims are zero-padded to the next multiple of N *inside the
    step* (jax 0.4.x cannot materialize uneven NamedShardings, and an
    uneven constraint inside jit silently degrades to replicated).
    Padded leaves keep their PERSISTENT param storage replicated at the
    true shape; their moments are stored padded + sharded.  Zero pads
    are a fixed point of every elementwise updater (zero grad -> zero
    moment -> zero update), so the pad region never leaks into values;
  * tiny / scalar leaves (biases smaller than the axis): replicated —
    sharding them would save nothing and cost a collective.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.sharding import ShardingRules, _path_str
from deeplearning4j_tpu.train.updaters import tree_map_like_params

PyTree = Any


@dataclasses.dataclass(frozen=True)
class LeafPlan:
    """Per-param-leaf placement decision.

    `store` is the persistent layout of the param leaf between steps,
    `update` the layout during the optimizer step (where the moments live
    permanently), `compute` the layout for forward/backward."""

    kind: str                 # "shard" | "repl" | "tp"
    shape: Tuple[int, ...]    # true (unpadded) shape
    pad: int                  # zero rows appended to reach divisibility
    store: P
    update: P
    compute: P

    @property
    def padded_shape(self) -> Tuple[int, ...]:
        if not self.shape:
            return self.shape
        return (self.shape[0] + self.pad,) + tuple(self.shape[1:])


def build_plans(params: PyTree, mesh: Mesh, axis: str = "data",
                rules: Optional[ShardingRules] = None) -> PyTree:
    """A `LeafPlan` for every param leaf (same tree structure, plans as
    leaves).  TP rules (when given) win per-leaf; otherwise leading dims
    that can cover the data axis are sharded, the rest replicated."""
    n = mesh.shape[axis]

    def plan(path, leaf):
        shape = tuple(np.shape(leaf))
        if rules is not None:
            spec = rules.spec_for(_path_str(path), shape, mesh)
            if any(s is not None for s in spec):
                return LeafPlan("tp", shape, 0, spec, spec, spec)
        if len(shape) >= 1 and shape[0] >= n:
            pad = (-shape[0]) % n
            store = P(axis) if pad == 0 else P()
            return LeafPlan("shard", shape, pad, store, P(axis), P())
        return LeafPlan("repl", shape, 0, P(), P(), P())

    return jax.tree_util.tree_map_with_path(plan, params)


class Zero1Transform:
    """The step-transform threaded through `_build_step_body()`.

    All methods are trace-time tree_maps emitting value-preserving
    `with_sharding_constraint`s, so they compose with jit donation, the
    `fit_steps` fused scan (layouts are a fixed point of one body
    application) and `compute_dtype` casts (the gather happens on the f32
    master copy; casting fuses after it)."""

    def __init__(self, mesh: Mesh, axis: str, plans: PyTree):
        self.mesh = mesh
        self.axis = axis
        self.plans = plans

    def _ns(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def _sub(self, name: Optional[str]) -> PyTree:
        return self.plans if name is None else self.plans[name]

    # ---- inside-the-step layout moves ----
    def gather_all(self, params: PyTree) -> PyTree:
        """Params at store layout -> compute layout (the all-gather; a
        no-op for replicated leaves, TP leaves keep their TP layout)."""
        return jax.tree_util.tree_map(
            lambda pl, x: jax.lax.with_sharding_constraint(
                x, self._ns(pl.compute)),
            self.plans, params)

    def _to_update(self, pl: LeafPlan, x):
        if pl.pad:
            # jnp.pad, NOT concatenate: the SPMD partitioner miscompiles a
            # concat whose output is constrained onto one axis of a multi-
            # axis mesh (replicated operands get summed over the other
            # axis); the pad op partitions correctly
            x = jnp.pad(x, [(0, pl.pad)] + [(0, 0)] * (x.ndim - 1))
        return jax.lax.with_sharding_constraint(x, self._ns(pl.update))

    def scatter(self, name: Optional[str], grads: PyTree) -> PyTree:
        """All-reduced grads -> update layout (the reduce-scatter)."""
        return jax.tree_util.tree_map(self._to_update, self._sub(name),
                                      grads)

    def update_view(self, name: Optional[str], params: PyTree) -> PyTree:
        """Master params -> the padded/sharded view the updater runs on."""
        return jax.tree_util.tree_map(self._to_update, self._sub(name),
                                      params)

    def restore(self, name: Optional[str], new_params: PyTree) -> PyTree:
        """Updated shards -> persistent store layout (the all-gather for
        leaves whose storage is replicated; pads sliced off)."""
        def r(pl, x):
            if pl.pad:
                # gather at the (even) padded shape FIRST, slice replicated:
                # an uneven slice of the sharded dim hits the same multi-
                # axis-mesh partitioner miscompile as concat (see _to_update)
                x = jax.lax.with_sharding_constraint(x, self._ns(P()))
                x = x[: pl.shape[0]]
            return jax.lax.with_sharding_constraint(x, self._ns(pl.store))
        return jax.tree_util.tree_map(r, self._sub(name), new_params)

    def constrain_update(self, name: Optional[str], grads: PyTree) -> PyTree:
        """Pin an ALREADY-PADDED gradient tree to the update layout.

        The hierarchical-sharing apply-half feeds gradients back that came
        off the wire at the grad-half's output layout — padded leaves are
        padded already, so `scatter` (which pads again) would be wrong;
        this is the re-entry constraint only."""
        return jax.tree_util.tree_map(
            lambda pl, x: jax.lax.with_sharding_constraint(
                x, self._ns(pl.update)),
            self._sub(name), grads)

    def constrain_opt(self, name: Optional[str], opt_state: PyTree) -> PyTree:
        """Pin the new moments to the update layout so the donated output
        matches the input buffers (scalar step counts etc. pass through)."""
        def pin(sub, plan_sub):
            return jax.tree_util.tree_map(
                lambda s, pl: jax.lax.with_sharding_constraint(
                    s, self._ns(pl.update)),
                sub, plan_sub)
        return tree_map_like_params(
            pin, opt_state, self._sub(name), lambda s: s,
            shape_of=lambda pl: pl.padded_shape)


def _params_attr(model) -> str:
    return "variables_" if hasattr(model, "variables_") else "params_"


def _place_params(params: PyTree, plans: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree_util.tree_map(
        lambda pl, leaf: jax.device_put(leaf, NamedSharding(mesh, pl.store)),
        plans, params)


def _place_opt_state(opt_state: PyTree, plans: PyTree, mesh: Mesh) -> PyTree:
    """Moments land padded (host-side zero pad — uneven device_put is
    unsupported) and sharded at their update layout; everything else
    (step counts, scalars, empty states) replicates."""
    repl = NamedSharding(mesh, P())

    def place_moments(sub, plan_sub):
        def one(s, pl):
            a = np.asarray(s)
            if pl.pad:
                a = np.concatenate(
                    [a, np.zeros((pl.pad,) + a.shape[1:], a.dtype)], axis=0)
            return jax.device_put(a, NamedSharding(mesh, pl.update))
        return jax.tree_util.tree_map(one, sub, plan_sub)

    return tree_map_like_params(
        place_moments, opt_state, plans,
        lambda sub: jax.device_put(sub, repl),
        shape_of=lambda pl: pl.shape)


def enable_zero1(model, mesh: Mesh, axis: str = "data",
                 rules: Optional[ShardingRules] = None) -> Zero1Transform:
    """Turn on the sharded weight update for a MultiLayerNetwork,
    ComputationGraph or SameDiff instance: build per-leaf plans, place
    params/moments accordingly, install the step transform and invalidate
    the compiled steps (they re-trace with the collectives baked in).
    Idempotent for an unchanged (mesh, axis).  For SameDiff, enable AFTER
    the graph (and training config) is final — plans snapshot the current
    variable set."""
    existing = getattr(model, "_step_transform", None)
    if existing is not None and existing.mesh is mesh \
            and existing.axis == axis:
        return existing
    attr = _params_attr(model)
    params = getattr(model, attr, None)
    if params is None:
        raise ValueError("model must be initialized before "
                         "optimizer sharding (call init() first)")
    if getattr(model, "opt_state_", None) is None:
        cfg = getattr(model, "training_config", None)
        if cfg is None or cfg.updater is None:
            raise ValueError("optimizer sharding needs an updater: call "
                             "set_training_config(...) first")
        model.opt_state_ = cfg.updater.init_state(params)
    plans = build_plans(params, mesh, axis=axis, rules=rules)
    zt = Zero1Transform(mesh, axis, plans)
    setattr(model, attr, _place_params(params, plans, mesh))
    model.opt_state_ = _place_opt_state(model.opt_state_, plans, mesh)
    if getattr(model, "state_", None) is not None:
        model.state_ = jax.device_put(model.state_,
                                      NamedSharding(mesh, P()))
    model._step_transform = zt
    model._invalidate_steps()
    return zt


def disable_zero1(model) -> None:
    """Remove the step transform and un-pad the stored moments back to
    their true shapes (use before `save()` — padded moments are a device
    layout detail, not a portable checkpoint format).  No-op when ZeRO-1
    was never enabled."""
    zt = getattr(model, "_step_transform", None)
    if zt is None:
        return
    if getattr(model, "opt_state_", None) is not None:
        def unpad(sub, plan_sub):
            # via host: eager-slicing the sharded dim would re-enter the
            # partitioner (see Zero1Transform.restore); this is a rare
            # teardown/checkpoint path, the D2H copy is fine
            return jax.tree_util.tree_map(
                lambda s, pl: (jnp.asarray(np.asarray(s)[: pl.shape[0]])
                               if pl.pad else s),
                sub, plan_sub)
        model.opt_state_ = tree_map_like_params(
            unpad, model.opt_state_, zt.plans, lambda s: s,
            shape_of=lambda pl: pl.padded_shape)
    model._step_transform = None
    model._invalidate_steps()


def reshard_zero1(model, new_mesh: Mesh, axis: str = "data",
                  rules: Optional[ShardingRules] = None) -> Zero1Transform:
    """Re-shard a ZeRO-1 model to a DIFFERENT mesh (elastic world-size
    change: a gang member left or joined, so the data axis shrank or
    grew).  Tears down the old transform through `disable_zero1` — which
    un-pads the moments to their true shapes, the portable layout — and
    re-enables on `new_mesh`, where `build_plans` re-derives shard/repl
    decisions and padding for the new axis size.  The same
    unpad-then-replan route the sharded-checkpoint loader takes when a
    restore lands on a differently-sized mesh, but in-process and without
    a disk round-trip.  Returns the new transform."""
    disable_zero1(model)
    zt = enable_zero1(model, new_mesh, axis=axis, rules=rules)
    # Step OUTPUTS (rng, device-resident counters) are committed to the
    # old mesh's devices; left in place they poison the re-traced step
    # with mixed device sets.  Pull them to host — the next step re-places
    # them on the new mesh like a fresh model's first step would.
    rng = getattr(model, "_rng", None)
    if rng is not None:
        model._rng = jnp.asarray(np.asarray(rng))
    for cached in ("_iter_dev", "_epoch_dev", "_iter_sync", "_epoch_sync"):
        if hasattr(model, cached):
            setattr(model, cached, None)
    return zt


def reshard_to_devices(model, devices, axis: str = "data",
                       rules: Optional[ShardingRules] = None
                       ) -> Optional[Zero1Transform]:
    """Externally-initiated world change (the pod arbiter handing a
    DeviceSlice to or from serving): re-shard the model's ZeRO-1 state
    to a fresh data-axis mesh over exactly `devices` — the surviving
    world after a shrink, or the grown world after a slice returns.
    Returns the new transform, or None (no-op) when ZeRO-1 was never
    enabled — a plain data-parallel model carries no sharded moments to
    move."""
    if getattr(model, "_step_transform", None) is None:
        return None
    from deeplearning4j_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({axis: len(devices)}, devices=list(devices))
    return reshard_zero1(model, mesh, axis=axis, rules=rules)


def opt_state_bytes_per_replica(opt_state: PyTree) -> int:
    """Optimizer-state bytes resident on ONE device: replicated leaves
    count in full, leaves sharded N ways count 1/N — the quantity the
    `training_opt_state_bytes{sharded=}` gauge reports."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(opt_state):
        shards = getattr(leaf, "addressable_shards", None)
        if not shards:
            total += int(getattr(leaf, "nbytes", 0) or 0)
            continue
        dev0 = shards[0].device
        total += sum(int(s.data.nbytes) for s in shards
                     if s.device == dev0)
    return total
