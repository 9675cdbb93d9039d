"""Composed 3D parallelism: dp x tp x pp in ONE train step.

Reference: the reference composes its two distribution mechanisms in a
single job — Spark orchestration over nodes with ParallelWrapper + Aeron
gradient sharing inside each node (`dl4j-spark-parameterserver/`,
SURVEY.md §3.4).  The TPU-idiomatic form of that composed story is one
mesh with three axes and one jitted step:

- ``data``  — batch sharding, gradient psum (the DP role)
- ``model`` — Megatron-style tensor parallelism for the MLP
  (column-parallel W1, row-parallel W2) *with sequence parallelism on
  the same axis*: activations stay sequence-sharded, an ``all_gather``
  materializes the full sequence only for the TP matmuls and a
  ``psum_scatter`` returns partial sums to sequence shards — and the
  attention itself runs as a **ring** over this axis
  (`ring_attention`), so the long-context path lives inside the tp
  group (scaling-book §sequence-parallelism).
- ``pipe``  — GPipe stage parallelism: homogeneous transformer stages
  with params stacked on a leading [S, ...] axis, microbatches streamed
  through a scan of compute + ``ppermute`` ticks (same schedule as
  `pipeline.pipeline_apply`, inlined here so the block can use
  model-axis collectives).

`composed_oracle` is the single-device semantics the sharded step must
match bit-for-bit up to fp tolerance — the correctness contract the
multihost test and the dryrun both check.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel.ring_attention import ring_attention


def init_stage_params(rng, n_stages: int, d_model: int, n_heads: int,
                      d_ff: int) -> Dict[str, jnp.ndarray]:
    """Per-stage transformer-block params stacked on a leading [S, ...]
    axis (the homogeneous-stage contract of the pipeline)."""
    import numpy as np
    def g(*s, scale=0.2):
        return jnp.asarray(rng.randn(*s).astype(np.float32) * scale)
    S, D, F = n_stages, d_model, d_ff
    return {
        "wqkv": g(S, D, 3 * D), "wo": g(S, D, D),
        "w1": g(S, D, F), "w2": g(S, F, D),
        "ln1_g": jnp.ones((S, D), jnp.float32),
        "ln1_b": jnp.zeros((S, D), jnp.float32),
        "ln2_g": jnp.ones((S, D), jnp.float32),
        "ln2_b": jnp.zeros((S, D), jnp.float32),
    }


def stage_specs(tp_axis: str = "model", pipe_axis: str = "pipe"):
    """PartitionSpecs for the stacked stage tree: every leaf is sharded
    on the stage axis; the MLP weights additionally shard on the tp axis
    (column-parallel W1 on its output dim, row-parallel W2 on its input
    dim).  Attention weights replicate across tp — the tp axis carries
    the sequence for attention (ring), not the heads."""
    return {
        "wqkv": P(pipe_axis, None, None), "wo": P(pipe_axis, None, None),
        "w1": P(pipe_axis, None, tp_axis), "w2": P(pipe_axis, tp_axis,
                                                   None),
        "ln1_g": P(pipe_axis, None), "ln1_b": P(pipe_axis, None),
        "ln2_g": P(pipe_axis, None), "ln2_b": P(pipe_axis, None),
    }


def _ln(x, g, b, eps=1e-5):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.var(x, -1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def _split_heads(x, n_heads):
    b, t, d = x.shape
    return x.reshape(b, t, n_heads, d // n_heads).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, t, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * dh)


def block_sp(p, h, n_heads: int, tp_axis: str):
    """One transformer block on a sequence-sharded activation
    [mb, T_local, D]; runs INSIDE shard_map with `tp_axis` manual."""
    # attention sublayer: ring over the tp axis (sequence-parallel)
    x = _ln(h, p["ln1_g"], p["ln1_b"])
    qkv = x @ p["wqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    att = ring_attention(_split_heads(q, n_heads),
                         _split_heads(k, n_heads),
                         _split_heads(v, n_heads),
                         axis_name=tp_axis, causal=True)
    h = h + _merge_heads(att) @ p["wo"]
    # MLP sublayer: Megatron sequence-parallel TP — gather the sequence
    # for the sharded matmuls, scatter the partial sums back
    x = _ln(h, p["ln2_g"], p["ln2_b"])
    full = jax.lax.all_gather(x, tp_axis, axis=1, tiled=True)
    u = jax.nn.relu(full @ p["w1"])          # [mb, T, F_local]
    part = u @ p["w2"]                       # [mb, T, D] partial sum
    mlp = jax.lax.psum_scatter(part, tp_axis, scatter_dimension=1,
                               tiled=True)   # [mb, T_local, D]
    return h + mlp


def block_oracle(p, h, n_heads: int):
    """Single-device semantics of `block_sp` (full sequence)."""
    x = _ln(h, p["ln1_g"], p["ln1_b"])
    qkv = x @ p["wqkv"]
    q, k, v = (_split_heads(t, n_heads) for t in jnp.split(qkv, 3, -1))
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k,
                   preferred_element_type=jnp.float32)
    T = q.shape[2]
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -1e30)
    att = jnp.einsum("bhqk,bhkd->bhqd",
                     jax.nn.softmax(s, -1).astype(v.dtype), v)
    h = h + _merge_heads(att) @ p["wo"]
    x = _ln(h, p["ln2_g"], p["ln2_b"])
    return h + jax.nn.relu(x @ p["w1"]) @ p["w2"]


def composed_apply(stacked, x, mesh: Mesh, n_heads: int,
                   data_axis: str = "data", tp_axis: str = "model",
                   pipe_axis: str = "pipe", num_microbatches=None,
                   remat: bool = False):
    """Forward through S pipelined sequence-parallel TP blocks.

    x: [B, T, D] with B sharded over `data_axis` and T over `tp_axis`.
    stacked: `init_stage_params` tree (leaves [S, ...]).
    `remat=True` wraps the per-tick block in `jax.checkpoint` — at real
    scale the pipeline holds M+S-1 ticks of activations live through the
    backward pass, exactly where rematerialization pays (HBM for FLOPs).
    Returns [B, T, D] with the same sharding.
    """
    S = mesh.shape[pipe_axis]
    M = num_microbatches or S
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} % {M} microbatches != 0")
    mb = B // M
    xs = x.reshape(M, mb, *x.shape[1:])

    specs = stage_specs(tp_axis, pipe_axis)
    in_x = P(None, data_axis, tp_axis, None)     # [M, mb, T, D]

    block = block_sp
    if remat:
        block = jax.checkpoint(block_sp, static_argnums=(2, 3))

    @partial(shard_map, mesh=mesh, in_specs=(specs, in_x),
             out_specs=in_x, check_vma=False)
    def run(params, xs_loc):
        p_local = jax.tree_util.tree_map(lambda l: l[0], params)
        stage = jax.lax.axis_index(pipe_axis)
        zeros = jnp.zeros_like(xs_loc[0])

        def tick(carry, t):
            incoming, outputs = carry
            inject = xs_loc[jnp.minimum(t, M - 1)]
            act_in = jnp.where(stage == 0, inject, incoming)
            y = block(p_local, act_in, n_heads, tp_axis)
            out_idx = t - (S - 1)
            valid = jnp.logical_and(stage == S - 1, out_idx >= 0)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid, y,
                                   outputs[jnp.maximum(out_idx, 0)]),
                jnp.maximum(out_idx, 0), 0)
            passed = jax.lax.ppermute(
                y, pipe_axis, [(i, (i + 1) % S) for i in range(S)])
            return (passed, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (zeros, jnp.zeros_like(xs_loc)), jnp.arange(M + S - 1))
        contrib = jnp.where(stage == S - 1, outputs,
                            jnp.zeros_like(outputs))
        # stay [M, mb_local, T_local, D]: the microbatch axis must merge
        # GLOBALLY (a local merge would interleave the data shards)
        return jax.lax.psum(contrib, pipe_axis)

    return run(stacked, xs).reshape(B, *x.shape[1:])


def composed_oracle(stacked, x, n_heads: int):
    """Sequential single-device semantics of `composed_apply`."""
    S = jax.tree_util.tree_leaves(stacked)[0].shape[0]

    def body(h, i):
        p_i = jax.tree_util.tree_map(lambda l: l[i], stacked)
        return block_oracle(p_i, h, n_heads), None

    h, _ = jax.lax.scan(body, x, jnp.arange(S))
    return h


def composed_train_step(mesh: Mesh, n_heads: int, lr: float = 0.1,
                        remat: bool = False, **axes):
    """Build the jitted full train step: forward through the 3D-parallel
    stack, MSE loss, grads, SGD update.  Returns step(params, x, y) ->
    (new_params, loss)."""

    @jax.jit
    def step(params, x, y):
        def loss_fn(p):
            out = composed_apply(p, x, mesh, n_heads, remat=remat,
                                 **axes)
            return jnp.mean((out - y) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new = jax.tree_util.tree_map(lambda a, g: a - lr * g, params,
                                     grads)
        return new, loss

    return step


class ComposedParallel:
    """Facade over the 3D-parallel train step with optional hierarchical
    compressed gradient sharing across hosts.

    Without sharing: `fit_batch` is `composed_train_step` (one jitted
    dp×tp×pp step).  With a `HierarchicalGradientSharing` config the step
    splits the same way the nn models' does — a jitted grad half (all
    intra-mesh collectives included), the host-side compressed DCN
    exchange (`parallel.hierarchical`), and a jitted apply half — so a
    gang of these (one per host, each on its own local 3D mesh) trains
    with threshold-int streams as the only cross-host traffic."""

    def __init__(self, mesh: Mesh, n_heads: int, lr: float = 0.1,
                 remat: bool = False, gradient_sharing=None, **axes):
        self.mesh = mesh
        self.n_heads = n_heads
        self.lr = lr
        self._sharing = None
        if gradient_sharing is not None:
            from deeplearning4j_tpu.parallel.hierarchical import (
                HierarchicalAllReduce, HierarchicalGradientSharing)
            self._sharing = (gradient_sharing
                             if isinstance(gradient_sharing,
                                           HierarchicalAllReduce)
                             else HierarchicalAllReduce(gradient_sharing))
        self._step = composed_train_step(mesh, n_heads, lr=lr, remat=remat,
                                         **axes)

        @jax.jit
        def grad_fn(params, x, y):
            def loss_fn(p):
                out = composed_apply(p, x, mesh, n_heads, remat=remat,
                                     **axes)
                return jnp.mean((out - y) ** 2)
            return jax.value_and_grad(loss_fn)(params)

        @jax.jit
        def apply_fn(params, grads):
            return jax.tree_util.tree_map(lambda a, g: a - lr * g,
                                          params, grads)

        self._grad_fn = grad_fn
        self._apply_fn = apply_fn

    @property
    def gradient_sharing(self):
        return self._sharing

    def fit_batch(self, params, x, y):
        """(params, loss) after one step; with sharing active the grads
        cross the compressed DCN hop between the two jitted halves."""
        if self._sharing is None:
            with self.mesh:
                return self._step(params, x, y)
        with self.mesh:
            loss, grads = self._grad_fn(params, x, y)
        combined = self._sharing.exchange(grads)
        with self.mesh:
            return self._apply_fn(params, combined), loss

    def close(self) -> None:
        if self._sharing is not None:
            self._sharing.close()


def composed_train_steps(mesh: Mesh, n_heads: int, lr: float = 0.1,
                         remat: bool = False, **axes):
    """Fused k-step form of `composed_train_step`: the fused-dispatch
    lever (utils/scan_fit.py) composed WITH 3D parallelism — k
    dp×tp×pp steps (pipeline ticks, TP collectives, DP psum all inside)
    run as one `lax.scan` dispatch.  `xs`/`ys` carry a leading steps
    axis; returns (params, per-step losses)."""

    @jax.jit
    def steps(params, xs, ys):
        def tick(p, batch):
            x, y = batch

            def loss_fn(q):
                out = composed_apply(q, x, mesh, n_heads, remat=remat,
                                     **axes)
                return jnp.mean((out - y) ** 2)

            loss, grads = jax.value_and_grad(loss_fn)(p)
            return jax.tree_util.tree_map(lambda a, g: a - lr * g, p,
                                          grads), loss

        return jax.lax.scan(tick, params, (xs, ys))

    return steps
