"""Pipeline parallelism: GPipe-style microbatched stage parallelism.

Reference scope: the reference has no pipeline engine (SURVEY §2.3 marks PP
optional — its scale-out is data-parallel only), so this is a TPU-native
extension following the public scaling-book recipe: place S identical
stages on S devices along a `pipe` mesh axis, stream M microbatches
through a `lax.scan` of compute+`ppermute` ticks under `shard_map`.

Key properties:
- SPMD-uniform: every device runs the same block_fn every tick (bubble
  ticks compute on garbage and are masked out), so one XLA program serves
  all stages.
- Differentiable: `jax.grad` through the scan/ppermute yields the reverse
  pipeline schedule automatically — no hand-written backward pass.
- Composable: the `pipe` axis is one axis of a larger mesh, so PP stacks
  with DP/TP axes the usual way.

Constraint (same as every SPMD pipeline): stages must be HOMOGENEOUS — a
stack of identical blocks with per-stage parameters stacked on a leading
[S, ...] axis (the transformer-encoder shape).  Heterogeneous prefixes
(embeddings, heads) run outside the pipelined region.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage_params: list):
    """[params_tree per stage] -> one tree with leaves stacked on axis 0."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def pipeline_apply(block_fn: Callable, stacked_params, x: jnp.ndarray,
                   mesh: Mesh, axis: str = "pipe",
                   num_microbatches: Optional[int] = None) -> jnp.ndarray:
    """Run `x` through S pipelined stages of `block_fn`.

    block_fn(stage_params, microbatch) -> microbatch (same shape).
    stacked_params: leaves [S, ...], S == mesh.shape[axis].
    x: [B, ...]; B must divide by num_microbatches (default S).

    Schedule: M + S - 1 ticks; at tick t stage s processes microbatch
    t - s (when in range).  Activations hop stages via ppermute each tick
    — the ICI-neighbor transfer pattern.
    """
    S = mesh.shape[axis]
    n_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_stages != S:
        raise ValueError(
            f"{n_stages} stacked stages but mesh axis '{axis}' has {S} "
            "devices — stage count must equal the pipe-axis size")
    M = num_microbatches or S
    B = x.shape[0]
    if B % M:
        raise ValueError(f"Batch {B} not divisible by {M} microbatches")
    mb = B // M
    xs = x.reshape(M, mb, *x.shape[1:])

    param_spec = jax.tree_util.tree_map(lambda _: P(axis), stacked_params)

    @partial(shard_map, mesh=mesh,
             in_specs=(param_spec, P()), out_specs=P(),
             check_vma=False)
    def run(params, xs_rep):
        # params leaves arrive as [1, ...] local slices -> this stage's tree
        p_local = jax.tree_util.tree_map(lambda l: l[0], params)
        stage = jax.lax.axis_index(axis)
        zeros = jnp.zeros_like(xs_rep[0])

        def tick(carry, t):
            incoming, outputs = carry
            # stage 0 injects microbatch t (or garbage past the end)
            inject = xs_rep[jnp.minimum(t, M - 1)]
            act_in = jnp.where(stage == 0, inject, incoming)
            y = block_fn(p_local, act_in)
            # last stage emits microbatch t-(S-1) at tick t
            out_idx = t - (S - 1)
            valid = jnp.logical_and(stage == S - 1, out_idx >= 0)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(valid, y, outputs[
                    jnp.maximum(out_idx, 0)]),
                jnp.maximum(out_idx, 0), 0)
            # hand activations to the next stage (ring; wrap is harmless —
            # stage 0 overwrites with injection)
            passed = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            return (passed, outputs), None

        outputs0 = jnp.zeros_like(xs_rep)
        (final_in, outputs), _ = jax.lax.scan(
            tick, (zeros, outputs0), jnp.arange(M + S - 1))
        # only the last stage holds real outputs; share them with everyone
        # (psum over one-hot contribution keeps the program SPMD-uniform)
        contrib = jnp.where(stage == S - 1, outputs,
                            jnp.zeros_like(outputs))
        outputs = jax.lax.psum(contrib, axis)
        return outputs.reshape(B, *x.shape[1:])

    return run(stacked_params, xs)


def sequential_apply(block_fn: Callable, stacked_params,
                     x: jnp.ndarray) -> jnp.ndarray:
    """The semantics pipeline_apply must match: apply the S stages in
    order, single-device (the correctness oracle and the S=1 fallback)."""
    S = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

    def body(h, i):
        p_i = jax.tree_util.tree_map(lambda l: l[i], stacked_params)
        return block_fn(p_i, h), None

    h, _ = jax.lax.scan(body, x, jnp.arange(S))
    return h
