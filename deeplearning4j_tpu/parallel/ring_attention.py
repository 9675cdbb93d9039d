"""Ring attention — sequence/context parallelism over the device mesh.

The reference has NO long-context story (SURVEY.md §5.7: attention exists
only as single-device ops; sequences are truncated).  This is the
capability-exceeding TPU-native addition: shard the sequence axis over mesh
axis `seq`; each step computes blockwise attention against the local KV
shard, then rotates KV around the ring with `ppermute` over ICI while the
online-softmax stats (acc, m, l) accumulate.  Communication overlaps the
next chunk's compute under XLA's scheduler.  (Liu et al. 2023 "Ring
Attention with Blockwise Transformers" — see PAPERS.md.)

Use inside shard_map:

    mesh = make_mesh({"data": 2, "seq": 4})
    f = shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
        mesh=mesh,
        in_specs=P("data", None, "seq", None),
        out_specs=P("data", None, "seq", None))
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None, mask=None):
    """[B, H, T_local, D] per device; returns the local output shard.

    Causal masking uses global positions: device i holds sequence chunk i
    (contiguous layout).  Per ring step the KV chunk's source device index
    is tracked so query/key global offsets stay correct.  ``mask``:
    optional [B, T_local] 1/0 keep-mask over the local KV chunk — it
    rotates around the ring with its K/V chunk, giving padded long-
    context batches the same semantics as `fused_attention`'s mask.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, H, T, D = q.shape
    qs = q * scale

    def chunk_scores(kc, mc, src):
        # f32 scores/stats regardless of input dtype — same accumulation
        # invariant as ops/attention_kernels.py (bf16 normalizer drift
        # grows with ring length, exactly where this path is used)
        s = jnp.einsum("bhqd,bhkd->bhqk", qs, kc,
                       preferred_element_type=jnp.float32)
        if mc is not None:
            s = jnp.where(mc[:, None, None, :] > 0, s, NEG_INF)
        if causal:
            qpos = my * T + jnp.arange(T)[:, None]
            kpos = src * T + jnp.arange(kc.shape[2])[None, :]
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        return s

    def accumulate(acc, m, l, kc, vc, mc, src):
        s = chunk_scores(kc, mc, src)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1)
        acc_new = corr[..., None] * acc + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    def step(i, carry):
        acc, m, l, kc, vc, mc = carry
        # rotate KV (+ its mask chunk) around the ring (ICI neighbour
        # exchange), then consume
        perm = [(j, (j + 1) % n) for j in range(n)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        if mc is not None:
            mc = jax.lax.ppermute(mc, axis_name, perm)
        acc, m, l = accumulate(acc, m, l, kc, vc, mc, (my - i) % n)
        return acc, m, l, kc, vc, mc

    # derive from q so the carries inherit shard_map's varying-axis type,
    # then promote to f32 accumulation
    acc = jnp.zeros_like(q, dtype=jnp.float32)
    m = jnp.full_like(q[..., 0], NEG_INF, dtype=jnp.float32)
    l = jnp.zeros_like(q[..., 0], dtype=jnp.float32)
    # step 0: local chunk, no communication; n-1 rotations total
    acc, m, l = accumulate(acc, m, l, k, v, mask, my)
    if mask is None:
        def step_unmasked(i, carry):
            acc_, m_, l_, kc, vc, _ = step(i, carry + (None,))
            return acc_, m_, l_, kc, vc

        acc, m, l, _, _ = jax.lax.fori_loop(
            1, n, step_unmasked, (acc, m, l, k, v))
    else:
        acc, m, l, _, _, _ = jax.lax.fori_loop(
            1, n, step, (acc, m, l, k, v, mask))
    return (acc / l[..., None]).astype(q.dtype)


def ring_attention_flash(q, k, v, axis_name: str, causal: bool = False,
                         scale=None, block_q: Optional[int] = None,
                         block_k: Optional[int] = None,
                         interpret: bool = False):
    """Ring attention whose INNER chunk-vs-chunk attention runs the
    Pallas flash kernel (`ops.attention_kernels.flash_attention_tpu`
    with ``return_lse``), merging per-chunk results by logsumexp:

        lse' = logaddexp(lse, lse_i)
        out' = exp(lse - lse')*out + exp(lse_i - lse')*out_i

    Causal needs NO per-step kernel variants with the contiguous chunk
    layout: at ring step i the incoming chunk (source device
    ``src = (my - i) mod n``) lies entirely BELOW the diagonal when
    ``src < my`` (keep everything) or entirely ABOVE it (``src > my``:
    suppress by forcing that chunk's lse to -inf so the merge no-ops);
    only step 0 — the diagonal chunk, whose global q/k offsets are equal
    — runs the causal kernel.  So every step launches the same plain
    kernel and the diagonal step launches the causal one once.

    Differentiable via custom_vjp: the backward delegates to the einsum
    ring's autodiff (mathematically the same function, so the gradients
    are exact); a ring over the one-kernel flash backward
    (``flash_attention_bwd_tpu``, which already yields dQ, dK and dV of a
    chunk pair in one pass) is a future multi-chip-measured step.
    Single-chip A/B is vacuous (axis size 1 = plain flash), so adoption
    into dispatch waits for multi-chip hardware; correctness is CPU-tested
    via interpret mode.

    ``block_q``/``block_k`` default to the kernel tier's installed
    attention :class:`TileConfig` (autotuned winners apply here too),
    clamped to divisors of the local chunk length via ``_pick_block``.
    """
    if block_q is None or block_k is None:
        from deeplearning4j_tpu.ops import pallas as _tier
        import deeplearning4j_tpu.ops.attention_kernels as _ak
        T = q.shape[2]
        tile = _tier.dispatch.get_tile("attention")
        if block_q is None:
            block_q = _ak._pick_block(T, min(tile.block_q, T)) or T
        if block_k is None:
            block_k = _ak._pick_block(T, min(tile.block_kv, T)) or T
    return _ring_flash(q, k, v, axis_name, causal, scale, block_q,
                       block_k, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(q, k, v, axis_name, causal, scale, block_q, block_k,
                interpret):
    from deeplearning4j_tpu.ops.attention_kernels import (
        flash_attention_tpu)

    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, H, T, D = q.shape

    def inner(kc, vc, diag):
        out, lse = flash_attention_tpu(
            q, kc, vc, causal=bool(causal and diag), scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
            return_lse=True)
        return out.astype(jnp.float32), lse.reshape(B, H, T)

    def merge(out, lse, out_i, lse_i):
        lse_new = jnp.logaddexp(lse, lse_i)
        w_old = jnp.exp(lse - lse_new)[..., None]
        w_new = jnp.exp(lse_i - lse_new)[..., None]
        return w_old * out + w_new * out_i, lse_new

    def step(i, carry):
        out, lse, kc, vc = carry
        perm = [(j, (j + 1) % n) for j in range(n)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        out_i, lse_i = inner(kc, vc, diag=False)
        if causal:
            src = (my - i) % n
            lse_i = jnp.where(src < my, lse_i, NEG_INF)
        out, lse = merge(out, lse, out_i, lse_i)
        return out, lse, kc, vc

    out, lse = inner(k, v, diag=True)
    out, lse, _, _ = jax.lax.fori_loop(1, n, step, (out, lse, k, v))
    return out.astype(q.dtype)


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, block_q, block_k,
                    interpret):
    out = _ring_flash(q, k, v, axis_name, causal, scale, block_q,
                      block_k, interpret)
    return out, (q, k, v)


def _ring_flash_bwd(axis_name, causal, scale, block_q, block_k,
                    interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ring_attention(q_, k_, v_,
                                          axis_name=axis_name,
                                          causal=causal, scale=scale),
        q, k, v)
    return vjp(g)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)
