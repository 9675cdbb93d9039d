"""The attention kernels of the train path, compiled by Mosaic for a
DESCRIBED TPU v5e (no chip attached, nothing runs): what interpret mode
cannot see — a block the tiling rule refuses, more VMEM than the call
states, an index map Mosaic cannot lower — and, for the latent-attention
layer around them, which arrays XLA copies between a product and a kernel.
A compile that passes is not a chip run and gives no time.

The topology is described inside a fixture and only in this file: the
process that describes it loads libtpu and keeps it (see the
`on-chip-measurement` guide, section 2)."""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.ops import attention_kernels as ak
from deeplearning4j_tpu.ops import pallas as tier
from deeplearning4j_tpu.zoo import DecoderConfig, DecoderModel


@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as env:
        for name, value in (("TPU_LOG_DIR", "disabled"),
                            ("TPU_ACCELERATOR_TYPE", "v5litepod-1"),
                            ("TPU_WORKER_HOSTNAMES", "localhost"),
                            ("TPU_SKIP_MDS_QUERY", "1")):
            if name not in os.environ:
                env.setenv(name, value)
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:1x1",
                chips_per_host_bounds=(1, 1, 1))
        except Exception as e:
            pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    """Lower for the TPU and compile; x64 off, as on the chip.  A shape is
    `(dims, dtype)` or a tree of them."""
    def spec(s):
        return jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)

    with jax.enable_x64(False):
        specs = [jax.tree_util.tree_map(
            spec, s, is_leaf=lambda s: isinstance(s, tuple)) for s in shapes]
        return jax.jit(fn).trace(*specs).lower(
            lowering_platforms=("tpu",)).compile()


# B, H, T, S, D, Dv, dtype, blocks, causal, key mask
_SHAPES = {
    # kanana's cell: keys 192, values 128, 2 x 32 heads of 4,096 tokens
    "kanana": (2, 32, 4096, 4096, 192, 128, jnp.bfloat16, (512, 1024),
               True, False),
    # a ragged masked batch as the tier pads it, float32
    "masked_f32": (2, 4, 2560, 2048, 128, 128, jnp.float32, (512, 1024),
                   True, True),
    # few queries over many keys: the queries (lanes of the backward's
    # tile) are a block of 48
    "short_q": (2, 4, 48, 2048, 64, 64, jnp.bfloat16, (48, 1024), False,
                True),
}


@pytest.mark.parametrize("case", list(_SHAPES))
def test_flash_forward_compiles_for_v5e(case, one_chip):
    B, H, T, S, D, Dv, dt, (bq, bk), causal, masked = _SHAPES[case]
    shapes = [((B, H, T, D), dt), ((B, H, S, D), dt), ((B, H, S, Dv), dt)]
    if masked:
        shapes.append(((B, S), dt))
    compiled = _compile(
        lambda q, k, v, mask=None: ak.flash_attention_tpu(
            q, k, v, causal=causal, block_q=bq, block_k=bk, return_lse=True,
            mask=mask),
        one_chip, *shapes)
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("case", list(_SHAPES))
def test_flash_backward_is_one_kernel_and_compiles_for_v5e(case, one_chip):
    B, H, T, S, D, Dv, dt, (bq, bk), causal, masked = _SHAPES[case]
    shapes = [((B, H, T, D), dt), ((B, H, S, D), dt), ((B, H, S, Dv), dt),
              ((B, H, T, Dv), dt), ((B * H, T), jnp.float32),
              ((B, H, T, Dv), dt)]
    if masked:
        shapes.append(((B, S), dt))
    compiled = _compile(
        lambda q, k, v, out, lse, g, mask=None: ak.flash_attention_bwd_tpu(
            q, k, v, out, lse, g, causal=causal, block_q=bq, block_k=bk,
            mask=mask),
        one_chip, *shapes)
    assert compiled.as_text().count("tpu_custom_call") == 1


def test_backward_in_spans_compiles_for_v5e(one_chip, monkeypatch):
    """Queries cut into spans (budget forced to 1,024 rows of 4,096)."""
    monkeypatch.setattr(ak, "_BWD_DQ_VMEM", 1024 * 192 * 8)
    B, H, T, D, Dv, dt = 1, 4, 4096, 192, 128, jnp.bfloat16
    compiled = _compile(
        lambda q, k, v, out, lse, g: ak.flash_attention_bwd_tpu(
            q, k, v, out, lse, g, causal=True, block_q=512, block_k=1024),
        one_chip, ((B, H, T, D), dt), ((B, H, T, D), dt), ((B, H, T, Dv), dt),
        ((B, H, T, Dv), dt), ((B * H, T), jnp.float32), ((B, H, T, Dv), dt))
    assert compiled.as_text().count("tpu_custom_call") == 4


def test_grouped_query_kernels_compile_for_v5e(one_chip):
    """LFM2-24B-A2B's attention layer: 32 query heads over 8 key-value heads
    of 64, one sequence of 8,192 tokens, the tier's 512 x 1024 tile.  Keys
    and values enter both kernels as 8 heads; the backward is one kernel
    over key-value heads that holds dQ of a whole group (32,768 rows of 64,
    all of its VMEM budget) and sums dK and dV over the group itself."""
    B, H, Hk, T, D, dt = 1, 32, 8, 8192, 64, jnp.bfloat16
    q, kv = ((B, H, T, D), dt), ((B, Hk, T, D), dt)
    fwd = _compile(
        lambda q, k, v: ak.flash_attention_tpu(
            q, k, v, causal=True, block_q=512, block_k=1024, return_lse=True),
        one_chip, q, kv, kv)
    assert fwd.as_text().count("tpu_custom_call") == 1
    bwd = _compile(
        lambda q, k, v, out, lse, g: ak.flash_attention_bwd_tpu(
            q, k, v, out, lse, g, causal=True, block_q=512, block_k=1024),
        one_chip, q, kv, kv, q, ((B * H, T), jnp.float32), q)
    text = bwd.as_text()
    assert text.count("tpu_custom_call") == 1
    for compiled in (fwd, bwd):         # keys and values as 8 heads
        assert f"bf16[{B * Hk},{T},{D}]" in compiled.as_text()
    # dQ over the query's heads, dK and dV over the key-value heads
    assert [o.shape for o in bwd.out_info] == [q[0], kv[0], kv[0]]


def test_block_diffusion_kernels_compile_for_v5e(one_chip):
    """SDAR-30B-A3B's attention layer under the block-diffusion mask: 32
    query heads over 4 key-value heads of 128, the noisy and the clean copy
    of one 4,096-token sequence (8,192 rows), blocks of 4 tokens, the tier's
    512 x 1024 tile.  The mask comes from iota inside the kernels (the only
    operands are q, k and v); dQ of a group of 8 heads of 128 fits the
    backward's VMEM 2,048 rows at a time, so it goes in four spans."""
    B, H, Hk, L, D, dt = 1, 32, 4, 4096, 128, jnp.bfloat16
    T, mask = 2 * L, (L, 4)
    q, kv = ((B, H, T, D), dt), ((B, Hk, T, D), dt)
    fwd = _compile(
        lambda q, k, v: ak.flash_attention_tpu(
            q, k, v, block_q=512, block_k=1024, return_lse=True,
            block_diffusion=mask),
        one_chip, q, kv, kv)
    assert fwd.as_text().count("tpu_custom_call") == 1
    bwd = _compile(
        lambda q, k, v, out, lse, g: ak.flash_attention_bwd_tpu(
            q, k, v, out, lse, g, block_q=512, block_k=1024,
            block_diffusion=mask),
        one_chip, q, kv, kv, q, ((B * H, T), jnp.float32), q)
    assert bwd.as_text().count("tpu_custom_call") == 4
    assert [o.shape for o in bwd.out_info] == [q[0], kv[0], kv[0]]
    # no [T, T] array in either program
    for compiled in (fwd, bwd):
        assert f"{T},{T}]" not in compiled.as_text()


def test_selection_kernels_compile_for_v5e(one_chip):
    """Keye-VL-2.0's attention layer at one 16,384-token sequence: 32 query
    heads over 4 key-value heads of 128 under a SELECTION, one bit a pair,
    `by_query` [1, 512, 16384] for the forward's tiles and `by_key` for the
    backward's, unpacked along the sublanes of a 512 x 1024 tile; causal's
    schedule, so the backward goes in eight spans of 2,048 queries."""
    B, H, Hk, T, D, dt = 1, 32, 4, 16384, 128, jnp.bfloat16
    q, kv = ((B, H, T, D), dt), ((B, Hk, T, D), dt)
    bits = ((B, T // 32, T), jnp.int32)
    fwd = _compile(
        lambda q, k, v, a, b: ak.flash_attention_tpu(
            q, k, v, causal=True, block_q=512, block_k=1024, return_lse=True,
            selection=ak.Selection(a, b)),
        one_chip, q, kv, kv, bits, bits)
    assert fwd.as_text().count("tpu_custom_call") == 1
    bwd = _compile(
        lambda q, k, v, out, lse, g, a, b: ak.flash_attention_bwd_tpu(
            q, k, v, out, lse, g, causal=True, block_q=512, block_k=1024,
            selection=ak.Selection(a, b)),
        one_chip, q, kv, kv, q, ((B * H, T), jnp.float32), q, bits, bits)
    assert bwd.as_text().count("tpu_custom_call") == 8
    assert [o.shape for o in bwd.out_info] == [q[0], kv[0], kv[0]]
    # the selection stays packed: no [T, T] array in either program
    for compiled in (fwd, bwd):
        assert f"{T},{T}]" not in compiled.as_text()


def test_index_kernels_compile_for_v5e(one_chip):
    """The indexer's five kernels on a chunk of 1,024 queries over 16,384
    keys: 16 index heads of 64 over one key head summed in a 512 x 1024
    tile, alone and with each row's logsumexp over the chunk's words of the
    selection, their gradients, the 32 main heads' probabilities summed
    over the grid's innermost axis into the loss's KL and cotangent, the
    chunk's first position a scalar operand; and, once a sequence, the
    selection's words of 32 queries turned into words of 32 keys, [1, 512,
    16384] both."""
    from deeplearning4j_tpu.ops.pallas import sparse_index as kernels
    B, n, C, d, S, dt = 1, 16, 1024, 64, 16384, jnp.bfloat16
    q_idx, k_idx, w = ((B, n, C, d), dt), ((B, S, d), dt), ((B, C, n),
                                                            jnp.float32)
    offset, dense = ((), jnp.int32), ((B, C, S), jnp.float32)
    words, rows = ((B, C // 32, S), jnp.int32), ((B, C), jnp.float32)
    scores = _compile(kernels.index_scores, one_chip, q_idx, k_idx, w, offset)
    assert scores.out_info.shape == dense[0]
    lse = _compile(kernels.index_scores_lse, one_chip, q_idx, k_idx, w,
                   words, offset)
    assert [o.shape for o in lse.out_info] == [dense[0], rows[0]]
    bwd = _compile(kernels.index_scores_bwd, one_chip, dense, q_idx, k_idx, w,
                   offset)
    assert [(o.shape, o.dtype) for o in bwd.out_info] == [
        (q_idx[0], dt), (k_idx[0], jnp.float32), (w[0], jnp.float32)]
    probs = _compile(
        lambda q, k, lse, *rest: kernels.kl_and_cotangent(
            q, k, lse, 128 ** -0.5, *rest[:3], S, rest[3]),
        one_chip, ((B, 32, C, 128), dt), ((B, 4, S, 128), dt),
        ((B, 32, C), jnp.float32), dense, words, rows, offset)
    assert [o.shape for o in probs.out_info] == [dense[0], rows[0]]
    pack = _compile(kernels.pack_by_key, one_chip,
                    ((B, S // 32, S), jnp.int32))
    assert (pack.out_info.shape, pack.out_info.dtype) == ((B, S // 32, S),
                                                          jnp.int32)
    for compiled in (scores, lse, bwd, probs, pack):
        assert compiled.as_text().count("tpu_custom_call") == 1


def test_delta_rule_kernels_compile_for_v5e(one_chip):
    """Solar Open 2's recurrence across chunks at one 4,096-token sequence:
    8 heads of 128 a grid step, 64 chunks of 64 in sequence, the state
    [128, 128] float32 in VMEM; forward (outputs, every chunk's entering
    state, the last) and the reverse walk (six gradients and the first
    state's), one Mosaic call each."""
    from deeplearning4j_tpu.ops.pallas import delta_rule as kernels
    BH, T, C, d, f32 = 8, 4096, 64, 128, jnp.float32
    rows, gc = ((BH, T, d), f32), ((BH, T // C, 1, d), f32)
    aqk, state = ((BH, T, C), f32), ((BH, d, d), f32)
    fwd = _compile(kernels.across_chunks, one_chip, rows, rows, rows, rows,
                   gc, aqk, state)
    assert [o.shape for o in fwd.out_info] == [
        (BH, T, d), (BH, T // C, d, d), (BH, d, d)]
    bwd = _compile(kernels.across_chunks_bwd, one_chip, rows, rows, rows,
                   rows, rows, gc, aqk, ((BH, T // C, d, d), f32), state)
    assert [o.shape for o in bwd.out_info] == [
        (BH, T, d), (BH, T, d), (BH, T, d), (BH, T, d), gc[0], aqk[0],
        (BH, d, d)]
    for compiled in (fwd, bwd):
        assert compiled.as_text().count("tpu_custom_call") == 1


def test_delta_rule_inside_kernels_compile_for_v5e(one_chip):
    """Solar Open 2's chunks' insides at one 4,096-token sequence: 8 heads
    of 128 a grid step, 64 chunks of 64 (sub-blocks of 16), forward (w, u,
    qg, kd, gc, aqk) and backward (dq, dk, dv, dg, dbeta), one Mosaic call
    each."""
    from deeplearning4j_tpu.ops.pallas import delta_rule as kernels
    BH, N, C, d, f32 = 8, 64, 64, 128, jnp.float32
    rows, beta = ((BH, N, C, d), f32), ((BH, N, C), f32)
    fwd = _compile(functools.partial(kernels.within_chunks, scale=d ** -0.5),
                   one_chip, rows, rows, rows, rows, beta)
    assert [o.shape for o in fwd.out_info] == [
        rows[0]] * 4 + [(BH, N, d), (BH, N, C, C)]
    bwd = _compile(
        functools.partial(kernels.within_chunks_bwd, scale=d ** -0.5),
        one_chip, rows, rows, rows, rows, beta, rows, rows, rows, rows,
        ((BH, N, d), f32), ((BH, N, C, C), f32))
    assert [o.shape for o in bwd.out_info] == [rows[0]] * 4 + [beta[0]]
    for compiled in (fwd, bwd):
        assert compiled.as_text().count("tpu_custom_call") == 1


def test_kimi_delta_attention_layer_compiles_for_v5e(one_chip,
                                                     forced_kernels):
    """Solar Open 2's KDA layer, `[1, 4096, 4096]` in, 8 held heads of 128,
    bf16 products over a float32 stream, forward and gradient: the chunks'
    insides and the recurrence a kernel each forward, and in the gradient
    those two and their two backward kernels; no triangular solve and no
    sub-block decay tile ([.., 16, 16, 128]) outside the kernels."""
    from benchmark.models import solar_open2
    from benchmark import harness
    c = solar_open2.decoder_config(harness.load_config(
        harness.load_manifest(), "solar_open2_250b"))
    model = object.__new__(DecoderModel)
    model.config = c
    n, d, H, dt = 8, 128, c.hidden, jnp.bfloat16
    lp = {"norm1": ((H,), dt), "Wqkv": ((H, 3 * n * d), dt),
          "conv_qkv": ((4, 3 * n * d), dt), "Wf_a": ((H, d), dt),
          "Wf_b": ((d, n * d), dt), "A_log": ((n,), jnp.float32),
          "dt_bias": ((n * d,), jnp.float32), "Wbeta": ((H, n), dt),
          "Wg_a": ((H, d), dt), "Wg_b": ((d, n * d), dt),
          "o_norm": ((d,), dt), "Wo": ((n * d, H), dt)}
    x = ((1, 4096, H), jnp.float32)
    layer = lambda x, lp: model._linear_attention(x, lp)[0]  # noqa: E731
    fwd = _compile(layer, one_chip, x, lp)
    grad = _compile(jax.grad(lambda x, lp, ct: jnp.sum(layer(x, lp) * ct),
                             (0, 1)), one_chip, x, lp, x)
    assert fwd.as_text().count("tpu_custom_call") == 2
    text = grad.as_text()
    assert text.count("tpu_custom_call") == 4
    assert "triangular-solve" not in text
    assert re.search(r"16,16,128\]", text) is None


# a `transpose` or `copy` whose result is a whole q, k, v or kernel output of
# kanana's cell, in either order of tokens and heads
_HEADS_COPY = re.compile(
    r"= \w+\[(?:2,32,4096|2,4096,32),\d+\]\S* (?:transpose|copy)\(")
_MINOR_TWO = re.compile(r"\[(?:\d+,)+2\]")


@pytest.fixture
def forced_kernels(monkeypatch):
    """`fused_attention` takes the Mosaic kernels, compiled: the CPU
    backend the tests run on would pick the XLA branch, or interpret mode."""
    tier.dispatch.set_dispatch_mode("pallas")
    monkeypatch.setattr(tier.dispatch, "interpret_mode", lambda: False)
    yield
    tier.dispatch.reset()


# (forward, forward + backward): what the layer compiled to when this was
# written (PR 36); the parent's form read (5, 16) and held 22 and 64 arrays
# with a minor dimension of 2
_HEADS_COPIES = (2, 9)


def test_latent_attention_layer_compiles_for_v5e(one_chip, forced_kernels):
    """kanana's latent-attention layer, `[2, 4096, 2048]` in, 32 heads of
    128 + 64 / 128 over a latent of 512, bf16 over a float32 stream, under
    the decoder's checkpoint policy, forward and gradient.  Between a
    product and a kernel XLA copies q and k once each (a 192-wide result
    leaves its product with the tokens in the lanes) and v never; the
    backward adds dq, dk, dv and the saved output on their way into the
    weight gradients.  No array has a minor dimension of 2: the rotation
    works on whole lanes."""
    c = DecoderConfig(hidden=2048, n_heads=32, qk_nope_dim=128,
                      qk_rope_dim=64, v_head_dim=128, kv_lora_rank=512,
                      rope_base=1e6, compute_dtype="bfloat16")
    model = object.__new__(DecoderModel)    # the layer needs no parameters
    model.config, model._diffusion = c, False
    nh, qk = c.n_heads, c.qk_nope_dim + c.qk_rope_dim
    dt = jnp.bfloat16
    lp = {"norm1": ((c.hidden,), dt),
          "Wq": ((c.hidden, nh * qk), dt),
          "Wkva": ((c.hidden, c.kv_lora_rank + c.qk_rope_dim), dt),
          "kv_norm": ((c.kv_lora_rank,), dt),
          "Wkvb": ((c.kv_lora_rank, nh * (c.qk_nope_dim + c.v_head_dim)), dt),
          "Wo": ((nh * c.v_head_dim, c.hidden), dt)}
    x = ((2, 4096, c.hidden), jnp.float32)
    layer = jax.checkpoint(
        model._attention, policy=jax.checkpoint_policies.
        save_only_these_names(ak.FLASH_OUT, ak.FLASH_LSE))
    fwd = _compile(layer, one_chip, x, lp).as_text()
    grad = _compile(
        jax.grad(lambda x, lp, ct: jnp.sum(layer(x, lp) * ct), (0, 1)),
        one_chip, x, lp, x).as_text()
    assert fwd.count("tpu_custom_call") == 1
    assert grad.count("tpu_custom_call") == 2   # no second forward kernel
    for text, most in zip((fwd, grad), _HEADS_COPIES):
        assert not _MINOR_TWO.findall(text)
        assert len(_HEADS_COPY.findall(text)) <= most


def test_the_top_ks_bits_stay_in_vmem(one_chip, forced_kernels):
    """Keye's selection of one 16,384-token sequence, 16 chunks of 1,024
    queries under `lax.map`, as XLA places it.  The top-k makes 32 passes
    over a chunk's score bits `u` (64 MB): 37 ms a step while XLA keeps
    them in VMEM (`S(1)` in a layout), 230 from HBM (PERF.md, PR 38).  What
    sent them there: a Mosaic call that read them, and a `cond` out of whose
    branches XLA had moved the packing, so that a [chunk, S] boolean or
    int32 array went in or came out.  So: whatever fusion writes a [1,
    1024, 16384] array writes it to VMEM, none is copied, the `cond` takes
    `u` from VMEM and nothing else that size, and hands back words."""
    from deeplearning4j_tpu.ops import sparse_index
    B, n, T, d, C = 1, 16, 16384, 64, 1024
    text = _compile(
        lambda q, k, w: sparse_index.sparse_index(q, k, w, 2048), one_chip,
        ((B, n, T, d), jnp.bfloat16), ((B, T, d), jnp.bfloat16),
        ((B, T, n), jnp.float32)).as_text()
    assert text.count("tpu_custom_call") == 2     # the scores, `pack_by_key`
    dense = rf"\w+\[{B},(?:{C}|{C // 32},32),{T}\]"
    written = re.findall(rf"= ({dense})(\S*) (fusion|copy)\(", text)
    assert any(a.startswith("u32") for a, _, _ in written)          # `u`
    assert all("S(1)" in layout and op == "fusion"
               for _, layout, op in written), written
    cond, = re.findall(
        r"= (\S+) conditional\(.*branch_computations=\{(%[\w.]+), (%[\w.]+)\}",
        text)
    assert not re.search(dense, cond[0]) and f"s32[{B},{C // 32},{T}]" in cond[0]
    for branch in cond[1:]:
        taken, = re.findall(
            rf"^{re.escape(branch)} \((.*)\) -> ", text, re.M)
        assert re.findall(dense, taken) == [f"u32[{B},{C},{T}]"], taken
        layout, = re.findall(
            rf"^{re.escape(branch)} .*?\n(?:.*\n)*?.*= \((u32\[{B},{C},{T}\]"
            r"\S*), .* parameter\(0\)", text, re.M)
        assert "S(1)" in layout, layout


def test_the_indexers_loss_leaves_no_dense_array_to_xla(one_chip,
                                                        forced_kernels):
    """Keye's `index_loss` and its gradients on one 16,384-token sequence,
    16 chunks of 1,024 queries under `lax.scan`: three Mosaic calls a
    chunk — the scores with each row's logsumexp over the selection, the
    main heads' probabilities ending in the KL and the scores' cotangent,
    the cotangent's three gradients — and nothing of XLA's reads or writes
    a [1, 1024, 16384] array (float32, int32 or boolean; the words
    unpacked are [1, 32, 32, 16384]): the two dense arrays pass from one
    kernel to the next, and XLA only takes them out of the calls' tuples."""
    from deeplearning4j_tpu.ops import sparse_index
    B, n, T, d, H, Hk, D, C = 1, 16, 16384, 64, 32, 4, 128, 1024
    dt = jnp.bfloat16
    text = _compile(
        jax.value_and_grad(lambda q_idx, k_idx, w, *rest: (
            sparse_index.index_loss(q_idx, k_idx, w, *rest, D ** -0.5)),
            (0, 1, 2)),
        one_chip, ((B, n, T, d), dt), ((B, T, d), dt),
        ((B, T, n), jnp.float32), ((B, T // 32, T), jnp.int32),
        ((B, H, T, D), dt), ((B, Hk, T, D), dt),
        ((B, H, T), jnp.float32)).as_text()
    assert text.count("tpu_custom_call") == 3
    # each claims at most 32 MB of VMEM: in the train step a larger claim
    # made XLA move the selection's words out of VMEM around every chunk
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert all(int(size) <= 32 << 20 for line in calls for size in
               re.findall(r'"scoped_memory_configs":\[\{[^]]*"size":"(\d+)"',
                          line))
    dense = re.compile(rf"\b(?:f32|s32|u32|pred)\[{B},(?:{C}|32,32),{T}\]")
    touched = [line for line in text.splitlines() if dense.search(line)]
    assert touched
    for line in touched:
        assert ("tpu_custom_call" in line
                or " get-tuple-element(%closed_call" in line), line
