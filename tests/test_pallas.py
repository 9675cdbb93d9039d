"""Fused-kernel tier (ops/pallas): conformance, dispatch, tile autotuning.

The tier's contract is two implementations per kernel — Pallas (TileConfig-
parameterized) and a pure-jnp reference that is the definition of
correctness — behind one dispatch layer.  These tests pin:

- conformance: `pallas(interpret=True) == reference` across dtypes
  (f32/bf16/int8), causal/masked attention variants, and ragged
  non-multiple-of-tile shapes (masked tails / zero padding).  The int8
  contraction + scale epilogue is pinned *bitwise* (integer accumulation
  is exact and the f32 dequant epilogue is shared code); bias-fused
  variants allow 1-ulp-scale drift because XLA may contract the
  `y*scale + b` epilogue into an FMA inside the kernel.
- dispatch: CPU always gets the reference in auto mode; forced `pallas`
  mode runs interpret-mode kernels on CPU; a kernel that raises
  propagates to the caller; a bad `DL4J_TPU_KERNEL_TIER` raises;
  decisions are counted in `ops_kernel_dispatch_total`.
- tiles: TileAutotuner grid+greedy search, memoization, persistence via
  the per-device tile table, zero re-search on replay (cache-hit metric),
  and `kernel_tier_fingerprint` splitting AOT keys on mode/tile changes.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.compile.autotune import (TileAutotuner,
                                                 autotune_tiles,
                                                 load_tile_table,
                                                 save_tile_entry,
                                                 tile_table_path)
from deeplearning4j_tpu.compile.fingerprint import (kernel_tier_fingerprint,
                                                    model_fingerprint)
from deeplearning4j_tpu.monitor.instrument import ops_instruments
from deeplearning4j_tpu.ops import pallas as tier
from deeplearning4j_tpu.ops.pallas import attention as pa
from deeplearning4j_tpu.ops.pallas import matmul as pm
from deeplearning4j_tpu.ops.pallas.tiles import TileConfig, shape_class
from deeplearning4j_tpu.ops.quant_kernels import (dequant_epilogue,
                                                  quantize_tensor,
                                                  quantized_dense,
                                                  quantized_matmul,
                                                  quantized_matmul_static)

dispatch = tier.dispatch


@pytest.fixture(autouse=True)
def _reset_dispatch():
    yield
    dispatch.reset()


def _rng(seed=0):
    return np.random.RandomState(seed)


def _qkv(rng, B, H, T, S, D, dtype=np.float32):
    return (jnp.asarray(rng.randn(B, H, T, D).astype(dtype) * 0.3),
            jnp.asarray(rng.randn(B, H, S, D).astype(dtype) * 0.3),
            jnp.asarray(rng.randn(B, H, S, D).astype(dtype) * 0.3))


SMALL_ATT = TileConfig(block_q=32, block_kv=64)
SMALL_MM = TileConfig(block_m=8, block_n=128, block_k=128)


# ---------------------------------------------------------------------------
# conformance: attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal,masked", [(False, False), (True, False),
                                           (False, True), (True, True)])
def test_attention_conformance_variants(causal, masked):
    rng = _rng(1)
    q, k, v = _qkv(rng, 2, 2, 128, 128, 64)
    mask = (jnp.asarray((rng.rand(2, 128) > 0.2).astype(np.float32))
            if masked else None)
    out = pa.flash_attention(q, k, v, mask=mask, causal=causal,
                             tile=SMALL_ATT, interpret=True)
    ref = pa.attention_reference(q, k, v, mask=mask, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


def test_attention_conformance_ragged_masked_tail():
    """T=100/S=72 hit no block multiple: the wrapper zero-pads and knocks
    the padded KV out through the additive mask, then slices Q rows."""
    rng = _rng(2)
    for causal in (False, True):
        q, k, v = _qkv(rng, 2, 2, 100, 72, 64)
        keep = (rng.rand(2, 72) > 0.3).astype(np.float32)
        keep[:, 0] = 1.0   # no fully-masked rows: those are undefined
        mask = jnp.asarray(keep)
        out = pa.flash_attention(q, k, v, mask=mask, causal=causal,
                                 tile=SMALL_ATT, interpret=True)
        ref = pa.attention_reference(q, k, v, mask=mask, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-6, atol=2e-6)


def test_attention_conformance_bf16():
    rng = _rng(3)
    q, k, v = _qkv(rng, 1, 2, 128, 128, 64)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    out = pa.flash_attention(q, k, v, tile=SMALL_ATT, interpret=True)
    ref = pa.attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_attention_grad_through_ragged_pallas():
    rng = _rng(4)
    q, k, v = _qkv(rng, 1, 1, 100, 72, 64)

    def f(fn):
        return jax.grad(lambda q_: fn(q_).sum())(q)

    g_pal = f(lambda q_: pa.flash_attention(q_, k, v, tile=SMALL_ATT,
                                            interpret=True))
    g_ref = f(lambda q_: pa.attention_reference(q_, k, v))
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# conformance: matmul family
# ---------------------------------------------------------------------------

def _int8_case(rng, M=37, K=70, N=45):
    xq = jnp.asarray(rng.randint(-127, 128, (M, K)).astype(np.int8))
    wq = jnp.asarray(rng.randint(-127, 128, (K, N)).astype(np.int8))
    ws = jnp.asarray(rng.rand(N).astype(np.float32) * 0.1)
    return xq, wq, ws


def test_int8_matmul_bitwise_ragged():
    """The headline tier guarantee: int8×int8→int32 stays exact under any
    tiling and the shared f32 dequant epilogue makes the scale application
    bit-identical to the reference — even on ragged M/K/N."""
    rng = _rng(5)
    for (M, K, N) in [(37, 70, 45), (8, 128, 128), (130, 257, 129)]:
        xq, wq, ws = _int8_case(rng, M, K, N)
        got = pm.int8_matmul(xq, wq, ws, x_scale=jnp.float32(0.02),
                             tile=SMALL_MM, interpret=True)
        want = pm.int8_matmul_reference(xq, wq, ws,
                                        x_scale=jnp.float32(0.02))
        assert got.dtype == want.dtype
        assert bool(jnp.all(got == want)), (M, K, N)


def test_int8_matmul_bias_epilogue():
    rng = _rng(6)
    xq, wq, ws = _int8_case(rng)
    bias = jnp.asarray(rng.randn(45).astype(np.float32))
    got = pm.int8_matmul(xq, wq, ws, x_scale=jnp.float32(0.02), bias=bias,
                         tile=SMALL_MM, interpret=True)
    want = pm.int8_matmul_reference(xq, wq, ws, x_scale=jnp.float32(0.02),
                                    bias=bias)
    # fused bias add may FMA-contract inside the kernel: 1-ulp tolerance
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-4)


def test_q_matmul_weight_only_conformance():
    rng = _rng(7)
    _, wq, ws = _int8_case(rng, K=70, N=45)
    for dt, tol in ((np.float32, 1e-4), (jnp.bfloat16, 5e-2)):
        x = jnp.asarray(rng.randn(33, 70).astype(np.float32)).astype(dt)
        got = pm.q_matmul(x, wq, ws, tile=SMALL_MM, interpret=True)
        want = pm.q_matmul_reference(x, wq, ws)
        assert got.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("act", ["identity", "relu", "tanh", "sigmoid",
                                 "gelu"])
def test_fused_dense_activation_epilogues(act):
    rng = _rng(8)
    x = jnp.asarray(rng.randn(33, 70).astype(np.float32))
    w = jnp.asarray(rng.randn(70, 45).astype(np.float32) * 0.1)
    b = jnp.asarray(rng.randn(45).astype(np.float32))
    got = pm.fused_dense(x, w, bias=b, activation=act, tile=SMALL_MM,
                         interpret=True)
    want = pm.fused_dense_reference(x, w, bias=b, activation=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fused_dense_grads_match_reference():
    rng = _rng(9)
    x = jnp.asarray(rng.randn(16, 24).astype(np.float32))
    w = jnp.asarray(rng.randn(24, 40).astype(np.float32) * 0.1)
    b = jnp.asarray(rng.randn(40).astype(np.float32))

    def loss(fn, *args):
        return jax.grad(lambda t: fn(*t).sum())(args)

    g_pal = loss(lambda x_, w_, b_: pm.fused_dense(
        x_, w_, b_, activation="tanh", tile=SMALL_MM, interpret=True),
        x, w, b)
    g_ref = loss(lambda x_, w_, b_: pm.fused_dense_reference(
        x_, w_, b_, activation="tanh"), x, w, b)
    for gp, gr in zip(g_pal, g_ref):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gr),
                                   rtol=1e-5, atol=1e-6)


def test_quantized_matmul_static_bitwise_across_modes():
    """The quant satellite: `quantized_matmul_static` keeps the int32
    contraction end-to-end and shares `dequant_epilogue`, so forcing the
    tier to Pallas changes nothing — bit-for-bit."""
    rng = _rng(10)
    x = jnp.asarray(rng.randn(16, 32).astype(np.float32))
    qt = quantize_tensor(rng.randn(32, 24).astype(np.float32))
    ref = quantized_matmul_static(x, qt, 0.05)
    dispatch.set_dispatch_mode("pallas")
    pal = quantized_matmul_static(x, qt, 0.05)
    assert bool(jnp.all(ref == pal))


def test_dequant_epilogue_shared_math():
    rng = _rng(11)
    y = jnp.asarray(rng.randint(-1000, 1000, (7, 5)).astype(np.int32))
    scale = jnp.asarray(rng.rand(1, 5).astype(np.float32))
    out = dequant_epilogue(y, scale, out_dtype=jnp.float32)
    want = (np.asarray(y).astype(np.float32)
            * np.asarray(scale).astype(np.float32))
    assert out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out), want)


def test_quantized_paths_forced_pallas_match_reference():
    rng = _rng(12)
    x = jnp.asarray(rng.randn(16, 32).astype(np.float32))
    qt = quantize_tensor(rng.randn(32, 24).astype(np.float32))
    b = jnp.asarray(rng.randn(24).astype(np.float32))
    ref_m = quantized_matmul(x, qt)
    ref_d = quantized_dense(x, qt, b)
    dispatch.set_dispatch_mode("pallas")
    np.testing.assert_allclose(np.asarray(quantized_matmul(x, qt)),
                               np.asarray(ref_m), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(quantized_dense(x, qt, b)),
                               np.asarray(ref_d), rtol=1e-5, atol=1e-5)


def test_quantized_mha_forced_pallas_matches_reference():
    """quantized_mha's projections + attention all route through the tier
    under forced mode (docs/quantization.md cross-link)."""
    rng = _rng(13)
    B, T, F, H = 2, 16, 32, 2
    x = jnp.asarray(rng.randn(B, T, F).astype(np.float32) * 0.3)
    w_qkv = quantize_tensor(rng.randn(F, 3 * 128).astype(np.float32) * 0.1)
    w_out = quantize_tensor(rng.randn(128, F).astype(np.float32) * 0.1)
    from deeplearning4j_tpu.ops.attention_kernels import quantized_mha
    ref = quantized_mha(x, w_qkv, w_out, n_heads=H)
    dispatch.set_dispatch_mode("pallas")
    pal = quantized_mha(x, w_qkv, w_out, n_heads=H)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------

def test_dispatch_cpu_auto_always_reference():
    rng = _rng(14)
    q = jnp.asarray(rng.randn(1, 1, 4096, 64).astype(np.float32))
    xq, wq, ws = _int8_case(rng, 512, 512, 512)
    x = jnp.asarray(rng.randn(512, 512).astype(np.float32))
    assert dispatch.dispatch_mode() == "auto"
    assert dispatch.resolve("attention", q, q, q) == "reference"
    assert dispatch.resolve("int8_matmul", xq, wq, ws,
                            jnp.float32(0.1)) == "reference"
    assert dispatch.resolve("q_matmul", x, wq, ws) == "reference"
    assert dispatch.resolve("fused_dense", x, x) == "reference"


def test_dispatch_forced_reference_mode():
    rng = _rng(15)
    xq, wq, ws = _int8_case(rng)
    dispatch.set_dispatch_mode("reference")
    assert dispatch.resolve("int8_matmul", xq, wq, ws) == "reference"


def test_dispatch_forced_pallas_respects_hard_supports():
    rng = _rng(16)
    dispatch.set_dispatch_mode("pallas")
    xq, wq, ws = _int8_case(rng)
    assert dispatch.resolve("int8_matmul", xq, wq, ws) == "pallas"
    # f64 activations are a hard no for the kernels (x64 test config)
    x64 = jnp.asarray(_rng(0).randn(8, 70).astype(np.float64))
    assert dispatch.resolve("q_matmul", x64, wq, ws) == "reference"
    # 3D mask is a hard no for the flash kernel's [B, S] mask contract
    q = jnp.asarray(_rng(0).randn(1, 1, 64, 64).astype(np.float32))
    bad_mask = jnp.ones((1, 64, 64), jnp.float32)
    assert dispatch.resolve("attention", q, q, q,
                            mask=bad_mask) == "reference"


def test_kernel_failure_propagates_in_forced_mode(monkeypatch):
    """Once dispatch says `pallas`, a kernel that raises is not replaced by
    the reference: the error reaches the caller of `DenseLayer.apply` and
    `fused_attention`."""
    from deeplearning4j_tpu.nn.core import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer
    from deeplearning4j_tpu.ops.attention_kernels import fused_attention

    def boom(*a, **kw):
        raise RuntimeError("mosaic refused")

    rng = _rng(17)
    dispatch.set_dispatch_mode("pallas")
    monkeypatch.setattr(pm, "fused_dense", boom)
    layer = DenseLayer(n_out=128, activation="relu")
    params, state, _ = layer.initialize(jax.random.PRNGKey(0),
                                        InputType.feed_forward(128))
    x = jnp.asarray(rng.randn(8, 128).astype(np.float32))
    with pytest.raises(RuntimeError, match="mosaic refused"):
        layer.apply(params, state, x)
    monkeypatch.setattr(pa, "flash_attention", boom)
    q, k, v = _qkv(rng, 1, 1, 64, 64, 64)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        fused_attention(q, k, v)


def test_bad_kernel_tier_env_raises(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_KERNEL_TIER", "fastest")
    with pytest.raises(ValueError, match="DL4J_TPU_KERNEL_TIER"):
        dispatch.reset()
    monkeypatch.delenv("DL4J_TPU_KERNEL_TIER")


def test_dispatch_decisions_counted():
    rng = _rng(18)
    xq, wq, ws = _int8_case(rng)
    before = ops_instruments().dispatch("int8_matmul", "reference").value
    dispatch.resolve("int8_matmul", xq, wq, ws)
    after = ops_instruments().dispatch("int8_matmul", "reference").value
    assert after == before + 1


def test_fused_attention_routes_reference_on_cpu():
    rng = _rng(19)
    from deeplearning4j_tpu.ops.attention_kernels import (fused_attention,
                                                         mha_reference)
    q, k, v = _qkv(rng, 1, 1, 64, 64, 32)
    np.testing.assert_array_equal(
        np.asarray(fused_attention(q, k, v)),
        np.asarray(mha_reference(q, k, v)))


def test_fused_attention_forced_pallas_interpret_on_cpu():
    rng = _rng(20)
    from deeplearning4j_tpu.ops.attention_kernels import (fused_attention,
                                                         mha_reference)
    q, k, v = _qkv(rng, 1, 1, 64, 64, 64)
    ref = mha_reference(q, k, v, causal=True)
    dispatch.set_dispatch_mode("pallas")
    out = fused_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


def test_dense_layer_routes_tier_on_accelerator(monkeypatch):
    """DenseLayer asks the tier; on a (faked) TPU with profitable shapes
    it must call the fused tile, passing bias + activation through."""
    from deeplearning4j_tpu.nn.core import InputType
    from deeplearning4j_tpu.nn.layers import DenseLayer
    rng = _rng(21)
    x = jnp.asarray(rng.randn(256, 128).astype(np.float32))
    layer = DenseLayer(n_out=128, activation="relu")
    params, state, _ = layer.initialize(jax.random.PRNGKey(0),
                                        InputType.feed_forward(128))
    calls = {}

    def fake_fused(x_, w_, bias=None, activation=None, tile=None,
                   interpret=False):
        calls.update(activation=activation, tile=tile, bias=bias)
        return pm.fused_dense_reference(x_, w_, bias=bias,
                                        activation=activation)

    monkeypatch.setattr(pm, "fused_dense", fake_fused)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    y, _ = layer.apply(params, state, x)
    assert calls["activation"] == "relu"
    assert calls["bias"] is params["b"]
    ref = np.maximum(np.asarray(x) @ np.asarray(params["W"])
                     + np.asarray(params["b"]), 0.0)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# tiles + autotuner
# ---------------------------------------------------------------------------

def test_tile_config_roundtrip_and_shape_class():
    cfg = TileConfig(block_q=128, block_kv=256, block_m=64, block_n=512,
                     block_k=1024)
    assert TileConfig.from_json(json.loads(json.dumps(cfg.to_json()))) == cfg
    assert shape_class(m=37, k=70, n=45) == "k128-m64-n64"
    assert shape_class(m=512, k=512, n=512) == shape_class(m=400, k=300,
                                                           n=257)


def test_get_tile_precedence():
    assert dispatch.get_tile("int8_matmul") == \
        tier.DEFAULT_TILES["int8_matmul"]
    wide = TileConfig(block_m=512)
    narrow = TileConfig(block_m=64)
    dispatch.set_tile("int8_matmul", wide)
    assert dispatch.get_tile("int8_matmul", "m64-k128-n128") == wide
    dispatch.set_tile("int8_matmul", narrow, "m64-k128-n128")
    assert dispatch.get_tile("int8_matmul", "m64-k128-n128") == narrow
    assert dispatch.get_tile("int8_matmul", "other") == wide


def test_tile_autotuner_finds_rigged_optimum():
    calls = []

    def measure(cfg):
        calls.append(cfg)
        return -(abs(cfg.block_m - 512) + abs(cfg.block_n - 128)
                 + abs(cfg.block_k - 1024))

    tuner = TileAutotuner(measure, "int8_matmul")
    best = tuner.search()
    assert (best.block_m, best.block_n, best.block_k) == (512, 128, 1024)
    assert tuner.best_rate == 0
    # memoized: every evaluated config measured exactly once
    keys = [c.config_key() for c in calls]
    assert len(keys) == len(set(keys)) == tuner.evaluated


def test_autotune_tiles_persists_then_replays_with_zero_search(tmp_path):
    counts = {"n": 0}

    def measure(cfg):
        counts["n"] += 1
        return float(cfg.block_m)

    hits0 = ops_instruments().tile_cache_hits.value
    t1, info1 = autotune_tiles("int8_matmul", "m512-k512-n512", measure,
                               str(tmp_path))
    assert info1["source"] == "searched" and counts["n"] > 0
    assert t1.block_m == 512
    searched = counts["n"]
    # fresh process simulated: no tuner memo survives, only the table
    t2, info2 = autotune_tiles("int8_matmul", "m512-k512-n512", measure,
                               str(tmp_path))
    assert info2["source"] == "cache"
    assert counts["n"] == searched            # ZERO re-search
    assert t2 == t1
    assert ops_instruments().tile_cache_hits.value == hits0 + 1
    # the winner is installed for dispatch + fingerprinting
    assert dispatch.get_tile("int8_matmul", "m512-k512-n512") == t1
    assert "int8_matmul/m512-k512-n512" in \
        kernel_tier_fingerprint()["tiles"]


def test_tile_table_roundtrip_and_corruption(tmp_path):
    cfg = TileConfig(block_m=64, block_n=128, block_k=256)
    save_tile_entry(str(tmp_path), "fused_dense", "m256-k256-n256", cfg,
                    rate=123.0, device_kind="testchip")
    table = load_tile_table(str(tmp_path), device_kind="testchip")
    assert table == {"fused_dense/m256-k256-n256": cfg}
    # corrupt file → empty table, not an exception
    with open(tile_table_path(str(tmp_path), "testchip"), "w") as f:
        f.write("{not json")
    assert load_tile_table(str(tmp_path), device_kind="testchip") == {}


def test_kernel_tier_fingerprint_splits_aot_keys():
    """reference, Pallas-default, and autotuned-tile programs must never
    share an AOT cache entry (acceptance criterion)."""

    class M:
        pass

    m = M()
    fps = set()
    fps.add(model_fingerprint(m))
    dispatch.set_dispatch_mode("pallas")
    fps.add(model_fingerprint(m))
    dispatch.set_tile("int8_matmul", TileConfig(block_m=512))
    fps.add(model_fingerprint(m))
    dispatch.set_tile("int8_matmul", TileConfig(block_m=128))
    fps.add(model_fingerprint(m))
    assert len(fps) == 4
