"""Pallas fused-kernel tier demo (docs/performance.md §7): every kernel
ships two implementations under one contract — a Pallas TPU kernel
parameterized by a `TileConfig`, and a pure-jnp reference that IS the
definition of correctness — selected per call by `ops.pallas.dispatch`.

Shows the tier end to end (on CPU the Pallas impls run in interpret
mode, so everything here works without an accelerator):
 1. conformance: flash attention vs the jnp reference, the int8-native
    matmul's integer contraction BITWISE vs the reference, fused dense
    bias+activation epilogues,
 2. dispatch: auto mode routes to the reference on CPU, forced-pallas
    drives the real kernels through interpret mode, every decision lands
    in `ops_kernel_dispatch_total{kernel=,impl=}`,
 3. tile autotuning: grid+greedy search over the kernel's tile space,
    winner persisted to `tiles-<device_kind>.json`, replayed on the next
    call with ZERO re-search,
 4. AOT identity: the installed tile schedule is part of
    `kernel_tier_fingerprint()`, so retuned programs never collide with
    default-tile or reference programs in the persistent cache.
"""
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np                                         # noqa: E402


def main():
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.compile import (autotune_tiles,
                                            kernel_tier_fingerprint,
                                            load_tile_table)
    from deeplearning4j_tpu.ops.pallas import attention as pa
    from deeplearning4j_tpu.ops.pallas import dispatch as kd
    from deeplearning4j_tpu.ops.pallas import matmul as pm
    from deeplearning4j_tpu.ops.pallas import (TileConfig, shape_class)

    kd.reset()
    rng = np.random.RandomState(0)
    interp = kd.interpret_mode()
    print(f"backend={jax.default_backend()}  interpret_mode={interp}")

    # -- 1. conformance: the reference is the spec --------------------------
    att_tile = TileConfig(block_q=32, block_kv=64)
    mm_tile = TileConfig(block_m=8, block_n=128, block_k=128)

    B, H, T, S, D = 1, 2, 100, 72, 64           # ragged on purpose
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    keep = (rng.rand(B, S) > 0.3).astype(np.float32)
    keep[:, 0] = 1.0                            # no fully-masked rows
    mask = jnp.asarray(keep)
    flash = pa.flash_attention(q, k, v, mask=mask, causal=True,
                               tile=att_tile, interpret=interp)
    ref = pa.attention_reference(q, k, v, mask=mask, causal=True)
    err = float(jnp.max(jnp.abs(flash - ref)))
    print(f"flash attention (causal+masked, ragged {T}x{S}): "
          f"max |err| = {err:.2e}")
    assert err < 2e-5

    M, K, N = 37, 70, 45
    xq = jnp.asarray(rng.randint(-128, 128, (M, K)), jnp.int8)
    wq = jnp.asarray(rng.randint(-128, 128, (K, N)), jnp.int8)
    ws = jnp.asarray(rng.rand(N) * 0.1 + 1e-3, jnp.float32)
    got = pm.int8_matmul(xq, wq, ws, tile=mm_tile, interpret=interp)
    want = pm.int8_matmul_reference(xq, wq, ws)
    assert bool(jnp.all(got == want))
    print(f"int8-native matmul ({M}x{K}x{N}): BITWISE equal to reference "
          "(integer contraction + fused dequant epilogue)")

    x = jnp.asarray(rng.randn(M, K), jnp.float32)
    w = jnp.asarray(rng.randn(K, N) * 0.1, jnp.float32)
    b = jnp.asarray(rng.randn(N) * 0.1, jnp.float32)
    for act in ("relu", "gelu", "tanh"):
        got = pm.fused_dense(x, w, bias=b, activation=act,
                             tile=mm_tile, interpret=interp)
        want = pm.fused_dense_reference(x, w, bias=b, activation=act)
        assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    print("fused dense bias+activation epilogues (relu/gelu/tanh): OK")

    # -- 2. dispatch: auto vs forced, observable ----------------------------
    from deeplearning4j_tpu.monitor.instrument import ops_instruments
    auto = kd.resolve("int8_matmul", xq, wq, ws)
    prev = kd.set_dispatch_mode("pallas")
    forced = kd.resolve("int8_matmul", xq, wq, ws)
    kd.set_dispatch_mode(prev)
    n_ref = ops_instruments().dispatch("int8_matmul", "reference").value
    n_pal = ops_instruments().dispatch("int8_matmul", "pallas").value
    print(f"dispatch: auto->{auto} forced->{forced}  "
          f"(counter: reference={n_ref:.0f} pallas={n_pal:.0f})")
    assert auto == ("pallas" if kd.on_accelerator() else "reference")
    assert forced == "pallas"

    # -- 3. tile autotune: search -> persist -> replay ----------------------
    calls = {"n": 0}

    def measure(cfg):          # stand-in rate; on TPU you'd time the kernel
        calls["n"] += 1
        return -(abs(cfg.block_m - 256) + abs(cfg.block_n - 128)
                 + abs(cfg.block_k - 1024))

    sc = shape_class(m=2048, k=2048, n=2048)
    tdir = tempfile.mkdtemp(prefix="pallas-tiles-")
    try:
        tile, info = autotune_tiles("int8_matmul", sc, measure, tdir)
        print(f"tile search: {info['evaluated']} configs evaluated -> "
              f"winner (bm={tile.block_m}, bn={tile.block_n}, "
              f"bk={tile.block_k}) persisted to {os.path.basename(info['path'])}")
        n_before = calls["n"]
        tile2, info2 = autotune_tiles("int8_matmul", sc, measure, tdir)
        assert info2["source"] == "cache" and calls["n"] == n_before
        assert tile2 == tile
        print(f"tile replay: source={info2['source']}, zero re-search "
              f"({calls['n'] - n_before} measure calls)")
        table = load_tile_table(tdir)
        assert f"int8_matmul/{sc}" in table

        # -- 4. AOT identity: the tile is part of the fingerprint ----------
        fp = kernel_tier_fingerprint()
        assert fp["tiles"][f"int8_matmul/{sc}"] == tile.to_json()
        kd.clear_tiles()
        assert kernel_tier_fingerprint()["tiles"] == {}
        print(f"kernel_tier_fingerprint: mode={fp['mode']} "
              f"tiles={list(fp['tiles'])} — folded into model_fingerprint, "
              "so retuned programs get their own AOT cache entries")
    finally:
        kd.reset()
        shutil.rmtree(tdir, ignore_errors=True)

    print("pallas kernel tier demo: OK")


if __name__ == "__main__":
    main()
