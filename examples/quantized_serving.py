"""Quantized serving demo (docs/quantization.md): post-training
quantization end to end — calibrate a trained model, quantize its
weights to per-channel int8, gate on f32 parity, then roll the quantized
version through a serving fleet and watch the warm-pool residency drop.

Shows the quant surface end to end:
 1. train a small MLP, calibrate activation ranges with the percentile
    observer (outlier-clipping histograms over a representative sample),
 2. `quantize_model`: int8 weights + bf16 fallback report, ~4x fewer
    resident parameter bytes, dequantize fused into the jitted forward,
 3. `parity_check` accuracy gate (top-1 disagreement vs the f32 model),
 4. distinct f32/int8 executable fingerprints — the quantized program is
    its own entry in the serving + persistent AOT caches,
 5. `fleet.quantize("m")`: zero-downtime quantized version roll, f32
    predecessor demoted to host, residency re-budgeted at int8 bytes.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np                                         # noqa: E402


def _net(n_in=32, hidden=128, n_out=10):
    from deeplearning4j_tpu.nn import (DenseLayer, InputType,
                                       MultiLayerNetwork,
                                       NeuralNetConfiguration, OutputLayer)
    from deeplearning4j_tpu.train.updaters import Sgd
    conf = (NeuralNetConfiguration.builder().seed(0).updater(Sgd(1e-1))
            .list([DenseLayer(n_out=hidden, activation="relu"),
                   DenseLayer(n_out=hidden, activation="relu"),
                   OutputLayer(n_out=n_out, loss="mcxent",
                               activation="softmax")])
            .set_input_type(InputType.feed_forward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def main():
    import tempfile

    import jax

    from deeplearning4j_tpu.compile import model_fingerprint
    from deeplearning4j_tpu.quant import (calibrate, parity_check,
                                          quantize_model)
    from deeplearning4j_tpu.serving import ModelFleet

    rng = np.random.RandomState(0)
    net = _net()
    # a learnable synthetic task: class = argmax of a fixed projection
    proj = rng.randn(32, 10).astype(np.float32)
    x_train = rng.randn(512, 32).astype(np.float32)
    y_train = np.eye(10, dtype=np.float32)[np.argmax(x_train @ proj, -1)]
    for _ in range(20):
        net.fit(x_train, y_train)

    # 1. calibrate over a representative sample
    calib = [rng.randn(64, 32).astype(np.float32) for _ in range(8)]
    stats = calibrate(net, calib, observer="percentile", percentile=99.9)
    print(f"calibrated {len(stats.ranges)} activation ranges over "
          f"{stats.batches} batches (crc 0x{stats.crc32():08x})")

    # 2. quantize: per-channel int8, bf16 fallback for hostile tensors
    qm = quantize_model(net, calibration=stats)
    f32_bytes = sum(l.nbytes
                    for l in jax.tree_util.tree_leaves(net.params_))
    print(f"dtype report: {qm.report}")
    print(f"resident bytes: {f32_bytes} f32 -> {qm.bytes_resident()} "
          f"quantized ({f32_bytes / qm.bytes_resident():.2f}x smaller)")

    # 3. accuracy gate BEFORE anything serves
    x_eval = rng.randn(512, 32).astype(np.float32)
    r = parity_check(net, qm, x_eval)
    print(f"parity: {r['task']} delta = {r['delta']:.4f}")
    assert r["delta"] <= 0.01, "quantization hurt accuracy; do not roll"

    # 4. the quantized program is its own executable-cache entry
    print(f"fingerprint f32   = {model_fingerprint(net)[:16]}…")
    print(f"fingerprint int8  = {model_fingerprint(qm)[:16]}…")

    # 5. fleet-wide quantized version roll
    cache_dir = tempfile.mkdtemp(prefix="quant-exec-cache-")
    with ModelFleet(max_resident=2, max_batch=8, batch_timeout_ms=2.0,
                    cache_dir=cache_dir) as fleet:
        fleet.deploy("m", net)
        before = fleet.output("m", x_eval[:4])
        b0 = fleet.resident_bytes()
        entry = fleet.quantize("m", calibration=stats)
        b1 = fleet.resident_bytes()
        after = fleet.output("m", x_eval[:4])   # served by v2 (int8)
        print(f"fleet roll: v{entry.version} source={entry.source}, "
              f"residency {b0} -> {b1} bytes "
              f"({b0 / max(b1, 1):.2f}x)")
        assert np.argmax(after, -1).tolist() == \
            np.argmax(before, -1).tolist()
    print("OK")


if __name__ == "__main__":
    main()
