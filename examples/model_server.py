"""Serving runtime demo (docs/serving.md): a zoo LeNet behind
`serving.ModelServer` under concurrent mixed-shape traffic.

Shows the production-serving surface end to end:
 1. deploy from the zoo catalog with bucket warmup (all XLA compiles paid
    before traffic),
 2. many client threads submitting different batch sizes — the continuous
    batcher aggregates them into few bucket-padded dispatches,
 3. per-request deadlines + bounded-queue load shedding (typed errors),
 4. SLO metrics (p50/p99, occupancy, compile-cache hit rate) and the
    live UI `/serving` endpoint.
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from deeplearning4j_tpu.compile import (                 # noqa: E402
    place_compilation_cache)

place_compilation_cache()

import numpy as np                                         # noqa: E402


def main():
    from concurrent.futures import ThreadPoolExecutor

    from deeplearning4j_tpu.serving import (DeadlineExceededError,
                                            ModelServer)

    srv = ModelServer(max_batch=32, batch_timeout_ms=5.0, max_queue=256)

    # 1. deploy + warm: every power-of-two bucket compiles NOW, so no
    # request ever waits on XLA
    entry = srv.deploy("lenet", zoo="LeNet", warmup=True)
    print(f"deployed {entry.key} from {entry.source}; warmed buckets "
          f"{entry.warmed_buckets} "
          f"({srv.metrics.cache.misses.value} compiles)")

    # 2. concurrent mixed-shape clients
    def client(i):
        rs = np.random.RandomState(i)
        x = rs.rand(1 + i % 4, 28, 28, 1).astype(np.float32)
        y = srv.output("lenet", x, deadline_ms=2000.0, timeout=60)
        assert y.shape == (x.shape[0], 10)
        return x.shape[0]

    with ThreadPoolExecutor(max_workers=16) as ex:
        rows = sum(ex.map(client, range(48)))
    s = srv.stats()
    print(f"served 48 requests ({rows} rows) in {s['dispatches']} "
          f"dispatches — occupancy {s['batch_occupancy']:.1f} req/dispatch, "
          f"p50 {s['latency_ms']['p50']:.1f} ms, "
          f"p99 {s['latency_ms']['p99']:.1f} ms, cache hit rate "
          f"{s['compile_cache']['hit_rate']:.0%}")

    # 3. deadlines fail fast with a typed error
    try:
        srv.submit("lenet", np.zeros((1, 28, 28, 1), np.float32),
                   deadline_ms=0.0).result(timeout=10)
    except DeadlineExceededError as e:
        print(f"past-deadline request failed fast: {e}")

    # 4. live metrics endpoint (scrape http://127.0.0.1:<port>/serving)
    from deeplearning4j_tpu.ui.server import UIServer
    ui = UIServer.get_instance().attach_serving(srv)
    port = ui.start(0)
    import json
    import urllib.request
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/serving", timeout=10) as r:
        scraped = json.loads(r.read())
    print(f"UI /serving endpoint live on port {port}: "
          f"{scraped[0]['completed']} completed, occupancy "
          f"{scraped[0]['batch_occupancy']:.1f}")
    ui.stop()

    srv.shutdown()      # graceful: drains in-flight futures; idempotent
    srv.shutdown()
    print("server drained and shut down")


if __name__ == "__main__":
    main()
