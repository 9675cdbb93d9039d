"""Find the knee of a serving mix once, on the chip.

    python3 benchmark/sweep.py --config resnet50 --traffic serve_steady \
        --from-rps 200 --steps 10 --seconds 10 --seed 1

Deploys the configuration once, then offers the mix at rates rising by 1.25x.
The knee is the highest rate at which at least 99% of the requests replied
within the mix's deadline and the queue at the window's end was no deeper
than at its middle.  A cell then fixes its rate at 0.8 x the knee, as a plain
number in its traffic file; PERF.md keeps the table this prints.  Like
`run.py` it runs on a TPU or not at all.
"""
import time

T_START = time.perf_counter()

import argparse                      # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--from-rps", type=float, required=True)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.drivers import serve_open_loop as drv
    from benchmark.harness import BenchmarkError, say
    try:
        manifest = harness.load_manifest()
        traffic = harness.load_traffic(args.traffic)
        cell = harness.Cell(
            name="sweep", chips=1, config_name=args.config,
            config=harness.load_config(manifest, args.config),
            traffic_name=args.traffic, traffic=traffic,
            end_to_end=[], per_layer=[])
        devices = harness.take_devices(1)
    except BenchmarkError as e:
        print(f"benchmark/sweep.py: {e}", file=sys.stderr)
        return 1
    harness.place_cache()
    run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                      traced=False, devices=devices,
                      watch=harness.CompileWatch(), t_start=T_START)
    _, _, srv, pool = drv.deploy(run)
    knee, rate = None, args.from_rps
    try:
        for k in sorted(int(k) for k in traffic["rows_mix"]):
            srv.output(args.config, pool[:k], timeout=120.0)
        for step in range(args.steps):
            _, s, d = drv.measure(srv, args.config, pool,
                                  {**traffic, "rate_rps": rate},
                                  args.seed + step, args.seconds)
            ok = (s["met_deadline_share"] >= 0.99
                  and s["depth_end"] <= max(s["depth_mid"], 1))
            say(f"rate {rate:8.1f} rps ({rate * s['rows'] / s['attempted']:.0f}"
                f" rows/s): p50 {s['p50_ms']:.2f} ms, p99 {s['p99_ms']:.2f} ms"
                f", within deadline {100 * s['met_deadline_share']:.2f}%, "
                f"failed {s['failed']} {s['errors']}, queue mid "
                f"{s['depth_mid']} end {s['depth_end']}, rows/dispatch "
                f"{d['rows_per_dispatch']:.1f}, padding "
                f"{d['padding_pct']:.1f}%, dispatch p50 "
                f"{d['dispatch_p50_ms']:.2f} ms, generator late p99 "
                f"{s['gen_late_p99_ms']:.2f} ms -> "
                f"{'sustained' if ok else 'NOT sustained'}")
            if ok:
                knee = rate
            elif knee is not None:
                break
            rate *= 1.25
    finally:
        srv.shutdown()
    say(f"allocator: {harness.memory_stats_line(devices)}")
    say(f"knee: {knee} rps; 0.8 x knee = "
        f"{None if knee is None else float(f'{0.8 * knee:.2g}')} rps")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
