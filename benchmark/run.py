"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Takes the chip in its own process, fails (never falls back) when the platform
is not `tpu` or jax shows another number of chips than the cell asks for,
builds the cell from the files `BENCHMARK.json` names, warms up, measures,
checks the outputs, and prints one JSON object as the last line of stdout.
With `--trace 0` its `metrics` are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics.  See `benchmark/README.md`.
"""
import time

T_START = time.perf_counter()       # before the heavy imports: set-up counts

import argparse                      # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.harness import BenchmarkError, say
    try:
        if not os.path.isdir(os.path.join(ROOT, "deeplearning4j_tpu")):
            raise BenchmarkError(
                f"no deeplearning4j_tpu package beside {harness.HERE}: the "
                "benchmark measures the program, it does not contain it")
        cell = harness.load_cell(harness.load_manifest(), args.workload)
        driver = harness.load_driver(cell.traffic)
        devices = harness.take_devices(cell.chips)
    except BenchmarkError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 1

    d0 = devices[0]
    say(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, driver {cell.traffic['driver']}, on "
        f"{len(devices)} x {d0.device_kind} ({d0.platform})")
    cache_dir = harness.place_cache()
    say(f"compilation cache before: {harness.cache_dir_report(cache_dir)}")
    trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = harness.Run(
        cell=cell, seed=args.seed, seconds=args.seconds,
        traced=bool(args.trace), devices=devices,
        watch=harness.CompileWatch(), t_start=T_START, trace_dir=trace_dir,
        peaks=harness.load_peaks(d0.device_kind))
    driver.run(run)
    say(f"compiles {run.watch.compiles} ({run.watch.compile_s:.1f} s), "
        f"persistent cache {run.watch.cache}; after: "
        f"{harness.cache_dir_report(cache_dir)}")

    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        if args.trace:
            value = harness.load_layer_metric(m["name"]).read(run)
            if value is None:
                say(f"per-layer metric {m['name']}: nothing to read")
                continue
        else:
            if m["name"] not in run.end_to_end:
                print(f"benchmark/run.py: driver {cell.traffic['driver']} "
                      f"gave no {m['name']}", file=sys.stderr)
                return 1
            value = run.end_to_end[m["name"]]
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics,
              "device": harness.device_line(run)}
    if args.trace and run.trace is not None:
        from benchmark.trace.reduce import breakdown
        result["breakdown"] = breakdown(run.trace)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
