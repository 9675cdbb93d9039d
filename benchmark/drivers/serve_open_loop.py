"""Driver `serve_open_loop`: `ModelServer` under an open-loop arrival schedule.

The model is deployed as a user deploys one (`ModelServer(**server)`,
`deploy(name, model=..., warmup=True)`), after `pretrain_steps` of `fit` on
seeded data so that its norm layers carry statistics (a served model is a
trained one; a fresh net's running statistics are 0 and 1).  Requests are
built before the window from the seed: Poisson arrivals at the FIXED
`rate_rps` of the traffic file, rows per request drawn from `rows_mix`,
payloads cut from a pool of `pool_rows` rows, every request with
`deadline_ms`.  One sender thread of this process (a chip belongs to one
process) submits each request when it is due; replies are timed in `Future`
callbacks.

The timing rule: a request's latency runs from the time it was DUE by the
schedule, not from when the generator got round to sending it, so a stall
charges every request it delays.  How late the generator ran is reported
(`gen_late_p99_ms`) and voids the run above `gen_late_limit_ms`.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import harness
from benchmark.harness import Run, say


def build_schedule(traffic: dict, seed: int, seconds: float,
                   pool_rows: int) -> dict:
    """Arrival times (seconds from the window's start), rows per request and
    the offset of each request's payload in the pool, all from the seed."""
    rng = np.random.default_rng(seed)
    rate = float(traffic["rate_rps"])
    n = max(1, int(rate * seconds))
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    due = due[due < seconds]
    sizes = np.array(sorted(int(k) for k in traffic["rows_mix"]))
    p = np.array([float(traffic["rows_mix"][str(k)]) for k in sizes])
    rows = rng.choice(sizes, size=len(due), p=p / p.sum())
    offset = rng.integers(0, pool_rows - sizes.max() + 1, len(due))
    return {"due": due, "rows": rows, "offset": offset}


class _Window:
    """One pass of the schedule through the server."""

    def __init__(self, srv, name: str, pool: np.ndarray, sched: dict,
                 deadline_ms: float, keep: set, clock=None):
        self.srv, self.name, self.pool, self.sched = srv, name, pool, sched
        self.deadline_ms, self.keep, self.clock = deadline_ms, keep, clock
        n = len(sched["due"])
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.error = [None] * n
        self.replies = {}
        self.depth_mid = self.depth_end = 0
        self.t0 = None

    def _callback(self, i):
        def cb(fut):
            self.done[i] = time.perf_counter()
            exc = fut.exception()
            if exc is not None:
                self.error[i] = type(exc).__name__
            elif i in self.keep:
                self.replies[i] = fut.result()
        return cb

    def _send_all(self):
        due, rows, offset = (self.sched[k] for k in ("due", "rows", "offset"))
        mid = len(due) // 2
        for i in range(len(due)):
            wait = self.t0 + due[i] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            x = self.pool[offset[i]: offset[i] + rows[i]]
            self.sent[i] = time.perf_counter()
            try:
                fut = self.srv.submit(self.name, x,
                                      deadline_ms=self.deadline_ms)
            except Exception as e:              # RejectedError: load shed
                self.done[i] = time.perf_counter()
                self.error[i] = type(e).__name__
                continue
            finally:
                if self.clock is not None:
                    self.clock.add("gen_send", self.sent[i],
                                   time.perf_counter())
            fut.add_done_callback(self._callback(i))
            if i == mid:
                self.depth_mid = self.srv.batcher.queue_depth
        self.depth_end = self.srv.batcher.queue_depth

    def run(self, grace_s: float = 30.0) -> "_Window":
        if self.clock is not None:
            self.clock.mark()
        self.t0 = time.perf_counter()
        sender = threading.Thread(target=self._send_all, name="bench-gen")
        sender.start()
        sender.join()
        end = time.perf_counter() + grace_s
        while np.isnan(self.done).any() and time.perf_counter() < end:
            time.sleep(0.002)
        self.t1 = time.perf_counter()
        if self.clock is not None:
            self.clock.mark()
        return self

    def summary(self) -> dict:
        due_abs = self.t0 + self.sched["due"]
        ok = np.array([e is None for e in self.error]) & ~np.isnan(self.done)
        lat = (self.done - due_abs)[ok] * 1e3
        late = (self.sent - due_abs) * 1e3
        errors = {}
        for e in self.error:
            if e is not None:
                errors[e] = errors.get(e, 0) + 1
        return {
            "attempted": int(len(due_abs)), "failed": int((~ok).sum()),
            "errors": errors, "latency_ms": lat,
            "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
            "p99_ms": float(np.percentile(lat, 99)) if len(lat) else None,
            "met_deadline_share": float(
                (lat <= self.deadline_ms).sum() / max(len(due_abs), 1)),
            "gen_late_p99_ms": float(np.percentile(late, 99)),
            "rows": int(self.sched["rows"].sum()),
            "seconds": self.t1 - self.t0,
            "depth_mid": int(self.depth_mid), "depth_end": int(self.depth_end)}


def _stats_delta(before: dict, after: dict) -> dict:
    d = {k: after[k] - before[k] for k in (
        "dispatches", "rows_dispatched", "rejected", "expired", "failed")}
    pad0 = before["padding_fraction"]
    pad1 = after["padding_fraction"]
    r0, r1 = before["rows_dispatched"], after["rows_dispatched"]
    # padding_fraction = padded / (rows + padded), lifetime: undo it
    padded0 = r0 * pad0 / (1 - pad0) if pad0 < 1 else 0.0
    padded1 = r1 * pad1 / (1 - pad1) if pad1 < 1 else 0.0
    rows, padded = r1 - r0, padded1 - padded0
    d["padding_pct"] = 100.0 * padded / (rows + padded) if rows + padded else 0.0
    d["rows_per_dispatch"] = rows / d["dispatches"] if d["dispatches"] else 0.0
    d["dispatch_p50_ms"] = after["dispatch_ms"].get("p50")
    d["cache_misses"] = (after["compile_cache"]["misses"]
                         - before["compile_cache"]["misses"])
    return d


def deploy(run: Run):
    """The model, pre-trained for its norm statistics, behind a warmed
    `ModelServer`; plus the pool of request rows."""
    import jax
    from deeplearning4j_tpu.serving import ModelServer
    config, traffic = run.cell.config, run.cell.traffic
    family = harness.load_family(config)
    model = family.build(config, run.seed, serving=True)
    steps = int(traffic.get("pretrain_steps", 0))
    if steps:
        batch = int(traffic["pretrain_batch"])
        rows = family.serve_rows(config, run.seed + 1, batch)
        labels = np.eye(int(config["n_classes"]), dtype=np.float32)[
            np.random.default_rng(run.seed).integers(
                0, int(config["n_classes"]), batch)]
        for _ in range(steps):
            model.fit(rows, labels)
        jax.block_until_ready(model.params_)
        say(f"pre-trained {steps} steps of {batch} rows, loss "
            f"{model.score():.4f}")
    srv = ModelServer(**traffic.get("server", {}))
    srv.deploy(run.cell.config_name, model=model, warmup=True,
               input_shape=tuple(config["input_shape"]))
    pool = family.serve_rows(config, run.seed, int(traffic["pool_rows"]))
    say(f"deployed behind buckets {srv.cache.buckets}, compile cache "
        f"{srv.stats()['compile_cache']}; pool of {len(pool)} rows")
    return family, model, srv, pool


def measure(srv, name, pool, traffic, seed, seconds, keep=frozenset(),
            clock=None) -> tuple:
    """One window at the traffic's rate: (`_Window`, summary, stats delta)."""
    sched = build_schedule(traffic, seed, seconds, len(pool))
    before = srv.stats()
    w = _Window(srv, name, pool, sched, float(traffic["deadline_ms"]),
                set(keep), clock).run()
    return w, w.summary(), _stats_delta(before, srv.stats())


def run(run: Run) -> None:
    cell = run.cell
    config, traffic = cell.config, cell.traffic
    family, model, srv, pool = deploy(run)
    name = cell.config_name
    try:
        # warm-up: the request path itself, each request size once
        for k in sorted(int(k) for k in traffic["rows_mix"]):
            srv.output(name, pool[:k], timeout=120.0)
        untraced_s = run.untraced_seconds
        rng = np.random.default_rng(run.seed)
        n_est = int(float(traffic["rate_rps"]) * untraced_s)
        keep = set(rng.choice(max(n_est // 2, 1), size=min(
            int(traffic["sample_replies"]), max(n_est // 2, 1)),
            replace=False).tolist())
        c0 = run.watch.compiles
        run.end_to_end["setup_s"] = time.perf_counter() - run.t_start
        w, s, d = measure(srv, name, pool, traffic, run.seed, untraced_s, keep)
        traced = None
        if run.traced:
            with harness.device_trace(run):
                traced = measure(srv, name, pool, traffic, run.seed + 1,
                                 harness.TRACE_SECONDS, clock=run.clock)[1]
        compiles = run.watch.compiles - c0
        run.attempted, run.failed = s["attempted"], s["failed"]
        run.end_to_end["serve_p50_ms"] = s["p50_ms"]
        run.end_to_end["serve_p99_ms"] = s["p99_ms"]
        say(f"window: {s['attempted']} requests ({s['rows']} rows) in "
            f"{s['seconds']:.3f} s, {s['failed']} failed {s['errors']}, p50 "
            f"{s['p50_ms']} ms, p99 {s['p99_ms']} ms, "
            f"{100 * s['met_deadline_share']:.2f}% within "
            f"{traffic['deadline_ms']} ms; generator late p99 "
            f"{s['gen_late_p99_ms']:.3f} ms; queue depth mid "
            f"{s['depth_mid']} end {s['depth_end']}; server {d}")
        say(f"allocator: {harness.memory_stats_line(run.devices)}")
        run.counters.update(
            compiles_in_window=compiles, gen_late_p99_ms=s["gen_late_p99_ms"],
            rows_per_dispatch=d["rows_per_dispatch"],
            padding_pct=d["padding_pct"],
            dispatch_p50_ms=d["dispatch_p50_ms"],
            met_deadline_share=s["met_deadline_share"],
            memory_peaks=harness.memory_peaks(run.devices))
        if traced is not None:
            say(f"traced window: p50 {traced['p50_ms']} ms against "
                f"{s['p50_ms']} ms untraced (tracing overhead)")
            run.check("device_ran", run.trace is not None)

        # -- correct? -------------------------------------------------------
        run.check("no_compile_in_window", compiles == 0 and
                  d["cache_misses"] == 0, f"{compiles} compiles, "
                  f"{d['cache_misses']} bucket misses")
        run.check("generator_on_time",
                  s["gen_late_p99_ms"] <= float(traffic["gen_late_limit_ms"]),
                  f"late p99 {s['gen_late_p99_ms']:.3f} ms, limit "
                  f"{traffic['gen_late_limit_ms']} ms")
        worst, ref_worst, n_ref = 0.0, 0.0, int(traffic["reference_replies"])
        sched = w.sched
        for j, i in enumerate(sorted(w.replies)):
            x = pool[sched["offset"][i]: sched["offset"][i] + sched["rows"][i]]
            got = np.asarray(w.replies[i], np.float32)
            direct = family.serve_direct(model, x)
            worst = max(worst, float(np.max(np.abs(got - direct))
                                     / (np.max(np.abs(direct)) + 1e-30)))
            if j < n_ref:
                want = np.asarray(family.reference_forward(
                    config, model.params_, model.state_, x, scaled=True))
                ref_worst = max(ref_worst, float(
                    np.max(np.abs(got - want)) / np.max(np.abs(want))))
        run.check("replies_match_direct_forward",
                  len(w.replies) > 0 and worst <= 1e-3,
                  f"{len(w.replies)} sampled replies, worst rel err "
                  f"{worst:.2e} (tol 1e-3: the same program on other rows)")
        tol = float(traffic["reference_rel_tol"])
        run.check("replies_match_reference", ref_worst <= tol,
                  f"{min(n_ref, len(w.replies))} replies, worst rel err "
                  f"{ref_worst:.3e} (tol {tol})")
    finally:
        srv.shutdown()
