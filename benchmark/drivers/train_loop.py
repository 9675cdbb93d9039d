"""Driver `train_loop`: the model's own `fit(iterator)` for a fixed time.

One chip: `model.fit(DevicePrefetchIterator(it))`, every argument at its
default.  A traffic file with a `mesh`: `ParallelWrapper(model,
mesh).fit_prefetched(it)`.  `it` cycles over a pool of host batches made from
the seed and ends when the clock says so; throughput is the steps the fit
loop took, times the rows of a batch, over the wall time from the first
dispatch to `block_until_ready` on the parameters after the last.

Traffic parameters: `batch_per_chip`, `pool_batches`, `mesh` (null or axis
sizes), `check_rows` (rows of the pool's first batch that are compared with
the plain reference), `loss_rows` (rows of that batch whose loss has to fall
over the window; `check_rows` where it is left out), and whatever the model
family's `make_pool` reads (`seq_len`, `mask_rate`).
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import harness
from benchmark.harness import Run, say

WARMUP_STEPS = 3     # ParallelWrapper compiles on its first two steps


class PoolIterator:
    """Cycles over host batches until `steps` were given or the clock passes
    `deadline` (`time.perf_counter()` seconds), whichever is set.  Iterable
    with a `reset()`: what the program's fit loops and prefetcher ask of an
    iterator."""

    def __init__(self, pool, steps=None, deadline=None):
        self.pool, self.steps, self.deadline = pool, steps, deadline

    def __iter__(self):
        i = 0
        while (self.steps is None or i < self.steps) and (
                self.deadline is None or time.perf_counter() < self.deadline):
            yield self.pool[i % len(self.pool)]
            i += 1

    def reset(self):
        pass


class TimedIterable:
    """What the fit loop iterates: times each `next()` as the loop calls it
    (the step's wait for input), and calls `on_step` when the loop comes back
    for the next batch, i.e. after it dispatched a step.  With a `clock` (a
    traced stretch) both are also recorded as host spans, `input_next` and
    `step_dispatch`."""

    def __init__(self, inner, on_step=None, clock=None):
        self.inner, self.on_step, self.clock = inner, on_step, clock
        self.wait_s = 0.0

    def reset(self):
        self.inner.reset()

    def __iter__(self):
        it = iter(self.inner)
        while True:
            t0 = time.perf_counter()
            try:
                ds = next(it)
            except StopIteration:
                return
            t1 = time.perf_counter()
            self.wait_s += t1 - t0
            yield ds
            if self.on_step is not None:
                self.on_step()
            if self.clock is not None:
                self.clock.add("input_next", t0, t1)
                self.clock.add("step_dispatch", t1, time.perf_counter())


class StepLog:
    """One entry per optimizer step: when the host got it back, and the loss
    as a device scalar (read after the window, in one transfer)."""

    def __init__(self, probe):
        self.probe, self.times, self.losses = probe, [], []

    def tick(self):
        self.times.append(time.perf_counter())
        self.losses.append(self.probe())


class _Fitter:
    """The program's fit call for this traffic, built once so the mesh
    placement (and its compiles) happen in warm-up."""

    def __init__(self, model, traffic: dict, devices: list, log: StepLog,
                 hooked: bool):
        self.model, self.log, self.hooked = model, log, hooked
        self.wrapper = None
        if traffic.get("mesh"):
            if not hooked:
                raise harness.BenchmarkError(
                    "a mesh cell needs a model family with a step hook")
            from deeplearning4j_tpu.parallel.mesh import make_mesh
            from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
            self.wrapper = ParallelWrapper(
                model, mesh=make_mesh(dict(traffic["mesh"]), devices))

    def fit(self, source: PoolIterator, clock=None):
        """Run one fit over `source`; returns the seconds the fit loop spent
        waiting in `next()` (None where the loop cannot be wrapped)."""
        if self.wrapper is not None:
            # fit_prefetched builds its own prefetcher around the host
            # iterator: the consumer side cannot be wrapped from outside
            self.wrapper.fit_prefetched(source)
            return None
        from deeplearning4j_tpu.data.pipeline import DevicePrefetchIterator
        timed = TimedIterable(DevicePrefetchIterator(source),
                              None if self.hooked else self.log.tick, clock)
        self.model.fit(timed)
        return timed.wait_s


def _window(fitter: _Fitter, family, model, pool, seconds: float,
            clock=None) -> dict:
    """One measured fit of `seconds`: steps, wall time, input wait.  With a
    `clock` (a traced stretch) the window lies between two marker runs."""
    import jax
    n0 = len(fitter.log.times)
    if clock is not None:
        clock.mark()
    t0 = time.perf_counter()
    wait = fitter.fit(PoolIterator(pool, deadline=t0 + seconds), clock)
    t_fit = time.perf_counter()
    jax.block_until_ready(family.parameters(model))
    t1 = time.perf_counter()
    if clock is not None:
        clock.add("fit", t0, t_fit)
        clock.add("drain", t_fit, t1)
        clock.mark()
    ticks = fitter.log.times[n0:]
    return {"steps": len(ticks), "seconds": t1 - t0, "input_wait_s": wait,
            # how far the host ran ahead of the device: the wait after the
            # fit loop's last step until the parameters were ready
            "drain_s": t1 - t_fit}


def _mosaic_calls(family, model, batch):
    """`tpu_custom_call`s in the lowered train step, where the model family
    can lower it (`lower_step`); None otherwise."""
    if not hasattr(family, "lower_step"):
        return None
    return family.lower_step(model, batch).as_text().count("tpu_custom_call")


def _replicas_equal(params, devices) -> bool:
    """Every parameter leaf bit-equal on all devices it lives on."""
    import jax
    for leaf in jax.tree_util.tree_leaves(params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        if len(shards) != len(devices):
            return False
        if not all(np.array_equal(shards[0], s) for s in shards[1:]):
            return False
    return True


def run(run: Run) -> None:
    import jax
    cell = run.cell
    config, traffic = cell.config, cell.traffic
    family = harness.load_family(config)
    chips = len(run.devices)
    rows = int(traffic["batch_per_chip"]) * chips
    check_rows = int(traffic["check_rows"])
    loss_rows = int(traffic.get("loss_rows", check_rows))
    items = family.items_per_row(config, traffic)

    model = family.build(config, run.seed)
    n_params = sum(l.size for l in jax.tree_util.tree_leaves(
        family.parameters(model)))
    say(f"built {config['family']} ({type(model).__name__}), "
        f"{n_params / 1e6:.1f}M parameters")
    pool = family.make_pool(config, traffic, run.seed, rows)
    say(f"pool of {len(pool)} batches x {rows} rows")

    log = StepLog(lambda: family.last_loss(model))
    fitter = _Fitter(model, traffic, run.devices, log,
                     family.step_hook(model, log.tick))

    # -- warm-up: every shape the window uses, through the same calls -------
    fitter.fit(PoolIterator(pool, steps=WARMUP_STEPS))
    jax.block_until_ready(family.parameters(model))
    say(f"warm-up: {len(log.times)} steps, {run.watch.compiles} compiles "
        f"({run.watch.compile_s:.1f} s), cache {run.watch.cache}")
    loss_before = family.eval_loss(model, pool[0], loss_rows)
    mosaic = _mosaic_calls(family, model, pool[0]) if run.traced else None

    # -- the measured window ------------------------------------------------
    n_warm = len(log.times)
    c0 = run.watch.compiles
    run.end_to_end["setup_s"] = time.perf_counter() - run.t_start
    w = _window(fitter, family, model, pool, run.untraced_seconds)
    traced = None
    if run.traced:
        with harness.device_trace(run):
            traced = _window(fitter, family, model, pool,
                             harness.TRACE_SECONDS, run.clock)
    compiles = run.watch.compiles - c0

    rate = w["steps"] * rows / w["seconds"]
    run.end_to_end["train_samples_per_s"] = rate * items["samples"]
    if "tokens" in items:
        run.end_to_end["train_tokens_per_s"] = rate * items["tokens"]
    say(f"window: {w['steps']} steps of {rows} rows in {w['seconds']:.3f} s"
        f" = {rate:.1f} rows/s; input wait {w['input_wait_s']} s, host "
        f"finished dispatching {w['drain_s']:.3f} s before the device")

    losses = np.asarray(jax.device_get(log.losses[n_warm:]), np.float64)
    run.attempted = int(len(losses))
    run.failed = int(np.sum(~np.isfinite(losses)))
    if len(losses) >= 2 * len(pool):
        say(f"step losses as the fit loop reported them: "
            f"{losses[:len(pool)].mean():.4f} over the window's first pass "
            f"through the pool, {losses[-len(pool):].mean():.4f} over its last")
    say(f"allocator: {harness.memory_stats_line(run.devices)}")
    peaks = harness.memory_peaks(run.devices)
    say("peak_hbm_gb per device (buffers + reserved for programs): "
        + " ".join(f"{p / 1e9:.3f}" for p in peaks))

    run.counters.update(
        steps=w["steps"], window_s=w["seconds"], rows=rows, chips=chips,
        rows_per_s=rate, input_wait_s=w["input_wait_s"],
        compiles_in_window=compiles, mosaic_calls=mosaic,
        flops_per_row=family.flops_per_item(config, traffic),
        memory_peaks=peaks)

    # -- the trace ----------------------------------------------------------
    if traced is not None:
        run.counters.update(steps_traced=traced["steps"],
                            traced_window_s=traced["seconds"])
        t_rate = traced["steps"] * rows / traced["seconds"]
        say(f"traced window: {traced['steps']} steps in "
            f"{traced['seconds']:.3f} s = {t_rate:.1f} rows/s, "
            f"{100 * (1 - t_rate / rate):+.1f}% against the untraced "
            f"stretch (tracing overhead)")
        if run.trace is not None:
            say("device time by op class, first chip, ms a step: " + ", ".join(
                f"{c} {1e3 * v / traced['steps']:.2f}" for c, v in sorted(
                    run.trace.category_s.items(), key=lambda kv: -kv[1])))
        run.check("device_ran", run.trace is not None,
                  "no device op inside the traced window"
                  if run.trace is None else
                  f"busy {run.trace.busy_s_mean:.3f} s of "
                  f"{run.trace.window_s:.3f} s")

    # -- correct? -----------------------------------------------------------
    run.check("no_compile_in_window", compiles == 0, f"{compiles} compiles")
    run.check("steps_finite", run.attempted > 0 and run.failed == 0,
              f"{run.failed} of {run.attempted} steps non-finite")
    loss_after = family.eval_loss(model, pool[0], loss_rows)
    run.check("loss_fell", np.isfinite(loss_after) and loss_after < loss_before,
              f"{loss_before:.4f} -> {loss_after:.4f} on {loss_rows} rows "
              f"of the pool's first batch")
    ref = family.reference_check(model, config, pool[0], check_rows)
    run.check("matches_reference",
              ref["rel_err"] <= ref["tol"]
              and abs(ref["loss"] - ref["loss_reference"])
              <= ref["loss_tol"] * abs(ref["loss_reference"]),
              f"output rel err {ref['rel_err']:.3e} (tol {ref['tol']}), loss "
              f"{ref['loss']:.5f} vs reference {ref['loss_reference']:.5f} "
              f"(tol {ref['loss_tol']} rel)")
    if chips > 1:
        run.check("replicas_equal",
                  _replicas_equal(family.parameters(model), run.devices),
                  f"every parameter leaf bit-equal on {chips} devices")
