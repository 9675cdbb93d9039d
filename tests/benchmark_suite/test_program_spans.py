"""`benchmark/trace/program_spans.py` and the five readers that start from
it, on a hand-built trace and ring with known answers, and on a traced CPU
run of the tiny BERT fixture."""
import os
import sys
import threading
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import harness  # noqa: E402
from benchmark.trace import program_spans  # noqa: E402
from benchmark.trace import reduce as R  # noqa: E402
from deeplearning4j_tpu import monitor  # noqa: E402
from test_harness import MANIFEST, TRAIN, drive  # noqa: E402

READERS = ["input_wait_ms_per_step", "input_stage_ms_per_step",
           "step_dispatch_ms_per_step", "idle_gap_input_pct",
           "idle_gap_unattributed_pct"]
M0, M1 = 100.0, 110.0     # host clock: the two marks
SKEW = 5.0                # trace clock = host clock + SKEW


def read_all(run):
    return {n: harness.load_layer_metric(n).read(run) for n in READERS}


def a_run(traced=True, trace=None):
    """A run between two marks; with a hand-built `trace`, also what
    `harness.device_trace` leaves: its reduction, and the file to load."""
    cell = harness.load_cell(MANIFEST, MANIFEST["workloads"][0]["name"])
    clock = types.SimpleNamespace(marks=[M0, M1], spans=[]) if traced else None
    return harness.Run(
        cell=cell, seed=0, seconds=1.0, traced=traced, devices=[],
        clock=clock, trace_dir="unused",
        trace=trace and R.reduce(trace, (M0 + SKEW, M1 + SKEW - 0.1)))


def on_device(*busy):
    """Device ops covering the given host-clock intervals."""
    return [R.Event(f"fusion.{i}", a + SKEW, b + SKEW)
            for i, (a, b) in enumerate(busy)]


def a_trace(worst_busy):
    """Two chips between two marker runs: chip 0 never idle, chip 1 busy
    only in `worst_busy`.  The window is [100.0, 109.9] on the host clock."""
    marks = [R.Event("jit_bench_marker(1)", M0 + SKEW - 0.1, M0 + SKEW),
             R.Event("jit_bench_marker(1)", M1 + SKEW - 0.1, M1 + SKEW)]
    return R.Trace(device_ops={0: on_device((M0, M1 - 0.1)),
                               1: on_device(*worst_busy)},
                   host_spans=[], modules={0: marks})


@pytest.fixture
def hand_built(ring, monkeypatch):
    """Three steps on the fit thread, a producer thread to be left out, and
    a worst chip with five idle gaps, one of them too short to count."""
    monitor.note("input_wait", 100.2, 100.5, 0)
    monitor.note("input_stage", 100.5, 101.5, 0)
    monitor.note("step_dispatch", 101.6, 102.0, 10)
    monitor.note("input_wait", 102.0, 102.2, 1)
    monitor.note("input_stage", 102.2, 102.6, 1)
    monitor.note("step_dispatch", 102.7, 104.7, 11)
    monitor.note("input_wait", 104.8, 104.9, 2)
    monitor.note("input_stage", 104.9, 105.0, 2)
    monitor.note("step_dispatch", 105.0, 107.0, 12)
    # what `span("fit_epoch")` leaves on exit, from before the first mark
    monitor.note("fit_epoch", 99.0, 109.5)
    monitor.note("step_dispatch", 90.0, 99.5, 9)        # before the window
    t = threading.Thread(target=monitor.note,
                         args=("input_wait", 100.0, 109.0, 0))
    t.start()
    t.join()
    trace = a_trace([(101.9, 102.8), (103.4, 105.0), (105.00005, 107.5),
                     (108.5, 109.6)])
    monkeypatch.setattr(R, "find_xplane", lambda d: d)
    monkeypatch.setattr(R, "load_xplane", lambda path: trace)
    run = a_run(trace=trace)
    # the benchmark's wrapper timed the first `next()` from outside, and one
    # before the window
    run.clock.spans += [("input_next", 100.2, 101.5), ("input_next", 98, 99)]
    return run


def test_collect_on_a_hand_built_trace(hand_built, capfd):
    p = program_spans.collect(hand_built)
    assert p.steps == 3 and p.window_s == pytest.approx(10.0)
    assert p.host_s == pytest.approx({
        "fit_epoch": 9.5,                       # cut at the first mark
        "input_wait": 0.6, "input_stage": 1.5, "step_dispatch": 4.4})
    # [100, 101.9] is cut at the edges of batch 0's spans: 0.2 before its
    # wait and 0.1 before its dispatch lie under fit_epoch alone, 0.3 under
    # the wait, 1.0 under the stage, 0.3 under the dispatch; [102.8, 103.4]
    # inside step 11's dispatch; [107.5, 108.5] after the last leaf span;
    # [109.6, 109.9] after fit_epoch; the 50 us at 105.0 is the device's own
    # turn-around and is not counted
    assert p.idle_s == pytest.approx({
        "input_wait": 0.3, "input_stage": 1.0, "step_dispatch": 0.9,
        "fit_epoch": 1.3, "none": 0.3})
    assert program_spans.collect(hand_built) is p          # worked out once
    out = capfd.readouterr().out
    assert out.count("program spans: 3 step_dispatch") == 1
    assert "the benchmark's own spans there: input_next 1.3000;" in out
    # the three longest of the four gaps, longest first, with where they lie
    assert "; 4 such gaps; 1900.00 ms at +0.0 ms into the window: " \
           "input_stage 1.0000, " in out
    assert "; 1000.00 ms at +7500.0 ms into the window: fit_epoch 1.0000; " \
           "600.00 ms at +2800.0 ms into the window: step_dispatch 0.6000\n" \
           in out


def test_the_five_metrics_on_a_hand_built_trace(hand_built):
    assert read_all(hand_built) == pytest.approx({
        "input_wait_ms_per_step": 200.0,
        "input_stage_ms_per_step": 500.0,
        "step_dispatch_ms_per_step": 4400.0 / 3,
        "idle_gap_input_pct": 100 * 1.3 / 3.8,
        "idle_gap_unattributed_pct": 100 * 1.6 / 3.8})


@pytest.mark.parametrize("busy,unattributed", [
    ([(100.0, 104.0), (106.0, 109.9)], 100.0),   # idle where no span is
    ([(100.0, 101.0), (101.5, 109.9)], 0.0),     # idle inside the dispatch
    ([(100.0, 109.9)], 0.0),                     # never idle: nothing to explain
])
def test_a_gap_no_span_covers_counts_as_unattributed(ring, monkeypatch, busy,
                                                     unattributed):
    monitor.note("step_dispatch", 101.0, 102.0, 0)
    trace = a_trace(busy)
    monkeypatch.setattr(R, "find_xplane", lambda d: d)
    monkeypatch.setattr(R, "load_xplane", lambda path: trace)
    got = read_all(a_run(trace=trace))
    assert got["idle_gap_unattributed_pct"] == pytest.approx(unattributed)
    assert got["idle_gap_input_pct"] == 0.0
    assert got["input_wait_ms_per_step"] == 0.0
    assert got["step_dispatch_ms_per_step"] == pytest.approx(1000.0)


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("situation", ["untraced", "empty_ring", "one_mark",
                                       "no_step_in_the_window"])
def test_readers_read_nothing_where_there_is_nothing(ring, reader, situation):
    run = a_run(traced=situation != "untraced")
    if situation == "untraced":
        monitor.note("step_dispatch", 101.0, 102.0, 0)
    elif situation == "one_mark":
        monitor.note("step_dispatch", 101.0, 102.0, 0)
        run.clock.marks.pop()
    elif situation == "no_step_in_the_window":
        monitor.note("input_wait", 101.0, 102.0, 0)
        monitor.note("step_dispatch", 90.0, 99.0, 0)
    assert harness.load_layer_metric(reader).read(run) is None


def test_a_program_without_the_ring_reads_nothing(monkeypatch):
    """The parent commit's `monitor` has no `recorded`: the readers are run
    against it too, and must leave the metric out, not raise."""
    monkeypatch.delattr(monitor, "recorded")
    assert read_all(a_run()) == dict.fromkeys(READERS)


def test_traced_on_the_cpu_reads_host_spans_but_no_idle_gaps(
        ring, tmp_path, monkeypatch, capfd):
    """A traced run off the chip has the ring and the marks but no device
    plane: the three host readings are there, the two shares of idle time
    are not."""
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.5)
    run = drive("bert_tiny.json", TRAIN, 1, traced=True, tmp_path=tmp_path)
    assert run.trace is None
    got = read_all(run)
    assert got["input_wait_ms_per_step"] >= 0
    assert got["input_stage_ms_per_step"] > 0
    assert got["step_dispatch_ms_per_step"] > 0
    assert got["idle_gap_input_pct"] is None
    assert got["idle_gap_unattributed_pct"] is None
    p = program_spans.collect(run)
    # the program's own count of steps is the driver's
    assert p.steps == run.counters["steps_traced"]
    # the program's spans add up to what the driver timed around `next()`
    # over that same loop, within the loop's own bookkeeping
    assert p.host_s["input_wait"] + p.host_s["input_stage"] \
        <= p.host_s["fit_epoch"] <= p.window_s
    assert "no device trace to lay them over" in capfd.readouterr().out
