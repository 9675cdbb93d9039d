"""`benchmark/trace/reduce.py` on hand-built traces with known answers."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.trace import reduce as R  # noqa: E402
from benchmark.trace.reduce import Event  # noqa: E402


def ev(name, start, end, text=""):
    return Event(name, float(start), float(end), text)


def test_busy_union_counts_overlap_once():
    ops = [ev("a", 0, 4), ev("b", 2, 6), ev("c", 8, 9), ev("d", 8.5, 8.7)]
    assert R.union_intervals(ops) == [(0.0, 6.0), (8.0, 9.0)]
    assert R.busy_seconds(ops) == pytest.approx(7.0)


def test_idle_gaps_include_the_window_edges():
    ops = [ev("a", 1, 2), ev("b", 4, 5)]
    assert R.idle_gaps(ops, 0, 6) == [(0, 1.0), (2.0, 4.0), (5.0, 6)]
    assert R.idle_gaps(ops, 1, 5) == [(2.0, 4.0)]


def test_clip_cuts_and_drops():
    got = R.clip([ev("a", 0, 4), ev("b", 5, 6), ev("c", 3, 9)], 2, 5)
    assert [(e.name, e.start, e.end) for e in got] == [("a", 2, 4),
                                                       ("c", 3, 5)]


def test_self_time_of_nested_ops():
    # a `while` of 10 s holds two body ops of 3 s and 4 s; one op after it
    ops = [ev("while.1", 0, 10), ev("fusion.1", 1, 4), ev("fusion.2", 5, 9),
           ev("copy.1", 10, 11)]
    selfs = {e.name: s for e, s in R.self_times(ops)}
    assert selfs == pytest.approx(
        {"while.1": 3.0, "fusion.1": 3.0, "fusion.2": 4.0, "copy.1": 1.0})


@pytest.mark.parametrize("name,text,want", [
    ("convolution.5", "", "mxu"),
    ("convolution_add_fusion.21",
     "bf16[16,512,768] fusion(...), kind=kOutput, calls=%fc", "mxu"),
    ("fusion.178", "f32[16,512,768] fusion(...), kind=kOutput", "mxu"),
    ("bitcast_dynamic-update-slice_fusion.29",
     "bf16[12,16,512,768] fusion(...), kind=kOutput", "mxu"),
    ("bitcast_dynamic-update-slice_fusion.3",
     "bf16[12,16,512,768] fusion(...), kind=kLoop", "copy"),
    ("fusion.9", "f32[8] fusion(...), kind=kLoop, calls=%fc", "other"),
    ("all-reduce.1", "", "collective"),
    ("all-reduce-start.2", "", "collective"),
    ("reduce-scatter.7", "", "collective"),
    ("while.4", "", "control"),
    ("copy.11", "", "copy"),
    ("closed_call.3", "custom-call(...), custom_call_target="
     "\"tpu_custom_call\"", "mosaic"),
    ("multiply_add_fusion", "f32[4] fusion(...), kind=kLoop", "other"),
])
def test_op_class(name, text, want):
    assert R.op_class(ev(name, 0, 1, text)) == want


def test_attribute_gap_prefers_the_inner_span_that_covers_half():
    spans = [ev("fit", 0, 100), ev("input_next", 10, 14),
             ev("step_dispatch", 13, 30)]
    assert R.attribute_gap((10, 13.5), spans) == "input_next"
    assert R.attribute_gap((14, 20), spans) == "step_dispatch"
    assert R.attribute_gap((40, 50), spans) == "fit"   # only the outer one
    assert R.attribute_gap((140, 150), spans) == "none"
    # nothing covers half of it: the one that overlaps most
    assert R.attribute_gap((0, 10), [ev("a", 0, 2), ev("b", 6, 9)]) == "b"


def _two_chip_trace():
    """Window [0, 10].  Chip 0: a while of 6 s holding a 2 s conv fusion and
    a 1 s all-reduce, then a 2 s loop fusion: busy 8, idle 20%.  Chip 1:
    busy 5 (idle 50%), with a 4 s gap while the host sits in input_next."""
    chip0 = [ev("while.1", 0, 6),
             ev("fusion.1", 1, 3, text="fusion(...), kind=kOutput"),
             ev("all-reduce.1", 3, 4),
             ev("fusion.2", 7, 9, text="fusion(...), kind=kLoop")]
    chip1 = [ev("fusion.1", 0, 3, text="fusion(...), kind=kOutput"),
             ev("fusion.2", 7, 9, text="fusion(...), kind=kLoop"),
             ev("late.1", 11, 12)]                     # outside the window
    host = [ev("fit", 0, 10), ev("input_next", 3, 6.5),
            ev("step_dispatch", 6.5, 7)]
    return R.Trace({0: chip0, 1: chip1}, host)


def test_reduce_known_answers():
    r = R.reduce(_two_chip_trace(), window=(0, 10))
    assert r.window_s == pytest.approx(10.0)
    assert r.busy_s == pytest.approx({0: 8.0, 1: 5.0})
    assert r.busy_s_mean == pytest.approx(6.5)
    assert r.idle_pct_worst == pytest.approx(50.0)
    # chip 0 self times: while 3 (control, left out), conv fusion 2,
    # all-reduce 1, loop fusion 2 -> 5 s of work, 2 of them on the MXU
    assert r.category_s == pytest.approx(
        {"control": 3.0, "mxu": 2.0, "collective": 1.0, "other": 2.0})
    assert r.mxu_pct == pytest.approx(40.0)
    assert r.collective_s == pytest.approx(1.0)
    assert r.top_ops[0][0] in ("fusion.1", "fusion.2")
    assert dict(r.top_ops) == pytest.approx(
        {"fusion.1": 2.0, "fusion.2": 2.0, "all-reduce.1": 1.0})
    # the worst chip's gaps: [3, 7] is mostly input_next, [9, 10] only fit
    assert dict(r.top_gaps) == pytest.approx(
        {"input_next": 4.0, "fit": 1.0})
    assert R.breakdown(r) == {
        "device_ops": [[n, s] for n, s in r.top_ops],
        "idle_gaps": [["input_next", pytest.approx(4.0)],
                      ["fit", pytest.approx(1.0)]]}


def test_reduce_without_a_window_takes_the_extent_of_the_ops():
    r = R.reduce(_two_chip_trace())
    assert r.window_s == pytest.approx(12.0)            # 0 .. late.1's end
    assert r.busy_s == pytest.approx({0: 8.0, 1: 6.0})


def test_reduce_with_nothing_on_the_device_is_none():
    t = _two_chip_trace()
    assert R.reduce(R.Trace({}, t.host_spans)) is None
    assert R.reduce(t, window=(20, 30)) is None


def test_align_ties_the_host_clock_to_the_markers():
    """The marker ran on the device at trace times [4.9, 5.0] and
    [15.0, 15.1]; the host saw it done at 105.0 and 115.1 on its own clock:
    offset -100, no skew.  Host spans move onto the trace's clock and the
    window is what lies between the two marker runs."""
    t = R.Trace({0: [ev("fusion.1", 6, 7)]}, [], {
        0: [ev("jit_step(1)", 6, 7), ev("jit_bench_marker(7)", 4.9, 5.0),
            ev("jit_bench_marker(7)", 15.0, 15.1)],
        1: [ev("jit_step(1)", 6, 7)]})
    window, skew = R.align(t, [105.0, 115.1], [("input_next", 107.0, 108.5)])
    assert window == pytest.approx((5.0, 15.0))
    assert skew == pytest.approx(0, abs=1e-9)
    assert [(s.name, s.start, s.end) for s in t.host_spans] == [
        ("input_next", pytest.approx(7.0), pytest.approx(8.5))]
    r = R.reduce(t, window)
    assert r.window_s == pytest.approx(10.0)
    # idle [5, 6]: no span touches it; idle [7, 15]: input_next overlaps most
    assert dict(r.top_gaps) == pytest.approx({"none": 1.0, "input_next": 8.0})
    # a host that saw the second marker 0.2 s late: that is the skew
    assert R.align(t, [105.0, 115.3], [])[1] == pytest.approx(-0.2)


def test_align_without_two_marker_runs_is_none():
    t = R.Trace({0: [ev("fusion.1", 6, 7)]}, [],
                {0: [ev("jit_bench_marker(7)", 4.9, 5.0)]})
    assert R.align(t, [105.0, 115.1], []) is None
    assert R.align(R.Trace({}, []), [105.0, 115.1], []) is None
    assert t.host_spans == []


def test_device_trace_on_the_cpu_records_spans_and_finds_no_device(tmp_path):
    """A real file written by jax.profiler on the CPU, through the
    harness's own context manager: no device planes, so no reduction; the
    clock's marks and spans are kept on the host clock."""
    import time

    from benchmark import harness

    run = harness.Run(cell=None, seed=0, seconds=1.0, traced=True,
                      devices=[], trace_dir=str(tmp_path))
    with harness.device_trace(run):
        run.clock.mark()
        t = time.perf_counter()
        time.sleep(0.02)
        run.clock.add("input_next", t, time.perf_counter())
        run.clock.mark()
    assert run.trace is None                 # no chip ran anything
    assert len(run.clock.marks) == 2
    (name, t0, t1), = run.clock.spans
    assert name == "input_next" and t1 - t0 >= 0.02
    assert run.clock.marks[0] <= t0 and t1 <= run.clock.marks[1]
    path = R.find_xplane(str(tmp_path))
    assert R.load_xplane(path).device_ops == {}
    assert "PLANE" in R.describe_xplane(path)
