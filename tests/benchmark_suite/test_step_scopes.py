"""`benchmark/trace/step_scopes.py` and the nine readers that start from it:
on hand-built events and HLO text with known answers, on a step whose
compiled text comes back without the `updater` scope (the stale-metadata
state), and on a traced CPU run of the tiny ResNet fixture."""
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import harness  # noqa: E402
from benchmark.trace import reduce as R  # noqa: E402
from benchmark.trace import scopes, step_scopes  # noqa: E402
from deeplearning4j_tpu import monitor  # noqa: E402
from test_harness import MANIFEST, TRAIN, drive  # noqa: E402

READERS = ["updater_ms_per_step", "unscoped_ms_per_step",
           "param_cast_ms_per_step", "conv_ms_per_step",
           "conv_backward_ms_per_step", "batchnorm_ms_per_step",
           "self_attention_ms_per_step", "ffn_ms_per_step",
           "mlm_head_ms_per_step"]

# instruction name -> op_name, as `scopes_from_hlo_text` finds them
HLO = """
HloModule jit_step

%fused (p: f32[8]) -> f32[8] {
  ROOT %m = f32[8] multiply(%p, %p), metadata={op_name="jit(step)/updater/stem_conv/mul"}
}

ENTRY %main {
  %fusion.1 = f32[8] fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/jvp(ConvolutionLayer/res2a)/conv_general_dilated" source_file="x.py"}
  %fusion.2 = f32[8] fusion(%a), kind=kOutput, calls=%fused, metadata={op_name="jit(step)/transpose(jvp(ConvolutionLayer/res2a))/conv_general_dilated"}
  %fusion.3 = f32[8] fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/transpose(jvp(BatchNormalizationLayer/res2a_bn))/mul"}
  %fusion.4 = f32[8] fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/updater/res2a/sub"}
  %fusion.5 = f32[8] fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/jvp(param_cast)/convert_element_type"}
  %fusion.6 = f32[8] fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/jvp()/while/body/closed_call/self_attention/dot_general"}
  %fusion.7 = f32[8] fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/ffn/jit(_var)/mul"}
  %fusion.8 = f32[8] fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/jvp(mlm_head)/transpose(jvp())/dot_general"}
  %fusion.9 = f32[8] fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/transpose(jvp())/while/body/closed_call/mul"}
  %while.1 = (f32[8]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jvp()/while"}
  %copy.1 = f32[8] copy(%a)
  ROOT %fusion.10 = f32[8] fusion(%a), kind=kLoop, calls=%fused, metadata={op_name="jit(step)/jit(_threefry_split)/LayerwiseTrainer._loss_and_grads/while/body/add"}
}
"""
# (instruction, start, end): `while.1` holds fusion.6 and fusion.7
TIMES = [("fusion.1", 0.0, 0.1), ("fusion.2", 0.1, 0.3), ("fusion.3", 0.3, 0.35),
         ("fusion.4", 0.35, 0.45), ("fusion.5", 0.45, 0.5),
         ("while.1", 0.5, 0.9), ("fusion.6", 0.5, 0.6), ("fusion.7", 0.6, 0.85),
         ("fusion.8", 0.9, 0.92), ("fusion.9", 0.92, 0.95),
         ("copy.1", 0.95, 0.96), ("fusion.10", 0.96, 0.97),
         ("fusion.99", 0.97, 0.99)]          # not in the step's text
STEPS = 2


def _read(name, run):
    return harness.load_layer_metric(name).read(run)


def _a_run(names=None, steps=STEPS):
    """A traced run record whose trace file is the hand-built one below and
    whose step names are `names` (default: what `HLO` holds)."""
    cell = harness.load_cell(MANIFEST, MANIFEST["workloads"][0]["name"])
    run = harness.Run(cell=cell, seed=0, seconds=1.0, traced=True, devices=[],
                      clock=types.SimpleNamespace(marks=[0.0, 1.0], spans=[]),
                      trace_dir="unused")
    run.trace = object()
    run.counters.update(steps_traced=steps)
    run._step_names = (scopes.scopes_from_hlo_text(HLO)
                       if names is None else names)
    return run


@pytest.fixture
def hand_built(monkeypatch):
    marks = [R.Event("jit_bench_marker(1)", -0.1, 0.0),
             R.Event("jit_bench_marker(1)", 1.0, 1.1)]
    trace = R.Trace(device_ops={0: [R.Event(n, a, b, "f32[8] fusion(%a), kind=kLoop")
                                    for n, a, b in TIMES]},
                    host_spans=[], modules={0: marks})
    monkeypatch.setattr(R, "find_xplane", lambda d: d)
    monkeypatch.setattr(R, "load_xplane", lambda path: trace)
    return _a_run()


def test_the_nine_metrics_on_a_hand_built_trace(hand_built):
    per_step = 1e3 / STEPS
    got = {n: _read(n, hand_built) for n in READERS}
    assert got == pytest.approx({
        "updater_ms_per_step": 0.1 * per_step,
        # fusion.9 (scan bookkeeping), copy.1 (no op_name), fusion.10 (a
        # jitted library function outside any scope), fusion.99 (not in the
        # text); the `while` itself is control and counts nowhere
        "unscoped_ms_per_step": (0.03 + 0.01 + 0.01 + 0.02) * per_step,
        "param_cast_ms_per_step": 0.05 * per_step,
        "conv_ms_per_step": 0.3 * per_step,
        "conv_backward_ms_per_step": 0.2 * per_step,
        "batchnorm_ms_per_step": 0.05 * per_step,
        "self_attention_ms_per_step": 0.1 * per_step,
        "ffn_ms_per_step": 0.25 * per_step,
        "mlm_head_ms_per_step": 0.02 * per_step})
    # named layers and the unscoped account for every op but the `while`,
    # whose own 0.05 s its body leaves, and which is control
    assert sum(v for k, v in got.items()
               if k != "conv_backward_ms_per_step") \
        == pytest.approx((0.99 - 0.05) * per_step)


def test_the_log_names_the_longest_ops_with_their_scopes(hand_built, capfd):
    events = step_scopes.scoped_events(hand_built)
    assert step_scopes.scoped_events(hand_built) is events     # once a run
    out = capfd.readouterr().out
    assert out.count("longest device ops, ms a step, with") == 1
    assert "longest device ops, ms a step, with their scopes: fusion.7 " \
           "125.00 jit(step)/transpose(jvp())/while/body/closed_call/ffn/" \
           "jit(_var)/mul; fusion.2 100.00 jit(step)/transpose(jvp(" \
           "ConvolutionLayer/res2a))/conv_general_dilated; " in out
    assert "; fusion.99 10.00 (no op_name)\n" in out            # the tenth
    assert "longest device ops under no scope, ms a step: fusion.9 15.00 " \
           "f32[8] fusion jit(step)/transpose(jvp())/while/body/closed_call/" \
           "mul; fusion.99 10.00 f32[8] fusion (no op_name); " in out
    assert "11 of 13 device ops in the window carry an op_name" in out
    assert "by top scope, first chip, ms a step: ConvolutionLayer 150.00, " \
           "ffn 125.00, " in out
    assert step_scopes.top_ops(step_scopes.self_times(events), STEPS,
                               n=2) == [
        ("fusion.7", pytest.approx(125.0),
         "jit(step)/transpose(jvp())/while/body/closed_call/ffn/jit(_var)/mul"),
        ("fusion.2", pytest.approx(100.0),
         "jit(step)/transpose(jvp(ConvolutionLayer/res2a))/"
         "conv_general_dilated")]


@pytest.mark.parametrize("path,want", [
    ("jit(step)/jvp(ConvolutionLayer/res2a_branch2a)/conv", "ConvolutionLayer"),
    ("jit(step)/transpose(jvp(loss))/jit(log_softmax)/sub", "loss"),
    ("jit(step)/updater/stem_bn/mul", "updater"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/moe/jit(_routed)/dispatch/gather", "moe"),
    ("jit(step)/jvp()/while/body/closed_call/param_cast/convert", "param_cast"),
    ("jit(step)/jvp()/while/body/closed_call/mul", ""),
    ("jit(step)/jvp()/while/body/dynamic_update_slice", ""),
    ("jit(step)/jit(_threefry_split)/LayerwiseTrainer._loss_and_grads/add", ""),
    ("jit(step)/jvp(jit(take_along_axis))/gather", ""),
    ("jit(step)/jvp()/cond/branch_1_fun/short_conv/mix/mul", "short_conv"),
    ("jit(step)/mul", ""), ("reduce_sum", ""), ("", "")])
def test_top_scope(path, want):
    assert step_scopes.top_scope(path) == want


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("situation", ["untraced", "no_device_trace",
                                       "no_steps", "no_step_text",
                                       "scopes_absent"])
def test_readers_read_nothing_where_there_is_nothing(hand_built, reader,
                                                     situation):
    run = hand_built
    if situation == "untraced":
        run.traced = False
    elif situation == "no_device_trace":
        run.trace = None
    elif situation == "no_steps":
        run.counters["steps_traced"] = 0
    elif situation == "no_step_text":
        run._step_names = None
    elif situation == "scopes_absent":      # the parent's program
        run._step_names = {"fusion.1": "jit(step)/jvp(moe)/mul",
                           "fusion.2": "jit(step)/mul"}
    assert _read(reader, run) is None


class _Step:
    """Stands in for a `jax.stages.Lowered`: `compile().as_text()` gives
    `stale` until the cache's entries of its module are gone."""

    def __init__(self, cache_dir, stale, fresh):
        self.cache_dir, self.stale, self.fresh = cache_dir, stale, fresh

    def as_text(self):
        return "module @jit_step attributes {mhlo.num_partitions = 1}"

    def compile(self):
        hit = any(f.startswith("jit_step-") for f in os.listdir(self.cache_dir))
        return types.SimpleNamespace(
            as_text=lambda: self.stale if hit else self.fresh)


def test_stale_names_are_detected_and_cured(tmp_path, monkeypatch, capfd):
    """The compiled text of a program WITH the handle holds no `updater`:
    the cache's entries of the step's module go (no other module's), the
    step is compiled again and its names are the fresh ones."""
    import jax
    for name in ("jit_step-abc-cache", "jit_step-abc-atime",
                 "jit_step-def-cache", "jit_stepper-abc-cache",
                 "jit__normal-abc-cache"):
        (tmp_path / name).write_bytes(b"x")
    stale = HLO.replace("updater", "")
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cleared = []
    monkeypatch.setattr(jax, "clear_caches", lambda: cleared.append(1))
    monkeypatch.setattr(monitor, "lowered_step",
                        lambda: _Step(str(tmp_path), stale, HLO))
    try:
        run = _a_run()
        del run._step_names
        names = step_scopes.step_names(run)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert step_scopes.has_scope(names, "updater") and cleared == [1]
    assert sorted(os.listdir(tmp_path)) == ["jit__normal-abc-cache",
                                            "jit_stepper-abc-cache"]
    out = capfd.readouterr().out
    assert "STALE NAMES" in out and "Removed 2 entries" in out
    assert "the scopes are there now" in out


def test_fresh_names_cost_one_compile_and_touch_no_cache(tmp_path,
                                                         monkeypatch, capfd):
    (tmp_path / "jit_step-abc-cache").write_bytes(b"x")
    monkeypatch.setattr(monitor, "lowered_step",
                        lambda: _Step(str(tmp_path), HLO, HLO))
    run = _a_run()
    del run._step_names
    assert step_scopes.has_scope(step_scopes.step_names(run), "updater")
    assert os.listdir(tmp_path) == ["jit_step-abc-cache"]
    assert "STALE" not in capfd.readouterr().out


def test_a_program_without_the_handle_falls_back_to_the_family(monkeypatch):
    """The parent commit's `monitor` has no `lowered_step`: the family's
    `LAST_LOWERED` where there is one (no `updater` there, and no second
    compile for that), else nothing; never an error."""
    monkeypatch.delattr(monitor, "lowered_step")
    run = _a_run()
    del run._step_names
    assert step_scopes.step_names(run) is None
    family = types.SimpleNamespace(LAST_LOWERED=_Step(
        os.getcwd(), "unused", HLO.replace("updater", "")))
    monkeypatch.setitem(sys.modules, "benchmark.models."
                        + run.cell.config["family"], family)
    run = _a_run()
    del run._step_names
    names = step_scopes.step_names(run)
    assert names and not step_scopes.has_scope(names, "updater")


def test_traced_on_the_cpu_takes_the_text_from_the_program(tmp_path,
                                                           monkeypatch):
    """A traced run off the chip has no device plane, so the readers read
    nothing; the program still hands out the step the driver ran, with the
    layer-wise trainer's scopes, forward and backward."""
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.5)
    run = drive("resnet_tiny.json", TRAIN, 1, traced=True, tmp_path=tmp_path)
    assert run.trace is None
    assert {n: _read(n, run) for n in READERS} == dict.fromkeys(READERS)
    names = step_scopes.step_names(run)
    tops = {step_scopes.top_scope(p) for p in names.values()}
    assert {"ConvolutionLayer", "BatchNormalizationLayer", "updater",
            "param_cast", "input_normalize", "loss"} <= tops
    assert any("transpose(jvp(ConvolutionLayer/" in p for p in names.values())
