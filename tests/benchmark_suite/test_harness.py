"""The harness finds every file the manifest names, the manifest keeps to the
contract's letter, each driver runs end to end on tiny fixtures, and
`run.py` refuses to run off the chip."""
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
MANIFEST = harness.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


# ---------------------------------------------------------------------------
# the loaders find what the manifest names
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_with_its_config_traffic_driver_and_family(cell):
    c = harness.load_cell(MANIFEST, cell)
    assert c.config["family"] and c.traffic["driver"]
    assert callable(harness.load_driver(c.traffic).run)
    family = harness.load_family(c.config)
    for fn in ("build", "make_pool", "flops_per_item", "reference_forward",
               "reference_check", "eval_loss", "step_hook", "last_loss"):
        assert callable(getattr(family, fn)), fn
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_layer_metric_reader_exists_and_reads_nothing_from_nothing(metric):
    reader = harness.load_layer_metric(metric)
    cell = harness.load_cell(MANIFEST, CELLS[0])
    empty = harness.Run(cell=cell, seed=0, seconds=1.0, traced=False,
                        devices=[])
    assert reader.read(empty) is None


@pytest.mark.parametrize("kind,name", [
    ("config", "no_such"), ("workload", "no_such")])
def test_unknown_names_are_errors(kind, name):
    with pytest.raises(harness.BenchmarkError, match="no_such"):
        if kind == "config":
            harness.load_config(MANIFEST, name)
        else:
            harness.load_cell(MANIFEST, name)


@pytest.mark.parametrize("loader,arg", [
    (harness.load_traffic, "no_such"),
    (harness.load_driver, {"driver": "no_such"}),
    (harness.load_family, {"family": "no_such"}),
    (harness.load_layer_metric, "no_such")])
def test_missing_files_are_errors(loader, arg):
    with pytest.raises(harness.BenchmarkError, match="no_such"):
        loader(arg)


def test_unknown_device_kind_is_an_error_not_a_default():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.BenchmarkError, match="TPU v9"):
        harness.load_peaks("TPU v9")


# ---------------------------------------------------------------------------
# the manifest keeps to the contract
# ---------------------------------------------------------------------------

def test_manifest_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(harness.MANIFEST) <= 64 * 1024
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    # a full check with the full 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= len(MANIFEST["paths"]) <= 16
    assert 1 <= len(MANIFEST["configs"]) <= 24
    assert 2 <= len(MANIFEST["workloads"]) <= 24
    assert 1 <= len(MANIFEST["end_to_end"]) <= 16
    assert 1 <= len(MANIFEST["per_layer"]) <= 128
    assert len(MANIFEST["command"]) <= 32


def _all_names():
    out = [("config", c["name"]) for c in MANIFEST["configs"]]
    out += [("workload", w["name"]) for w in MANIFEST["workloads"]]
    out += [("traffic", w["traffic"]) for w in MANIFEST["workloads"]]
    out += [("metric", m["name"]) for m in METRICS]
    out += [("reduced", k) for c in MANIFEST["configs"] for k in c["reduced"]]
    return out


@pytest.mark.parametrize("kind,name", _all_names())
def test_names_use_the_allowed_characters(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_entry(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    per_layer = m in MANIFEST["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"layer", "moves"} if per_layer else {"bound"}
    assert set(m) <= allowed and set(m) >= allowed - {"workloads"}
    assert UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if per_layer:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert LINE.match(m["layer"])
        moved = next(e for e in MANIFEST["end_to_end"]
                     if e["name"] == m["moves"])
        # reported only where the metric it moves is
        assert set(m.get("workloads", CELLS)) <= set(
            moved.get("workloads", CELLS))
    else:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert set(m.get("workloads", [])) <= set(CELLS)


def test_metric_names_cells_and_configs_are_unique():
    for names in ([m["name"] for m in METRICS], CELLS,
                  [c["name"] for c in MANIFEST["configs"]],
                  [c["file"] for c in MANIFEST["configs"]],
                  [(w["config"], w["traffic"])
                   for w in MANIFEST["workloads"]]):
        assert len(names) == len(set(names))
    assert "setup_s" in [m["name"] for m in MANIFEST["end_to_end"]]


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_config_entry(config):
    c = next(x for x in MANIFEST["configs"] if x["name"] == config)
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert LINE.match(c["source"]) and LINE.match(c["why"])
    assert len(c["reduced"]) <= 16
    assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
    assert any(w["config"] == config for w in MANIFEST["workloads"])
    body = harness.load_config(MANIFEST, config)
    assert body["source"] == c["source"]
    assert body["reduced"] == c["reduced"]
    width = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$"
                       r"|head|expan|experts_per)")
    assert not [k for k in c["reduced"] if width.search(k)]


@pytest.mark.parametrize("cell", CELLS)
def test_workload_entry(cell):
    w = next(x for x in MANIFEST["workloads"] if x["name"] == cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4)
    assert LINE.match(w["why"])
    assert w["config"] in [c["name"] for c in MANIFEST["configs"]]


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)


def test_command_and_paths():
    for word in MANIFEST["command"]:
        assert LINE.match(word)
        assert not word.startswith("/") and ".." not in word.split("/")
    for p in MANIFEST["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        assert os.path.isdir(os.path.join(ROOT, p))
        for dirpath, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), (dirpath, f)
    script = [w for w in MANIFEST["command"] if w.endswith(".py")]
    assert script and all(
        any(s.startswith(p + "/") for p in MANIFEST["paths"]) for s in script)


# ---------------------------------------------------------------------------
# each driver, end to end, in-process, on tiny fixtures
# ---------------------------------------------------------------------------

def drive(config_file, traffic, chips, seconds=2.0, traced=False,
          tmp_path=None):
    """The test-only way in: a cell built by hand from fixture files and the
    devices of the CPU.  Nothing on `run.py`'s command line reaches this."""
    cell = harness.Cell(
        name="fixture", chips=chips, config_name="fixture",
        config=harness.load_json(os.path.join(FIXTURES, config_file)),
        traffic_name="fixture", traffic=traffic,
        end_to_end=MANIFEST["end_to_end"], per_layer=MANIFEST["per_layer"])
    run = harness.Run(
        cell=cell, seed=7, seconds=seconds, traced=traced,
        devices=harness.take_devices(chips, allow_platform="cpu"),
        watch=harness.CompileWatch(), t_start=time.perf_counter(),
        trace_dir=None if tmp_path is None else str(tmp_path),
        peaks=harness.load_peaks("TPU v5 lite"))
    harness.load_driver(traffic).run(run)
    return run


TRAIN = {"driver": "train_loop", "batch_per_chip": 8, "pool_batches": 4,
         "mesh": None, "check_rows": 4, "seq_len": 16, "mask_rate": 0.15}


@pytest.mark.parametrize("config_file,traffic,chips,metric", [
    ("resnet_tiny.json", TRAIN, 1, "train_samples_per_s"),
    ("bert_tiny.json", TRAIN, 1, "train_tokens_per_s"),
    ("resnet_tiny.json", {**TRAIN, "mesh": {"data": 4}}, 4,
     "train_samples_per_s"),
    # the loss has to fall on `loss_rows` rows, the whole first batch here
    ("resnet_tiny.json", {**TRAIN, "loss_rows": 8}, 1, "train_samples_per_s"),
])
def test_train_loop_end_to_end(config_file, traffic, chips, metric, capfd):
    run = drive(config_file, traffic, chips)
    assert run.correct, run.checks
    rows = traffic.get("loss_rows", traffic["check_rows"])
    assert f"on {rows} rows of the pool's first batch" in capfd.readouterr().out
    assert run.attempted > 10 and run.failed == 0
    assert run.end_to_end[metric] > 0 and run.end_to_end["setup_s"] > 0
    assert run.counters["compiles_in_window"] == 0
    assert run.counters["steps"] == run.attempted
    if chips > 1:
        assert run.checks["replicas_equal"]
        assert run.counters["input_wait_s"] is None
    else:
        assert 0 <= run.counters["input_wait_s"] < run.counters["window_s"]
    assert harness.device_line(run)["count"] == chips


def test_train_loop_traced_on_the_cpu_has_no_device_and_says_so(
        tmp_path, monkeypatch):
    """A traced run off the chip finds no device plane: the run is not
    `correct`, the trace metrics read nothing, the counters still do."""
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.5)
    run = drive("bert_tiny.json", TRAIN, 1, traced=True, tmp_path=tmp_path)
    assert run.trace is None and not run.correct
    assert run.checks["device_ran"] is False
    assert all(ok for name, ok in run.checks.items() if name != "device_ran")
    assert run.counters["steps_traced"] > 0
    assert run.counters["mosaic_calls"] == 0       # the CPU dispatches none
    read = {m["name"]: harness.load_layer_metric(m["name"]).read(run)
            for m in MANIFEST["per_layer"]}
    assert read["device_idle_pct"] is None and read["mfu_busy_pct"] is None
    assert read["compiles_in_window"] == 0
    assert read["input_wait_pct"] >= 0


def test_a_non_finite_loss_is_a_failed_step_and_not_correct(monkeypatch, capfd):
    from benchmark.models import resnet
    monkeypatch.setattr(resnet, "last_loss", lambda model: float("nan"))
    run = drive("resnet_tiny.json", TRAIN, 1, seconds=0.5)
    assert run.failed == run.attempted > 0
    assert not run.correct and run.checks["steps_finite"] is False
    # the failed check is named on stderr too, for whoever keeps only that
    err = capfd.readouterr().err
    assert "cell fixture, seed 7: check steps_finite: FAILED" in err
    assert "check loss_fell" not in err


SERVE = {"driver": "serve_open_loop", "rate_rps": 150.0,
         "rows_mix": {"1": 0.8, "4": 0.15, "8": 0.05}, "deadline_ms": 2000.0,
         "pool_rows": 16, "server": {"max_batch": 8}, "pretrain_steps": 40,
         "pretrain_batch": 8, "sample_replies": 8, "reference_replies": 2,
         "reference_rel_tol": 0.2, "gen_late_limit_ms": 100.0}


def test_serve_open_loop_end_to_end():
    run = drive("resnet_tiny.json", SERVE, 1)
    assert run.correct, run.checks
    assert run.attempted > 200 and run.failed == 0
    assert 0 < run.end_to_end["serve_p50_ms"] <= run.end_to_end["serve_p99_ms"]
    assert run.end_to_end["serve_p99_ms"] < SERVE["deadline_ms"]
    read = {n: harness.load_layer_metric(n).read(run) for n in (
        "serve_rows_per_dispatch", "serve_padding_pct",
        "serve_dispatch_p50_ms", "gen_late_p99_ms", "compiles_in_window")}
    assert read["serve_rows_per_dispatch"] >= 1
    assert 0 <= read["serve_padding_pct"] < 100
    assert read["serve_dispatch_p50_ms"] > 0
    assert read["gen_late_p99_ms"] < SERVE["gen_late_limit_ms"]
    assert read["compiles_in_window"] == 0


def test_serve_schedule_is_the_seeds_and_times_from_due():
    from benchmark.drivers.serve_open_loop import build_schedule
    a = build_schedule(SERVE, 3, 2.0, 16)
    b = build_schedule(SERVE, 3, 2.0, 16)
    c = build_schedule(SERVE, 4, 2.0, 16)
    assert all((a[k] == b[k]).all() for k in a)
    assert len(a["due"]) != len(c["due"]) or (a["due"] != c["due"]).any()
    assert (a["due"][1:] >= a["due"][:-1]).all() and a["due"][-1] < 2.0
    assert 0.7 * 300 < len(a["due"]) < 1.3 * 300         # 150 rps x 2 s
    assert set(a["rows"]) <= {1, 4, 8}
    assert (a["offset"] + a["rows"] <= 16).all()


def test_a_shed_request_counts_as_failed_not_in_the_latency():
    """Above capacity with a tiny queue the server sheds load: those
    requests are `failed`, and the percentiles are of the rest."""
    hot = {**SERVE, "rate_rps": 3000.0, "deadline_ms": 50.0,
           "server": {"max_batch": 8, "max_queue": 4}}
    run = drive("resnet_tiny.json", hot, 1, seconds=1.0)
    assert run.failed > 0 and run.attempted > run.failed
    assert run.end_to_end["serve_p50_ms"] > 0


# ---------------------------------------------------------------------------
# run.py itself never runs off the chip
# ---------------------------------------------------------------------------

def _run_py(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


ARGS = ("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
        "--trace", "0")


def test_run_py_exits_non_zero_naming_cpu():
    p = _run_py(ROOT, *ARGS)
    assert p.returncode != 0
    assert "cpu" in p.stderr and "need 'tpu'" in p.stderr
    assert not p.stdout.strip().startswith("{")


def test_run_py_has_no_way_round_the_device_check():
    src = open(os.path.join(ROOT, "benchmark", "run.py")).read()
    assert "allow_platform" not in src and "environ" not in src
    flags = set(re.findall(r'add_argument\("(--[a-z]+)"', src))
    assert flags == {"--workload", "--seed", "--seconds", "--trace"}


def test_run_py_alone_with_the_benchmark_exits_non_zero(tmp_path):
    """In a directory that holds only BENCHMARK.json and `paths`."""
    shutil.copy(harness.MANIFEST, tmp_path / "BENCHMARK.json")
    for p in MANIFEST["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(str(tmp_path), *ARGS)
    assert p.returncode != 0
    assert "deeplearning4j_tpu" in p.stderr
    assert not p.stdout.strip()


def test_run_py_unknown_workload_exits_non_zero():
    p = _run_py(ROOT, "--workload", "no_such", "--seed", "1", "--seconds",
                "1", "--trace", "0")
    assert p.returncode != 0 and "no_such" in p.stderr
    assert not p.stdout.strip()
