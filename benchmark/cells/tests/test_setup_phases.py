"""The set-up phase metrics (``setup_import_s``, ``setup_trace_s``,
``setup_lower_s``, ``setup_backend_s``; reader ``setup_phase_s``) on the CPU:
(a) the reader on a hand-made run, cut at the window's opening; (b) a program
without ``profiler.compile_phase_counters`` reads nothing; (c) a rehearsed
traced run of a tiny train cell reports all four where its spec root lists
them."""
import contextlib
import io
import json
import os
import sys

import pytest

CELLS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CELLS)
import run as run_mod                                   # noqa: E402
from harness.spec import Spec                           # noqa: E402

PHASES = ("import", "trace", "lower", "backend")
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture
def reader():
    return Spec(None).module("readers", "setup_phase_s")


@pytest.fixture
def phases(monkeypatch):
    """The program's counters, replaced by a fresh set fed by hand."""
    from mxnet_tpu import profiler
    fresh = profiler.CompilePhases()
    monkeypatch.setattr(profiler, "_phases", fresh)
    return fresh


def test_reader_cuts_at_the_window_opening(reader, phases, capsys):
    phases.stamp_import(100.0, 101.5)
    phases.listener(TRACE, 102.0, 104.0, fun_name="step_fn")
    phases.listener(TRACE, 102.5, 103.0, fun_name="layer")   # nested
    phases.listener(LOWER, 104.0, 105.0, fun_name="jit_step_fn")
    phases.listener(BACKEND, 105.0, 108.0, fun_name="jit_step_fn")
    # the check's reference compiles, after the window opened at 110
    phases.listener(TRACE, 170.0, 171.0, fun_name="served_gaps")
    phases.listener(BACKEND, 172.0, 180.0, fun_name="jit_served_gaps")
    run = run_mod.Run({"window_open_wall": 110.0}, None, 1, [], 1.0, 1.0)
    got = {p: reader.read(run, {"phase": p}) for p in PHASES}
    assert got == {"import": pytest.approx(1.5), "trace": pytest.approx(2.0),
                   "lower": pytest.approx(1.0), "backend": pytest.approx(3.0)}
    lines = [json.loads(line) for line in capsys.readouterr().err.splitlines()]
    assert len(lines) == 1 and lines[0]["event"] == "setup_phases"
    assert lines[0]["top"]["backend"] == [["jit_step_fn", 3.0]]
    assert lines[0]["events"] == {"trace": 2, "lower": 1, "backend": 1}


def test_reader_on_a_program_without_the_counters_reads_nothing(
        reader, monkeypatch, capsys):
    from mxnet_tpu import profiler
    monkeypatch.delattr(profiler, "compile_phase_counters")
    run = run_mod.Run({"window_open_wall": 110.0}, None, 1, [], 1.0, 1.0)
    assert all(reader.read(run, {"phase": p}) is None for p in PHASES)
    assert capsys.readouterr().err == ""


@pytest.fixture(scope="module")
def phase_root(tmp_path_factory):
    """A benchmark of one tiny train cell that lists the four metrics."""
    root = str(tmp_path_factory.mktemp("phase_bench"))
    _write(root, "cells/configs/tiny_resnet.json", {
        "driver": "fit_train", "reference": "resnet_v2",
        "control": "bfloat16",
        "num_layers": 8, "num_classes": 10, "image_side": 28,
        "symbol": {"module": "mxnet_tpu.models.resnet",
                   "function": "get_symbol",
                   "kwargs": {"num_classes": 10, "num_layers": 8,
                              "image_shape": "3,28,28"}},
        "optimizer": "sgd",
        "optimizer_params": {"learning_rate": 0.05, "momentum": 0.9,
                             "wd": 0.0001}})
    _write(root, "cells/traffic/tiny_train.json", {
        "kind": "steps", "batch_size": 8, "pool_batches": 3, "warm_steps": 4,
        "trace": {"delay_s": 0.1, "length_s": 0.3},
        "limits": {"grad1_median_gap": 0.002, "change3_median_gap": 0.002,
                   "change3_diff.fc1_weight": 0.01}})
    _write(root, "BENCHMARK.json", {
        "command": ["python3", "benchmark/cells/run.py"], "paths": ["cells"],
        "run_seconds": 1,
        "configs": [{"name": "tiny_resnet",
                     "file": "cells/configs/tiny_resnet.json"}],
        "workloads": [{"name": "t_train", "config": "tiny_resnet",
                       "traffic": "tiny_train", "chips": 1}],
        "end_to_end": [{"name": "train_samples_per_s", "unit": "samples/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "setup_compile_s", "unit": "s"}]
        + [{"name": "setup_%s_s" % p, "unit": "s"} for p in PHASES]})
    return root


def test_traced_train_run_reports_the_four_phases(phase_root, monkeypatch):
    # run.py places the compile cache through the environment; the tests
    # after this one, and the processes they start, must not inherit it
    for name in ("JAX_COMPILATION_CACHE_DIR", "TPU_LOG_DIR",
                 "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                 "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"):
        monkeypatch.setenv(name, "")      # recorded, so restored after
        monkeypatch.delenv(name)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run_mod.main(["--spec-root", phase_root, "--rehearse",
                           "--workload", "t_train", "--seed", "5000000011",
                           "--seconds", "1", "--trace", "1"])
    assert rc == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    got = {p: line["metrics"]["setup_%s_s" % p]["value"] for p in PHASES}
    assert all(v > 0 for v in got.values()), got
    assert line["metrics"]["setup_compile_s"]["value"] > 0
