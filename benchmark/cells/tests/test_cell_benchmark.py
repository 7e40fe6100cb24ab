"""The benchmark's own tests (CPU only, tiny sizes, under a minute together).

(a) ``run.py`` end to end from configuration / traffic files written HERE into
a temporary directory — the proof that a cell is data; (b) the traffic and
load generators; (c) the trace reduction on a hand-made event list; (d) the
FLOPs functions against hand-worked values; (e) an unknown device is an error;
(f) the controls (the reference in a lower precision, put in the program's
place) come out NOT correct; (g) a run whose timed path is broken underneath
comes out NOT correct, once for each fault a cell can have.
"""
import contextlib
import io
import json
import os
import sys

import numpy as np
import pytest

CELLS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CELLS)
import run as run_mod                                   # noqa: E402
from harness import flops, loadgen, peaks, trace, traffic   # noqa: E402
from harness.trace import Event                         # noqa: E402

LENS = {"prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                       "min": 4, "max": 90},
        "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.7,
                       "min": 2, "max": 24}}
ENGINE = {"batch_size": 4, "max_seq_len": 128, "block_size": 8,
          "num_blocks": 65, "prefill_buckets": [16, 32], "prefill_chunk": 32}
TRACE = {"delay_s": 0.1, "length_s": 0.3}


def _write(root, rel, obj):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def spec_root(tmp_path_factory):
    """A whole benchmark of tiny cells, as files: what a later PR would add."""
    root = str(tmp_path_factory.mktemp("tiny_bench"))
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
    _write(root, "cells/configs/tiny_gpt.json", {
        "driver": "decode_serve", "reference": "gpt2",
        # float32 on the CPU here, so the nearest step down that a few
        # hundred tokens can show is an 8-bit float (bfloat16 at full size)
        "control": "float8_e4m3fn",
        "vocab_size": 2048, "n_positions": 128, "n_embd": 64, "n_layer": 2,
        "n_head": 4, "n_inner": 256, "initializer_range": 0.02})
    _write(root, "cells/traffic/tiny_train.json", {
        "kind": "steps", "batch_size": 8, "pool_batches": 3, "warm_steps": 4,
        "trace": TRACE,
        "limits": {"grad1_median_gap": 0.002, "change3_median_gap": 0.002,
                   "change3_diff.fc1_weight": 0.01}})
    _write(root, "cells/layer_metrics/generator_late_p95_ms.json", {
        "reader": "ratio", "args": {"num": ["generator_late_p95_ms"]}})
    _write(root, "cells/traffic/tiny_backlog.json", dict(
        kind="backlog", requests=64, block=16, engine=ENGINE, trace=TRACE,
        check={"sample_requests": 24, "block_requests": 8},
        limits={"served_gap_ratio": 0.01},
        **LENS))
    _write(root, "cells/traffic/tiny_chat.json", dict(
        kind="open_loop", rate_rps=6.0, drain_s=20.0, block=8, engine=ENGINE,
        trace=TRACE, check={"sample_requests": 8, "block_requests": 8},
        limits={"served_gap_ratio": 0.01}, **LENS))
    train, back, chat = ["t_train"], ["t_backlog"], ["t_chat"]
    _write(root, "BENCHMARK.json", {
        "command": ["python3", "benchmark/cells/run.py"], "paths": ["cells"],
        "run_seconds": 1,
        "configs": [
            {"name": "tiny_resnet", "file": "cells/configs/tiny_resnet.json"},
            {"name": "tiny_gpt", "file": "cells/configs/tiny_gpt.json"}],
        "workloads": [
            {"name": "t_train", "config": "tiny_resnet",
             "traffic": "tiny_train", "chips": 1},
            {"name": "t_backlog", "config": "tiny_gpt",
             "traffic": "tiny_backlog", "chips": 1},
            {"name": "t_chat", "config": "tiny_gpt", "traffic": "tiny_chat",
             "chips": 1}],
        "end_to_end": [
            {"name": "train_samples_per_s", "unit": "samples/s",
             "workloads": train},
            {"name": "decode_tok_per_s", "unit": "tokens/s",
             "workloads": back},
            {"name": "ttft_p90_ms", "unit": "ms", "workloads": chat},
            {"name": "itl_p95_ms", "unit": "ms", "workloads": chat},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [
            {"name": "setup_compile_s", "unit": "s"},
            {"name": "generator_late_p95_ms", "unit": "ms",
             "workloads": chat},
            {"name": "decode_batch_fill_pct", "unit": "%",
             "workloads": back},
            {"name": "device_idle_pct.train", "unit": "%",
             "workloads": train}]})
    return root


def run_cell(spec_root, cell, seed=3, seconds=1.0, trace_on=0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run_mod.main(["--spec-root", spec_root, "--rehearse",
                           "--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds),
                           "--trace", str(trace_on)])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def make_driver(spec_root, cell, seed, seconds):
    import argparse
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0, spec_root=spec_root, rehearse=True)
    spec, _, ctx, _ = run_mod.prepare(args)
    return spec.module("drivers", ctx.config["driver"]).Driver(ctx), ctx


# ---------------------------------------------------------------- (a) ----

LAST_LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_train_cell_end_to_end_from_files(spec_root):
    line = run_cell(spec_root, "t_train", seed=5000000011)
    assert LAST_LINE_KEYS <= set(line)
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert line["metrics"]["train_samples_per_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert set(line["compared"]) == {"compiles_in_window", "grad1_median_gap",
                                     "change3_median_gap",
                                     "change3_diff.fc1_weight"}


def test_chat_cell_traced_run_reports_per_layer_metrics(spec_root):
    line = run_cell(spec_root, "t_chat", seed=12, seconds=2.0, trace_on=1)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # a reader with nothing to read (no device plane on the CPU) is left out
    assert set(line["metrics"]) == {"setup_compile_s",
                                    "generator_late_p95_ms"}
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_run_refuses_the_cpu_without_rehearse(spec_root):
    with pytest.raises(SystemExit) as e:
        run_mod.main(["--spec-root", spec_root, "--workload", "t_train",
                      "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert "not 'tpu'" in str(e.value)


# ---------------------------------------------------------------- (b) ----

def test_requests_repeat_from_a_seed_and_share_one_multiset():
    t = dict(kind="open_loop", rate_rps=5.0, block=8, **LENS)
    a = traffic.make_requests(t, 100, 2 ** 31 + 7, 10.0)
    b = traffic.make_requests(t, 100, 2 ** 31 + 7, 10.0)
    c = traffic.make_requests(t, 100, 99, 10.0)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert [r.due_s for r in a] != [r.due_s for r in c]
    # whole blocks hold the same lengths whatever the seed
    assert sorted(len(r.prompt) for r in a[:8]) == \
        sorted(len(r.prompt) for r in c[:8])
    assert all(0 <= r.due_s < 10.0 for r in a)
    assert abs(len(a) - 50) <= 8 and abs(len(a) - len(c)) <= 8


def test_iid_arrivals_are_a_poisson_process_from_the_seed():
    t = dict(kind="open_loop", rate_rps=5.0, block=8,
             arrivals={"sample": "iid"}, **LENS)
    t["prompt_len"] = dict(t["prompt_len"], sample="iid")
    a = traffic.make_requests(t, 100, 11, 20.0)
    b = traffic.make_requests(t, 100, 11, 20.0)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    counts = [len(traffic.make_requests(t, 100, s, 20.0)) for s in range(40)]
    # Poisson: the count's variance is its mean (100); stratified gaps in
    # blocks of 8 would hold every count within a few of 100
    assert 85 < np.mean(counts) < 115 and 5 < np.std(counts) < 16
    lens = {len(r.prompt) for s in range(5)
            for r in traffic.make_requests(t, 100, s, 20.0)}
    assert len(lens) > 40 and min(lens) >= 4 and max(lens) <= 90


def test_a_split_metric_shares_one_file(spec_root):
    from harness.spec import Spec, SpecError
    spec = Spec(spec_root)
    assert spec.layer_metric("device_idle_pct.train") == \
        spec.layer_metric("device_idle_pct")
    assert spec.layer_metric("step_device_ms.train") != \
        spec.layer_metric("step_device_ms.decode")
    with pytest.raises(SpecError):
        spec.layer_metric("no_such_metric.train")


def test_latency_counts_from_the_due_time_and_lateness_is_reported():
    reqs = [traffic.Request(0, 1.0, np.zeros(3, np.int32), 4),
            traffic.Request(1, 2.0, np.zeros(3, np.int32), 4)]
    reqs[0].sent_s, reqs[0].token_s = 1.25, [1.5, 1.6, 1.8]
    reqs[1].sent_s = 2.0                         # never answered
    assert loadgen.ttft_ms(reqs) == [pytest.approx(500.0), float("inf")]
    assert loadgen.lateness_ms(reqs) == [pytest.approx(250.0), 0.0]
    assert loadgen.inter_token_ms(reqs) == [pytest.approx(100.0),
                                            pytest.approx(200.0)]
    assert traffic.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90) == 9
    assert traffic.percentile([3.0, float("inf")], 90) == float("inf")


def test_open_loop_sends_at_the_due_times():
    import time
    reqs = [traffic.Request(i, 0.05 * i, np.zeros(1, np.int32), 1)
            for i in range(4)]
    t0 = time.monotonic()
    loadgen.send_all(reqs, lambda r: None, t0, lambda n: contextlib.nullcontext())
    assert all(0 <= r.sent_s - r.due_s < 0.05 for r in reqs)


# ---------------------------------------------------------------- (c) ----

DEV, HOST = "/device:TPU:0", "/host:CPU"
EVENTS = [
    Event(DEV, "XLA Modules", "jit_step(1)", -150, 150),  # cut by the edge
    Event(DEV, "XLA Modules", "jit_step(1)", 0, 400),
    Event(DEV, "XLA Modules", "jit_step(1)", 1000, 400),
    Event(DEV, "XLA Modules", "jit_prefill_fn(2)", 500, 100),
    Event(DEV, "XLA Ops", "fusion.1", 0, 300),
    Event(DEV, "XLA Ops", "fusion.2", 200, 200),      # overlaps fusion.1
    Event(DEV, "XLA Ops", "copy.3", 500, 100),
    Event(DEV, "XLA Ops", "fusion.1", 1000, 400),
    Event(HOST, "python", "bench.submit", 390, 100),
    Event(HOST, "python", "bench.iter_next", 600, 420),
    Event(HOST, "python", "unrelated", 0, 2000),
]


def test_trace_reduction_on_a_hand_made_event_list():
    assert trace.device_planes(EVENTS) == [DEV]
    assert trace.union_ns([(0, 300), (200, 400), (500, 600)]) == 500
    assert trace.busy_seconds(EVENTS) == pytest.approx(900e-9)
    assert trace.module_times(EVENTS, r"^jit_step\(") == \
        [pytest.approx(150e-9), pytest.approx(400e-9), pytest.approx(400e-9)]
    assert trace.module_times(EVENTS, "no_such_program") == []
    from harness.spec import Spec
    reader = Spec(None).module("readers", "trace_module_ms")
    run = run_mod.Run({}, None, 1, EVENTS, None, None)
    assert reader.read(run, {"pattern": "jit_step"}) == pytest.approx(400e-6)
    assert reader.read(run, {"pattern": "no_such_program"}) is None
    assert trace.top_device_ops(EVENTS, 2)[0] == ("fusion.1",
                                                  pytest.approx(700e-9))
    gaps = dict(trace.idle_gaps(EVENTS))
    assert gaps == {"bench.submit": pytest.approx(100e-9),
                    "bench.iter_next": pytest.approx(400e-9)}
    assert trace.busy_seconds([e for e in EVENTS if e.plane == HOST]) is None


# ---------------------------------------------------------------- (d) ----

def test_flops_against_hand_worked_values():
    # conv0 of ResNet-50: 7x7, 3 -> 64, 112x112 out: 2*64*112*112*3*49
    assert flops.conv_flops(1, 3, 64, (7, 7), (112, 112)) == 236027904
    assert flops.dense_flops(256, 2048, 1000) == 2 * 256 * 2048 * 1000
    # 4 query rows at offset 2 over 8 keys, causal: 3+4+5+6 pairs
    assert flops.attention_flops(4, 8, 12, 64, causal_offset=2) == \
        4 * 18 * 12 * 64
    assert flops.attention_flops(4, 8, 12, 64) == 4 * 32 * 12 * 64
    layers = [dict(kind="conv", c_in=3, c_out=64, kernel=(7, 7),
                   out_hw=(112, 112)),
              dict(kind="dense", d_in=2048, d_out=1000)]
    assert flops.train_flops_per_sample(layers) == \
        3 * (236027904 + 2 * 2048 * 1000)
    assert flops.decoder_flops_per_token(12, 768, 3072, 100) == \
        12 * (8 * 768 * 768 + 4 * 768 * 3072 + 4 * 100 * 768)


def test_resnet50_reference_counts_the_published_operations(spec_root):
    from harness.spec import Spec
    ref = Spec(spec_root).module("references", "resnet_v2")
    cfg = {"num_layers": 50, "image_side": 224, "num_classes": 1000}
    per_sample = flops.train_flops_per_sample(ref.matrix_layers(cfg))
    assert per_sample == pytest.approx(24.5e9, rel=0.01)   # ~4.1 GMAC fwd
    args, aux = ref.param_shapes(cfg)
    assert sum(int(np.prod(s)) for s in args.values()) == 25549486


# ---------------------------------------------------------------- (e) ----

def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9000")


# ---------------------------------------------------------------- (f) ----

def test_training_control_in_lower_precision_is_not_correct(spec_root):
    driver, ctx = make_driver(spec_root, "t_train", seed=21, seconds=0.2)
    driver.run()
    driver.release()
    assert all(c.ok for c in driver.check()), \
        [(c.name, c.value) for c in driver.check()]
    control = driver.check(quant=ctx.reference.Precision(
        ctx.config["control"]))
    assert not all(c.ok for c in control), \
        [(c.name, c.value) for c in control]


def test_decode_control_in_lower_precision_is_not_correct(spec_root):
    driver, ctx = make_driver(spec_root, "t_backlog", seed=22, seconds=2.0)
    driver.run()
    driver.release()
    assert all(c.ok for c in driver.check())
    control = driver.check(control_in_place=True)
    assert not all(c.ok for c in control), \
        [(c.name, c.value) for c in control]
    assert control[-1].value == pytest.approx(1.0)


# ---------------------------------------------------------------- (g) ----

def test_fault_step_returns_its_state_unchanged(spec_root, monkeypatch):
    from mxnet_tpu.parallel.tpu_step import DataParallelTrainStep
    real = DataParallelTrainStep.__call__

    def frozen(self, *a, **k):
        import jax
        import jax.numpy as jnp
        params, state = jax.tree_util.tree_map(
            jnp.copy, (self.params, self.opt_state))    # the step donates
        outs = real(self, *a, **k)
        self.params, self.opt_state = params, state
        return outs

    monkeypatch.setattr(DataParallelTrainStep, "__call__", frozen)
    line = run_cell(spec_root, "t_train", seed=31, seconds=0.2)
    assert line["correct"] is False
    assert line["compared"]["change3_median_gap"]["value"] == \
        pytest.approx(1.0)


def test_fault_the_update_goes_the_wrong_way(spec_root, monkeypatch):
    """Every norm is right and every leaf moves: only the number that
    carries direction sees it."""
    from mxnet_tpu.parallel.tpu_step import DataParallelTrainStep
    real = DataParallelTrainStep.__call__

    def mirrored(self, *a, **k):
        import jax
        import jax.numpy as jnp
        before = jax.tree_util.tree_map(jnp.copy, self.params)
        outs = real(self, *a, **k)
        self.params = jax.tree_util.tree_map(
            lambda b, p: 2 * b - p, before, self.params)
        return outs

    monkeypatch.setattr(DataParallelTrainStep, "__call__", mirrored)
    line = run_cell(spec_root, "t_train", seed=34, seconds=0.2)
    assert line["correct"] is False
    assert line["compared"]["change3_diff.fc1_weight"]["value"] > 1.0
    assert line["compared"]["change3_median_gap"]["value"] < 0.5


def test_fault_half_of_the_batch_left_out(spec_root, monkeypatch):
    import mxnet_tpu as mx
    real = mx.io.DataBatch

    def halved(data, label, **kw):
        half = data[0].shape[0] // 2
        x, y = data[0].copy(), label[0].copy()
        x[half:], y[half:] = x[:half], y[:half]     # mean over the rest
        return real(data=[x], label=[y], **kw)

    monkeypatch.setattr(mx.io, "DataBatch", halved)
    line = run_cell(spec_root, "t_train", seed=32, seconds=0.2)
    assert line["correct"] is False, line["compared"]


def test_fault_a_token_altered_where_it_is_produced(spec_root, monkeypatch):
    from mxnet_tpu.serving.decode import DecodeStream
    real = DecodeStream._emit

    def altered(self, token):
        if len(self.tokens) == 2:
            token = (token + 1) % 2048
        return real(self, token)

    monkeypatch.setattr(DecodeStream, "_emit", altered)
    line = run_cell(spec_root, "t_backlog", seed=33, seconds=2.0)
    assert line["correct"] is False
    assert line["compared"]["served_gap_ratio"]["value"] > 0.01
