"""The program's own host spans (`mx.profiler.span`, docs/faq/perf.md) and the
benchmark readers that turn them into per-layer metrics.

(a) a tiny DecodeEngine under `jax.profiler.start_trace`: every `mx.decode.*`
name is in the `.xplane.pb`, children lie inside parents, the leaves tile each
pass; (b) a tiny `fit(kvstore='tpu_sync')`: every `mx.fit.*` / `mx.prefetch.*`
name, one dispatch span per step; (c) the profiler being on does not make the
fused step synchronous; (d) `gap_under_span_pct` and (e) `span_ms` on
hand-made event lists; (f) a recompile shows as one `mx.compile` with its site.
"""
import os
import sys
import time
from collections import namedtuple

import jax
import numpy as np
import pytest

import mxnet_tpu as mx

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), *[".."] * 3))
CELLS = os.path.join(REPO, "benchmark", "cells")
sys.path.insert(0, CELLS)
import run as run_mod                                   # noqa: E402
from harness.spec import Spec                           # noqa: E402
from harness.trace import Event                         # noqa: E402

Span = namedtuple("Span", "thread name start end attrs")


@pytest.fixture(scope="module")
def readers():
    spec = Spec(None)
    return {name: spec.module("readers", name)
            for name in ("gap_under_span_pct", "span_ms")}


def Run(events, window_s):
    """What `benchmark/cells/run.py` hands a reader."""
    return run_mod.Run({}, None, 1, events, None, window_s)


def traced(tmp, work):
    """Runs `work()` under a profiler session (host spans only: the Python
    tracer would add a second event to every call) and returns the `mx.*`
    spans, each with its thread (the line's index) and attributes."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    from harness import trace
    data = jax.profiler.ProfileData.from_file(trace.find_xplane(str(tmp)))
    spans = []
    for plane in data.planes:
        for tid, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("mx."):
                    spans.append(Span(
                        (plane.name, tid), ev.name, float(ev.start_ns),
                        float(ev.start_ns + ev.duration_ns), dict(ev.stats)))
    return spans


def inside(child, parents):
    return any(p.thread == child.thread and p.start <= child.start
               and child.end <= p.end for p in parents)


# ---------------------------------------------------------------- (a) ----

DECODE_NAMES = {
    "mx.decode.submit", "mx.decode.iteration", "mx.decode.wait",
    "mx.decode.admit", "mx.decode.prefill", "mx.decode.prefill.dispatch",
    "mx.decode.prefill.readback", "mx.decode.step", "mx.decode.step.grow",
    "mx.decode.step.pack", "mx.decode.step.dispatch",
    "mx.decode.step.readback", "mx.decode.step.emit"}
PARENT_OF = {"mx.decode.wait": "mx.decode.admit",
             "mx.decode.admit": "mx.decode.iteration",
             "mx.decode.prefill": "mx.decode.iteration",
             "mx.decode.step": "mx.decode.iteration",
             "mx.decode.prefill.dispatch": "mx.decode.prefill",
             "mx.decode.prefill.readback": "mx.decode.prefill",
             "mx.decode.step.grow": "mx.decode.step",
             "mx.decode.step.pack": "mx.decode.step",
             "mx.decode.step.dispatch": "mx.decode.step",
             "mx.decode.step.readback": "mx.decode.step",
             "mx.decode.step.emit": "mx.decode.step"}


@pytest.fixture(scope="module")
def decode_spans(tmp_path_factory):
    from mxnet_tpu.models.transformer import (TransformerConfig,
                                              TransformerDecodeModel)
    from mxnet_tpu.serving.decode import DecodeEngine
    # wide enough that a pass takes milliseconds on the CPU: the few
    # microseconds between two spans are then well under a twentieth
    cfg = TransformerConfig(vocab_size=1024, num_layers=4, num_heads=4,
                            d_model=256, max_len=128)
    model = TransformerDecodeModel(cfg, flash="0")
    eng = DecodeEngine(**model.engine_kwargs(), max_seq_len=128, batch_size=4,
                       block_size=8, num_blocks=65, prefill_buckets=(16, 32),
                       prefill_chunk=32, name="spans")
    eng.generate([1, 2, 3], 2)              # every program compiled

    def work():
        for burst in range(2):              # the pause between: a wait
            streams = [eng.submit(list(range(1, 6 + 9 * i)), 5)
                       for i in range(6)]   # the longest: two chunks
            for s in streams:
                s.result_wait(60)
            time.sleep(0.12)
    try:
        spans = traced(tmp_path_factory.mktemp("decode_trace"), work)
    finally:
        eng.stop()
    return spans


def test_decode_trace_holds_every_span_of_the_table(decode_spans):
    assert {s.name for s in decode_spans
            if s.name.startswith("mx.decode.")} == DECODE_NAMES
    by = {}
    for s in decode_spans:
        by.setdefault(s.name, []).append(s)
    assert len(by["mx.decode.submit"]) == 12
    assert {s.attrs["rid"] for s in by["mx.decode.submit"]} == \
        {s.attrs["rid"] for s in by["mx.decode.prefill"]}
    assert max(s.attrs["pieces"] for s in by["mx.decode.prefill"]) == 2
    assert {s.attrs["bucket"] for s in by["mx.decode.prefill.dispatch"]} \
        <= {16, 32}
    # the pass that was waiting when the session began is not in it
    assert sum(s.attrs["admitted"] for s in by["mx.decode.admit"]) in (8, 12)
    assert sum(s.attrs["retired"] for s in by["mx.decode.step.emit"]) == 12
    # one span per boundary per pass, never one per token or row
    assert len(by["mx.decode.step.emit"]) == len(by["mx.decode.step.pack"]) \
        == len(by["mx.decode.step.dispatch"])
    assert len(by["mx.decode.step"]) == len(by["mx.decode.step.grow"])
    # the engine's family lives on one thread, submit on the caller's
    engine = {s.thread for s in decode_spans
              if s.name.startswith("mx.decode.") and
              s.name != "mx.decode.submit"}
    assert len(engine) == 1
    assert {s.thread for s in by["mx.decode.submit"]}.isdisjoint(engine)


def test_decode_children_lie_inside_their_parents(decode_spans):
    by = {}
    for s in decode_spans:
        by.setdefault(s.name, []).append(s)
    # the pass that was open when the session began is not in the trace
    # (its children are): look from the first whole one on
    t0 = min(s.start for s in by["mx.decode.iteration"])
    for child, parent in PARENT_OF.items():
        for s in by[child]:
            assert s.start < t0 or inside(s, by[parent]), (child, s)


def test_decode_leaves_tile_each_iteration(decode_spans, readers):
    """Outside `mx.decode.wait`, at most a twentieth of a pass lies under a
    parent alone (`iteration`, `step`): a device gap in it has a leaf's name."""
    segments = readers["gap_under_span_pct"].innermost_segments
    engine = [Event("/host:CPU", "python3", s.name, s.start, s.end - s.start)
              for s in decode_spans if s.name.startswith("mx.decode.")
              and s.name != "mx.decode.submit"]
    segs = segments(engine)
    assert all(a[1] <= b[0] for a, b in zip(segs, segs[1:]))   # disjoint
    parents = ("mx.decode.iteration", "mx.decode.step")
    busy_all = bare_all = 0.0
    dispatched = tiled = 0
    for it in (e for e in engine if e.name == "mx.decode.iteration"):
        mine = [(s, e, n) for s, e, n in segs
                if it.start_ns <= s and e <= it.start_ns + it.dur_ns]
        assert sum(e - s for s, e, _ in mine) == pytest.approx(it.dur_ns)
        busy = sum(e - s for s, e, n in mine if n != "mx.decode.wait")
        bare = sum(e - s for s, e, n in mine if n in parents)
        busy_all, bare_all = busy_all + busy, bare_all + bare
        if any(n.endswith(".dispatch") for _, _, n in mine):
            dispatched += 1
            tiled += bare <= 0.05 * busy
    # every pass but those the scheduler tore a hole in (a loaded test
    # machine takes the thread away between two spans now and then)
    assert dispatched >= 10 and tiled >= 0.8 * dispatched, (tiled, dispatched)
    assert bare_all <= 0.05 * busy_all


# ---------------------------------------------------------------- (b) ----

def _mlp():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _toy_iter(n=256):
    rng = np.random.RandomState(0)
    X = rng.normal(0, 1, (n, 10)).astype(np.float32)
    y = rng.randint(0, 4, (n,)).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=32, label_name="softmax_label")


FIT_NAMES = {"mx.fit.next_batch", "mx.fit.step.dispatch",
             "mx.fit.step.retire", "mx.fit.metric", "mx.fit.callback",
             "mx.prefetch.fetch", "mx.prefetch.stage", "mx.prefetch.put"}


@pytest.fixture(scope="module")
def fit_spans(tmp_path_factory):
    mx.random.seed(7)
    mod = mx.mod.Module(_mlp(), context=[mx.tpu(0)])
    seen = []

    def work():
        mod.fit(_toy_iter(), num_epoch=1, kvstore="tpu_sync",
                initializer=mx.init.Xavier(), optimizer="sgd",
                optimizer_params={"learning_rate": 0.05},
                batch_end_callback=lambda p: seen.append(p.nbatch))
    spans = traced(tmp_path_factory.mktemp("fit_trace"), work)
    assert mod._fused_step is not None and seen == list(range(8))
    return spans


def test_fit_trace_holds_every_span_and_one_dispatch_per_step(fit_spans):
    names = {s.name for s in fit_spans}
    assert FIT_NAMES <= names, FIT_NAMES - names
    by = {}
    for s in fit_spans:
        by.setdefault(s.name, []).append(s)
    dispatch = sorted(by["mx.fit.step.dispatch"], key=lambda s: s.start)
    assert [s.attrs["step"] for s in dispatch] == list(range(8))
    assert len(by["mx.fit.metric"]) == len(by["mx.fit.callback"]) == 8
    # 8 batches and the end of the epoch
    waits = sorted(by["mx.fit.next_batch"], key=lambda s: s.start)
    assert len(waits) == 9 and all("hit" in s.attrs for s in waits[:8])
    assert len(by["mx.prefetch.stage"]) == 8
    assert {s.attrs["bytes"] for s in by["mx.prefetch.stage"]} == \
        {32 * 10 * 4 + 32 * 4}
    # the stager is a thread of its own: the two families do not share one
    fit_threads = {s.thread for s in fit_spans if s.name.startswith("mx.fit.")}
    stager = {s.thread for s in fit_spans
              if s.name.startswith("mx.prefetch.")}
    assert len(fit_threads) == 1 and len(stager) == 1
    assert fit_threads != stager
    # bounded async dispatch (depth 2): six of the eight steps retire one
    assert len(by["mx.fit.step.retire"]) == 6


def test_fit_trace_names_the_compile_and_its_site(fit_spans):
    compiles = [s for s in fit_spans if s.name == "mx.compile"]
    assert [s.attrs["site"] for s in compiles] == ["train.fused_step"]
    assert compiles[0].attrs["aot"] == 1
    assert compiles[0].attrs["persistent_hit"] in (0, 1)


# ---------------------------------------------------------------- (c) ----

def test_profiler_on_keeps_the_fused_step_asynchronous(tmp_path, monkeypatch):
    """`mx.profiler` running feeds the aggregate table its fused-step rows
    from the host's enqueue time and waits for nothing: a profiled fit is
    the fit that is not profiled."""
    it = _toy_iter()
    mod = mx.mod.Module(_mlp(), context=[mx.tpu(0), mx.tpu(1)])
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05})
    batches = list(it)
    mod.forward(batches[0], is_train=True)
    mod.update()
    mod._dispatch_depth = 16            # no step retires in what follows
    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (waits.append(1), real(x))[1])
    mx.profiler.set_config(filename=str(tmp_path / "profile.json"))
    mx.profiler.set_state("run")
    try:
        for batch in batches[1:4]:
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
    finally:
        mx.profiler.set_state("stop")
    rows = [e for e in mx.profiler._state["events"]
            if e["name"] == "tpu_sync_fused_step"]
    mx.profiler._state["events"] = []
    assert len(rows) == 3 and all(e["dur"] > 0 for e in rows)
    assert waits == []


def test_span_without_a_session_records_nothing_and_costs_little():
    t0 = time.perf_counter()
    for i in range(10000):
        with mx.profiler.span("mx.test.off", n=i) as sp:
            sp.set_metadata(hit=True)
    assert (time.perf_counter() - t0) / 10000 < 50e-6
    assert not hasattr(mx.profiler, "record_event")


# ---------------------------------------------------------------- (d) ----

DEV, HOST = "/device:TPU:0", "/host:CPU"
# device busy 0-100, 200-300, 350-400, 900-1000: gaps 100-200, 300-350,
# 400-900 = 650 of a window of 1000
OPS = [Event(DEV, "XLA Ops", "fusion.%d" % i, s, d)
       for i, (s, d) in enumerate([(0, 100), (200, 100), (350, 50),
                                   (900, 100)])]
ENGINE = [
    Event(HOST, "python3", "mx.decode.iteration", 50, 500),      # 50-550
    Event(HOST, "python3", "mx.decode.admit", 60, 80),           # 60-140
    Event(HOST, "python3", "mx.decode.step", 150, 380),          # 150-530
    Event(HOST, "python3", "mx.decode.step.grow", 150, 30),      # 150-180
    Event(HOST, "python3", "mx.decode.step.dispatch", 190, 20),  # 190-210
    Event(HOST, "python3", "mx.decode.step.readback", 210, 200),  # 210-410
    Event(HOST, "python3", "mx.decode.step.emit", 420, 100),     # 420-520
    Event(HOST, "python3", "mx.decode.iteration", 560, 400),     # 560-960
    Event(HOST, "python3", "mx.decode.admit", 560, 300),         # 560-860
    Event(HOST, "python3", "mx.decode.wait", 600, 200),          # 600-800
]
# another thread's family covers everything and must get nothing
OTHERS = [Event(HOST, "python3", "mx.decode.submit", 0, 1000),
          Event(HOST, "python3", "mx.fit.next_batch", 0, 1000),
          Event(HOST, "python3", "$decode.py:718 _decode_step", 0, 1000)]
LEAVES = ["mx.decode.admit", "mx.decode.step.emit", "mx.decode.step.grow",
          "mx.decode.step.pack", "mx.decode.step.dispatch",
          "mx.decode.step.readback", "mx.decode.prefill",
          "mx.decode.prefill.dispatch", "mx.decode.prefill.readback"]
EXPECTED = {    # nanoseconds of gap under each innermost span
    "mx.decode.admit": 40 + 40 + 60,        # 100-140; 560-600, 800-860
    "mx.decode.step.grow": 30,              # 150-180
    "mx.decode.step.dispatch": 10,          # 190-200
    "mx.decode.step.readback": 50 + 10,     # 300-350, 400-410
    "mx.decode.step.emit": 100,             # 420-520
    "mx.decode.step": 10 + 10 + 10,         # 180-190, 410-420, 520-530
    "mx.decode.iteration": 10 + 20 + 40,    # 140-150, 530-550, 860-900
    "mx.decode.wait": 200,                  # 600-800
    None: 10,                               # 550-560: between two passes
}


def test_gap_reader_partitions_each_gap_by_the_innermost_span(readers):
    mod = readers["gap_under_span_pct"]
    events = OPS + ENGINE + OTHERS
    assert mod.device_gaps(events) == [(100, 200), (300, 350), (400, 900)]
    got = mod.gap_seconds_by_span(events)
    assert {k: round(v * 1e9) for k, v in got.items() if v} == EXPECTED
    assert sum(got.values()) * 1e9 == pytest.approx(650)


def test_gap_reader_parts_add_up_to_the_idle_share(readers):
    mod = readers["gap_under_span_pct"]
    run = Run(OPS + ENGINE + OTHERS, 1000e-9)
    parts = {
        "admit": ["mx.decode.admit"], "emit": ["mx.decode.step.emit"],
        "grow": ["mx.decode.step.grow"],
        "step_host": ["mx.decode.step.pack", "mx.decode.step.dispatch",
                      "mx.decode.step.readback"],
        "prefill_host": ["mx.decode.prefill", "mx.decode.prefill.dispatch",
                         "mx.decode.prefill.readback"]}
    got = {k: mod.read(run, {"spans": v}) for k, v in parts.items()}
    got["unnamed"] = mod.read(run, {"spans": None, "leaves": LEAVES})
    assert got == {"admit": pytest.approx(14.0), "emit": pytest.approx(10.0),
                   "grow": pytest.approx(3.0),
                   "step_host": pytest.approx(7.0), "prefill_host": 0.0,
                   "unnamed": pytest.approx(31.0)}
    idle_pct = 100.0 * (1 - 350 / 1000)     # what trace_idle_pct reads
    assert sum(got.values()) == pytest.approx(idle_pct)


def test_gap_reader_finds_nothing_to_read_without_the_spans(readers):
    """The parent of the PR that brought the spans has none: every part is
    left out of the line, none raises."""
    mod = readers["gap_under_span_pct"]
    run = Run(OPS + OTHERS, 1000e-9)
    assert mod.read(run, {"spans": ["mx.decode.admit"]}) is None
    assert mod.read(run, {"spans": None, "leaves": LEAVES}) is None
    assert mod.read(Run(None, None), {"spans": None}) is None


def test_gap_reader_clock_check_measures_how_far_a_module_sticks_out(
        readers, capsys):
    mod = readers["gap_under_span_pct"]
    mods = [Event(DEV, "XLA Modules", "jit_step_fn(1)", s, d)
            for s, d in [(-50, 60), (195, 150), (1195, 150), (2000, 10)]]
    host = [Event(HOST, "python3", "mx.decode.step.dispatch", 190, 20),
            Event(HOST, "python3", "mx.decode.step.readback", 210, 200),
            Event(HOST, "python3", "mx.decode.step.dispatch", 1200, 20),
            Event(HOST, "python3", "mx.decode.step.readback", 1220, 120)]
    # second whole execution starts 5 before its dispatch, ends 5 after
    worst, n = mod.clock_excess_ms(mods + host, "jit_step_fn")
    assert n == 2 and worst == pytest.approx(10e-6)
    run = Run(OPS + ENGINE + mods + host, 1000e-9)
    mod.read(run, {"spans": ["mx.decode.admit"],
                   "clock_check": "jit_step_fn"})
    assert '"event": "clock_check"' in capsys.readouterr().err


# ---------------------------------------------------------------- (e) ----

def test_span_ms_reads_the_median_and_none(readers):
    mod = readers["span_ms"]
    events = [Event(HOST, "python3", "mx.prefetch.stage", 0, d)
              for d in (1e6, 9e6, 2e6)] + \
             [Event(HOST, "python3", "mx.prefetch.stage.x", 0, 7e6),
              Event(DEV, "XLA Ops", "mx.prefetch.stage", 0, 50e6)]
    run = Run(events, 1.0)
    assert mod.read(run, {"span": "mx.prefetch.stage"}) == pytest.approx(2.0)
    assert mod.read(run, {"span": "mx.fit.next_batch"}) is None
    assert mod.read(Run(None, None), {"span": "mx.prefetch.stage"}) is None


def test_new_per_layer_metrics_are_files_the_harness_finds():
    spec = Spec(None)
    new = [m for m in spec.benchmark["per_layer"]
           if m["name"].startswith(("decode_gap_pct.", "prefetch_", "fit_"))]
    assert len(new) == 9
    for m in new:
        lm = spec.layer_metric(m["name"])
        assert hasattr(spec.module("readers", lm["reader"]), "read")
        assert m["workloads"] and m["source"] in ("device_trace",
                                                  "program_span")
    leaves = spec.layer_metric("decode_gap_pct.unnamed")["args"]["leaves"]
    named = [s for m in new if m["name"].startswith("decode_gap_pct.")
             for s in spec.layer_metric(m["name"])["args"]["spans"] or []]
    assert sorted(named) == sorted(leaves)      # six parts, one whole


# ---------------------------------------------------------------- (f) ----

def test_a_forced_recompile_shows_one_compile_span_with_its_site(tmp_path):
    import jax.numpy as jnp
    from mxnet_tpu.compile.builder import ProgramBuilder
    b = ProgramBuilder(lambda x: x * 2 + 1, site="test.recompile")
    b(jnp.ones((4,)))                                   # compiled outside

    def work():
        b(jnp.ones((4,)))                               # cached: no span
        b(jnp.ones((8,)))                               # new shape: compiles
    spans = [s for s in traced(tmp_path, work) if s.name == "mx.compile"]
    assert [(s.attrs["site"], s.attrs["aot"]) for s in spans] == \
        [("test.recompile", 0)]
