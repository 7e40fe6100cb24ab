"""Profiler aggregate stats (reference: src/profiler/aggregate_stats.cc
table dump + python/mxnet/profiler.py dumps()), asserted output.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler


def test_per_op_aggregate_table(tmp_path):
    profiler.set_config(filename=str(tmp_path / "p.json"),
                        profile_symbolic=True, profile_imperative=True)
    profiler.set_state("run")
    a = mx.nd.array(np.ones((16, 16), np.float32))
    for _ in range(3):
        b = mx.nd.dot(a, a)
    c = mx.nd.relu(b)
    c.wait_to_read()
    profiler.set_state("stop")
    table = profiler.dumps()
    assert "Total Count" in table and "Avg Time" in table
    assert "dot" in table
    assert "relu" in table
    # dot ran 3 times
    dot_line = [l for l in table.splitlines() if l.startswith("dot")][0]
    assert int(dot_line.split()[1]) == 3


def test_executor_events_and_chrome_dump(tmp_path):
    fname = str(tmp_path / "exec.json")
    profiler.set_config(filename=fname)
    profiler.dumps(reset=True)  # clear prior events
    profiler.set_state("run")
    x = mx.sym.Variable("x")
    net = mx.sym.make_loss(mx.sym.sum(2 * x))
    ex = net.simple_bind(mx.cpu(), x=(4, 4))
    ex.arg_dict["x"][:] = np.ones((4, 4), np.float32)
    ex.forward(is_train=True)
    ex.backward()
    profiler.set_state("stop")
    table = profiler.dumps()
    assert "graph_forward_backward" in table
    profiler.dump()
    with open(fname) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "graph_forward_backward" in names
    profiler.dumps(reset=True)


def test_profiler_off_records_nothing():
    profiler.dumps(reset=True)
    a = mx.nd.array(np.ones((4, 4), np.float32))
    (a + a).wait_to_read()
    table = profiler.dumps()
    assert "_plus" not in table and "elemwise_add" not in table


def test_kernel_roofline_counters():
    """record_kernel_roofline/kernel_counters (ISSUE 6): always-on (no
    profiler session), ratio derived not stored (re-record with a better
    measurement stays self-consistent), reset clears."""
    profiler.kernel_counters(reset=True)
    profiler.record_kernel_roofline("opt_update", 715.4, 511.0,
                                    unit="bytes_mb")
    snap = profiler.kernel_counters()
    assert snap["opt_update"]["measured_vs_ideal"] == round(715.4 / 511.0, 4)
    assert snap["opt_update"]["unit"] == "bytes_mb"
    # re-record wins wholesale
    profiler.record_kernel_roofline("opt_update", 516.0, 511.0,
                                    unit="bytes_mb")
    assert profiler.kernel_counters()["opt_update"]["measured"] == 516.0
    # zero ideal never divides
    profiler.record_kernel_roofline("degenerate", 1.0, 0.0)
    assert profiler.kernel_counters()["degenerate"]["measured_vs_ideal"] is None
    assert profiler.kernel_counters(reset=True)
    assert not profiler.kernel_counters()


# ----------------------------------------------------------------------
# compile-phase counters: the wall time of every compile's trace, lowering
# and backend compile-or-load, and the package's import
# ----------------------------------------------------------------------
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"

# (event, start, end, fun_name) as jax.monitoring hands them over: an outer
# trace that holds two inner ones, a trace that overlaps it from another
# thread, and one apart
NESTED = [(TRACE, 12.0, 14.0, "inner"), (TRACE, 13.0, 15.0, "inner"),
          (TRACE, 10.0, 20.0, "outer"), (TRACE, 18.0, 25.0, "other"),
          (TRACE, 30.0, 31.0, "apart")]


@pytest.mark.parametrize("order", [(0, 1, 2, 3, 4), (4, 3, 2, 1, 0),
                                   (2, 4, 0, 3, 1)])
def test_compile_phases_count_nested_and_overlapping_time_once(order):
    phases = profiler.CompilePhases()
    for i in order:
        event, start, end, name = NESTED[i]
        phases.listener(event, start, end, fun_name=name)
    phases.listener("/jax/some/other_event", 0.0, 100.0, fun_name="x")
    snap = phases.snapshot(top=10)
    # the union [10, 25] + [30, 31], not the sum of the five (22 s)
    assert snap["trace_s"] == pytest.approx(16.0)
    assert snap["lower_s"] == snap["backend_s"] == 0.0
    assert snap["events"] == {"trace": 5, "lower": 0, "backend": 0}
    assert snap["top"]["trace"] == [["outer", 10.0], ["other", 7.0],
                                    ["inner", 4.0], ["apart", 1.0]]
    assert snap["import_s"] is None
    # an interval that bridges two stretches joins them
    phases.listener(TRACE, 24.0, 30.5, fun_name="bridge")
    assert phases.snapshot()["trace_s"] == pytest.approx(21.0)


def test_compile_phases_before_leaves_out_intervals_that_end_later():
    phases = profiler.CompilePhases()
    phases.listener(TRACE, 0.0, 10.0, fun_name="setup")
    phases.listener(LOWER, 2.0, 4.0, fun_name="jit_setup")
    phases.listener(BACKEND, 20.0, 30.0, fun_name="check")
    phases.listener(TRACE, 20.0, 26.0, fun_name="check")
    # one function compiled in set-up and again after the cut
    phases.listener(BACKEND, 5.0, 7.0, fun_name="step")
    phases.listener(BACKEND, 31.0, 34.0, fun_name="step")
    snap = phases.snapshot(before=15.0)
    assert (snap["trace_s"], snap["lower_s"], snap["backend_s"]) == \
        (10.0, 2.0, 2.0)
    assert snap["top"]["trace"] == [["setup", 10.0]]
    assert snap["top"]["backend"] == [["step", 2.0]]
    assert snap["events"] == {"trace": 1, "lower": 1, "backend": 1}
    assert phases.snapshot(top=10)["top"]["backend"] == [["check", 10.0],
                                                         ["step", 5.0]]
    # a function met only after the cut takes no place among the top
    assert phases.snapshot(before=15.0, top=1)["top"]["backend"] == \
        [["step", 2.0]]
    assert phases.snapshot(before=30.0)["trace_s"] == pytest.approx(16.0)
    assert phases.snapshot()["backend_s"] == pytest.approx(15.0)
    # overlapping intervals are kept merged: a stretch that runs past the
    # cut is left out whole
    phases.listener(TRACE, 9.0, 16.0, fun_name="late")
    assert phases.snapshot(before=15.0)["trace_s"] == 0.0
    assert phases.snapshot(before=16.0)["trace_s"] == pytest.approx(16.0)


def test_real_nested_jit_trace_counts_the_outer_span_once():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _phase_probe_inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def _phase_probe_outer(x):
        return _phase_probe_inner(x) + _phase_probe_inner(x + 1)

    x = jnp.ones(7)
    x.block_until_ready()
    s0 = profiler.compile_phase_counters(top=10 ** 6)
    _phase_probe_outer(x).block_until_ready()
    s1 = profiler.compile_phase_counters(top=10 ** 6)
    funs = dict(s1["top"]["trace"])
    before = dict(s0["top"]["trace"])
    outer = funs["_phase_probe_outer"]
    assert funs["_phase_probe_inner"] > 0 and outer > 0
    traced = s1["trace_s"] - s0["trace_s"]
    assert 0 < traced <= outer + 1e-9
    summed = sum(s - before.get(name, 0.0) for name, s in funs.items())
    assert summed > traced       # the sum counts the nested traces twice
    assert s1["lower_s"] > s0["lower_s"] and s1["backend_s"] > s0["backend_s"]


@pytest.mark.parametrize("path", ["jit", "program_builder"])
def test_calls_to_a_built_program_leave_every_counter_unchanged(path):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.compile.builder import ProgramBuilder

    def body(x):
        return x * 3 + 1

    fn = jax.jit(body) if path == "jit" else ProgramBuilder(body, site="t")
    x = jnp.ones((5,))
    fn(x).block_until_ready()
    before = profiler.compile_phase_counters(top=10 ** 6)
    for _ in range(1000):
        y = fn(x)
    y.block_until_ready()
    assert profiler.compile_phase_counters(top=10 ** 6) == before


def test_the_package_import_is_stamped():
    import_s = profiler.compile_phase_counters()["import_s"]
    assert import_s is not None and 0 < import_s < 600


def test_compile_listeners_are_registered_once_at_import():
    from jax._src import monitoring
    assert monitoring.get_event_time_span_listeners().count(
        profiler._phases.listener) == 1
    assert monitoring.get_event_listeners().count(
        profiler._pcache_listener) == 1
