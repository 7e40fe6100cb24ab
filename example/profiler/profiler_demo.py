"""Profiler walkthrough (reference: example/profiler/profiler_ndarray.py
and profiler_executor.py — set_config/set_state around work, dump a
chrome trace, print per-op aggregates).

What it shows on this runtime: per-op dispatch counts and wall time for
the EAGER path (each op blocks for its device time while profiling, the
reference engine's on-thread measurement), a scoped `profiler.span` for
labeling phases in the XLA trace (`<trace>_jax_trace/`, the device's own
clock) with a row of its own in the aggregate table, and a chrome://tracing
dump.
"""
import argparse
import contextlib
import logging
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402


@contextlib.contextmanager
def phase(name):
    """Label a phase: a span in the XLA trace, a row in the table."""
    t0 = time.perf_counter()
    with profiler.span(name):
        yield
    profiler.record_op_event(name, time.perf_counter() - t0, category="phase")


def workload(n_iter=20, size=256):
    rng = np.random.RandomState(0)
    a = mx.nd.array(rng.normal(0, 1, (size, size)).astype(np.float32))
    b = mx.nd.array(rng.normal(0, 1, (size, size)).astype(np.float32))
    with phase("matmul-phase"):
        for _ in range(n_iter):
            c = mx.nd.dot(a, b)
    with phase("elemwise-phase"):
        for _ in range(n_iter):
            c = mx.nd.relu(a + b) * c.mean()
    c.wait_to_read()
    return c


def main(trace_path=None, n_iter=20):
    trace_path = trace_path or os.path.join(tempfile.gettempdir(),
                                            "mxtpu_profile.json")
    profiler.set_config(filename=trace_path, aggregate_stats=True)
    profiler.set_state("run")
    workload(n_iter)
    profiler.set_state("stop")
    table = profiler.dumps(format="table")
    print(table)
    profiler.dump()
    print("chrome trace -> %s (open in chrome://tracing)" % trace_path)
    return table, trace_path


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", type=str, default=None)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    main(args.trace, args.iters)
