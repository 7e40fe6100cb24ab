"""Reader: the median duration in milliseconds of the host spans named
``args["span"]`` in the traced stretch (the program's own ``mx.*`` spans,
``mxnet_tpu/profiler.py::span``, on the profiler's clock). Nothing to read,
as on a program that has no such span, is ``None``."""
import statistics

from harness import trace


def read(run, args):
    if run.events is None:
        return None
    durs = [e.dur_ns for e in run.events
            if e.name == args["span"] and not trace.DEVICE_PLANE.match(e.plane)]
    return statistics.median(durs) / 1e6 if durs else None
