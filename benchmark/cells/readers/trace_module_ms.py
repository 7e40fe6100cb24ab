"""Reader: device milliseconds of one execution of the XLA program whose name
matches ``args["pattern"]`` in the traced stretch: the median, because the
trace's edges cut the first and the last execution short (PERF.md section 6,
PR 25: a mean over them read the training step 4 % short)."""
import statistics

from harness import trace


def read(run, args):
    if run.events is None:
        return None
    times = trace.module_times(run.events, args["pattern"])
    return statistics.median(times) * 1e3 if times else None
