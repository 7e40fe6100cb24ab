"""Reader: the share of the traced stretch in which the XLA programs whose
name matches ``args["pattern"]`` ran on the device: the sum of their
executions' device time over ``run.trace_window_s``. No such program in the
trace (or no trace) is ``None``."""
from harness import trace


def read(run, args):
    if run.events is None or not run.trace_window_s:
        return None
    times = trace.module_times(run.events, args["pattern"])
    return 100.0 * sum(times) / run.trace_window_s if times else None
